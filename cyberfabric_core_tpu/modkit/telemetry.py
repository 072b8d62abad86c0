"""Tracing: spans, W3C trace-context propagation, and profiler hooks.

Reference: libs/modkit/src/telemetry/init.rs (OTel tracing init, samplers, OTLP
exporters), tower-http TraceLayer per request
(modules/system/api-gateway/src/module.rs:276-281), W3C propagation.

TPU build: host spans carry request_id/trace_id through the middleware stack and are
exported to structured logs (an OTLP exporter can be slotted in later — the exporter
interface is one method). Device-side profiling hooks into `jax.profiler` when
enabled. Includes the throttled-log helper (telemetry/throttled_log.rs).
"""

from __future__ import annotations

import contextvars
import json
import logging
import os
import random
import re
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

from .metrics import bump_counter, default_registry

logger = logging.getLogger("telemetry")

_TRACEPARENT_RE = re.compile(r"^00-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$")
#: the parent id of a traceparent that names a trace and no parent span
_NO_PARENT = "0" * 16

_current_span: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "current_span", default=None
)


@dataclass
class Span:
    name: str
    trace_id: str
    span_id: str
    parent_id: Optional[str] = None
    attributes: dict[str, Any] = field(default_factory=dict)
    start_ns: int = field(default_factory=time.monotonic_ns)
    start_unix_ns: int = field(default_factory=time.time_ns)
    status: str = "ok"
    sampled: bool = True

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    def traceparent(self) -> str:
        # the flags byte carries the sampling decision downstream: a worker
        # thread holding only this string can decide "emit nothing" without
        # consulting the tracer (W3C trace-context §3.2.3.3)
        return (f"00-{self.trace_id}-{self.span_id}-"
                f"{'01' if self.sampled else '00'}")


class SpanExporter:
    """Export finished spans; default sink is the structured log stream."""

    def export(self, span: Span, duration_ms: float) -> None:
        logger.debug(
            "span %s trace=%s dur=%.2fms status=%s %s",
            span.name, span.trace_id, duration_ms, span.status, span.attributes,
        )


class _SpanScope:
    """Class-based span context manager — the per-request hot path avoids the
    generator + contextlib machinery of ``@contextmanager`` (~20 µs/request
    in the gateway overhead profile)."""

    __slots__ = ("_tracer", "span", "_token")

    def __init__(self, tracer: "Tracer", span: Span) -> None:
        self._tracer = tracer
        self.span = span

    def __enter__(self) -> Span:
        self._token = _current_span.set(self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        span = self.span
        if exc_type is not None:
            span.status = "error"
        _current_span.reset(self._token)
        tracer = self._tracer
        if tracer.enabled and span.sampled:
            tracer.exporter.export(
                span, (time.monotonic_ns() - span.start_ns) / 1e6)
        return False


class Tracer:
    """Sampling tracer (parent-based ratio sampler parity, telemetry/config.rs)."""

    def __init__(self, *, enabled: bool = True, sample_ratio: float = 1.0,
                 exporter: Optional[SpanExporter] = None) -> None:
        self.enabled = enabled
        self.sample_ratio = sample_ratio
        self.exporter = exporter or SpanExporter()

    def span(self, name: str, *, traceparent: Optional[str] = None,
             **attributes: Any) -> _SpanScope:
        parent = _current_span.get()
        trace_id, parent_id = None, None
        flag_sampled: Optional[bool] = None
        if traceparent:
            m = _TRACEPARENT_RE.match(traceparent.strip())
            if m:
                trace_id, parent_id = m.group(1), m.group(2)
                flag_sampled = bool(int(m.group(3), 16) & 1)
        if trace_id is None and parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
        if trace_id is None:
            # os.urandom over uuid4: same 128 random bits without UUID object
            # construction (~3x faster; spans are per-request hot-path)
            trace_id = os.urandom(16).hex()
        # parent-based sampling: children inherit the parent's decision — the
        # in-context parent's bit, else the traceparent flags byte (a remote
        # or cross-thread parent); only true roots roll the dice, so an
        # unsampled trace emits nothing at all
        if parent is not None:
            sampled = parent.sampled
        elif flag_sampled is not None:
            sampled = flag_sampled
        else:
            sampled = random.random() < self.sample_ratio
        return _SpanScope(self, Span(
            name=name,
            trace_id=trace_id,
            span_id=os.urandom(8).hex(),
            parent_id=parent_id,
            attributes=dict(attributes),
            sampled=sampled,
        ))

    def emit_span(self, name: str, *, traceparent: Optional[str] = None,
                  start_unix_ns: Optional[int] = None, duration_ms: float = 0.0,
                  status: str = "ok", span_id: Optional[str] = None,
                  **attributes: Any) -> Optional[Span]:
        """Export one retrospective span without touching the contextvar.

        Built for the scheduler thread: device work is timed first, then the
        span is emitted after the fact with explicit timestamps (the same
        backdating trick as the gateway's unmatched-route epilogue). The
        sampling decision comes from the traceparent flags byte — an
        unsampled parent means this returns None before allocating anything.
        A traceparent whose parent id is all zeros names a trace and no
        parent (a root of that trace); ``span_id`` is for a caller whose
        children close before their parent (the start-up timeline), so that
        a parent's id exists before its span does.
        """
        if not self.enabled:
            return None
        trace_id = parent_id = None
        sampled = True
        if traceparent:
            m = _TRACEPARENT_RE.match(traceparent.strip())
            if m:
                trace_id, parent_id = m.group(1), m.group(2)
                sampled = bool(int(m.group(3), 16) & 1)
                if parent_id == _NO_PARENT:
                    parent_id = None
        if trace_id is None:
            trace_id = os.urandom(16).hex()
            sampled = random.random() < self.sample_ratio
        if not sampled:
            return None
        span = Span(name=name, trace_id=trace_id,
                    span_id=span_id or os.urandom(8).hex(), parent_id=parent_id,
                    attributes=dict(attributes), status=status)
        if start_unix_ns is not None:
            span.start_unix_ns = int(start_unix_ns)
        self.exporter.export(span, duration_ms)
        return span

    @staticmethod
    def current() -> Optional[Span]:
        return _current_span.get()


#: process-global tracer: the gateway installs its configured tracer here at
#: init so off-loop layers (scheduler thread, replicas pool) export child
#: spans through the SAME exporter pipeline as the HTTP spans — one OTLP
#: trace covers gateway → prefill → decode chunks. Defaults to a log-exporter
#: tracer so library use without a gateway still works.
_global_tracer = Tracer()


def set_global_tracer(tracer: Tracer) -> Tracer:
    global _global_tracer
    _global_tracer = tracer
    return tracer


def get_global_tracer() -> Tracer:
    return _global_tracer


class OtlpHttpExporter(SpanExporter):
    """OTLP/HTTP JSON span exporter (reference: telemetry/init.rs builds OTLP
    gRPC/HTTP exporters; this speaks the standard OTLP/HTTP JSON encoding to
    any collector's 4318 endpoint).

    Spans are buffered and shipped from a daemon thread — span exit never
    blocks on the network; a dead collector drops batches with a throttled
    warning (availability over telemetry)."""

    def __init__(self, endpoint: str, service_name: str = "tpu-fabric",
                 flush_interval_s: float = 2.0, max_batch: int = 256,
                 max_buffer: int = 4096) -> None:
        import queue
        import threading

        self.endpoint = endpoint.rstrip("/")
        if not self.endpoint.endswith("/v1/traces"):
            self.endpoint += "/v1/traces"
        self.service_name = service_name
        self.flush_interval_s = flush_interval_s
        self.max_batch = max_batch
        self._queue: "queue.Queue[dict]" = queue.Queue(maxsize=max_buffer)
        self._throttle = ThrottledLog(30.0)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="otlp-exporter")
        self._thread.start()

    # -------------------------------------------------------------- encoding
    @staticmethod
    def _attr(key: str, value: Any) -> dict:
        if isinstance(value, bool):
            return {"key": key, "value": {"boolValue": value}}
        if isinstance(value, int):
            return {"key": key, "value": {"intValue": str(value)}}
        if isinstance(value, float):
            return {"key": key, "value": {"doubleValue": value}}
        return {"key": key, "value": {"stringValue": str(value)}}

    def _encode(self, span: Span, duration_ms: float) -> dict:
        end_ns = span.start_unix_ns + int(duration_ms * 1e6)
        out = {
            "traceId": span.trace_id,
            "spanId": span.span_id,
            "name": span.name,
            "kind": 2,  # SERVER
            "startTimeUnixNano": str(span.start_unix_ns),
            "endTimeUnixNano": str(end_ns),
            "attributes": [self._attr(k, v) for k, v in span.attributes.items()],
            "status": {"code": 2 if span.status == "error" else 1},
        }
        if span.parent_id:
            out["parentSpanId"] = span.parent_id
        return out

    def export(self, span: Span, duration_ms: float) -> None:
        try:
            self._queue.put_nowait(self._encode(span, duration_ms))
        except Exception:  # noqa: BLE001 — full buffer: drop, never block
            if self._throttle.should_log("buffer_full"):
                logger.warning("OTLP span buffer full; dropping spans")

    # -------------------------------------------------------------- shipping
    def _drain(self) -> list[dict]:
        import queue

        batch: list[dict] = []
        while len(batch) < self.max_batch:
            try:
                batch.append(self._queue.get_nowait())
            except queue.Empty:
                break
        return batch

    def _post(self, batch: list[dict], timeout_s: float = 10.0) -> None:
        import json as _json
        import urllib.request

        payload = _json.dumps({"resourceSpans": [{
            "resource": {"attributes": [
                self._attr("service.name", self.service_name)]},
            "scopeSpans": [{"scope": {"name": "cyberfabric_core_tpu"},
                            "spans": batch}],
        }]}).encode()
        req = urllib.request.Request(
            self.endpoint, data=payload,
            headers={"Content-Type": "application/json"}, method="POST")
        urllib.request.urlopen(req, timeout=max(0.1, timeout_s))  # noqa: S310

    def _run(self) -> None:
        while not self._stop.is_set():
            self._stop.wait(self.flush_interval_s)
            batch = self._drain()
            if not batch:
                continue
            try:
                self._post(batch)
            except Exception as e:  # noqa: BLE001 — collector down
                if self._throttle.should_log("post_failed"):
                    logger.warning("OTLP export failed (%d spans dropped): %s",
                                   len(batch), e)

    def flush(self, timeout_s: float = 5.0) -> None:
        """Synchronously ship whatever is buffered (tests/shutdown). The
        network timeout is bounded by the remaining flush budget so flush can
        never overrun its deadline on a blackholed collector."""
        deadline = time.monotonic() + timeout_s
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return
            batch = self._drain()
            if not batch:
                return
            try:
                self._post(batch, timeout_s=remaining)
            except Exception:  # noqa: BLE001
                return

    def shutdown(self) -> None:
        self._stop.set()
        self.flush(timeout_s=2.0)


def tracer_from_config(cfg: dict) -> Tracer:
    """Build the tracer from the app-level ``tracing`` config section:
    {enabled, sample_ratio, otlp_endpoint?, service_name?}. Without an
    otlp_endpoint, spans export to the structured log stream."""
    exporter: Optional[SpanExporter] = None
    endpoint = cfg.get("otlp_endpoint")
    if endpoint:
        exporter = OtlpHttpExporter(
            endpoint, service_name=cfg.get("service_name", "tpu-fabric"))
    return Tracer(enabled=bool(cfg.get("enabled", True)),
                  sample_ratio=float(cfg.get("sample_ratio", 1.0)),
                  exporter=exporter)


def traceparent_ids(traceparent: Optional[str]) -> tuple[Optional[str], bool]:
    """(trace_id, sampled) from a W3C traceparent; (None, False) if invalid.
    Parsed ONCE at request submission so the decode hot loop's span guard is
    a single bool attribute check, never a regex."""
    if not traceparent:
        return None, False
    m = _TRACEPARENT_RE.match(traceparent.strip())
    if not m:
        return None, False
    return m.group(1), bool(int(m.group(3), 16) & 1)


#: (request_id, trace_id) for log correlation. A contextvar covers BOTH
#: worlds: asyncio handlers inherit it through task context, and the
#: scheduler/worker threads each see their own default — set_log_context
#: scopes it around per-request operations on those threads.
_log_ctx: contextvars.ContextVar[tuple[str, str]] = contextvars.ContextVar(
    "log_request_ctx", default=("-", "-"))


def set_log_context(request_id: Optional[str],
                    trace_id: Optional[str]) -> contextvars.Token:
    """Bind request/trace ids for log records emitted by this context; returns
    the token for ``reset_log_context``. Never raises."""
    return _log_ctx.set((request_id or "-", trace_id or "-"))


def reset_log_context(token: contextvars.Token) -> None:
    try:
        _log_ctx.reset(token)
    except Exception:  # noqa: BLE001 — cross-context reset: leave as-is
        pass


class TraceContextFilter(logging.Filter):
    """Injects ``%(request_id)s`` / ``%(trace_id)s`` into every log record so
    scheduler and worker lines become greppable by trace. Installed on the
    logging-host handlers (modkit/logging_host.py); always passes the record
    through — it annotates, never filters."""

    def filter(self, record: logging.LogRecord) -> bool:
        rid, tid = _log_ctx.get()
        record.request_id = rid
        record.trace_id = tid
        return True


class ThrottledLog:
    """Log at most once per ``interval`` seconds per key (throttled_log.rs)."""

    def __init__(self, interval: float = 5.0) -> None:
        self.interval = interval
        self._last: dict[str, float] = {}

    def should_log(self, key: str) -> bool:
        now = time.monotonic()
        if now - self._last.get(key, -1e9) >= self.interval:
            self._last[key] = now
            return True
        return False


def xla_cost_summary(compiled) -> dict[str, float]:
    """Normalize a compiled computation's XLA cost analysis to the few numbers
    perf work needs (SURVEY §5: jax.profiler traces + XLA cost-analysis dumps
    are the device-side counterpart of OTel host spans).

    Returns {} when the backend exposes no cost model (e.g. interpret mode)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — backend without a cost model
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out: dict[str, float] = {}
    for key in ("flops", "bytes accessed", "transcendentals",
                "utilization operand 0 {}", "optimal_seconds"):
        if key in ca:
            out[key.replace(" ", "_")] = float(ca[key])
    # keep any hbm-ish byte counters the backend reports
    for k, v in ca.items():
        if "bytes accessed" in k and k != "bytes accessed":
            out[k.replace(" ", "_")] = float(v)
    return out


# ------------------------------------------------------------------ start-up
#
# One timeline of a process's start, kept by the program: STAGES (boot, each
# HostRuntime phase, an engine's build, the wait of the first user after a
# restart) recorded where the work happens, and the PROGRAM events JAX itself
# times (every jit's trace, lowering and backend compile or cache load), fed
# by ``jax.monitoring`` listeners and not by a log. Like the scheduler's
# ``_PhaseClock``, ``StartupTimeline`` is the ONE place start-up time is
# taken: a stage is opened here, never timed beside it.

#: the stage open in this context. A ContextVar, as ``_log_ctx`` is: a thread
#: sees its own, and so does an asyncio task, so a stage held across an
#: ``await`` is not the parent of what another task opens meanwhile
_open_stage: contextvars.ContextVar[Optional["Stage"]] = contextvars.ContextVar(
    "startup_stage", default=None)

#: JAX's compile events (jax 0.9.0 ``dispatch.LogElapsedTimeContextManager``:
#: a scalar at the start, a duration and a time span at the end, ``fun_name=``
#: on each, times from ``time.time()``) -> (event kind, its /metrics series)
JAX_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("trace", "jax_trace_seconds_total"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("lower", "jax_lower_seconds_total"),
    "/jax/core/compile/backend_compile_duration":
        ("compile", "jax_backend_compile_seconds_total"),
}
_HITS, _MISSES = "jax_compile_cache_hits_total", "jax_compile_cache_misses_total"
#: the persistent cache's two events -> their series. JAX records a miss
#: where a compiled program is WRITTEN to the cache (one that compiled in
#: under ``jax_persistent_cache_min_compile_time_secs`` never is: neither)
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": _HITS,
                 "/jax/compilation_cache/cache_misses": _MISSES}
#: /metrics series of the compile ledger, beside ``JAX_COMPILE_EVENTS``'
LEDGER_COUNTERS = {
    "jax_trace_seconds_total":
        "Seconds tracing jitted functions to jaxprs (outermost traces)",
    "jax_lower_seconds_total": "Seconds lowering jaxprs to MLIR modules",
    "jax_backend_compile_seconds_total":
        "Seconds in the backend compile of a program, or in its load from "
        "the persistent cache",
    "jax_backend_compiles_total":
        "Programs brought up: backend compiles and persistent-cache loads",
    "jax_compile_cache_hits_total":
        "Programs loaded from the persistent compilation cache",
    "jax_compile_cache_misses_total":
        "Programs compiled and written to the persistent compilation cache "
        "(the next start loads them)",
}
_PROGRAM_NAME_RE = re.compile(r"^(?:jit|pmap)\((.*)\)$")
#: the stage of what the first user after a restart waits for, a model
FIRST_TOKEN = "first_token"


def _process_start_unix_ns() -> int:
    """When the OS started this process: field 22 of ``/proc/self/stat``
    (clock ticks after the machine's boot) against ``/proc/uptime``, so that
    the interpreter's start and the imports are time ON the timeline and not
    a gap before it. Where /proc does not say, now (this module's import)."""
    now_ns = time.time_ns()
    try:
        with open("/proc/self/stat") as f:
            # the command's name (field 2) may hold spaces and brackets
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            age = float(f.read().split()[0]) - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return now_ns
    return now_ns - int(age * 1e9) if age >= 0 else now_ns


@dataclass(eq=False)
class Stage:
    name: str
    parent: Optional["Stage"]
    start_unix_ns: int
    attrs: dict[str, Any]
    span_id: str = field(default_factory=lambda: os.urandom(8).hex())
    end_unix_ns: Optional[int] = None
    #: sums into its attributes (``trace_s``, ``lower_s``, ``compile_s``) the
    #: program events that end under it: on a thread whose open stage it is,
    #: or an ancestor of
    sums_programs: bool = False

    def open_ancestor(self) -> Optional["Stage"]:
        """This stage if it is open, else the nearest ancestor that is: a
        context copied while a stage was open (a server task started in
        ``boot.start``) still names it long after it closed."""
        s: Optional[Stage] = self
        while s is not None and s.end_unix_ns is not None:
            s = s.parent
        return s


class _StageScope:
    """``with timeline.stage(name):`` — the stage is the open one of this
    context while the block runs, and a ``startup.<name>`` annotation on the
    host plane of a profile taken across it."""

    __slots__ = ("_timeline", "stage", "_token", "_annotation")

    def __init__(self, timeline: "StartupTimeline", stage: Stage) -> None:
        self._timeline, self.stage = timeline, stage

    def __enter__(self) -> Stage:
        self._token = _open_stage.set(self.stage)
        self._annotation = None
        # never imported for a stage's sake (and absent while it imports)
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is not None:
            self._annotation = profiler.TraceAnnotation(
                "startup." + self.stage.name)
            self._annotation.__enter__()
        return self.stage

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        _open_stage.reset(self._token)
        self._timeline.end(self.stage,
                           status="error" if exc_type is not None else "ok")
        return False


_INHERIT: Any = object()


class StartupTimeline:
    """Stages and program events of this process, newest ``MAX_STAGES`` and
    ``MAX_EVENTS`` (the sums by program are kept whole). Always on: a process
    opens a few dozen stages in its life, none on a request's or a round's
    path, and a program event exists only where a ``jit`` missed its cache."""

    MAX_STAGES = 512
    MAX_EVENTS = 512

    def __init__(self) -> None:
        from collections import deque

        self.process_start_unix_ns = _process_start_unix_ns()
        self.ready_unix_ns: Optional[int] = None
        #: every stage and program event of the process exports under it,
        #: so a collector shows a restart as one trace
        self.trace_id = os.urandom(16).hex()
        self._lock = threading.Lock()
        self._stages: "deque[Stage]" = deque(maxlen=self.MAX_STAGES)
        self._events: "deque[dict]" = deque(maxlen=self.MAX_EVENTS)
        self._programs: dict[str, dict] = {}
        self._seq = 0
        self._boot: Optional[Stage] = None
        #: the listeners registered with ``jax.monitoring``: (unregister, fn)
        self._listeners: list[tuple[Any, Any]] = []
        #: per thread: the compile events open on it, and what it compiled
        #: since the scheduler's clock last asked
        self._tls = threading.local()
        #: the listeners' own cost: calls and seconds inside them
        self.listener_calls = 0
        self.listener_seconds = 0.0
        self.listener_errors = 0

    # ------------------------------------------------------------- stages
    def begin(self, name: str, *, parent: Any = _INHERIT,
              start_unix_ns: Optional[int] = None, sums_programs: bool = False,
              **attrs: Any) -> Stage:
        """Open a stage that ``end`` closes, for one that outlives a block
        or changes threads (``boot``, ``first_token``). Its parent is the
        stage open in this context unless one is given (None: none)."""
        if parent is _INHERIT:
            parent = _open_stage.get()
        if parent is not None:
            parent = parent.open_ancestor()
        stage = Stage(name, parent, start_unix_ns or time.time_ns(), attrs,
                      sums_programs=sums_programs)
        with self._lock:
            self._stages.append(stage)
        return stage

    def stage(self, name: str, *, parent: Any = _INHERIT,
              start_unix_ns: Optional[int] = None, **attrs: Any) -> _StageScope:
        return _StageScope(self, self.begin(
            name, parent=parent, start_unix_ns=start_unix_ns, **attrs))

    def end(self, stage: Stage, *, status: str = "ok", **attrs: Any) -> None:
        if stage.end_unix_ns is not None:
            return
        stage.attrs.update(attrs)
        stage.end_unix_ns = max(time.time_ns(), stage.start_unix_ns)
        self._export(stage.name, stage.start_unix_ns, stage.end_unix_ns,
                     stage.parent, stage.span_id, status, stage.attrs)
        if stage.parent is None:
            # ONE line a top-level stage, its children inside it: what a
            # reader has of a server that has stopped
            logger.info("startup: %s", json.dumps(
                {"kind": "stage",
                 **self._node(stage, self._children(self.stages()))},
                default=str))

    @staticmethod
    def current() -> Optional[Stage]:
        """The stage open in this context, for a thread about to be started
        to ``adopt``: a thread starts with none of its own."""
        return _open_stage.get()

    @staticmethod
    def adopt(stage: Optional[Stage]) -> None:
        """The calling thread works for ``stage`` (the scheduler thread for
        its model's ``engine.build``, and so for that model's ``first_token``
        while it is open): what the thread opens and compiles is under it."""
        _open_stage.set(stage)

    def begin_boot(self) -> Stage:
        """The root: from the process's start as the OS has it, open in this
        context until ``ready``."""
        self._boot = self.begin("boot", parent=None,
                                start_unix_ns=self.process_start_unix_ns,
                                pid=os.getpid())
        _open_stage.set(self._boot)
        return self._boot

    def ready(self) -> None:
        """``/healthz`` can answer: ``boot`` closes (the first time)."""
        boot, self._boot = self._boot, None
        if boot is not None:
            self.end(boot)
            self.ready_unix_ns = boot.end_unix_ns
        elif self.ready_unix_ns is None:
            self.ready_unix_ns = time.time_ns()

    def _export(self, name: str, start_ns: int, end_ns: int,
                parent: Optional[Stage], span_id: Optional[str], status: str,
                attrs: dict) -> None:
        parent_id = parent.span_id if parent is not None else _NO_PARENT
        try:
            get_global_tracer().emit_span(
                name, traceparent=f"00-{self.trace_id}-{parent_id}-01",
                start_unix_ns=start_ns, duration_ms=(end_ns - start_ns) / 1e6,
                status=status, span_id=span_id, **attrs)
        except Exception:  # noqa: BLE001 — an exporter must not fail a start
            pass

    def stages(self) -> list[Stage]:
        with self._lock:
            return list(self._stages)

    @staticmethod
    def _children(stages: list[Stage]) -> dict[int, list[Stage]]:
        """Stages by their parent (its ``id``), in order of their starts."""
        by_parent: dict[int, list[Stage]] = {}
        for s in sorted(stages, key=lambda s: s.start_unix_ns):
            by_parent.setdefault(id(s.parent), []).append(s)
        return by_parent

    @staticmethod
    def _node(stage: Stage, by_parent: dict[int, list[Stage]]) -> dict:
        """A stage with its children inside it and its self time: its
        duration less what its children cover of it (an open stage: so far)."""
        end = stage.end_unix_ns or time.time_ns()
        children = by_parent.get(id(stage), ())
        covered, at = 0, stage.start_unix_ns
        for c in children:
            lo = max(at, c.start_unix_ns)
            hi = min(end, c.end_unix_ns or end)
            if hi > lo:
                covered, at = covered + hi - lo, hi
        return {"name": stage.name,
                "start_unix_ns": stage.start_unix_ns,
                "end_unix_ns": stage.end_unix_ns,
                "duration_s": (end - stage.start_unix_ns) / 1e9,
                "self_s": (end - stage.start_unix_ns - covered) / 1e9,
                "attrs": stage.attrs,
                "children": [StartupTimeline._node(c, by_parent)
                             for c in children]}

    # ----------------------------------------------------- program events
    def install_jax_listeners(self) -> None:
        """Register the ledger's three listeners with ``jax.monitoring``, once
        a process. They fire only where a ``jit`` misses its C++ cache: a
        warmed server pays nothing for them."""
        if self._listeners:
            return
        from jax import monitoring as m

        for series, text in LEDGER_COUNTERS.items():
            default_registry.counter(series, text).inc(0.0)
        for register, unregister, fn in (
                (m.register_scalar_listener, m.unregister_scalar_listener,
                 self._on_start),
                (m.register_event_time_span_listener,
                 m.unregister_event_time_span_listener, self._on_span),
                (m.register_event_listener, m.unregister_event_listener,
                 self._on_event)):
            listener = self._guard(fn)
            register(listener)
            self._listeners.append((unregister, listener))

    def remove_jax_listeners(self) -> None:
        """Take the ledger's listeners off ``jax.monitoring`` again."""
        while self._listeners:
            unregister, listener = self._listeners.pop()
            unregister(listener)

    def _guard(self, fn):
        """JAX calls a listener bare, inside the compile: one that raises
        would fail the compile. Also the listeners' own clock."""
        def listener(event: str, *args: Any, **kwargs: Any) -> None:
            t0 = time.perf_counter()
            try:
                fn(event, *args, **kwargs)
            except Exception:  # noqa: BLE001
                self.listener_errors += 1
            self.listener_calls += 1
            self.listener_seconds += time.perf_counter() - t0
        return listener

    def _frames(self) -> list:
        frames = getattr(self._tls, "frames", None)
        if frames is None:
            frames = self._tls.frames = []
        return frames

    def _on_start(self, event: str, value: float, **kw: Any) -> None:
        if event in JAX_COMPILE_EVENTS:
            # [event, fun_name, seconds of recorded events inside it, the
            #  series of what the persistent cache did (loaded it, was
            #  written it) or None]
            self._frames().append([event, kw.get("fun_name"), 0.0, None])

    def _on_event(self, event: str, **kw: Any) -> None:
        if event in _CACHE_EVENTS:
            # pxla wraps compile_or_get_cached in the backend-compile span:
            # the hit (or the write after a miss) belongs to the program
            # whose span is open on this thread
            frames = self._frames()
            if frames:
                frames[-1][3] = _CACHE_EVENTS[event]

    def _on_span(self, event: str, start: float, end: float, **kw: Any) -> None:
        if event not in JAX_COMPILE_EVENTS:
            return
        kind, series = JAX_COMPILE_EVENTS[event]
        fun_name = kw.get("fun_name")
        frames, inside, cache = self._frames(), 0.0, None
        for i in range(len(frames) - 1, -1, -1):
            if frames[i][0] == event and frames[i][1] == fun_name:
                inside, cache = frames[i][2], frames[i][3]
                del frames[i:]
                break
        hit = cache == _HITS
        outer = frames[-1] if frames else None
        if outer is not None and kind != "compile":
            # a trace or a lowering inside another event (a jnp function
            # inside the program's trace, a kernel's body traced while the
            # program lowers): the outer event's time covers it, and it is
            # the outer's program that caused it
            outer[2] += inside
            return
        if outer is not None:
            outer[2] += end - start
        # a compile inside another event (an op run eagerly while tracing)
        # is a program of its own with a record of its own: the seconds are
        # each event's own, so the series add up to the threads' time and
        # count nothing twice
        seconds = max(0.0, end - start - inside)
        name = str(fun_name)
        m = _PROGRAM_NAME_RE.match(name)
        program = m.group(1) if m else name
        stage = _open_stage.get()
        stage = stage.open_ancestor() if stage is not None else None
        record = {"kind": "program", "program": program, "event": kind,
                  "start_unix_ns": int(start * 1e9),
                  "end_unix_ns": int(end * 1e9), "seconds": seconds,
                  "thread": threading.current_thread().name}
        if kind == "compile":
            record["cache_hit"] = hit
        # the scheduler thread adopted its model's stages: ``first_token``
        # while that model's first user waits, none after — and a compile
        # while ``serving`` is a recompile on the request path
        record["stage"] = stage.name if stage is not None else "serving"
        waiting = stage
        while waiting is not None and not waiting.sums_programs:
            waiting = waiting.parent
        with self._lock:
            if waiting is not None:
                key = kind + "_s"
                waiting.attrs[key] = waiting.attrs.get(key, 0.0) + seconds
            self._seq += 1
            record["seq"] = self._seq
            self._events.append(record)
            p = self._programs.setdefault(program, {
                "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                "compiles": 0, "cache_hits": 0, "cache_misses": 0,
                "first_at_unix": start, "last_at_unix": end})
            p[kind + "_s"] += seconds
            p["last_at_unix"] = end
            if kind == "compile":
                p["compiles"] += 1
                p["cache_hits"] += int(hit)
                p["cache_misses"] += int(cache == _MISSES)
        for labels in ({}, {"program": program}):
            bump_counter(series, n=seconds, **labels)
            if kind == "compile":
                bump_counter("jax_backend_compiles_total", **labels)
                if cache is not None:
                    bump_counter(cache, **labels)
        compiled = getattr(self._tls, "compiled", None)
        if compiled is None:
            compiled = self._tls.compiled = {}
        compiled[program] = compiled.get(program, 0.0) + seconds
        logger.info("startup: %s", json.dumps(record))
        self._export(f"jax.{kind}", record["start_unix_ns"],
                     record["end_unix_ns"], stage, None, "ok",
                     {"program": program, "cache_hit": hit})

    def take_compiled(self) -> Optional[dict[str, float]]:
        """What THIS thread traced, lowered and compiled since it last
        asked: program -> seconds, or None (the usual answer, one
        thread-local read). The scheduler's clock asks once a pass."""
        compiled = getattr(self._tls, "compiled", None)
        if not compiled:
            return None
        self._tls.compiled = None
        return compiled

    def events(self, after_seq: int = 0) -> list[dict]:
        with self._lock:
            return [e for e in self._events if e["seq"] > after_seq]

    @property
    def seq(self) -> int:
        return self._seq

    # ------------------------------------------------------- the one view
    def snapshot(self) -> dict:
        """``GET /v1/monitoring/startup``."""
        stages = self.stages()
        with self._lock:
            events = list(self._events)
            programs = {k: dict(v) for k, v in sorted(self._programs.items())}
        by_parent = self._children(stages)
        tree = [self._node(s, by_parent) for s in by_parent.get(id(None), ())]
        boot = next((n for n in tree if n["name"] == "boot"), None)
        first_token = {str(s.attrs.get("model")): self._node(s, by_parent)
                       for s in stages if s.name == FIRST_TOKEN}
        return {
            "process_start_unix": self.process_start_unix_ns / 1e9,
            "ready_unix": (self.ready_unix_ns / 1e9
                           if self.ready_unix_ns is not None else None),
            "trace_id": self.trace_id,
            "stages": tree,
            "unnamed_s": boot["self_s"] if boot is not None else None,
            "first_token": first_token,
            "programs": programs,
            "events": events[-64:],
            "listeners": {"installed": bool(self._listeners),
                          "calls": self.listener_calls,
                          "seconds": self.listener_seconds,
                          "errors": self.listener_errors},
        }


#: the process's timeline (``server.py`` opens ``boot`` on it and installs
#: the listeners; a library user gets stages without either)
startup = StartupTimeline()


def jax_devices() -> list:
    """``jax.devices()``. The call that brings the backend up (the first in a
    process; on a TPU, where the runtime starts) is the stage
    ``boot.device_init``."""
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        return jax.devices()
    with startup.stage("boot.device_init"):
        return jax.devices()
