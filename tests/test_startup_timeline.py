"""The start-up timeline (modkit/telemetry.py: StartupTimeline).

STAGES tile: siblings do not overlap and lie inside their parent, self time
plus the children is the duration, and an engine's build is its five
children. PROGRAM events come from JAX's own monitoring events: a ``jit``
that misses its cache is one trace, one lowering and one compile record under
its name, a call that hits is none; a load from the persistent cache is a
``cache_hit``; the round whose pass compiled says so; and the counters'
unlabelled series are their labelled ones' sums. ``GET
/v1/monitoring/startup`` is the one view of both."""

import asyncio
import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import boot_stack, stop_stack
from cyberfabric_core_tpu.modkit import telemetry
from cyberfabric_core_tpu.modkit.metrics import default_registry
from cyberfabric_core_tpu.modkit.telemetry import (LEDGER_COUNTERS,
                                                   StartupTimeline, startup)
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

ENGINE_STAGES = ("engine.config", "engine.weights", "engine.pool",
                 "engine.programs", "engine.thread")


@pytest.fixture(scope="module", autouse=True)
def ledger():
    """The process's ledger listens while this module runs, and not for the
    tests collected after it."""
    startup.install_jax_listeners()
    yield
    startup.remove_jax_listeners()


def _walk(node):
    yield node
    for c in node["children"]:
        yield from _walk(c)


# ------------------------------------------------------------------ stages
@pytest.fixture(scope="module")
def tree():
    """A root with three children in a row, the middle one with three of its
    own, one of them opened on another thread under an explicit parent."""
    tl = StartupTimeline()

    def elsewhere(parent):
        assert telemetry._open_stage.get() is None      # a thread's own
        with tl.stage("b.2", parent=parent):
            time.sleep(0.005)

    with tl.stage("root", who="test"):
        with tl.stage("a"):
            time.sleep(0.01)
        with tl.stage("b") as b:
            with tl.stage("b.1"):
                time.sleep(0.01)
            t = threading.Thread(target=elsewhere, args=(b,))
            t.start()
            t.join()
            with tl.stage("b.3"):
                time.sleep(0.005)
            time.sleep(0.005)       # b's own time
        with tl.stage("c"):
            time.sleep(0.005)
        time.sleep(0.005)           # the root's own time
    assert all(s.end_unix_ns is not None for s in tl.stages())
    (node,) = tl.snapshot()["stages"]
    assert [n["name"] for n in _walk(node)] == [
        "root", "a", "b", "b.1", "b.2", "b.3", "c"]
    return node


def _siblings_do_not_overlap(node):
    for n in _walk(node):
        kids = n["children"]
        for before, after in zip(kids, kids[1:]):
            assert before["end_unix_ns"] <= after["start_unix_ns"], (
                before["name"], after["name"])


def _children_lie_inside_their_parent(node):
    for n in _walk(node):
        for c in n["children"]:
            assert n["start_unix_ns"] <= c["start_unix_ns"]
            assert c["end_unix_ns"] <= n["end_unix_ns"], (n["name"], c["name"])


def _self_plus_children_is_duration(node):
    for n in _walk(node):
        covered = sum(c["duration_s"] for c in n["children"])
        assert n["self_s"] + covered == pytest.approx(n["duration_s"],
                                                      abs=1e-6), n["name"]
        assert n["self_s"] >= 0.0


def _own_time_is_self_time(node):
    by_name = {n["name"]: n for n in _walk(node)}
    assert by_name["root"]["self_s"] >= 0.004
    assert by_name["b"]["self_s"] >= 0.004
    assert by_name["a"]["self_s"] == by_name["a"]["duration_s"] >= 0.009
    assert by_name["root"]["attrs"] == {"who": "test"}


@pytest.mark.parametrize("check", [
    _siblings_do_not_overlap, _children_lie_inside_their_parent,
    _self_plus_children_is_duration, _own_time_is_self_time],
    ids=lambda f: f.__name__.strip("_"))
def test_stages_tile(tree, check):
    check(tree)


def test_a_closed_stage_is_nobodys_parent():
    """A context copied while a stage was open (a server task started in
    ``boot.start``) names it long after it closed: what opens there is the
    child of the nearest ancestor still open, else of nothing."""
    tl = StartupTimeline()
    with tl.stage("outer") as outer:
        with tl.stage("inner") as inner:
            ctx = __import__("contextvars").copy_context()
        late = ctx.run(tl.begin, "late")
        assert late.parent is outer
    orphan = ctx.run(tl.begin, "orphan")
    assert orphan.parent is None and inner.end_unix_ns is not None
    tl.end(late)
    tl.end(late)                        # closing twice closes once
    assert late.end_unix_ns is not None


def test_a_stage_that_raises_closes_with_its_status(monkeypatch):
    seen = []

    class Collect(telemetry.SpanExporter):
        def export(self, span, duration_ms):
            seen.append(span)

    monkeypatch.setattr(telemetry, "_global_tracer",
                        telemetry.Tracer(exporter=Collect()))
    tl = StartupTimeline()
    with pytest.raises(ValueError):
        with tl.stage("parent"):
            with tl.stage("breaks"):
                raise ValueError("x")
    assert [s.end_unix_ns is not None for s in tl.stages()] == [True, True]
    # one trace for the process's start; the child names its parent's span
    by_name = {s.name: s for s in seen}
    assert by_name["breaks"].status == by_name["parent"].status == "error"
    assert {s.trace_id for s in seen} == {tl.trace_id}
    assert by_name["breaks"].parent_id == by_name["parent"].span_id
    assert by_name["parent"].parent_id is None


def test_boot_runs_from_the_process_start_to_ready():
    tl = StartupTimeline()
    assert 0 < time.time_ns() - tl.process_start_unix_ns < 3600e9
    boot = tl.begin_boot()
    try:
        with tl.stage("boot.imports",
                      start_unix_ns=tl.process_start_unix_ns) as imports:
            pass
        assert imports.parent is boot
        tl.ready()
    finally:
        telemetry._open_stage.set(None)
    snap = tl.snapshot()
    assert snap["ready_unix"] == boot.end_unix_ns / 1e9
    assert snap["process_start_unix"] == boot.start_unix_ns / 1e9
    assert snap["unnamed_s"] == pytest.approx(
        snap["stages"][0]["self_s"]) and snap["unnamed_s"] >= 0


# -------------------------------------------------------- an engine's build
def _tiny_model(name):
    from cyberfabric_core_tpu.modules.sdk import ModelInfo

    return ModelInfo(
        canonical_id=f"local::{name}", provider_slug="local",
        provider_model_id=name,
        engine_options={"model_config": "tiny-llama", "max_seq_len": 128,
                        "max_batch": 2, "decode_chunk": 4,
                        "quantization": "int8"})


@pytest.fixture(scope="module")
def cold_start(retrace_module):
    """One request to a worker that has not built its engine: the timeline's
    ``first_token`` stage of the model, and the worker's round records."""
    from cyberfabric_core_tpu.modules.llm_gateway.worker import LocalTpuWorker

    async def go():
        worker = LocalTpuWorker({})
        params = {"max_tokens": 6, "_request_id": "startup-cold-1"}
        chunks = [c async for c in worker.completion_stream(
            _tiny_model("startup-tiny"), "hello start-up", params)]
        again = [c async for c in worker.completion_stream(
            _tiny_model("startup-tiny"), "hello start-up",
            {"max_tokens": 6})]
        sched = next(iter(worker._entries.values())).scheduler
        records = list(sched.round_timings)
        sched.shutdown()
        return chunks, again, records

    chunks, again, records = asyncio.run(go())
    assert chunks[-1].finish_reason and again[-1].finish_reason
    node = startup.snapshot()["first_token"]["local::startup-tiny"]
    return node, records


@pytest.fixture(scope="module")
def retrace_module():
    from cyberfabric_core_tpu.runtime.programs import step_programs

    step_programs.cache_clear()
    yield
    step_programs.cache_clear()


def test_engine_build_is_its_five_children(cold_start):
    node, _ = cold_start
    (build,) = [c for c in node["children"] if c["name"] == "engine.build"]
    names = [c["name"] for c in build["children"]]
    assert sorted(set(names)) == sorted(ENGINE_STAGES), names
    covered = sum(c["duration_s"] for c in build["children"])
    assert covered == pytest.approx(build["duration_s"], rel=0.02)
    assert build["attrs"] == {"model": "local::startup-tiny",
                              "request_id": "startup-cold-1"}
    by_name = {c["name"]: c for c in build["children"]}
    weights = by_name["engine.weights"]["attrs"]
    assert weights["source"] == "synthetic" and weights["bytes"] > 0
    assert weights["quantization"] == "int8"
    pool = by_name["engine.pool"]["attrs"]
    assert pool["pages"] > 0 and pool["bytes"] > 0


def test_first_token_is_the_build_the_programs_and_the_rest(cold_start):
    node, _ = cold_start
    assert node["end_unix_ns"] is not None      # closed at the first chunk
    attrs = node["attrs"]
    assert attrs["request_id"] == "startup-cold-1"
    # the serving programs were traced, lowered and compiled while it waited
    assert attrs["trace_s"] > 0 and attrs["lower_s"] > 0
    assert attrs["compile_s"] > 0
    (build,) = node["children"]
    # ... some inside the build (the weight makers), the rest behind it
    named = attrs["trace_s"] + attrs["lower_s"] + attrs["compile_s"]
    assert build["duration_s"] < node["duration_s"]
    assert named <= node["duration_s"]
    # the next request's stages are not this one's: the model has one
    assert [s.name for s in startup.stages()
            if s.attrs.get("model") == "local::startup-tiny"
            ] == ["first_token", "engine.build"]


def test_the_round_that_compiled_says_so(cold_start):
    _, records = cold_start
    first = records[0]
    assert first["compile_ms"] > 0
    assert "mixed_step" in first["compiled"]
    assert first["compile_ms"] <= first["pass_ms"]
    # a later round of shapes already seen carries neither
    quiet = [r for r in records if "compile_ms" not in r]
    assert quiet and all("compiled" not in r for r in quiet)
    assert "compile_ms" not in records[-1]


def test_a_pass_by_hand_reads_what_its_thread_compiled(retrace):
    """The same through ``_loop_pass`` by hand: the compile's seconds ride
    the thread that compiled, and ``take`` zeroes them."""
    eng = ContinuousBatchingEngine(EngineConfig(
        model="tiny-llama", max_seq_len=128, max_batch=2,
        prefix_cache_pages=40, prefix_page_size=16, decode_chunk=4), seed=0)
    eng.start = lambda: None
    startup.take_compiled()             # the build's own compiles
    done = []
    for n in (20, 20):
        eng.submit(np.arange(3, 3 + n).tolist(), SamplingParams(max_tokens=10),
                   lambda ev: done.append(1) if ev.finished else None)
    for _ in range(200):
        eng._loop_pass()
        if len(done) == 2:
            break
    eng._flush_held_emit()
    records = list(eng.round_timings)
    eng.shutdown()
    compiled = [r for r in records if "compile_ms" in r]
    assert compiled and compiled[0] is records[0]
    names = {n for r in compiled for n in r["compiled"]}
    assert {"mixed_step", "paged_decode_chunk"} <= names
    assert "compile_ms" not in records[-1]
    assert startup.take_compiled() is None


# ------------------------------------------------------- the compile ledger
def _records_of(name, after):
    return [(e["event"], e.get("cache_hit")) for e in startup.events(after)
            if e["program"] == name]


def test_a_fresh_jit_is_one_trace_one_lower_one_compile():
    @jax.jit
    def ledger_fresh(x):
        return jnp.tanh(x) * 2 + 1      # jnp functions trace inside it

    mark = startup.seq
    ledger_fresh(jnp.ones(4)).block_until_ready()
    assert _records_of("ledger_fresh", mark) == [
        ("trace", None), ("lower", None), ("compile", False)]
    (event,) = [e for e in startup.events(mark)
                if e["program"] == "ledger_fresh" and e["event"] == "compile"]
    assert event["end_unix_ns"] > event["start_unix_ns"]
    assert event["seconds"] > 0 and event["stage"] == "serving"
    assert event["thread"] == threading.current_thread().name


def test_a_call_that_hits_the_cache_is_no_record():
    @jax.jit
    def ledger_twice(x):
        return x + 1

    ledger_twice(jnp.ones(4)).block_until_ready()
    mark = startup.seq
    ledger_twice(jnp.ones(4)).block_until_ready()
    assert _records_of("ledger_twice", mark) == []


def test_a_new_shape_is_one_more_of_each():
    @jax.jit
    def ledger_shapes(x):
        return x * 3

    ledger_shapes(jnp.ones(4)).block_until_ready()
    mark = startup.seq
    ledger_shapes(jnp.ones(8)).block_until_ready()
    assert sorted(e for e, _ in _records_of("ledger_shapes", mark)) == [
        "compile", "lower", "trace"]


def test_an_event_names_the_stage_open_on_its_thread():
    @jax.jit
    def ledger_staged(x):
        return x - 1

    mark = startup.seq
    with startup.stage("engine.weights", source="test"):
        ledger_staged(jnp.ones(3)).block_until_ready()
    stages = {e["stage"] for e in startup.events(mark)
              if e["program"] == "ledger_staged"}
    assert stages == {"engine.weights"}


@pytest.fixture()
def persistent_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_enable_compilation_cache")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    yield tmp_path
    for k, v in keep.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_a_load_from_the_persistent_cache_is_a_cache_hit(persistent_cache):
    def make():
        @jax.jit
        def ledger_cached(x):
            return jnp.sin(x) @ x.T

        return ledger_cached

    hits = default_registry.counter("jax_compile_cache_hits_total")
    misses = default_registry.counter("jax_compile_cache_misses_total")
    compiles = default_registry.counter("jax_backend_compiles_total")

    def read(counter):
        return dict((tuple(sorted(k.items())), v)
                    for k, v in counter.samples())

    x = jnp.ones((4, 4))
    mark, written = startup.seq, read(misses)
    make()(x).block_until_ready()
    assert ("compile", False) in _records_of("ledger_cached", mark)
    assert list(persistent_cache.iterdir())
    label = (("program", "ledger_cached"),)
    # compiled and WRITTEN to the cache: JAX's miss, so that hits over
    # hits + misses is 0 on a first run and 1 on the next
    assert read(misses)[label] == written.get(label, 0) + 1
    written = read(misses)
    before, n_before = read(hits), read(compiles)
    jax.clear_caches()
    mark = startup.seq
    make()(x).block_until_ready()
    assert ("compile", True) in _records_of("ledger_cached", mark)
    after, n_after = read(hits), read(compiles)
    assert read(misses) == written
    assert after[()] == before[()] + 1
    assert after[label] == before.get(label, 0) + 1
    # a hit is a program brought up all the same
    assert n_after[label] == n_before[label] + 1


@pytest.mark.parametrize("series", sorted(LEDGER_COUNTERS))
def test_the_unlabelled_series_is_its_labelled_ones_sum(series):
    @jax.jit
    def ledger_summed(x):
        return x / 2

    ledger_summed(jnp.ones(5)).block_until_ready()
    samples = default_registry.counter(series).samples()
    total = [v for labels, v in samples if not labels]
    by_program = [v for labels, v in samples if labels]
    assert len(total) == 1 and all(set(k) == {"program"}
                                   for k, v in samples if k)
    assert sum(by_program) == pytest.approx(total[0], rel=1e-9)
    text = default_registry.render()
    assert f"\n{series} " in text
    if "cache" not in series:       # no persistent cache here: no hit, no write
        assert f'{series}{{program="ledger_summed"}}' in text


def test_a_listener_that_raises_does_not_fail_a_compile(monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("the ledger broke")

    monkeypatch.setattr(telemetry, "bump_counter", boom)
    errors = startup.listener_errors

    @jax.jit
    def ledger_guarded(x):
        return x + 2

    out = ledger_guarded(jnp.ones(2))
    assert out.tolist() == [3.0, 3.0]
    assert startup.listener_errors > errors


def test_the_listeners_time_themselves():
    calls, seconds = startup.listener_calls, startup.listener_seconds

    @jax.jit
    def ledger_timed(x):
        return x * x

    ledger_timed(jnp.ones(2)).block_until_ready()
    assert startup.listener_calls >= calls + 6      # a start and an end each
    assert 0 < startup.listener_seconds - seconds < 0.5


def test_listeners_come_off_again():
    """A timeline of its own listens, then does not: the global one's
    records go on (this module's fixture), its own stop."""
    own = StartupTimeline()
    own.install_jax_listeners()
    own.install_jax_listeners()         # once, however often asked
    assert len(own._listeners) == 3

    def compile_one():
        jax.jit(lambda x: x - 3)(jnp.ones(2)).block_until_ready()

    try:
        compile_one()
        seen = own.seq
        assert seen >= 3
    finally:
        own.remove_jax_listeners()
    mark = startup.seq
    compile_one()
    assert own.seq == seen and startup.seq >= mark + 3
    assert own.snapshot()["listeners"]["installed"] is False


# ------------------------------------------- whose first user an event is
def _compile_on_a_thread(stage=None, adopt=False):
    """A fresh program compiled on a thread of its own, which works for
    ``stage`` if it adopts it: the records of that program."""
    fn = jax.jit(lambda x: x * 5 + 1)
    mark = startup.seq

    def run():
        if adopt:
            startup.adopt(stage)
        fn(jnp.ones(7)).block_until_ready()

    t = threading.Thread(target=run, name="other-model")
    t.start()
    t.join()
    return [e for e in startup.events(mark) if e["thread"] == "other-model"]


def test_an_event_belongs_to_the_model_its_thread_works_for():
    """While one model's first user waits, another model's scheduler thread,
    already serving, recompiles: that is ``serving`` (the alert's case), and
    none of the waiting model's seconds. The waiting model's own thread,
    which adopted its stages, is the one that sums into it."""
    waiting = startup.begin(telemetry.FIRST_TOKEN, parent=None,
                            sums_programs=True, model="local::waiting")
    try:
        with startup.stage("engine.thread", parent=waiting) as started:
            pass
        events = _compile_on_a_thread()
        assert events and {e["stage"] for e in events} == {"serving"}
        assert not {"trace_s", "lower_s", "compile_s"} & set(waiting.attrs)
        # adopted through a stage that has closed: its open ancestor's
        events = _compile_on_a_thread(started, adopt=True)
        assert {e["stage"] for e in events} == {"first_token"}
        assert waiting.attrs["compile_s"] == pytest.approx(sum(
            e["seconds"] for e in events if e["event"] == "compile"))
        assert waiting.attrs["trace_s"] > 0 and waiting.attrs["lower_s"] > 0
    finally:
        startup.end(waiting)
    # the first user has its token: the same thread is serving now
    events = _compile_on_a_thread(started, adopt=True)
    assert {e["stage"] for e in events} == {"serving"}


@pytest.mark.parametrize("whose, closes", [("its own", True),
                                           ("another request's", False)])
def test_first_token_closes_at_the_causing_requests_chunk(whose, closes):
    from types import SimpleNamespace

    from cyberfabric_core_tpu.modules.llm_gateway.worker import LocalTpuWorker

    own = StartupTimeline()
    cause, other = {"max_tokens": 1}, {"max_tokens": 1}     # equal, not one
    stage = own.begin(telemetry.FIRST_TOKEN, parent=None, model="m")
    entry = SimpleNamespace(cold_start=(stage, cause))
    LocalTpuWorker._first_token(entry, cause if closes else other)
    assert (stage.end_unix_ns is not None) is closes
    assert (entry.cold_start is None) is closes
    own.end(stage)


# ------------------------------------------------------------ the one view
STACK = {"modules": {
    "api_gateway": {"config": {"bind_addr": "127.0.0.1:0",
                               "auth_disabled": True}},
    "tenant_resolver": {"config": {"single_tenant": "default"}},
    "authn_resolver": {"config": {"mode": "accept_all",
                                  "default_tenant": "default"}},
    "authz_resolver": {}, "monitoring": {},
}}


@pytest.fixture(scope="module")
def view():
    """``GET /v1/monitoring/startup`` and ``/metrics`` of a stack booted
    under a ``boot`` root, as ``server.py`` boots it."""
    loop = asyncio.new_event_loop()

    def get(base, path):
        with urllib.request.urlopen(base + path, timeout=30) as r:
            return r.read().decode()

    async def go():
        boot = startup.begin_boot()
        try:
            rt, base = await boot_stack(STACK)
        finally:
            telemetry._open_stage.set(None)
        try:
            doc = json.loads(await asyncio.to_thread(
                get, base, "/v1/monitoring/startup"))
            text = await asyncio.to_thread(get, base, "/metrics")
        finally:
            await stop_stack(rt)
        return boot, doc, text

    try:
        return loop.run_until_complete(go())
    finally:
        loop.close()


def test_the_endpoint_returns_the_tree(view):
    boot, doc, _ = view
    (node,) = [n for n in doc["stages"] if n["name"] == "boot"
               and n["start_unix_ns"] == boot.start_unix_ns]
    phases = [c["name"] for c in node["children"]]
    assert phases == ["boot." + p for p in (
        "pre_init", "db", "init", "post_init", "rest", "grpc", "start",
        "oop_spawn")]
    by_name = {c["name"]: c for c in node["children"]}
    assert {c["name"] for c in by_name["boot.init"]["children"]} == {
        "boot.init." + m for m in STACK["modules"]}
    assert "boot.start.monitoring" in {
        c["name"] for c in by_name["boot.start"]["children"]}


def test_the_endpoint_returns_ready_and_unnamed(view):
    boot, doc, _ = view
    assert doc["ready_unix"] == pytest.approx(boot.end_unix_ns / 1e9)
    assert doc["process_start_unix"] == pytest.approx(
        startup.process_start_unix_ns / 1e9)
    assert doc["ready_unix"] > doc["process_start_unix"]
    assert doc["unnamed_s"] is not None and doc["unnamed_s"] >= 0
    assert doc["listeners"]["installed"] is True
    assert set(doc) >= {"stages", "programs", "first_token", "events",
                        "trace_id"}


def test_the_endpoint_sums_the_ledger_by_program(view):
    _, doc, _ = view
    assert doc["programs"], "the tests above compiled"
    for name, p in doc["programs"].items():
        assert set(p) == {"trace_s", "lower_s", "compile_s", "compiles",
                          "cache_hits", "cache_misses", "first_at_unix",
                          "last_at_unix"}
        assert p["cache_hits"] + p["cache_misses"] <= p["compiles"]
        assert p["first_at_unix"] <= p["last_at_unix"]


@pytest.mark.parametrize("gauge", ["process_start_time_seconds",
                                   "process_uptime_seconds",
                                   "startup_ready_seconds"])
def test_the_three_instants_are_gauges(view, gauge):
    _, doc, text = view
    (line,) = [ln for ln in text.splitlines() if ln.startswith(gauge + " ")]
    value = float(line.split()[1])
    if gauge == "process_start_time_seconds":
        assert value == pytest.approx(doc["process_start_unix"])
    elif gauge == "process_uptime_seconds":
        assert 0 < value < time.time() - doc["process_start_unix"] + 1
    else:
        assert value == pytest.approx(
            doc["ready_unix"] - doc["process_start_unix"], abs=1e-3)
