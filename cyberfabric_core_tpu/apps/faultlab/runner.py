"""The deterministic chaos-scenario runner.

A scenario spec is a plain dict (see scenarios.py for the catalog and the
format). ``run_scenario`` dispatches on ``kind``:

- ``engine``   — drives one ContinuousBatchingEngine in-process (greedy
  decode): readback crashes, prefill faults, admission delays, forced
  preemption, resume crashes. Stream comparisons run against an unfaulted
  baseline computed once per (config, load) and cached.
- ``pool``     — drives a DataParallelServingPool (2 replicas) through
  mid-stream replica death and failover-path faults.
- ``pd_pool``  — drives a prefill/decode-disaggregated PDServingPool
  through a mid-handoff prefill-replica crash; streams must match the
  UNIFIED single-engine baseline.
- ``http_retry`` — the layered HttpClient against a local mock server with
  per-attempt transport faults (retry triggers + budget).
- ``db_commit``  — SqliteEngine with injected commit failures (atomicity).
- ``server``   — boots the real gateway + oagw + monitoring stack
  in-process; faults are armed over the GUARDED monitoring REST endpoint
  (the same path a live soak rehearsal uses) and exercised through the
  proxy (breaker open/recover) or the middleware (injected 5xx).
- ``serverless`` — gateway + serverless stack: retry/backoff, dead-letter,
  scheduler-loop tick resilience.
- ``worker``   — LocalTpuWorker job crash at the stream boundary.
- ``worker_host_crash`` — two REAL worker subprocesses behind a
  FederatedServingPool; SIGKILL mid-stream → failover, prefix-affinity
  routing, and lease-window eviction.
- ``grpc_evict`` — grpc-hub eviction tick resilience.

Determinism: every scenario seeds modkit.failpoints (probability decisions),
generates load from its own ``random.Random(seed)``, and decodes greedily —
same seed, same verdict, same fingerprint.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

from ...modkit import failpoints as fp
from .invariants import StreamRecord, record_event, run_checkers

__all__ = ["ScenarioResult", "arm_over_rest", "run_all", "run_scenario"]

_DRAIN_TIMEOUT_S = 180.0


@dataclass
class ScenarioResult:
    name: str
    kind: str
    seed: int
    verdict: bool
    invariants: dict[str, list[str]] = field(default_factory=dict)
    fingerprint: str = ""
    details: dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> dict[str, Any]:
        return {"name": self.name, "kind": self.kind, "seed": self.seed,
                "verdict": self.verdict, "invariants": self.invariants,
                "fingerprint": self.fingerprint, "details": self.details}


def _fingerprint(payload: Any) -> str:
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()).hexdigest()[:16]


def _finish(name: str, kind: str, seed: int, invariants: dict[str, list[str]],
            fp_payload: Any, **details: Any) -> ScenarioResult:
    verdict = all(not probs for probs in invariants.values())
    return ScenarioResult(
        name=name, kind=kind, seed=seed, verdict=verdict,
        invariants=invariants,
        fingerprint=_fingerprint({"verdict": verdict, "data": fp_payload}),
        details=details)


# --------------------------------------------------------------- engine kind

#: unfaulted baseline streams, cached per (engine-config, load) — several
#: scenarios compare against the same baseline; recomputing it per scenario
#: would double the jit/compile bill of the suite
_BASELINE_CACHE: dict[str, dict[int, StreamRecord]] = {}


def _engine_config(spec: dict):
    from ...runtime.engine import EngineConfig

    cfg = dict(spec.get("engine") or {})
    cfg.setdefault("model", "tiny-llama")
    cfg.setdefault("max_seq_len", 64)
    cfg.setdefault("max_batch", 2)
    cfg.setdefault("decode_chunk", 4)
    cfg.setdefault("prefix_cache_pages", 64)
    cfg.setdefault("prefix_page_size", 16)
    return EngineConfig(**cfg)


def _make_load(spec: dict) -> list[tuple[list[int], int]]:
    """(prompt_ids, max_tokens) per request, from the scenario's own rng.
    ``vocab: [lo, hi]`` narrows the token alphabet — a tiny alphabet makes
    prompts (and greedy continuations) repetitive, which is what arms the
    speculative scenarios' ngram proposers from the first rounds."""
    load = dict(spec.get("load") or {})
    rng = random.Random(int(spec.get("seed", 0)))
    n = int(load.get("requests", 4))
    lo, hi = load.get("prompt_len", [4, 10])
    v_lo, v_hi = load.get("vocab", [3, 250])
    max_tokens = int(load.get("max_tokens", 10))
    return [([rng.randrange(v_lo, v_hi)
              for _ in range(rng.randrange(lo, hi + 1))],
             max_tokens) for _ in range(n)]


def _drive_engine(cfg, load, faults: list[dict],
                  stagger_s: float = 0.0) -> tuple[dict[int, StreamRecord], Any]:
    """Run one engine through the load with the given faults armed; returns
    (streams, engine). The engine is NOT shut down (checkers inspect it)."""
    from ...runtime.engine import SamplingParams
    from ...runtime.scheduler import ContinuousBatchingEngine

    engine = ContinuousBatchingEngine(cfg, seed=0)
    streams = {i: StreamRecord() for i in range(len(load))}
    done = threading.Event()
    lock = threading.Lock()
    remaining = [len(load)]

    def mk_emit(i):
        def emit(ev):
            with lock:
                was_finished = streams[i].finished
                record_event(streams[i], ev.token_id, ev.finished)
                if ev.finished and not was_finished:
                    remaining[0] -= 1
                    if remaining[0] == 0:
                        done.set()
        return emit

    for f in faults:
        fp.arm(f["point"], f["spec"])
    if not stagger_s:
        # the whole load is queued before the loop's first pass: what an armed
        # fault meets first must not depend on how long the first round takes
        engine.start = lambda: None
    try:
        for i, (prompt, max_tokens) in enumerate(load):
            engine.submit(prompt, SamplingParams(max_tokens=max_tokens),
                          mk_emit(i))
            if stagger_s:
                time.sleep(stagger_s)  # fabric-lint: waive AS01 reason=scenario driver thread staggering arrivals; no event loop in this process path
        engine.__dict__.pop("start", None)
        engine.start()
        done.wait(_DRAIN_TIMEOUT_S)
    finally:
        for f in faults:
            fp.disarm(f["point"])
    return streams, engine


def _baseline_streams(spec: dict, cfg, load) -> dict[int, StreamRecord]:
    key = _fingerprint({"cfg": sorted(
        (k, str(v)) for k, v in cfg.__dict__.items()),
        "load": load})
    if key not in _BASELINE_CACHE:
        streams, engine = _drive_engine(cfg, load, faults=[])
        engine.shutdown()
        _BASELINE_CACHE[key] = streams
    return _BASELINE_CACHE[key]


def _streams_payload(streams: dict[int, StreamRecord],
                     tokens: bool = True) -> Any:
    """Fingerprint material. Crash scenarios set tokens=False: how far a
    stream got before an injected crash is timing-dependent, but the set of
    terminal reasons is not."""
    return {str(i): {"terminals": rec.terminals,
                     **({"tokens": rec.tokens} if tokens else {})}
            for i, rec in sorted(streams.items())}


def _run_engine_scenario(spec: dict) -> ScenarioResult:
    seed = int(spec.get("seed", 0))
    cfg = _engine_config(spec)
    load = _make_load(spec)
    checkers = list(spec.get("invariants", ["exactly_one_terminal"]))
    evidence: dict[str, Any] = {"expect_error": spec.get("expect_error", [])}
    if "streams_match_baseline" in checkers:
        # ``baseline_engine`` overrides the baseline run's EngineConfig on
        # top of the faulted run's (e.g. decode_lookahead: 0 pins the fully
        # synchronous scheduler) — the deep-lookahead scenarios use it to
        # assert depth-N + faults ≡ depth-0 unfaulted, not just
        # faulted ≡ unfaulted at the same depth
        base_over = spec.get("baseline_engine")
        base_cfg = (_engine_config({**spec, "engine": {
            **(spec.get("engine") or {}), **base_over}})
            if base_over else cfg)
        evidence["baseline"] = _baseline_streams(spec, base_cfg, load)
    fp.configure(seed)
    streams, engine = _drive_engine(cfg, load, list(spec.get("faults", [])),
                                    stagger_s=float(spec.get("stagger_s", 0)))
    stats = engine.stats()
    engine.shutdown()
    evidence["streams"] = streams
    evidence["engine"] = engine
    invariants = run_checkers(checkers, evidence)
    for name, expr in (spec.get("expect_stats") or {}).items():
        # e.g. {"preemptions": [1, null]} — inclusive [min, max] bounds;
        # dotted names descend into nested stats ("speculative.rounds")
        lo, hi = expr
        val: Any = stats
        for part in name.split("."):
            val = val.get(part, 0) if isinstance(val, dict) else 0
        ok = (lo is None or val >= lo) and (hi is None or val <= hi)
        invariants[f"stats:{name}"] = (
            [] if ok else [f"{name}={val} outside [{lo}, {hi}]"])
    deterministic_tokens = bool(spec.get("deterministic_tokens", True))
    return _finish(spec["name"], "engine", seed, invariants,
                   _streams_payload(streams, tokens=deterministic_tokens),
                   stats={k: stats[k] for k in
                          ("preemptions", "requests_completed",
                           "tokens_emitted", "broken") if k in stats})


# -------------------------------------------------------- cancellation kinds

def _run_cancel_storm_scenario(spec: dict) -> ScenarioResult:
    """cancel-storm: N concurrent greedy streams, a subset cancelled
    MID-DECODE (each victim's cancel fires from its own emit callback once
    it has emitted ``cancel_after_tokens`` — on the scheduler thread, so the
    application point is deterministic). Survivors must be bit-identical to
    the uncancelled baseline, every stream gets exactly one terminal
    (victims: ``cancelled``), and the drained engine holds zero slot /
    page-ref / orphan leftovers — a cancel storm reclaims capacity without
    perturbing a single live user."""
    from ...runtime.engine import SamplingParams
    from ...runtime.scheduler import ContinuousBatchingEngine

    seed = int(spec.get("seed", 0))
    cfg = _engine_config(spec)
    load = _make_load(spec)
    cancel_idx = set(spec.get("cancel", ()))
    after_tokens = int(spec.get("cancel_after_tokens", 4))
    checkers = list(spec.get("invariants", ["exactly_one_terminal"]))
    evidence: dict[str, Any] = {
        "expect_error": spec.get("expect_error", []),
        "expect_cancelled": {i: "cancelled" for i in sorted(cancel_idx)},
    }
    if "streams_match_baseline" in checkers:
        evidence["baseline"] = _baseline_streams(spec, cfg, load)
    fp.configure(seed)
    engine = ContinuousBatchingEngine(cfg, seed=0)
    streams = {i: StreamRecord() for i in range(len(load))}
    rids = {i: f"cancel-storm-{seed}-{i}" for i in range(len(load))}
    done = threading.Event()
    lock = threading.Lock()
    remaining = [len(load)]
    triggered: set[int] = set()

    def mk_emit(i):
        def emit(ev):
            with lock:
                was_finished = streams[i].finished
                record_event(streams[i], ev.token_id, ev.finished)
                if (i in cancel_idx and i not in triggered
                        and len(streams[i].tokens) >= after_tokens):
                    # fired on the scheduler thread inside the emit pass:
                    # applied at the next round boundary, deterministically
                    triggered.add(i)
                    engine.cancel(rids[i], "storm")
                if ev.finished and not was_finished:
                    remaining[0] -= 1
                    if remaining[0] == 0:
                        done.set()
        return emit

    for f in spec.get("faults", []):
        fp.arm(f["point"], f["spec"])
    try:
        for i, (prompt, max_tokens) in enumerate(load):
            engine.submit(prompt, SamplingParams(max_tokens=max_tokens),
                          mk_emit(i), request_id=rids[i])
        done.wait(_DRAIN_TIMEOUT_S)
    finally:
        for f in spec.get("faults", []):
            fp.disarm(f["point"])
    stats = engine.stats()
    engine.shutdown()
    evidence["streams"] = streams
    evidence["engine"] = engine
    invariants = run_checkers(checkers, evidence)
    got = stats.get("cancellations", {}).get("storm", 0)
    invariants["cancel_count"] = (
        [] if got == len(cancel_idx) else
        [f"{got} cancels applied, expected {len(cancel_idx)}"])
    invariants["budget_reclaimed"] = (
        [] if stats.get("reclaimed_tokens", 0) > 0 else
        ["no decode budget reclaimed by the storm"])
    return _finish(spec["name"], "cancel_storm", seed, invariants,
                   _streams_payload(streams, tokens=True),
                   stats={"cancellations": stats.get("cancellations"),
                          "reclaimed_tokens": stats.get("reclaimed_tokens")})


def _run_deadline_scenario(spec: dict) -> ScenarioResult:
    """deadline-under-load: both slots are pinned by long-running streams
    while an armed ``scheduler.readback`` delay makes every round glacial —
    then laggards arrive with tiny deadlines. They must lapse IN THE QUEUE
    (``deadline`` terminal, zero tokens, never admitted to a slot — their
    flight-recorder timelines show enqueued → deadline_exceeded and nothing
    else), while the runners finish bit-identically to the unfaulted
    baseline (the delay changes only latency)."""
    from ...modkit.flight_recorder import default_recorder
    from ...runtime.engine import SamplingParams
    from ...runtime.scheduler import ContinuousBatchingEngine

    seed = int(spec.get("seed", 0))
    cfg = _engine_config(spec)
    load = _make_load(spec)  # the runners
    n_lag = int(spec.get("laggards", 4))
    deadline_s = float(spec.get("deadline_ms", 150)) / 1000.0
    checkers = list(spec.get("invariants", ["exactly_one_terminal"]))
    lag_base = len(load)
    evidence: dict[str, Any] = {
        "expect_error": spec.get("expect_error", []),
        "expect_cancelled": {lag_base + j: "deadline" for j in range(n_lag)},
    }
    if "streams_match_baseline" in checkers:
        evidence["baseline"] = _baseline_streams(spec, cfg, load)
    fp.configure(seed)
    default_recorder.reset()  # leftover records would pollute the timelines
    engine = ContinuousBatchingEngine(cfg, seed=0)
    n_total = len(load) + n_lag
    streams = {i: StreamRecord() for i in range(n_total)}
    done = threading.Event()
    lock = threading.Lock()
    remaining = [n_total]

    def mk_emit(i):
        def emit(ev):
            with lock:
                was_finished = streams[i].finished
                record_event(streams[i], ev.token_id, ev.finished)
                if ev.finished and not was_finished:
                    remaining[0] -= 1
                    if remaining[0] == 0:
                        done.set()
        return emit

    lag_rng = random.Random(seed ^ 0xDEAD)
    lag_rids = []
    faults = list(spec.get("faults", []))
    for f in faults:
        fp.arm(f["point"], f["spec"])
    try:
        for i, (prompt, max_tokens) in enumerate(load):
            engine.submit(prompt, SamplingParams(max_tokens=max_tokens),
                          mk_emit(i))
        # wait until every slot is occupied: the laggards must pile up
        # BEHIND the armed rounds, not find a free slot
        deadline_poll = time.monotonic() + 30.0
        while engine.active_slots + len(engine._prefill_slots) \
                < cfg.max_batch and time.monotonic() < deadline_poll:
            time.sleep(0.01)  # fabric-lint: waive AS01 reason=scenario driver thread waiting for slot occupancy; no event loop in this process path
        for j in range(n_lag):
            rid = f"deadline-{seed}-{j}"
            lag_rids.append(rid)
            prompt = [lag_rng.randrange(3, 250) for _ in range(6)]
            engine.submit(prompt, SamplingParams(max_tokens=10),
                          mk_emit(lag_base + j), request_id=rid,
                          deadline=time.monotonic() + deadline_s)
        done.wait(_DRAIN_TIMEOUT_S)
    finally:
        for f in faults:
            fp.disarm(f["point"])
    stats = engine.stats()
    engine.shutdown()
    evidence["streams"] = streams
    evidence["engine"] = engine
    invariants = run_checkers(checkers, evidence)
    lapse_count = stats.get("cancellations", {}).get("deadline", 0)
    invariants["all_laggards_lapsed"] = (
        [] if lapse_count == n_lag else
        [f"{lapse_count} deadline lapses, expected {n_lag}"])
    timeline_problems = []
    for rid in lag_rids:
        rec = default_recorder.lookup(rid)
        kinds = [e["event"] for e in (rec or {}).get("timeline", ())]
        if kinds != ["enqueued", "deadline_exceeded"]:
            timeline_problems.append(f"{rid}: timeline {kinds}")
    invariants["laggards_never_admitted"] = timeline_problems
    return _finish(spec["name"], "deadline", seed, invariants,
                   _streams_payload(streams, tokens=True),
                   stats={"cancellations": stats.get("cancellations"),
                          "reclaimed_tokens": stats.get("reclaimed_tokens")})


# ------------------------------------------------------- tenancy kinds

def _run_noisy_neighbor_scenario(spec: dict) -> ScenarioResult:
    """noisy-neighbor: one tenant floods ``heavy_requests`` (default 32)
    greedy streams while a light tenant submits ``light_requests`` (default
    4) right behind them, through ONE tenant-fair engine. The weighted-fair
    queue must bound the light tenant's exposure to the flood:

    - every light request is admitted while a large chunk of the heavy
      backlog is still waiting (under tenant-blind FIFO, ALL heavy requests
      admit first — the decisive structural check);
    - the light tenant's worst queue wait stays under an absolute sanity
      bound (and within a generous factor of its solo run — recorded as
      detail; CPU timing is too noisy for a tight relative invariant);
    - at the instant the light tenant's LAST stream finishes (captured on
      the scheduler thread — a deterministic observation point), the two
      tenants' weight-normalized charged tokens are within a fixed factor:
      token shares converge to the configured weights instead of the heavy
      tenant serializing the engine;
    - every stream is bit-identical to its tenant's solo (unloaded) run —
      fairness reorders admission, never tokens — and the drained engine
      holds zero slot/page leaks."""
    from ...modkit.flight_recorder import default_recorder
    from ...runtime.engine import SamplingParams
    from ...runtime.scheduler import ContinuousBatchingEngine

    seed = int(spec.get("seed", 0))
    cfg = _engine_config(spec)
    heavy_n = int(spec.get("heavy_requests", 32))
    light_n = int(spec.get("light_requests", 4))
    max_tokens = int((spec.get("load") or {}).get("max_tokens", 8))
    rng = random.Random(seed)
    lo, hi = (spec.get("load") or {}).get("prompt_len", [4, 10])

    def mk_prompts(n):
        return [[rng.randrange(3, 250) for _ in range(rng.randrange(lo, hi + 1))]
                for _ in range(n)]

    heavy_prompts = mk_prompts(heavy_n)
    light_prompts = mk_prompts(light_n)
    heavy_load = [(p, max_tokens) for p in heavy_prompts]
    light_load = [(p, max_tokens) for p in light_prompts]
    fp.configure(seed)
    # solo (unloaded) baselines per tenant — greedy streams are admission-
    # order invariant, so each tenant's solo run is the bit-identity oracle
    light_solo = _baseline_streams({**spec, "load": {}}, cfg, light_load)
    heavy_solo = _baseline_streams({**spec, "load": {}}, cfg, heavy_load)
    # solo queue waits for the light tenant (detail / sanity factor)
    default_recorder.reset()
    solo_engine = ContinuousBatchingEngine(cfg, seed=0)
    solo_done = threading.Event()
    solo_left = [light_n]

    def mk_solo_emit():
        def emit(ev):
            if ev.finished:
                solo_left[0] -= 1
                if solo_left[0] == 0:
                    solo_done.set()
        return emit

    solo_rids = []
    for j, (prompt, mt) in enumerate(light_load):
        rid = f"nn-solo-{seed}-{j}"
        solo_rids.append(rid)
        solo_engine.submit(prompt, SamplingParams(max_tokens=mt),
                           mk_solo_emit(), request_id=rid, tenant="light")
    solo_done.wait(_DRAIN_TIMEOUT_S)
    solo_engine.shutdown()

    def queue_waits(rids):
        waits = []
        for rid in rids:
            rec = default_recorder.lookup(rid) or {}
            for ev in rec.get("timeline", ()):
                if ev.get("event") == "admitted":
                    waits.append(float(ev.get("queue_wait_ms", 0.0)))
        return waits

    solo_waits = queue_waits(solo_rids)

    # ---- the contended run: heavy floods first, light right behind
    default_recorder.reset()
    engine = ContinuousBatchingEngine(cfg, seed=0)
    n_total = heavy_n + light_n
    streams = {i: StreamRecord() for i in range(n_total)}
    done = threading.Event()
    lock = threading.Lock()
    remaining = [n_total]
    light_left = [light_n]
    share_at_light_finish: dict[str, Any] = {}

    def mk_emit(i, light: bool):
        def emit(ev):
            with lock:
                was_finished = streams[i].finished
                record_event(streams[i], ev.token_id, ev.finished)
                if ev.finished and not was_finished:
                    remaining[0] -= 1
                    if light:
                        light_left[0] -= 1
                        if light_left[0] == 0:
                            # deterministic observation point, on the
                            # scheduler thread: the fairness ledger the
                            # moment the light tenant's work completes
                            share_at_light_finish.update(
                                engine.tenant_snapshot())
                    if remaining[0] == 0:
                        done.set()
        return emit

    heavy_rids = [f"nn-heavy-{seed}-{i}" for i in range(heavy_n)]
    light_rids = [f"nn-light-{seed}-{j}" for j in range(light_n)]
    for i, (prompt, mt) in enumerate(heavy_load):
        engine.submit(prompt, SamplingParams(max_tokens=mt), mk_emit(i, False),
                      request_id=heavy_rids[i], tenant="heavy")
    for j, (prompt, mt) in enumerate(light_load):
        engine.submit(prompt, SamplingParams(max_tokens=mt),
                      mk_emit(heavy_n + j, True),
                      request_id=light_rids[j], tenant="light")
    done.wait(_DRAIN_TIMEOUT_S)
    stats = engine.stats()
    engine.shutdown()

    # admission order: ts of each request's 'admitted' event
    admitted_at: dict[str, float] = {}
    for rid in heavy_rids + light_rids:
        rec = default_recorder.lookup(rid) or {}
        for ev in rec.get("timeline", ()):
            if ev.get("event") == "admitted":
                admitted_at[rid] = ev["ts"]
    problems: dict[str, list[str]] = {}
    order_probs = []
    # under fair scheduling every light request admits while most of the
    # heavy backlog still waits; tenant-blind FIFO admits all heavy first
    max_heavy_before = int(spec.get("max_heavy_admitted_before",
                                    heavy_n - 8))
    for rid in light_rids:
        ts = admitted_at.get(rid)
        if ts is None:
            order_probs.append(f"{rid} never admitted")
            continue
        before = sum(1 for h in heavy_rids
                     if admitted_at.get(h) is not None
                     and admitted_at[h] < ts)
        if before > max_heavy_before:
            order_probs.append(
                f"{rid}: {before} heavy requests admitted first "
                f"(> {max_heavy_before} — FIFO-like starvation)")
    problems["light_admitted_while_heavy_backlogged"] = order_probs
    cont_waits = queue_waits(light_rids)
    wait_bound_s = float(spec.get("light_wait_bound_s", 10.0))
    worst = max(cont_waits) / 1000.0 if cont_waits else float("inf")
    problems["light_queue_wait_bounded"] = (
        [] if cont_waits and worst <= wait_bound_s else
        [f"light worst queue wait {worst:.2f}s > {wait_bound_s}s "
         f"(solo waits ms: {solo_waits})"])
    # token shares at the light tenant's completion instant
    share_probs = []
    ledger = share_at_light_finish
    if not ledger.get("light") or not ledger.get("heavy"):
        share_probs.append(f"fairness ledger missing tenants: {ledger}")
    else:
        def norm(t):
            row = ledger[t]
            return row["charged_tokens"] / max(row["weight"], 1e-9)

        ratio = norm("heavy") / max(norm("light"), 1e-9)
        lo_f, hi_f = spec.get("share_ratio_bounds", [0.1, 6.0])
        if not lo_f <= ratio <= hi_f:
            share_probs.append(
                f"weight-normalized heavy/light charged ratio {ratio:.2f} "
                f"outside [{lo_f}, {hi_f}] at light completion — shares "
                "did not converge to the configured weights")
    problems["token_shares_converge"] = share_probs
    # bit-identity against the solo baselines + leak checks
    evidence = {
        "streams": streams,
        "engine": engine,
        "expect_error": [],
        "baseline": {**{i: heavy_solo[i] for i in range(heavy_n)},
                     **{heavy_n + j: light_solo[j]
                        for j in range(light_n)}},
    }
    problems.update(run_checkers(
        list(spec.get("invariants",
                      ["exactly_one_terminal", "streams_match_baseline",
                       "engine_accounting"])), evidence))
    return _finish(
        spec["name"], "noisy_neighbor", seed, problems,
        _streams_payload(streams, tokens=True),
        waits={"light_solo_ms": solo_waits, "light_contended_ms": cont_waits},
        tenants={t: {k: row[k] for k in ("charged_tokens", "weight")}
                 for t, row in ledger.items()} if ledger else {},
        stats={"tenants": {t: r.get("charged_tokens")
                           for t, r in stats.get("tenants", {}).items()}})


def _run_selective_shed_scenario(spec: dict) -> ScenarioResult:
    """selective-shed: on a REAL two-tenant stack (accept_all authn —
    x-tenant-id selects the tenant), a readback delay armed over the
    guarded REST control plane burns the itl objective while the ``heavy``
    tenant floods concurrent completions and the ``light`` tenant probes
    politely. The doctor must attribute the burn/queue pressure to the
    over-fair-share tenant and the gateway must shed ONLY it:

    - a heavy probe gets 429 ``tenant_shed`` + Retry-After while a light
      probe keeps returning 200 with baseline-identical text;
    - global shedding never engages (``/readyz`` stays 200 — ``shed_after``
      is set out of reach, selective shedding is the first line);
    - after disarm + drain the shed set clears and heavy serves again."""
    seed = int(spec.get("seed", 0))
    delay_spec = spec.get("delay_spec", "delay(0.4)")

    async def go():
        import aiohttp

        doctor_cfg = {
            "eval_interval_s": 0.1, "fast_window_s": 2.0,
            "slow_window_s": 4.0, "min_samples": 3,
            # global shedding out of reach: selective shedding must carry
            "shed_after": 10 ** 6, "recover_after": 2,
            # only the itl objective is under test: on a loaded CPU a cold
            # compile or a queued request burns the shipped ttft and queue
            # objectives before the fault is armed, so those are out of reach
            "objectives": {"itl_p99": {"threshold_ms": float(
                               spec.get("itl_threshold_ms", 30.0))},
                           "ttft_p95": {"threshold_ms": 120000.0},
                           "queue_wait_p95": {"threshold_ms": 120000.0}},
            "tenant_over_share": 1.5, "tenant_min_activity": 8,
            "tenant_shed_retry_after_s": 1.0,
            "stream_stall_s": 120.0, "round_stall_floor_s": 120.0,
            "queue_deadline_s": 120.0,
        }
        rt, base = await _boot_stack(
            ["authn_resolver", "authz_resolver", "monitoring",
             "model_registry", "llm_gateway"],
            {"tenant_resolver": {"config": {"tenants": {
                # both tenants inherit the shared model from root (model
                # resolution walks up the tenant hierarchy)
                "root": {}, "light": {"parent": "root"},
                "heavy": {"parent": "root"}}}},
             "authn_resolver": {"config": {"mode": "accept_all",
                                           "default_tenant": "light"}},
             "model_registry": {"config": {"seed_tenant": "root",
                                           "models": [{
                 "provider_slug": "local", "provider_model_id": "tiny-llama",
                 "approval_state": "approved", "managed": True,
                 "architecture": "llama",
                 "engine_options": {"model_config": "tiny-llama",
                                    "max_seq_len": 128, "max_batch": 4,
                                    "decode_chunk": 8}}]}},
             "llm_gateway": {},
             "monitoring": {"config": {"allow_fault_injection": True,
                                       "doctor": doctor_cfg}}},
            auth_disabled=False)
        out: dict[str, Any] = {}
        try:
            async with aiohttp.ClientSession() as s:
                async def completion(tenant: str, prompt: str,
                                     max_tokens: int = 16):
                    async with s.post(
                            f"{base}/v1/completions",
                            json={"model": "local::tiny-llama",
                                  "prompt": prompt,
                                  "max_tokens": max_tokens},
                            headers={"x-tenant-id": tenant}) as r:
                        body = await r.json()
                        return r.status, dict(r.headers), body

                def text_of(body: dict) -> str:
                    return "".join(p.get("text", "")
                                   for p in body.get("content", []))

                # warmup compile + light baseline text
                await completion("light", "selective shed warmup", 8)
                st, _, body = await completion("light", f"probe {seed}", 8)
                out["light_baseline"] = {"status": st,
                                         "text": text_of(body)}

                await arm_over_rest(s, base, "scheduler.readback",
                                    delay_spec, seed=seed)
                flood = [asyncio.ensure_future(
                    completion("heavy", f"flood {seed} {i}", 24))
                    for i in range(int(spec.get("heavy_requests", 16)))]
                # wait for the doctor to attribute the burn to the heavy
                # tenant, on the surfaces that name it. Read them FIRST and
                # while the flood runs: they are GETs, where a heavy probe
                # sent now would queue behind the flood and come back when
                # it is over, a window or two before the marks clear
                deadline = time.monotonic() + 45.0
                while time.monotonic() < deadline:
                    async with s.get(f"{base}/v1/monitoring/slo",
                                     headers={"x-tenant-id": "light"}) as r:
                        slo = await r.json()
                    out["shed_tenants"] = slo.get("shed_tenants", [])
                    out["state_during"] = slo.get("state")
                    async with s.get(f"{base}/v1/monitoring/tenants",
                                     headers={"x-tenant-id": "light"}) as r:
                        out["tenants_rows"] = {
                            row["tenant"]: row.get("shed")
                            for row in (await r.json()).get("tenants", [])}
                    if out["shed_tenants"] == ["heavy"] and \
                            out["tenants_rows"].get("heavy") is True:
                        break
                    await asyncio.sleep(0.1)
                # the gateway sheds the marked tenant pre-enqueue
                st, headers, body = await completion(
                    "heavy", f"shed probe {seed}", 8)
                out["heavy_shed_probe"] = {
                    "status": st, "code": body.get("code"),
                    "retry_after": headers.get("Retry-After")}
                # while the heavy tenant is shed, the light tenant serves
                st, _, body = await completion("light", f"probe {seed}", 8)
                out["light_during_shed"] = {
                    "status": st,
                    "text_matches": text_of(body)
                    == out["light_baseline"]["text"]}
                # global shedding never engaged: /readyz stays 200
                async with s.get(f"{base}/readyz") as r:
                    out["readyz_during_shed"] = r.status
                await _disarm_over_rest(s, base, "scheduler.readback")
                flood_done = await asyncio.gather(*flood)
                out["flood_status"] = sorted(
                    {st for st, _, _ in flood_done})
                # burn subsides → the shed set clears and heavy serves
                recovered = None
                deadline = time.monotonic() + 45.0
                while time.monotonic() < deadline:
                    st, _, _ = await completion(
                        "heavy", f"recovered probe {seed}", 8)
                    if st == 200:
                        recovered = st
                        break
                    await asyncio.sleep(0.3)
                out["heavy_recovered"] = recovered
        finally:
            from ...modkit.doctor import DoctorConfig, default_doctor

            await _stop_stack(rt)
            default_doctor.stop()
            default_doctor.configure(DoctorConfig())
        return out

    out = asyncio.run(go())
    shed_probe = out.get("heavy_shed_probe") or {}
    invariants = {
        "heavy_tenant_shed_with_retry_after": (
            [] if (shed_probe.get("status") == 429
                   and shed_probe.get("code") == "tenant_shed"
                   and shed_probe.get("retry_after")) else
            [f"heavy shed probe {shed_probe}"]),
        "light_tenant_keeps_serving": (
            [] if (out.get("light_during_shed", {}).get("status") == 200
                   and out.get("light_during_shed", {}).get("text_matches"))
            else [f"light during shed: {out.get('light_during_shed')}"]),
        "global_shedding_stays_last_resort": (
            [] if (out.get("readyz_during_shed") == 200
                   and out.get("state_during") != "shedding") else
            [f"readyz={out.get('readyz_during_shed')} "
             f"state={out.get('state_during')} — global shedding engaged"]),
        "doctor_names_the_abuser": (
            [] if out.get("shed_tenants") == ["heavy"] else
            [f"shed_tenants {out.get('shed_tenants')}"]),
        "tenants_surface_marks_shed": (
            [] if out.get("tenants_rows", {}).get("heavy") is True else
            [f"/v1/monitoring/tenants rows: {out.get('tenants_rows')}"]),
        "heavy_recovers_after_drain": (
            [] if out.get("heavy_recovered") == 200 else
            [f"heavy never recovered ({out.get('heavy_recovered')})"]),
        "flood_terminates": (
            [] if out.get("flood_status") and
            set(out["flood_status"]) <= {200, 429} else
            [f"flood statuses {out.get('flood_status')}"]),
    }
    return _finish(spec["name"], "selective_shed", seed, invariants,
                   {"shed_probe": {k: shed_probe.get(k)
                                   for k in ("status", "code")},
                    "light": out.get("light_during_shed"),
                    "readyz": out.get("readyz_during_shed")},
                   shed_tenants=out.get("shed_tenants"),
                   flood_status=out.get("flood_status"))


# ----------------------------------------------------------------- pool kind

def _drive_pool(cfg, load, faults: list[dict], n_replicas: int = 2,
                pool=None):
    """``pool`` overrides construction (the lifecycle kinds pass a
    supervised pool and keep driving it after this load drains)."""
    from ...runtime.engine import SamplingParams
    from ...runtime.replicas import DataParallelServingPool

    if pool is None:
        pool = DataParallelServingPool(cfg, n_replicas=n_replicas)
    streams = {i: StreamRecord() for i in range(len(load))}
    done = threading.Event()
    lock = threading.Lock()
    remaining = [len(load)]
    submit_errors: list[str] = []

    def mk_emit(i):
        def emit(ev):
            with lock:
                was_finished = streams[i].finished
                record_event(streams[i], ev.token_id, ev.finished)
                if ev.finished and not was_finished:
                    remaining[0] -= 1
                    if remaining[0] == 0:
                        done.set()
        return emit

    for f in faults:
        fp.arm(f["point"], f["spec"])
    try:
        for i, (prompt, max_tokens) in enumerate(load):
            try:
                pool.submit(prompt, SamplingParams(max_tokens=max_tokens),
                            mk_emit(i))
            except Exception as e:  # noqa: BLE001 — e.g. replicas.submit fault
                submit_errors.append(f"{i}: {type(e).__name__}")
                with lock:
                    # a synchronous rejection IS this request's terminal
                    record_event(streams[i], -1, "error")
                    remaining[0] -= 1
                    if remaining[0] == 0:
                        done.set()
        done.wait(_DRAIN_TIMEOUT_S)
    finally:
        for f in faults:
            fp.disarm(f["point"])
    return streams, pool, submit_errors


def _run_pool_scenario(spec: dict) -> ScenarioResult:
    import jax

    seed = int(spec.get("seed", 0))
    n_replicas = int(spec.get("replicas", 2))
    if len(jax.devices()) < n_replicas:
        return ScenarioResult(
            spec["name"], "pool", seed, verdict=True,
            invariants={"skipped": []}, fingerprint="skipped",
            details={"skipped": f"needs {n_replicas} devices"})
    cfg = _engine_config(spec)
    load = _make_load(spec)
    checkers = list(spec.get("invariants", ["exactly_one_terminal"]))
    evidence: dict[str, Any] = {"expect_error": spec.get("expect_error", [])}
    if "streams_match_baseline" in checkers:
        # the pool baseline is the ENGINE baseline: a failover continuation
        # must reproduce exactly what one healthy engine would have emitted
        evidence["baseline"] = _baseline_streams(spec, cfg, load)
    fp.configure(seed)
    streams, pool, submit_errors = _drive_pool(
        cfg, load, list(spec.get("faults", [])), n_replicas)
    stats = pool.stats()
    pool.shutdown()
    evidence["streams"] = streams
    evidence["pool"] = pool
    invariants = run_checkers(checkers, evidence)
    for name, expr in (spec.get("expect_stats") or {}).items():
        lo, hi = expr
        val = stats.get(name, 0)
        ok = (lo is None or val >= lo) and (hi is None or val <= hi)
        invariants[f"stats:{name}"] = (
            [] if ok else [f"{name}={val} outside [{lo}, {hi}]"])
    if "expect_submit_errors" in spec:
        want = int(spec["expect_submit_errors"])
        invariants["submit_errors"] = (
            [] if len(submit_errors) == want else
            [f"{len(submit_errors)} submit errors, expected {want}: "
             f"{submit_errors}"])
    deterministic_tokens = bool(spec.get("deterministic_tokens", True))
    return _finish(spec["name"], "pool", seed, invariants,
                   _streams_payload(streams, tokens=deterministic_tokens),
                   stats={k: stats[k] for k in
                          ("failovers", "failovers_failed", "healthy")})


def _run_pd_pool_scenario(spec: dict) -> ScenarioResult:
    """pd_pool kind: a prefill/decode-disaggregated PDServingPool
    (``prefill_replicas`` + ``decode_replicas``) driven through the same
    load/fault machinery as the unified pool kind. The baseline is the
    UNIFIED single-engine run: splitting prefill from decode — and crashing
    a prefill replica mid-handoff — must not change a single token.
    ``expect_stats`` names may be dotted (``pd.handoffs``)."""
    import jax

    from ...runtime.pd import PDServingPool

    seed = int(spec.get("seed", 0))
    n_prefill = int(spec.get("prefill_replicas", 2))
    n_decode = int(spec.get("decode_replicas", 1))
    n_replicas = n_prefill + n_decode
    if len(jax.devices()) < n_replicas:
        return ScenarioResult(
            spec["name"], "pd_pool", seed, verdict=True,
            invariants={"skipped": []}, fingerprint="skipped",
            details={"skipped": f"needs {n_replicas} devices"})
    cfg = _engine_config(spec)
    load = _make_load(spec)
    checkers = list(spec.get("invariants", ["exactly_one_terminal"]))
    evidence: dict[str, Any] = {"expect_error": spec.get("expect_error", [])}
    if "streams_match_baseline" in checkers:
        evidence["baseline"] = _baseline_streams(spec, cfg, load)
    fp.configure(seed)
    pool = PDServingPool(cfg, n_prefill=n_prefill, n_decode=n_decode)
    streams, pool, submit_errors = _drive_pool(
        cfg, load, list(spec.get("faults", [])), n_replicas, pool=pool)
    stats = pool.stats()
    pool.shutdown()
    evidence["streams"] = streams
    evidence["pool"] = pool
    invariants = run_checkers(checkers, evidence)
    for name, expr in (spec.get("expect_stats") or {}).items():
        lo, hi = expr
        val: Any = stats
        for part in name.split("."):
            val = val.get(part, 0) if isinstance(val, dict) else 0
        ok = (lo is None or val >= lo) and (hi is None or val <= hi)
        invariants[f"stats:{name}"] = (
            [] if ok else [f"{name}={val} outside [{lo}, {hi}]"])
    if submit_errors:
        invariants["submit_errors"] = [
            f"unexpected submit rejections: {submit_errors}"]
    deterministic_tokens = bool(spec.get("deterministic_tokens", True))
    return _finish(spec["name"], "pd_pool", seed, invariants,
                   _streams_payload(streams, tokens=deterministic_tokens),
                   stats={"failovers": stats["failovers"],
                          "healthy": stats["healthy"],
                          "handoffs": stats["pd"]["handoffs"],
                          "handoffs_failed": stats["pd"]["handoffs_failed"]})


# ------------------------------------------------- replica lifecycle kinds

def _pool_probe(pool, prompt: list[int], max_tokens: int,
                timeout_s: float = 60.0) -> StreamRecord:
    """One greedy probe request through the pool (probation canaries and
    rebuilt-replica bit-identity checks)."""
    from ...runtime.engine import SamplingParams

    rec = StreamRecord()
    done = threading.Event()

    def emit(ev):
        record_event(rec, ev.token_id, ev.finished)
        if ev.finished:
            done.set()

    pool.submit(prompt, SamplingParams(max_tokens=max_tokens), emit)
    done.wait(timeout_s)
    return rec


def _wait_for(predicate, timeout_s: float) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.05)  # fabric-lint: waive AS01 reason=scenario driver thread polling lifecycle state; no event loop in this process path
    return False


def _lifecycle_pool(spec: dict, cfg, n_replicas: int):
    """A supervised pool with scenario-speed lifecycle knobs (production
    defaults are seconds; the state walk is identical)."""
    from ...runtime.lifecycle import LifecycleConfig
    from ...runtime.replicas import DataParallelServingPool

    lc = LifecycleConfig(
        check_interval_s=0.05,
        rebuild_backoff_s=0.05,
        rebuild_backoff_max_s=0.2,
        max_strikes=int(spec.get("max_strikes", 2)),
        probation_successes=1,
        drain_deadline_s=float(spec.get("drain_deadline_s", 30.0)),
        seed=int(spec.get("seed", 0)))
    return DataParallelServingPool(cfg, n_replicas=n_replicas, lifecycle=lc)


def _run_replica_crash_loop_scenario(spec: dict) -> ScenarioResult:
    """replica-crash-loop: an injected mid-stream break under load fails the
    victim's streams over to the survivor (bit-identical, exactly one
    terminal each); the lifecycle supervisor's rebuilds keep failing (armed
    ``replicas.rebuild``), so strikes walk through exponential backoff until
    the replica is BENCHED. Disarming + an operator ``restart`` (strikes
    cleared) rebuilds it for real, a probation canary promotes it, and the
    pool returns to ``healthy == n_replicas`` — capacity recovered without a
    process restart, with zero slot/page/tracking leaks."""
    import jax

    seed = int(spec.get("seed", 0))
    n_replicas = int(spec.get("replicas", 2))
    if len(jax.devices()) < n_replicas:
        return ScenarioResult(
            spec["name"], "replica_crash_loop", seed, verdict=True,
            invariants={"skipped": []}, fingerprint="skipped",
            details={"skipped": f"needs {n_replicas} devices"})
    cfg = _engine_config(spec)
    load = _make_load(spec)
    checkers = list(spec.get("invariants", ["exactly_one_terminal"]))
    evidence: dict[str, Any] = {"expect_error": spec.get("expect_error", [])}
    if "streams_match_baseline" in checkers:
        evidence["baseline"] = _baseline_streams(spec, cfg, load)
    fp.configure(seed)
    pool = _lifecycle_pool(spec, cfg, n_replicas)
    lc = pool.lifecycle
    problems: dict[str, list[str]] = {}
    # replicas.rebuild stays armed past the load's end (which may come before
    # the second attempt): max_strikes failures → benched, the backstop
    faults = list(spec.get("faults", []))
    rebuild = [f for f in faults if f["point"] == "replicas.rebuild"]
    for f in rebuild:
        fp.arm(f["point"], f["spec"])
    try:
        streams, pool, _errs = _drive_pool(
            cfg, load, [f for f in faults if f not in rebuild], n_replicas,
            pool=pool)
        benched = _wait_for(lambda: lc.counts()["benched"] >= 1, 20.0)
    finally:
        fp.disarm("replicas.rebuild")
    problems["crash_loop_benched"] = [] if benched else [
        f"replica never benched: {lc.status()}"]
    problems["rebuild_retries_backed_off"] = (
        [] if lc.rebuilds_failed >= int(spec.get("max_strikes", 2))
        else [f"only {lc.rebuilds_failed} failed rebuild attempts"])
    benched_idx = next(
        (row["index"] for row in lc.status()["replicas"]
         if row["state"] == "benched"), None)
    recovered = False
    probe = None
    if benched_idx is not None:
        lc.restart(benched_idx)
        # the rebuilt engine counts as pool-healthy immediately; the
        # probation canary below promotes its lifecycle state too
        recovered = _wait_for(
            lambda: pool.stats()["healthy"] == n_replicas, 60.0)
        if recovered:
            probe = _pool_probe(pool, load[0][0], load[0][1])
            _wait_for(lambda: lc.counts()["healthy"] == n_replicas, 10.0)
    problems["pool_recovered_to_full_capacity"] = [] if recovered else [
        f"healthy={pool.stats()['healthy']} != {n_replicas} after "
        f"restart ({lc.status()})"]
    base0 = evidence.get("baseline", {}).get(0)
    problems["rebuilt_replica_stream_bit_identical"] = (
        [] if probe is not None and base0 is not None
        and probe.tokens == base0.tokens
        and probe.terminals == base0.terminals else
        [f"probe through the rebuilt pool diverged: "
         f"{probe and probe.terminals} vs {base0 and base0.terminals}"])
    problems["probation_promoted"] = (
        [] if lc.probation_promotions >= 1 and
        lc.counts()["healthy"] == n_replicas else
        [f"probation never promoted: {lc.counts()}"])
    stats = pool.stats()
    # shutdown BEFORE the accounting checkers: joining the scheduler threads
    # guarantees the last terminal's chain release has landed (the pool kind
    # orders it the same way)
    pool.shutdown()
    evidence["streams"] = streams
    evidence["pool"] = pool
    problems.update(run_checkers(checkers, evidence))
    deterministic_tokens = bool(spec.get("deterministic_tokens", True))
    return _finish(
        spec["name"], "replica_crash_loop", seed, problems,
        _streams_payload(streams, tokens=deterministic_tokens),
        lifecycle={"rebuilds_ok": lc.rebuilds_ok,
                   "rebuilds_failed": lc.rebuilds_failed,
                   "benched_total": lc.benched_total,
                   "promotions": lc.probation_promotions},
        stats={k: stats[k] for k in ("failovers", "healthy", "replicas")})


def _run_replica_drain_scenario(spec: dict) -> ScenarioResult:
    """drain-under-load: a replica is drained WHILE its streams are mid-
    flight. New admissions route around it instantly; past the (tiny)
    deadline the engine is closed and the stragglers fail over to the
    survivor — every stream still bit-identical to an undrained baseline
    with exactly one terminal. The drained replica's episode lands in the
    flight recorder (drain_begin → drain_end), and a restart + canary
    returns the pool to full capacity."""
    import jax

    from ...modkit.flight_recorder import default_recorder
    from ...runtime.engine import SamplingParams

    seed = int(spec.get("seed", 0))
    n_replicas = int(spec.get("replicas", 2))
    if len(jax.devices()) < n_replicas:
        return ScenarioResult(
            spec["name"], "replica_drain", seed, verdict=True,
            invariants={"skipped": []}, fingerprint="skipped",
            details={"skipped": f"needs {n_replicas} devices"})
    cfg = _engine_config(spec)
    load = _make_load(spec)
    checkers = list(spec.get("invariants", ["exactly_one_terminal"]))
    evidence: dict[str, Any] = {"expect_error": spec.get("expect_error", [])}
    if "streams_match_baseline" in checkers:
        evidence["baseline"] = _baseline_streams(spec, cfg, load)
    fp.configure(seed)
    pool = _lifecycle_pool(spec, cfg, n_replicas)
    lc = pool.lifecycle
    streams = {i: StreamRecord() for i in range(len(load))}
    done = threading.Event()
    lock = threading.Lock()
    remaining = [len(load)]
    problems: dict[str, list[str]] = {}

    def mk_emit(i):
        def emit(ev):
            with lock:
                was_finished = streams[i].finished
                record_event(streams[i], ev.token_id, ev.finished)
                if ev.finished and not was_finished:
                    remaining[0] -= 1
                    if remaining[0] == 0:
                        done.set()
        return emit

    faults = list(spec.get("faults", []))
    for f in faults:
        fp.arm(f["point"], f["spec"])
    try:
        rids = [pool.submit(prompt, SamplingParams(max_tokens=mt), mk_emit(i))
                for i, (prompt, mt) in enumerate(load)]
        time.sleep(float(spec.get("drain_after_s", 0.2)))  # fabric-lint: waive AS01 reason=scenario driver thread letting streams start before the drain; no event loop in this process path
        with pool._lock:
            live = next((t.replica for rid, t in pool._requests.items()
                         if rid in rids), 0)
        victim = int(live)
        lc.drain(victim, deadline_s=float(spec.get("deadline_s", 0.05)))
        drained = _wait_for(lambda: lc.counts()["drained"] >= 1, 30.0)
        all_done = done.wait(_DRAIN_TIMEOUT_S)
    finally:
        for f in faults:
            fp.disarm(f["point"])
    problems["streams_survive_drain"] = [] if all_done else [
        f"{remaining[0]} streams never finished after the drain"]
    problems["drain_completed"] = [] if drained else [
        f"replica {victim} never reached drained: {lc.status()}"]
    episode = default_recorder.lookup(f"{lc.name}/replica{victim}/drain-1")
    ep_events = [e["event"] for e in (episode or {}).get("timeline", ())]
    problems["drain_episode_recorded"] = (
        [] if ep_events[:1] == ["drain_begin"] and "drain_end" in ep_events
        else [f"drain episode timeline {ep_events}"])
    lc.restart(victim)
    recovered = _wait_for(lambda: pool.stats()["healthy"] == n_replicas, 60.0)
    if recovered:
        _pool_probe(pool, load[0][0], load[0][1])
        _wait_for(lambda: lc.counts()["healthy"] == n_replicas, 10.0)
    problems["pool_recovered_after_restart"] = [] if recovered and \
        lc.counts()["healthy"] == n_replicas else [
        f"post-restart counts {lc.counts()}"]
    stats = pool.stats()
    # shutdown BEFORE the accounting checkers: joining the scheduler threads
    # guarantees the last terminal's chain release has landed
    pool.shutdown()
    evidence["streams"] = streams
    evidence["pool"] = pool
    problems.update(run_checkers(checkers, evidence))
    return _finish(
        spec["name"], "replica_drain", seed, problems,
        _streams_payload(streams, tokens=True),
        lifecycle={"drains_clean": lc.drains_clean,
                   "drains_killed": lc.drains_killed,
                   "rebuilds_ok": lc.rebuilds_ok},
        stats={k: stats[k] for k in ("failovers", "healthy", "replicas")})


# ----------------------------------------------------------- http retry kind

def _run_http_retry_scenario(spec: dict) -> ScenarioResult:
    seed = int(spec.get("seed", 0))

    async def go():
        from aiohttp import web

        from ...modkit.http_client import (HttpClient, HttpClientConfig,
                                           RetryBudget, RetryConfig)

        hits = {"n": 0}

        async def hello(request):
            hits["n"] += 1
            return web.json_response({"ok": True})

        app = web.Application()
        app.router.add_get("/hello", hello)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]  # noqa: SLF001
        fp.configure(seed)
        faults = list(spec.get("faults", []))
        for f in faults:
            fp.arm(f["point"], f["spec"])
        try:
            # a budget with deposit history: five completed first attempts
            # bank exactly one retry (retry_ratio 0.2) — the injected fault
            # must consume it, proving the budget really gates retries
            budget = RetryBudget()
            for _ in range(5):
                budget.deposit()
            client = HttpClient(HttpClientConfig(
                base_url=f"http://127.0.0.1:{port}",
                retry=RetryConfig(max_retries=3, budget=budget)))
            async with client:
                resp = await client.get("/hello")
            stats = fp.stats()["armed"].get("http_client.request", {})
            budget_drawn = budget._tokens < 1.0  # noqa: SLF001
        finally:
            for f in faults:
                fp.disarm(f["point"])
            await runner.cleanup()
        return resp, hits["n"], stats, budget_drawn

    resp, upstream_hits, point_stats, budget_drawn = asyncio.run(go())
    injected = int(spec.get("expect_injected", 1))
    invariants = {
        "request_succeeded_after_retry": (
            [] if resp.ok else [f"final status {resp.status}"]),
        "faults_injected": (
            [] if point_stats.get("injected", 0) == injected else
            [f"injected={point_stats.get('injected')} expected {injected}"]),
        "upstream_hit_once_per_surviving_attempt": (
            [] if upstream_hits == 1 else
            [f"upstream saw {upstream_hits} hits, expected 1"]),
        "retry_budget_consumed": (
            [] if budget_drawn else
            ["the retry did not draw down the retry budget"]),
    }
    return _finish(spec["name"], "http_retry", seed, invariants,
                   {"status": resp.status, "injected": injected},
                   attempts=point_stats.get("hits"))


# ------------------------------------------------------------ db commit kind

def _run_db_commit_scenario(spec: dict) -> ScenarioResult:
    from ...modkit.db_engine import SqliteEngine

    seed = int(spec.get("seed", 0))
    fp.configure(seed)
    engine = SqliteEngine(":memory:")
    engine.execute("CREATE TABLE t (id TEXT PRIMARY KEY, v TEXT)")
    problems_atomic: list[str] = []
    faults = list(spec.get("faults", []))
    for f in faults:
        fp.arm(f["point"], f["spec"])
    raised = None
    try:
        engine.execute("INSERT INTO t (id, v) VALUES (?, ?)", ["a", "1"])
    except Exception as e:  # noqa: BLE001 — the injected commit failure
        raised = type(e).__name__
    finally:
        for f in faults:
            fp.disarm(f["point"])
    if raised is None:
        problems_atomic.append("injected commit fault did not surface")
    rows = engine.execute("SELECT * FROM t").rows
    if rows:
        problems_atomic.append(
            f"partial write survived the injected commit failure: {rows}")
    # the engine must recover once the fault clears
    engine.execute("INSERT INTO t (id, v) VALUES (?, ?)", ["b", "2"])
    rows = engine.execute("SELECT id FROM t ORDER BY id").rows
    recovered = ([] if [r["id"] for r in rows] == ["b"] else
                 [f"post-fault write landed wrong: {rows}"])
    engine.close()
    invariants = {"commit_fault_atomic": problems_atomic,
                  "engine_recovered": recovered}
    return _finish(spec["name"], "db_commit", seed, invariants,
                   {"raised": raised})


# -------------------------------------------------------- server-stack kinds

async def _boot_stack(modules: list[str], module_configs: dict,
                      auth_disabled: bool = True):
    """Boot a minimal in-process server stack (the test_oagw.py pattern):
    gateway + the requested modules over an in-memory DB. Auth is disabled
    by default; ``auth_disabled=False`` routes requests through the
    accept_all authn resolver instead, so the ``x-tenant-id`` header
    selects the tenant (the multi-tenant scenarios need per-request
    tenants — configure ``tenant_resolver``/``authn_resolver`` in
    ``module_configs``)."""
    from ...gateway.module import ApiGatewayModule
    from ...modkit import (AppConfig, ClientHub, ModuleRegistry, RunOptions)
    from ...modkit.db import DbManager
    from ...modkit.registry import Registration, _REGISTRATIONS
    from ...modkit.runtime import HostRuntime
    from ...modules.credstore import CredStoreModule
    from ...modules.llm_gateway import LlmGatewayModule
    from ...modules.model_registry import ModelRegistryModule
    from ...modules.monitoring import MonitoringModule
    from ...modules.oagw import OagwModule
    from ...modules.resolvers import (AuthnResolverModule,
                                      AuthzResolverModule,
                                      TenantResolverModule)
    from ...modules.serverless_runtime import ServerlessRuntimeModule

    available = {
        "credstore": Registration("credstore", CredStoreModule,
                                  ("tenant_resolver",), ("db", "rest")),
        "oagw": Registration("oagw", OagwModule, ("credstore",),
                             ("db", "rest")),
        "monitoring": Registration("monitoring", MonitoringModule, (),
                                   ("rest", "stateful")),
        "serverless_runtime": Registration(
            "serverless_runtime", ServerlessRuntimeModule, (),
            ("db", "rest", "stateful")),
        # the doctor scenarios drive the REAL serving path: registry-resolved
        # tiny model on the continuous scheduler behind /v1/completions
        "model_registry": Registration(
            "model_registry", ModelRegistryModule, ("tenant_resolver",),
            ("db", "rest")),
        "llm_gateway": Registration(
            "llm_gateway", LlmGatewayModule, ("model_registry",),
            ("rest", "stateful", "grpc", "db")),
        # multi-tenant scenarios: accept_all authn takes the tenant from
        # x-tenant-id (restricted to tenant_resolver's configured tree)
        "authn_resolver": Registration(
            "authn_resolver", AuthnResolverModule, ("tenant_resolver",),
            ("system",)),
        "authz_resolver": Registration(
            "authz_resolver", AuthzResolverModule, (), ("system",)),
    }
    regs = [
        Registration("api_gateway", ApiGatewayModule, (),
                     ("rest_host", "stateful", "system")),
        Registration("tenant_resolver", TenantResolverModule, (), ("system",)),
    ] + [available[m] for m in modules]
    saved = list(_REGISTRATIONS)
    _REGISTRATIONS.clear()
    cfg = AppConfig.load_or_default(environ={}, cli_overrides={"modules": {
        "api_gateway": {"config": {"bind_addr": "127.0.0.1:0",
                                   "auth_disabled": auth_disabled}},
        "tenant_resolver": {},
        **module_configs,
    }})
    registry = ModuleRegistry.discover_and_build(extra=regs)
    rt = HostRuntime(RunOptions(config=cfg, registry=registry,
                                client_hub=ClientHub(),
                                db_manager=DbManager(in_memory=True)))
    await rt.run_setup_phases()
    _REGISTRATIONS[:] = saved
    gw = registry.get("api_gateway").instance
    return rt, f"http://127.0.0.1:{gw.bound_port}"


async def _stop_stack(rt) -> None:
    try:
        oagw = rt.registry.get("oagw")
    except Exception:  # noqa: BLE001 — stack without oagw
        oagw = None
    if oagw is not None and getattr(oagw.instance, "service", None):
        await oagw.instance.service.close()
    rt.root_token.cancel()
    await rt.run_stop_phase()


async def arm_over_rest(session, base: str, name: str, spec: Any,
                        seed: Optional[int] = None) -> dict:
    """Arm a failpoint on a LIVE server over the guarded monitoring REST
    endpoint — the path a soak rehearsal (apps/load_rehearsal.py-style
    drivers) uses against a deployed gateway."""
    body: dict[str, Any] = {"spec": spec}
    if seed is not None:
        body["seed"] = seed
    async with session.put(f"{base}/v1/monitoring/failpoints/{name}",
                           json=body) as r:
        payload = await r.json()
        if r.status != 200:
            raise RuntimeError(f"arm over REST failed: {r.status} {payload}")
        return payload


async def _disarm_over_rest(session, base: str, name: str) -> None:
    async with session.delete(
            f"{base}/v1/monitoring/failpoints/{name}") as r:
        await r.read()


def _run_server_breaker_scenario(spec: dict) -> ScenarioResult:
    """oagw.upstream faults armed over REST trip the circuit breaker; after
    the open timeout and disarm, the breaker recovers through half-open."""
    seed = int(spec.get("seed", 0))

    async def go():
        import aiohttp
        from aiohttp import web

        hits = {"n": 0}

        async def hello(request):
            hits["n"] += 1
            return web.json_response({"ok": True})

        mock = web.Application()
        mock.router.add_route("*", "/api/hello", hello)
        mock_runner = web.AppRunner(mock)
        await mock_runner.setup()
        site = web.TCPSite(mock_runner, "127.0.0.1", 0)
        await site.start()
        mock_port = site._server.sockets[0].getsockname()[1]  # noqa: SLF001

        rt, base = await _boot_stack(
            ["credstore", "oagw", "monitoring"],
            {"credstore": {},
             "oagw": {"config": {"allow_insecure_http": True,
                                 "allow_private_upstreams": True}},
             "monitoring": {"config": {"allow_fault_injection": True}}})
        trace: list[str] = []
        open_timeout = 0.3
        try:
            async with aiohttp.ClientSession() as s:
                async with s.post(f"{base}/v1/oagw/upstreams", json={
                        "slug": "mockai",
                        "base_url": f"http://127.0.0.1:{mock_port}",
                        "circuit_breaker": {
                            "failure_threshold": 2,
                            "open_timeout_s": open_timeout}}) as r:
                    assert r.status == 201, await r.text()

                async def breaker_state() -> str:
                    async with s.get(f"{base}/v1/oagw/upstreams") as r:
                        body = await r.json()
                    return body["items"][0]["breaker_state"]

                async def proxy_once() -> int:
                    async with s.get(
                            f"{base}/v1/oagw/proxy/mockai/api/hello") as r:
                        await r.read()
                        return r.status

                trace.append(await breaker_state())       # closed
                await arm_over_rest(s, base, "oagw.upstream",
                                    spec.get("fault_spec",
                                             "2*raise(ClientError)"),
                                    seed=seed)
                statuses = [await proxy_once() for _ in range(2)]
                trace.append(await breaker_state())       # open
                hits_before = hits["n"]
                open_status = await proxy_once()          # rejected w/o a hit
                short_circuited = hits["n"] == hits_before
                await _disarm_over_rest(s, base, "oagw.upstream")
                await asyncio.sleep(open_timeout + 0.1)
                recovery_status = await proxy_once()      # half-open probe ok
                trace.append(await breaker_state())       # closed again
                # fault counters visible on /metrics (the exporter leg)
                async with s.get(f"{base}/metrics") as r:
                    metrics_text = await r.text()
        finally:
            await _stop_stack(rt)
            await mock_runner.cleanup()
        return (trace, statuses, open_status, short_circuited,
                recovery_status, metrics_text)

    (trace, statuses, open_status, short_circuited, recovery_status,
     metrics_text) = asyncio.run(go())
    invariants = {
        "breaker_recovered": run_checkers(
            ["breaker_recovered"], {"breaker_trace": trace}
        )["breaker_recovered"],
        "injected_faults_seen_as_5xx": (
            [] if all(s >= 500 for s in statuses) else
            [f"fault statuses {statuses}"]),
        "open_state_short_circuits": (
            [] if (open_status == 503 and short_circuited) else
            [f"open status {open_status}, short_circuited={short_circuited}"]),
        "recovered_request_ok": (
            [] if recovery_status == 200 else [f"status {recovery_status}"]),
        "fault_metric_exported": (
            [] if "fault_injected_total" in metrics_text else
            ["fault_injected_total missing from /metrics"]),
    }
    return _finish(spec["name"], "server", seed, invariants,
                   {"trace": trace, "statuses": statuses})


def _run_server_gateway_scenario(spec: dict) -> ScenarioResult:
    """gateway.request armed over REST: one request 5xxs through the
    error-mapping layer, the next succeeds; disabled deployments 403 the
    arming endpoint (the guard)."""
    seed = int(spec.get("seed", 0))

    async def go():
        import aiohttp

        rt, base = await _boot_stack(
            ["monitoring"],
            {"monitoring": {"config": {"allow_fault_injection": True}}})
        try:
            async with aiohttp.ClientSession() as s:
                await arm_over_rest(s, base, "gateway.request", "1*raise",
                                    seed=seed)
                async with s.get(f"{base}/health") as r:
                    faulted_status = r.status
                    faulted_body = await r.json()
                async with s.get(f"{base}/health") as r:
                    ok_status = r.status
                async with s.get(
                        f"{base}/v1/monitoring/failpoints") as r:
                    listing = await r.json()
                # lockout-proofing: even an ALWAYS-raise on gateway.request
                # must leave the failpoint control plane reachable, or a
                # remote rehearsal could never recover the server
                await arm_over_rest(s, base, "gateway.request", "raise")
                async with s.get(f"{base}/health") as r:
                    always_status = r.status
                await _disarm_over_rest(s, base, "gateway.request")
                async with s.get(f"{base}/health") as r:
                    recovered_status = r.status
        finally:
            await _stop_stack(rt)

        # guard leg: a stack WITHOUT allow_fault_injection must 403 arming
        rt2, base2 = await _boot_stack(["monitoring"], {"monitoring": {}})
        try:
            async with aiohttp.ClientSession() as s:
                async with s.put(
                        f"{base2}/v1/monitoring/failpoints/gateway.request",
                        json={"spec": "raise"}) as r:
                    guard_status = r.status
        finally:
            await _stop_stack(rt2)
        return (faulted_status, faulted_body, ok_status, listing,
                always_status, recovered_status, guard_status)

    (faulted_status, faulted_body, ok_status, listing, always_status,
     recovered_status, guard_status) = asyncio.run(go())
    invariants = {
        "injected_fault_maps_to_rfc9457_5xx": (
            [] if (faulted_status == 500
                   and faulted_body.get("status") == 500) else
            [f"got {faulted_status}: {faulted_body}"]),
        "next_request_healthy": (
            [] if ok_status == 200 else [f"status {ok_status}"]),
        "catalog_listed": (
            [] if "gateway.request" in (listing.get("catalog") or {}) else
            ["catalog missing gateway.request"]),
        "control_plane_survives_always_raise": (
            [] if (always_status == 500 and recovered_status == 200) else
            [f"always-armed health={always_status}, after disarm="
             f"{recovered_status} (disarm endpoint must stay reachable)"]),
        "arming_guarded_when_disabled": (
            [] if guard_status == 403 else [f"guard returned {guard_status}"]),
    }
    return _finish(spec["name"], "server", seed, invariants,
                   {"faulted_status": faulted_status,
                    "guard_status": guard_status})


def _run_serverless_scenario(spec: dict) -> ScenarioResult:
    """serverless.invoke faults drive retry/backoff into completion or
    dead-letter; serverless.tick faults must not kill the schedule loop."""
    seed = int(spec.get("seed", 0))

    async def go():
        import aiohttp

        rt, base = await _boot_stack(["serverless_runtime"],
                                     {"serverless_runtime": {}})
        svc = rt.registry.get("serverless_runtime").instance.service
        out: dict[str, Any] = {}
        try:
            async with aiohttp.ClientSession() as s:
                async def ep(name: str, retry: dict) -> None:
                    async with s.post(f"{base}/v1/serverless/entrypoints",
                                      json={"name": name, "kind": "function",
                                            "definition": {"function": "echo"},
                                            "retry_policy": retry}) as r:
                        assert r.status == 201, await r.text()
                    async with s.post(
                            f"{base}/v1/serverless/entrypoints/{name}/status",
                            json={"action": "activate"}) as r:
                        assert r.status == 200, await r.text()

                async def invoke(name: str) -> dict:
                    async with s.post(f"{base}/v1/serverless/invocations",
                                      json={"entrypoint": name,
                                            "params": {"x": 1}}) as r:
                        return (await r.json())["record"]

                await ep("flaky", {"max_attempts": 3,
                                   "backoff_seconds": 0.01})
                fp.configure(seed)
                fp.arm("serverless.invoke", "2*raise")
                rec = await invoke("flaky")
                fp.disarm("serverless.invoke")
                out["retried"] = rec

                await ep("doomed", {"max_attempts": 2,
                                    "backoff_seconds": 0.01})
                fp.arm("serverless.invoke", "raise")
                rec = await invoke("doomed")
                fp.disarm("serverless.invoke")
                out["dead_letter"] = rec

                # tick resilience: one failing tick, then the loop must
                # still fire a due schedule
                fp.arm("serverless.tick", "1*raise")
                try:
                    async with s.post(f"{base}/v1/serverless/schedules",
                                      json={"entrypoint": "flaky",
                                            "every_seconds": 0.1}) as r:
                        assert r.status == 201, await r.text()
                    for _ in range(40):
                        await asyncio.sleep(0.1)
                        async with s.get(
                                f"{base}/v1/serverless/invocations") as r:
                            items = (await r.json())["items"]
                        fired = [i for i in items
                                 if i["entrypoint_name"] == "flaky"
                                 and i["mode"] == "async"]
                        if fired:
                            break
                    # snapshot while STILL ARMED — stats()["armed"] drops a
                    # point at disarm, and the invariant below needs proof
                    # the tick fault actually fired
                    out["tick_stats"] = dict(
                        fp.stats()["armed"].get("serverless.tick", {}))
                finally:
                    fp.disarm("serverless.tick")
                out["schedule_fired"] = len(fired)
        finally:
            await _stop_stack(rt)
        return out

    out = asyncio.run(go())
    retried, dead = out["retried"], out["dead_letter"]
    dead_events = [e["event"] for e in dead.get("timeline", [])]
    invariants = {
        "retry_recovers": (
            [] if (retried["status"] == "completed"
                   and retried["attempt"] == 3) else
            [f"status={retried['status']} attempt={retried['attempt']}"]),
        "dead_letter_after_budget": (
            [] if (dead["status"] == "failed"
                   and "dead_letter" in dead_events) else
            [f"status={dead['status']} events={dead_events}"]),
        "tick_loop_survives": (
            [] if out["schedule_fired"] >= 1 else
            ["schedule never fired after the failing tick"]),
        "tick_fault_injected": (
            [] if out["tick_stats"].get("injected", 0) >= 1 else
            [f"tick fault never fired: {out['tick_stats']}"]),
    }
    return _finish(spec["name"], "serverless", seed, invariants,
                   {"retried_attempts": retried["attempt"],
                    "dead_events": dead_events})


# --------------------------------------------------------------- worker kind

def _run_worker_scenario(spec: dict) -> ScenarioResult:
    """llm_gateway.worker_stream crash at the job boundary: the armed call
    dies before the engine sees it; the next call streams normally."""
    seed = int(spec.get("seed", 0))

    async def go():
        from ...modules.llm_gateway.worker import LocalTpuWorker
        from ...modules.sdk import ModelInfo

        worker = LocalTpuWorker({})
        model = ModelInfo(
            canonical_id="local::faultlab-tiny", provider_slug="local",
            provider_model_id="faultlab-tiny",
            engine_options={"model_config": "tiny-llama", "max_seq_len": 64,
                            "max_batch": 2, "decode_chunk": 4})
        fp.configure(seed)
        fp.arm("llm_gateway.worker_stream", "1*raise")
        crashed = None
        try:
            try:
                async for _chunk in worker.completion_stream(
                        model, "hi", {"max_tokens": 4}):
                    pass
            except RuntimeError as e:
                crashed = str(e)
        finally:
            fp.disarm("llm_gateway.worker_stream")
        text = []
        finish = None
        async for chunk in worker.completion_stream(
                model, "hi", {"max_tokens": 4}):
            if chunk.text:
                text.append(chunk.text)
            if chunk.finish_reason:
                finish = chunk.finish_reason
        entry = next(iter(worker._entries.values()))
        sched = entry.scheduler
        # the terminal chunk reaches this coroutine from the emit callback
        # BEFORE the scheduler thread finishes the round's slot teardown,
        # so a single instantaneous read races thread scheduling — poll
        # briefly; a real leak stays leaked and still fails the invariant
        clean = False
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            clean = (len(sched._free_slots) == sched.n_slots
                     and not sched._pending.qsize())
            if clean:
                break
            await asyncio.sleep(0.05)
        sched.shutdown()
        return crashed, finish, clean

    crashed, finish, clean = asyncio.run(go())
    invariants = {
        "job_crashed_at_boundary": (
            [] if crashed and "llm_gateway.worker_stream" in crashed else
            [f"no injected crash surfaced ({crashed!r})"]),
        "next_job_streams": (
            [] if finish in ("stop", "length") else
            [f"finish_reason={finish!r}"]),
        "engine_accounting": (
            [] if clean else ["slots/pending leaked after the crashed job"]),
    }
    return _finish(spec["name"], "worker", seed, invariants,
                   {"finish": finish})


# ----------------------------------------------------- doctor: slo_burn kind

def _run_slo_burn_scenario(spec: dict) -> ScenarioResult:
    """The acceptance-cycle scenario: a delay failpoint on
    ``scheduler.readback`` (armed over the guarded REST control plane, like
    a live rehearsal) blows the itl objective's burn rate on a REAL server
    — gateway → llm_gateway → continuous scheduler — and the fabric-doctor
    drives the full healthy → degraded → shedding → recovering → healthy
    cycle:

    - ``/readyz`` flips 200 → 503 (reasons naming the violated objective)
      → 200;
    - while shedding, a new request is rejected PRE-enqueue with
      ``llm.load_shed`` 429 + Retry-After;
    - streams already in flight when the state flips complete
      bit-identically to the unfaulted baseline (the delay changes only
      latency — greedy tokens are invariant);
    - once the burn subsides (windows drain), a clean request serves again
      and reproduces the baseline text.
    """
    seed = int(spec.get("seed", 0))
    delay_spec = spec.get("delay_spec", "delay(0.5)")
    itl_threshold_ms = float(spec.get("itl_threshold_ms", 30.0))

    async def go():
        import aiohttp

        doctor_cfg = {
            # tight windows/hysteresis so the cycle completes in seconds;
            # production defaults are 60s/1800s — the MATH is identical
            "eval_interval_s": 0.1, "fast_window_s": 2.0,
            "slow_window_s": 4.0, "min_samples": 3,
            "shed_after": 2, "recover_after": 2, "shed_retry_after_s": 1.0,
            # only the itl objective is under test: on a loaded CPU a cold
            # compile or a queued request burns the shipped ttft and queue
            # objectives before the fault is armed, so those are out of reach
            "objectives": {"itl_p99": {"threshold_ms": itl_threshold_ms},
                           "ttft_p95": {"threshold_ms": 120000.0},
                           "queue_wait_p95": {"threshold_ms": 120000.0}},
            # watchdogs quiet — this scenario is the SLO leg (the stall
            # scenario owns the watchdog leg)
            "stream_stall_s": 120.0, "round_stall_floor_s": 120.0,
            "queue_deadline_s": 120.0,
        }
        rt, base = await _boot_stack(
            ["monitoring", "model_registry", "llm_gateway"],
            {"model_registry": {"config": {"models": [{
                "provider_slug": "local", "provider_model_id": "tiny-llama",
                "approval_state": "approved", "managed": True,
                "architecture": "llama",
                "engine_options": {"model_config": "tiny-llama",
                                   "max_seq_len": 128, "max_batch": 4,
                                   "decode_chunk": 8}}]}},
             "llm_gateway": {},
             "monitoring": {"config": {"allow_fault_injection": True,
                                       "doctor": doctor_cfg}}})
        out: dict[str, Any] = {}
        try:
            async with aiohttp.ClientSession() as s:
                async def completion(prompt: str, max_tokens: int = 24):
                    async with s.post(f"{base}/v1/completions", json={
                            "model": "local::tiny-llama", "prompt": prompt,
                            "max_tokens": max_tokens}) as r:
                        body = await r.json()
                        return r.status, dict(r.headers), body

                async def readyz() -> tuple[int, dict]:
                    async with s.get(f"{base}/readyz") as r:
                        return r.status, await r.json()

                async def slo_state() -> dict:
                    async with s.get(f"{base}/v1/monitoring/slo") as r:
                        return await r.json()

                def text_of(body: dict) -> str:
                    return "".join(p.get("text", "")
                                   for p in body.get("content", []))

                prompts = [f"slo burn probe {seed} {i}" for i in range(4)]
                await completion("warmup compile", 8)  # compile outside phases

                # phase A — healthy baseline
                baseline = [await completion(p) for p in prompts]
                out["baseline_status"] = [st for st, _, _ in baseline]
                base_texts = [text_of(b) for _, _, b in baseline]
                out["readyz_healthy"], _ = await readyz()

                # phase B — arm the burn over the guarded control plane,
                # then keep streams in flight while the state machine flips:
                # a second wave submitted WITH the first passes the gate
                # while the state is still healthy (no faulted request has
                # finished yet) and queues behind it for the four slots.
                # Submitted after the first wave's answers, it raced the
                # doctor, which sheds 0.2 s after those four samples land
                await arm_over_rest(s, base, "scheduler.readback",
                                    delay_spec, seed=seed)
                first = [asyncio.ensure_future(completion(p))
                         for p in prompts]
                inflight = [asyncio.ensure_future(completion(p))
                            for p in prompts]
                first_wave = await asyncio.gather(*first)
                out["first_wave_status"] = [st for st, _, _ in first_wave]
                shed_status, shed_doc = None, {}
                deadline = time.monotonic() + 30.0
                while time.monotonic() < deadline:
                    st, doc = await readyz()
                    if st == 503:
                        shed_status, shed_doc = st, doc
                        break
                    await asyncio.sleep(0.1)
                out["readyz_shedding"] = shed_status
                out["shed_reasons"] = shed_doc.get("reasons", [])
                # pre-enqueue rejection while shedding
                st, headers, body = await completion(prompts[0])
                out["shed_probe"] = {
                    "status": st, "code": body.get("code"),
                    "retry_after": headers.get("Retry-After")}
                done = await asyncio.gather(*inflight)
                out["inflight_status"] = [st for st, _, _ in done]
                out["inflight_texts_match"] = (
                    [text_of(b) for _, _, b in done] == base_texts)

                # phase C — disarm; the windows drain and the machine walks
                # shedding → recovering → healthy
                await _disarm_over_rest(s, base, "scheduler.readback")
                recovered_status = None
                deadline = time.monotonic() + 30.0
                while time.monotonic() < deadline:
                    st, _doc = await readyz()
                    if st == 200:
                        recovered_status = st
                        break
                    await asyncio.sleep(0.2)
                out["readyz_recovered"] = recovered_status
                deadline = time.monotonic() + 15.0
                while time.monotonic() < deadline:
                    doc = await slo_state()
                    if doc.get("state") == "healthy":
                        break
                    await asyncio.sleep(0.2)
                st, _, body = await completion(prompts[0])
                out["clean_after"] = {"status": st,
                                      "text_matches": text_of(body)
                                      == base_texts[0]}
                final = await slo_state()
                out["state_sequence"] = ["healthy"] + [
                    h["to"] for h in final.get("state_history", [])]
                out["final_state"] = final.get("state")
        finally:
            # the global doctor/recorder outlive this stack — leave them
            # healthy for whoever runs next in this process
            from ...modkit.doctor import DoctorConfig, default_doctor

            await _stop_stack(rt)
            default_doctor.stop()  # the next monitoring boot restarts it
            default_doctor.configure(DoctorConfig())
        return out

    out = asyncio.run(go())
    shed_probe = out.get("shed_probe", {})
    invariants = {
        "state_sequence": run_checkers(
            ["state_sequence"],
            {"state_sequence": out.get("state_sequence", [])},
        )["state_sequence"],
        "readyz_cycle_200_503_200": (
            [] if (out.get("readyz_healthy") == 200
                   and out.get("readyz_shedding") == 503
                   and out.get("readyz_recovered") == 200) else
            [f"readyz {out.get('readyz_healthy')} → "
             f"{out.get('readyz_shedding')} → {out.get('readyz_recovered')}"]),
        "readyz_names_violated_objective": (
            [] if any("itl_p99" in r for r in out.get("shed_reasons", []))
            else [f"503 reasons {out.get('shed_reasons')} do not name "
                  "the burning objective"]),
        "shed_rejects_pre_enqueue_with_retry_after": (
            [] if (shed_probe.get("status") == 429
                   and shed_probe.get("code") == "load_shed"
                   and shed_probe.get("retry_after")) else
            [f"shed probe {shed_probe}"]),
        "inflight_streams_bit_identical": (
            [] if (out.get("inflight_status") == [200] * 4
                   and out.get("inflight_texts_match")) else
            [f"in-flight statuses {out.get('inflight_status')}, "
             f"texts_match={out.get('inflight_texts_match')}"]),
        "recovered_request_matches_baseline": (
            [] if (out.get("clean_after", {}).get("status") == 200
                   and out.get("clean_after", {}).get("text_matches")) else
            [f"post-recovery probe {out.get('clean_after')}"]),
    }
    # state_sequence stays OUT of the fingerprint: the checker tolerates
    # hysteresis bounces at window edges (timing, not seed), so hashing the
    # raw walk would make same-seed fingerprints flaky. The checker verdict
    # (folded into the fingerprint) already pins the required order.
    return _finish(spec["name"], "slo_burn", seed, invariants,
                   {"readyz": [out.get("readyz_healthy"),
                               out.get("readyz_shedding"),
                               out.get("readyz_recovered")],
                    "shed_probe": {k: shed_probe.get(k)
                                   for k in ("status", "code")}},
                   state_sequence=out.get("state_sequence"),
                   final_state=out.get("final_state"))


# -------------------------------------------------------- doctor: stall kind

def _run_stall_scenario(spec: dict) -> ScenarioResult:
    """The watchdog leg: a delay on every ``scheduler.readback`` makes each
    round glacial without changing a single token. A scenario-local Doctor
    with tight stall thresholds must trip all three watchdogs
    (scheduler_round, stream_stall, queue_age) while the storm runs, mark
    the stalled streams in the flight recorder (the ``?stalled=true`` triage
    view), and walk back to healthy once the storm drains — with every
    stream bit-identical to the unfaulted baseline."""
    from ...modkit.doctor import Doctor, DoctorConfig
    from ...modkit.flight_recorder import default_recorder
    from ...runtime.engine import SamplingParams
    from ...runtime.scheduler import ContinuousBatchingEngine

    seed = int(spec.get("seed", 0))
    cfg = _engine_config(spec)
    load = _make_load(spec)
    checkers = list(spec.get("invariants", ["exactly_one_terminal"]))
    evidence: dict[str, Any] = {"expect_error": spec.get("expect_error", []),
                                "expect_watchdogs":
                                    spec.get("expect_watchdogs", []),
                                "expect_state_sequence":
                                    spec.get("expect_state_sequence")}
    if "streams_match_baseline" in checkers:
        evidence["baseline"] = _baseline_streams(spec, cfg, load)
    fp.configure(seed)
    # leftover live records from earlier runs in this process would read as
    # ancient stalled streams — the watchdogs must judge THIS storm only
    default_recorder.reset()
    doctor = Doctor(DoctorConfig(
        eval_interval_s=0.05,
        min_samples=10 ** 6,  # SLO leg quiet — this is the watchdog leg
        stream_stall_s=0.12, round_stall_mult=0.25, round_stall_floor_s=0.12,
        queue_deadline_s=0.15, watchdog_cooldown_s=0.1,
        shed_after=10 ** 6,  # watchdog trips degrade; only burn rates shed
        recover_after=2))
    doctor.attach_recorder()  # scenario-local: no ensure_started() thread

    engine = ContinuousBatchingEngine(cfg, seed=0)
    doctor.set_scheduler_provider(lambda: [("tiny-llama", engine)])
    streams = {i: StreamRecord() for i in range(len(load))}
    done = threading.Event()
    lock = threading.Lock()
    remaining = [len(load)]

    def mk_emit(i):
        def emit(ev):
            with lock:
                was_finished = streams[i].finished
                record_event(streams[i], ev.token_id, ev.finished)
                if ev.finished and not was_finished:
                    remaining[0] -= 1
                    if remaining[0] == 0:
                        done.set()
        return emit

    faults = list(spec.get("faults", []))
    stalled_rows_seen = False
    for f in faults:
        fp.arm(f["point"], f["spec"])
    try:
        for i, (prompt, max_tokens) in enumerate(load):
            engine.submit(prompt, SamplingParams(max_tokens=max_tokens),
                          mk_emit(i), request_id=f"stall-{seed}-{i}")
        deadline = time.monotonic() + _DRAIN_TIMEOUT_S
        while not done.is_set() and time.monotonic() < deadline:
            doctor.evaluate()
            if not stalled_rows_seen:
                stalled_rows_seen = bool(
                    default_recorder.inflight(stalled_only=True))
            time.sleep(0.05)  # fabric-lint: waive AS01 reason=scenario driver thread pacing doctor evals; no event loop in this process path
    finally:
        for f in faults:
            fp.disarm(f["point"])
        doctor.detach_recorder()
    # storm over: the watchdogs fall silent and the machine must walk home
    deadline = time.monotonic() + 5.0
    while doctor.state != "healthy" and time.monotonic() < deadline:
        doctor.evaluate()
        time.sleep(0.05)  # fabric-lint: waive AS01 reason=scenario driver thread pacing doctor evals; no event loop in this process path
    report = doctor.report()
    stats = engine.stats()
    engine.shutdown()
    evidence["streams"] = streams
    evidence["engine"] = engine
    evidence["watchdog_trips"] = report["watchdog_trips"]
    evidence["state_sequence"] = doctor.state_sequence()
    invariants = run_checkers(checkers, evidence)
    invariants["stalled_streams_marked"] = (
        [] if stalled_rows_seen else
        ["no live row ever showed stalled=true while the storm ran"])
    invariants["recovered_to_healthy"] = (
        [] if doctor.state == "healthy" else
        [f"final state {doctor.state!r}"])
    tripped = {name: bool(report["watchdog_trips"].get(name))
               for name in spec.get("expect_watchdogs", ())}
    return _finish(spec["name"], "stall", seed, invariants,
                   {"streams": _streams_payload(streams, tokens=True),
                    "tripped": tripped,
                    "final_state": doctor.state},
                   stats={k: stats[k] for k in
                          ("requests_completed", "tokens_emitted", "broken")})


# ------------------------------------------------------------ grpc evict kind

def _run_grpc_evict_scenario(spec: dict) -> ScenarioResult:
    from ...modules.grpc_hub import GrpcHubModule

    seed = int(spec.get("seed", 0))
    fp.configure(seed)
    hub = GrpcHubModule()
    fp.arm("grpc_hub.evict", "1*raise")
    raised = False
    try:
        try:
            hub._evict_tick()
        except RuntimeError:
            raised = True  # the loop's except-and-log path would swallow this
        # next tick must work — the eviction loop survives a failing tick
        hub._evict_tick()
    finally:
        fp.disarm("grpc_hub.evict")
    invariants = {
        "tick_fault_injected": ([] if raised else ["fault did not fire"]),
        "next_tick_survives": [],
    }
    return _finish(spec["name"], "grpc_evict", seed, invariants,
                   {"raised": raised})


# ----------------------------------------- federation: worker_host_crash kind

def _run_worker_host_crash_scenario(spec: dict) -> ScenarioResult:
    """Cross-host federation under a real host death: two REAL worker
    subprocesses (serve-mode ``python -m ...llm_gateway.worker``) announce
    to an in-process WorkerRegistry over loopback gRPC, a
    FederatedServingPool routes to them, and one host is SIGKILLed
    mid-stream. Proves, end to end across process boundaries:

    - an armed ``federation.route`` failpoint rejects the request as a
      typed 503 (replica_unavailable) before any host is dialed;
    - repeated-prefix requests land on the host already holding the prefix
      (gossiped digest chains → routing reason ``prefix``);
    - the SIGKILLed stream fails over to the survivor and the delivered
      text is BIT-IDENTICAL to an in-process single-worker baseline, with
      exactly one terminal;
    - the corpse leaves the registry within one lease window (the crash
      report evicts immediately; the lease sweep is the backstop), so lost
      host = lost capacity is visible to the doctor.

    The fingerprint hashes only the delivered texts + terminal reasons —
    hosts, pids, and timing stay out of it (seed-stable across repeats).
    """
    import os
    import signal
    import subprocess
    import sys

    from ...modkit.errors import ProblemError
    from ...modkit.flight_recorder import default_recorder
    from ...modkit.transport_grpc import JsonGrpcServer
    from ...modules.grpc_hub import register_worker_registry_service
    from ...modules.llm_gateway.grpc_service import (GrpcLlmWorkerClient,
                                                     model_ref_dict)
    from ...modules.llm_gateway.worker import LocalTpuWorker
    from ...modules.sdk import ChatStreamChunk, ModelInfo
    from ...runtime.federation import (FederatedServingPool, FederationConfig,
                                       WorkerRegistry, digest_chain)

    from ...ops.platform import require_cpu

    require_cpu("faultlab worker_host_crash")
    seed = int(spec.get("seed", 0))
    lease_ttl_s = float(spec.get("lease_ttl_s", 2.0))
    max_tokens = int((spec.get("load") or {}).get("max_tokens", 16))
    model = ModelInfo(
        canonical_id="local::faultlab-tiny", provider_slug="local",
        provider_model_id="faultlab-tiny", managed=True, architecture="llama",
        engine_options={"model_config": "tiny-llama", "max_seq_len": 192,
                        "max_batch": 2, "decode_chunk": 4})
    model_key = model.canonical_id
    # each prompt must span >= 2 digest blocks (48 chars) so the gossiped
    # chain carries a usable prefix hint
    prompt_a = f"federated prefix probe seed {seed} " * 4
    prompt_b = f"federated crash victim seed {seed} " * 4
    faults = list(spec.get("faults", []))

    async def baseline(prompt: str) -> tuple[str, Optional[str]]:
        worker = LocalTpuWorker({})
        text, finish = [], None
        try:
            async for chunk in worker.completion_stream(
                    model, prompt, {"max_tokens": max_tokens}):
                text.append(chunk.text or "")
                if chunk.finish_reason:
                    finish = chunk.finish_reason
        finally:
            for entry in worker._entries.values():
                entry.scheduler.shutdown()
        return "".join(text), finish

    async def read_ready(proc, timeout_s: float = 240.0) -> dict:
        loop = asyncio.get_running_loop()
        line = await asyncio.wait_for(
            loop.run_in_executor(None, proc.stdout.readline), timeout_s)
        if not line:
            raise RuntimeError("worker died before READY "
                               f"(rc={proc.poll()})")
        return json.loads(line)

    async def go() -> dict[str, Any]:
        out: dict[str, Any] = {}
        fp.configure(seed)
        default_recorder.reset()
        # the gateway-side half: registry + its gRPC service on loopback
        registry = WorkerRegistry(lease_ttl_s=lease_ttl_s)
        server = JsonGrpcServer()
        register_worker_registry_service(server, registry)
        port = await server.start("127.0.0.1:0")
        procs: list[subprocess.Popen] = []
        ready: list[dict] = []
        pool = FederatedServingPool(
            registry,
            lambda w: GrpcLlmWorkerClient(endpoint=w.endpoint),
            ChatStreamChunk,
            FederationConfig(seed=seed, failover_backoff_s=0.01))

        async def drive(prompt: str, rid: str,
                        kill_after: Optional[int] = None) -> dict[str, Any]:
            """Stream one federated completion; optionally SIGKILL the
            serving host once ``kill_after`` text chunks arrived."""
            text, finishes, killed_host = [], [], None
            async for chunk in pool.completion_stream(
                    model, prompt, {"max_tokens": max_tokens,
                                    "_request_id": rid}):
                if chunk.text:
                    text.append(chunk.text)
                if chunk.finish_reason:
                    finishes.append(chunk.finish_reason)
                if kill_after is not None and killed_host is None \
                        and len(text) >= kill_after:
                    rec = default_recorder.lookup(rid) or {}
                    killed_host = rec.get("worker_host")
                    victim = next((r for r in ready
                                   if r["host"] == killed_host), None)
                    if victim is not None:
                        os.kill(victim["pid"], signal.SIGKILL)
            return {"text": "".join(text), "finishes": finishes,
                    "killed_host": killed_host}

        try:
            loop = asyncio.get_running_loop()
            for i in range(2):
                cfg_json = json.dumps({
                    "hub_endpoint": f"127.0.0.1:{port}",
                    "host": f"worker-{i}", "worker": {},
                    "models": [model_ref_dict(model)],
                    "heartbeat_interval_s": 0.25})

                def spawn(cfg: str = cfg_json) -> subprocess.Popen:
                    return subprocess.Popen(
                        [sys.executable, "-m",
                         "cyberfabric_core_tpu.modules.llm_gateway.worker"],
                        env={**os.environ, "JAX_PLATFORMS": "cpu",
                             "FED_WORKER_CONFIG": cfg},
                        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                        text=True)

                procs.append(await loop.run_in_executor(None, spawn))
            ready.extend([await read_ready(p) for p in procs])
            out["hosts_announced"] = registry.healthy()

            # phase 0 — armed federation.route rejects BEFORE dialing any
            # host: the typed 503 surfaces, no worker sees the request
            for f in faults:
                fp.arm(f["point"], f["spec"])
            try:
                try:
                    async for _ in pool.completion_stream(
                            model, prompt_a,
                            {"max_tokens": 2,
                             "_request_id": f"fed-route-{seed}"}):
                        pass
                    out["route_fault"] = "no error surfaced"
                except ProblemError as e:
                    out["route_fault"] = e.problem.code
            finally:
                for f in faults:
                    fp.disarm(f["point"])

            # phase 1 — prefix affinity: serve prompt_a once, let the
            # serving host gossip its radix prefix (>= 2 heartbeats), then
            # the router must send the repeat to the SAME host for reason
            # ``prefix``
            first = await drive(prompt_a, f"fed-a-{seed}")
            out["first_stream"] = first
            first_host = (default_recorder.lookup(f"fed-a-{seed}")
                          or {}).get("worker_host")
            chain = digest_chain(prompt_a)
            deadline = time.monotonic() + 10.0
            hint = None
            while time.monotonic() < deadline:
                w, reason = pool.route(model_key, chain)
                if reason == "prefix":
                    hint = {"host": w.host, "reason": reason}
                    break
                await asyncio.sleep(0.25)
            out["prefix_hint"] = hint
            out["prefix_host_matches"] = bool(
                hint and first_host and hint["host"] == first_host)

            # phase 2 — SIGKILL the host mid-stream; the pool must fail
            # over to the survivor and deliver the baseline text exactly
            crash = await drive(prompt_b, f"fed-b-{seed}", kill_after=1)
            out["crash_stream"] = crash

            # phase 3 — the corpse leaves the registry within one lease
            # window (report_failure evicts at the failover; the lease
            # sweep below is the backstop the hub's evict tick runs)
            deadline = time.monotonic() + lease_ttl_s + 2.0
            while time.monotonic() < deadline and registry.healthy() > 1:
                registry.evict_expired()
                await asyncio.sleep(0.1)
            out["hosts_after_crash"] = registry.healthy()
            out["evicted"] = [
                {"host": e["host"], "reason": e["reason"]}
                for e in registry.rows()["evicted"]]

            # phase 4 — the survivor still serves, baseline-identical
            out["survivor_stream"] = await drive(prompt_a,
                                                 f"fed-c-{seed}")
        finally:
            await pool.close()
            for p in procs:
                if p.poll() is None:
                    p.kill()
                p.wait(timeout=30)
                if p.stdout is not None:
                    p.stdout.close()
            await server.stop()
        return out

    base_a_text, base_a_finish = asyncio.run(baseline(prompt_a))
    base_b_text, base_b_finish = asyncio.run(baseline(prompt_b))
    out = asyncio.run(go())

    first = out.get("first_stream") or {}
    crash = out.get("crash_stream") or {}
    survivor = out.get("survivor_stream") or {}
    invariants = {
        "both_hosts_announced": (
            [] if out.get("hosts_announced") == 2 else
            [f"{out.get('hosts_announced')} hosts in the registry"]),
        "route_fault_typed_503": (
            [] if out.get("route_fault") == "replica_unavailable" else
            [f"armed route fault surfaced as {out.get('route_fault')!r}"]),
        "prefix_routing": (
            [] if out.get("prefix_host_matches") else
            [f"repeat did not land on the prefix host: "
             f"{out.get('prefix_hint')}"]),
        "first_stream_matches_baseline": (
            [] if (first.get("text") == base_a_text
                   and first.get("finishes") == [base_a_finish]) else
            [f"first stream diverged: {first.get('finishes')}"]),
        "failover_stream_bit_identical": (
            [] if crash.get("text") == base_b_text else
            [f"crashed stream text diverged "
             f"({len(crash.get('text') or '')} vs {len(base_b_text)} chars)"]),
        "exactly_one_terminal": (
            [] if crash.get("finishes") == [base_b_finish] else
            [f"terminals {crash.get('finishes')} != [{base_b_finish}]"]),
        "host_was_killed_mid_stream": (
            [] if crash.get("killed_host") else
            ["never identified/killed the serving host"]),
        "corpse_evicted_within_lease": (
            [] if (out.get("hosts_after_crash") == 1
                   and any(e["reason"] in ("crash", "lease_expired")
                           for e in out.get("evicted", []))) else
            [f"hosts={out.get('hosts_after_crash')} "
             f"evicted={out.get('evicted')}"]),
        "survivor_serves_baseline": (
            [] if (survivor.get("text") == base_a_text
                   and survivor.get("finishes") == [base_a_finish]) else
            [f"survivor stream diverged: {survivor.get('finishes')}"]),
    }
    return _finish(
        spec["name"], "worker_host_crash", seed, invariants,
        {"texts": sorted([first.get("text", ""), crash.get("text", ""),
                          survivor.get("text", "")]),
         "finishes": sorted([str(first.get("finishes")),
                             str(crash.get("finishes")),
                             str(survivor.get("finishes"))]),
         "route_fault": out.get("route_fault")},
        evicted=out.get("evicted"), killed_host=crash.get("killed_host"))


def _run_fleet_doctor_shed_scenario(spec: dict) -> ScenarioResult:
    """fabric-fleetscope's acceptance cycle on a REAL federated stack: one
    gateway (grpc_hub + llm_gateway ``federation.enabled`` + monitoring)
    and TWO worker subprocesses on loopback, each running its own tight
    fabric-doctor that piggybacks reports on the heartbeat census. A
    ``scheduler.readback`` delay is armed ON one worker host over the
    guarded REST plane (``PUT /v1/monitoring/failpoints/{name}`` with a
    ``host`` body — the arm crosses the wire and fires in the WORKER
    process), and the fleet fold must tell the whole story:

    - prefix-affine traffic pins the burn to the armed host; its itl
      objective blows and ``GET /v1/monitoring/fleet`` marks the host
      ``degraded`` off nothing but heartbeats;
    - the router's health rung steers NEW requests to the healthy host
      (timelines prove the placement) while streams served under the delay
      stay bit-identical to the pre-arm baseline — the fault changes only
      latency, never tokens;
    - the gateway's own /readyz keeps its 200 (a sick WORKER host must not
      get the gateway mass-evicted) but carries the host-level reason;
    - disarming over REST drains the worker's windows and the fleet table
      walks the host back to ``healthy``, after which it serves the
      baseline again.

    The fingerprint hashes the delivered texts + the observed state edges —
    host names, pids, and timing stay out (which of the two hosts gets
    armed depends on routing, not on the seed alone).
    """
    import os
    import subprocess
    import sys

    from ... import modules  # noqa: F401 — registers every module
    from ...modkit import AppConfig, ClientHub, ModuleRegistry, RunOptions
    from ...modkit.db import DbManager
    from ...modkit.runtime import HostRuntime
    from ...modules.llm_gateway.grpc_service import model_ref_dict
    from ...modules.sdk import ModelInfo

    from ...ops.platform import require_cpu

    require_cpu("faultlab fleet_doctor_shed")
    seed = int(spec.get("seed", 0))
    lease_ttl_s = float(spec.get("lease_ttl_s", 4.0))
    delay_spec = spec.get("delay_spec", "delay(0.4)")
    itl_threshold_ms = float(spec.get("itl_threshold_ms", 30.0))
    max_tokens = int((spec.get("load") or {}).get("max_tokens", 8))

    # decode_chunk 2: itl_ms derives from gaps BETWEEN decode_chunk flight
    # events — at the default chunk of 8 an 8-token request has a single
    # event and the workers' itl objective never sees a sample
    engine_options = {"model_config": "tiny-llama", "max_seq_len": 256,
                      "max_batch": 4, "decode_chunk": 2}
    model = ModelInfo(
        canonical_id="local::tiny-llama", provider_slug="local",
        provider_model_id="tiny-llama", managed=True, architecture="llama",
        engine_options=engine_options)
    # >= 2 digest blocks so the armed host's gossiped prefix chain keeps
    # pulling the burn traffic back to IT (not the healthy host)
    prompt_burn = f"fleetscope burn probe seed {seed} " * 4
    prompt_probe = f"fleetscope steering probe seed {seed} " * 4

    #: the WORKER-side doctor: tight windows so the cycle completes in
    #: seconds. min_samples 1 because a faulted request outlasts the fast
    #: window (terminals arrive one per window at best); shed_after is high
    #: on purpose — the scenario proves the GATEWAY steers on ``degraded``,
    #: not that the worker self-sheds — and recover_after keeps the host
    #: degraded through the probe phase instead of flapping back
    worker_doctor = {
        "eval_interval_s": 0.1, "fast_window_s": 4.0, "slow_window_s": 8.0,
        "min_samples": 1, "shed_after": 1000, "recover_after": 40,
        # only the itl objective is under test — at min_samples 1 the
        # default ttft/queue/error objectives become hair-triggers (one
        # cold compile would degrade the HEALTHY host too), so pin them
        # untrippable
        "objectives": {"itl_p99": {"threshold_ms": itl_threshold_ms},
                       "ttft_p95": {"threshold_ms": 120000.0},
                       "queue_wait_p95": {"threshold_ms": 120000.0},
                       "error_rate": {"budget": 1.0}},
        "stream_stall_s": 120.0, "round_stall_floor_s": 120.0,
        "queue_deadline_s": 120.0,
    }
    config = {
        "modules": {
            "api_gateway": {"config": {"bind_addr": "127.0.0.1:0",
                                       "timeout_secs": 30.0}},
            "tenant_resolver": {"config": {"tenants": {
                "root": {}, "acme": {"parent": "root"}}}},
            "authn_resolver": {"config": {"mode": "accept_all",
                                          "default_tenant": "acme"}},
            "authz_resolver": {},
            "types_registry": {}, "types": {},
            "module_orchestrator": {},
            "nodes_registry": {"config": {"tenant": "acme"}},
            "model_registry": {"config": {
                "seed_tenant": "acme",
                "models": [{
                    "provider_slug": "local",
                    "provider_model_id": "tiny-llama",
                    "approval_state": "approved", "managed": True,
                    "architecture": "llama", "format": "safetensors",
                    "capabilities": {"chat": True, "streaming": True},
                    "limits": {"max_input_tokens": 200,
                               "max_output_tokens": 64},
                    "engine_options": engine_options}]}},
            "grpc_hub": {"config": {"bind_addr": "127.0.0.1:0",
                                    "worker_lease_ttl_s": lease_ttl_s,
                                    "eviction_interval_s": 0.5}},
            "llm_gateway": {"config": {"federation": {
                "enabled": True, "failover_backoff_s": 0.01,
                "seed": seed}}},
            # the GATEWAY doctor stays generous: only the armed WORKER's
            # doctor may degrade, so the fleet fold (not local burn) is
            # what the assertions read
            "monitoring": {"config": {
                "allow_fault_injection": True,
                "doctor": {
                    "objectives": {"ttft_p95": {"threshold_ms": 120000.0,
                                                "budget": 0.5}},
                    "stream_stall_s": 300.0, "round_stall_floor_s": 300.0,
                    "queue_deadline_s": 300.0, "shed_after": 1000}}},
        }
    }

    async def go() -> dict[str, Any]:
        import aiohttp

        out: dict[str, Any] = {}
        cfg = AppConfig.load_or_default(environ={}, cli_overrides=config)
        registry = ModuleRegistry.discover_and_build(
            enabled=cfg.module_names())
        opts = RunOptions(config=cfg, registry=registry,
                          client_hub=ClientHub(),
                          db_manager=DbManager(in_memory=True))
        rt = HostRuntime(opts)
        await rt.run_setup_phases()
        gw = registry.get("api_gateway").instance
        hub = registry.get("grpc_hub").instance
        base = f"http://127.0.0.1:{gw.bound_port}"
        procs: list[subprocess.Popen] = []
        loop = asyncio.get_running_loop()
        try:
            for i in range(2):
                cfg_json = json.dumps({
                    "hub_endpoint": hub.endpoint,
                    "host": f"fleet-{i}", "worker": {},
                    "observability": {"allow_fault_injection": True,
                                      "doctor": worker_doctor},
                    "models": [model_ref_dict(model)],
                    "heartbeat_interval_s": 0.25})

                def spawn(c: str = cfg_json) -> subprocess.Popen:
                    return subprocess.Popen(
                        [sys.executable, "-m",
                         "cyberfabric_core_tpu.modules.llm_gateway.worker"],
                        env={**os.environ, "JAX_PLATFORMS": "cpu",
                             "FED_WORKER_CONFIG": c},
                        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                        text=True)

                procs.append(await loop.run_in_executor(None, spawn))
            for p in procs:
                line = await asyncio.wait_for(
                    loop.run_in_executor(None, p.stdout.readline), 240.0)
                if not line:
                    raise RuntimeError("worker died before READY "
                                       f"(rc={p.poll()})")

            async with aiohttp.ClientSession() as s:
                async def completion(prompt: str, rid: str) -> str:
                    async with s.post(
                            f"{base}/v1/completions",
                            headers={"X-Request-Id": rid},
                            json={"model": model.canonical_id,
                                  "prompt": prompt,
                                  "max_tokens": max_tokens}) as r:
                        body = await r.json()
                        if r.status != 200:
                            raise RuntimeError(f"completion {r.status}: "
                                               f"{body}")
                        return body["content"][0]["text"]

                async def fleet(host: Optional[str] = None
                                ) -> tuple[int, dict]:
                    url = f"{base}/v1/monitoring/fleet"
                    if host:
                        url += f"?host={host}"
                    async with s.get(url) as r:
                        return r.status, await r.json()

                async def served_by(rid: str) -> Optional[str]:
                    async with s.get(
                            f"{base}/v1/monitoring/requests/{rid}") as r:
                        body = await r.json()
                        return body.get("worker_host") \
                            if r.status == 200 else None

                async def host_state(host: str) -> str:
                    st, doc = await fleet(host)
                    if st != 200 or not doc.get("hosts"):
                        return "unknown"
                    return str(doc["hosts"][0].get("state", "unknown"))

                # phase 0 — both hosts announce and the fleet fold sees
                # their heartbeat reports; unknown host is a typed 404
                deadline = time.monotonic() + 30.0
                while time.monotonic() < deadline:
                    st, doc = await fleet()
                    if st == 200 and doc.get("workers") == 2:
                        break
                    await asyncio.sleep(0.2)
                out["fleet_workers"] = doc.get("workers")
                out["federation_flag"] = doc.get("federation")
                st, problem = await fleet("no-such-host")
                out["unknown_host"] = {"status": st,
                                       "code": problem.get("code")}

                # phase 1 — warm BOTH hosts (cold-compile itl transients
                # must drain before any state edge counts), then baseline
                warm_hosts: set = set()
                for i in range(8):
                    rid = f"fls-warm-{seed}-{i}"
                    await completion(f"fleetscope warmup {seed} {i} " * 4,
                                     rid)
                    h = await served_by(rid)
                    if h:
                        warm_hosts.add(h)
                    if len(warm_hosts) == 2 and i >= 3:
                        break
                out["warmed_hosts"] = sorted(warm_hosts)
                base_burn = await completion(prompt_burn,
                                             f"fls-base-{seed}")
                base_probe = await completion(prompt_probe,
                                              f"fls-base2-{seed}")
                target = await served_by(f"fls-base-{seed}")
                out["target_found"] = bool(target)
                healthy = [h for h in ("fleet-0", "fleet-1")
                           if h != target][0]
                # let warmup transients age out of the 4s fast window so
                # the armed host is the ONLY one that can degrade
                deadline = time.monotonic() + 20.0
                while time.monotonic() < deadline:
                    states = [await host_state(h)
                              for h in ("fleet-0", "fleet-1")]
                    if states == ["healthy", "healthy"]:
                        break
                    await asyncio.sleep(0.25)
                out["pre_arm_states"] = states

                # phase 2 — arm the delay ON the target host over REST;
                # prefix affinity keeps pulling prompt_burn back to it
                async with s.put(
                        f"{base}/v1/monitoring/failpoints/"
                        "scheduler.readback",
                        json={"spec": delay_spec, "seed": seed,
                              "host": target}) as r:
                    out["armed"] = {"status": r.status,
                                    **(await r.json())}

                burn_texts: list[str] = []
                sick_state = None
                deadline = time.monotonic() + 90.0
                i = 0
                while time.monotonic() < deadline:
                    state = await host_state(target)
                    if state in ("degraded", "shedding"):
                        sick_state = state
                        break
                    burn_texts.append(await completion(
                        prompt_burn, f"fls-burn-{seed}-{i}"))
                    i += 1
                out["sick_state"] = sick_state
                out["burn_texts_match"] = all(t == base_burn
                                              for t in burn_texts)
                out["burn_requests"] = len(burn_texts)
                st, doc = await fleet()
                out["fleet_state"] = doc.get("state")
                out["fleet_reasons"] = doc.get("reasons")
                async with s.get(f"{base}/readyz") as r:
                    out["readyz"] = {"status": r.status,
                                     "reasons": (await r.json()
                                                 ).get("reasons", [])}

                # phase 3 — the health rung steers NEW requests off the
                # sick host (timelines prove it), tokens stay identical
                probe_hosts, probe_texts = [], []
                for i in range(3):
                    rid = f"fls-probe-{seed}-{i}"
                    probe_texts.append(await completion(prompt_probe, rid))
                    probe_hosts.append(await served_by(rid))
                out["probe_hosts"] = probe_hosts
                out["probes_avoid_sick"] = all(h == healthy
                                               for h in probe_hosts)
                out["probe_texts_match"] = all(t == base_probe
                                               for t in probe_texts)

                # the host-labeled rung is on the federated /metrics
                async with s.get(f"{base}/metrics") as r:
                    text = await r.text()
                out["host_labeled_metrics"] = (
                    f'llm_remote_workers_healthy{{host="{target}"}}' in text
                    and "llm_federated_placements_total" in text)

                # phase 4 — disarm over REST; the worker's windows drain
                # and the fleet table walks the host back to healthy
                async with s.delete(
                        f"{base}/v1/monitoring/failpoints/"
                        f"scheduler.readback?host={target}") as r:
                    out["disarmed"] = {"status": r.status,
                                       **(await r.json())}
                recovered = None
                deadline = time.monotonic() + 60.0
                while time.monotonic() < deadline:
                    state = await host_state(target)
                    if state == "healthy":
                        recovered = state
                        break
                    await asyncio.sleep(0.25)
                out["recovered_state"] = recovered
                out["final_text_matches"] = (await completion(
                    prompt_burn, f"fls-final-{seed}")) == base_burn
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                p.wait(timeout=30)
                if p.stdout is not None:
                    p.stdout.close()
            from ...modkit.doctor import DoctorConfig, default_doctor

            rt.root_token.cancel()
            await rt.run_stop_phase()
            default_doctor.stop()
            default_doctor.set_fleet_provider(None)
            default_doctor.configure(DoctorConfig())
        return out

    out = asyncio.run(go())
    invariants = {
        "fleet_endpoint_sees_both_hosts": (
            [] if (out.get("federation_flag") is True
                   and out.get("fleet_workers") == 2) else
            [f"workers={out.get('fleet_workers')} "
             f"federation={out.get('federation_flag')}"]),
        "unknown_host_is_typed_404": (
            [] if out.get("unknown_host") == {
                "status": 404, "code": "unknown_host"} else
            [f"?host=no-such-host → {out.get('unknown_host')}"]),
        "armed_over_rest_on_worker": (
            [] if (out.get("armed", {}).get("status") == 200
                   and out.get("armed", {}).get("host")) else
            [f"cross-host arm → {out.get('armed')}"]),
        "burn_marks_host_degraded": (
            [] if out.get("sick_state") in ("degraded", "shedding") else
            [f"armed host never degraded (state={out.get('sick_state')}, "
             f"{out.get('burn_requests')} burn requests)"]),
        "fleet_reasons_name_the_host": (
            [] if any("fleet-" in r for r in out.get("fleet_reasons", []))
            else [f"fleet reasons {out.get('fleet_reasons')}"]),
        "gateway_readyz_stays_200_with_reason": (
            [] if (out.get("readyz", {}).get("status") == 200
                   and any("fleet-" in r for r in
                           out.get("readyz", {}).get("reasons", []))) else
            [f"/readyz → {out.get('readyz')}"]),
        "routing_steers_to_healthy_host": (
            [] if out.get("probes_avoid_sick") else
            [f"probe hosts {out.get('probe_hosts')}"]),
        "streams_bit_identical_under_fault": (
            [] if (out.get("burn_texts_match")
                   and out.get("probe_texts_match")) else
            ["texts diverged under the armed delay"]),
        "host_labeled_metrics_exported": (
            [] if out.get("host_labeled_metrics") else
            ["llm_remote_workers_healthy{host=...} missing from /metrics"]),
        "disarm_walks_host_back_healthy": (
            [] if (out.get("disarmed", {}).get("status") == 200
                   and out.get("recovered_state") == "healthy") else
            [f"recovery: disarm={out.get('disarmed')} "
             f"state={out.get('recovered_state')}"]),
        "healthy_again_serves_baseline": (
            [] if out.get("final_text_matches") else
            ["post-recovery text diverged from baseline"]),
    }
    return _finish(
        spec["name"], "fleet_doctor_shed", seed, invariants,
        {"sick_state": out.get("sick_state"),
         "recovered_state": out.get("recovered_state"),
         "texts_match": [out.get("burn_texts_match"),
                         out.get("probe_texts_match"),
                         out.get("final_text_matches")],
         "unknown_host": out.get("unknown_host")},
        fleet_state=out.get("fleet_state"),
        burn_requests=out.get("burn_requests"))


# ------------------------------------------------------------------ dispatch

_KINDS = {
    "engine": _run_engine_scenario,
    "cancel_storm": _run_cancel_storm_scenario,
    "deadline": _run_deadline_scenario,
    "noisy_neighbor": _run_noisy_neighbor_scenario,
    "selective_shed": _run_selective_shed_scenario,
    "pool": _run_pool_scenario,
    "pd_pool": _run_pd_pool_scenario,
    "replica_crash_loop": _run_replica_crash_loop_scenario,
    "replica_drain": _run_replica_drain_scenario,
    "http_retry": _run_http_retry_scenario,
    "db_commit": _run_db_commit_scenario,
    "server_breaker": _run_server_breaker_scenario,
    "server_gateway": _run_server_gateway_scenario,
    "serverless": _run_serverless_scenario,
    "worker": _run_worker_scenario,
    "worker_host_crash": _run_worker_host_crash_scenario,
    "fleet_doctor_shed": _run_fleet_doctor_shed_scenario,
    "grpc_evict": _run_grpc_evict_scenario,
    "slo_burn": _run_slo_burn_scenario,
    "stall": _run_stall_scenario,
}


def run_scenario(spec: dict) -> ScenarioResult:
    """Run one scenario spec to a ScenarioResult. Failpoints are reset on
    entry and on exit — a scenario can never leak an armed fault."""
    kind = spec.get("kind", "engine")
    if kind not in _KINDS:
        raise ValueError(f"unknown scenario kind {kind!r}; "
                         f"known: {sorted(_KINDS)}")
    fp.reset()
    try:
        return _KINDS[kind](spec)
    finally:
        fp.reset()


def run_all(specs: Optional[list[dict]] = None,
            seed: Optional[int] = None) -> list[ScenarioResult]:
    from .scenarios import BUILTIN_SCENARIOS

    out = []
    for spec in (specs if specs is not None else BUILTIN_SCENARIOS):
        if seed is not None:
            spec = {**spec, "seed": seed}
        out.append(run_scenario(spec))
    return out
