"""Replica lifecycle supervision (runtime/lifecycle.py): the state machine
on fast fakes, the restartable engine close(), and the self-healing pool /
worker integration paths."""

import threading
import time

import numpy as np
import pytest

from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.lifecycle import (EngineSupervisor,
                                                    LifecycleConfig,
                                                    LifecycleStateError,
                                                    ReplicaLifecycleManager,
                                                    ReplicaUnavailable)
from cyberfabric_core_tpu.runtime.replicas import DataParallelServingPool
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine


# --------------------------------------------------------------- state fakes

class _FakeEngine:
    def __init__(self):
        self.broken = None
        self.closed = False
        self.load = dict(active=0, pending=0, prefilling=0, suspended=0)
        self.started = False

    def stats(self):
        return {"broken": self.broken, "closed": self.closed, **self.load}

    def start(self):
        self.started = True

    def close(self, timeout=0.0):
        self.closed = True

    def shutdown(self, timeout=0.0):
        pass


class _FakePool:
    def __init__(self, n, build=None):
        self.replicas = [_FakeEngine() for _ in range(n)]
        self.builds = 0
        self._build = build

    def build_replica(self, idx):
        self.builds += 1
        if self._build is not None:
            return self._build(idx)
        return _FakeEngine()


def _mgr(pool, **kw):
    kw.setdefault("check_interval_s", 0.01)
    kw.setdefault("rebuild_backoff_s", 0.005)
    kw.setdefault("rebuild_backoff_max_s", 0.02)
    kw.setdefault("probation_successes", 2)
    # the supervisor thread is NOT started: tests drive tick() directly
    return ReplicaLifecycleManager(pool, LifecycleConfig(**kw))


def _tick_until(mgr, predicate, timeout_s=2.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        mgr.tick()
        if predicate():
            return True
        time.sleep(0.005)
    return False


def test_break_quarantine_rebuild_probation_promote():
    pool = _FakePool(2)
    mgr = _mgr(pool)
    old = pool.replicas[0]
    old.broken = "device fault"
    mgr.tick()
    assert mgr.status_row(0)["state"] == "quarantined"
    assert mgr.status_row(0)["strikes"] == 1
    assert not mgr.admit_allowed(0) and mgr.admit_allowed(1)
    # backoff elapses → rebuild commits a fresh engine and enters probation
    assert _tick_until(mgr, lambda: mgr.status_row(0)["state"] == "probation")
    assert pool.replicas[0] is not old and pool.replicas[0].started
    assert old.closed, "the spent engine must be close()d before replacement"
    assert mgr.rebuilds_ok == 1
    # probation: canary budget gates admission, clean terminals promote
    assert mgr.admit_allowed(0) and mgr.canary_wanted(0)
    mgr.note_dispatch(0)
    assert not mgr.admit_allowed(0)  # probation_max_inflight=1
    mgr.on_terminal(0, ok=True)
    mgr.note_dispatch(0)
    mgr.on_terminal(0, ok=True)
    assert mgr.status_row(0)["state"] == "healthy"
    assert mgr.status_row(0)["strikes"] == 0
    assert mgr.probation_promotions == 1


def test_rebuild_failures_back_off_exponentially_then_bench():
    def explode(idx):
        raise RuntimeError("still sick")

    pool = _FakePool(2, build=explode)
    mgr = _mgr(pool, max_strikes=2, backoff_jitter=0.0)
    pool.replicas[0].broken = "fault"
    mgr.tick()
    backoffs = [mgr._recs[0].backoff_until - time.monotonic()]
    assert _tick_until(mgr, lambda: mgr.rebuilds_failed >= 1)
    backoffs.append(mgr._recs[0].backoff_until - time.monotonic())
    assert _tick_until(mgr, lambda: mgr.counts()["benched"] == 1)
    # strike 2's backoff doubled strike 1's (jitter pinned to 0)
    assert backoffs[1] > backoffs[0]
    assert mgr.rebuilds_failed == 2  # two attempts, then benched — no loop
    assert mgr.benched_total == 1
    assert not mgr.admit_allowed(0)
    # benched replicas stay benched without operator action
    before = pool.builds
    for _ in range(5):
        mgr.tick()
    assert pool.builds == before
    # operator restart clears strikes and rebuilds for real
    pool._build = None
    mgr.restart(0)
    assert _tick_until(mgr, lambda: mgr.status_row(0)["state"] == "probation")
    assert mgr.rebuilds_ok == 1


def test_probation_canary_error_requarantines():
    pool = _FakePool(2)
    mgr = _mgr(pool)
    pool.replicas[0].broken = "fault"
    mgr.tick()
    assert _tick_until(mgr, lambda: mgr.status_row(0)["state"] == "probation")
    mgr.note_dispatch(0)
    mgr.on_terminal(0, ok=False)
    row = mgr.status_row(0)
    assert row["state"] == "quarantined"
    assert row["strikes"] == 2  # the break + the failed canary


def test_drain_clean_then_restart_and_undrain_rules():
    pool = _FakePool(2)
    mgr = _mgr(pool)
    eng = pool.replicas[0]
    mgr.drain(0, deadline_s=30.0)
    assert mgr.status_row(0)["state"] == "draining"
    assert not mgr.admit_allowed(0)
    # idle replica → the tick closes it clean
    assert _tick_until(mgr, lambda: mgr.status_row(0)["state"] == "drained")
    assert eng.closed and mgr.drains_clean == 1
    # a completed drain cannot be undrained — restart is the way back
    with pytest.raises(LifecycleStateError):
        mgr.undrain(0)
    mgr.restart(0)
    assert _tick_until(mgr, lambda: mgr.status_row(0)["state"] == "probation")
    # undrain DOES return a still-draining replica to rotation
    mgr.drain(1, deadline_s=30.0)
    pool.replicas[1].load["active"] = 1  # busy: the tick cannot close it
    mgr.tick()
    assert mgr.status_row(1)["state"] == "draining"
    mgr.undrain(1)
    assert mgr.status_row(1)["state"] == "healthy"
    assert not pool.replicas[1].closed


def test_undrain_racing_drain_close_heals_via_rebuild():
    """The narrow race: the tick decides to close an idle draining replica,
    undrain() flips it back to healthy before close() lands — the replica
    would sit lifecycle-healthy with a closed (unroutable) engine forever.
    The supervisor treats healthy+closed as broken and rebuilds it."""
    pool = _FakePool(2)
    mgr = _mgr(pool)
    mgr.drain(0, deadline_s=30.0)
    # simulate the race outcome: undrain won the state walk, close landed
    mgr.undrain(0)
    pool.replicas[0].closed = True
    mgr.tick()
    row = mgr.status_row(0)
    assert row["state"] == "quarantined" and "closed" in row["last_error"]
    assert _tick_until(mgr, lambda: mgr.status_row(0)["state"] == "probation")


def test_drain_deadline_kills_stragglers():
    pool = _FakePool(2)
    mgr = _mgr(pool)
    eng = pool.replicas[0]
    eng.load["active"] = 2
    mgr.drain(0, deadline_s=0.0)
    assert _tick_until(mgr, lambda: mgr.status_row(0)["state"] == "drained")
    assert eng.closed and mgr.drains_killed == 1


def test_drain_rejected_from_non_serving_states():
    pool = _FakePool(2)
    mgr = _mgr(pool)
    pool.replicas[0].broken = "fault"
    mgr.tick()
    with pytest.raises(LifecycleStateError):
        mgr.drain(0)
    with pytest.raises(IndexError):
        mgr.drain(7)


def test_counts_census():
    pool = _FakePool(3)
    mgr = _mgr(pool)
    pool.replicas[1].broken = "fault"
    mgr.tick()
    mgr.drain(2, deadline_s=30.0)
    c = mgr.counts()
    assert c["replicas"] == 3
    assert c["healthy"] == 1
    assert c["quarantined"] == 1
    assert c["draining"] == 1
    assert c["serving"] == 1


# --------------------------------------------------------- engine supervisor

def test_engine_supervisor_rebuild_backoff_bench_and_reset():
    built = []

    def build(old):
        if len(built) == 0:
            built.append("fail")
            raise RuntimeError("still sick")
        eng = _FakeEngine()
        built.append(eng)
        return eng

    sup = EngineSupervisor(build, LifecycleConfig(
        rebuild_backoff_s=0.2, rebuild_backoff_max_s=0.4, max_strikes=2,
        backoff_jitter=0.0), name="t")
    healthy = _FakeEngine()
    assert sup.ensure(healthy) is healthy  # no-op on a servable engine
    broken = _FakeEngine()
    broken.broken = "fault"
    # first attempt fails → strike + backoff window
    with pytest.raises(ReplicaUnavailable):
        sup.ensure(broken)
    assert broken.closed
    with pytest.raises(ReplicaUnavailable) as ei:
        sup.ensure(broken)  # inside the backoff window
    assert ei.value.retry_after_s is not None
    time.sleep(0.45)    # past the window (10 ms windows lost a race under load)
    fresh = sup.ensure(broken)
    assert fresh is built[-1] and fresh.started
    sup.note_ok()
    assert sup.strikes == 0
    # bench: strikes past max without a clean stream in between — benched at
    # CLAIM time, so the over-limit strike never burns another rebuild
    sup2 = EngineSupervisor(
        lambda old: (_ for _ in ()).throw(RuntimeError("sick")),
        LifecycleConfig(rebuild_backoff_s=0.0, rebuild_backoff_max_s=0.0,
                        max_strikes=1, backoff_jitter=0.0), name="t2")
    b = _FakeEngine()
    b.broken = "fault"
    with pytest.raises(ReplicaUnavailable):
        sup2.ensure(b)  # strike 1: rebuild attempted, fails
    with pytest.raises(ReplicaUnavailable):
        sup2.ensure(b)  # strike 2 > max: benched before any build
    assert sup2.benched
    with pytest.raises(ReplicaUnavailable):
        sup2.ensure(b)  # benched: no further rebuild attempts
    assert sup2.rebuilds_failed == 1
    sup2.reset()
    assert not sup2.benched and sup2.strikes == 0


def test_engine_supervisor_benches_crash_on_first_use_loop():
    """An engine that rebuilds FINE but crashes before any clean stream
    (note_ok never fires) must not hot-loop a program build per request —
    successful rebuilds count toward the bench too."""
    sup = EngineSupervisor(
        lambda old: _FakeEngine(),
        LifecycleConfig(rebuild_backoff_s=0.0, rebuild_backoff_max_s=0.0,
                        max_strikes=2, backoff_jitter=0.0), name="loop")
    for _ in range(2):  # strikes 1, 2: rebuilds succeed
        b = _FakeEngine()
        b.broken = "crashes on first decode"
        assert sup.ensure(b).started
    b = _FakeEngine()
    b.broken = "crashes on first decode"
    with pytest.raises(ReplicaUnavailable, match="benched"):
        sup.ensure(b)  # strike 3 > max: benched, no third build
    assert sup.benched and sup.rebuilds_ok == 2


# ------------------------------------------------------- real-engine close()

def _tiny_cfg(**kw):
    base = dict(model="tiny-llama", max_seq_len=64, max_batch=2,
                decode_chunk=4, prefix_cache_pages=64, prefix_page_size=16)
    base.update(kw)
    return EngineConfig(**base)


def test_engine_close_fails_inflight_exactly_once_and_is_spent():
    eng = ContinuousBatchingEngine(_tiny_cfg(), seed=0)
    rng = np.random.default_rng(0)
    lock = threading.Lock()
    terminals = {0: [], 1: []}
    first_token = threading.Event()

    def mk(i):
        def emit(ev):
            with lock:
                if ev.token_id >= 0:
                    first_token.set()
                if ev.finished is not None:
                    terminals[i].append(ev.finished)
        return emit

    for i in range(2):
        eng.submit(rng.integers(3, 250, 8).tolist(),
                   SamplingParams(max_tokens=256), mk(i))
    assert first_token.wait(60)
    eng.close()
    assert all(t == ["error"] for t in terminals.values()), terminals
    assert eng.stats()["closed"] and eng.stats()["broken"] is None
    assert not eng.servable()
    # spent, not poisoned: submit/start reject cleanly; idempotent close
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit([5, 6, 7], SamplingParams(max_tokens=2), lambda ev: None)
    with pytest.raises(RuntimeError, match="closed"):
        eng.start()
    eng.close()
    assert all(t == ["error"] for t in terminals.values())  # no double emit


def test_close_idle_engine_emits_nothing():
    eng = ContinuousBatchingEngine(_tiny_cfg(), seed=0)
    rng = np.random.default_rng(1)
    done = threading.Event()
    events = []

    def emit(ev):
        events.append(ev)
        if ev.finished is not None:
            done.set()

    eng.submit(rng.integers(3, 250, 8).tolist(),
               SamplingParams(max_tokens=4), emit)
    assert done.wait(60)
    n = len(events)
    eng.close()
    assert len(events) == n  # a clean drain has nothing to fail


def test_fail_all_inflight_emits_queued_errors_outside_submit_lock():
    """The queued-request drain pops under _submit_lock but EMITS outside
    it: a pool failover emit submits into another engine's _submit_lock
    (and sleeps), so emitting under ours would ABBA-deadlock two same-round
    teardowns against each other."""
    from cyberfabric_core_tpu.runtime.scheduler import _Pending

    eng = ContinuousBatchingEngine(_tiny_cfg(), seed=0)  # thread not started
    seen = []

    def emit(ev):
        # the emit must be able to take the submit lock (a failover would)
        acquired = eng._submit_lock.acquire(blocking=False)
        if acquired:
            eng._submit_lock.release()
        seen.append((ev.finished, acquired))

    eng._pending.put(_Pending("queued-1", [5, 6, 7],
                              SamplingParams(max_tokens=4), emit))
    eng.close()
    assert seen == [("error", True)], seen


def test_engine_supervisor_single_flight_rebuild():
    """A rebuild slower than the backoff window must not let a second
    caller stack a duplicate compile (or strike the engine toward the
    bench while it is already recovering)."""
    gate = threading.Event()
    started = threading.Event()

    def slow_build(old):
        started.set()
        gate.wait(10)
        return _FakeEngine()

    sup = EngineSupervisor(slow_build, LifecycleConfig(
        rebuild_backoff_s=0.0, rebuild_backoff_max_s=0.0, max_strikes=5,
        backoff_jitter=0.0), name="sf")
    broken = _FakeEngine()
    broken.broken = "fault"
    out = {}
    t = threading.Thread(target=lambda: out.update(
        eng=sup.ensure(broken)), daemon=True)
    t.start()
    assert started.wait(5)
    with pytest.raises(ReplicaUnavailable, match="in progress"):
        sup.ensure(broken)  # concurrent caller: no second build, no strike
    assert sup.strikes == 1
    gate.set()
    t.join(5)
    assert out["eng"].started and sup.rebuilds_ok == 1


# --------------------------------------------------- pool integration (real)

@pytest.mark.slow
def test_pool_self_heals_and_rebuilt_streams_match():
    cfg = _tiny_cfg()
    pool = DataParallelServingPool(
        cfg, n_replicas=2, seed=0,
        lifecycle=LifecycleConfig(check_interval_s=0.05,
                                  rebuild_backoff_s=0.05,
                                  probation_successes=1))
    try:
        rng = np.random.default_rng(0)
        prompt = rng.integers(3, 250, 8).tolist()

        def run(p, mt=8):
            done = threading.Event()
            out = {"tokens": [], "fin": None}

            def emit(ev):
                if ev.token_id >= 0:
                    out["tokens"].append(ev.token_id)
                if ev.finished is not None:
                    out["fin"] = ev.finished
                    done.set()

            pool.submit(p, SamplingParams(max_tokens=mt), emit)
            assert done.wait(120)
            return out

        baseline = run(prompt)
        victim = pool.replicas[0]

        def boom():
            raise RuntimeError("injected device fault")

        victim._decode_round = boom
        crash = run(rng.integers(3, 250, 8).tolist())  # breaks replica 0
        assert crash["fin"] in ("stop", "length")  # failover hid the break
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if pool.stats()["healthy"] == 2:
                break
            time.sleep(0.1)
        assert pool.stats()["healthy"] == 2, pool.lifecycle.status()
        assert pool.replicas[0] is not victim
        # the rebuilt replica serves the canary bit-identically
        again = run(prompt)
        assert again["tokens"] == baseline["tokens"]
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if pool.lifecycle.counts()["healthy"] == 2:
                break
            time.sleep(0.05)
        assert pool.lifecycle.counts()["healthy"] == 2
        assert pool.lifecycle.rebuilds_ok == 1
        assert not pool._requests, "tracking records leaked"
    finally:
        pool.shutdown()


# -------------------------------------------------- worker single-engine path

@pytest.mark.slow
def test_worker_single_engine_self_heals():
    import asyncio

    from cyberfabric_core_tpu.modules.llm_gateway.worker import LocalTpuWorker
    from cyberfabric_core_tpu.modules.sdk import ModelInfo

    async def go():
        worker = LocalTpuWorker({})
        model = ModelInfo(
            canonical_id="local::lifecycle-tiny", provider_slug="local",
            provider_model_id="lifecycle-tiny",
            engine_options={"model_config": "tiny-llama", "max_seq_len": 64,
                            "max_batch": 2, "decode_chunk": 4,
                            "lifecycle": {"rebuild_backoff_s": 0.0,
                                          "backoff_jitter": 0.0}})

        async def stream():
            text, fin = [], None
            async for c in worker.completion_stream(model, "hi",
                                                    {"max_tokens": 4}):
                if c.text:
                    text.append(c.text)
                if c.finish_reason:
                    fin = c.finish_reason
            return "".join(text), fin

        first = await stream()
        assert first[1] in ("stop", "length")
        entry = worker._entries["local::lifecycle-tiny"]
        old = entry.scheduler
        old._broken = "injected"
        healed = await stream()  # the supervisor rebuilds before admitting
        assert healed == first
        assert entry.scheduler is not old
        assert entry.supervisor.rebuilds_ok == 1
        assert entry.supervisor.strikes == 0  # note_ok cleared the strike
        entry.scheduler.shutdown()

    asyncio.run(go())
