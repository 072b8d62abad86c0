"""DP serving-path request fan-out (runtime/replicas.py)."""

import threading
import time

import numpy as np
import pytest

from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.engine import StepEvent
from cyberfabric_core_tpu.runtime.replicas import (DataParallelServingPool,
                                                   _Tracked)


def _cfg(**kw):
    base = dict(model="tiny-llama", max_seq_len=128, max_batch=2,
                decode_chunk=4)
    base.update(kw)
    return EngineConfig(**base)


def _run(pool, prompt, max_tokens=8, seed=None):
    done = threading.Event()
    out = {"tokens": [], "finish": None}

    def emit(ev):
        if ev.token_id >= 0:
            out["tokens"].append(ev.token_id)
        if ev.finished is not None:
            out["finish"] = ev.finished
            done.set()

    pool.submit(prompt, SamplingParams(max_tokens=max_tokens, temperature=0.0,
                                       seed=seed), emit)
    assert done.wait(90), "request did not finish"
    return out


def test_fanout_spreads_load_and_completes():
    pool = DataParallelServingPool(_cfg(), n_replicas=2, seed=0)
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(3, 900, 12 + i).tolist() for i in range(6)]
        done = threading.Event()
        lock = threading.Lock()
        state = {"finished": 0, "by_req": {}}

        def mk(i):
            def emit(ev):
                with lock:
                    state["by_req"].setdefault(i, []).append(ev.token_id)
                    if ev.finished is not None:
                        state["finished"] += 1
                        if state["finished"] == len(prompts):
                            done.set()
            return emit

        for i, p in enumerate(prompts):
            pool.submit(p, SamplingParams(max_tokens=6, temperature=0.0), mk(i))
        assert done.wait(120), pool.stats()
        assert state["finished"] == len(prompts)
        st = pool.stats()
        assert st["requests_completed"] == len(prompts)
        # both replicas actually served traffic (6 requests, 2 slots each)
        served = [s["requests_completed"] for s in st["per_replica"]]
        assert all(c > 0 for c in served), served
    finally:
        pool.shutdown()


def test_replicas_pinned_to_distinct_devices():
    """Each replica's params are COMMITTED to its own device — the whole point
    of the pool (weights and compute spread over the dp devices)."""
    import jax

    pool = DataParallelServingPool(_cfg(), n_replicas=2, seed=0)
    try:
        for eng, dev in zip(pool.replicas, pool.devices):
            leaf = jax.tree.leaves(eng.params)[0]
            assert list(leaf.devices()) == [dev], (leaf.devices(), dev)
        # and decode actually ran there: generate then re-check placement
        prompt = np.random.default_rng(3).integers(3, 900, 8).tolist()
        _run(pool, prompt, max_tokens=3)
    finally:
        pool.shutdown()


def test_replicas_agree_greedy():
    """Same weights on every replica: greedy output is replica-independent."""
    pool = DataParallelServingPool(_cfg(), n_replicas=2, seed=0)
    try:
        prompt = np.random.default_rng(1).integers(3, 900, 16).tolist()
        a = _run(pool, prompt)
        b = _run(pool, prompt)
        assert a["tokens"] == b["tokens"]
    finally:
        pool.shutdown()


def test_cache_aware_placement_prefers_warm_replica():
    """RTP-LLM's routing recipe: a request whose prompt head is already in
    one replica's prefix cache routes there (within the load slack) instead
    of to the bare least-loaded replica — the prefill skip beats a marginal
    load difference. Falls back to the existing policy when nothing
    matches."""
    cfg = _cfg(prefix_cache_pages=80, prefix_page_size=16)
    pool = DataParallelServingPool(cfg, n_replicas=2, seed=0)
    try:
        rng = np.random.default_rng(4)
        head = rng.integers(3, 900, 48).tolist()  # 3 full pages
        first = _run(pool, head + rng.integers(3, 900, 6).tolist())
        assert first["finish"] is not None
        hits_before = pool.placement_hint_hits
        # the replica that served request 1 now caches the head's pages —
        # the probe must find it and the counter must record the hint
        warm = [i for i, r in enumerate(pool.replicas)
                if r.pool.peek_prefix_len(head + [999]) > 0]
        assert len(warm) == 1, "exactly one replica should be warm"
        second = _run(pool, head + rng.integers(3, 900, 8).tolist())
        assert second["finish"] is not None
        assert pool.placement_hint_hits > hits_before
        # the scheduler emits a stream's terminal token before it counts the
        # request as completed: give its thread a moment to get there
        deadline = time.monotonic() + 10.0
        served = pool.replicas[warm[0]].stats()
        while served["requests_completed"] < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
            served = pool.replicas[warm[0]].stats()
        assert served["requests_completed"] >= 2, \
            "second request was not routed to the warm replica"
        assert pool.stats()["placement_hint_hits"] > hits_before
        # a cold prompt takes the plain least-loaded path (no hint bump)
        cold_hits = pool.placement_hint_hits
        _run(pool, rng.integers(3, 900, 20).tolist())
        assert pool.placement_hint_hits == cold_hits
    finally:
        pool.shutdown()


def test_failover_resumes_on_survivor():
    """Breaking one replica mid-stream fails over; the client still gets a
    complete, uninterrupted token stream."""
    pool = DataParallelServingPool(_cfg(max_batch=1), n_replicas=2, seed=0)
    try:
        prompt = np.random.default_rng(2).integers(3, 900, 10).tolist()
        # force the route target: break replica 0 AFTER its first token
        first_tok = threading.Event()
        done = threading.Event()
        out = {"tokens": [], "finish": None}

        def emit(ev):
            if ev.token_id >= 0:
                out["tokens"].append(ev.token_id)
                if not first_tok.is_set():
                    first_tok.set()
            if ev.finished is not None:
                out["finish"] = ev.finished
                done.set()

        rid = pool.submit(prompt, SamplingParams(max_tokens=10, temperature=0.0), emit)
        assert first_tok.wait(60)
        victim = pool._requests[rid].replica
        # simulate a device fault: poison the replica's decode path
        eng = pool.replicas[victim]
        eng._broken = None  # ensure flag clean before poisoning
        orig = eng._decode_round

        def boom():
            raise RuntimeError("injected device fault")

        eng._decode_round = boom
        assert done.wait(120), (out, pool.stats())
        # stream completed without surfacing an error
        assert out["finish"] in ("stop", "length"), out
        assert len(out["tokens"]) == 10, out
        st = pool.stats()
        assert st["healthy"] == 1
        eng._decode_round = orig
    finally:
        pool.shutdown()


def test_no_healthy_replicas_raises():
    pool = DataParallelServingPool(_cfg(), n_replicas=1, seed=0)
    try:
        pool.replicas[0]._broken = "poisoned"
        with pytest.raises(RuntimeError):
            pool.submit([5, 6, 7], SamplingParams(max_tokens=2), lambda ev: None)
    finally:
        pool.shutdown()


def test_too_many_replicas_rejected():
    import jax

    with pytest.raises(ValueError):
        DataParallelServingPool(_cfg(), n_replicas=len(jax.devices()) + 1)


# ------------------------------------------------------- failover unit tests
# (bare-instance doubles, the tests/test_faultlab.py pattern: the failover
# policy is host-side bookkeeping — no engine needed)

def _bare_pool():
    pool = DataParallelServingPool.__new__(DataParallelServingPool)
    pool._lock = threading.Lock()
    pool._requests = {}
    pool.replicas = []
    pool.max_retries = 1
    pool.failovers = 0
    pool.failovers_failed = 0
    return pool


class _FakeReplica:
    """stats()-healthy replica double recording submissions."""

    def __init__(self, fail_submits=0):
        self.submissions = []
        self._fail = fail_submits

    def stats(self):
        return {"broken": None, "closed": False, "active": 0, "pending": 0}

    def submit(self, prompt_ids, sampling, emit, request_id=None, trace=None):
        if self._fail > 0:
            self._fail -= 1
            raise RuntimeError("submit refused")
        self.submissions.append((list(prompt_ids), sampling.max_tokens,
                                 request_id))


def test_failover_synthesizes_length_when_budget_already_served():
    """Regression: a replica break that lands AFTER a request emitted its
    full max_tokens budget (only the terminal was lost) must close the
    stream with a clean 'length', not a spurious 'error' — the old
    `remaining <= 0 → return False` path surfaced the break to a client
    whose response was already complete."""
    pool = _bare_pool()
    events = []
    tracked = _Tracked([1, 2, 3], SamplingParams(max_tokens=3), events.append,
                       [7, 8, 9], replica=0, retries_left=1)
    pool._requests["rid"] = tracked
    emit = pool._wrap("rid", tracked)
    emit(StepEvent(0, -1, "error"))  # the break arriving on the final token
    assert [(e.token_id, e.finished) for e in events] == [(-1, "length")]
    assert tracked.done
    assert "rid" not in pool._requests, "tracking record leaked"
    assert pool.failovers == 0 and pool.failovers_failed == 0


def test_failover_synthesized_terminal_does_not_credit_canary():
    """The synthesized length terminal comes from a replica that BROKE —
    it must release the probation canary slot without counting as a clean
    success, or a replica crashing at end-of-stream would be promoted (and
    its strikes reset) every cycle, evading the bench backstop."""

    class _Lc:
        def __init__(self):
            self.calls = []

        def on_departed(self, idx):
            self.calls.append(("departed", idx))

        def on_terminal(self, idx, ok):
            self.calls.append(("terminal", idx, ok))

    pool = _bare_pool()
    pool.lifecycle = _Lc()
    tracked = _Tracked([1, 2, 3], SamplingParams(max_tokens=3),
                       lambda ev: None, [7, 8, 9], replica=0, retries_left=1)
    pool._requests["rid"] = tracked
    assert pool._failover("rid", tracked)
    assert pool.lifecycle.calls == [("departed", 0)]


def test_failover_excludes_breaking_replica_before_broken_flips():
    """The race the exclusion closes: mid-teardown the breaking replica's
    stats()['broken'] may still read None — failover must not resubmit to
    the corpse anyway."""
    pool = _bare_pool()
    corpse, survivor = _FakeReplica(), _FakeReplica()
    pool.replicas = [corpse, survivor]
    events = []
    tracked = _Tracked([1, 2, 3], SamplingParams(max_tokens=8), events.append,
                       [7], replica=0, retries_left=1)
    pool._requests["rid"] = tracked
    assert pool._failover("rid", tracked)
    assert corpse.submissions == [], "resubmitted to the breaking replica"
    assert len(survivor.submissions) == 1
    prompt, max_tokens, rid = survivor.submissions[0]
    assert prompt == [1, 2, 3, 7] and max_tokens == 7 and rid == "rid"
    assert tracked.replica == 1
    assert pool.failovers == 1


def test_failover_retries_with_backoff_until_a_target_appears():
    """A transient capacity hole (every pick failing while a rebuild is in
    flight) is absorbed by the jittered-backoff retries instead of failing
    the stream on the first attempt."""
    pool = _bare_pool()
    flaky = _FakeReplica(fail_submits=1)  # first resubmission refused
    pool.replicas = [_FakeReplica(), flaky]
    pool.failover_backoff_s = 0.001  # keep the test fast
    tracked = _Tracked([1, 2], SamplingParams(max_tokens=8), lambda ev: None,
                       [5], replica=0, retries_left=1)
    pool._requests["rid"] = tracked
    t0 = time.monotonic()
    assert pool._failover("rid", tracked)
    assert time.monotonic() - t0 < 5.0
    assert len(flaky.submissions) == 1  # second attempt landed
    assert pool.failovers == 1 and pool.failovers_failed == 0
    # and when every attempt fails, the budgeted retries exhaust cleanly
    pool2 = _bare_pool()
    pool2.replicas = [_FakeReplica(fail_submits=99),
                      _FakeReplica(fail_submits=99)]
    pool2.failover_backoff_s = 0.001
    tracked2 = _Tracked([1, 2], SamplingParams(max_tokens=8), lambda ev: None,
                        [5], replica=0, retries_left=1)
    assert not pool2._failover("rid2", tracked2)
    assert pool2.failovers_failed == 1


# --------------------------------------------------- concurrent-break torture

@pytest.mark.slow
def test_concurrent_break_torture_recovers_full_capacity():
    """Two replicas broken in the same round under a 16-stream storm: every
    request still sees exactly one terminal, no tracking records leak, and
    the lifecycle supervisor rebuilds the pool back to healthy == replicas
    without a process restart."""
    from cyberfabric_core_tpu.modkit import failpoints as fp
    from cyberfabric_core_tpu.runtime.lifecycle import LifecycleConfig

    cfg = _cfg(max_seq_len=64, prefix_cache_pages=64, prefix_page_size=16)
    pool = DataParallelServingPool(
        cfg, n_replicas=3, seed=0, max_retries=2,
        lifecycle=LifecycleConfig(check_interval_s=0.05,
                                  rebuild_backoff_s=0.05,
                                  probation_successes=1))
    rng = np.random.default_rng(7)
    n = 16
    lock = threading.Lock()
    terminals = {i: [] for i in range(n)}
    done = threading.Event()
    left = [n]

    def mk(i):
        def emit(ev):
            with lock:
                if ev.finished is not None:
                    terminals[i].append(ev.finished)
                    if len(terminals[i]) == 1:
                        left[0] -= 1
                        if left[0] == 0:
                            done.set()
        return emit

    fp.configure(7)
    fp.arm("scheduler.readback", "2*raise")  # two loop crashes, two replicas
    try:
        for i in range(n):
            pool.submit(rng.integers(3, 250, 6 + (i % 5)).tolist(),
                        SamplingParams(max_tokens=8), mk(i))
        assert done.wait(180), (left, pool.stats())
    finally:
        fp.disarm("scheduler.readback")
    # exactly one terminal per stream — none lost, none double-terminated
    assert all(len(t) == 1 for t in terminals.values()), terminals
    assert not pool._requests, "tracking records leaked"
    # the supervisor rebuilds both corpses; canaries promote them
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if pool.stats()["healthy"] == 3:
            break
        time.sleep(0.2)
    assert pool.stats()["healthy"] == 3, pool.lifecycle.status()
    prompt = rng.integers(3, 250, 8).tolist()
    for _ in range(3):  # canary traffic drives probation → healthy
        d = threading.Event()
        pool.submit(prompt, SamplingParams(max_tokens=4),
                    lambda ev: d.set() if ev.finished else None)
        assert d.wait(60)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        if pool.lifecycle.counts()["healthy"] == 3:
            break
        time.sleep(0.1)
    assert pool.lifecycle.counts()["healthy"] == 3, pool.lifecycle.status()
    assert pool.lifecycle.rebuilds_ok >= 2
    # zero slot/page leaks on every serving engine
    pool.shutdown()
    for i, eng in enumerate(pool.replicas):
        st = eng.stats()
        if st["broken"] or st["closed"]:
            continue
        assert len(eng._free_slots) == eng.n_slots, f"replica {i} slot leak"
        assert st["prefix_cache"]["pages_referenced"] == 0, f"replica {i}"
