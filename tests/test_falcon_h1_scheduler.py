"""``tiny-falcon-h1`` served by the continuous scheduler (no gateway): the
recurrent state beside the K/V pages in one manager (``PrefixKVPool``).

The contract each case holds the scheduler to is the uninterrupted
computation: a request that resumes from a snapshot, one that is preempted
and resumed, a finished row and an idle row each leave exactly what a run
without them leaves. Greedy tokens are compared, and where the test can stop
the world, the state rows bit for bit."""

import functools
import threading
import time

import jax
import numpy as np
import pytest

from cyberfabric_core_tpu.modkit import failpoints as fp
from cyberfabric_core_tpu.modkit.metrics import default_registry
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

BUDGET = 32          # the prefill budget: a snapshot boundary every 32 tokens


def _cfg(**over):
    base = dict(model="tiny-falcon-h1", max_seq_len=256, max_batch=4,
                decode_chunk=4, prefix_cache_pages=80,
                prefix_page_size=16, prefill_budget_tokens=BUDGET)
    base.update(over)
    return EngineConfig(**base)


class _Collector:
    def __init__(self, n):
        self.tokens = {i: [] for i in range(n)}
        self.finishes = {}
        self.done = threading.Event()
        self._lock = threading.Lock()
        self._n = n

    def emit_for(self, i, then=None):
        def emit(ev):
            with self._lock:
                if ev.token_id >= 0:
                    self.tokens[i].append(ev.token_id)
                if ev.finished:
                    self.finishes[i] = ev.finished
                    if len(self.finishes) == self._n:
                        self.done.set()
            if then:
                then(ev)
        return emit


def _run(cfg, prompts, max_tokens=12, stagger_s=0.0, in_turn=False):
    """``in_turn``: a request is sent when the one before it has its first
    token, that is, when that prompt is committed to the prefix tree."""
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(len(prompts))
    try:
        for i, p in enumerate(prompts):
            if stagger_s and i:
                time.sleep(stagger_s)
            started = threading.Event()
            sched.submit(p, SamplingParams(max_tokens=max_tokens),
                         col.emit_for(i, then=lambda ev, e=started: e.set()))
            if in_turn:
                assert started.wait(240), sched.stats()
        assert col.done.wait(240), (col.finishes, sched.stats())
        time.sleep(0.2)     # let the scheduler thread finish slot teardown
        return col.tokens, sched.stats(), sched
    finally:
        sched.shutdown()


def _counter(name):
    for labels, value in default_registry.counter(name).samples():
        return value
    return 0.0


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    base = rng.integers(3, 500, 70).tolist()
    return base, rng.integers(3, 500, 20).tolist()


def test_a_greedy_answer_repeats():
    base, other = _prompts()
    first, _, _ = _run(_cfg(), [base, other])
    again, _, _ = _run(_cfg(), [base, other])
    assert first == again
    assert all(len(t) == 12 for t in first.values())


def _staggered_prompts():
    base, other = _prompts(1)
    return [base, other, base[:40], other[:9]]


@functools.cache
def _sync_streams(model):
    """What the synchronous scheduler emits for the staggered prompts."""
    return _run(_cfg(model=model, decode_lookahead=0), _staggered_prompts(),
                max_tokens=24)[0]


@pytest.mark.parametrize("lookahead", [0, 1, 2])
@pytest.mark.parametrize("model", ["tiny-falcon-h1", "tiny-llama"])
def test_lookahead_depth_never_changes_a_stream(model, lookahead):
    """A chunk in flight has advanced the state; a stale ring is drained, not
    replayed. Arrivals land while the ring holds chunks (staggered), and wait
    for them: the one admission rule, so the case holds a model without
    state to the same."""
    sync = _sync_streams(model)
    waits = _counter("llm_admission_ring_waits_total")
    got, stats, _ = _run(_cfg(model=model, decode_lookahead=lookahead),
                         _staggered_prompts(), max_tokens=24, stagger_s=0.3)
    assert got == sync
    if lookahead:   # the scenario occurred: chunks were in flight
        assert stats["pipeline"]["lookahead"]["dispatched"] > 0
    else:           # nothing is ever in flight at a pass's start
        assert _counter("llm_admission_ring_waits_total") == waits


def test_a_prompt_sharing_two_whole_chunks_resumes_from_the_snapshot():
    """The second request shares 64 tokens = two chunks of the budget with
    the first: it takes the pages AND the snapshot at token 64, prefills only
    its suffix, and answers as a cold run of the same prompt does."""
    base, _ = _prompts(2)
    shared = base[:2 * BUDGET] + [7, 8, 9, 10, 11, 12, 13, 14, 15]
    cold, _, _ = _run(_cfg(), [shared])
    hits = _counter("llm_state_snapshot_hits_total")
    warm, stats, _ = _run(_cfg(), [base, shared], in_turn=True)
    pool = stats["prefix_cache"]
    assert _counter("llm_state_snapshot_hits_total") == hits + 1
    assert pool["state_snapshot_hits"] == 1
    assert pool["prefill_tokens_saved"] == 2 * BUDGET
    assert pool["state_snapshots_taken"] >= 2       # at 32 and at 64
    assert warm[1] == cold[0]


@pytest.mark.parametrize("n", [2, 3])
def test_prompts_admitted_in_one_round_take_consecutive_steps(n):
    """``n`` prompts pending when the loop starts are admitted in one pass
    and take the lane in turn, a chunk a step: chunks still end on snapshot
    boundaries, a snapshot is taken wherever one does, and every answer is
    that of the prompt served alone."""
    base, other = _prompts(5)
    prompts = [base, other + other[:25], base[10:55]][:n]    # 70, 45, 45
    alone = [_run(_cfg(), [p])[0][0] for p in prompts]
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    col = _Collector(n)
    start, sched.start = sched.start, lambda: None          # hold the loop
    try:
        for i, p in enumerate(prompts):
            sched.submit(p, SamplingParams(max_tokens=12), col.emit_for(i))
        sched.start = start
        sched.start()
        assert col.done.wait(240), (col.finishes, sched.stats())
        time.sleep(0.2)
        timings = list(sched.round_timings)
        pool = sched.stats()["prefix_cache"]
    finally:
        sched.shutdown()
    assert [col.tokens[i] for i in range(n)] == alone
    mixed = [t for t in timings if t["mixed"]]
    want = [min(BUDGET, len(p) - at) for p in prompts
            for at in range(0, len(p), BUDGET)]
    assert [t["chunk_tokens"] for t in mixed] == want
    assert all(t["positions"] == 4 + _cfg().bucket_for(t["chunk_tokens"])
               for t in mixed)
    # ends on a boundary: 32 and 64 of the first prompt, 32 of each other
    assert pool["state_snapshots_taken"] == n + 1


def test_a_prompt_sharing_less_than_a_chunk_saves_nothing():
    """Pages without a snapshot at their end are worth nothing: 16 shared
    tokens (one page, half a chunk) are prefilled again."""
    base, _ = _prompts(3)
    short = base[:16] + [9] * 20
    cold, _, _ = _run(_cfg(), [short])
    warm, stats, _ = _run(_cfg(), [base, short], in_turn=True)
    pool = stats["prefix_cache"]
    assert pool["state_snapshot_hits"] == 0
    assert pool["prefill_tokens_saved"] == 0
    assert warm[1] == cold[0]


def test_snapshots_are_bounded_and_an_evicted_page_frees_its_snapshot():
    """Two snapshot rows, both owned by the first prompt's pages (tokens 32
    and 64): the second prompt's boundary drops the older; evicting the
    tree's pages frees every snapshot they own."""
    base, other = _prompts(4)
    evictions = _counter("llm_state_snapshot_evictions_total")
    _, stats, sched = _run(_cfg(state_snapshots=2), [base, other + other],
                           in_turn=True)
    pool = stats["prefix_cache"]
    assert pool["state_rows"] == 4 + 2
    assert pool["state_snapshots_taken"] == 3       # 32, 64; then 32
    assert pool["state_snapshot_rows_in_use"] == 2
    assert pool["state_snapshot_evictions"] == 1
    with sched.pool._tree_lock:
        freed = sched.pool.tree.evict(80)
    for page in freed:
        sched.pool._drop_snapshot(page)
    assert sched.pool.stats()["state_snapshot_rows_in_use"] == 0
    assert _counter("llm_state_snapshot_evictions_total") == evictions + 3


def test_preempt_mid_prefill_and_resume_equals_the_uninterrupted_run():
    base, other = _prompts(5)
    prompts = [base, other, base[:45]]
    want, _, _ = _run(_cfg(prefill_budget_tokens=16), prompts, max_tokens=16)
    restores = _counter("llm_state_restores_total")
    fp.configure(0)
    fp.arm("scheduler.prefill_chunk",
           {"kind": "raise", "exc": "MemoryError", "mode": "once", "after": 2,
            "n": 2})
    try:
        got, stats, sched = _run(_cfg(prefill_budget_tokens=16), prompts,
                                 max_tokens=16)
    finally:
        fp.disarm("scheduler.prefill_chunk")
    assert stats["preemptions"] >= 1, "the fault never forced a preempt"
    assert got == want
    assert _counter("llm_state_restores_total") >= restores + 1
    pool = sched.pool.stats()
    assert pool["pages_referenced"] == 0 and pool["orphan_pages"] == 0


@pytest.mark.parametrize("depth", [0, 2])
def test_preempt_mid_decode_and_resume_equals_the_uninterrupted_run(depth):
    """Pool pressure while the stream decodes (with and without chunks in
    flight): the row's state goes to the host with its pages and comes back
    exactly."""
    prompt = np.random.default_rng(6).integers(3, 500, 20).tolist()
    cfg = _cfg(max_batch=2, max_seq_len=128, prefix_cache_pages=64,
               prefix_page_size=8, decode_lookahead=depth)
    want, _, _ = _run(cfg, [prompt], max_tokens=40)

    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(1)
    try:
        orig_extend = sched.pool.extend_chain
        armed = threading.Event()

        def flaky_extend(chain, needed):
            if armed.is_set() and sched.preemptions == 0:
                raise MemoryError("injected pool pressure")
            return orig_extend(chain, needed)

        sched.pool.extend_chain = flaky_extend

        def arm(ev):
            if len(col.tokens[0]) == 12:
                armed.set()
        sched.submit(prompt, SamplingParams(max_tokens=40),
                     col.emit_for(0, then=arm))
        assert col.done.wait(240), (col.tokens, sched.stats())
    finally:
        sched.shutdown()
    assert sched.preemptions >= 1, "injected pressure never preempted"
    assert col.tokens == want
    assert sched.pool.stats()["state_restores"] >= 1


def test_a_finished_row_freezes_and_an_idle_row_is_untouched():
    """While slot 0's long answer decodes: slot 1's request has finished, so
    its state stops changing; slot 2 never held a request, so its state is
    what it was at build, bit for bit."""
    base, other = _prompts(7)
    sched = ContinuousBatchingEngine(_cfg(decode_lookahead=0), seed=0)
    col = _Collector(2)
    seen = {}
    try:
        idle0 = sched.pool.state_row(2)

        def watch(ev):
            n = len(col.tokens[0])
            if n in (30, 60) and 1 in col.finishes:
                seen[n] = sched.pool.state_row(1), sched.pool.state_row(0)
        sched.submit(base, SamplingParams(max_tokens=80),
                     col.emit_for(0, then=watch))
        sched.submit(other, SamplingParams(max_tokens=5), col.emit_for(1))
        assert col.done.wait(240), (col.finishes, sched.stats())
        idle1 = sched.pool.state_row(2)
    finally:
        sched.shutdown()
    assert set(seen) == {30, 60}
    (fin_a, run_a), (fin_b, run_b) = seen[30], seen[60]
    for leaf in ("ssm", "conv"):
        assert np.array_equal(fin_a[leaf], fin_b[leaf]), leaf      # frozen
        assert not np.array_equal(run_a[leaf], run_b[leaf]), leaf  # running
        assert np.array_equal(idle0[leaf], idle1[leaf]), leaf      # untouched
        assert not idle1[leaf].any()


@pytest.mark.parametrize("over,what", [
    (dict(scheduler_spec_k=3), "state rollback"),
    (dict(pd_role="prefill"), "export carries no recurrent state"),
    (dict(tp=2), "no sharding for the state slab"),
])
def test_a_mode_that_cannot_carry_state_is_refused_at_build(over, what):
    with pytest.raises(ValueError, match=what):
        ContinuousBatchingEngine(_cfg(**over), seed=0)


def test_the_pd_page_export_refuses_a_pool_with_state():
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    try:
        with pytest.raises(ValueError, match="no recurrent state"):
            sched.pool.export_pages([1])
        with pytest.raises(ValueError, match="no recurrent state"):
            sched.pool.import_pages((np.zeros((2, 0, 16, 2, 16)),) * 2)
    finally:
        sched.shutdown()


def test_llama_programs_take_the_operands_they_took():
    """For a llama-family model the two serving programs take params, the two
    pools (donated) and the control rows, as before this architecture came:
    no third cache operand, nothing of the mixer in the lowered text."""
    sched = ContinuousBatchingEngine(_cfg(model="tiny-llama"), seed=0)
    try:
        assert sched.pool.state is None
        assert len(sched.pool.cache_operands()) == 2
        control = (sched._rows_dev, sched._last_tokens, sched._lengths_dev,
                   sched._active_dev, sched._finished_dev, sched._slot_keys)
        lowered = sched._paged_decode_fn.lower(
            sched.params, sched.pool.k_pool, sched.pool.v_pool, *control)
        n_params = len(jax.tree.leaves(sched.params))
        text = lowered.as_text()
        flat = jax.tree.leaves(lowered.args_info)
        assert len(flat) == n_params + 2 + len(control)
        donated = [a.donated for a in flat]
        assert donated == [False] * n_params + [True, True] \
            + [False] * len(control)
        assert "ssm" not in text and "f32[2,8" not in text
    finally:
        sched.shutdown()
