"""``tiny-granite-hybrid`` served by the continuous scheduler (no gateway): a
model with recurrent state AND routed experts on the one path. The pool is as
deep as the attention layers and the slab as the mamba layers; the state
rides the programs as a third donated operand and the expert counters ride
their drain, from one forward.

The contract is falcon_h1's (``test_falcon_h1_scheduler.py``): a request that
resumes from a snapshot, or is preempted and resumed, leaves what the
uninterrupted run leaves. Greedy tokens are compared. Most cases build the
one-period cut ``tiny-granite-hybrid-4l`` (three runs of layers to compile,
not five)."""

import threading
import time

import jax
import numpy as np
import pytest

from cyberfabric_core_tpu.models import get_config
from cyberfabric_core_tpu.modkit.metrics import default_registry
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import (ContinuousBatchingEngine,
                                                    _moe_series)

BUDGET = 32          # the prefill budget: a snapshot boundary every 32 tokens
ONE_PERIOD = "tiny-granite-hybrid-4l"
SERIES = _moe_series(("assignments", "local", "touched")) + (
    "llm_attn_pages_walked_total", "llm_attn_pages_offered_total",
    "llm_state_snapshots_taken_total", "llm_state_snapshot_hits_total",
    "llm_state_restores_total")


def _cfg(**over):
    base = dict(model=ONE_PERIOD, max_seq_len=256, max_batch=4,
                decode_chunk=4, prefix_cache_pages=80,
                prefix_page_size=16, prefill_budget_tokens=BUDGET)
    base.update(over)
    return EngineConfig(**base)


class _Collector:
    def __init__(self, n):
        self.tokens = {i: [] for i in range(n)}
        self.finishes = {}
        self.done = threading.Event()
        self._lock, self._n = threading.Lock(), n

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


def _run(cfg, prompts, max_tokens=12, in_turn=False):
    """``in_turn``: a request is sent when the one before it has its first
    token, that is, when that prompt is committed to the prefix tree."""
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(len(prompts))
    try:
        for i, p in enumerate(prompts):
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
    for _, value in default_registry.counter(name).samples():
        return value
    return 0.0


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(3, 500, 70).tolist(), rng.integers(3, 500, 20).tolist()


def test_one_model_counts_experts_and_state():
    """Two periods of the stack, int8: ``/metrics``' expert counters AND
    state counters move from one model's forwards; the pool reports the
    layers each cache was built with. (That a greedy answer repeats from
    engine to engine is held by the two cases below: a cold run against a
    warm one, an uninterrupted run against a preempted one.)"""
    base, other = _prompts()
    cfg = _cfg(model="tiny-granite-hybrid", quantization="int8",
               decode_lookahead=0)
    model = get_config("tiny-granite-hybrid")
    before = {s: _counter(s) for s in SERIES}
    first, stats, sched = _run(cfg, [base, other])
    d = {s.removeprefix("llm_").removesuffix("_total"):
         _counter(s) - before[s] for s in SERIES}
    assert all(len(t) == 12 for t in first.values())
    # 70 tokens = chunks of 32, 32, 6; 20 tokens one chunk: 4 mixed steps of
    # 4 decode rows + a lane, then decode chunks of 4 steps
    mixed = [r for r in sched.round_timings if r["mixed"]]
    decode_chunks = [r for r in sched.round_timings if not r["mixed"]]
    forwards = len(mixed) + 4 * len(decode_chunks)
    L, E, K = model.num_layers, model.num_experts, model.experts_per_token
    assert d["moe_experts_offered"] == forwards * L * E
    assert d["moe_decode_experts_offered"] == 4 * len(decode_chunks) * L * E
    positions = sum(r["positions"] for r in mixed) + 16 * len(decode_chunks)
    assert d["moe_assignments"] == d["moe_assignments_local"] \
        == positions * K * L
    assert 0 < d["moe_decode_experts_touched"] < d["moe_experts_touched"] \
        <= d["moe_experts_offered"]
    # pages are offered by the layers that attend: 2 of 8
    assert d["attn_pages_offered"] == forwards * model.kv_layers * 4 * 16
    assert 0 < d["attn_pages_walked"] < d["attn_pages_offered"]
    assert d["state_snapshots_taken"] == 2          # at 32 and at 64
    pool = stats["prefix_cache"]
    assert (pool["kv_layers"], pool["state_layers"], pool["model_layers"]) \
        == (2, 6, 8)
    assert pool["cache_bytes"] == pool["pool_bytes"] + pool["state_bytes"]
    assert pool["state_rows"] == 4 + 4 and stats["state_rows_in_use"] >= 0
    assert sched.pool.k_pool.shape[0] == 2
    assert sched.pool.state["ssm"].shape[0] == 6
    assert len(sched.pool.cache_operands()) == 3
    assert sched.pool.k_pool.dtype == jax.numpy.bfloat16


def test_a_prompt_sharing_two_whole_chunks_resumes_from_the_snapshot():
    """The second request shares 64 tokens = two chunks of the budget with
    the first: it takes the attention layer's pages AND the mamba layers'
    snapshot at token 64, prefills only its suffix, and answers as a cold
    run of the same prompt does."""
    base, _ = _prompts(2)
    shared = base[:2 * BUDGET] + [7, 8, 9, 10, 11, 12, 13, 14, 15]
    cold, _, _ = _run(_cfg(), [shared])
    warm, stats, _ = _run(_cfg(), [base, shared], in_turn=True)
    pool = stats["prefix_cache"]
    assert pool["state_snapshot_hits"] == 1
    assert pool["prefill_tokens_saved"] == 2 * BUDGET
    assert pool["state_snapshots_taken"] >= 2       # at 32 and at 64
    assert warm[1] == cold[0]


def test_preempt_mid_decode_and_resume_equals_the_uninterrupted_run():
    """Pool pressure while the stream decodes, with chunks in flight: the
    row's state (the mamba layers') goes to the host with its pages (the
    attention layer's) and comes back exactly."""
    prompt = np.random.default_rng(6).integers(3, 500, 20).tolist()
    cfg = _cfg(max_batch=2, max_seq_len=128, prefix_cache_pages=64,
               prefix_page_size=8, decode_lookahead=2)
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


@pytest.mark.parametrize("over,what", [
    (dict(scheduler_spec_k=3), "state rollback"),
    (dict(pd_role="prefill"), "export carries no recurrent state"),
    (dict(tp=2), "no sharding for the state slab"),
])
def test_a_mode_that_cannot_carry_state_is_refused_at_build(over, what):
    """The lines falcon_h1 is refused with, for the same reasons."""
    with pytest.raises(ValueError, match=what):
        ContinuousBatchingEngine(_cfg(**over), seed=0)
