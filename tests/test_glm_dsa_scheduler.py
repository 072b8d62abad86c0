"""``tiny-glm-dsa-share4`` served by the continuous scheduler (no gateway) on
the one scheduler and the one pool class every model is served by: the latent
chain of TWO arrays (``index_pool`` riding ``latent_pool``'s page ids, tree,
refcounts and movers), the programs ``mixed_step`` and ``paged_decode_chunk``
over two donated cache operands, and the counters of the index passes."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.models import get_config
from cyberfabric_core_tpu.modkit.metrics import default_registry
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.paged import PrefixKVPool
from cyberfabric_core_tpu.runtime.scheduler import (
    ContinuousBatchingEngine, _moe_series)

CFG = get_config("tiny-glm-dsa-share4")
DSA = ("llm_dsa_keys_scored_total", "llm_dsa_keys_selected_total",
       "llm_dsa_queries_total", "llm_dsa_queries_binding_total",
       "llm_dsa_decode_keys_scored_total",
       "llm_dsa_decode_keys_selected_total", "llm_dsa_decode_queries_total",
       "llm_dsa_decode_queries_binding_total", "llm_dsa_decode_calls_total")


def _cfg(**over):
    base = dict(model="tiny-glm-dsa-share4", max_seq_len=128, max_batch=4,
                decode_chunk=4, prefix_cache_pages=160, prefix_page_size=4,
                prefill_budget_tokens=16, quantization="int8")
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


def _run(cfg, prompts, max_tokens=12, stagger_s=0.0):
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(len(prompts))
    try:
        for i, p in enumerate(prompts):
            if stagger_s and i:
                time.sleep(stagger_s)
            sched.submit(p, SamplingParams(max_tokens=max_tokens),
                         col.emit_for(i))
        assert col.done.wait(240), (col.finishes, sched.stats())
        time.sleep(0.2)
        return col, sched
    finally:
        sched.shutdown()


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(3, 250, n).tolist()


def _counter(name):
    for _, value in default_registry.counter(name).samples():
        return value
    return 0.0


# -------------------------------------------------- the chain of two arrays
def test_the_latent_chain_is_two_arrays_under_one_page_id():
    """``index_pool`` beside ``latent_pool``: the same layers, pages and page
    size, its own minor dimension (one lane tile); both are cache operands
    and both are adopted; ``stats()`` and ``cache_bytes_per_token`` read both;
    the allocator, the refcounts and the tree count pages as ever; a plain
    latent model keeps ONE array."""
    pool = PrefixKVPool(CFG, num_pages=20, page_size=4,
                        force_python_native=True)
    assert pool.latent_pool.shape == (5, 20, 4, CFG.latent_lanes)
    assert pool.index_pool.shape == (5, 20, 4, CFG.index_lanes)
    assert (CFG.latent_lanes, CFG.index_lanes, CFG.index_head_dim) == (
        128, 128, 16)
    assert pool.cache_operands() == (pool.latent_pool, pool.index_pool)
    rest = pool.adopt((pool.latent_pool + 1, pool.index_pool + 2, "last"))
    assert rest == ("last",)
    assert float(pool.latent_pool[0, 0, 0, 0]) == 1
    assert float(pool.index_pool[0, 0, 0, 0]) == 2
    st = pool.stats()
    assert st["page_layout"] == "latent" and st["page_shape"] == [4, 128]
    assert st["cache_bytes_per_token"] == CFG.cache_bytes_per_token() == \
        5 * (128 + 128) * 2
    assert st["index_cache_bytes"] == 5 * 20 * 4 * 128 * 2
    assert st["pool_bytes"] == st["cache_bytes"] == 2 * st["index_cache_bytes"]
    assert (st["index_topk"], st["index_heads"], st["index_lanes"]) == (
        12, 4, 128)
    prompt = list(range(3, 3 + 11))
    chain = pool.extend_chain([], 11)
    assert len(chain) == 3 and pool.stats()["pages_referenced"] == 3
    pool.commit_chain(prompt, chain)
    pool.release_slot(chain)
    hit, cached = pool.match_prefix(prompt + [7])
    assert cached == 8 and hit == chain[:2]      # one id names both arrays
    pool.release(prompt + [7])
    plain = PrefixKVPool(get_config("tiny-kimi-share4"), num_pages=8,
                         page_size=16, force_python_native=True)
    assert plain.cache_operands() == (plain.latent_pool,)
    assert not hasattr(plain, "index_pool")
    assert plain.stats()["index_cache_bytes"] == 0
    assert plain.stats()["index_topk"] == plain.stats()["index_heads"] == 0


def test_the_movers_carry_both_arrays():
    """The preemption movers save and restore the chain's pages of BOTH
    arrays, each with its own minor dimension; the PD export and a sharding
    are refused as any latent pool's."""
    pool = PrefixKVPool(CFG, num_pages=12, page_size=4,
                        force_python_native=True)
    chain = pool.extend_chain([], 10)
    rng = np.random.default_rng(0)
    latent = jnp.asarray(rng.standard_normal((5, 3, 4, 128)), jnp.bfloat16)
    index = jnp.asarray(rng.standard_normal((5, 3, 4, 128)), jnp.bfloat16)
    at = jnp.asarray(chain)
    pool.latent_pool = pool.latent_pool.at[:, at].set(latent)
    pool.index_pool = pool.index_pool.at[:, at].set(index)
    saved = pool.save_chain_to_host(chain)
    assert [s.shape for s in saved] == [(5, 3, 4, 128)] * 2
    pool.release_slot(chain)
    pool.latent_pool = jnp.zeros_like(pool.latent_pool)
    pool.index_pool = jnp.zeros_like(pool.index_pool)
    back = jnp.asarray(pool.restore_chain_from_host(saved))
    np.testing.assert_array_equal(
        np.asarray(pool.latent_pool[:, back], np.float32),
        np.asarray(latent, np.float32))
    np.testing.assert_array_equal(
        np.asarray(pool.index_pool[:, back], np.float32),
        np.asarray(index, np.float32))
    with pytest.raises(ValueError, match="kv-head axis"):
        pool.export_pages(list(np.asarray(back)))
    with pytest.raises(ValueError, match="kv-head axis to shard"):
        PrefixKVPool(CFG, num_pages=12, page_size=4, sharding=object(),
                     force_python_native=True)


@pytest.mark.parametrize("over,says", [
    (dict(scheduler_spec_k=2), "scheduler_spec_k"),
    (dict(pd_role="prefill"), "pd_role"),
    (dict(tp=2), "tp > 1"),
])
def test_modes_that_lack_one_named_thing_are_refused_at_build(over, says):
    """Speculation, PD and tp > 1 die typed at BUILD, by the lines that
    refuse any latent model's."""
    with pytest.raises(ValueError, match=says):
        ContinuousBatchingEngine(_cfg(**over), seed=0)


# ---------------------------------------------------------------- the programs
def test_a_greedy_answer_repeats_whatever_rides_beside_it():
    """Alone and beside two others arriving while it decodes: a prompt of
    50 (four chunks of 16, the selection binding from the first) answers the
    same; every row on its own pages of both arrays; nothing is left held."""
    mine = _prompt(4, 50)
    others = [_prompt(5 + i, 9 + 17 * i) for i in range(2)]
    alone, _ = _run(_cfg(), [mine], max_tokens=14)
    again, _ = _run(_cfg(), [mine], max_tokens=14)
    beside, sched = _run(_cfg(), [mine, *others], max_tokens=14,
                         stagger_s=0.05)
    assert len(alone.tokens[0]) == 14 and alone.finishes[0] == "length"
    assert alone.tokens[0] == again.tokens[0] == beside.tokens[0]
    assert max(alone.tokens[0]) < CFG.vocab_rows == 256   # over the slice
    assert sched.mixed_rounds >= 4
    pool = sched.pool.stats()
    assert pool["pages_referenced"] == 0 and pool["orphan_pages"] == 0


def test_a_prefix_hit_reuses_latent_and_index_pages():
    """The radix tree hands a second request the first one's full pages: one
    alias of the page table serves the latent rows AND the index keys, and
    the answer is the one without the hit."""
    head = _prompt(11, 40)
    first, _ = _run(_cfg(), [head + [9, 8, 7]], max_tokens=8)
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    col = _Collector(2)
    try:
        started = threading.Event()     # its prompt's pages are committed
        sched.submit(head + [9, 8, 7], SamplingParams(max_tokens=8),
                     col.emit_for(0, then=lambda ev: started.set()))
        assert started.wait(240)
        sched.submit(head + [9, 8, 7], SamplingParams(max_tokens=8),
                     col.emit_for(1))
        assert col.done.wait(240)
        assert sched.pool.stats()["hits"] >= 1
        assert sched.pool.stats()["prefill_tokens_saved"] >= 40
    finally:
        sched.shutdown()
    assert col.tokens[0] == col.tokens[1] == first.tokens[0]


def test_preempt_and_resume_moves_both_arrays():
    """Pool pressure while the stream decodes past ``index_topk``: its chain
    goes to the host as two arrays and comes back to fresh pages, and the
    answer is the uninterrupted one (a lost index key would change what the
    resumed row selects)."""
    prompt = _prompt(6, 21)
    cfg = _cfg()            # the programs the tests above compiled
    want, _ = _run(cfg, [prompt], max_tokens=24)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(1)
    try:
        orig_extend, armed = sched.pool.extend_chain, threading.Event()

        def flaky_extend(chain, needed):
            if armed.is_set() and sched.preemptions == 0:
                raise MemoryError("injected pool pressure")
            return orig_extend(chain, needed)

        sched.pool.extend_chain = flaky_extend
        sched.submit(prompt, SamplingParams(max_tokens=24), col.emit_for(
            0, then=lambda ev: len(col.tokens[0]) >= 8 and armed.set()))
        assert col.done.wait(240), (col.tokens, sched.stats())
    finally:
        sched.shutdown()
    assert sched.preemptions >= 1, "injected pressure never preempted"
    assert col.tokens == want.tokens


def test_the_counters_of_the_index_passes():
    """/metrics: keys scored and attended, queries and those the selection
    bound, over layers and forwards; the decode-only pair and the index
    passes of decode chunks; the round records carry ``keys_scored`` and
    ``keys_selected``; a model without an indexer has none of the series."""
    series = _moe_series(("assignments", "keys_scored", "keys_selected",
                          "queries", "queries_binding"))
    assert set(DSA) <= set(series)
    assert not set(DSA) & set(_moe_series(("assignments", "local")))
    before = {s: _counter(s) for s in DSA}
    col, sched = _run(_cfg(decode_lookahead=0), [_prompt(9, 18)],
                      max_tokens=9)
    d = {s.removeprefix("llm_dsa_").removesuffix("_total"):
         _counter(s) - before[s] for s in DSA}
    L, topk = CFG.num_layers, CFG.index_topk
    assert (L, topk) == (5, 12)
    # a prompt of 18 in chunks of 16 + 2, then 2 chunks of 4 decode steps,
    # of which 8 steps ran the row (its first token came with the prompt)
    chunk = sum(range(1, 19))                          # a query sees t + 1
    steps = [19 + i for i in range(8)]                 # lengths at decode
    assert d["decode_keys_scored"] == L * sum(steps)
    assert d["decode_keys_selected"] == L * 8 * topk
    assert d["decode_calls"] == 2 * 4 * L
    assert d["decode_queries"] == d["decode_queries_binding"] == L * 8
    assert d["keys_scored"] == L * (chunk + sum(steps))
    assert d["keys_selected"] == L * (
        sum(min(t, topk) for t in range(1, 19)) + 8 * topk)
    assert d["queries"] == L * (18 + 8)
    assert d["queries_binding"] == L * (6 + 8)
    rounds = list(sched.round_timings)
    assert all("keys_scored" in r and "keys_selected" in r for r in rounds)
    assert sum(r["keys_scored"] for r in rounds) == d["keys_scored"]
    st = sched.stats()["prefix_cache"]
    assert st["cache_bytes_per_token"] == CFG.cache_bytes_per_token()
    assert st["kv_layers"] == st["model_layers"] == 5
    assert len(col.tokens[0]) == 9
