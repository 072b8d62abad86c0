"""``tiny-kimi-share4`` served by the continuous scheduler (no gateway) on the
one scheduler and the one pool class every model is served by: the latent
pool (ONE array, page accounting, preemption save and restore, the PD export
refused), the programs ``mixed_step`` and ``paged_decode_chunk`` over one
donated cache operand, the expert counters of a chip's share and the
walked/offered counters of the latent kernel's work list."""

import dataclasses
import json
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.models import get_config
from cyberfabric_core_tpu.modkit.metrics import default_registry
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.paged import PrefixKVPool
from cyberfabric_core_tpu.runtime.scheduler import (
    ContinuousBatchingEngine, _moe_series)

CFG = get_config("tiny-kimi-share4")
SERIES = _moe_series(("assignments", "local", "touched")) + (
    "llm_attn_pages_walked_total", "llm_attn_pages_offered_total")


def _cfg(**over):
    base = dict(model="tiny-kimi-share4", max_seq_len=128, max_batch=4,
                decode_chunk=4, prefix_cache_pages=80,
                prefix_page_size=16, prefill_budget_tokens=32,
                quantization="int8")
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


# ------------------------------------------------------------ the latent pool
def test_the_latent_pool_is_one_array_and_counts_pages():
    """(g) ONE array of [L, P, page, lanes]: no kv-head axis, no V pool; the
    allocator, the refcounts and the prefix tree count pages as ever."""
    pool = PrefixKVPool(CFG, num_pages=20, page_size=16,
                        force_python_native=True)
    assert not hasattr(pool, "k_pool") and not hasattr(pool, "v_pool")
    assert pool.latent_pool.shape == (3, 20, 16, CFG.latent_lanes)
    assert CFG.latent_width == 48 and CFG.latent_lanes == 128
    assert pool.cache_operands() == (pool.latent_pool,)
    rest = pool.adopt((pool.latent_pool + 1, "last", "keys"))
    assert rest == ("last", "keys") and float(pool.latent_pool[0, 0, 0, 0]) == 1
    st = pool.stats()
    assert st["page_layout"] == "latent" and st["page_shape"] == [16, 128]
    assert st["cache_bytes_per_token"] == 3 * 128 * 2
    assert st["pool_bytes"] == 3 * 20 * 16 * 128 * 2
    prompt = list(range(3, 3 + 40))
    pages, cached = pool.match_prefix(prompt)
    assert (pages, cached) == ([], 0)
    pool.release(prompt)
    chain = pool.extend_chain([], 40)
    assert len(chain) == 3 and pool.stats()["pages_referenced"] == 3
    pool.commit_chain(prompt, chain)
    pool.release_slot(chain)
    hit, cached = pool.match_prefix(prompt + [7])
    assert cached == 32 and hit == chain[:2]        # the two full pages
    pool.release(prompt + [7])
    # a K/V pool of the llama family keeps its two arrays and its names
    kv = PrefixKVPool(get_config("tiny-llama"), num_pages=8, page_size=16,
                      force_python_native=True)
    assert kv.cache_operands() == (kv.k_pool, kv.v_pool)
    assert kv.stats()["page_layout"] == "kv"
    assert kv.stats()["cache_bytes_per_token"] == 2 * 2 * 2 * 16 * 2


def test_the_latent_pool_saves_and_restores_a_chain():
    """(g) The preemption movers carry the one array: what comes back to
    fresh pages is what was saved, in every layer."""
    pool = PrefixKVPool(CFG, num_pages=12, page_size=16,
                        force_python_native=True)
    chain = pool.extend_chain([], 40)
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.standard_normal((3, 3, 16, 128)), jnp.bfloat16)
    pool.latent_pool = pool.latent_pool.at[:, jnp.asarray(chain)].set(rows)
    saved = pool.save_chain_to_host(chain)
    assert len(saved) == 1 and saved[0].shape == (3, 3, 16, 128)
    pool.release_slot(chain)
    pool.latent_pool = jnp.zeros_like(pool.latent_pool)
    back = pool.restore_chain_from_host(saved)
    assert len(back) == 3 and pool.stats()["pages_referenced"] == 3
    np.testing.assert_array_equal(
        np.asarray(pool.latent_pool[:, jnp.asarray(back)], np.float32),
        np.asarray(rows, np.float32))
    with pytest.raises(ValueError, match="kv-head axis"):
        pool.export_pages(back)
    with pytest.raises(ValueError, match="kv-head axis"):
        pool.import_pages(saved)
    with pytest.raises(ValueError, match="kv-head axis to shard"):
        PrefixKVPool(CFG, num_pages=12, page_size=16, sharding=object(),
                     force_python_native=True)


@pytest.mark.parametrize("over,says", [
    (dict(scheduler_spec_k=2), "scheduler_spec_k"),
    (dict(pd_role="prefill"), "pd_role"),
    (dict(pd_role="decode"), "pd_role"),
    (dict(tp=2), "tp > 1"),
])
def test_modes_that_lack_one_named_thing_are_refused_at_build(over, says):
    """(g) PD export, tp > 1 and speculation die typed at BUILD."""
    with pytest.raises(ValueError, match=says):
        ContinuousBatchingEngine(_cfg(**over), seed=0)


# ---------------------------------------------------------------- the programs
def test_a_greedy_answer_is_the_same_whatever_rides_beside_it():
    """Alone, beside three others admitted with it, and with neighbours
    arriving while it decodes: chunks of a long prompt as mixed steps beside
    running rows, every row on its own pages of the one latent pool."""
    mine = _prompt(4, 50)
    others = [_prompt(5 + i, 9 + 17 * i) for i in range(3)]
    alone, _ = _run(_cfg(), [mine], max_tokens=20)
    beside, _ = _run(_cfg(), [mine, *others], max_tokens=20)
    late, sched = _run(_cfg(decode_chunk=3), [mine, *others], max_tokens=20,
                       stagger_s=0.05)
    assert len(alone.tokens[0]) == 20 and alone.finishes[0] == "length"
    assert beside.tokens[0] == alone.tokens[0] == late.tokens[0]
    assert max(alone.tokens[0]) < CFG.vocab_rows == 256   # over the slice
    assert sched.mixed_rounds >= 3 and sched.decode_rounds > sched.mixed_rounds
    pool = sched.pool.stats()
    assert pool["pages_referenced"] == 0 and pool["orphan_pages"] == 0


def test_a_shared_prefix_is_served_from_the_latent_pages():
    """The radix tree hands a second request the first one's full pages: a
    page-table alias, whatever a page holds."""
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
        assert sched.pool.stats()["prefill_tokens_saved"] >= 32
    finally:
        sched.shutdown()
    assert col.tokens[0] == col.tokens[1] == first.tokens[0]


@pytest.mark.parametrize("depth", [0, 2])
def test_preempt_and_resume_through_the_latent_pool(depth):
    """Pool pressure while the stream decodes: its chain goes to the host as
    ONE array and comes back, and the answer is the uninterrupted one."""
    prompt = _prompt(6, 21)
    cfg = _cfg(max_batch=2, prefix_cache_pages=64, prefix_page_size=4,
               decode_chunk=3, decode_lookahead=depth)
    want, _ = _run(cfg, [prompt], max_tokens=30)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(1)
    try:
        orig_extend, armed = sched.pool.extend_chain, threading.Event()

        def flaky_extend(chain, needed):
            if armed.is_set() and sched.preemptions == 0:
                raise MemoryError("injected pool pressure")
            return orig_extend(chain, needed)

        sched.pool.extend_chain = flaky_extend
        sched.submit(prompt, SamplingParams(max_tokens=30), col.emit_for(
            0, then=lambda ev: len(col.tokens[0]) >= 10 and armed.set()))
        assert col.done.wait(240), (col.tokens, sched.stats())
    finally:
        sched.shutdown()
    assert sched.preemptions >= 1, "injected pressure never preempted"
    assert col.tokens == want.tokens


def test_the_counters_of_a_chips_share():
    """/metrics: assignments routed and those on held experts, held experts
    touched over held experts offered, pages walked over pages offered; the
    round records carry ``local_assignments``; the tenants' rows and the
    pool's stats the page layout."""
    before = {s: _counter(s) for s in SERIES}
    col, sched = _run(_cfg(decode_lookahead=0), [_prompt(9, 18)],
                      max_tokens=13)
    d = {s.removeprefix("llm_").removesuffix("_total"):
         _counter(s) - before[s] for s in SERIES}
    K, layers, held = CFG.experts_per_token, CFG.num_moe_layers, 4
    assert layers == 2 and CFG.experts_local == held
    # one mixed step of 4 slots + a lane of 32 positions, then 3 chunks of 4
    forwards = 1 + 3 * 4
    assert d["moe_experts_offered"] == forwards * layers * held
    # the decode-only pair leaves out the mixed step, whose lane of 32
    # positions touches more of the held experts than a step of 4 rows
    assert d["moe_decode_experts_offered"] == 3 * 4 * layers * held
    assert 0 < d["moe_decode_experts_touched"] < d["moe_experts_touched"]
    tokens = (4 + 32) + 3 * 4 * 4
    assert d["moe_assignments"] == tokens * K * layers
    assert 0 < d["moe_assignments_local"] < d["moe_assignments"]
    assert 0 < d["moe_experts_touched"] <= d["moe_experts_offered"]
    assert 0 < d["attn_pages_walked"] < d["attn_pages_offered"]
    assert d["attn_pages_offered"] == forwards * CFG.num_layers * 4 * 8
    rounds = list(sched.round_timings)
    assert all("local_assignments" in r for r in rounds)
    assert sum(r["local_assignments"] for r in rounds) == \
        d["moe_assignments_local"]
    st = sched.stats()["prefix_cache"]
    assert st["page_layout"] == "latent"
    assert st["cache_bytes_per_token"] == CFG.cache_bytes_per_token() == 768
    assert len(col.tokens[0]) == 13


def test_the_ragged_walk_is_counted_at_a_mixed_steps_dispatch():
    """/metrics ``llm_ragged_pages_walked_total`` and
    ``llm_ragged_trips_total``, the round records' ``ragged_pages`` /
    ``ragged_trips`` and the ``llm.prefill_chunk`` span's attributes: what
    the ragged kernel copies and attends over for a prompt's chunks, counted
    from the lane's operands by the kernel's own span (``ragged_walk``: the
    kernel's side of the equality is tests/test_mla_attention.py's)."""
    from cyberfabric_core_tpu.modkit.telemetry import (
        Span, SpanExporter, Tracer, get_global_tracer, set_global_tracer)
    from cyberfabric_core_tpu.ops.mla_attention import ragged_trip_pages

    class Collect(SpanExporter):
        spans: list = []

        def export(self, span: Span, duration_ms: float) -> None:
            self.spans.append(span)

    names = ("llm_ragged_pages_walked_total", "llm_ragged_trips_total")
    before = {s: _counter(s) for s in names}
    prev = get_global_tracer()
    set_global_tracer(Tracer(exporter=Collect()))
    sched = ContinuousBatchingEngine(_cfg(decode_lookahead=0), seed=0)
    col = _Collector(1)
    try:
        sched.submit(_prompt(9, 50), SamplingParams(max_tokens=5),
                     col.emit_for(0),
                     trace="00-" + "ab" * 16 + "-" + "cd" * 8 + "-01")
        assert col.done.wait(240), sched.stats()
        time.sleep(0.2)
    finally:
        sched.shutdown()
        set_global_tracer(prev)
    d = {s: _counter(s) - before[s] for s in names}
    # a prompt of 50 in chunks of 32 + 18, pages of 16, one q-block a chunk:
    # keys 0..31 are 2 pages, keys 0..49 are 4, in each of the 3 layers, and
    # a trip of 16 pages takes either whole
    assert ragged_trip_pages(16, None, 32) == 16 and CFG.num_layers == 3
    assert d == {names[0]: 3 * (2 + 4), names[1]: 3 * (1 + 1)}
    mixed = [r for r in sched.round_timings if r["chunk_tokens"]]
    assert [(r["ragged_pages"], r["ragged_trips"]) for r in mixed] == \
        [(6, 3), (12, 3)]
    assert not any("ragged_pages" in r for r in sched.round_timings
                   if not r["chunk_tokens"])
    chunks = [s.attributes for s in Collect.spans
              if s.name == "llm.prefill_chunk"]
    assert [(a["tokens"], a["ragged_pages"], a["ragged_trips"])
            for a in chunks] == [(32, 6, 3), (18, 12, 3)]


COMPACT = ("llm_moe_layer_forwards_compact_total",
           "llm_moe_layer_forwards_total")


def test_a_served_share_counts_its_compact_expert_layers():
    """/metrics: every expert layer of every forward is counted, and all of
    them as compact where the held assignments fit the capacity (here every
    row is inside it: a share of a quarter). The benchmark's
    ``moe_compact_share`` reads the pair from a window's two scrapes through
    the ``counter`` kind, by its data file's own arguments."""
    from benchmark import layer_readers
    from cyberfabric_core_tpu.models.kimi_k2 import STEP_COUNTERS

    assert STEP_COUNTERS[3:5] == ("compact", "forwards")
    assert _moe_series(STEP_COUNTERS)[3:5] == COMPACT
    start = {s: _counter(s) for s in COMPACT}
    _run(_cfg(decode_lookahead=0), [_prompt(9, 18)], max_tokens=13)
    end = {s: _counter(s) for s in COMPACT}
    forwards = 1 + 3 * 4            # one mixed step, then 3 chunks of 4
    assert end[COMPACT[1]] - start[COMPACT[1]] == \
        forwards * CFG.num_moe_layers
    assert end[COMPACT[0]] - start[COMPACT[0]] == \
        forwards * CFG.num_moe_layers
    metric = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                         / "layer_metrics/moe_compact_share.json").read_text())
    assert metric["kind"] == "counter"
    args = {k: v for k, v in metric.items() if k not in ("kind", "what")}
    assert (args["series"], args["over"]) == COMPACT
    ctx = {"scrapes": {"start": start, "end": end}}
    assert layer_readers.counter(ctx, **args) == 1.0
    # a program without the series (this PR's parent): nothing to read
    assert layer_readers.counter({"scrapes": {"start": {}, "end": {}}},
                                 **args) is None


def test_an_overflowing_step_takes_every_row_and_is_counted(monkeypatch,
                                                            retrace):
    """A thin share (3 of 64 experts) whose router is biased onto the held
    experts: a mixed step of 4 + 64 tokens holds 3 x 68 = 204 assignments a
    layer where the capacity is 128, so its expert layers take every row (the
    counters say so) and the answer is the one the same engine gives with no
    capacity at all; a decode step's 16 assignments are all inside theirs."""
    from cyberfabric_core_tpu.models import MODEL_CONFIGS, llama

    thin = dataclasses.replace(CFG, name="tiny-kimi-share3of64",
                               num_experts=64, experts_held=3)
    monkeypatch.setitem(MODEL_CONFIGS, thin.name, thin)
    assert llama.moe_capacity((4 + 64) * thin.experts_per_token, thin) == 128

    def served():
        sched = ContinuousBatchingEngine(
            _cfg(model=thin.name, decode_lookahead=0,
                 prefill_budget_tokens=64), seed=0)
        layers = dict(sched.params["layers"])
        layers["router_bias"] = layers["router_bias"].at[
            :, thin.expert_offset: thin.expert_offset + 3].set(100.0)
        sched.params = {**sched.params, "layers": layers}
        col = _Collector(1)
        before = {s: _counter(s) for s in COMPACT + (
            "llm_moe_assignments_local_total", "llm_moe_assignments_total")}
        try:
            sched.submit(_prompt(11, 70), SamplingParams(max_tokens=9),
                         col.emit_for(0))
            assert col.done.wait(240), sched.stats()
            time.sleep(0.2)
        finally:
            sched.shutdown()
        return col.tokens[0], {s: _counter(s) - v for s, v in before.items()}

    tokens, d = served()
    layers = thin.num_moe_layers
    # two mixed steps (64 + 6 prompt tokens), then 2 chunks of 4 steps
    assert d[COMPACT[1]] == (2 + 2 * 4) * layers
    assert d[COMPACT[1]] - d[COMPACT[0]] == layers      # the 68-token step
    # every token chose all three held experts: nothing was dropped
    assert d["llm_moe_assignments_local_total"] * 4 == \
        d["llm_moe_assignments_total"] * 3
    monkeypatch.setattr(llama, "moe_capacity", lambda n, cfg: n)
    retrace()
    assert served()[0] == tokens and len(tokens) == 9
