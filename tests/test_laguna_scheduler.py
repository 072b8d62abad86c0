"""``tiny-laguna-share4`` served by the continuous scheduler (no gateway): a
model whose K/V pages come in TWO page groups and whose two kinds of
attention layer differ in their query heads. The layers that attend over
everything keep a row's pages for its whole length; the window layers' K and
V pages live in a pair of arrays of their own, and a row gives back each one
as its committed length passes it (runtime/paged.py, ``_trim_windows``).

The contract is motif's (tests/test_motif_scheduler.py), on K/V pages: what
the pool frees nothing reads again. Greedy tokens are compared: a greedy
answer repeats beside other rows; a row preempted to the host with pages
already freed, and resumed, answers as the uninterrupted run; the window
group stays at its bound however long a row grows."""

import threading

import jax
import numpy as np
import pytest

from cyberfabric_core_tpu.models import get_config, laguna
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.paged import PrefixKVPool
from cyberfabric_core_tpu.runtime.scheduler import (ContinuousBatchingEngine,
                                                    _moe_series)
from test_nemotron_h_scheduler import _Collector, _counter, _run

MODEL = "tiny-laguna-share4"    # 2 full layers (6 heads), 4 window (9 heads)
WINDOW, PAGE, BUDGET = 8, 4, 16
SERIES = _moe_series(laguna.STEP_COUNTERS) + (
    "llm_attn_pages_walked_total", "llm_attn_pages_offered_total",
    "llm_attn_page_groups_total", "llm_attn_window_pages_walked_total",
    "llm_attn_window_pages_offered_total",
    "llm_attn_window_page_groups_total", "llm_window_pages_freed_total")


def _cfg(**over):
    base = dict(model=MODEL, max_seq_len=128, max_batch=4, decode_chunk=4,
                prefix_cache_pages=140,
                prefix_page_size=PAGE, prefill_budget_tokens=BUDGET,
                quantization="int8")
    base.update(over)
    return EngineConfig(**base)


def _prompts(seed=0, sizes=(40, 12, 25, 40)):
    rng = np.random.default_rng(seed)
    out = [rng.integers(3, 250, n).tolist() for n in sizes]
    out[-1] = list(out[0])          # the same request twice, two rows apart
    return out


@pytest.fixture(scope="module")
def served():
    """Four requests of 24 tokens beside each other (the first and the last
    the same prompt), and the counters' differences over the run."""
    before = {s: _counter(s) for s in SERIES}
    tokens, stats, sched = _run(_cfg(), _prompts(), max_tokens=24)
    return tokens, stats, sched, {s: _counter(s) - before[s] for s in SERIES}


def test_both_groups_are_built_counted_and_returned(served):
    """The four arrays, the table and the gauges' sources are the
    configuration's; ``stats()`` reads both groups; the window layers' walk
    and its grid programs are counted apart and stay a window's worth; every
    page of both groups is back when the requests have left."""
    tokens, stats, sched, d = served
    model = get_config(MODEL)
    assert (model.kv_layers, model.window_layers) == (2, 4)
    assert all(len(t) == 24 for t in tokens.values())
    pool = stats["prefix_cache"]
    assert (pool["kv_layers"], pool["window_layers"], pool["model_layers"],
            pool["page_layout"]) == (2, 4, 6, "kv")
    pools = sched.pool.cache_operands()
    assert [p.shape[:2] for p in pools] == [
        (2, 140), (2, 140), (4, sched._window_pages()),
        (4, sched._window_pages())]
    assert sched.pool.window_k_pool is pools[2]
    assert sched.pool.window_v_pool is pools[3]
    assert sched._tw == 2 * sched.pmax and sched._rows.shape[1] > sched._tw
    # both groups in the bytes: 3 kv heads of 32, K and V, bfloat16
    row = 2 * 3 * 32 * 2
    assert (pool["cache_bytes_per_token"], pool["window_bytes_per_token"]) \
        == (2 * row, 4 * row) == (model.cache_bytes_per_token(),
                                  model.window_bytes_per_token())
    assert pool["window_pool_bytes"] == \
        4 * sched._window_pages() * PAGE * row
    assert pool["cache_bytes"] == pool["pool_bytes"] == \
        pool["window_pool_bytes"] + 2 * 140 * PAGE * row
    # the heads as the weights were built, by kind
    assert sched.attn_heads_built() == (6, 9)
    from cyberfabric_core_tpu.parallel.sharding import abstract_params
    assert jax.tree.structure(sched.params) == jax.tree.structure(
        abstract_params(model, sched.dtype, "int8"))
    # released at finish: both groups whole again, the tables clear
    assert pool["pages_free"] == pool["pages_total"]
    assert pool["window_pages_in_use"] == 0 and not sched._tables.any()
    assert pool["window_pages_freed"] == d["llm_window_pages_freed_total"] > 20
    # the window layers walk 2-3 pages a row however long it is, in one
    # grid program a row a layer; the full layers walk the row
    assert d["llm_attn_window_pages_offered_total"] == \
        2 * d["llm_attn_pages_offered_total"]
    assert d["llm_attn_window_pages_walked_total"] < \
        d["llm_attn_pages_walked_total"]
    assert d["llm_attn_window_page_groups_total"] <= \
        d["llm_attn_window_pages_walked_total"] <= \
        3 * d["llm_attn_window_page_groups_total"]
    assert 0 < d["llm_attn_page_groups_total"] < \
        d["llm_attn_pages_walked_total"]
    # no prefix is ever matched, none committed
    assert pool["hits"] == 0 and pool["prefill_tokens_saved"] == 0
    # the experts' counters ride the drain as in kimi's programs
    assert d["llm_moe_assignments_total"] > d["llm_moe_assignments_local_total"] > 0
    assert d["llm_moe_layer_forwards_total"] % 5 == 0


def test_a_greedy_answer_repeats_and_the_round_records_say_what_was_walked(
        served):
    tokens, _, sched, d = served
    assert tokens[0] == tokens[3]       # the same prompt, two rows apart
    rounds = [r for r in sched.round_timings if "full_pages" in r]
    assert rounds and all(
        r["window_pages"] > 0 and r["window_pages_freed"] >= 0
        for r in rounds)
    assert sum(r["full_pages"] for r in rounds) <= \
        d["llm_attn_pages_walked_total"]
    assert sum(r["window_pages_freed"] for r in rounds) <= \
        d["llm_window_pages_freed_total"]
    assert any(r["window_pages_freed"] for r in rounds)


def test_window_pages_are_freed_as_a_row_grows_and_stay_at_their_bound():
    """Pass by pass: each row's live window pages cover the window of its
    COMMITTED length and nothing left of it, the table names scratch (0)
    where a page was given back, no page is held twice, and a decoding row
    never holds more than the bound the group was sized by."""
    eng = ContinuousBatchingEngine(_cfg(decode_lookahead=2), seed=0)
    eng.start = lambda: None    # no thread: the test makes the loop's passes
    col = _Collector(2)
    rng = np.random.default_rng(3)
    for i, n in enumerate((50, 21)):
        eng.submit(rng.integers(3, 250, n).tolist(),
                   SamplingParams(max_tokens=40), col.emit_for(i))
    model = get_config(MODEL)
    ring = 4 * 3
    bound = model.window_pages(PAGE, ring) + 1
    seen_freed = 0
    for _ in range(400):
        eng._loop_pass()
        held = []
        for slot, state in enumerate(eng.slots):
            if state is None:
                assert not eng._tables[slot].any()
                continue
            length = (state.prefill_pos if state.phase == "prefill"
                      else int(eng.lengths[slot]))
            first = max(length - WINDOW + 1, 0) // PAGE
            wchain = state.wchain
            assert len(wchain) == len(state.chain)
            assert not any(wchain[:first]) and all(wchain[first:])
            np.testing.assert_array_equal(
                eng.window_table[slot, : len(wchain)], wchain)
            if state.phase != "prefill":
                assert sum(1 for p in wchain if p) <= bound
            held += [p for p in wchain if p]
            seen_freed += first > 0
        assert len(held) == len(set(held)) == \
            eng.pool.window_pages_in_use()
        if col.done.is_set():
            break
    eng._settle()
    assert col.done.is_set() and seen_freed
    assert all(len(t) == 40 for t in col.tokens.values())


@pytest.mark.parametrize("group", ["extend_chain", "extend_window"])
def test_preempt_with_freed_pages_and_resume_equals_the_uninterrupted_run(
        served, group):
    """Pool pressure in EITHER page group while a row of 40 + 8 tokens
    decodes: the row is preempted, never run with one group's table short.
    Most of its window pages were given back long before; those it still
    holds go to the host, K and V, beside its full chain and come back under
    their logical indices."""
    want = served[0][0]
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    col = _Collector(1)
    try:
        orig_extend = getattr(sched.pool, group)
        armed = threading.Event()

        def flaky_extend(chain, needed):
            if armed.is_set() and sched.preemptions == 0:
                raise MemoryError("injected pool pressure")
            return orig_extend(chain, needed)

        setattr(sched.pool, group, flaky_extend)

        def arm(ev):
            if len(col.tokens[0]) == 8:
                armed.set()
        sched.submit(_prompts()[0], SamplingParams(max_tokens=24),
                     col.emit_for(0, then=arm))
        assert col.done.wait(240), (col.tokens, sched.stats())
    finally:
        sched.shutdown()
    assert sched.preemptions >= 1, "injected pressure never preempted"
    assert col.tokens[0] == want
    assert sched.pool.window_pages_in_use() == 0


@pytest.mark.parametrize("over,what", [
    (dict(scheduler_spec_k=3), "one page group, one window and one count"),
    (dict(pd_role="prefill"), "the pages of ONE page group"),
    (dict(tp=2), "no sharding for the window page group"),
])
def test_a_mode_the_page_groups_cannot_carry_is_refused_at_build(over, what):
    with pytest.raises(ValueError, match=what):
        ContinuousBatchingEngine(_cfg(**over), seed=0)


def test_the_pool_saves_and_restores_both_arrays_of_the_window_group():
    """``save_window_to_host`` / ``restore_window_from_host`` carry K AND V
    of the live window pages under their logical indices; the PD export is
    refused; a group that is spent says so."""
    import jax.numpy as jnp

    cfg = get_config(MODEL)
    with pytest.raises(ValueError, match="window page group"):
        PrefixKVPool(cfg, num_pages=9, page_size=PAGE)
    pool = PrefixKVPool(cfg, num_pages=9, page_size=PAGE, window_pages=6)
    with pytest.raises(ValueError, match="one page group"):
        pool.export_pages([1])
    wchain = pool.extend_window([], 20)              # 5 pages: the group
    with pytest.raises(MemoryError):
        pool.extend_window(wchain, 24)
    assert pool.trim_window(wchain, 17) == 2         # 17 - 7 = 10: pages 0, 1
    assert wchain[:2] == [0, 0] and all(wchain[2:])
    live = jnp.asarray(wchain[2:])
    pool.window_k_pool = pool.window_k_pool.at[:, live].set(1.5)
    pool.window_v_pool = pool.window_v_pool.at[:, live].set(-2.5)
    saved = pool.save_window_to_host(wchain)
    assert saved["at"] == [2, 3, 4] and len(saved["rows"]) == 2
    pool.release_window(wchain)
    assert pool.window_pages_in_use() == 0
    pool.window_k_pool = jnp.zeros_like(pool.window_k_pool)
    pool.window_v_pool = jnp.zeros_like(pool.window_v_pool)
    back = pool.restore_window_from_host(saved)
    assert back[:2] == [0, 0] and all(back[2:])
    again = jnp.asarray(back[2:])
    assert float(pool.window_k_pool[:, again].min()) == 1.5
    assert float(pool.window_v_pool[:, again].max()) == -2.5
    assert pool.stats()["window_pages_in_use"] == 3


def test_the_ragged_walk_counts_each_kind_of_layer_by_its_window_and_heads():
    """``llm_ragged_pages_walked_total`` / ``llm_ragged_trips_total`` for a
    model with K/V pages (PR 55: its ragged kernel walks a q-block's pages
    inside the program, as the latent kernel does): the full layers' walk (6
    query heads, a q-block's whole history) and the window layers' (9 heads,
    its windows' span) over a prompt's chunks, by the kernel's own span on
    the host; the round records and the ``llm.prefill_chunk`` spans carry
    each step's share."""
    from cyberfabric_core_tpu.modkit.telemetry import (
        Span, SpanExporter, Tracer, get_global_tracer, set_global_tracer)
    from cyberfabric_core_tpu.ops.paged_attention import ragged_walk

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
        sched.submit(_prompts(sizes=(40,))[0], SamplingParams(max_tokens=4),
                     col.emit_for(0),
                     trace="00-" + "ab" * 16 + "-" + "cd" * 8 + "-01")
        assert col.done.wait(240), sched.stats()
    finally:
        sched.shutdown()
        set_global_tracer(prev)
    model = get_config(MODEL)
    want = []
    for hist, qlen in ((0, 16), (16, 16), (32, 8)):
        full = ragged_walk([hist], [qlen], 16, PAGE, sched.pmax, None,
                           model.num_heads)
        window = ragged_walk([hist], [qlen], 16, PAGE, sched.pmax, WINDOW,
                             model.window_heads)
        # the full layers read every page so far, a window layer the 5-6
        # that the windows of 16 queries span (the 3 of 8)
        assert full[0] == (hist + qlen) // PAGE and 3 <= window[0] <= 6
        want.append(tuple(2 * full[i] + 4 * window[i] for i in (0, 1)))
    assert [_counter(s) - before[s] for s in names] == \
        [sum(w[i] for w in want) for i in (0, 1)]
    mixed = [r for r in sched.round_timings if r["chunk_tokens"]]
    assert [(r["ragged_pages"], r["ragged_trips"]) for r in mixed] == want
    assert not any("ragged_pages" in r for r in sched.round_timings
                   if not r["chunk_tokens"])
    chunks = [s.attributes for s in Collect.spans
              if s.name == "llm.prefill_chunk"]
    assert [(a["ragged_pages"], a["ragged_trips"]) for a in chunks] == want
