"""``tiny-motif-share4-4l`` served by the continuous scheduler (no gateway):
a model whose cache is TWO page groups. The layers that attend over
everything keep a row's pages for its whole length; the window layers' pages
live in a group of their own, and a row gives back each one as its committed
length passes it (runtime/paged.py, ``_trim_windows``).

The contract: what the pool frees nothing reads again. Greedy tokens are
compared: a row served beside others through a window group so small that
every freed page is written by another row at once answers as it does alone;
a row preempted to the host with pages already freed, and resumed, answers as
the uninterrupted run. The pool treats every prefix as no match."""

import threading

import numpy as np
import pytest

from cyberfabric_core_tpu.models import get_config, motif
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.paged import PrefixKVPool
from cyberfabric_core_tpu.runtime.scheduler import (ContinuousBatchingEngine,
                                                    _moe_series)
from test_nemotron_h_scheduler import _Collector, _counter, _run

MODEL = "tiny-motif-share4-4l"      # dense, dense, a window layer, a full one
WINDOW, PAGE, BUDGET = 24, 16, 32
SERIES = _moe_series(motif.STEP_COUNTERS) + (
    "llm_attn_pages_walked_total", "llm_attn_pages_offered_total",
    "llm_attn_window_pages_walked_total",
    "llm_attn_window_pages_offered_total", "llm_window_pages_freed_total")


def _cfg(**over):
    base = dict(model=MODEL, max_seq_len=256, max_batch=4, decode_chunk=4,
                prefix_cache_pages=80, prefix_page_size=PAGE,
                prefill_budget_tokens=BUDGET)
    base.update(over)
    return EngineConfig(**base)


def _manual(cfg):
    eng = ContinuousBatchingEngine(cfg, seed=0)
    eng.start = lambda: None    # no thread: the test makes the loop's passes
    return eng


def _prompts(seed=0, sizes=(70, 20, 45)):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 250, n).tolist() for n in sizes]


def test_both_groups_are_built_counted_and_returned():
    """The pools, the table and the gauges' sources are the configuration's:
    1 full layer and 3 window layers of 4, a table of two runs a row; the
    window layers' walk is counted apart and stays a window's worth; every
    page of both groups is back when the requests have left."""
    model = get_config(MODEL)
    assert (model.kv_layers, model.window_layers) == (1, 3)
    before = {s: _counter(s) for s in SERIES}
    tokens, stats, sched = _run(_cfg(quantization="int8"), _prompts(),
                                max_tokens=40)
    d = {s: _counter(s) - before[s] for s in SERIES}
    assert all(len(t) == 40 for t in tokens.values())
    pool = stats["prefix_cache"]
    assert (pool["kv_layers"], pool["window_layers"], pool["model_layers"],
            pool["page_layout"]) == (1, 3, 4, "latent")
    assert len(sched.pool.cache_operands()) == 2
    assert sched.pool.latent_pool.shape[0] == 1
    assert sched.pool.window_pool.shape[:2] == (3, sched._window_pages())
    assert sched._rows.shape[1] > 2 * sched.pmax and sched._tw == 2 * sched.pmax
    # released at finish: both groups whole again, the tables clear
    assert pool["pages_free"] == pool["pages_total"]
    assert pool["window_pages_in_use"] == 0
    assert not sched._tables.any()
    # rows of 110, 60 and 85 tokens passed several window pages each
    assert pool["window_pages_freed"] == d["llm_window_pages_freed_total"] > 8
    # the window layers walk 2-3 pages a row however long it is; the full
    # layer walks the row
    assert d["llm_attn_window_pages_offered_total"] == \
        3 * d["llm_attn_pages_offered_total"]
    assert d["llm_attn_window_pages_walked_total"] < \
        3 * d["llm_attn_pages_walked_total"]
    # no prefix is ever matched, none committed
    assert pool["hits"] == 0 and pool["prefill_tokens_saved"] == 0


def test_the_ragged_walk_counts_each_kind_of_layer_by_its_own_span():
    """``llm_ragged_pages_walked_total`` / ``llm_ragged_trips_total`` sum the
    full layers' walk (a q-block's whole history) and the window layers' (its
    windows' span) over a prompt's chunks: here 1 full layer and 3 window
    layers over one prompt of 70 in chunks of 32, 32 and 6."""
    from cyberfabric_core_tpu.ops.mla_attention import ragged_walk

    names = ("llm_ragged_pages_walked_total", "llm_ragged_trips_total")
    before = {s: _counter(s) for s in names}
    _, _, sched = _run(_cfg(quantization="int8", decode_lookahead=0),
                       _prompts(sizes=(70,)), max_tokens=4)
    d = [_counter(s) - before[s] for s in names]
    want = [0, 0]
    for hist, qlen in ((0, 32), (32, 32), (64, 6)):
        full = ragged_walk([hist], [qlen], 32, PAGE, sched.pmax, None)
        window = ragged_walk([hist], [qlen], 32, PAGE, sched.pmax, WINDOW)
        # the full layer reads every page so far, a window layer 3-4 of them
        assert full[0] == -(-(hist + qlen) // PAGE) and 2 <= window[0] <= 4
        for i in (0, 1):
            want[i] += full[i] + 3 * window[i]
    assert d == want
    mixed = [r for r in sched.round_timings if r["chunk_tokens"]]
    assert [sum(r[k] for r in mixed)
            for k in ("ragged_pages", "ragged_trips")] == want


def test_a_window_page_is_freed_only_when_no_queued_step_reads_it():
    """Pass by pass: before every launch and after every commit, each row's
    live window pages cover the window of its COMMITTED length (what a step
    in flight or launched next may read: its queries sit at or past it), the
    table names scratch left of them, and no page is held twice."""
    eng = _manual(_cfg(decode_lookahead=2))
    col = _Collector(2)
    for i, p in enumerate(_prompts(3, (90, 37))):
        eng.submit(p, SamplingParams(max_tokens=48), col.emit_for(i))
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
            np.testing.assert_array_equal(
                eng.page_table[slot, : len(state.chain)], state.chain)
            held += [p for p in wchain if p]
            seen_freed += first > 0
        assert len(held) == len(set(held)) == \
            eng.pool.window_pages_in_use()
        if col.done.is_set():
            break
    eng._settle()
    assert col.done.is_set() and seen_freed
    assert all(len(t) == 48 for t in col.tokens.values())
    assert eng.stats()["pipeline"]["lookahead"] is not None


def test_a_freed_page_written_by_another_row_leaves_a_rows_tokens_unchanged(
        monkeypatch):
    """The window group cut to what four rows need and no more: every page
    a long row frees is another row's at once. The long row's greedy stream
    equals the one it produces alone in a roomy group."""
    prompts = _prompts(5, (100, 30, 50, 64))
    alone, _, _ = _run(_cfg(), prompts[:1], max_tokens=60)
    monkeypatch.setattr(ContinuousBatchingEngine, "_window_pages",
                        lambda self: 4 * 4 + 2 * 4 + 1)
    together, stats, sched = _run(_cfg(), prompts, max_tokens=60)
    assert together[0] == alone[0]
    assert stats["prefix_cache"]["window_pages_freed"] > 20
    assert stats["prefix_cache"]["window_pages_total"] == 24


def test_cancel_returns_both_groups():
    eng = _manual(_cfg())
    col = _Collector(2)
    ids = [eng.submit(p, SamplingParams(max_tokens=64), col.emit_for(i))
           for i, p in enumerate(_prompts(7, (80, 40)))]
    for _ in range(12):
        eng._loop_pass()
    assert eng.pool.window_pages_in_use() > 0
    for rid in ids:
        eng.cancel(rid)
    for _ in range(6):
        eng._loop_pass()
    eng._settle()
    st = eng.pool.stats()
    assert st["window_pages_in_use"] == 0
    assert st["pages_free"] == st["pages_total"]
    assert not eng._tables.any()
    assert set(col.finishes.values()) == {"cancelled"}


@pytest.mark.parametrize("group", ["extend_chain", "extend_window"])
def test_preempt_with_freed_pages_and_resume_equals_the_uninterrupted_run(
        group):
    """Pool pressure in EITHER page group while a row of 90 + 12 tokens
    decodes behind a lookahead ring (the ring's ``_extend_chain_to`` and the
    capacity sweep's ``_grow_chain`` both meet it): the row is preempted,
    never run with one group's table short. Four of its window pages were
    given back long before; the two it still holds go to the host beside its
    full chain and come back under their logical indices."""
    prompt = _prompts(9, (90,))[0]
    cfg = _cfg(max_batch=2, decode_lookahead=2)
    want, _, _ = _run(cfg, [prompt], max_tokens=40)

    sched = ContinuousBatchingEngine(cfg, seed=0)
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
            if len(col.tokens[0]) == 12:
                armed.set()
        sched.submit(prompt, SamplingParams(max_tokens=40),
                     col.emit_for(0, then=arm))
        assert col.done.wait(240), (col.tokens, sched.stats())
    finally:
        sched.shutdown()
    assert sched.preemptions >= 1, "injected pressure never preempted"
    assert col.tokens == want
    assert sched.pool.window_pages_in_use() == 0


@pytest.mark.parametrize("group", ["extend_chain", "extend_window"])
@pytest.mark.parametrize("site", ["_extend_chain_to", "_grow_chain",
                                  "_grow_chain_prefill"])
def test_growth_takes_both_groups_pages_or_neither(group, site):
    """A page group that runs out while the other still has pages leaves
    BOTH chains, both table runs and both allocators as they were, at each
    of the growth sites (the capacity sweep, whose mandatory chunk is
    covered here, skips the ring's horizon without a word); the guards then
    still see the need, so the next call grows both."""
    eng = _manual(_cfg(max_batch=2))
    col = _Collector(1)
    eng.submit(_prompts(11, (40,))[0], SamplingParams(max_tokens=64),
               col.emit_for(0))
    for _ in range(40):
        eng._loop_pass()
        state = eng.slots[0]
        if state is not None and state.phase != "prefill" \
                and len(col.tokens[0]) >= 4:
            break
    eng._settle()
    slot, state = 0, eng.slots[0]
    held = len(state.chain)
    assert held == len(state.wchain) == eng._pages_held(state)
    target = (held + 2) * PAGE

    def grow():
        if site == "_grow_chain":
            eng._grow_chain(slot, state, target - int(eng.lengths[slot]))
        else:
            getattr(eng, site)(slot, state, target)

    def snapshot():
        return (list(state.chain), list(state.wchain),
                eng._tables[slot].copy(), eng.pool.allocator.num_free,
                eng.pool.window_allocator.num_free)

    before = snapshot()
    orig = getattr(eng.pool, group)

    def spent(chain, needed):
        raise MemoryError("injected: this group is spent")
    setattr(eng.pool, group, spent)
    if site == "_grow_chain":
        grow()      # the ring's horizon is opportunistic: the chunk is covered
    else:
        with pytest.raises(MemoryError):
            grow()
    after = snapshot()
    assert before[:2] == after[:2] and before[3:] == after[3:]
    np.testing.assert_array_equal(before[2], after[2])
    setattr(eng.pool, group, orig)
    grow()
    assert len(state.chain) == len(state.wchain) == held + 2
    assert all(state.wchain[held:]) and all(state.chain[held:])
    np.testing.assert_array_equal(
        eng.window_table[slot, : held + 2], state.wchain)
    np.testing.assert_array_equal(
        eng.page_table[slot, : held + 2], state.chain)


def test_a_prefix_is_no_match_and_the_second_prompt_answers_as_cold():
    """The rule chosen (runtime/paged.py): the window pages before a
    prefix's boundary are not kept past their row, so the tree is never
    consulted and a prompt that shares 64 tokens prefills all of them."""
    base = _prompts(2, (70,))[0]
    shared = base[:64] + [7, 8, 9, 10, 11, 12, 13, 14, 15]
    cold, _, _ = _run(_cfg(), [shared])
    warm, stats, sched = _run(_cfg(), [base, shared], in_turn=True)
    assert warm[1] == cold[0]
    pool = stats["prefix_cache"]
    assert pool["hits"] == 0 and pool["prefill_tokens_saved"] == 0
    assert sched.pool.peek_prefix_len(shared) == 0


@pytest.mark.parametrize("over,what", [
    (dict(scheduler_spec_k=3), "latent kernels have no such program"),
    (dict(pd_role="prefill"), "a latent page has neither"),
    (dict(tp=2), "no sharding for a latent page"),
])
def test_a_mode_the_page_groups_cannot_carry_is_refused_at_build(over, what):
    with pytest.raises(ValueError, match=what):
        ContinuousBatchingEngine(_cfg(**over), seed=0)


def test_the_pool_refuses_what_it_cannot_do_with_two_groups():
    cfg = get_config(MODEL)
    with pytest.raises(ValueError, match="window page group"):
        PrefixKVPool(cfg, num_pages=9, page_size=PAGE)
    pool = PrefixKVPool(cfg, num_pages=9, page_size=PAGE, window_pages=5)
    with pytest.raises(ValueError, match="one page group"):
        pool.export_pages([1])
    wchain = pool.extend_window([], 60)              # 4 pages: the group
    with pytest.raises(MemoryError):
        pool.extend_window(wchain, 70)
    assert pool.trim_window(wchain, 50) == 1         # 50 - 23 = 27: page 0
    assert wchain[0] == 0 and all(wchain[1:])
    assert pool.trim_window(wchain, 50) == 0
    pool.release_window(wchain)
    assert pool.window_pages_in_use() == 0


def test_a_per_layer_window_on_kv_pages_is_refused_by_the_configuration():
    import dataclasses
    with pytest.raises(ValueError, match="over two page groups"):
        dataclasses.replace(get_config("mistral-7b"), sliding_window_period=4)
    with pytest.raises(ValueError, match="needs a sliding_window"):
        dataclasses.replace(get_config("tiny-kimi"), sliding_window_period=4)
