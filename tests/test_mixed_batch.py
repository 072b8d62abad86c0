"""Mixed-batch scheduling tests (ragged chunked prefill piggybacked into
decode rounds — Sarathi-style, one dispatch for prefill + decode rows).

The golden contracts:

- **Greedy identity with an independent reference.** With temperature 0
  (the serving default) every streamed token is the argmax of a float32
  teacher-forced dense forward, at prompts chosen clear of ties, whatever
  the chunk budget cut the prompts into.
- **Identity across schedules.** Lookahead on/off, preempt mid-prefill, and
  injected faults never change any stream (the PR 2/3 invariants carry
  over).
- **No head-of-line blocking.** A prefill storm is consumed in per-round
  chunks bounded by prefill_budget_tokens; in-flight decode streams keep
  emitting between chunks instead of stalling behind a cold-prefill drain.
"""

import threading
import time

import numpy as np
import pytest

from cyberfabric_core_tpu.modkit import failpoints as fp
from cyberfabric_core_tpu.modkit.flight_recorder import default_recorder
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine


def _cfg(**over):
    base = dict(model="tiny-llama", max_seq_len=256, max_batch=4,
                decode_chunk=4,
                prefix_cache_pages=80, prefix_page_size=16,
                prefill_budget_tokens=24)
    base.update(over)
    return EngineConfig(**base)


class _Collector:
    def __init__(self, n: int):
        self.tokens: dict[int, list[int]] = {i: [] for i in range(n)}
        self.finishes: dict[int, str] = {}
        self.order: list[tuple[int, int]] = []
        self.done = threading.Event()
        self._lock = threading.Lock()
        self._n = n

    def emit_for(self, i: int):
        def emit(ev):
            with self._lock:
                if ev.token_id >= 0:
                    self.tokens[i].append(ev.token_id)
                    self.order.append((i, ev.token_id))
                if ev.finished:
                    self.finishes[i] = ev.finished
                    if len(self.finishes) == self._n:
                        self.done.set()
        return emit


def _run_streams(cfg, prompts, samplings, timeout=240.0, stagger_s=0.0,
                 request_ids=None):
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(len(prompts))
    try:
        for i, (p, s) in enumerate(zip(prompts, samplings)):
            if stagger_s:
                time.sleep(stagger_s)
            rid = request_ids[i] if request_ids else None
            sched.submit(p, s, col.emit_for(i), request_id=rid)
        assert col.done.wait(timeout), (col.finishes, sched.stats())
        stats = sched.stats()
    finally:
        sched.shutdown()
    return col, stats


#: The golden test's prompts, one rng seed a row (row i holds 12 + 9 i tokens):
#: each the first seed whose greedy answer keeps its two best logits at least
#: _TIE_CLEARANCE apart at all 24 steps, by a float32 dense reference, and
#: holds 8 tokens or more that differ. The served programs compute in bf16,
#: which resolves 0.0156 at these logits (2 to 4): a step that sits nearer to
#: a tie than that falls either way, and says nothing of the scheduler.
#: (Prompts drawn without this check diverged from the reference in 3 rows of
#: 6, all at reference gaps of 0.002 to 0.008.)
_CLEAR_PROMPT_SEEDS = (67, 47, 617, 868, 280, 456)
_TIE_CLEARANCE = 0.0625


def _assert_clear_of_ties(prompts, streams):
    """Every streamed token is the float32 reference's argmax given what came
    before it, by _TIE_CLEARANCE or more: the prompts exercise bit-identity,
    not rounding luck. One teacher-forced dense forward a row."""
    import jax
    import jax.numpy as jnp

    from cyberfabric_core_tpu.models.configs import get_config
    from cyberfabric_core_tpu.models.llama import (forward, init_cache,
                                                   init_params, lm_head_logits)
    from cyberfabric_core_tpu.ops.rope import rope_frequencies

    cfg = get_config("tiny-llama")
    params = jax.tree.map(  # the engines' seed-0 weights, widened
        lambda x: x.astype(jnp.float32),
        init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    rope = rope_frequencies(cfg.head_dim, cfg.max_position, cfg.rope_theta)
    for row, (prompt, stream) in enumerate(zip(prompts, streams)):
        ids = jnp.asarray([prompt + stream[:-1]], jnp.int32)
        T = ids.shape[1]
        hidden, _ = forward(
            params, cfg, ids, jnp.arange(T, dtype=jnp.int32)[None],
            init_cache(cfg, 1, T, jnp.float32), jnp.zeros((1,), jnp.int32), rope)
        logits = np.asarray(lm_head_logits(params, cfg, hidden))[0]
        for step, tok in enumerate(stream):
            at = logits[len(prompt) - 1 + step]
            second, first = np.partition(at, -2)[-2:]
            assert tok == int(at.argmax()) and first - second >= _TIE_CLEARANCE, (
                f"row {row} step {step}: streamed {tok}, reference "
                f"{int(at.argmax())} by {first - second:.4f}: pick this row "
                "another seed (see _CLEAR_PROMPT_SEEDS)")


@pytest.mark.parametrize("budget", [16, 512],
                         ids=["several-chunks-a-prompt", "one-lane-a-prompt"])
def test_mixed_streams_match_the_float32_reference_greedy(budget):
    """THE golden test: greedy streams of staggered arrivals, prompts riding
    decode rounds as chunks, against a float32 dense forward that shares no
    program with the scheduler — and the run must actually piggyback chunks
    (non-vacuous)."""
    prompts = [np.random.default_rng(seed).integers(3, 900, 12 + 9 * i).tolist()
               for i, seed in enumerate(_CLEAR_PROMPT_SEEDS)]
    samplings = [SamplingParams(max_tokens=24) for _ in range(6)]

    col, stats = _run_streams(_cfg(prefill_budget_tokens=budget), prompts,
                              samplings, stagger_s=0.01)

    _assert_clear_of_ties(prompts, [col.tokens[i] for i in range(6)])
    assert set(col.finishes.values()) == {"length"}
    pipe = stats["pipeline"]
    assert pipe["mixed_rounds"] >= 1
    assert pipe["prefill_chunks"] >= sum(
        -(-len(p) // budget) for p in prompts)
    assert pipe["chunked_prefill_tokens"] == sum(len(p) for p in prompts)


def _lane_chunks(lengths, budget):
    """The chunk sizes FIFO lane steps take: each prompt whole, in turn, in
    chunks of the budget."""
    return [min(budget, n - at) for n in lengths for at in range(0, n, budget)]


@pytest.mark.parametrize("n", [2, 5])
def test_prompts_admitted_in_one_round_take_consecutive_steps_fifo(n):
    """``n`` prompts are all pending when the loop starts, so one admission
    pass takes them all. The lane holds one slot's chunk a step: the slots
    take consecutive mixed steps in admission order, each step within the
    budget and computing ``slots + width`` positions, and every stream is
    the float32 reference's greedy answer."""
    prompts = [np.random.default_rng(seed).integers(3, 900, 12 + 9 * i).tolist()
               for i, seed in enumerate(_CLEAR_PROMPT_SEEDS)][:n]
    samplings = [SamplingParams(max_tokens=24) for _ in range(n)]
    cfg = _cfg(max_batch=6)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(n)
    start, sched.start = sched.start, lambda: None      # hold the loop
    try:
        for i, (p, s) in enumerate(zip(prompts, samplings)):
            sched.submit(p, s, col.emit_for(i))
        sched.start = start
        sched.start()
        assert col.done.wait(240), (col.finishes, sched.stats())
        timings = list(sched.round_timings)
        stats = sched.stats()["pipeline"]
    finally:
        sched.shutdown()
    _assert_clear_of_ties(prompts, [col.tokens[i] for i in range(n)])
    assert set(col.finishes.values()) == {"length"}

    budget = cfg.prefill_budget_tokens
    mixed = [t for t in timings if t["mixed"]]
    assert [t["chunk_tokens"] for t in mixed] == _lane_chunks(
        [len(p) for p in prompts], budget)
    for t in mixed:
        assert t["positions"] == 6 + cfg.bucket_for(t["chunk_tokens"])
    assert stats["mixed_positions"] == sum(t["positions"] for t in mixed)
    # one slot's chunk a step: the first prompt's first token leaves before
    # the second prompt's prefill is through
    assert col.order.index((0, col.tokens[0][0])) < min(
        col.order.index((i, col.tokens[i][0])) for i in range(1, n))


def test_mixed_lookahead_vs_sync_bit_identical_seeded():
    """The PR 2 pipeline invariant carries into mixed batching: lookahead
    on/off never changes a stream, including seeded sampling — rounds with
    prefill chunks fall back deterministically (no lookahead spans them) and
    pure-decode rounds keep overlapping."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(3, 900, 30 + 7 * i).tolist() for i in range(4)]
    samplings = [SamplingParams(max_tokens=40, temperature=0.8, top_p=0.9,
                                seed=500 + i) for i in range(4)]
    ahead_col, ahead_stats = _run_streams(
        _cfg(decode_lookahead=True), prompts, samplings, stagger_s=0.01)
    sync_col, _ = _run_streams(
        _cfg(decode_lookahead=False), prompts, samplings, stagger_s=0.01)
    assert ahead_col.tokens == sync_col.tokens
    assert ahead_col.finishes == sync_col.finishes
    assert ahead_stats["pipeline"]["mixed_rounds"] >= 1
    assert ahead_stats["pipeline"]["lookahead"]["used"] > 0, \
        "lookahead never engaged after prefill drained — vacuous"


@pytest.mark.parametrize("depth", [1, 2])
def test_an_arrivals_lane_runs_off_an_empty_ring_and_rebuilds_it(depth):
    """A mixed round never meets chunks in flight: the arrival is admitted
    only once the ring has drained (nothing discarded), its one-chunk prompt
    runs as the lane of the next step, and the ring is rebuilt off that
    dispatch (``depth`` in the round's record), so the round after it is
    served by a chunk already in flight."""
    rng = np.random.default_rng(8)
    eng = ContinuousBatchingEngine(_cfg(decode_lookahead=depth), seed=0)
    eng.start = lambda: None    # the test makes the loop's passes
    col = _Collector(2)
    try:
        eng.submit(rng.integers(3, 900, 10).tolist(),
                   SamplingParams(max_tokens=60), col.emit_for(0))
        while not (eng.active.any() and len(eng._ring) == depth):
            eng._loop_pass()
        eng.submit(rng.integers(3, 900, 20).tolist(),
                   SamplingParams(max_tokens=8), col.emit_for(1))
        rings, records = [], len(eng.round_timings)
        while not col.tokens[1]:
            rings.append(len(eng._ring))
            eng._loop_pass()
        # depth drains of the chunks in flight, then the lane off an empty
        # ring, which chains depth chunks behind itself (the record of the
        # drain that emptied the ring closes in the lane's pass: its emit
        # was held for the lane's launch)
        assert rings == list(range(depth, -1, -1))
        kinds = [(r["kind"], r["depth"])
                 for r in list(eng.round_timings)[records:]]
        assert kinds == [("decode", depth - i - 1)
                         for i in range(depth)] + [("mixed", depth)]
        assert eng._lookahead_stats["discarded"] == 0
        eng._loop_pass()
        assert eng.round_timings[-1]["lookahead"] is True
        while not col.done.is_set():
            eng._loop_pass()
    finally:
        eng.shutdown()
    assert len(col.tokens[0]) == 60 and len(col.tokens[1]) == 8


def test_prefill_storm_rounds_bounded_by_chunk_budget():
    """A storm of long prompts must be consumed in budget-bounded chunks: no
    round prefills more than prefill_budget_tokens, and the in-flight decode
    stream keeps emitting BETWEEN storm chunks."""
    budget = 32
    cfg = _cfg(max_batch=6, prefill_budget_tokens=budget)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    n_storm = 5
    col = _Collector(n_storm + 1)
    rng = np.random.default_rng(3)
    try:
        # one in-flight stream, decoding
        sched.submit(rng.integers(3, 900, 8).tolist(),
                     SamplingParams(max_tokens=120), col.emit_for(0))
        deadline = time.monotonic() + 60
        while not col.tokens[0] and time.monotonic() < deadline:
            time.sleep(0.005)
        assert col.tokens[0], "stream 0 never started"
        # storm: long prompts, each needing several chunks
        for i in range(1, n_storm + 1):
            sched.submit(rng.integers(3, 900, 100 + i).tolist(),
                         SamplingParams(max_tokens=4), col.emit_for(i))
        assert col.done.wait(240), (col.finishes, sched.stats())
        stats = sched.stats()
        timings = list(sched.round_timings)
    finally:
        sched.shutdown()
    mixed = [t for t in timings if t.get("mixed")]
    assert mixed, "storm never produced a mixed round"
    # the satellite claim: no decode round is delayed by more than one
    # chunk budget worth of prefill work
    assert max(t["chunk_tokens"] for t in mixed) <= budget
    assert stats["pipeline"]["prefill_chunks"] >= n_storm * 3, \
        "100+-token prompts at budget 32 must take >= 4 chunks each"
    # a round computes its decode rows and ONE slot's chunk at its width,
    # never every slot at that width; most of what it computes is a token
    assert all(t["positions"] == 6 + cfg.bucket_for(t["chunk_tokens"])
               for t in mixed)
    assert stats["pipeline"]["mixed_useful_share"] > 0.5
    # stream 0 interleaves with the storm: its tokens appear between the
    # storm requests' first tokens rather than only after the drain
    first_pos = {}
    s0_positions = []
    for pos, (req, _tok) in enumerate(col.order):
        if req == 0:
            s0_positions.append(pos)
        elif req not in first_pos:
            first_pos[req] = pos
    storm_firsts = sorted(first_pos.values())
    between = sum(1 for a, b in zip(storm_firsts, storm_firsts[1:])
                  if any(a < p < b for p in s0_positions))
    assert between >= 1, \
        "stream 0 emitted nothing between storm prefills — HOL blocking"


def test_preempt_mid_chunked_prefill_stream_identical():
    """An injected MemoryError on a prefill-chunk page growth preempts the
    request mid-prefill (pages saved to host); after resume the stream must
    be bit-identical to the unfaulted run, and the pool must not leak refs.
    The pool is out of pages for two asks in a row: one made for a step
    planned ahead of a drain launches nothing, and it is the next round's
    own ask, on committed state, that preempts."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(3, 900, 40 + 5 * i).tolist() for i in range(3)]
    samplings = [SamplingParams(max_tokens=16) for _ in range(3)]
    cfg = _cfg(prefill_budget_tokens=16)

    base_col, _ = _run_streams(cfg, prompts, samplings)

    fp.configure(0)
    fp.arm("scheduler.prefill_chunk",
           {"kind": "raise", "exc": "MemoryError", "mode": "once",
            "after": 2, "n": 2})
    try:
        sched = ContinuousBatchingEngine(cfg, seed=0)
        col = _Collector(3)
        for i, (p, s) in enumerate(zip(prompts, samplings)):
            sched.submit(p, s, col.emit_for(i))
        assert col.done.wait(240), (col.finishes, sched.stats())
        stats = sched.stats()
        time.sleep(0.2)  # let the scheduler thread finish slot teardown
        pool_stats = sched.pool.stats()
        sched.shutdown()
    finally:
        fp.disarm("scheduler.prefill_chunk")
    assert stats["preemptions"] >= 1, "the fault never forced a preempt"
    assert col.tokens == base_col.tokens
    assert col.finishes == base_col.finishes
    assert pool_stats["pages_referenced"] == 0
    assert pool_stats["orphan_pages"] == 0


def test_prefix_hit_chunks_only_the_suffix():
    """A second request sharing a long page-aligned prefix must chunk-prefill
    only its uncached suffix: the chain starts from the cached pages (the
    commit of request 1's chunks made them shareable) and the hit-rate stats
    record the skip."""
    rng = np.random.default_rng(13)
    head = rng.integers(3, 900, 64).tolist()  # 4 full pages of 16
    p1 = head + rng.integers(3, 900, 10).tolist()
    p2 = head + rng.integers(3, 900, 12).tolist()
    cfg = _cfg(max_batch=2, prefill_budget_tokens=32)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(2)
    try:
        sched.submit(p1, SamplingParams(max_tokens=8), col.emit_for(0))
        # wait until request 1 fully lands (its pages reach the radix tree)
        deadline = time.monotonic() + 60
        while 0 not in col.finishes and time.monotonic() < deadline:
            time.sleep(0.01)
        sched.submit(p2, SamplingParams(max_tokens=8), col.emit_for(1))
        assert col.done.wait(240), (col.finishes, sched.stats())
        stats = sched.stats()
    finally:
        sched.shutdown()
    pc = stats["prefix_cache"]
    assert pc["prefill_tokens_saved"] >= 64
    assert pc["hits"] >= 1
    assert pc["lookups"] >= 2
    assert 0.0 < pc["hit_rate"] < 1.0
    # the suffix (10..12 tokens + boundary) fits one chunk: request 2 must
    # not have re-chunked the shared 64-token head
    assert stats["pipeline"]["chunked_prefill_tokens"] \
        <= len(p1) + (len(p2) - 64)


@pytest.mark.parametrize("shares_head", [False, True],
                         ids=["cold", "prefix-hit"])
def test_an_admission_that_fails_after_its_match_gives_everything_back(
        shares_head):
    """An admission that raises AFTER the chain took its hold on the matched
    pages (here: the slot's rows cannot be written) ends that request
    alone with ``error``: the slot is free again, its page-table row is
    zero, no page stays referenced, and the next request is served — from
    the same cached head where there is one."""
    rng = np.random.default_rng(17)
    head = rng.integers(3, 900, 32).tolist()  # 2 full pages of 16
    first = head + rng.integers(3, 900, 9).tolist()
    prompt = (head if shares_head else rng.integers(3, 900, 32).tolist()) \
        + rng.integers(3, 900, 7).tolist()
    sched = ContinuousBatchingEngine(_cfg(max_batch=2), seed=0)
    col = _Collector(3)

    def wait_idle_after(request: int):
        """The request has its terminal and the loop has let its slot go."""
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not (
                request in col.finishes
                and len(sched._free_slots) == sched.n_slots
                and all(s is None for s in sched.slots)):
            time.sleep(0.01)
        assert request in col.finishes, sched.stats()

    try:
        sched.submit(first, SamplingParams(max_tokens=6), col.emit_for(0))
        wait_idle_after(0)
        patch = sched._set_slot_rows

        def fails_once(*a, **k):
            sched._set_slot_rows = patch
            raise RuntimeError("injected: the slot's rows not written")

        sched._set_slot_rows = fails_once
        sched.submit(prompt, SamplingParams(max_tokens=6), col.emit_for(1))
        wait_idle_after(1)
        table_after = sched.page_table.copy()
        refs_after = sched.pool.stats()["pages_referenced"]
        sched.submit(prompt, SamplingParams(max_tokens=6), col.emit_for(2))
        assert col.done.wait(240), (col.finishes, sched.stats())
    finally:
        sched.shutdown()
    stats = sched.stats()
    assert col.finishes == {0: "length", 1: "error", 2: "length"}
    assert not col.tokens[1] and len(col.tokens[2]) == 6
    assert not table_after.any(), table_after
    assert refs_after == 0
    assert stats["prefix_cache"]["pages_referenced"] == 0
    assert stats["prefix_cache"]["orphan_pages"] == 0
    assert stats["prefix_cache"]["hits"] == (2 if shares_head else 0)
    assert sched._broken is None


def test_fully_cached_prompt_admission_releases_radix_pins():
    """A prompt whose pages are ALL already in the radix tree matches (and
    pins) tree nodes, but match_prefix trims its page list to empty (at
    least one token must prefill for first-token logits) — admission
    must still drop the pin (the LOAD-BEARING release of
    _admit_prefill_slot). A leaked pin makes the node
    permanently unevictable: repeated cache-hit short prompts would shrink
    usable pool capacity to nothing."""
    rng = np.random.default_rng(11)
    prompt = rng.integers(3, 900, 16).tolist()  # exactly one 16-token page
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    try:
        for _ in range(2):  # run 2 is the fully-cached (trimmed) admission
            done = threading.Event()
            sched.submit(prompt, SamplingParams(max_tokens=4),
                         lambda ev: done.set() if ev.finished else None)
            assert done.wait(120), sched.stats()
        pool = sched.pool
        cached = pool.tree.stats()["cached_pages"]
        assert cached >= 1, "prompt page never reached the tree"
        # with every stream finished nothing holds a pin: a full evict must
        # recover every cached page (the test's engine is torn down after,
        # so the raw tree evict needs no pool-bookkeeping reconciliation)
        with pool._tree_lock:
            freed = pool.tree.evict(cached)
        assert len(freed) == cached, \
            f"unevictable pages: freed {len(freed)}/{cached} — pin leaked"
    finally:
        sched.shutdown()


def test_mixed_timeline_shows_prefill_chunks():
    """Flight-recorder satellite: each piggybacked chunk lands one
    prefill_chunk event (mirroring decode_chunk), the terminal prefill event
    carries the chunk count, and the phase stays 'prefill' until the flip."""
    rng = np.random.default_rng(17)
    prompt = rng.integers(3, 900, 50).tolist()
    rid = "req-mixed-timeline"
    cfg = _cfg(prefill_budget_tokens=16)
    col, _ = _run_streams(cfg, [prompt], [SamplingParams(max_tokens=6)],
                          request_ids=[rid])
    rec = default_recorder.lookup(rid)
    assert rec is not None
    kinds = [e["event"] for e in rec["timeline"]]
    n_chunks = kinds.count("prefill_chunk")
    assert n_chunks >= 3, kinds  # 50 tokens / budget 16
    assert "prefill" in kinds
    pf = next(e for e in rec["timeline"] if e["event"] == "prefill")
    assert pf["mixed"] is True and pf["chunks"] == n_chunks
    assert pf["prompt_tokens"] == 50
    # chunk progress is monotonic and ends at the full prompt
    chunk_pos = [e["pos"] for e in rec["timeline"]
                 if e["event"] == "prefill_chunk"]
    assert chunk_pos == sorted(chunk_pos) and chunk_pos[-1] == 50
    assert rec["derived"]["ttft_ms"] is not None


def test_mixed_single_tiny_prompt_single_round():
    """A prompt under the budget takes exactly one chunk (one mixed round) —
    the degenerate case must not regress to multiple dispatches."""
    col, stats = _run_streams(
        _cfg(prefill_budget_tokens=64),
        [[5, 6, 7, 8]], [SamplingParams(max_tokens=5)])
    assert len(col.tokens[0]) == 5
    assert stats["pipeline"]["prefill_chunks"] == 1
    assert stats["pipeline"]["chunked_prefill_tokens"] == 4


def test_mixed_stop_token_on_first_token():
    """The first token sampled at the final chunk can itself be terminal
    (stop set); the flip must emit exactly one token with reason 'stop' and
    release the slot cleanly."""
    rng = np.random.default_rng(19)
    prompt = rng.integers(3, 900, 20).tolist()
    col, stats = _run_streams(
        _cfg(), [prompt],
        [SamplingParams(max_tokens=10, stop_token_ids=tuple(range(512)))])
    assert col.finishes[0] == "stop"
    assert len(col.tokens[0]) == 1
    assert stats["active"] == 0 and stats["prefilling"] == 0


def test_mixed_max_pending_and_accounting_after_storm():
    """After a mixed-mode storm drains: no slot-state, free-slot, page-ref or
    orphan leaks (the faultlab engine_accounting contract, unfaulted)."""
    rng = np.random.default_rng(23)
    prompts = [rng.integers(3, 900, 20 + i).tolist() for i in range(12)]
    samplings = [SamplingParams(max_tokens=6) for _ in range(12)]
    cfg = _cfg(max_batch=3, prefill_budget_tokens=16)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(12)
    try:
        for i, (p, s) in enumerate(zip(prompts, samplings)):
            sched.submit(p, s, col.emit_for(i))
        assert col.done.wait(240), (col.finishes, sched.stats())
        time.sleep(0.2)  # scheduler thread finishes the last slot teardown
        assert len(sched._free_slots) == sched.n_slots
        assert not sched._prefill_slots and not sched._suspended
        pool_stats = sched.pool.stats()
    finally:
        sched.shutdown()
    assert pool_stats["pages_referenced"] == 0
    assert pool_stats["orphan_pages"] == 0
