"""Overlapped decode pipeline tests (scheduler lookahead + admission budget).

The golden contract: with one-chunk lookahead and prefill budgeting
enabled, per-request token streams are BIT-IDENTICAL to the
synchronous scheduler for fixed seeds — speculation and admission shaping may
change *when* device work runs, never *what* any request receives.
"""

import functools
import threading
import time
import uuid

import numpy as np
import pytest

from cyberfabric_core_tpu.modkit.metrics import default_registry
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine


def _cfg(**over):
    base = dict(model="tiny-llama", max_seq_len=256, max_batch=4,
                decode_chunk=4,
                prefix_cache_pages=80, prefix_page_size=16)
    base.update(over)
    return EngineConfig(**base)


class _Collector:
    """Thread-safe per-request stream collection with a global event order."""

    def __init__(self, n: int):
        self.tokens: dict[int, list[int]] = {i: [] for i in range(n)}
        self.finishes: dict[int, str] = {}
        self.order: list[tuple[int, int]] = []  # (request, token)
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


def _run_streams(cfg, prompts, samplings, timeout=240.0,
                 stagger_s: float = 0.0):
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(len(prompts))
    try:
        for i, (p, s) in enumerate(zip(prompts, samplings)):
            if stagger_s:
                time.sleep(stagger_s)
            sched.submit(p, s, col.emit_for(i))
        assert col.done.wait(timeout), (col.finishes, sched.stats())
        stats = sched.stats()
    finally:
        sched.shutdown()
    return col, stats


def _manual(cfg):
    eng = ContinuousBatchingEngine(cfg, seed=0)
    eng.start = lambda: None    # no thread: the test makes the loop's passes
    return eng


def _passes_until(eng, done, limit=600) -> int:
    for n in range(1, limit + 1):
        eng._loop_pass()
        if done():
            return n
    raise AssertionError(f"not reached in {limit} passes: {eng.stats()}")


def test_lookahead_streams_bit_identical_to_sync():
    """The golden test: pipeline on (lookahead + budget) vs the
    synchronous scheduler — same seeds, identical per-request streams. The
    pipeline run must actually overlap (lookahead rounds used), so the
    equivalence cannot pass vacuously."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(3, 900, 10 + 5 * i).tolist() for i in range(6)]
    samplings = [SamplingParams(max_tokens=40, temperature=0.8, top_p=0.9,
                                seed=1000 + i) for i in range(6)]

    pipe_col, pipe_stats = _run_streams(
        _cfg(decode_lookahead=True, prefill_budget_tokens=64),
        prompts, samplings)
    sync_col, sync_stats = _run_streams(
        _cfg(decode_lookahead=False, prefill_budget_tokens=0),
        prompts, samplings)

    assert pipe_col.tokens == sync_col.tokens, "pipelined streams diverged"
    assert pipe_col.finishes == sync_col.finishes
    # the pipelined run really pipelined; the sync run really didn't
    assert pipe_stats["pipeline"]["lookahead"]["used"] > 0
    assert pipe_stats["pipeline"]["overlap_ratio"] > 0
    assert sync_stats["pipeline"]["lookahead_rounds"] == 0


def test_lookahead_discard_on_stop_token_stays_identical():
    """Stop-token finishes are unpredictable to the lookahead heuristic, so
    they exercise the discard-stale-chunk path; streams must still match."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(3, 900, 12).tolist() for _ in range(3)]
    # greedy + a broad stop set makes mid-chunk stop finishes likely
    samplings = [SamplingParams(max_tokens=60, temperature=0.9, seed=50 + i,
                                stop_token_ids=tuple(range(0, 400)))
                 for i in range(3)]
    pipe_col, pipe_stats = _run_streams(
        _cfg(decode_lookahead=True), prompts, samplings)
    sync_col, _ = _run_streams(
        _cfg(decode_lookahead=False), prompts, samplings)
    assert pipe_col.tokens == sync_col.tokens
    assert pipe_col.finishes == sync_col.finishes


def test_prefill_storm_does_not_starve_decode():
    """32 queued arrivals must not stall an in-flight stream: the admission
    budget spreads their prefills across rounds, so the active request keeps
    emitting tokens BETWEEN storm admissions (the unbounded drain admitted
    everything back-to-back before decode resumed)."""
    n_storm = 32
    # slots don't bound the admission cadence (the budget does: 24-token
    # prompts, budget 48 → ≤2 admissions/round → ≥16 admission rounds for the
    # storm); a small batch keeps the CPU decode rounds cheap while storm
    # requests recycle slots fast (max_tokens=4)
    cfg = _cfg(max_batch=12, max_seq_len=256,
               prefill_budget_tokens=48,
               prefix_cache_pages=12 * 16 + 1)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(n_storm + 1)
    rng = np.random.default_rng(11)
    try:
        # request 0: the long-running stream that must keep advancing
        sched.submit(rng.integers(3, 900, 8).tolist(),
                     SamplingParams(max_tokens=120, seed=1), col.emit_for(0))
        # wait until it is decoding
        deadline = time.monotonic() + 60
        while not col.tokens[0] and time.monotonic() < deadline:
            time.sleep(0.01)
        assert col.tokens[0], "stream 0 never started"
        # the storm: 24-token prompts, budget 48 → ≤2 admissions per round
        for i in range(1, n_storm + 1):
            sched.submit(rng.integers(3, 900, 24).tolist(),
                         SamplingParams(max_tokens=4, seed=1 + i),
                         col.emit_for(i))
        assert col.done.wait(240), (len(col.finishes), sched.stats())
        stats = sched.stats()
    finally:
        sched.shutdown()

    assert len(col.tokens[0]) == 120
    assert all(len(col.tokens[i]) == 4 for i in range(1, n_storm + 1))
    # interleave evidence: stream 0 emitted between the first and the last
    # storm admission (their FIRST tokens bracket the admission window)
    with col._lock:
        order = list(col.order)
    first_tok_idx = {}
    for idx, (req, _) in enumerate(order):
        if req not in first_tok_idx:
            first_tok_idx[req] = idx
    storm_first = [first_tok_idx[i] for i in range(1, n_storm + 1)]
    lo, hi = min(storm_first), max(storm_first)
    zero_between = sum(1 for idx in range(lo, hi + 1)
                       if order[idx][0] == 0)
    assert zero_between >= 8, (
        f"stream 0 emitted only {zero_between} tokens during the storm "
        "admission window — prefills drained back-to-back")
    # queue-wait surfaced (satellite: _Pending.enqueued_at is finally read)
    qw = stats["queue_wait_ms"]
    assert qw["count"] == n_storm + 1
    assert qw["max"] > 0 and qw["p50"] >= 0


def test_deep_lookahead_streams_bit_identical_across_depths():
    """THE deep-ring golden: depths 0 (synchronous), 1 (legacy single-chunk
    lookahead) and 3 (epoch ring) produce bit-identical per-request streams
    for mixed greedy + seeded sampling — the ring and device-side
    termination change WHEN device work runs, never what any request
    receives. The deep run must actually run deep (achieved depth ≥ 2 in
    the drain histogram) so the equivalence cannot pass vacuously."""
    rng = np.random.default_rng(21)
    prompts = [rng.integers(3, 900, 10 + 5 * i).tolist() for i in range(6)]
    samplings = [SamplingParams(max_tokens=40,
                                temperature=0.8 if i % 2 else 0.0,
                                top_p=0.9, seed=2000 + i)
                 for i in range(6)]
    results = {}
    for depth in (0, 1, 3):
        results[depth] = _run_streams(
            _cfg(decode_lookahead=depth, prefill_budget_tokens=64),
            prompts, samplings)
    for depth in (1, 3):
        assert results[depth][0].tokens == results[0][0].tokens, \
            f"depth {depth} streams diverged from synchronous"
        assert results[depth][0].finishes == results[0][0].finishes
    deep_pipe = results[3][1]["pipeline"]
    assert deep_pipe["depth"] == 3
    hist = {int(d): n for d, n in deep_pipe["depth_hist"].items()}
    assert hist and max(hist) >= 2, f"ring never ran deep: {hist}"
    sync_pipe = results[0][1]["pipeline"]
    assert sync_pipe["lookahead_rounds"] == 0
    assert set(sync_pipe["depth_hist"]) <= {"0"}  # never ran deep


def test_device_termination_keeps_ring_alive_through_finish():
    """A single request draining at depth 3: its finish (max-tokens bound)
    is predicted ON DEVICE, so no ring entry is ever discarded — the
    pre-ring scheduler discarded the speculative chunk at every finish.
    Also pins the mixed→pure-decode spanning: the request admits through
    chunked prefill, and the ring must engage with ZERO synchronous
    fallback rounds after the flip (every post-prefill round is served by
    a pre-dispatched chunk)."""
    prompt = np.random.default_rng(4).integers(3, 900, 12).tolist()
    eng = _manual(_cfg(decode_lookahead=3))
    col = _Collector(1)
    try:
        eng.submit(prompt, SamplingParams(max_tokens=40, temperature=0.7,
                                          seed=9), col.emit_for(0))
        _passes_until(eng, col.done.is_set)
        stats = eng.stats()
        eng._loop_pass()        # no row runs: the chunks past the end go
        left = eng._lookahead_stats["discarded"]
    finally:
        eng.shutdown()
    assert len(col.tokens[0]) == 40
    pipe = stats["pipeline"]
    assert pipe["lookahead"]["discarded"] == 0, pipe
    assert pipe["discard_ratio"] == 0.0
    assert pipe["lookahead"]["used"] > 0
    # what was in flight past the stream's end is dropped once nothing runs
    assert 0 < left <= 3
    # mixed rounds ran (chunked admission), and every later decode round
    # was ring-served: rounds == mixed_rounds + lookahead_rounds exactly
    assert pipe["mixed_rounds"] >= 1
    assert pipe["rounds"] == pipe["mixed_rounds"] + pipe["lookahead_rounds"], \
        f"synchronous fallback round after the flip: {pipe}"


def test_mixed_to_pure_decode_transition_bit_identical_seeded():
    """Seeded sampled streams across the mixed→pure-decode transition:
    ring-spanning (depth 3, chunks chained off the mixed dispatch's
    device-computed flip state) vs the fully synchronous path — identical
    tokens, and the spanning run really spanned (no sync round between the
    last mixed round and the first ring-served drain)."""
    rng = np.random.default_rng(33)
    prompts = [rng.integers(3, 900, 20 + 7 * i).tolist() for i in range(4)]
    samplings = [SamplingParams(max_tokens=24, temperature=0.9, top_p=0.85,
                                seed=500 + i) for i in range(4)]
    span_col, span_stats = _run_streams(
        _cfg(decode_lookahead=3, prefill_budget_tokens=16), prompts,
        samplings)
    sync_col, _ = _run_streams(
        _cfg(decode_lookahead=0, prefill_budget_tokens=16), prompts,
        samplings)
    assert span_col.tokens == sync_col.tokens
    assert span_col.finishes == sync_col.finishes
    pipe = span_stats["pipeline"]
    assert pipe["mixed_rounds"] >= 2  # budget 16 forces real chunking
    assert pipe["lookahead"]["used"] > 0


def test_stop_finish_within_device_width_keeps_ring():
    """A stop set that FITS device_stop_width terminates on-device: streams
    match the synchronous scheduler AND the host classifies the same stop
    reason the device froze on."""
    prompt = np.random.default_rng(6).integers(3, 900, 10).tolist()
    # temperature + a broad-but-fitting stop set: tokens 3..8 (6 ids < 8)
    sampling = [SamplingParams(max_tokens=60, temperature=1.3, seed=77,
                               stop_token_ids=tuple(range(3, 9)))]
    deep_col, _ = _run_streams(_cfg(decode_lookahead=3), [prompt], sampling)
    sync_col, _ = _run_streams(_cfg(decode_lookahead=0), [prompt], sampling)
    assert deep_col.tokens == sync_col.tokens
    assert deep_col.finishes == sync_col.finishes


@pytest.mark.parametrize("depth", [1, 3])
def test_preempt_resume_under_lookahead_bit_exact(depth):
    """Pool-pressure preemption while the pipeline is overlapping (depth 1
    and a 3-deep mid-ring preempt): the preempted stream must resume
    bit-exact, and the run must actually have used lookahead rounds before
    the fault."""
    prompt = np.random.default_rng(0).integers(3, 900, 20).tolist()
    cfg = _cfg(max_batch=2, max_seq_len=128, prefix_cache_pages=64,
               prefix_page_size=8, decode_lookahead=depth)
    sampling = [SamplingParams(max_tokens=40, temperature=0.0)]

    ref_col, _ = _run_streams(cfg, [prompt], sampling)
    assert len(ref_col.tokens[0]) == 40

    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(1)
    try:
        pool = sched.pool
        orig_extend = pool.extend_chain
        armed = threading.Event()

        def flaky_extend(chain, needed):
            # once armed, keep failing until a preemption actually lands
            # (the first failure may only skip a lookahead dispatch)
            if armed.is_set() and sched.preemptions == 0:
                raise MemoryError("injected pool pressure")
            return orig_extend(chain, needed)

        pool.extend_chain = flaky_extend

        def emit(ev):
            inner = col.emit_for(0)
            inner(ev)
            if len(col.tokens[0]) == 12:
                armed.set()  # mid-stream, after lookahead has engaged
        sched.submit(prompt, sampling[0], emit)
        assert col.done.wait(240), (col.tokens, sched.stats())
        stats = sched.stats()
    finally:
        sched.shutdown()

    assert sched.preemptions >= 1, "injected pressure never preempted"
    assert col.tokens[0] == ref_col.tokens[0], "resume lost bit-exactness"
    assert stats["pipeline"]["lookahead"]["used"] > 0, \
        "run never pipelined — the scenario under test did not occur"


def test_free_slot_deque_and_device_mirrors_stay_consistent():
    """After churn (more requests than slots, mixed sampling), the free-slot
    deque must hold exactly the inactive slots with no duplicates, and the
    device-resident rows must mirror host state."""
    cfg = _cfg(max_batch=3)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(7)
    rng = np.random.default_rng(5)
    try:
        for i in range(7):
            sched.submit(rng.integers(3, 900, 5 + 3 * i).tolist(),
                         SamplingParams(max_tokens=6 + i,
                                        temperature=0.5 * (i % 2),
                                        seed=i), col.emit_for(i))
        assert col.done.wait(240), (col.finishes, sched.stats())
        # quiesce: let in-flight rounds drain, then JOIN the scheduler thread
        # (emit fires before the finish bookkeeping — polling host state alone
        # races the finish bookkeeping by a few statements)
        deadline = time.monotonic() + 30
        while (sched.active.any() or sched._pending.qsize()) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        sched.shutdown()
        free = list(sched._free_slots)
        assert sorted(free) == list(range(cfg.max_batch)), free
        assert len(set(free)) == len(free), f"duplicate free slots: {free}"
        # what the NEXT dispatch would read is the host's rows: a finish
        # writes nothing to the device, the upload ahead of a dispatch does
        sched._sync_rows()
        np.testing.assert_array_equal(
            np.asarray(sched._active_dev), sched.active)
        np.testing.assert_array_equal(
            np.asarray(sched._rows_dev), sched._rows)
        # ACTIVE rows' device lengths mirror host lengths exactly. Inactive
        # rows are DON'T-CARE: they keep the frozen terminal value until a
        # program pins them to 0 at its entry (their page-table rows are
        # zeroed: writes park on scratch). What must hold for safety: no
        # inactive device length exceeds the window, and their page-table
        # rows are zeroed.
        lengths_dev = np.asarray(sched._lengths_dev)
        np.testing.assert_array_equal(
            lengths_dev[sched.active], sched.lengths[sched.active])
        assert (lengths_dev <= cfg.max_seq_len).all()
        assert (sched.page_table[~sched.active] == 0).all()
    finally:
        sched.shutdown()


def test_stats_surface_pipeline_breakdown():
    """stats() carries the per-round timing breakdown and lookahead counters
    the monitoring module scrapes."""
    cfg = _cfg(max_batch=2)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(1)
    try:
        sched.submit([5, 6, 7, 8], SamplingParams(max_tokens=24),
                     col.emit_for(0))
        assert col.done.wait(120)
        st = sched.stats()
    finally:
        sched.shutdown()
    pipe = st["pipeline"]
    assert pipe["rounds"] > 0
    for key in ("admit_ms_p50", "dispatch_ms_p50", "sync_wait_ms_p50",
                "host_emit_ms_p50", "overlap_ratio"):
        assert key in pipe and pipe[key] >= 0
    assert set(pipe["lookahead"]) == {"dispatched", "used", "discarded"}
    assert pipe["lookahead"]["dispatched"] >= pipe["lookahead"]["used"]
    assert set(st["queue_wait_ms"]) == {"p50", "max", "count"}


# ------------------------------------------- admission waits for the ring
#
# One rule for every model: an arrival (or a resume) waits until the chunks
# in flight have been drained — each to every running row — and is admitted
# off the empty ring. The loop's passes are made by hand here (no thread), so
# rounds can be counted.

FAMILIES = ["tiny-llama", "tiny-falcon-h1"]


def _counter(name) -> float:
    for _labels, value in default_registry.counter(name).samples():
        return value
    return 0.0


def _arrival_run(model, depth, arrivals):
    """Two rows decode with the ring as deep as it gets; then ``arrivals``
    requests arrive at once into the two free slots. Returns the streams and
    what the arrival met."""
    rng = np.random.default_rng(40)
    prompts = [rng.integers(3, 500, n).tolist() for n in (12, 20, 9, 15)]
    samplings = [SamplingParams(max_tokens=44,
                                temperature=0.8 if i % 2 else 0.0,
                                top_p=0.9, seed=300 + i) for i in range(4)]
    eng = _manual(_cfg(model=model, decode_lookahead=depth,
                       prefill_budget_tokens=32))
    col = _Collector(2 + arrivals)
    try:
        for i in range(2):
            eng.submit(prompts[i], samplings[i], col.emit_for(i))
        _passes_until(eng, lambda: eng.active.sum() == 2
                      and len(eng._ring) == depth)
        seen = {"ring": len(eng._ring),
                "waits": _counter("llm_admission_ring_waits_total"),
                "emitted": eng.tokens_emitted}
        for i in range(2, 2 + arrivals):
            eng.submit(prompts[i], samplings[i], col.emit_for(i))
        held = []               # tokens emitted as each pass began

        def admitted():
            held.append(eng.tokens_emitted)
            return eng._pending.empty()
        held.append(eng.tokens_emitted)
        seen["passes"] = _passes_until(eng, admitted)
        seen["waits"] = _counter("llm_admission_ring_waits_total") - seen["waits"]
        # what the running rows received while the arrival was held, and by
        # the end of the pass that admitted it
        seen["emitted"], seen["emitted_after"] = (
            held[-2] - seen["emitted"], held[-1] - seen["emitted"])
        seen["occupied"] = sum(s is not None for s in eng.slots)
        seen["ring_rebuilt"] = len(eng._ring)
        seen["discarded"] = eng._lookahead_stats["discarded"]
        _passes_until(eng, col.done.is_set)
    finally:
        eng.shutdown()
    return col, seen


@functools.cache
def _sync_arrivals(model, arrivals):
    """The synchronous scheduler's run of the same arrivals: the reference."""
    return _arrival_run(model, 0, arrivals)


@pytest.mark.parametrize("arrivals", [1, 2])
@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("model", FAMILIES)
def test_an_arrival_waits_for_the_ring_and_discards_nothing(model, depth,
                                                           arrivals):
    """The tentpole, for both families: an arrival into a full ring is held
    for exactly the chunks in flight (``depth`` drains, each emitted to both
    running rows: the last one's emit behind the arrival's launch, in the
    admitting pass), then admitted off the empty ring in the next pass — two
    arrivals into two free slots in that ONE pass — with nothing discarded,
    and every stream is what the synchronous scheduler emits."""
    sync_col, sync_seen = _sync_arrivals(model, arrivals)
    assert sync_seen["passes"] == 1 and sync_seen["waits"] == 0
    col, seen = _arrival_run(model, depth, arrivals)
    assert col.tokens == sync_col.tokens, "streams differ from depth 0"
    assert col.finishes == sync_col.finishes
    assert seen["ring"] == depth
    # no starvation: the ring stopped extending, so the wait is its depth
    assert seen["passes"] == depth + 1, seen
    assert seen["waits"] == depth, seen
    # each drain went to both running rows: the wait cost them nothing.
    # The drain that emptied the ring held its emit for the arrival's launch
    assert seen["emitted"] == (depth - 1) * 2 * 4, seen
    assert seen["emitted_after"] >= depth * 2 * 4, seen
    # the admitting pass ran the arrival's chunk as a lane; a lone arrival's
    # step drains the prefill queue, so the ring is rebuilt off that dispatch
    assert seen["ring_rebuilt"] == (depth if arrivals == 1 else 0), seen
    assert seen["occupied"] == 2 + arrivals, "not all admitted in one pass"
    assert seen["discarded"] == 0, seen


@pytest.mark.parametrize("model,discards", [("tiny-llama", True),
                                            ("tiny-falcon-h1", False)])
def test_a_host_fallback_stop_discards_on_llama_and_drains_with_state(
        model, discards):
    """What cannot wait still invalidates the ring: a stop the device could
    not see (the stop set overflows ``device_stop_width``) while another row
    runs. Without state the stale chunks are dropped and recomputed; with
    state they are drained (a replay would advance the state twice) and
    nothing is admitted meanwhile. Either way the survivor's stream is the
    synchronous scheduler's."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(3, 500, 14).tolist() for _ in range(2)]
    samplings = [SamplingParams(max_tokens=60, temperature=0.0),
                 SamplingParams(max_tokens=60, temperature=0.9, seed=51,
                                stop_token_ids=tuple(range(0, 400)))]

    def run(depth):
        eng = _manual(_cfg(model=model, decode_lookahead=depth,
                           prefill_budget_tokens=32))
        col = _Collector(2)
        try:
            for i in range(2):
                eng.submit(prompts[i], samplings[i], col.emit_for(i))
            before = _counter("llm_decode_chunks_discarded_total")
            _passes_until(eng, lambda: 1 in col.finishes)
            assert col.finishes[1] == "stop" and 0 not in col.finishes
            seen = {"discarded": eng._lookahead_stats["discarded"],
                    "counted": _counter("llm_decode_chunks_discarded_total")
                    - before,
                    "ring": len(eng._ring)}
            _passes_until(eng, col.done.is_set)
        finally:
            eng.shutdown()
        return col, seen

    sync_col, _ = run(0)
    col, seen = run(2)
    assert col.tokens == sync_col.tokens and col.finishes == sync_col.finishes
    assert len(col.tokens[1]) < 60, "the stop set never fired"
    assert seen["counted"] == seen["discarded"]     # one count, two surfaces
    if discards:
        assert seen["discarded"] >= 1 and seen["ring"] == 0, seen
    else:
        assert seen["discarded"] == 0, seen


def test_the_rings_series_are_at_zero_from_engine_build():
    """The three counters the rule brings exist from engine build, unlabelled,
    so a window without a discard reads 0 and not nothing; a dispatch and a
    drop then move them as ``stats()["pipeline"]["lookahead"]`` moves."""
    names = ("llm_decode_chunks_dispatched_total",
             "llm_decode_chunks_discarded_total",
             "llm_admission_ring_waits_total",
             "llm_drains_ring_empty_total",
             "llm_emits_deferred_total",
             "llm_mixed_steps_total",
             "llm_mixed_steps_chained_total",
             "llm_control_rows_uploads_total",
             "llm_loose_row_programs_total",
             "llm_attn_pages_walked_total",
             "llm_attn_page_groups_total",
             "llm_attn_pages_offered_total")
    before = {n: _counter(n) for n in names}
    eng = _manual(_cfg(decode_lookahead=2))
    col = _Collector(1)
    try:
        text = default_registry.render()
        for n in names:
            assert any(line.split(" ")[0] == n
                       for line in text.splitlines()), f"{n} not rendered"
            assert _counter(n) == before[n], "engine build moved a counter"
        eng.submit([5, 6, 7, 8], SamplingParams(max_tokens=24),
                   col.emit_for(0))
        _passes_until(eng, col.done.is_set)
        eng._loop_pass()        # no row runs: what is left in flight is dropped
        la = dict(eng._lookahead_stats)
    finally:
        eng.shutdown()
    dispatched = _counter(names[0]) - before[names[0]]
    # every _dispatch_chunk: the chained ones and the heads of sync rounds
    assert dispatched >= la["dispatched"] > 0
    assert _counter(names[1]) - before[names[1]] == la["discarded"]
    assert _counter(names[2]) == before[names[2]]   # nobody waited


@pytest.mark.parametrize("model", ["tiny-llama", "tiny-falcon-h1"])
def test_attn_pages_walked_is_what_the_rows_lengths_say(model):
    """``llm_attn_pages_walked_total`` over ``llm_attn_pages_offered_total``
    for one request served alone, against a count made here from its length
    at every step: the arrival's mixed step finds every row of the decode
    group idle (one program each), then each step of each chunk walks the
    pages the running row's tokens lie on, its own included, and one program
    for each of the three idle rows; a row the device froze inside a chunk
    stays at the length it reached. ``llm_attn_page_groups_total`` counts the
    same steps' trips: a row's pages a trip at a time."""
    from cyberfabric_core_tpu.models.llama import decode_page_group

    names = ("llm_attn_pages_walked_total", "llm_attn_pages_offered_total",
             "llm_attn_page_groups_total")
    prompt, answer, page, k = 40, 11, 16, 4
    eng = _manual(_cfg(model=model, decode_lookahead=0))
    col = _Collector(1)
    try:
        before = [_counter(n) for n in names]
        eng.submit(list(range(5, 5 + prompt)),
                   SamplingParams(max_tokens=answer), col.emit_for(0))
        _passes_until(eng, col.done.is_set)
        rows, slots = eng.n_slots, eng.page_table.shape[1]
        layers, mixed = eng.model_config.num_layers, eng.mixed_rounds
        chunks = eng.decode_rounds - mixed
        group = decode_page_group(eng.model_config, page, slots,
                                  np.dtype(eng.dtype).itemsize,
                                  eng.model_config.sliding_window)
    finally:
        eng.shutdown()
    assert col.finishes[0] == "length" and len(col.tokens[0]) == answer
    walked, length, left = mixed * rows, prompt, answer - 1
    programs = walked
    assert chunks == -(-left // k) and group > 1
    for _ in range(chunks * k):
        pages = -(-(length + 1) // page)
        walked += pages + rows - 1
        programs += -(-pages // group) + rows - 1
        if left:
            length, left = length + 1, left - 1
    got = [_counter(n) - b for n, b in zip(names, before)]
    assert got == [walked * layers,
                   (mixed + chunks * k) * rows * slots * layers,
                   programs * layers]
    assert 0.0 < got[0] / got[1] < 0.5


@pytest.mark.parametrize("model,over", [
    ("tiny-llama", {}), ("tiny-sdar", {}),
    ("tiny-kimi-share4", {"quantization": "int8"}),
    # pages of 4 tokens: a table of 64 slots a row
    ("tiny-llama", {"prefix_page_size": 4, "prefix_cache_pages": 320}),
], ids=["tiny-llama", "tiny-sdar", "tiny-kimi-share4", "tiny-llama-page4"])
def test_attn_pages_counted_by_step_from_kept_lengths(model, over):
    """The count itself, for a token step, a block step and a latent page:
    a forward reads the row's kept length and the step's own tokens; a
    forward that added to the length moves the ones after it; a row that
    does not run is at 0 on the device. The groups counted are the trips of
    the kernel's own rule (tests/test_paged_attention.py and
    tests/test_mla_attention.py count the trips a call runs), and the pages
    walked do not depend on how they are grouped."""
    from cyberfabric_core_tpu.ops.mla_attention import trip_pages
    from cyberfabric_core_tpu.ops.paged_attention import decode_trip_pages

    names = ("llm_attn_pages_walked_total", "llm_attn_page_groups_total",
             "llm_attn_pages_offered_total")
    eng = _manual(_cfg(model=model, **over))
    try:
        step, slots = eng._step_tokens, eng.page_table.shape[1]
        page = eng.config.prefix_page_size
        layers, cfg = eng.model_config.num_layers, eng.model_config
        eng.active[:] = [True, True, False, True]
        kept = np.asarray([15, 100, 77, 255], np.int32)
        grew = np.asarray([[1, 1, 0], [0, 1, 0], [1, 1, 1], [0, 0, 0]], bool)
        before = [_counter(n) for n in names]
        eng._count_attn_pages(kept, grew)
        got = [_counter(n) - b for n, b in zip(names, before)]
        pool = eng.pool.cache_operands()[0]
    finally:
        eng.active[:] = False
        eng.shutdown()
    walked, programs = 0, 0
    lengths = np.where([True, True, False, True], kept, 0)
    for f in range(3):
        pages = np.minimum(-(-(lengths + step) // page), slots)
        walked += int(pages.sum())
        group = trip_pages(page, cfg.sliding_window) if cfg.is_latent else \
            decode_trip_pages(page, pool.shape[3], pool.dtype.itemsize, slots,
                              cfg.sliding_window)
        programs += int((-(-pages // group)).sum())
        lengths = lengths + step * grew[:, f]
    assert got == [walked * layers, programs * layers,
                   4 * 3 * slots * layers]
    assert group > 1 and walked / group <= programs < walked


def test_the_rings_series_are_on_metrics_before_the_first_request():
    """A freshly booted stack's ``/metrics`` names the three series, with
    their help text and an unlabelled sample, before any request is served:
    the benchmark's ``counter`` reader needs both ends of a window."""
    import asyncio

    import aiohttp
    from conftest import boot_stack, stop_stack

    async def go():
        rt, base = await boot_stack({"modules": {
            "api_gateway": {"config": {"bind_addr": "127.0.0.1:0"}},
            "monitoring": {}}})
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(base + "/metrics") as r:
                    assert r.status == 200
                    return await r.text()
        finally:
            await stop_stack(rt)

    text = asyncio.run(go())
    for name in ("llm_decode_chunks_dispatched_total",
                 "llm_decode_chunks_discarded_total",
                 "llm_admission_ring_waits_total",
                 "llm_drains_ring_empty_total",
                 "llm_emits_deferred_total",
                 "llm_mixed_steps_total",
                 "llm_mixed_steps_chained_total",
                 "llm_control_rows_uploads_total",
                 "llm_loose_row_programs_total",
                 "llm_attn_pages_walked_total",
                 "llm_attn_page_groups_total",
                 "llm_attn_pages_offered_total"):
        assert f"# TYPE {name} counter" in text
        help_line = next(line for line in text.splitlines()
                         if line.startswith(f"# HELP {name} "))
        assert len(help_line) > len(f"# HELP {name} ") + 20, help_line
        sample = [line for line in text.splitlines()
                  if line.split(" ")[0] == name]
        assert len(sample) == 1 and float(sample[0].split(" ")[1]) >= 0.0


# ------------------------------------------------- cancellation × pipeline


def _drain_clean(sched):
    """Shared leak assertions: slots, pending, suspended, pool refs."""
    assert len(sched._free_slots) == sched.n_slots
    assert all(s is None for s in sched.slots)
    assert not sched.active.any()
    assert sched._pending.qsize() == 0
    assert not sched._suspended
    if sched.pool is not None:
        st = sched.pool.stats()
        assert st.get("pages_referenced", 0) == 0, st
        assert st.get("orphan_pages", 0) == 0, st


def test_cancel_mid_decode_survivor_bit_identical_no_ring_discard():
    """The tentpole golden: cancelling stream B mid-decode (from B's own
    emit callback — scheduler-thread deterministic) must leave stream A
    BIT-IDENTICAL to the uncancelled run, free B's slot/pages leak-free,
    and drain the lookahead ring WITHOUT a discard (the cancel freezes the
    row instead of bumping the epoch)."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(3, 900, 10).tolist(),
               rng.integers(3, 900, 12).tolist()]
    samplings = [SamplingParams(max_tokens=40), SamplingParams(max_tokens=40)]
    cfg = _cfg(decode_lookahead=2)

    ref_col, _ = _run_streams(cfg, prompts, samplings)

    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(2)
    triggered = []
    discarded_at_finish = []
    try:
        inner_a = col.emit_for(0)

        def emit_a(ev):
            if ev.finished:     # on the scheduler thread, before any later pass
                discarded_at_finish.append(
                    sched._lookahead_stats["discarded"])
            inner_a(ev)
        sched.submit(prompts[0], samplings[0], emit_a, request_id="surv")
        inner_b = col.emit_for(1)

        def emit_b(ev):
            inner_b(ev)
            if len(col.tokens[1]) >= 6 and not triggered:
                triggered.append(1)
                assert sched.cancel("vict", "test") is True
        sched.submit(prompts[1], samplings[1], emit_b, request_id="vict")
        assert col.done.wait(240), (col.finishes, sched.stats())
        stats = sched.stats()
    finally:
        sched.shutdown()

    assert col.tokens[0] == ref_col.tokens[0], "survivor diverged"
    assert col.finishes[0] == ref_col.finishes[0]
    assert col.finishes[1] == "cancelled"
    assert len(col.tokens[1]) < 40, "victim ran to completion anyway"
    assert stats["cancellations"] == {"test": 1}
    assert stats["reclaimed_tokens"] == 40 - len(col.tokens[1])
    # the ring survived the cancel (and the victim's admission waited for
    # it): nothing was discarded while the survivor ran
    assert discarded_at_finish == [0]
    _drain_clean(sched)


def test_cancel_racing_device_finish_single_terminal():
    """Cancel landing in the same rounds as a device-side finish must not
    double-release pages or emit two terminals — in either order."""
    rng = np.random.default_rng(12)
    prompt = rng.integers(3, 900, 10).tolist()
    cfg = _cfg(decode_lookahead=2)

    # order 1 — finish wins: cancel registered on the FINAL token's emit;
    # the sweep then finds nothing to cancel
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(1)
    try:
        inner = col.emit_for(0)

        def emit(ev):
            inner(ev)
            if len(col.tokens[0]) == 8:  # max_tokens reached in this event
                sched.cancel("r1", "late")
        sched.submit(prompt, SamplingParams(max_tokens=8), emit,
                     request_id="r1")
        assert col.done.wait(240)
        stats = sched.stats()
    finally:
        sched.shutdown()
    assert col.finishes[0] == "length"
    assert len(col.tokens[0]) == 8
    assert stats["cancellations"] == {}, \
        "a post-terminal cancel must be a no-op"
    _drain_clean(sched)

    # order 2 — cancel wins: registered mid-stream; chunks carrying the
    # device-predicted finish may still be in the ring, but the deactivated
    # row is masked out of every later drain
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(1)
    try:
        inner = col.emit_for(0)
        fired = []

        def emit(ev):
            inner(ev)
            if len(col.tokens[0]) >= 5 and not fired:
                fired.append(1)
                sched.cancel("r2", "early")
        sched.submit(prompt, SamplingParams(max_tokens=8), emit,
                     request_id="r2")
        assert col.done.wait(240)
        stats = sched.stats()
    finally:
        sched.shutdown()
    assert col.finishes[0] == "cancelled", "exactly one terminal, the cancel"
    assert stats["cancellations"] == {"early": 1}
    _drain_clean(sched)


def test_cancel_while_suspended_never_resurrects():
    """Cancel during preempt/resume: a suspended (preempted-to-host)
    request that gets cancelled must terminate once, never resume, and the
    other stream must stay bit-identical to its unfaulted run."""
    from cyberfabric_core_tpu.modkit import failpoints as fp

    rng = np.random.default_rng(13)
    prompts = [rng.integers(3, 900, 10).tolist(),
               rng.integers(3, 900, 10).tolist()]
    samplings = [SamplingParams(max_tokens=30), SamplingParams(max_tokens=30)]
    cfg = _cfg(max_batch=2)

    ref_col, _ = _run_streams(cfg, prompts, samplings)

    fp.reset()
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(2)
    try:
        # forced MemoryErrors on page-chain growth until one lands on the
        # capacity sweep → preempt-to-host (a ring extension that meets one
        # only caps the ring: which comes first is a matter of timing)
        fp.arm("scheduler.page_alloc", "raise(MemoryError)")
        sched.submit(prompts[0], samplings[0], col.emit_for(0),
                     request_id="keeper")
        sched.submit(prompts[1], samplings[1], col.emit_for(1),
                     request_id="parked")
        deadline = time.monotonic() + 60.0
        while sched.preemptions == 0 and time.monotonic() < deadline:
            time.sleep(0.001)
        fp.disarm("scheduler.page_alloc")
        assert sched.preemptions >= 1, "injected pressure never preempted"
        # cancel whichever request is currently suspended
        victim = None
        for _ in range(2000):
            susp = list(sched._suspended)
            if susp:
                victim = susp[0].state.request_id
                break
            if len(col.finishes) == 2:
                break  # resumed and finished before we could look
            time.sleep(0.002)
        if victim is not None:
            sched.cancel(victim, "mid_suspend")
        assert col.done.wait(240), (col.finishes, sched.stats())
        stats = sched.stats()
    finally:
        fp.reset()
        sched.shutdown()
    if victim is not None:
        vic_idx = 0 if victim == "keeper" else 1
        # the cancel may race the resume: either it caught the request
        # suspended (cancelled terminal) or the request resumed first and
        # finished cleanly — but never both, and never zero
        assert col.finishes[vic_idx] in ("cancelled", "stop", "length")
        other = 1 - vic_idx
        assert col.tokens[other] == ref_col.tokens[other], \
            "the surviving stream diverged"
        if col.finishes[vic_idx] == "cancelled":
            assert stats["cancellations"] == {"mid_suspend": 1}
    assert len(col.finishes) == 2
    _drain_clean(sched)


# --------------------------------------------- the emit behind the next launch
#
# A drain that leaves nothing in flight holds its emit until the next program
# is queued (`_close_round`). Held or not, a stream is what it was: the
# reference below makes the same passes and emits at the end of each, which
# is the order the scheduler had before (drain, commit, emit, then the next
# pass's admit and launch).

HELD_FAMILIES = {"tiny-llama": {}, "tiny-falcon-h1": {},
                 "tiny-sdar": {"decode_chunk": 10}}
_TIMELINE_KEYS = ("event", "tokens", "pos", "of", "reason", "chunks",
                  "prompt_tokens", "blocks")


def _held_run(model, depth, parents_order, chain=False):
    """Four seeded requests, passes by hand: A (greedy, ends by max-tokens)
    and B (sampled, a stop set wider than ``device_stop_width``: the host
    alone sees it stop) start together; C (four chunks) and D (two) arrive
    as B's chunk runs, so six mixed steps follow one another with nothing
    chained between them and A's and B's tokens ride held emits (``chain``
    False: the test says in the engine's place that no step may be launched
    ahead of a drain, which is what a model that cannot know its next step
    says; True: the engine decides, and every one of those steps but the
    first is launched off the undrained one before it). Returns the
    streams, each request's flight record and what the flushes met."""
    from cyberfabric_core_tpu.modkit.flight_recorder import default_recorder

    rng = np.random.default_rng(11)
    # every chunk 32 wide: one mixed_step shape to compile an engine
    prompts = [rng.integers(3, 500, n).tolist() for n in (32, 32, 128, 64)]
    samplings = [
        SamplingParams(max_tokens=4, temperature=0.0),
        SamplingParams(max_tokens=60, temperature=0.9, seed=51,
                       stop_token_ids=tuple(range(0, 400))),
        SamplingParams(max_tokens=10, temperature=0.0),
        SamplingParams(max_tokens=6, temperature=0.8, top_p=0.9, seed=7)]
    arrive = {0: (0, 1), 1: (2, 3)}         # pass -> submitted ahead of it
    eng = _manual(_cfg(model=model, decode_lookahead=depth,
                       prefill_budget_tokens=32, **HELD_FAMILIES[model]))
    if not chain:
        eng._chains_mixed = lambda step: False
    ids = [f"held-{model}-{depth}-{parents_order}-{chain}-{i}"
           for i in range(4)]
    col = _Collector(4)
    seen = {"flushed_behind_a_chunk": 0, "finished_there": 0,
            "stopped_there": 0}
    flush = eng._flush_held_emit

    def spy(behind_launch=False):
        carrying = behind_launch and bool(eng._prefill_slots)
        done, epoch = eng.requests_completed, eng._epoch
        held = flush(behind_launch)
        if held and carrying:
            seen["flushed_behind_a_chunk"] += 1
            seen["finished_there"] += eng.requests_completed - done
            seen["stopped_there"] += eng._epoch - epoch
        return held
    eng._flush_held_emit = spy
    series = ("llm_drains_ring_empty_total", "llm_emits_deferred_total",
              "llm_mixed_steps_total", "llm_mixed_steps_chained_total")
    before = [_counter(n) for n in series]
    try:
        for n in range(600):
            for i in arrive.get(n, ()):
                eng.submit(prompts[i], samplings[i], col.emit_for(i),
                           request_id=ids[i])
            eng._loop_pass()
            if parents_order:
                flush()
            if col.done.is_set():
                break
        assert col.done.is_set(), eng.stats()
        seen["prefill"] = (eng.prefill_chunks, eng.chunked_prefill_tokens)
        seen["discarded"] = eng._lookahead_stats["discarded"]
        (seen["ring_empty"], seen["deferred"], seen["steps"],
         seen["chained"]) = (_counter(n) - b for n, b in zip(series, before))
        seen["chained_records"] = sum(r["chained"] for r in eng.round_timings)
        seen["depth_0"] = sum(r["depth"] == 0 for r in eng.round_timings)
        seen["records"] = len(eng.round_timings)
    finally:
        eng.shutdown()
    records = [[{k: e[k] for k in _TIMELINE_KEYS if k in e}
                for e in default_recorder.lookup(rid)["timeline"]]
               for rid in ids]
    return col, records, seen


@functools.cache
def _parents_order(model, depth):
    return _held_run(model, depth, True)


@pytest.mark.parametrize("model,depth", [(m, 0) for m in sorted(HELD_FAMILIES)]
                         + [("tiny-llama", 2)])
def test_a_held_emit_never_changes_a_stream(model, depth):
    """Every stream's tokens, finish reason and event order are those of the
    order the scheduler had, for K/V pages, recurrent state and blocks, with
    and without a ring: through arrivals, prompts of several chunks, a
    max-tokens finish inside a held emit and a host-fallback stop inside one.
    The step launched ahead of that stop holds a token for a row that is
    gone: it is not emitted, and the prompt chunk the step carried is kept
    (every chunk is computed once)."""
    want, want_records, want_seen = _parents_order(model, depth)
    col, records, seen = _held_run(model, depth, False)
    assert col.tokens == want.tokens and col.finishes == want.finishes
    assert col.finishes[0] == "length" and col.finishes[1] == "stop"
    assert len(col.tokens[0]) == 4 and len(col.tokens[1]) < 60
    for i, (got, ref) in enumerate(zip(records, want_records)):
        assert got == ref, f"request {i}: event order"
    # the cases did occur: emits ran behind steps that carried a chunk, A's
    # last token and B's stop among them; the reference held nothing there
    assert seen["flushed_behind_a_chunk"] >= 5, seen
    assert seen["finished_there"] >= 2 and seen["stopped_there"] == 1, seen
    assert want_seen["flushed_behind_a_chunk"] == 0
    # the counters count as stated: every drain that left nothing undrained
    # (the records of depth 0; without a ring, every round), and of those
    # the emits that ran behind the next launch: all, nothing flushed first
    assert seen["ring_empty"] == seen["depth_0"] == seen["deferred"] > 0
    assert depth or seen["depth_0"] == seen["records"]
    assert want_seen["ring_empty"] == seen["ring_empty"]
    assert want_seen["deferred"] < seen["deferred"]
    # 32 + 32 + 128 + 64 tokens in 1 + 1 + 4 + 2 chunks, none twice
    assert seen["prefill"] == want_seen["prefill"] == (8, 256)
    assert seen["chained"] == want_seen["chained"] == 0


@pytest.mark.parametrize("model,depth", [("tiny-llama", 0), ("tiny-llama", 2),
                                         ("tiny-falcon-h1", 0)])
def test_a_step_chained_off_an_undrained_one_never_changes_a_stream(model,
                                                                    depth):
    """The same four requests with the engine left to decide: six mixed
    steps follow one another and all but the first are launched off the
    undrained step before them, so A's max-tokens finish and B's
    host-fallback stop are found in emits that run UNDER a step already
    launched with those rows in it. Every stream's tokens, finish reason
    and event order are the parents'; each chunk is computed once; the
    drains that leave nothing in flight are the few around the steps."""
    want, want_records, want_seen = _parents_order(model, depth)
    col, records, seen = _held_run(model, depth, False, chain=True)
    assert col.tokens == want.tokens and col.finishes == want.finishes
    assert col.finishes[0] == "length" and col.finishes[1] == "stop"
    for i, (got, ref) in enumerate(zip(records, want_records)):
        assert got == ref, f"request {i}: event order"
    assert seen["prefill"] == want_seen["prefill"] == (8, 256)
    assert seen["steps"] == want_seen["steps"]
    assert seen["chained"] == seen["chained_records"] >= 5, seen
    assert seen["ring_empty"] < want_seen["ring_empty"], seen
    assert seen["deferred"] == seen["ring_empty"]      # none flushed first


def _end_cancel(eng, rid):
    eng.cancel(rid)
    eng._loop_pass()
    return "cancelled"


def _end_close(eng, rid):
    eng.close()
    return "error"


def _end_stop(eng, rid):
    eng._stop.set()             # the loop's thread, stopped with one held
    eng._loop_body()
    return None


def _end_idle(eng, rid):
    eng._decode_round = lambda: None    # a pass that launches nothing
    eng._loop_pass()
    return None


def _end_preempt(eng, rid):
    slot = next(s for s, st in enumerate(eng.slots) if st is not None)
    eng._preempt_slot(slot, eng.slots[slot])
    assert eng._suspended[0].state.emitted == eng.tokens_emitted
    return None


@pytest.mark.parametrize("model,end", [
    (m, end) for m in sorted(HELD_FAMILIES)
    for end in (_end_cancel, _end_close, _end_idle)
] + [("tiny-llama", _end_stop), ("tiny-llama", _end_preempt)],
    ids=lambda v: v if isinstance(v, str) else v.__name__[5:])
def test_what_ends_or_reads_a_stream_flushes_the_held_emit_first(model, end):
    """A cancel, ``close()``, the loop's stop, a pass with nothing to launch
    and a preemption each meet an emit that is held: its tokens go out
    first, in order, then whatever terminal the act brings; none of it
    counts as an emit deferred behind a launch."""
    eng = _manual(_cfg(model=model, decode_lookahead=0,
                       prefill_budget_tokens=32, **HELD_FAMILIES[model]))
    events = []
    try:
        eng.submit(list(range(5, 37)), SamplingParams(max_tokens=200),
                   lambda ev: events.append((ev.token_id, ev.finished)),
                   request_id=f"flush-first-{model}-{end.__name__}")
        _passes_until(eng, lambda: eng.decode_rounds >= 3
                      and eng._held is not None)
        got, emitted = len(events), eng.tokens_emitted
        deferred = _counter("llm_emits_deferred_total")
        terminal = end(eng, f"flush-first-{model}-{end.__name__}")
        assert eng._held is None
        assert _counter("llm_emits_deferred_total") == deferred
        new = events[got:]
        tokens = [e for e in new if e[0] >= 0]
        # one chunk's tokens (a block model's: the blocks it committed)
        assert len(tokens) == eng.tokens_emitted - emitted > 0
        assert len(tokens) == 4 or model == "tiny-sdar"
        assert all(fin is None for _, fin in tokens)
        if terminal is None:
            assert new == tokens
        else:
            assert new == tokens + [(-1, terminal)]
    finally:
        eng.shutdown()


# ------------------------------------- a prompt's next chunk behind the one in flight
#
# A mixed round is a launch half and a drain half. Where the host knows what
# the next `mixed_step` computes before this one's tokens are read (a plain
# step: every running row one token on, the lane a prompt's ids) the next
# step is launched off this one's device outputs ahead of the drain
# (`_chains_mixed`, `_dispatch_mixed(after=)`). Chained or not, a stream is
# what it was: the reference below is the same engine with the predicate
# answered False from the test (no setting does that).

CHAIN_FAMILIES = {                      # K/V pages; a latent page; state
    "tiny-llama": {},
    "tiny-kimi-share4": {"quantization": "int8"},
    "tiny-nemotron-h-share4-8l": {}}
_STEPS = ("llm_mixed_steps_total", "llm_mixed_steps_chained_total")


class _ChainRun:
    """A (``a``: its sampling) and B (seeded sampling) decode with the ring
    as deep as it gets; then C arrives, a prompt of three chunks of the
    budget. Passes by hand. ``at_chunk_1`` runs right after the pass that
    admitted C: chunk 1's step is drained and committed, and where the
    engine chains, chunk 2's step is in flight behind it, undrained."""

    def __init__(self, model, chain=True, a=None, b=None, c=None, **over):
        self.eng = eng = _manual(_cfg(
            model=model, decode_lookahead=2, prefill_budget_tokens=32,
            **CHAIN_FAMILIES.get(model, {}), **over))
        if not chain:
            eng._chains_mixed = lambda step: False
        rng = np.random.default_rng(23)
        self.prompts = {n: rng.integers(3, 200, size).tolist()
                        for n, size in (("A", 32), ("B", 32), ("C", 96))}
        self.sampling = {
            "A": a or SamplingParams(max_tokens=40, temperature=0.0),
            "B": b or SamplingParams(max_tokens=40, temperature=0.9,
                                     top_p=0.95, seed=77),
            "C": c or SamplingParams(max_tokens=6, temperature=0.0)}
        self.events = {n: [] for n in "ABC"}
        self.ids = {n: f"chain-{uuid.uuid4().hex[:8]}-{n}" for n in "ABC"}
        self.before = [_counter(n) for n in _STEPS]

    def ask(self, name):
        self.eng.submit(self.prompts[name], self.sampling[name],
                        lambda ev: self.events[name].append(
                            (ev.token_id, ev.finished)),
                        request_id=self.ids[name])

    def tokens(self, name):
        return [t for t, _ in self.events[name] if t >= 0]

    def finishes(self, name):
        return [f for _, f in self.events[name] if f]

    def steps(self):
        """(mixed steps launched, those chained) since the run began."""
        return tuple(_counter(n) - b for n, b in zip(_STEPS, self.before))

    def run(self, at_chunk_1=None, asked="ABC"):
        eng = self.eng
        try:
            for name in asked[:2]:
                self.ask(name)
            _passes_until(eng, lambda: eng.active.sum() == 2
                          and len(eng._ring) == 2)
            self.rows_before_c = eng.state_rows_in_use()
            self.ask("C")
            _passes_until(eng, lambda: bool(eng._prefill_slots))
            self.state_c = eng.slots[eng._prefill_slots[0]]
            assert self.state_c.prefill_pos == 32
            if at_chunk_1 is not None:
                at_chunk_1(self)
            _passes_until(eng, lambda: all(
                self.finishes(n) for n in asked))
            eng._loop_pass()
            eng._flush_held_emit()
            self.records = list(eng.round_timings)
            self.counted = self.steps()
            self.snapshots = eng.pool.stats().get("state_snapshots_taken")
            # the state rows left: the snapshots that pages of the tree own
            self.rows_left = eng.state_rows_in_use()
            _drain_clean(eng)
        finally:
            eng.shutdown()
        return self


def _sampled(on: bool, max_tokens: int, seed: int) -> SamplingParams:
    return SamplingParams(max_tokens=max_tokens, temperature=0.8, top_p=0.9,
                          seed=seed) if on else \
        SamplingParams(max_tokens=max_tokens, temperature=0.0)


@functools.cache
def _unchained(model, sampled):
    return _ChainRun(model, chain=False, a=_sampled(sampled, 40, 5),
                     c=_sampled(sampled, 6, 9)).run()


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "seeded"])
@pytest.mark.parametrize("model", sorted(CHAIN_FAMILIES))
def test_a_three_chunk_prompt_chains_and_every_stream_is_the_unchained_one(
        model, sampled):
    """(a) Chunk 2's step is launched off chunk 1's undrained step and
    chunk 3's off chunk 2's; the decode rows beside them and the prompt's
    own answer are token for token those of the engine that chains nothing,
    greedy and under seeded sampling."""
    want = _unchained(model, sampled)
    seen = {}

    def in_flight(run):
        step = run.eng._mixed
        seen["chunk 2"] = (step is not None and step.chained
                           and not step.finals, run.eng._held)
    got = _ChainRun(model, a=_sampled(sampled, 40, 5),
                    c=_sampled(sampled, 6, 9)).run(in_flight)
    assert seen["chunk 2"] == (True, None)
    for name in "ABC":
        assert got.tokens(name) == want.tokens(name), name
        assert got.finishes(name) == want.finishes(name) == ["length"]
    assert (len(got.tokens("A")), len(got.tokens("C"))) == (40, 6)
    # A's and B's own steps (a chunk each, B's known when A's is launched),
    # then C's three: chunk 2 and chunk 3 chained
    assert got.counted == (5, 3) and want.counted == (5, 0)
    assert [r["chained"] for r in got.records if r["mixed"]] == [
        False, True, False, True, True]
    assert not any(r["chained"] for r in want.records)
    assert got.snapshots == want.snapshots


@pytest.mark.parametrize("model", sorted(CHAIN_FAMILIES))
def test_a_row_that_ends_by_max_tokens_under_a_chained_step_ends_once(model):
    """(b) A's last token is the one chunk 1's step samples. When the host
    reads it, chunk 2's step is already launched with A's row running in
    its lane: the device froze the row by its limit, the host ends the
    stream once, with ``length`` and its ``max_tokens``, and the chained
    step's emit has nothing for it."""
    want = _unchained(model, False)
    at_chunk_1 = {}
    _ChainRun(model).run(lambda run: at_chunk_1.update(
        n=len(run.tokens("A"))))
    n = at_chunk_1["n"]
    assert 4 < n < 40
    seen = {}

    def ended(run):
        seen["finishes"] = run.finishes("A")
        seen["in flight"] = run.eng._mixed is not None
        seen["completed"] = run.eng.requests_completed
    got = _ChainRun(model, a=SamplingParams(max_tokens=n, temperature=0.0)
                    ).run(ended)
    assert seen == {"finishes": ["length"], "in flight": True,
                    "completed": 1}
    assert got.events["A"][-1] == (want.tokens("A")[n - 1], "length")
    assert got.tokens("A") == want.tokens("A")[:n]
    assert got.finishes("A") == ["length"]
    assert got.tokens("B") == want.tokens("B")
    assert got.tokens("C") == want.tokens("C")
    assert got.counted == (5, 3) and got.eng.requests_completed == 3


def _cancel(victim):
    def act(run):
        eng = run.eng
        step, before = eng._mixed, len(run.tokens("A"))
        assert step is not None and step.chained and run.state_c.prefill_pos == 32
        assert not run.finishes(victim)
        deferred = _counter("llm_emits_deferred_total")
        real, order = eng._cancel_slot, []
        eng._cancel_slot = lambda *a: (order.append(
            (run.state_c.prefill_pos, eng._mixed, len(run.tokens("A")))),
            real(*a))[1]
        eng.cancel(run.ids[victim], "test")
        eng._loop_pass()
        # settled first: the chained step was drained and COMMITTED (chunk
        # 2 landed) and its emit went out (A's token of that step) before
        # the row was ended; none of it counts as an emit behind a launch
        assert order == [(64, None, before + 1)]
        assert run.events[victim][-1] == (-1, "cancelled")
        assert _counter("llm_emits_deferred_total") == deferred
    return act


def _host_stop(run):
    """B's stop set is wider than ``device_stop_width`` (the host alone sees
    it stop) and holds the token chunk 1's step sampled for B."""
    assert run.finishes("B") == ["stop"] and run.eng._mixed is not None
    run.b_tokens = len(run.tokens("B"))
    run.epoch = run.eng._epoch


@pytest.mark.parametrize("end", ["cancel-a-row", "cancel-the-prompt",
                                 "host-stop"])
@pytest.mark.parametrize("model", sorted(CHAIN_FAMILIES))
def test_a_stream_ended_while_a_chained_step_is_in_flight(model, end):
    """(c) A cancel (of a decode row; of the prompt whose chunk is in the
    step) lands while chunk 2's step is in flight: the step is drained and
    committed and its tokens emitted FIRST, then the row ends, once. A
    host-fallback stop found in chunk 1's emit cannot un-run chunk 2's
    step, which had the row in it: the row is absent from that step's
    emit. Either way the pages (and the state row) are freed once and the
    other streams are the unchained ones."""
    want = _unchained(model, False)
    if end == "host-stop":
        at_chunk_1 = {}
        _ChainRun(model).run(lambda run: at_chunk_1.update(
            b=run.tokens("B")))
        *earlier, tok = at_chunk_1["b"]
        assert tok not in earlier
        stops = (tok, *range(10_000, 10_020))
        got = _ChainRun(model, b=SamplingParams(
            max_tokens=40, temperature=0.9, top_p=0.95, seed=77,
            stop_token_ids=stops)).run(_host_stop)
        assert got.tokens("B") == want.tokens("B")[:len(earlier) + 1]
        assert got.b_tokens == len(earlier) + 1     # nothing after the stop
        assert got.finishes("B") == ["stop"] and got.eng._epoch >= got.epoch
        others = "AC"
    else:
        victim = "A" if end == "cancel-a-row" else "C"
        got = _ChainRun(model).run(_cancel(victim))
        assert got.finishes(victim) == ["cancelled"]
        assert got.tokens(victim) == want.tokens(victim)[
            :len(got.tokens(victim))]
        assert got.eng.cancellations == {"test": 1}
        others = "ABC".replace(victim, "")
    for name in others:
        assert got.tokens(name) == want.tokens(name), name
        assert got.finishes(name) == ["length"]
    # every chunk computed once, whoever ended meanwhile (a cancelled
    # prompt's third chunk never ran)
    assert got.eng.prefill_chunks == (4 if end == "cancel-the-prompt" else 5)
    if end == "cancel-the-prompt":
        # with state: A's and B's rows went with their slots, and the
        # snapshots C took on the way (at 32, at 64) went back with it, so
        # what is left is what A's and B's pages own
        assert got.rows_left == max(got.rows_before_c - 2, 0)
    else:
        assert got.rows_left == want.rows_left


@pytest.mark.parametrize("model", sorted(CHAIN_FAMILIES))
def test_no_pages_for_the_next_chunk_means_no_chained_step(model):
    """(d) The pool has no page for the next chunk when the step that
    would carry it is to be launched ahead of the drain: nothing is chained
    and nothing is preempted there; the round ends as one with nothing to
    chain (its emit held), and the next pass's own capacity pass takes the
    pages as ever."""
    want = _unchained(model, False)
    run = _ChainRun(model)
    grow = run.eng._grow_chain_prefill

    def no_pages_ahead(slot, state, end):
        if end > state.prefill_pos + 32:    # past the chunk not yet drained
            raise MemoryError("no page for a chunk planned ahead")
        return grow(slot, state, end)
    run.eng._grow_chain_prefill = no_pages_ahead
    seen = {}
    got = run.run(lambda r: seen.update(in_flight=r.eng._mixed,
                                        held=r.eng._held is not None))
    assert seen == {"in_flight": None, "held": True}
    # B's own chunk, planned behind A's undrained step, had its pages; no
    # later chunk of C's had: one step chained of five
    assert got.counted == (5, 1) and got.eng.preemptions == 0
    for name in "ABC":
        assert got.tokens(name) == want.tokens(name), name


def test_a_snapshot_is_the_rows_state_after_its_own_step():
    """(e) With recurrent state a chunk that ends on a boundary leaves a
    snapshot of the row, taken right behind its own step and AHEAD of the
    step chained behind it, which advances the row. The snapshots at 32 and
    at 64 are taken with the next chunk's step about to be queued off an
    undrained one: they equal, bit for bit, those of the engine that chains
    nothing, and a second prompt that shares the first 64 tokens resumes
    from the one at 64 and answers as it does there."""
    model = "tiny-nemotron-h-share4-8l"
    rng = np.random.default_rng(23)
    first = rng.integers(3, 200, 96).tolist()
    shared = first[:64] + list(range(7, 20))

    def serve(chain):
        eng = _manual(_cfg(model=model, decode_lookahead=2,
                           prefill_budget_tokens=32))
        if not chain:
            eng._chains_mixed = lambda step: False
        taken, take = [], eng.pool.take_snapshot
        eng.pool.take_snapshot = lambda slot: taken.append(take(slot)) \
            or taken[-1]
        out, chained = {0: [], 1: []}, _counter(_STEPS[1])
        try:
            for i, prompt in enumerate((first, shared)):
                eng.submit(prompt, SamplingParams(max_tokens=8),
                           lambda ev, i=i: out[i].append(
                               (ev.token_id, ev.finished)))
                _passes_until(eng, lambda: out[i] and out[i][-1][1])
                if i == 0:
                    rows = [eng.pool.state_row(row) for row in taken]
            eng._loop_pass()
            hits = eng.pool.stats()["state_snapshot_hits"]
            _drain_clean(eng)
        finally:
            eng.shutdown()
        return out, rows, hits, _counter(_STEPS[1]) - chained
    out, rows, hits, chained = serve(True)
    ref_out, ref_rows, ref_hits, ref_chained = serve(False)
    assert chained >= 2 and ref_chained == 0
    assert len(rows) == len(ref_rows) == 3          # at 32, 64 and 96
    for got, ref in zip(rows, ref_rows):
        assert got.keys() == ref.keys()
        for leaf in got:
            np.testing.assert_array_equal(got[leaf], ref[leaf])
    assert any((rows[0][leaf] != rows[1][leaf]).any() for leaf in rows[0])
    assert (hits, ref_hits) == (1, 1)
    assert out == ref_out and len(out[1]) == 8


@pytest.mark.parametrize("model,over", [
    ("tiny-llama", {"scheduler_spec_k": 2}),
    ("tiny-sdar", {"decode_chunk": 10})], ids=["speculating", "blocks"])
def test_an_engine_that_cannot_know_its_next_step_never_chains(model, over):
    """(f) A speculating engine (a draft span advances a row by 1..k+1, and
    the next proposals come from text not yet emitted) and a block model (a
    row advances by what the forward committed) take a prompt of three
    chunks as three steps with a drain between them."""
    run = _ChainRun(model, **over)
    seen = {}
    run.run(lambda r: seen.update(in_flight=r.eng._mixed))
    assert seen == {"in_flight": None}
    steps, chained = run.counted
    assert steps >= 5 and chained == 0
    assert not any(r["chained"] for r in run.records)
