"""Batched speculative decoding in the continuous scheduler (k-token ragged
verify with on-device accept/rollback — runtime/scheduler.py spec rounds).

The golden contracts:

- **k=0 bit-identity.** ``scheduler_spec_k=0`` (the default) takes the exact
  pre-speculation code path: greedy AND seeded-sampling streams are
  bit-identical whether the spec fields are left at their defaults or set
  explicitly to zero, and no spec program is ever built.
- **Greedy k>0 output-identity.** Speculation changes speed, never text:
  greedy streams at any k are byte-identical to k=0 — including stop-token
  finishes and max-tokens finishes — while the engine really speculates
  (acceptance asserted, so the identity checks are never vacuous).
- **Rejected-suffix KV never commits.** A rejected draft's KV writes land
  past the committed length and are rewritten before any later read
  (kernel-level golden vs a garbage-free reference).
- **Mixed-round composition.** Prefill chunks + speculating rows + plain
  decode rows ride ONE ragged dispatch, and the greedy speculating stream
  stays identical to its solo k=0 run.
"""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine
from cyberfabric_core_tpu.runtime.speculative import (NgramProposer,
                                                      greedy_accept_counts)


def _cfg(**over):
    base = dict(model="tiny-llama", max_seq_len=256, max_batch=4,
                decode_chunk=4,
                prefix_cache_pages=80, prefix_page_size=16,
                prefill_budget_tokens=24)
    base.update(over)
    return EngineConfig(**base)


#: repetitive prompts: the ngram proposer needs recurring n-grams, and a
#: tiled motif gives it hits from the very first decode round
_REP_PROMPTS = [[5, 6, 7, 8] * 4, [9, 10, 11] * 5, [3, 4] * 6]


class _Collector:
    def __init__(self, n: int):
        self.tokens: dict[int, list[int]] = {i: [] for i in range(n)}
        self.finishes: dict[int, str] = {}
        self.done = threading.Event()
        self._lock = threading.Lock()
        self._n = n

    def emit_for(self, i: int):
        def emit(ev):
            with self._lock:
                if ev.token_id >= 0:
                    self.tokens[i].append(ev.token_id)
                if ev.finished:
                    self.finishes[i] = ev.finished
                    if len(self.finishes) == self._n:
                        self.done.set()
        return emit


def _run_streams(cfg, prompts, samplings, timeout=240.0):
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(len(prompts))
    try:
        for i, (p, s) in enumerate(zip(prompts, samplings)):
            sched.submit(p, s, col.emit_for(i))
        assert col.done.wait(timeout), (col.finishes, sched.stats())
        stats = sched.stats()
    finally:
        sched.shutdown()
    return col, stats


def test_spec_fields_at_zero_are_bit_identical_to_defaults():
    """k=0 golden: explicit zeros take the exact default code path — greedy
    AND seeded sampling — and the spec surface reports a dormant engine."""
    samp = [SamplingParams(max_tokens=24),
            SamplingParams(max_tokens=24, temperature=0.9, seed=7),
            SamplingParams(max_tokens=24, temperature=0.7, top_p=0.9,
                           seed=11)]
    base, base_stats = _run_streams(_cfg(), _REP_PROMPTS, samp)
    zero, zero_stats = _run_streams(
        _cfg(scheduler_spec_k=0, spec_min_accept=0.5), _REP_PROMPTS, samp)
    assert base.tokens == zero.tokens
    assert base.finishes == zero.finishes
    for stats in (base_stats, zero_stats):
        assert stats["speculative"]["k"] == 0
        assert stats["speculative"]["rounds"] == 0


def test_greedy_spec_streams_byte_identical_to_k0_with_real_acceptance():
    """The headline contract: greedy k>0 output == k=0 output, asserted
    alongside evidence that speculation actually ran AND accepted drafts
    (an engine that never speculates would pass identity vacuously)."""
    samp = [SamplingParams(max_tokens=48)] * len(_REP_PROMPTS)
    k0, _ = _run_streams(_cfg(), _REP_PROMPTS, samp)
    for k in (1, 4):
        kN, stats = _run_streams(_cfg(scheduler_spec_k=k),
                                 _REP_PROMPTS, samp)
        spec = stats["speculative"]
        assert kN.tokens == k0.tokens, f"spec_k={k} changed greedy text"
        assert kN.finishes == k0.finishes
        assert spec["rounds"] > 0, f"spec_k={k} never speculated"
        assert spec["accepted"] > 0, f"spec_k={k} never accepted a draft"
        assert spec["emitted"] > 0
        # the histogram bins every span by its accepted length
        assert sum(spec["accept_hist"].values()) > 0


def test_stop_token_finish_identical_under_speculation():
    """A stop token inside an accepted draft span must truncate the commit
    on device exactly where the k=0 scheduler would have stopped."""
    # greedy decode on tiny-llama settles into a cycle; stop on the emitted
    # token whose FIRST occurrence is latest, so the stream runs long enough
    # for speculation to engage before the stop truncates a span
    samp0 = [SamplingParams(max_tokens=64)]
    k0_probe, _ = _run_streams(_cfg(), [_REP_PROMPTS[0]], samp0)
    first: dict[int, int] = {}
    for i, t in enumerate(k0_probe.tokens[0]):
        first.setdefault(t, i)
    stop_tok = max(first, key=first.get)
    samp = [SamplingParams(max_tokens=64, stop_token_ids=(stop_tok,))]
    k0, _ = _run_streams(_cfg(), [_REP_PROMPTS[0]], samp)
    # synchronous ring: a deep ring drains for ~depth rounds before the
    # first spec round can run, and the stop-truncated stream is short —
    # depth 0 engages speculation the moment proposals appear (tokens are
    # depth-invariant, so the k=0 oracle needs no matching knob)
    kN, stats = _run_streams(_cfg(scheduler_spec_k=4, decode_lookahead=0),
                             [_REP_PROMPTS[0]], samp)
    assert k0.finishes[0] == "stop"
    assert kN.tokens == k0.tokens
    assert kN.finishes == k0.finishes
    assert stats["speculative"]["rounds"] > 0


def test_seeded_sampling_rides_spec_rounds_unchanged():
    """Sampled rows never speculate but DO share the ragged dispatch with
    speculating greedy rows — their per-token key streams (one split per
    emitted token) and therefore their tokens must be unchanged vs k=0."""
    # the greedy row is the one of _REP_PROMPTS whose continuation repeats
    # (334 334 334 441 ...): a row whose tail never recurs never drafts
    prompts = [[20, 21, 22] * 4, _REP_PROMPTS[1]]
    samp = [SamplingParams(max_tokens=30, temperature=0.8, seed=42),
            SamplingParams(max_tokens=48)]
    k0, _ = _run_streams(_cfg(), prompts, samp)
    kN, stats = _run_streams(_cfg(scheduler_spec_k=4), prompts, samp)
    assert kN.tokens == k0.tokens
    assert kN.finishes == k0.finishes
    assert stats["speculative"]["rounds"] > 0, \
        "the greedy row never speculated — the ride-along check is vacuous"


def test_spec_composes_with_lookahead_ring_and_preemption():
    """Speculation + a deep ring + a forced preempt/resume round-trip: the
    streams stay byte-identical to the synchronous k=0 scheduler (the
    faultlab spec-preempt scenario pins the same contract under fault
    injection; this is the in-suite twin)."""
    from cyberfabric_core_tpu.modkit import failpoints as fp

    samp = [SamplingParams(max_tokens=40)] * len(_REP_PROMPTS)
    k0, _ = _run_streams(_cfg(decode_lookahead=0), _REP_PROMPTS, samp)
    fp.configure(0)
    fp.arm("scheduler.page_alloc",
           {"kind": "raise", "exc": "MemoryError", "mode": "once",
            "after": 6})
    try:
        kN, stats = _run_streams(
            _cfg(scheduler_spec_k=3, decode_lookahead=3),
            _REP_PROMPTS, samp)
    finally:
        fp.disarm("scheduler.page_alloc")
    assert kN.tokens == k0.tokens
    assert kN.finishes == k0.finishes
    assert stats["speculative"]["rounds"] > 0


def test_rejected_suffix_kv_never_commits_kernel_golden():
    """Rollback is rewrite-before-read: write GARBAGE KV at the positions a
    rejected suffix would occupy (past the committed length), then run the
    next round's span over those positions — hidden states must match a
    reference pool that never saw the garbage (attend-after-rollback ==
    dense reference)."""
    from cyberfabric_core_tpu.models import llama
    from cyberfabric_core_tpu.models.configs import get_config
    from cyberfabric_core_tpu.ops.rope import rope_frequencies

    cfg = get_config("tiny-llama")
    import jax

    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    rope = rope_frequencies(cfg.head_dim, cfg.max_position, cfg.rope_theta)
    page = 8
    n_pages = 5
    pool_shape = (cfg.num_layers, n_pages, page,
                  cfg.num_kv_heads * cfg.head_dim)
    table = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
    rng = np.random.default_rng(3)
    prompt = rng.integers(3, 200, 8).tolist()
    committed = len(prompt)  # history through position 7
    cont = rng.integers(3, 200, 8).tolist()  # the true continuation span

    def run(poison: bool):
        pools = (jnp.zeros(pool_shape, jnp.float32),
                 jnp.zeros(pool_shape, jnp.float32))
        # prefill the committed history into the chain
        ids = jnp.asarray([prompt], jnp.int32)
        _, pools = llama.forward_paged_mixed(
            params, cfg, ids, pools, table,
            jnp.asarray([0], jnp.int32),
            jnp.asarray([committed], jnp.int32), rope, interpret=True)
        if poison:
            # a rejected draft span: garbage KV at positions committed..+7
            # (the state a spec round leaves after rejecting its suffix)
            k_pool, v_pool = pools
            junk = jnp.full((cfg.num_layers, page, pool_shape[-1]), 7.25,
                            jnp.float32)
            pools = (k_pool.at[:, 2].set(junk), v_pool.at[:, 2].set(junk))
        # next round: the span starts AT the committed length and rewrites
        # the poisoned positions before attending
        hidden, pools = llama.forward_paged_mixed(
            params, cfg, jnp.asarray([cont], jnp.int32), pools, table,
            jnp.asarray([committed], jnp.int32),
            jnp.asarray([len(cont)], jnp.int32), rope, interpret=True)
        return np.asarray(hidden[0, :len(cont)])

    clean = run(poison=False)
    poisoned = run(poison=True)
    np.testing.assert_array_equal(poisoned, clean)


def test_mixed_round_composition_chunks_plus_spec_plus_decode():
    """Chunks + speculating rows + plain decode rows in one dispatch: while
    a long prompt is mid-chunked-prefill, an in-flight greedy stream keeps
    speculating (spec_stats counts rounds that carried BOTH), a sampled
    stream rides along, and the greedy stream's text equals its solo k=0
    run (greedy streams are composition-invariant)."""
    cfg = _cfg(scheduler_spec_k=4, prefill_budget_tokens=16)
    solo_k0, _ = _run_streams(_cfg(), [_REP_PROMPTS[0]],
                              [SamplingParams(max_tokens=60)])
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(3)
    try:
        sched.submit(_REP_PROMPTS[0], SamplingParams(max_tokens=60),
                     col.emit_for(0))
        # wait until the greedy stream is decoding (and proposing) so the
        # long prompt's chunk rounds overlap live speculation
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            with col._lock:
                if len(col.tokens[0]) >= 6:
                    break
            time.sleep(0.01)
        long_prompt = list(np.random.default_rng(9).integers(3, 200, 120))
        sched.submit([int(t) for t in long_prompt],
                     SamplingParams(max_tokens=8), col.emit_for(1))
        sched.submit([13, 14, 15] * 4,
                     SamplingParams(max_tokens=8, temperature=0.9, seed=5),
                     col.emit_for(2))
        assert col.done.wait(240.0), (col.finishes, sched.stats())
        stats = sched.stats()
    finally:
        sched.shutdown()
    spec = stats["speculative"]
    assert spec["rounds"] > 0
    assert spec["mixed_rounds"] >= 1, \
        f"no round carried prefill chunks AND draft spans: {spec}"
    assert stats["pipeline"]["prefill_chunks"] >= 2
    assert col.tokens[0] == solo_k0.tokens[0]
    assert col.finishes[0] == solo_k0.finishes[0]


def test_spec_min_accept_gate_disables_hopeless_streams():
    """An impossible floor (>1.0) must switch every speculating stream off
    after its probation window — with text still byte-identical to k=0
    (the gate is a speed knob, never a correctness knob)."""
    samp = [SamplingParams(max_tokens=60)] * 2
    prompts = _REP_PROMPTS[:2]
    k0, _ = _run_streams(_cfg(), prompts, samp)
    kN, stats = _run_streams(
        _cfg(scheduler_spec_k=2, spec_min_accept=1.01), prompts, samp)
    assert kN.tokens == k0.tokens
    assert kN.finishes == k0.finishes
    spec = stats["speculative"]
    assert spec["rounds"] > 0, "gate test needs some pre-probation rounds"
    assert spec["slots_disabled"] >= 1, spec


def test_window_bound_streams_never_speculate_and_stay_identical():
    """A request whose max_tokens cannot fit before the window (the
    window-bound class) must keep the exact k=0 chunk-lattice 'length'
    finish — the engine refuses to speculate around it."""
    cfg0 = _cfg(max_seq_len=64)
    cfgN = _cfg(max_seq_len=64, scheduler_spec_k=4)
    prompts = [[5, 6, 7, 8] * 3]
    samp = [SamplingParams(max_tokens=200)]  # window-bound: 12+200 >> 64
    k0, _ = _run_streams(cfg0, prompts, samp)
    kN, stats = _run_streams(cfgN, prompts, samp)
    assert kN.tokens == k0.tokens
    assert kN.finishes == k0.finishes
    assert stats["speculative"]["rounds"] == 0


def test_spec_stats_and_round_timings_surface():
    """The observability satellite: stats()['speculative'] carries the full
    acceptance ledger and round timings stamp spec_tokens."""
    samp = [SamplingParams(max_tokens=32)] * 2
    _, stats = _run_streams(_cfg(scheduler_spec_k=3), _REP_PROMPTS[:2], samp)
    spec = stats["speculative"]
    for key in ("k", "rounds", "mixed_rounds", "proposed", "accepted",
                "emitted", "accept_rate", "accept_hist", "slots_disabled"):
        assert key in spec, key
    assert spec["k"] == 3
    assert spec["proposed"] >= spec["accepted"] >= 0
    assert 0.0 <= spec["accept_rate"] <= 1.0


def test_aot_serving_set_gains_spec_variant():
    """The AOT satellite: spec_k > 0 adds the ragged verify step to the
    serving program set, parameterized like --device-stop-width."""
    from cyberfabric_core_tpu.runtime.aot_tpu import serving_programs

    progs = serving_programs("tiny-llama", dtype=jnp.float32,
                             prefill_bucket=32, decode_chunk=4,
                             max_batch=2, max_seq_len=64, page_size=16,
                             spec_k=3)
    assert "spec-verify-w4x2" in progs
    base = serving_programs("tiny-llama", dtype=jnp.float32,
                            prefill_bucket=32, decode_chunk=4,
                            max_batch=2, max_seq_len=64, page_size=16)
    assert not any(name.startswith("spec-verify") for name in base)


def test_proposer_matches_longest_recent_ngram():
    p = NgramProposer(max_n=3, min_n=1, k=4)
    p.extend([1, 2, 3, 9, 1, 2, 3])
    # tail trigram (1,2,3) matched its earlier occurrence -> continues with 9…
    assert p.propose() == [9, 1, 2, 3]


def test_proposer_prefers_most_recent_occurrence():
    p = NgramProposer(max_n=2, min_n=1, k=2)
    p.extend([7, 1, 7, 2, 7])
    # unigram (7,): latest EARLIER occurrence is index 2 -> follows with 2, 7
    assert p.propose() == [2, 7]


def test_proposer_no_match_returns_none():
    p = NgramProposer(max_n=3, min_n=2, k=4)
    p.extend([1, 2, 3, 4, 5])
    assert p.propose() is None


def test_proposer_short_continuation_truncates():
    p = NgramProposer(max_n=1, min_n=1, k=8)
    p.extend([5, 6, 5])
    assert p.propose() == [6, 5]  # only two tokens follow the match


def accept_length(drafts: list[int], outs: list[int]) -> int:
    """The host oracle of greedy acceptance: number of leading drafts equal
    to the model's own argmax continuation (outs[i] is the model token after
    draft prefix i)."""
    a = 0
    for i, d in enumerate(drafts):
        if d != outs[i]:
            break
        a += 1
    return a


def test_accept_length():
    assert accept_length([1, 2, 3], [1, 2, 3, 4]) == 3
    assert accept_length([1, 9, 3], [1, 2, 3, 4]) == 1
    assert accept_length([9, 2, 3], [1, 2, 3, 4]) == 0
    assert accept_length([], [4]) == 0


def test_shared_accept_builder_matches_host_accept_length():
    """The device-side greedy_accept_counts and the host oracle agree on
    every (drafts, outs) shape."""
    rng = np.random.default_rng(0)
    S = 5
    for _ in range(50):
        outs = rng.integers(0, 4, (1, S)).astype(np.int32)
        d = int(rng.integers(0, S))
        drafts = rng.integers(0, 4, (1, S - 1)).astype(np.int32)
        dev = int(np.asarray(greedy_accept_counts(
            jnp.asarray(outs), jnp.asarray(drafts),
            jnp.asarray([d], jnp.int32)))[0])
        host = accept_length(list(drafts[0][:d]), list(outs[0]))
        assert dev == host, (outs, drafts, d)


@pytest.mark.parametrize("temp", [0.0, 0.8])
def test_sampled_requests_never_arm_a_proposer(temp):
    """Eligibility: only greedy, limit-bound requests arm a proposer."""
    cfg = _cfg(scheduler_spec_k=4)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(1)
    try:
        sched.submit(_REP_PROMPTS[0],
                     SamplingParams(max_tokens=8, temperature=temp,
                                    seed=3 if temp else None),
                     col.emit_for(0))
        assert col.done.wait(120.0)
        stats = sched.stats()
    finally:
        sched.shutdown()
    if temp:
        assert stats["speculative"]["rounds"] == 0
