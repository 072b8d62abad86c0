"""``tiny-ouro`` served by the continuous scheduler (no gateway): a model whose
stack runs 3 times a token, so the page pool is 9 cache layers deep for 3
layers of weights. Greedy tokens are compared: an answer repeats, a prefix hit
answers as a miss, a row preempted to the host (all 9 cache layers moved) and
resumed answers as the uninterrupted run; the pool, ``stats()``, the counters
and the round records read ``loop_steps x L`` layers and what the exit gate
says; the modes that cannot carry the loop are refused at build."""

import dataclasses
import threading

import numpy as np
import pytest

from cyberfabric_core_tpu.models import get_config, ouro
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import (_LOOP_SERIES,
                                                    ContinuousBatchingEngine)
from test_nemotron_h_scheduler import _Collector, _counter, _run

MODEL = get_config("tiny-ouro")
L, R = MODEL.num_layers, MODEL.loop_steps
PAGE = 16
SERIES = _LOOP_SERIES + ("llm_attn_pages_walked_total",
                         "llm_attn_pages_offered_total")


def _cfg(**over):
    # one set of shapes and one tree (int8) for every engine of this file:
    # the step programs compile once (``runtime/programs.py`` keeps them)
    base = dict(model="tiny-ouro", max_seq_len=256, max_batch=4,
                decode_chunk=4, prefix_cache_pages=65,
                prefix_page_size=PAGE, prefill_budget_tokens=32,
                quantization="int8")
    base.update(over)
    return EngineConfig(**base)


def _prompts(seed=0, sizes=(70, 20)):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 250, n).tolist() for n in sizes]


@pytest.fixture(scope="module")
def served():
    """Two requests through an int8 engine, and the counters they moved."""
    before = {s: _counter(s) for s in SERIES}
    tokens, stats, sched = _run(_cfg(), _prompts(), max_tokens=20)
    moved = {s.removeprefix("llm_").removesuffix("_total"):
             _counter(s) - before[s] for s in SERIES}
    return tokens, stats, sched, moved


def test_the_pool_and_the_stats_read_loop_steps_x_layers(served):
    tokens, stats, sched, _ = served
    assert all(len(t) == 20 for t in tokens.values())
    pool = stats["prefix_cache"]
    assert (pool["kv_layers"], pool["model_layers"], pool["loop_steps"],
            pool["page_layout"]) == (R * L, L, R, "kv")
    # 9 cache layers x K and V x 4 heads of 32, bfloat16
    assert pool["cache_bytes_per_token"] == R * L * 2 * 4 * 32 * 2 == \
        MODEL.cache_bytes_per_token()
    assert sched.pool.k_pool.shape[:2] == (R * L, 65)
    assert pool["pool_bytes"] == 2 * R * L * 65 * PAGE * 128 * 2
    assert len(sched.pool.cache_operands()) == 2
    assert sched.params["layers"]["wq"]["q"].shape[0] == L   # weights: once
    assert sched.params["exit_gate"]["w"].dtype == np.float32
    # released at finish; the tree keeps the prompts' whole pages (4 + 1)
    assert pool["pages_free"] == pool["pages_total"] - 5


def test_passes_over_forwards_is_loop_steps_and_the_gate_is_counted(served):
    """Counters bumped at a drain, for decode chunks and mixed steps alike:
    ``loop_steps`` passes a forward; the gate's expected exit pass summed
    over the decode rows that ran lies between the first pass and the last;
    the kernel's pages are offered by all 9 cache layers; the round records
    carry ``passes`` and ``exit_pass_mean``."""
    _, _, sched, d = served
    mixed = [r for r in sched.round_timings if r["mixed"]]
    chunks = [r for r in sched.round_timings if not r["mixed"]]
    forwards = len(mixed) + 4 * len(chunks)
    assert d["loop_forwards"] == forwards > 0
    assert d["loop_passes"] == R * forwards
    # every generated token but a request's first came from a decode row
    assert d["loop_exit_rows"] >= 2 * 19
    mean = d["loop_exit_pass_sum"] / d["loop_exit_rows"]
    assert 1.0 < mean < R
    assert d["attn_pages_offered"] == forwards * R * L * 4 * 16
    assert all((r["loop_steps"], r["passes"]) == (
        R, R * (1 if r["mixed"] else 4)) for r in sched.round_timings)
    with_rows = [r for r in sched.round_timings if "exit_pass_mean" in r]
    assert with_rows and all(1.0 < r["exit_pass_mean"] < R
                             for r in with_rows)


def test_a_greedy_answer_repeats_and_a_prefix_hit_answers_as_a_miss(served):
    """Sent in turn behind a prompt that shares its first 64 tokens (four
    pages in all 9 cache layers), a prompt answers as it does cold; and the
    fixture's first prompt, served again by another engine beside another
    neighbour, repeats its greedy answer."""
    tokens, _, _, _ = served
    base = _prompts()[0]
    shared = base[:64] + [7, 8, 9, 10, 11, 12, 13, 14, 15]
    cold, _, _ = _run(_cfg(), [shared], max_tokens=20)
    warm, stats, _ = _run(_cfg(), [base, shared], max_tokens=20,
                          in_turn=True)
    assert warm[1] == cold[0]
    assert warm[0] == tokens[0]
    pool = stats["prefix_cache"]
    assert pool["hits"] >= 1 and pool["prefill_tokens_saved"] >= 64


def test_preempt_mid_decode_and_resume_equals_the_uninterrupted_run():
    """Pool pressure while a row of 40 + 8 tokens decodes behind a
    lookahead ring: the row goes to the host, ALL ``loop_steps x L`` cache
    layers of its pages, and comes back to answer as the uninterrupted run
    (a mover that carried the model's 3 layers would bring back a row whose
    passes 2 and 3 attend over nothing)."""
    prompt = _prompts(9, (40,))[0]
    cfg = _cfg(decode_lookahead=2)
    want, _, _ = _run(cfg, [prompt], max_tokens=28)

    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(1)
    saved = []
    try:
        orig_extend, orig_save = sched.pool.extend_chain, \
            sched.pool.save_chain_to_host
        armed = threading.Event()

        def flaky_extend(chain, needed):
            if armed.is_set() and sched.preemptions == 0:
                raise MemoryError("injected pool pressure")
            return orig_extend(chain, needed)

        def save(chain, state_row=None):
            host_kv = orig_save(chain, state_row)
            saved.append([a.shape for a in host_kv])
            return host_kv

        sched.pool.extend_chain = flaky_extend
        sched.pool.save_chain_to_host = save

        def arm(ev):
            if len(col.tokens[0]) == 8:
                armed.set()
        sched.submit(prompt, SamplingParams(max_tokens=28),
                     col.emit_for(0, then=arm))
        assert col.done.wait(240), (col.tokens, sched.stats())
    finally:
        sched.shutdown()
    assert sched.preemptions >= 1, "injected pressure never preempted"
    assert col.tokens == want
    # K and V, each [9 cache layers, pages, page, heads, head size]
    assert saved and all(shape[0] == R * L and shape[2:] == (PAGE, 4, 32)
                         for shapes in saved for shape in shapes)


@pytest.mark.parametrize("over,model,what", [
    (dict(), dataclasses.replace(MODEL, early_exit_threshold=0.9),
     "only the published 1"),
    (dict(scheduler_spec_k=3), None, "runs the stack once"),
    (dict(pd_role="prefill"), None, "no decode-role admission"),
    (dict(tp=2), None, "no sharding for the sandwich norms"),
])
def test_a_mode_the_loop_cannot_carry_is_refused_at_build(over, model, what):
    with pytest.raises(ValueError, match=what):
        ContinuousBatchingEngine(_cfg(**over), model_config=model, seed=0)


def test_the_forwards_refuse_a_mesh():
    with pytest.raises(ValueError, match="one device"):
        ouro._one_device(object(), None)
