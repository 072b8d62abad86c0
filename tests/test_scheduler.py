"""Continuous batching scheduler tests (CPU backend, tiny model).

Key invariants: slot reuse mid-flight, greedy parity with the lockstep engine,
no token corruption when requests join/leave, capacity finishing.
"""

import queue
import threading
import time

import pytest

from cyberfabric_core_tpu.runtime.engine import EngineConfig, InferenceEngine, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine


@pytest.fixture(scope="module")
def engines():
    cfg = EngineConfig(model="tiny-llama", max_seq_len=96, max_batch=3,
                       decode_chunk=4)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    ref = InferenceEngine(cfg, seed=0)
    # identical params (same seed/init path)
    yield sched, ref
    sched.shutdown()


def run_request(sched, prompt, sampling, timeout=120.0):
    q: "queue.Queue" = queue.Queue()
    done = threading.Event()
    tokens: list[int] = []
    finish: list[str] = []

    def emit(ev):
        if ev.token_id >= 0:
            tokens.append(ev.token_id)
        if ev.finished:
            finish.append(ev.finished)
            done.set()

    sched.submit(prompt, sampling, emit)
    assert done.wait(timeout), "request did not finish"
    return tokens, finish[0]


def test_single_request_matches_lockstep(engines):
    sched, ref = engines
    prompt = [1, 5, 9, 13]
    sampling = SamplingParams(max_tokens=10)
    expected = ref.generate([prompt], sampling)[0]
    tokens, finish = run_request(sched, prompt, sampling)
    # lockstep result drops the stop token from visible output; scheduler emits
    # raw tokens — compare modulo trailing stop token
    if finish == "stop":
        tokens = tokens[:-1]
    assert tokens == expected.token_ids
    assert finish == expected.finish_reason


def test_concurrent_requests_and_slot_reuse(engines):
    sched, ref = engines
    prompts = [[1, 5], [1, 7, 9], [2, 4, 6, 8], [3], [9, 9, 1]]
    sampling = SamplingParams(max_tokens=6)
    expected = [ref.generate([p], sampling)[0].token_ids for p in prompts]

    results: dict[int, list[int]] = {i: [] for i in range(len(prompts))}
    finishes: dict[int, str] = {}
    done = threading.Event()
    lock = threading.Lock()

    def mk_emit(i):
        def emit(ev):
            if ev.token_id >= 0:
                results[i].append(ev.token_id)
            if ev.finished:
                with lock:
                    finishes[i] = ev.finished
                    if len(finishes) == len(prompts):
                        done.set()
        return emit

    # submit 5 requests into 3 slots — forces mid-flight slot reuse
    for i, p in enumerate(prompts):
        sched.submit(p, sampling, mk_emit(i))
    assert done.wait(180), f"finished only {len(finishes)}/{len(prompts)}"

    for i in range(len(prompts)):
        got = results[i][:-1] if finishes[i] == "stop" else results[i]
        assert got == expected[i], f"request {i} diverged"


def test_capacity_finish(engines):
    sched, _ = engines
    long_prompt = list(range(3, 88))  # 85 tokens in a 96 window, chunk 4
    tokens, finish = run_request(sched, long_prompt,
                                 SamplingParams(max_tokens=500))
    assert finish == "length"
    assert 1 <= len(tokens) <= 96 - 85


def test_stats(engines):
    sched, _ = engines
    s = sched.stats()
    assert s["requests_completed"] >= 7
    assert s["tokens_emitted"] > 10
    assert s["slots"] == 3


_SMALL = dict(model="tiny-llama", max_seq_len=64, max_batch=2, decode_chunk=4,
              prefix_page_size=16)
#: every slot a full window (64 / 16 = 4 pages), plus the scratch page
_SLOT_MINIMUM = 2 * 4 + 1


@pytest.mark.parametrize("pages", [None, 1, _SLOT_MINIMUM - 1],
                         ids=["default", "1", "minimum-1"])
def test_a_pool_size_under_the_slot_minimum_is_raised_to_it(pages):
    """``prefix_cache_pages`` sizes the pool and picks nothing: the
    dataclass default (0) builds the engine every configuration serves."""
    over = {} if pages is None else {"prefix_cache_pages": pages}
    sched = ContinuousBatchingEngine(EngineConfig(**_SMALL, **over), seed=0)
    try:
        assert sched.pool.num_pages == _SLOT_MINIMUM
        assert sched.page_table.shape == (2, 4)
    finally:
        sched.shutdown()


def test_the_default_engine_honours_a_request_seed():
    """An EngineConfig that names no pool size carries per-slot key streams
    like any other: one seed, one stream, whatever ran before it."""
    sched = ContinuousBatchingEngine(EngineConfig(**_SMALL), seed=0)
    sampling = SamplingParams(max_tokens=8, temperature=0.9, seed=42)
    try:
        first, _ = run_request(sched, [5, 6, 7], sampling)
        run_request(sched, [9, 9], SamplingParams(max_tokens=3,
                                                  temperature=0.9))
        again, _ = run_request(sched, [5, 6, 7], sampling)
        other, _ = run_request(sched, [5, 6, 7], SamplingParams(
            max_tokens=8, temperature=0.9, seed=43))
    finally:
        sched.shutdown()
    assert first == again and len(first) == 8
    assert other != first


@pytest.mark.parametrize("quant", ["int8", "int4"])
def test_quantized_continuous_scheduler_decodes(quant):
    """The paged scheduler honors EngineConfig.quantization end to end.
    Regression: it used to init bf16 params regardless, and prefill_collect
    crashed on quantized trees (dict embed has no .dtype) — so the bench's
    int8 aggregate rung had silently never run quantized."""
    import threading

    from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    cfg = EngineConfig(model="tiny-llama", max_seq_len=64, max_batch=2,
                       decode_chunk=4, use_flash=False, quantization=quant,
                       prefix_cache_pages=20, prefix_page_size=16)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    try:
        assert isinstance(sched.params["layers"]["wq"], dict)
        done = threading.Event()
        toks = []

        def emit(ev):
            if ev.token_id >= 0:
                toks.append(ev.token_id)
            if ev.finished:
                done.set()

        sched.submit([5, 6, 7], SamplingParams(max_tokens=5), emit)
        assert done.wait(180)
        assert len(toks) == 5
    finally:
        sched.shutdown()


def test_the_ragged_walk_of_a_kv_model_is_counted_at_a_mixed_steps_dispatch():
    """/metrics ``llm_ragged_pages_walked_total`` over
    ``llm_ragged_trips_total``, the round records' ``ragged_pages`` /
    ``ragged_trips`` and the ``llm.prefill_chunk`` span's attributes for the
    llama path (mistral's, qwen2's): what the ragged K/V kernel copies and
    attends over for a prompt's chunks, by its own span on the host (the
    kernel's side of the equality is tests/test_ragged_attention.py's)."""
    from cyberfabric_core_tpu.modkit.metrics import default_registry
    from cyberfabric_core_tpu.modkit.telemetry import (
        Span, SpanExporter, Tracer, get_global_tracer, set_global_tracer)

    class Collect(SpanExporter):
        spans: list = []

        def export(self, span: Span, duration_ms: float) -> None:
            self.spans.append(span)

    def counter(name):
        for _, value in default_registry.counter(name).samples():
            return value
        return 0.0

    names = ("llm_ragged_pages_walked_total", "llm_ragged_trips_total")
    before = [counter(s) for s in names]
    prev = get_global_tracer()
    set_global_tracer(Tracer(exporter=Collect()))
    sched = ContinuousBatchingEngine(EngineConfig(
        model="tiny-llama", max_seq_len=96, max_batch=3, decode_chunk=4,
        prefix_page_size=8, prefill_budget_tokens=32, decode_lookahead=0),
        seed=0)
    done = threading.Event()
    try:
        sched.submit(list(range(5, 55)), SamplingParams(max_tokens=5),
                     lambda ev: ev.finished and done.set(),
                     trace="00-" + "ab" * 16 + "-" + "cd" * 8 + "-01")
        assert done.wait(240), sched.stats()
    finally:
        sched.shutdown()
        set_global_tracer(prev)
    # a prompt of 50 in chunks of 32 + 18, pages of 8, one q-block a chunk:
    # keys 0..31 are 4 pages, keys 0..49 are 7, in each of the 2 layers, and
    # a trip of 16 pages takes either whole
    want = [(2 * 4, 2 * 1), (2 * 7, 2 * 1)]
    assert [counter(s) - b for s, b in zip(names, before)] == [22, 4]
    mixed = [r for r in sched.round_timings if r["chunk_tokens"]]
    assert [(r["ragged_pages"], r["ragged_trips"]) for r in mixed] == want
    chunks = [s.attributes for s in Collect.spans
              if s.name == "llm.prefill_chunk"]
    assert [(a["tokens"], a["ragged_pages"], a["ragged_trips"])
            for a in chunks] == [(32, *want[0]), (18, *want[1])]
