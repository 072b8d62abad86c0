"""Continuous batching scheduler tests (CPU backend, tiny model).

Key invariants: slot reuse mid-flight, greedy parity with the plain oracle
(``conftest.greedy_oracle``), no token corruption when requests join/leave,
capacity finishing.
"""

import threading
import time

import pytest

from conftest import greedy_oracle, run_request
from cyberfabric_core_tpu.runtime.engine import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine


@pytest.fixture(scope="module")
def sched():
    cfg = EngineConfig(model="tiny-llama", max_seq_len=96, max_batch=3,
                       decode_chunk=4)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    yield sched
    sched.shutdown()


def oracle(sched, prompt, sampling):
    """The plain greedy continuation on the engine's own params."""
    return greedy_oracle(sched.params, sched.model_config, prompt,
                         sampling.max_tokens,
                         (*sampling.stop_token_ids,
                          *sched.config.eos_token_ids))


def test_single_request_matches_the_plain_oracle(sched):
    prompt = [1, 5, 9, 13]
    sampling = SamplingParams(max_tokens=10)
    assert run_request(sched, prompt, sampling) == oracle(
        sched, prompt, sampling)


def test_concurrent_requests_and_slot_reuse(sched):
    prompts = [[1, 5], [1, 7, 9], [2, 4, 6, 8], [3], [9, 9, 1]]
    sampling = SamplingParams(max_tokens=6)
    expected = [oracle(sched, p, sampling) for p in prompts]

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
        assert (results[i], finishes[i]) == expected[i], \
            f"request {i} diverged"


def test_capacity_finish(sched):
    long_prompt = list(range(3, 88))  # 85 tokens in a 96 window, chunk 4
    tokens, finish = run_request(sched, long_prompt,
                                 SamplingParams(max_tokens=500))
    assert finish == "length"
    assert 1 <= len(tokens) <= 96 - 85


def test_stats(sched):
    s = sched.stats()
    assert s["requests_completed"] >= 7
    assert s["tokens_emitted"] > 10
    assert s["slots"] == 3


@pytest.mark.parametrize("model,quant", [
    ("tiny-llama", "none"), ("tiny-qwen2", "none"), ("tiny-gemma", "none"),
    ("tiny-llama", "int8"), ("tiny-llama", "int4"), ("tiny-qwen2", "int8"),
    ("tiny-moe", "none")])
def test_greedy_streams_are_the_plain_oracles(model, quant):
    """Every dense preset and quantization the engine serves, against the
    plain forward on the engine's own params, for a prompt that spans two
    pages and two prefill chunks. In float32: two chunks
    round a bfloat16 sum otherwise than one pass, and a near tie then picks
    another token."""
    eng = ContinuousBatchingEngine(EngineConfig(
        model=model, max_seq_len=64, max_batch=2, decode_chunk=4,
        dtype="float32", quantization=quant, prefix_page_size=16,
        prefill_budget_tokens=16), seed=0)
    sampling = SamplingParams(max_tokens=6)
    try:
        if quant != "none":
            assert isinstance(eng.params["layers"]["wq"], dict)
            # a bias stays as it was made
            assert not isinstance(eng.params["layers"].get("bq"), dict)
        prompt = list(range(3, 24))
        assert run_request(eng, prompt, sampling) == oracle(
            eng, prompt, sampling)
    finally:
        eng.shutdown()


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
                       decode_chunk=4, quantization=quant,
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
