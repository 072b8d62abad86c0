"""``tiny-sdar`` served by the continuous scheduler (no gateway): a decode
step that yields a block of tokens, not a token, on the one scheduler every
model is served by (programs ``mixed_step`` and ``paged_decode_chunk``, the
ring, the page pool).

The contract each case holds the scheduler to is the plain generate loop of
``benchmark/sdar_reference.py``: a request gets exactly its ``max_tokens``
whatever that is modulo the block, a stop id ends it inside a block, and a
greedy answer is the same whatever rides beside it, arrives mid-block of it,
or preempts it with a block open."""

import dataclasses
import json
import threading
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import layer_readers, sdar_reference, sdar_weights
from cyberfabric_core_tpu.models import get_config, sdar_moe
from cyberfabric_core_tpu.modkit.metrics import default_registry
from cyberfabric_core_tpu.ops.grouped_matmul import row_tile
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import (
    _BLOCK_SERIES, ContinuousBatchingEngine, _moe_series)

CFG = get_config("tiny-sdar")
W = CFG.block_length


def _cfg(**over):
    base = dict(model="tiny-sdar", max_seq_len=128, max_batch=4,
                decode_chunk=10, prefix_cache_pages=80,
                prefix_page_size=16, prefill_budget_tokens=32)
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


def _run(cfg, prompts, max_tokens=12, stagger_s=0.0, stops=(), **engine):
    sched = ContinuousBatchingEngine(cfg, seed=0, **engine)
    col = _Collector(len(prompts))
    limits = max_tokens if isinstance(max_tokens, list) \
        else [max_tokens] * len(prompts)
    try:
        for i, p in enumerate(prompts):
            if stagger_s and i:
                time.sleep(stagger_s)
            sched.submit(p, SamplingParams(max_tokens=limits[i],
                                           stop_token_ids=list(stops)),
                         col.emit_for(i))
        assert col.done.wait(240), (col.finishes, sched.stats())
        time.sleep(0.2)
        return col, sched
    finally:
        sched.shutdown()


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(3, 500, n).tolist()


def _counter(name):
    for _, value in default_registry.counter(name).samples():
        return value
    return 0.0


@pytest.mark.parametrize("max_tokens", [1, 4, 9, 10, 11, 12])
@pytest.mark.parametrize("leftover", [0, 3])
def test_every_request_gets_its_max_tokens(max_tokens, leftover):
    """``max_tokens`` modulo the block in 0-3, a prompt that ends on a block
    boundary and one that leaves 3 tokens to open the first block: the
    answer is cut INSIDE a block, and a shorter one is the longer's prefix."""
    prompt = _prompt(1, 20 + leftover)
    col, sched = _run(_cfg(), [prompt, prompt], max_tokens=[max_tokens, 16])
    assert len(col.tokens[0]) == max_tokens and col.finishes[0] == "length"
    assert col.tokens[1][:max_tokens] == col.tokens[0]
    assert CFG.mask_token_id not in col.tokens[1]
    pool = sched.pool.stats()
    assert pool["pages_referenced"] == 0 and pool["orphan_pages"] == 0


@pytest.mark.parametrize("prompt_len", [2, 16, 37])
def test_a_stop_id_inside_a_block_ends_the_answer_there(prompt_len):
    """Also a prompt shorter than a block (no lane tokens at all) and one of
    exactly a page."""
    prompt = _prompt(2, prompt_len)
    free, _ = _run(_cfg(), [prompt], max_tokens=14)
    want = free.tokens[0]
    at = next(i for i in range(5, 14) if want[i] not in want[:i])
    col, _ = _run(_cfg(), [prompt], max_tokens=14, stops=[want[at]])
    assert col.tokens[0] == want[: at + 1] and col.finishes[0] == "stop"
    assert (at + 1) % W or at + 1 < 14


def test_a_stop_id_in_the_prompts_leftover_is_no_stop():
    prompt = _prompt(3, 22)           # 20 whole, 2 open the first block
    col, _ = _run(_cfg(), [prompt], max_tokens=9, stops=[prompt[-1]])
    assert len(col.tokens[0]) == 9 or col.finishes[0] == "stop" \
        and col.tokens[0][-1] == prompt[-1]
    free, _ = _run(_cfg(), [prompt], max_tokens=9)
    n = len(col.tokens[0])
    assert col.tokens[0] == free.tokens[0][:n] and n >= 1


def test_a_greedy_answer_is_the_same_whatever_rides_beside_it():
    """Alone; beside three others admitted with it; and with neighbours
    arriving while it is mid-block (rows denoise and commit each by their own
    phase: no lockstep)."""
    mine, others = _prompt(4, 27), [_prompt(5 + i, 9 + 7 * i) for i in range(3)]
    alone, _ = _run(_cfg(), [mine], max_tokens=24)
    beside, _ = _run(_cfg(), [mine, *others], max_tokens=24)
    late, sched = _run(_cfg(decode_chunk=3), [mine, *others], max_tokens=24,
                       stagger_s=0.05)
    assert beside.tokens[0] == alone.tokens[0] == late.tokens[0]
    solo = [_run(_cfg(), [p], max_tokens=24)[0].tokens[0] for p in others]
    assert [late.tokens[i + 1] for i in range(3)] == solo
    assert sched.mixed_rounds >= 4 and sched.decode_rounds > sched.mixed_rounds


@pytest.mark.parametrize("remasking,threshold", [
    ("low_confidence_static", 0.9), ("low_confidence_dynamic", 0.004)])
def test_the_served_answer_is_the_plain_generate_loop(remasking, threshold):
    """The scheduler (pages, chunks, the ring) against the reference's loop
    of whole forwards, greedy, on the benchmark's seeded weights with the
    norms in float32 (so both sides compute in float32 and the largest logit
    is the same). Under the dynamic rule at a threshold random logits pass,
    blocks close in fewer forwards."""
    conf = {"hidden_size": 64, "moe_intermediate_size": 32, "vocab_size": 512,
            "num_experts": 8, "num_experts_per_tok": 2, "head_dim": 16,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
            "serving": {"block_length": W}}
    tree = sdar_weights.make_weights(conf, 5, 2)
    as_f32 = lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
    tree = {**tree, "final_norm": as_f32(tree["final_norm"]),
            "layers": {k: (as_f32(v) if not isinstance(v, dict) else v)
                       for k, v in tree["layers"].items()}}
    model = dataclasses.replace(CFG, remasking=remasking,
                                confidence_threshold=threshold)
    kw = sdar_reference.reference_kwargs(conf, 2)

    def logits_of(tokens):
        n = -(-len(tokens) // 32) * 32
        ids = np.full(n, CFG.mask_token_id, np.int32)
        ids[: len(tokens)] = tokens
        out, _ = sdar_reference.forward_logits(
            tree, jnp.asarray(ids), jnp.arange(len(tokens)), **kw)
        return out

    prompt = _prompt(8, 23)
    want = sdar_reference.generate(
        logits_of, prompt, 13, block=W, steps=CFG.denoising_steps,
        mask_id=CFG.mask_token_id,
        dynamic=remasking == "low_confidence_dynamic", threshold=threshold)
    before = _counter("llm_block_row_forwards_total")
    col, _ = _run(_cfg(quantization="int8", dtype="float32"), [prompt],
                  max_tokens=13, model_config=model, params=tree)
    assert col.tokens[0] == want
    forwards = _counter("llm_block_row_forwards_total") - before
    # 3 leftover tokens: 1 denoise + 1 commit forward, then 3 blocks of 4 + 1
    if remasking == "low_confidence_static":
        assert forwards == 2 + 3 * 5
    else:
        assert forwards < 2 + 3 * 5


@pytest.mark.parametrize("depth", [0, 2])
def test_preempt_with_a_block_open_and_resume(depth):
    """Pool pressure while the stream decodes, in chunks of 3 forwards so
    that a round ends mid-block: the open block goes to the host with the
    pages and the answer is the uninterrupted one."""
    prompt = _prompt(6, 21)
    cfg = _cfg(max_batch=2, prefix_cache_pages=64, prefix_page_size=4,
               decode_chunk=3, decode_lookahead=depth)
    want, _ = _run(cfg, [prompt], max_tokens=40)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(1)
    try:
        orig_extend, armed = sched.pool.extend_chain, threading.Event()

        def flaky_extend(chain, needed):
            if armed.is_set() and sched.preemptions == 0:
                raise MemoryError("injected pool pressure")
            return orig_extend(chain, needed)

        sched.pool.extend_chain = flaky_extend
        sched.submit(prompt, SamplingParams(max_tokens=40), col.emit_for(
            0, then=lambda ev: len(col.tokens[0]) >= 10 and armed.set()))
        assert col.done.wait(240), (col.tokens, sched.stats())
    finally:
        sched.shutdown()
    assert sched.preemptions >= 1, "injected pressure never preempted"
    assert col.tokens == want.tokens


@pytest.mark.parametrize("over,says", [
    (dict(scheduler_spec_k=2), "scheduler_spec_k"),
    (dict(pd_role="prefill"), "pd_role"),
    (dict(tp=2), "tp > 1"),
    (dict(prefix_page_size=6), "whole blocks"),
])
def test_modes_that_lack_one_named_thing_are_refused_at_build(over, says):
    with pytest.raises(ValueError, match=says):
        ContinuousBatchingEngine(_cfg(**over), seed=0)


def test_the_counters_of_a_block_model():
    """/metrics: a running row's forwards, the commit forwards among them,
    blocks and tokens the host took, experts touched over experts offered;
    the round records and a request's flight record carry the same."""
    from cyberfabric_core_tpu.modkit.flight_recorder import default_recorder

    series = _BLOCK_SERIES + _moe_series(sdar_moe.STEP_COUNTERS)
    before = {s: _counter(s) for s in series}
    col, sched = _run(_cfg(decode_lookahead=0), [_prompt(9, 18)],
                      max_tokens=16)
    after = {s: _counter(s) for s in series}
    d = {s.removeprefix("llm_").removesuffix("_total"):
         after[s] - before[s] for s in series}
    assert d["block_tokens_emitted"] == 16
    assert d["blocks_committed"] == d["block_commit_row_forwards"] == 5
    # 2 leftover tokens: 2 denoise forwards, then 4 a block; one commit each
    assert d["block_row_forwards"] == 2 + 4 * 4 + 5
    assert 0 < d["moe_experts_touched"] <= d["moe_experts_offered"]
    assert d["moe_experts_offered"] % (CFG.num_layers * CFG.num_experts) == 0
    # the one mechanism: the decode chunks' share of both, counted apart
    assert 0 < d["moe_decode_experts_touched"] <= d["moe_experts_touched"]
    assert 0 < d["moe_decode_experts_offered"] < d["moe_experts_offered"]
    # the rows one grouped matmul of a layer multiplied: whole tiles, at
    # least one for every expert touched; the benchmark's
    # ``moe_item_rows_per_touched_expert`` reads the pair through the
    # ``counter`` kind, and nothing from a program without the series
    tile = row_tile(16 * CFG.experts_per_token, CFG.num_experts)
    assert d["moe_item_rows"] % 16 == 0
    assert d["moe_item_rows"] >= tile * d["moe_experts_touched"] // 2
    metric = json.loads((Path(__file__).resolve().parents[1] / "benchmark" /
                         "layer_metrics/moe_item_rows_per_touched_expert.json"
                         ).read_text())
    args = {k: v for k, v in metric.items() if k not in ("kind", "what")}
    names = (args["series"], args["over"])
    assert metric["kind"] == "counter" and set(names) <= set(series)
    ends = {"start": before, "end": after}
    assert layer_readers.counter({"scrapes": ends}, **args) == \
        d["moe_item_rows"] / d["moe_experts_touched"]
    assert layer_readers.counter(
        {"scrapes": {k: {names[1]: v[names[1]]} for k, v in ends.items()}},
        **args) is None
    rounds = [r for r in sched.round_timings if "forwards" in r]
    assert sum(r["blocks_committed"] for r in rounds) == 5
    assert sum(r["tokens_emitted"] for r in rounds) == 16
    assert {r["forwards"] for r in rounds} <= {1, 10}
    newest = default_recorder.recent(1)[0]["request_id"]
    chunks = [e for e in default_recorder.lookup(newest)["timeline"]
              if e["event"] == "decode_chunk"]
    assert sum(e["blocks"] for e in chunks) == 5
    assert sum(e["row_forwards"] for e in chunks) == 2 + 4 * 4 + 5
