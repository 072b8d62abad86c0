"""Readers and counts that are found by name: the ``counter`` kind over two
canned scrapes, a reader kind brought as a file, a count named by a
configuration, and no count for an architecture that names none."""

import pytest

from benchmark import layer_readers, opcounts
from benchmark.server import HarnessFailure, parse_prometheus

START = parse_prometheus("""# HELP llm_prefill_tokens_saved_total cumulative
llm_prefill_tokens_saved_total 1000
llm_prefill_chunk_tokens_total 4000
llm_queue_wait_p50_ms 10
labelled_total{model="a"} 5
""")
MID = {**START, "llm_queue_wait_p50_ms": 40.0}
END = parse_prometheus("""llm_prefill_tokens_saved_total 1600
llm_prefill_chunk_tokens_total 4200
llm_queue_wait_p50_ms 70
""")
CTX = {"scrapes": {"start": START, "end": END, "all": [START, MID, END]}}
SAVED, CHUNK = "llm_prefill_tokens_saved_total", "llm_prefill_chunk_tokens_total"


@pytest.mark.parametrize("spec, want", [
    ({"series": SAVED}, 600.0),
    ({"series": CHUNK}, 200.0),
    ({"series": SAVED, "over": CHUNK}, 3.0),
    ({"series": "llm_queue_wait_p50_ms", "gauge": True}, 40.0),
    ({"series": CHUNK, "over": "llm_queue_wait_p50_ms", "gauge": True}, None),
    ({"series": "labelled_total"}, None),           # unlabelled series only
    ({"series": "no_such_series"}, None),
    ({"series": SAVED, "over": "no_such_series"}, None),
])
def test_counter_over_two_scrapes(spec, want):
    if spec.get("over") == "llm_queue_wait_p50_ms":     # a gauge's mean ratio
        want = ((4000 + 4000 + 4200) / 3) / 40.0
    assert layer_readers.resolve("counter")(CTX, **spec) == pytest.approx(want)


def test_a_divisor_that_did_not_move_is_nothing_to_read():
    still = {"scrapes": {"start": START, "end": START, "all": [START]}}
    assert layer_readers.counter(still, SAVED) == 0.0
    assert layer_readers.counter(still, SAVED, over=CHUNK) is None
    assert layer_readers.counter({}, SAVED) is None


LLAMA = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 512, "serving": {"max_batch": 4}, "correctness": {}}
TOY = "benchmark.tests.rehearsal.toy_recurrent"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def roofline(conf):
    ctx = {"values": {"decode_step_ms": 0.001}, "peaks": PEAKS, "config": conf}
    return layer_readers.roofline(ctx, "decode_step_weights", "decode_step_ms")


def test_a_count_is_the_configurations():
    named = {**LLAMA, "correctness": {"adapter": f"{TOY}.adapter"},
             "counts": f"{TOY}.counts"}
    own = opcounts.count_function(named, "decode_step_weights")
    assert own.__module__ == f"{TOY}.counts"
    assert opcounts.count_function(LLAMA, "decode_step_weights") \
        is opcounts.decode_step_weights
    assert roofline(named) > roofline(LLAMA) > 0


def test_another_architecture_that_names_no_count_reads_nothing():
    bare = {**LLAMA, "correctness": {"adapter": f"{TOY}.adapter"}}
    assert opcounts.count_function(bare, "decode_step_weights") is None
    assert roofline(bare) is None
    named = {**bare, "counts": f"{TOY}.counts"}
    assert opcounts.count_function(named, "no_such_role") is None
    assert opcounts.count_function(named, "_private") is None


def test_reader_kinds_resolve_from_files():
    kind = layer_readers.resolve(f"{TOY}.readers:count_field")
    named = {**LLAMA, "counts": f"{TOY}.counts"}
    assert kind({"config": named}, "decode_step_weights", "bytes") > 0
    assert layer_readers.resolve("rounds") is layer_readers.rounds
    for broken in ("no_such_kind", f"{TOY}.readers:no_such", "no.such.module:f"):
        with pytest.raises(HarnessFailure):
            layer_readers.resolve(broken)
