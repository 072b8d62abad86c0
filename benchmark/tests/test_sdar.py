"""What the SDAR-MoE configuration brings to the yardstick, shown at no chip
cost on ``tiny-sdar`` (``rehearsal/BENCHMARK-sdar.json``, a rehearsal
benchmark file of its own: no file that was there is edited): every name in
its data files resolves; the judge passes it through
``benchmark/adapters/sdar.py`` and catches its controls; a commit forward
that keeps a denoise forward's K/V fails; its cell runs through the harness
with the two counter metrics on the line; and the expert layer's time is a
layer's three kernel calls."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import layer_readers, sdar_readers
from benchmark.tests import test_names_resolve as resolve
from benchmark.tests.test_readers import PEAKS
from benchmark.tests.test_seam import CONFIGS, REPO, judge

BENCH = "benchmark/tests/rehearsal/BENCHMARK-sdar.json"
CELL = "tiny-sdar.decode-closed"
NEW_METRICS = ("moe_experts_us", "moe_kernel_time_share",
               "moe_experts_touched_share", "denoise_tokens_per_forward",
               "moe_experts_roofline", "block_forward_roofline")


def test_the_rehearsal_files_names_resolve():
    bench = json.loads((REPO / BENCH).read_text())
    assert [w["name"] for w in bench["workloads"]] == [CELL]
    resolve.test_configuration_resolves(BENCH, "tiny-sdar")
    resolve.test_traffic_file_resolves(BENCH, "decode-closed")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL] for name in NEW_METRICS)
    for name in listed:
        resolve.test_layer_metric_resolves(BENCH, name)


def test_the_block_architecture_passes_and_its_controls_are_caught():
    """The row the judge keys by (row, p) is the logits at p + 1 of
    seq[:p+1] + masks, the decode steps cross two block boundaries, and the
    reference computes with the experts the program chose."""
    rc, result = judge("tiny-sdar", "--control")
    assert rc == 0 and result["ok"]
    assert result["adapter"] == "benchmark.adapters.sdar"
    limit = result["limit"]
    for r in result["readings"]:
        assert r["program"]["worst_row_rms"] <= limit
        assert r["program"]["rows"] >= 40
        assert r["idle_rows_touched"] == []
        assert r["control_int4"]["worst_row_rms"] > 3 * limit
        assert r["control_fp8"]["worst_row_rms"] > 2 * limit


def test_a_commit_forward_that_keeps_a_denoise_forwards_kv_fails():
    """``rehearsal/sdar_broken.py`` counts a block as kept on the strength
    of the denoise forward that ran it last (its later positions were still
    masks): the experts of those positions are not the ones the reference's
    scores allow, and the run fails at the epsilon check."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.correctness", "--config",
         f"{CONFIGS}/tiny-sdar-broken.json", "--seeds", "3", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0
    assert "routing: an expert the program chose lies" in proc.stderr
    assert not any(l.startswith("RESULT ") and json.loads(l[7:])["ok"]
                   for l in proc.stdout.splitlines())


def test_the_cell_runs_through_the_harness():
    """Every request gets its ``max_tokens`` out of blocks of 4, the two
    counter metrics read (at most 4 tokens in 5 forwards; experts touched over
    experts offered), and without a device in the trace the expert kernel's
    three metrics are left out of the line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--benchmark-file", BENCH,
         "--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "5",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    result = json.loads(last[len("REHEARSAL "):])
    assert result["correct"] and result["failed"] == 0
    assert "correctness: adapter benchmark.adapters.sdar" in proc.stdout
    metrics = result["metrics"]
    # 0.8 in the long run; a 5 s window's two scrapes cut through chunks
    assert 0.6 < metrics["denoise_tokens_per_forward"]["value"] < 0.9
    assert 0 < metrics["moe_experts_touched_share"]["value"] <= 1
    assert not {"moe_experts_us", "moe_experts_roofline",
                "moe_kernel_time_share", "block_forward_roofline",
                "decode_step_roofline"} & set(metrics)


SDAR = {"hidden_size": 2048, "moe_intermediate_size": 768, "vocab_size": 151936,
        "num_hidden_layers": 16, "num_experts": 128, "num_experts_per_tok": 8,
        "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
        "serving": {"max_batch": 16, "block_length": 4, "decode_chunk": 10},
        "correctness": {"adapter": "benchmark.adapters.sdar"},
        "counts": "benchmark.sdar_counts"}


def test_the_expert_layers_time_is_a_layers_three_calls():
    trace = {"op_kinds": {"%grouped_matmul": {"total_s": 0.9, "count": 3000},
                          "%fusion": {"total_s": 5.0, "count": 10}},
             "busy_s": 2.0}
    us = sdar_readers.per_layer_us({"trace": trace}, "^%grouped_matmul$", 3)
    assert us == pytest.approx(900.0)
    assert sdar_readers.per_layer_us({"trace": {"op_kinds": {}}},
                                     "^%grouped_matmul$", 3) is None
    assert sdar_readers.per_layer_us({}, "^%grouped_matmul$", 3) is None
    ctx = {"values": {"moe_experts_us": us, "moe_experts_touched_share": 0.5},
           "peaks": PEAKS, "config": SDAR}
    share = sdar_readers.roofline_touched(
        ctx, "moe_experts", "moe_experts_us", "moe_experts_touched_share", "us")
    assert 40 < share < 43        # 303 MB of 64 experts at 819 GB/s: 370 us
    # the whole forward, its time in ms; nothing without the measured share
    ctx["values"]["decode_step_ms"] = 12.0
    step = sdar_readers.roofline_touched(
        ctx, "forward_weights", "decode_step_ms", "moe_experts_touched_share")
    assert 50 < step < 60
    del ctx["values"]["moe_experts_touched_share"]
    assert sdar_readers.roofline_touched(
        ctx, "forward_weights", "decode_step_ms",
        "moe_experts_touched_share") is None
    # the llama family's count by shapes finds nothing for this architecture
    assert layer_readers.resolve("roofline")(
        ctx, "decode_step_weights", "decode_step_ms") is None
