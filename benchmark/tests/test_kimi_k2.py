"""What the Kimi-K2 configuration brings to the yardstick, shown at no chip
cost on ``tiny-kimi`` (``rehearsal/BENCHMARK-kimi.json``, a rehearsal
benchmark file of its own: no file that was there is edited): every name in
its data files resolves, in the rehearsal's file and in the real one; the
judge passes the tiny share through ``benchmark/adapters/kimi_k2.py`` and
catches its controls; its cell runs through the harness with the counter
metrics on the line; the traffic file is the one ISSUE 33 names."""

import json
import os
import subprocess
import sys

from benchmark.tests import test_names_resolve as resolve
from benchmark.tests.test_seam import REPO, judge

BENCH = "benchmark/tests/rehearsal/BENCHMARK-kimi.json"
CELL = "tiny-kimi.decode-closed"
REAL_CELL = "kimi-k2.5-int8.reason-closed-64"
NEW_METRICS = ("mla_decode_attention_us", "mla_decode_attention_roofline",
               "mla_kernels_time_share", "moe_assignments_local_share",
               "moe_decode_experts_touched_share", "routed_experts_roofline",
               "latent_moe_step_roofline")
#: the shared expert kernel and the touched counters read under the names
#: they have: the new cell is appended to the accepted metrics' lists
SHARED_METRICS = ("moe_experts_us", "moe_kernel_time_share",
                  "moe_experts_touched_share")


def test_the_rehearsal_files_names_resolve():
    bench = json.loads((REPO / BENCH).read_text())
    assert [w["name"] for w in bench["workloads"]] == [CELL]
    resolve.test_configuration_resolves(BENCH, "tiny-kimi")
    resolve.test_traffic_file_resolves(BENCH, "decode-closed")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL]
               for name in NEW_METRICS + SHARED_METRICS)
    for name in listed:
        resolve.test_layer_metric_resolves(BENCH, name)


def test_the_real_files_names_resolve_and_lose_nothing():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert [c["name"] for c in bench["configs"]][-1] == "kimi-k2.5-int8"
    assert [w["name"] for w in bench["workloads"]][-1] == REAL_CELL
    assert len(bench["workloads"]) == 5 and len(bench["configs"]) == 5
    resolve.test_configuration_resolves("BENCHMARK.json", "kimi-k2.5-int8")
    resolve.test_traffic_file_resolves("BENCHMARK.json", "reason-closed-64")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    accepted_moe = "sdar-30b-a3b-int8.decode-closed"
    # the readers for a metric a measured value feeds come after it
    order = [m["name"] for m in bench["per_layer"]]
    assert order.index("attn_pages_walked_share") < order.index(
        "mla_decode_attention_roofline")
    assert order.index("moe_experts_us") < order.index(
        "moe_assignments_local_share") < order.index(
        "moe_decode_experts_touched_share") < order.index(
        "routed_experts_roofline") < order.index("latent_moe_step_roofline")
    for name in SHARED_METRICS:
        assert listed[name] == [accepted_moe, REAL_CELL]
    for name in NEW_METRICS:
        assert listed[name] == [REAL_CELL]
        resolve.test_layer_metric_resolves("BENCHMARK.json", name)
    accepted = [w["name"] for w in bench["workloads"][:4]]
    for name in ("paged_decode_attention_us", "attn_kernels_time_share"):
        assert listed[name] == accepted       # their patterns read K/V kernels
    conf = json.loads((REPO / "benchmark/configs/kimi-k2.5-int8.json")
                      .read_text())
    assert sorted(conf["reduced"]) == sorted(bench["configs"][-1]["reduced"]) \
        == ["max_position_embeddings", "n_routed_experts",
            "num_hidden_layers", "vocab_size"]
    assert (conf["hidden_size"], conf["num_attention_heads"]) == (7168, 64)
    assert [conf[k] for k in ("q_lora_rank", "kv_lora_rank",
                              "qk_nope_head_dim", "qk_rope_head_dim",
                              "v_head_dim")] == [1536, 512, 128, 64, 128]
    assert (conf["intermediate_size"], conf["moe_intermediate_size"],
            conf["num_experts_per_tok"], conf["routed_scaling_factor"]) == (
                18432, 2048, 8, 2.827)
    assert conf["serving"]["experts_routed"] == 384
    assert conf["n_routed_experts"] == 12 and conf["vocab_size"] == 20480


def test_the_traffic_is_what_the_issue_names():
    mix = json.loads((REPO / "benchmark/traffic/reason-closed-64.json")
                     .read_text())
    assert (mix["kind"], mix["clients"], mix["temperature"]) == (
        "closed", 64, 0.0)
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 512,
                                    "max": 1500}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 1024,
                                    "max": 1536}
    assert mix["cycle"] == 256


def test_the_share_passes_and_its_controls_are_caught():
    """The tiny share (experts 4-7 of 16, half the vocabulary) through the
    judge: chunks, the resumed row, the rider, the idle row, decode steps
    through the latent cache; the reference computes with the experts the
    program chose."""
    rc, result = judge("tiny-kimi", "--control")
    assert rc == 0 and result["ok"]
    assert result["adapter"] == "benchmark.adapters.kimi_k2"
    limit = result["limit"]
    for r in result["readings"]:
        assert r["program"]["worst_row_rms"] <= limit
        assert r["program"]["rows"] >= 20
        assert r["idle_rows_touched"] == []
        assert r["control_int4"]["worst_row_rms"] > 3 * limit
        assert r["control_fp8"]["worst_row_rms"] > 2 * limit
        assert {"control_router_int8", "control_latent_int8"} <= set(r)


def test_the_cell_runs_through_the_harness():
    """Every request gets its ``max_tokens``; the two counter metrics of a
    chip's share read (assignments on held experts over assignments routed:
    4 of 16 held; held experts touched over held experts offered); without a
    device in the trace the kernels' metrics are left out of the line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--benchmark-file", BENCH,
         "--workload", CELL, "--seed", str(2**31 + 33), "--seconds", "5",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    result = json.loads(last[len("REHEARSAL "):])
    assert result["correct"] and result["failed"] == 0
    assert "correctness: adapter benchmark.adapters.kimi_k2" in proc.stdout
    metrics = result["metrics"]
    assert 0.05 < metrics["moe_assignments_local_share"]["value"] < 0.6
    assert 0 < metrics["moe_decode_experts_touched_share"]["value"] \
        <= metrics["moe_experts_touched_share"]["value"] <= 1
    assert not {"mla_decode_attention_us", "mla_decode_attention_roofline",
                "moe_experts_us", "routed_experts_roofline",
                "latent_moe_step_roofline", "decode_step_roofline",
                "paged_decode_attention_us"} & set(metrics)
