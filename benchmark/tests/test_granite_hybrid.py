"""What the Granite-4.0-H configuration brings to the yardstick, shown at no
chip cost on ``tiny-granite-hybrid`` (``rehearsal/BENCHMARK-granite.json``, a
rehearsal benchmark file of its own: no file that was there is edited): every
name in its data files resolves, in the rehearsal's file and in the real one;
the judge passes the tiny stack through ``benchmark/adapters/granite_hybrid.py``
and catches its controls; its cell runs through the harness with the counter
and gauge metrics of BOTH mechanisms on one line; the configuration file
carries the published keys unchanged."""

import json
import os
import subprocess
import sys

from benchmark.tests import test_names_resolve as resolve
from benchmark.tests.test_seam import REPO, judge

BENCH = "benchmark/tests/rehearsal/BENCHMARK-granite.json"
CELL = "tiny-granite.decode-closed"
REAL_CELL = "granite-4.0-h-small-int8.reason-closed-64"
NEW_METRICS = ("hybrid_moe_step_roofline", "state_share_of_cache_bytes",
               "kv_layers_share")
#: accepted metrics the cell reads under the names they have: the new cell is
#: appended to their lists, and the counts module answers their roles
APPENDED = ("attn_kernels_time_share", "paged_decode_attention_us",
            "ssm_state_update_us", "ssm_state_update_roofline",
            "ssm_kernel_time_share", "state_rows_in_use_share",
            "moe_experts_us", "moe_kernel_time_share",
            "moe_experts_touched_share", "moe_decode_experts_touched_share",
            "moe_assignments_local_share", "routed_experts_roofline")


def test_the_rehearsal_files_names_resolve():
    bench = json.loads((REPO / BENCH).read_text())
    assert [w["name"] for w in bench["workloads"]] == [CELL]
    resolve.test_configuration_resolves(BENCH, "tiny-granite")
    resolve.test_traffic_file_resolves(BENCH, "decode-closed")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL] for name in NEW_METRICS + APPENDED)
    for name in listed:
        resolve.test_layer_metric_resolves(BENCH, name)


def test_the_real_files_names_resolve_and_only_add():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    # there, not "last": a later PR appends its own entries after these
    assert "granite-4.0-h-small-int8" in [c["name"] for c in bench["configs"]]
    (cell,) = [w for w in bench["workloads"] if w["name"] == REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-small-int8", "reason-closed-64", 1)
    resolve.test_configuration_resolves("BENCHMARK.json",
                                        "granite-4.0-h-small-int8")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    order = [m["name"] for m in bench["per_layer"]]
    # a reader comes after every metric whose value it is fed
    for fed in ("attn_pages_walked_share", "moe_decode_experts_touched_share",
                "batch_occupancy", "decode_step_ms"):
        assert order.index(fed) < order.index("hybrid_moe_step_roofline")
    for name in APPENDED:
        assert REAL_CELL in listed[name] and len(listed[name]) >= 2
    for name in NEW_METRICS:
        assert REAL_CELL in listed[name]
        resolve.test_layer_metric_resolves("BENCHMARK.json", name)
    assert [n for n in order if n in NEW_METRICS] == list(NEW_METRICS)


def test_the_configuration_carries_the_published_keys_unchanged():
    """Every key of the catalog's ``config`` is in the file with its value,
    but the two under ``reduced``; no width, expert count, experts a token,
    head count or vocabulary row differs."""
    conf = json.loads((REPO / "benchmark/configs/"
                       "granite-4.0-h-small-int8.json").read_text())
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert sorted(conf["reduced"]) == sorted(bench["configs"][-1]["reduced"]) \
        == ["max_position_embeddings", "num_hidden_layers"]
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 768, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
        "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
        "num_attention_heads": 32, "num_experts_per_tok": 10,
        "num_key_value_heads": 8, "num_local_experts": 72,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True,
        "vocab_size": 100352}
    for key, value in published.items():
        assert conf[key] == value, key
    assert len(conf["layer_types"]) == 40
    assert [i for i, k in enumerate(conf["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert (conf["num_hidden_layers"], conf["max_position_embeddings"]) == (
        10, 4096)
    assert conf["correctness"]["depth"] == 6          # an attention layer in
    assert conf["layer_types"][:6].count("attention") == 1
    assert conf["deployment"]["pipeline_stages"] == 4
    serving = conf["serving"]
    assert (serving["max_batch"], serving["max_seq_len"], serving["page"],
            serving["state_snapshots"]) == (64, 4096, 64, 32)
    # the pool the worker builds when no option sizes it: a quarter more
    # than the slots' own pages (prefix retention), as falcon-h1's 640
    assert serving["pool_pages"] == 64 * (4096 // 64) * 5 // 4


def test_the_stack_passes_and_its_controls_are_caught():
    """Depth 4 of the tiny stack (``m m a m``) through the judge: chunks, the
    row resumed from a snapshot and aliased pages, the rider, the idle row,
    decode steps through pages and slab; the reference computes with the
    experts the program chose."""
    rc, result = judge("tiny-granite", "--control")
    assert rc == 0 and result["ok"]
    assert result["adapter"] == "benchmark.adapters.granite_hybrid"
    limit = result["limit"]
    for r in result["readings"]:
        assert r["program"]["worst_row_rms"] <= limit
        assert r["program"]["rows"] >= 20
        assert r["idle_rows_touched"] == []
        assert r["control_int4"]["worst_row_rms"] > 3 * limit
        assert r["control_fp8"]["worst_row_rms"] > 2 * limit
        assert {"control_kv_int8", "control_state_bf16"} <= set(r)


def test_the_cell_runs_through_the_harness():
    """Every request gets its ``max_tokens``; one line carries the expert
    counters AND the state gauges of one model, and the gauges of what the
    caches were built with (2 pool layers of 8: 25%); without a device in the
    trace the kernels' metrics are left out of the line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--benchmark-file", BENCH,
         "--workload", CELL, "--seed", str(2**31 + 39), "--seconds", "5",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    result = json.loads(last[len("REHEARSAL "):])
    assert result["correct"] and result["failed"] == 0
    assert "correctness: adapter benchmark.adapters.granite_hybrid" \
        in proc.stdout
    metrics = result["metrics"]
    assert metrics["moe_assignments_local_share"]["value"] == 1.0
    assert 0 < metrics["moe_decode_experts_touched_share"]["value"] <= 1
    assert 0 < metrics["moe_experts_touched_share"]["value"] <= 1
    assert 0 < metrics["state_rows_in_use_share"]["value"] <= 100
    assert metrics["kv_layers_share"]["value"] == 25.0
    # f32 state of 6 layers beside bf16 pages of 2
    assert 50 < metrics["state_share_of_cache_bytes"]["value"] < 70
    assert not {"ssm_state_update_us", "ssm_state_update_roofline",
                "moe_experts_us", "routed_experts_roofline",
                "hybrid_moe_step_roofline", "paged_decode_attention_us",
                "decode_step_roofline"} & set(metrics)
