"""What the Nemotron-3-Super configuration brings to the yardstick, shown at
no chip cost on ``tiny-nemotron-h-share4-8l``
(``rehearsal/BENCHMARK-nemotron.json``, a rehearsal benchmark file of its
own: no file that was there is edited): every name in its data files
resolves, in the rehearsal's file and in the real one; the judge passes the
tiny stack through ``benchmark/adapters/nemotron_h.py`` and catches its
controls; its cell runs through the harness with the expert counters, the
state gauges and the gauges of what the three stacks were built with on one
line; the configuration file carries the published keys unchanged; the counts
module answers the roles the accepted readers ask."""

import json
import os
import subprocess
import sys

from benchmark import nemotron_h_counts as counts
from benchmark.tests import test_names_resolve as resolve
from benchmark.tests.test_seam import REPO, judge

BENCH = "benchmark/tests/rehearsal/BENCHMARK-nemotron.json"
CELL = "tiny-nemotron.decode-closed"
REAL = "nemotron-3-super-int8"
REAL_CELL = "nemotron-3-super-int8.reason-closed-64"
NEW_METRICS = ("latent_experts_us", "latent_experts_roofline",
               "ssm_latent_moe_step_roofline", "moe_rows_per_touched_expert",
               "moe_layers_share")
#: accepted metrics the cell reads under the names they have: the new cell is
#: appended to their lists, and the counts module answers their roles
APPENDED = ("attn_kernels_time_share", "paged_decode_attention_us",
            "ssm_state_update_us", "ssm_state_update_roofline",
            "ssm_kernel_time_share", "state_rows_in_use_share",
            "moe_kernel_time_share", "moe_experts_touched_share",
            "moe_assignments_local_share", "moe_decode_experts_touched_share",
            "state_share_of_cache_bytes", "kv_layers_share")
#: accepted metrics the cell must NOT be listed for: their files price an
#: expert layer at three kernel calls, or a capacity this share never fits
ABSENT = ("moe_experts_us", "routed_experts_roofline", "moe_compact_share")


def test_the_rehearsal_files_names_resolve():
    bench = json.loads((REPO / BENCH).read_text())
    assert [w["name"] for w in bench["workloads"]] == [CELL]
    resolve.test_configuration_resolves(BENCH, "tiny-nemotron")
    resolve.test_traffic_file_resolves(BENCH, "decode-closed")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL] for name in NEW_METRICS + APPENDED)
    assert not set(ABSENT) & set(listed)
    for name in listed:
        resolve.test_layer_metric_resolves(BENCH, name)


def test_the_real_files_names_resolve_and_only_add():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    # there, not "last": a later PR appends its own entries after these
    (entry,) = [c for c in bench["configs"] if c["name"] == REAL]
    assert entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings", "num_nextn_predict_layers"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL, "reason-closed-64", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    resolve.test_configuration_resolves("BENCHMARK.json", REAL)
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    order = [m["name"] for m in bench["per_layer"]]
    # a reader comes after every metric whose value it is fed
    for fed in ("attn_pages_walked_share", "moe_decode_experts_touched_share",
                "moe_assignments_local_share", "batch_occupancy",
                "decode_step_ms"):
        assert order.index(fed) < order.index("ssm_latent_moe_step_roofline")
    assert order.index("latent_experts_us") \
        < order.index("latent_experts_roofline")
    for name in APPENDED:
        assert REAL_CELL in listed[name] and len(listed[name]) >= 2
    for name in ABSENT:
        assert REAL_CELL not in listed[name]
    for name in NEW_METRICS:
        assert REAL_CELL in listed[name]
        resolve.test_layer_metric_resolves("BENCHMARK.json", name)
    assert [n for n in order if n in NEW_METRICS] == list(NEW_METRICS)
    # the cells that were there keep their lines
    assert [w["name"] for w in bench["workloads"]][:6] == [
        "mistral-7b-int8.decode-closed", "qwen2-7b-int8.decode-closed",
        "falcon-h1-34b-int8.decode-closed", "sdar-30b-a3b-int8.decode-closed",
        "kimi-k2.5-int8.reason-closed-64",
        "granite-4.0-h-small-int8.reason-closed-64"]


def test_the_configuration_carries_the_published_keys_unchanged():
    """Every key of the catalog's ``config`` is in the file with its value,
    but the five under ``reduced``; no width, the router's count of experts a
    token nor its scale differs; the deployment is stated."""
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    assert sorted(conf["reduced"]) == sorted([
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings", "num_nextn_predict_layers"])
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 4096,
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 128, "mamba_proj_bias": False, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376,
        "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
        "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 22,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
        "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 5, "sliding_window": None,
        "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True}
    for key, value in published.items():
        assert conf[key] == value, key
    pattern = conf["hybrid_override_pattern"]
    assert len(pattern) == 88 and (pattern.count("M"), pattern.count("E"),
                                   pattern.count("*")) == (40, 40, 8)
    assert [i for i, k in enumerate(pattern) if k == "*"] == [
        7, 16, 25, 36, 47, 58, 69, 78]
    assert (conf["num_hidden_layers"], conf["n_routed_experts"],
            conf["vocab_size"], conf["max_position_embeddings"],
            conf["num_nextn_predict_layers"]) == (22, 128, 32768, 4096, 0)
    assert pattern[:22] == "MEMEMEM*EMEMEMEM*EMEME"
    depth = conf["correctness"]["depth"]
    assert depth == 8 and pattern[:depth] == "MEMEMEM*"   # an attention layer
    deployment = conf["deployment"]
    assert (deployment["chips"], deployment["pipeline_stages"],
            deployment["chips_sharing_a_layer"]) == (16, 4, 4)
    serving = conf["serving"]
    assert (serving["experts_routed"], serving["expert_offset"],
            serving["vocab_published"], serving["layers_published"]) == (
                512, 0, 131072, 88)
    assert (serving["max_batch"], serving["max_seq_len"], serving["page"]
            ) == (64, 4096, 64)
    assert serving["pool_pages"] == 64 * (4096 // 64) * 5 // 4
    # the program's preset is the file's numbers
    from cyberfabric_core_tpu.models import get_config

    cfg = get_config(serving["model_config"])
    assert (cfg.hidden_size, cfg.moe_latent_size, cfg.intermediate_size,
            cfg.shared_intermediate_size, cfg.ssm_heads, cfg.ssm_head_dim,
            cfg.ssm_state, cfg.ssm_groups, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.num_experts, cfg.experts_per_token,
            cfg.routed_scaling_factor, cfg.experts_held, cfg.vocab_held) == (
                4096, 1024, 2688, 5376, 128, 64, 128, 8, 32, 2, 128, 512, 22,
                5.0, 128, 32768)


def test_the_counts_answer_the_roles_at_this_models_sizes():
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    serving = conf["serving"]
    state = counts.ssm_state_update(conf, serving)
    assert state["bytes"] > 2 * 4 * 64 * 128 * 64 * 128        # 0.54 GB
    assert counts.cache_bytes_per_token(conf) == 2 * 1024
    assert counts.state_bytes_per_row(conf) == 4 * (128 * 64 * 128 + 3 * 10240)
    # nothing from shapes alone where a counter has to say it
    assert counts.latent_experts(conf, serving) is None
    assert counts.ssm_latent_moe_step(conf, serving) is None
    assert not hasattr(counts, "routed_experts")
    measured = {**serving, "experts_touched_share": 0.9,
                "assignments_local_share": 0.25,
                "attn_pages_walked_share": 0.4, "rows_running_share": 100.0}
    layer = counts.latent_experts(conf, measured)
    one = 2 * 1024 * 2688
    assert layer["bytes"] == 0.9 * 128 * (one + 4.0 * (2688 + 1024))
    assert layer["flops"] == 2.0 * one * 0.25 * 64 * 22
    step = counts.ssm_latent_moe_step(conf, measured)
    # state is 5.5 GB of the step, the held experts touched 6.3: ~14 GB
    assert 13e9 < step["bytes"] < 16e9
    assert step["bytes"] > 10 * layer["bytes"]


def test_the_stack_passes_and_its_controls_are_caught():
    """Depth 8 of the tiny stack (``MEME*EME``) through the judge: chunks,
    the row resumed from a snapshot and aliased pages, the rider, the idle
    row, decode steps through pages and slab; the reference computes with the
    experts the program chose, over the share it is given."""
    rc, result = judge("tiny-nemotron", "--control")
    assert rc == 0 and result["ok"]
    assert result["adapter"] == "benchmark.adapters.nemotron_h"
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
    three stacks were built with (1 pool layer and 4 expert layers of 8);
    without a device in the trace the kernels' metrics are left out."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--benchmark-file", BENCH,
         "--workload", CELL, "--seed", str(2**31 + 41), "--seconds", "5",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    result = json.loads(last[len("REHEARSAL "):])
    assert result["correct"] and result["failed"] == 0
    assert "correctness: adapter benchmark.adapters.nemotron_h" in proc.stdout
    metrics = result["metrics"]
    # 4 of 16 experts held: about a quarter of the assignments
    assert 0.1 < metrics["moe_assignments_local_share"]["value"] < 0.45
    assert 0 < metrics["moe_decode_experts_touched_share"]["value"] <= 1
    assert 0 < metrics["moe_experts_touched_share"]["value"] <= 1
    assert 1.0 <= metrics["moe_rows_per_touched_expert"]["value"] <= 3 * 4
    assert 0 < metrics["state_rows_in_use_share"]["value"] <= 100
    assert metrics["kv_layers_share"]["value"] == 12.5
    assert metrics["moe_layers_share"]["value"] == 50.0
    # f32 state of 3 layers beside bf16 pages of 1
    assert 60 < metrics["state_share_of_cache_bytes"]["value"] < 90
    assert not {"ssm_state_update_us", "ssm_state_update_roofline",
                "latent_experts_us", "latent_experts_roofline",
                "ssm_latent_moe_step_roofline", "paged_decode_attention_us",
                "moe_experts_us", "routed_experts_roofline",
                "moe_compact_share"} & set(metrics)
