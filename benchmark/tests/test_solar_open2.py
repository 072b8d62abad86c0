"""What the Solar-Open2 configuration brings to the yardstick, shown at no
chip cost on ``tiny-solar-open2-share4-4l``
(``rehearsal/BENCHMARK-solar.json``, a rehearsal benchmark file of its own: no
file that was there is edited): every name in its data files resolves, in the
rehearsal's file and in the real one; the judge passes the tiny stack through
``benchmark/adapters/solar_open2.py`` and catches its controls; its cell runs
through the harness with the expert counters, the state gauges and the gauges
of what the caches were built with on one line; the configuration file
carries the catalog's keys unchanged; the counts module answers the roles the
readers ask."""

import json
import os
import subprocess
import sys

from benchmark import solar_open2_counts as counts
from benchmark.tests import test_names_resolve as resolve
from benchmark.tests.test_seam import REPO, judge

BENCH = "benchmark/tests/rehearsal/BENCHMARK-solar.json"
CELL = "tiny-solar.decode-closed"
REAL = "solar-open2-int8"
REAL_CELL = "solar-open2-int8.reason-closed-64"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "max_position_embeddings"]
NEW_METRICS = ("kda_state_update_us", "kda_state_update_roofline",
               "kda_kernel_time_share", "kda_moe_step_roofline",
               "kda_layers_share")
#: accepted metrics the cell reads under the names they have: the new cell is
#: appended to their lists, and the counts module answers their roles
APPENDED = ("attn_kernels_time_share", "paged_decode_attention_us",
            "state_rows_in_use_share", "state_share_of_cache_bytes",
            "kv_layers_share", "moe_experts_us", "routed_experts_roofline",
            "moe_kernel_time_share", "moe_experts_touched_share",
            "moe_assignments_local_share", "moe_decode_experts_touched_share",
            "moe_compact_share", "moe_item_rows_per_touched_expert")
#: accepted metrics the cell must NOT be listed for: they read the Mamba-2
#: kernel, which this model does not run
ABSENT = ("ssm_state_update_us", "ssm_state_update_roofline",
          "ssm_kernel_time_share")


def test_the_rehearsal_files_names_resolve():
    bench = json.loads((REPO / BENCH).read_text())
    assert [w["name"] for w in bench["workloads"]] == [CELL]
    resolve.test_configuration_resolves(BENCH, "tiny-solar")
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
    assert entry["reduced"] == REDUCED
    assert entry["source"] == ("https://huggingface.co/upstage/"
                               "Solar-Open2-250B/blob/main/config.json")
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
        assert order.index(fed) < order.index("kda_moe_step_roofline")
    assert order.index("kda_state_update_us") \
        < order.index("kda_state_update_roofline")
    for name in APPENDED:
        assert REAL_CELL in listed[name] and len(listed[name]) >= 2
    for name in ABSENT:
        assert REAL_CELL not in listed[name]
    for name in NEW_METRICS:
        assert listed[name] == [REAL_CELL] or REAL_CELL in listed[name]
        resolve.test_layer_metric_resolves("BENCHMARK.json", name)
    assert [n for n in order if n in NEW_METRICS] == list(NEW_METRICS)
    # the cells that were there keep their lines, in their order
    assert [w["name"] for w in bench["workloads"]][:7] == [
        "mistral-7b-int8.decode-closed", "qwen2-7b-int8.decode-closed",
        "falcon-h1-34b-int8.decode-closed", "sdar-30b-a3b-int8.decode-closed",
        "kimi-k2.5-int8.reason-closed-64",
        "granite-4.0-h-small-int8.reason-closed-64",
        "nemotron-3-super-int8.reason-closed-64"]
    # one four-byte-free rule: the traffic file is the one that was there
    traffic = json.loads(
        (REPO / "benchmark/traffic/reason-closed-64.json").read_text())
    assert (traffic["clients"], traffic["prompt_tokens"]["max"],
            traffic["output_tokens"]["max"]) == (64, 1500, 1536)


def test_the_configuration_carries_the_published_keys_unchanged():
    """Every key of the catalog's ``config`` is in the file with its value,
    but the four under ``reduced``; no width, the router's count of experts a
    token nor its scale differs; the deployment is stated."""
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    assert sorted(conf["reduced"]) == sorted(REDUCED)
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
        "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    for key, value in published.items():
        assert conf[key] == value, key
    assert (conf["num_hidden_layers"], conf["n_routed_experts"],
            conf["vocab_size"], conf["max_position_embeddings"]) == (
                12, 40, 24576, 3072)
    # the floors: three whole periods, 40 >= 8 experts, an eighth of the rows
    assert conf["vocab_size"] * 8 == conf["serving"]["vocab_published"]
    from benchmark.solar_open2_reference import layer_kinds

    assert layer_kinds(conf, 12) == "AKKK" * 3
    depth = conf["correctness"]["depth"]
    assert depth == 4 and layer_kinds(conf, depth) == "AKKK"   # every kind
    deployment = conf["deployment"]
    assert (deployment["chips"], deployment["pipeline_stages"],
            deployment["chips_sharing_a_layer"]) == (32, 4, 8)
    serving = conf["serving"]
    assert (serving["experts_routed"], serving["expert_offset"],
            serving["vocab_published"], serving["layers_published"]) == (
                320, 0, 196608, 48)
    assert (serving["max_batch"], serving["max_seq_len"], serving["page"],
            serving["state_snapshots"], serving["decode_chunk"],
            serving["prefill_budget_tokens"]) == (64, 3072, 64, 16, 8, 512)
    assert serving["pool_pages"] == 64 * (3072 // 64)
    yaml = (REPO / serving["yaml"]).read_text()
    assert f"prefix_cache_pages: {serving['pool_pages'] + 1}" in yaml
    assert "state_snapshots: 16" in yaml and "solar_open2" in yaml
    assert len(conf["assumed"]) >= 10
    # the program's preset is the file's numbers
    from cyberfabric_core_tpu.models import get_config

    cfg = get_config(serving["model_config"])
    assert (cfg.hidden_size, cfg.moe_intermediate_size, cfg.shared_width,
            cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv,
            cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.num_experts,
            cfg.experts_per_token, cfg.routed_scaling_factor,
            cfg.experts_held, cfg.vocab_held, cfg.rotary, cfg.use_gqa_gate,
            cfg.kda_allow_neg_eigval) == (
                4096, 1280, 1280, 64, 128, 128, 4, 64, 8, 128, 320, 8, 1.0,
                40, 24576, False, True, True)
    assert [i for i, k in enumerate(cfg.layer_types) if k == "attention"] \
        == [l for l in conf["gqa_layers"] if l < 12]


def test_the_counts_answer_the_roles_at_this_models_sizes():
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    serving = conf["serving"]
    state = counts.kda_state_update(conf, serving)
    assert state["bytes"] == 2 * 4 * 64 * 64 * 128 * 128 \
        + 64 * 4 * 6 * 64 * 128                          # 537 MB + 12.6 MB
    assert state["flops"] == 7.0 * 64 * 64 * 128 * 128
    assert counts.cache_bytes_per_token(conf) == 3 * 4096
    assert counts.state_bytes_per_row(conf) == 4 * (64 * 128 * 128
                                                    + 3 * 24576)
    assert counts.kda_params(conf)[0] == 137_625_600         # with beta
    assert counts.attention_params(conf)[0] == 109_051_904
    assert counts.expert_params(conf)[0] == 15_728_640
    # nothing from shapes alone where a counter has to say it
    assert counts.routed_experts(conf, serving) is None
    assert counts.kda_moe_step(conf, serving) is None
    assert not hasattr(counts, "ssm_state_update")
    measured = {**serving, "experts_touched_share": 0.75,
                "assignments_local_share": 0.125,
                "attn_pages_walked_share": 0.7, "rows_running_share": 100.0}
    layer = counts.routed_experts(conf, measured)
    one = 3 * 4096 * 1280
    assert layer["bytes"] == 0.75 * 40 * (one + 4.0 * (2 * 1280 + 4096))
    assert layer["flops"] == 2.0 * one * 0.125 * 64 * 8
    step = counts.kda_moe_step(conf, measured)
    # state 4.65 GB, held experts touched 5.7 GB, pages 1.6 GB: ~14 GB
    assert 12e9 < step["bytes"] < 16e9
    assert step["bytes"] > 10 * layer["bytes"]
    # the counts module's shapes are the program's
    from cyberfabric_core_tpu.models import get_config

    cfg = get_config(serving["model_config"])
    assert counts.state_bytes_per_row(conf) * 9 == cfg.state_bytes_per_row()
    assert counts.cache_bytes_per_token(conf) == cfg.cache_bytes_per_token()


def test_the_stack_passes_and_its_controls_are_caught():
    """Depth 4 of the tiny stack (``a k k k``) through the judge: chunks, the
    row resumed from a snapshot and aliased pages, the rider, the idle row,
    decode steps through pages and slab; the reference computes with the
    experts the program chose, over the share it is given."""
    rc, result = judge("tiny-solar", "--control")
    assert rc == 0 and result["ok"]
    assert result["adapter"] == "benchmark.adapters.solar_open2"
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
    caches were built with (1 pool layer and 3 slab layers of 4); without a
    device in the trace the kernels' metrics are left out."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--benchmark-file", BENCH,
         "--workload", CELL, "--seed", str(2**31 + 45), "--seconds", "5",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    result = json.loads(last[len("REHEARSAL "):])
    assert result["correct"] and result["failed"] == 0
    assert "correctness: adapter benchmark.adapters.solar_open2" in proc.stdout
    metrics = result["metrics"]
    # 4 of 16 experts held: about a quarter of the assignments
    assert 0.1 < metrics["moe_assignments_local_share"]["value"] < 0.45
    assert 0 < metrics["moe_decode_experts_touched_share"]["value"] <= 1
    assert 0 < metrics["moe_experts_touched_share"]["value"] <= 1
    assert 0 <= metrics["moe_compact_share"]["value"] <= 1
    assert 0 < metrics["state_rows_in_use_share"]["value"] <= 100
    assert metrics["kv_layers_share"]["value"] == 25.0
    assert metrics["kda_layers_share"]["value"] == 75.0
    assert 30 < metrics["state_share_of_cache_bytes"]["value"] < 70
    assert not {"kda_state_update_us", "kda_state_update_roofline",
                "kda_kernel_time_share", "kda_moe_step_roofline",
                "paged_decode_attention_us", "moe_experts_us",
                "routed_experts_roofline", "ssm_state_update_us"} & set(metrics)
