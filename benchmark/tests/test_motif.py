"""What the Motif configuration brings to the yardstick, shown at no chip
cost on ``tiny-motif-share4-4l`` (``rehearsal/BENCHMARK-motif.json``, a
rehearsal benchmark file of its own: no file that was there is edited):
every name in its data files resolves, in the rehearsal's file and in the
real one; the judge passes the tiny stack through
``benchmark/adapters/motif.py`` with window pages freed and reused, and
catches its controls; its cell runs through the harness with the expert
counters and the window group's gauges on one line; the configuration file
carries the catalog's keys unchanged; the counts module answers the roles the
readers ask."""

import json
import os
import subprocess
import sys

from benchmark import motif_counts as counts
from benchmark.tests import test_names_resolve as resolve
from benchmark.tests.test_seam import REPO, judge

BENCH = "benchmark/tests/rehearsal/BENCHMARK-motif.json"
CELL = "tiny-motif.decode-closed"
REAL = "motif-3-beta-int8"
REAL_CELL = "motif-3-beta-int8.longtail-closed-64"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "max_position_embeddings", "num_nextn_predict_layers"]
NEW_METRICS = ("attn_window_pages_walked_share",
               "gdla_full_decode_attention_us",
               "gdla_window_decode_attention_us",
               "gdla_full_decode_attention_roofline",
               "gdla_window_decode_attention_roofline",
               "gdla_kernels_time_share", "window_layers_share",
               "window_pages_per_row", "gdla_moe_step_roofline")
#: accepted metrics the cell reads under the names they have
APPENDED = ("moe_experts_us", "routed_experts_roofline",
            "moe_kernel_time_share", "moe_experts_touched_share",
            "moe_assignments_local_share", "moe_decode_experts_touched_share",
            "moe_compact_share", "moe_item_rows_per_touched_expert")
#: accepted metrics the cell must NOT be listed for: they read kimi's
#: call-site names of the latent kernels, and K/V page kernels
ABSENT = ("mla_decode_attention_us", "mla_decode_attention_roofline",
          "mla_kernels_time_share", "latent_moe_step_roofline",
          "paged_decode_attention_us", "attn_kernels_time_share")
#: the catalog's ``config`` of Motif-3-Beta, as published
PUBLISHED = {
    "attention_cls": "gdla", "diff_v2": True,
    "elementwise_attn_output_gate": True, "experts_top_k": 8,
    "head_dim": 192, "headwise_attn_output_gate": False,
    "hidden_act": "poly_norm", "hidden_size": 4096,
    "interleave_moe_layer_step": 1, "intermediate_size": 12288, "k_ratio": 1,
    "kv_lora_rank": 512, "load_balance_coeff": 0.0001,
    "max_window_layers": 9, "mhc_enabled": True, "mhc_expansion_rate": 4,
    "mhc_identity_init": False, "mhc_sinkhorn_iters": 20,
    "model_type": "Motif", "moe_intermediate_size": 1280, "mscale": 1,
    "n_dense_first_layers": 2, "num_attention_heads": 80,
    "num_key_value_heads": 16, "num_noise_heads": 16,
    "num_shared_experts": 1, "q_lora_rank": 1024, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2, "score_before_experts": False, "score_func": "sigmoid",
    "sliding_window": 128, "sliding_window_pattern": "interleave",
    "sliding_window_period": 4, "swa_rope_theta": 10000,
    "tie_word_embeddings": False, "use_sliding_window": True,
    "v_head_dim": 128, "rope_factor": 64, "original_seq_len": 4096,
    "rope_scaling": {"original_max_position_embeddings": 4096, "factor": 64,
                     "mscale": 1, "rope_type": "yarn", "rope_theta": 10000,
                     "beta_fast": 32, "beta_slow": 1,
                     "apply_yarn_scaling": False},
    "polynorm_output_scale": 0.5, "polynorm_output_scale_per_layer": {},
    "polynorm_bias_clamp": 0.5, "hidden_clamp": 1000000}


def test_the_rehearsal_files_names_resolve():
    bench = json.loads((REPO / BENCH).read_text())
    assert [w["name"] for w in bench["workloads"]] == [CELL]
    resolve.test_configuration_resolves(BENCH, "tiny-motif")
    resolve.test_traffic_file_resolves(BENCH, "decode-closed")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL] for name in NEW_METRICS)
    assert not {"mla_decode_attention_us", "mla_kernels_time_share",
                "latent_moe_step_roofline"} & set(listed)
    for name in listed:
        resolve.test_layer_metric_resolves(BENCH, name)


def test_the_real_files_names_resolve_and_only_add():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [c for c in bench["configs"] if c["name"] == REAL]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == ("https://huggingface.co/Motif-Technologies/"
                               "Motif-3-Beta/blob/main/config.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL, "longtail-closed-64", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    resolve.test_configuration_resolves("BENCHMARK.json", REAL)
    resolve.test_traffic_file_resolves("BENCHMARK.json", "longtail-closed-64")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    order = [m["name"] for m in bench["per_layer"]]
    # a reader comes after every metric whose value it is fed
    for fed in ("attn_pages_walked_share", "attn_window_pages_walked_share",
                "moe_decode_experts_touched_share",
                "moe_assignments_local_share", "decode_step_ms"):
        assert order.index(fed) < order.index("gdla_moe_step_roofline")
    for site in ("full", "window"):
        assert order.index(f"gdla_{site}_decode_attention_us") \
            < order.index(f"gdla_{site}_decode_attention_roofline")
        assert order.index("attn_window_pages_walked_share") \
            < order.index(f"gdla_{site}_decode_attention_roofline")
    for name in APPENDED:
        assert REAL_CELL in listed[name] and len(listed[name]) >= 2
    for name in ABSENT:
        assert REAL_CELL not in listed[name]
    for name in NEW_METRICS:
        assert REAL_CELL in listed[name]
        resolve.test_layer_metric_resolves("BENCHMARK.json", name)
    assert [n for n in order if n in NEW_METRICS] == list(NEW_METRICS)
    # the cells that were there keep their lines, in their order
    assert [w["name"] for w in bench["workloads"]][:8] == [
        "mistral-7b-int8.decode-closed", "qwen2-7b-int8.decode-closed",
        "falcon-h1-34b-int8.decode-closed", "sdar-30b-a3b-int8.decode-closed",
        "kimi-k2.5-int8.reason-closed-64",
        "granite-4.0-h-small-int8.reason-closed-64",
        "nemotron-3-super-int8.reason-closed-64",
        "solar-open2-int8.reason-closed-64"]
    # the traffic is the issue's table
    traffic = json.loads(
        (REPO / "benchmark/traffic/longtail-closed-64.json").read_text())
    assert (traffic["kind"], traffic["clients"], traffic["cycle"],
            traffic["temperature"]) == ("closed", 64, 256, 0.0)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.8, "min": 256,
        "max": 5120}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 1536,
                                        "max": 2560}
    from benchmark.loadgen import quantile_sizes

    prompts = quantile_sizes(traffic["prompt_tokens"], 256)
    assert 1300 < sum(prompts) / 256 < 1420            # mean about 1.35 k
    assert 0.07 < sum(p > 3000 for p in prompts) / 256 < 0.11


def test_the_configuration_carries_the_published_keys_unchanged():
    """Every key of the catalog's ``config`` is in the file with its value,
    but the five under ``reduced``; the deployment and every inference are
    stated; the program's preset is the file's numbers."""
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    assert sorted(conf["reduced"]) == sorted(REDUCED)
    for key, value in PUBLISHED.items():
        assert conf[key] == value, key
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"], conf["max_position_embeddings"],
            conf["num_nextn_predict_layers"]) == (27, 12, 27520, 8192, 0)
    # the floors: a whole period and more, 12 >= 8 experts, an eighth of rows
    assert conf["vocab_size"] * 8 == conf["serving"]["vocab_published"]
    deployment = conf["deployment"]
    assert (deployment["chips"], deployment["pipeline_stages"],
            deployment["chips_sharing_a_layer"]) == (64, 2, 32)
    serving = conf["serving"]
    assert (serving["experts_routed"], serving["expert_offset"],
            serving["vocab_published"], serving["layers_published"]) == (
                384, 0, 220160, 53)
    assert (serving["max_batch"], serving["max_seq_len"], serving["page"],
            serving["decode_chunk"], serving["prefill_budget_tokens"],
            serving["max_input_tokens"], serving["max_output_tokens"]) == (
                64, 8192, 64, 8, 512, 5120, 2560)
    assert serving["pool_pages"] == 64 * (8192 // 64)
    assert serving["mixed_widths"] == [16, 32, 64, 128, 256, 512]
    yaml = (REPO / serving["yaml"]).read_text()
    assert f"prefix_cache_pages: {serving['pool_pages'] + 1}" in yaml
    assert "window_cache_pages" not in yaml     # no option: from shapes
    assert "architecture: motif" in yaml
    assumed = " ".join(conf["assumed"])
    for said in ("LAST 16", "4g..4g+3", "(i + 1) % sliding_window_period",
                 "max_window_layers 9", "'modified'", "4 copies",
                 "query's own position", "ONE set of coefficients",
                 "hidden_clamp"):
        assert said in assumed, said
    cc = conf["correctness"]
    # the judged depth: both dense layers, a window expert layer, a whole
    # unit (full, window x 3) and a full layer; row A of the scenario is
    # longer than the window and two chunks
    assert cc["depth"] == 8 and cc["chunk"] == 512
    assert cc["controls"]["caught"] == ["int4", "fp8"]
    from cyberfabric_core_tpu.models import get_config

    cfg = get_config(serving["model_config"])
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.num_heads, cfg.num_noise_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.num_experts, cfg.experts_per_token,
            cfg.routed_scaling_factor, cfg.sliding_window,
            cfg.sliding_window_period, cfg.mhc_expansion_rate,
            cfg.mhc_sinkhorn_iters, cfg.first_k_dense, cfg.experts_held,
            cfg.vocab_held, cfg.num_layers, cfg.polynorm_output_scale,
            cfg.polynorm_bias_clamp, cfg.hidden_clamp) == (
                4096, 12288, 1280, 80, 16, 16, 192, 128, 64, 128, 1024, 512,
                384, 8, 2.0, 128, 4, 4, 20, 2, 12, 27520, 27, 0.5, 0.5, 1e6)
    assert cfg.rope_factor == 1.0          # apply_yarn_scaling false
    # the window group's pages are what the scheduler derives from these
    # shapes (4 + 1 a slot through a ring of 24 tokens, two chunks of 11,
    # scratch), and the file states them
    from types import SimpleNamespace

    from cyberfabric_core_tpu.runtime import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import \
        ContinuousBatchingEngine

    built = ContinuousBatchingEngine._window_pages(SimpleNamespace(
        model_config=cfg, n_slots=serving["max_batch"], config=EngineConfig(
            model=cfg.name, max_batch=serving["max_batch"],
            decode_chunk=serving["decode_chunk"],
            prefix_page_size=serving["page"],
            prefill_budget_tokens=serving["prefill_budget_tokens"])))
    assert built == serving["window_pool_pages"] + 1 == 343


def test_the_counts_answer_the_roles_at_this_models_sizes():
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    serving = conf["serving"]
    assert counts.attention_params(conf)[0] == 91_750_400
    assert counts.expert_params(conf)[0] == 15_728_640
    assert counts.mhc_bytes(conf) == 2 * 4 * (16384 * 24 + 16384 + 24 + 3)
    assert counts.cache_bytes_per_token(conf) == 6 * 576 * 2
    # nothing from shapes alone where a counter has to say it
    assert counts.gdla_decode_attention(conf, serving) is None
    assert counts.routed_experts(conf, serving) is None
    assert counts.gdla_moe_step(conf, serving) is None
    # 64 rows of 2 400 tokens: 38 pages of 128 a row in a full layer, 3 in a
    # window layer
    measured = {**serving, "attn_pages_walked_share": 38 / 128,
                "window_pages_walked_share": 3 / 128,
                "experts_touched_share": 0.32,
                "assignments_local_share": 12 / 384}
    full = counts.gdla_decode_attention(conf, measured)
    tokens = (38 * 64 - 32) * 64
    assert full["bytes"] == tokens * 576 * 2.0
    assert full["flops"] == 80 * tokens * 2.0 * (576 + 512)
    window = counts.gdla_decode_attention(conf, measured,
                                          "window_pages_walked_share")
    assert window["bytes"] == (3 * 64 - 32) * 64 * 576 * 2.0
    layer = counts.routed_experts(conf, measured)
    assert layer["bytes"] == 0.32 * 12 * (15_728_640 + 4.0 * (2560 + 4096))
    step = counts.gdla_moe_step(conf, measured)
    # attention 2.5 GB, touched experts 1.5, the rest 1, latent rows 1.3
    assert 5.5e9 < step["bytes"] < 7.5e9
    assert step["bytes"] > 6 * full["bytes"] + 21 * window["bytes"]
    from cyberfabric_core_tpu.models import get_config

    cfg = get_config(serving["model_config"])
    assert counts.cache_bytes_per_token(conf) * 640 // 576 == \
        cfg.cache_bytes_per_token()
    weights = cfg.num_layers * (counts.attention_params(conf)[0]
                                + counts.mhc_bytes(conf)) \
        + 2 * 3 * 4096 * 12288 + 25 * (13 * 15_728_640 + 4 * 4096 * 384) \
        + 2 * 27520 * 4096
    assert 8.3e9 < weights < 8.4e9        # the file's 8.36 GB


def test_the_stack_passes_and_its_controls_are_caught():
    """Depth 4 of the tiny stack (dense, dense, a window layer, a full one)
    through the judge: chunks, the row resumed from pages another row wrote,
    the rider, the idle row, decode steps through both page groups, window
    pages freed and written again on the way."""
    rc, result = judge("tiny-motif", "--control")
    assert rc == 0 and result["ok"]
    assert result["adapter"] == "benchmark.adapters.motif"
    limit = result["limit"]
    for r in result["readings"]:
        assert r["program"]["worst_row_rms"] <= limit
        assert r["program"]["rows"] >= 20
        assert r["idle_rows_touched"] == []
        assert r["control_int4"]["worst_row_rms"] > 3 * limit
        assert r["control_fp8"]["worst_row_rms"] > 2 * limit


def test_the_judged_freeing_is_the_pools_own():
    """The binding takes every page from the program's ``PrefixKVPool``:
    four rows of three chunks and a few tokens, in a window group kept short,
    give back more pages than the group has (so freed pages were written
    again) and end exactly where they end in a roomy group; with the POOL's
    rule made to free a page too soon, the same calls end elsewhere."""
    import numpy as np

    from benchmark.adapters import motif as adapter

    conf = json.loads(
        (REPO / "benchmark/tests/rehearsal/configs/tiny-motif.json")
        .read_text())
    cc = conf["correctness"]
    chunk, rows = cc["chunk"], 4
    w = adapter.make_weights(conf, 3, cc["depth"])
    rng = np.random.default_rng(3)
    ids = rng.integers(3, conf["vocab_size"], (4, rows, chunk)).astype(
        np.int32)

    def run(window_pages=None, pool_window=None):
        binding = adapter.bind(conf, cc["depth"], rows)
        if window_pages:
            binding.window_pages = window_pages
        state = binding.new_state()
        pool = state["pool"]
        if pool_window:
            pool.window = pool_window
        done = np.zeros(rows, np.int32)
        # three whole chunks, then a few tokens whose windows reach back
        # into the third
        for call, n in enumerate((chunk, chunk, chunk, binding.page // 2)):
            q = np.full(rows, n, np.int32)
            last, state = binding.mixed(w, ids[call], state, done, q)
            done += q
            first = max(int(done[0]) - pool.window + 1, 0) // binding.page
            for wchain in state["wchains"]:
                assert not any(wchain[:first]) and all(wchain[first:])
        return np.asarray(last, np.float32), binding, state

    short, binding, state = run()
    pool = state["pool"]
    assert pool.window_pages == binding.window_pages + 1
    assert pool.window_pages_freed > binding.window_pages
    assert state["reused"] > binding.window_pages // 2
    roomy, _, _ = run(window_pages=10 * binding.window_pages)
    np.testing.assert_array_equal(short, roomy)
    eager, _, _ = run(pool_window=1)    # only the query's own page is kept
    assert np.abs(eager - short).max() > 0.05 * np.abs(short).max()


def test_the_cell_runs_through_the_harness():
    """Every request gets its ``max_tokens``; one line carries the expert
    counters and the gauges of what the two page groups were built with;
    without a device in the trace the kernels' metrics are left out."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--benchmark-file", BENCH,
         "--workload", CELL, "--seed", str(2**31 + 48), "--seconds", "5",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    result = json.loads(last[len("REHEARSAL "):])
    assert result["correct"] and result["failed"] == 0
    assert "correctness: adapter benchmark.adapters.motif" in proc.stdout
    metrics = result["metrics"]
    assert 0.1 < metrics["moe_assignments_local_share"]["value"] < 0.45
    assert 0 < metrics["moe_decode_experts_touched_share"]["value"] <= 1
    assert metrics["window_layers_share"]["value"] == 75.0
    # a window of 24 in pages of 16: 2-3 pages of a table of 16 a row
    assert 0.05 < metrics["attn_window_pages_walked_share"]["value"] < 0.2
    assert not {"gdla_full_decode_attention_us",
                "gdla_window_decode_attention_roofline",
                "gdla_kernels_time_share", "gdla_moe_step_roofline",
                "mla_kernels_time_share"} & set(metrics)
