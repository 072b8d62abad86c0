"""What the GLM-5 configuration brings to the yardstick, shown at no chip cost
on ``tiny-glm-dsa-share4`` (``rehearsal/BENCHMARK-glm-dsa.json``, a rehearsal
benchmark file of its own: no file that was there is edited): every name in
its data files resolves, in the rehearsal's file and in the real one; the
judge passes the tiny share through ``benchmark/adapters/glm_dsa.py`` (the
reference attends over the keys the program chose, each held to
``selection_epsilon`` and the count exactly) and catches its five controls;
its cell runs through the harness with the selection's counters on the line;
the configuration file carries the catalog's keys unchanged; the counts
module answers the roles the readers ask and agrees with a count by hand;
every new metric file reads what a trace or a scrape holds.

**The entries are pinned by name and by what stands BEFORE them, never as
the last of a list**: a later PR appends behind them and this file holds."""

import json
import os
import subprocess
import sys

from benchmark import glm_dsa_counts as counts
from benchmark import layer_readers, reduce_trace
from benchmark.tests import test_names_resolve as resolve
from benchmark.tests.test_seam import REPO, judge

BENCH = "benchmark/tests/rehearsal/BENCHMARK-glm-dsa.json"
CELL = "tiny-glm-dsa.decode-closed"
REAL = "glm-5-int8"
REAL_CELL = "glm-5-int8.longctx-closed-32"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "max_position_embeddings",
           "num_nextn_predict_layers"]
NEW_METRICS = ("dsa_index_scores_us", "dsa_topk_us",
               "dsa_sparse_decode_attention_us", "dsa_ragged_attention_us",
               "dsa_kernels_time_share", "dsa_selected_share",
               "dsa_binding_share", "dsa_decode_keys_scored_per_call",
               "dsa_decode_keys_selected_per_call",
               "dsa_index_scores_roofline",
               "dsa_sparse_decode_attention_roofline",
               "dsa_moe_step_roofline")
COUNTER_METRICS = NEW_METRICS[5:9]
#: accepted metrics the cell reads under the names they have
APPENDED = ("moe_experts_us", "moe_kernel_time_share",
            "moe_experts_touched_share", "moe_assignments_local_share",
            "moe_decode_experts_touched_share", "routed_experts_roofline",
            "moe_compact_share", "moe_item_rows_per_touched_expert",
            "moe_layers_share")
#: accepted metrics the cell must NOT be listed for: its call sites carry
#: other names, and kimi's count walks every page
ABSENT = ("mla_decode_attention_us", "mla_decode_attention_roofline",
          "mla_kernels_time_share", "mla_ragged_attention_us",
          "latent_moe_step_roofline", "kv_layers_share")
LAGUNA_CELL = "laguna-s-2.1-int8.longtail-closed-64"


def _published() -> dict:
    """The catalog's ``config`` of GLM-5, as published."""
    return {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
        "hidden_act": "silu", "head_dim": 64, "hidden_size": 6144,
        "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
        "indexer_rope_interleave": True, "intermediate_size": 12288,
        "kv_lora_rank": 512, "max_position_embeddings": 202752,
        "moe_intermediate_size": 2048, "moe_layer_freq": 1,
        "model_type": "glm_moe_dsa", "n_group": 1, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 78, "num_key_value_heads": 64,
        "num_nextn_predict_layers": 1, "q_lora_rank": 2048,
        "qk_head_dim": 256, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_interleave": True,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 256, "vocab_size": 154880}


def test_the_rehearsal_files_names_resolve():
    bench = json.loads((REPO / BENCH).read_text())
    assert [w["name"] for w in bench["workloads"]] == [CELL]
    resolve.test_configuration_resolves(BENCH, "tiny-glm-dsa")
    resolve.test_traffic_file_resolves(BENCH, "decode-closed")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL] for name in NEW_METRICS + APPENDED)
    assert not set(ABSENT) & set(listed)
    for name in listed:
        resolve.test_layer_metric_resolves(BENCH, name)


def test_the_real_files_names_resolve_and_only_add():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    entry = bench["configs"][configs.index(REAL)]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == ("https://huggingface.co/zai-org/GLM-5/blob/"
                               "main/config.json")
    assert entry["file"] == f"benchmark/configs/{REAL}.json"
    cell = bench["workloads"][cells.index(REAL_CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL, "longctx-closed-32", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # what stands before them: the eleven configurations and cells PR 54
    # left, in their order (whatever a later PR appends stands behind)
    assert configs[: configs.index(REAL)] == [
        "mistral-7b-int8", "qwen2-7b-int8", "falcon-h1-34b-int8",
        "sdar-30b-a3b-int8", "kimi-k2.5-int8", "granite-4.0-h-small-int8",
        "nemotron-3-super-int8", "solar-open2-int8", "motif-3-beta-int8",
        "ouro-2.6b-int8", "laguna-s-2.1-int8"]
    assert cells[: cells.index(REAL_CELL)] == [
        "mistral-7b-int8.decode-closed", "qwen2-7b-int8.decode-closed",
        "falcon-h1-34b-int8.decode-closed", "sdar-30b-a3b-int8.decode-closed",
        "kimi-k2.5-int8.reason-closed-64",
        "granite-4.0-h-small-int8.reason-closed-64",
        "nemotron-3-super-int8.reason-closed-64",
        "solar-open2-int8.reason-closed-64",
        "motif-3-beta-int8.longtail-closed-64",
        "ouro-2.6b-int8.decode-closed-8", LAGUNA_CELL]
    resolve.test_configuration_resolves("BENCHMARK.json", REAL)
    resolve.test_traffic_file_resolves("BENCHMARK.json", "longctx-closed-32")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    order = [m["name"] for m in bench["per_layer"]]
    # the twelve new metrics in one piece, in this order, behind PR 55's
    at = order.index(NEW_METRICS[0])
    assert order[at: at + len(NEW_METRICS)] == list(NEW_METRICS)
    assert order[at - 1] == "ragged_paged_attention_us"
    # a reader comes after every metric whose value it is fed
    for fed in ("moe_decode_experts_touched_share",
                "moe_assignments_local_share", "decode_step_ms",
                "dsa_decode_keys_scored_per_call",
                "dsa_decode_keys_selected_per_call"):
        assert order.index(fed) < order.index("dsa_moe_step_roofline")
    assert order.index("dsa_index_scores_us") < order.index(
        "dsa_decode_keys_scored_per_call") < order.index(
        "dsa_index_scores_roofline")
    assert order.index("dsa_sparse_decode_attention_us") < order.index(
        "dsa_decode_keys_selected_per_call") < order.index(
        "dsa_sparse_decode_attention_roofline")
    for name in APPENDED:
        behind = listed[name][: listed[name].index(REAL_CELL)]
        assert LAGUNA_CELL in behind, name
    for name in ABSENT:
        assert REAL_CELL not in listed[name]
    for name in NEW_METRICS:
        assert listed[name][0] == REAL_CELL
        entry = bench["per_layer"][order.index(name)]
        assert entry["moves"] == "out_tokens_per_s"
        assert entry["source"] == ("program_counter"
                                   if name in COUNTER_METRICS
                                   else "device_trace")
        assert entry["unit"] == (
            "us" if name.endswith("_us") else "keys" if "per_call" in name
            else "ratio" if name in COUNTER_METRICS else "%")
        assert entry["layer"] == ("model step" if name in (
            "dsa_binding_share", "dsa_moe_step_roofline") else "kernels")
        resolve.test_layer_metric_resolves("BENCHMARK.json", name)
    # the traffic file holds exactly the issue's parameters
    traffic = json.loads(
        (REPO / "benchmark/traffic/longctx-closed-32.json").read_text())
    assert (traffic["kind"], traffic["clients"], traffic["cycle"],
            traffic["temperature"]) == ("closed", 32, 256, 0.0)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.6, "min": 2048,
        "max": 12288}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 1536,
                                        "max": 2560}
    assert 30 <= traffic["lead_in_s"] <= 90
    assert traffic["clients_why"] and traffic["lead_in_why"]
    assert set(traffic) == {"kind", "clients", "clients_why", "lead_in_s",
                            "lead_in_why", "cycle", "prompt_tokens",
                            "output_tokens", "temperature"}


def test_the_configuration_carries_the_published_keys_unchanged():
    """Every key of the catalog's ``config`` is in the file with its value,
    but the six under ``reduced``; the deployment, both departures and every
    inference are stated; the program's preset is the file's numbers."""
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    assert sorted(conf["reduced"]) == sorted(REDUCED)
    for key, value in _published().items():
        if key not in REDUCED:
            assert conf[key] == value, key
    assert [conf[k] for k in REDUCED] == [7, 1, 16, 19360, 16384, 0]
    # the floors: a leading dense layer and six expert layers, 16 >= 8
    # experts, an eighth of the vocabulary's rows
    assert conf["vocab_size"] * 8 == conf["serving"]["vocab_published"]
    assert conf["n_routed_experts"] * 16 == \
        conf["serving"]["experts_routed"] == 256
    deployment = conf["deployment"]
    assert deployment["chips_sharing_a_layer"] == 16
    assert "1 row a step" in deployment["expert_load"]
    assert "WHOLE indexer" in deployment["attention"]
    serving = conf["serving"]
    assert (serving["expert_offset"], serving["vocab_published"],
            serving["layers_published"]) == (0, 154880, 78)
    assert (serving["max_batch"], serving["max_seq_len"], serving["page"],
            serving["decode_chunk"], serving["prefill_budget_tokens"],
            serving["max_input_tokens"], serving["max_output_tokens"]) == (
                32, 16384, 64, 8, 512, 12288, 2560)
    assert serving["pool_pages"] == 32 * (16384 // 64)
    assert serving["mixed_widths"] == [16, 32, 64, 128, 256, 512]
    assert serving["programs"] == ["mixed_step", "paged_decode_chunk"]
    yaml = (REPO / serving["yaml"]).read_text()
    assert "architecture: glm_moe_dsa" in yaml
    assert "model_config: glm-5-share16-7l" in yaml
    assert "max_seq_len: 16384, max_batch: 32" in yaml
    assert "eos_token_ids: [19360]" in yaml
    assumed = " ".join(conf["assumed"])
    for said in ("rotate-half", "LayerNorm(x W_kI) with a weight AND a bias",
                 "FIRST 64", "DEPARTURE: the published inference code "
                 "rotates", "DEPARTURE: the published inference code keeps "
                 "kI in float8", "selection bias is drawn at 0.1",
                 "byte fallback", "eos_token_ids", "every page",
                 "selection_epsilon", "head_dim 64 is the published value",
                 "runtime/weights.py refuses"):
        assert said in assumed, said
    cc = conf["correctness"]
    # the judged depth: the dense layer and three expert layers; chunks of
    # 2048 so that rows A, B and C pass index_topk
    assert cc["depth"] == 4 and cc["chunk"] == 2048
    assert cc["decode_steps"] == 6
    assert sorted(cc["controls"]["caught"] + cc["controls"]["read_only"]) \
        == sorted(["int4", "fp8", "no_select", "no_relu",
                   "index_unweighted", "latent_int8"])
    assert {"int4", "no_select", "no_relu", "index_unweighted"} <= set(
        cc["controls"]["caught"])
    assert 0.005 < cc["limit"] < 0.05 and "1." in cc["limit_why"]
    assert 0 < cc["selection_epsilon"] < 0.5
    assert "four times the served" in cc["limit_why"]
    from benchmark.correctness import scenario

    rows = scenario(cc["chunk"], serving["page"], serving["page"])
    assert (rows["A"], rows["B"], rows["D"]) == (4388, 2816, 40)
    assert min(rows["A"], rows["B"], rows["C"]) > conf["index_topk"]
    from cyberfabric_core_tpu.models import get_config

    cfg = get_config(serving["model_config"])
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.num_heads, cfg.head_dim,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.q_lora_rank, cfg.kv_lora_rank, cfg.index_heads,
            cfg.index_head_dim, cfg.index_topk, cfg.num_experts,
            cfg.experts_per_token, cfg.routed_scaling_factor,
            cfg.shared_experts, cfg.first_k_dense, cfg.experts_held,
            cfg.vocab_held, cfg.num_layers, cfg.rope_theta, cfg.rope_factor,
            cfg.rms_norm_eps, cfg.max_position) == (
                6144, 12288, 2048, 64, 256, 192, 64, 256, 2048, 512, 32, 128,
                2048, 256, 8, 2.5, 1, 1, 16, 19360, 7, 1e6, 1.0, 1e-5, 16384)
    assert cfg.head_dim == conf["qk_head_dim"]
    # the pool stores a latent row in 640 lanes; the counts count its 576
    assert cfg.cache_bytes_per_token() == 7 * (640 + 128) * 2
    assert counts.cache_bytes_per_token(conf) == 7 * (576 + 128) * 2


def test_the_counts_answer_the_roles_and_agree_with_a_count_by_hand():
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    serving = conf["serving"]
    assert counts.indexer_params(conf) == (
        2048 * 4096 + 6144 * 128, 4096 + 128, 4 * 6144 * 32)
    assert counts.expert_params(conf) == (3 * 6144 * 2048, 2 * 2048 + 6144)
    assert counts.latent_row(conf) == 576
    assert counts.cache_bytes_per_token(conf) == 7 * (576 + 128) * 2
    # nothing from shapes alone where a counter has to say it
    for role in ("dsa_index_scores", "dsa_sparse_decode_attention",
                 "routed_experts", "step_weights", "decode_step"):
        assert getattr(counts, role)(conf, serving) is None, role
    # 32 rows of 6 000 tokens: one call scores 192 000 keys and attends
    # 65 536; 10 of 16 held experts touched
    measured = {**serving, "keys_scored_per_call": 192000.0,
                "keys_selected_per_call": 65536.0,
                "experts_touched_share": 0.625,
                "assignments_local_share": 16 / 256}
    index = counts.dsa_index_scores(conf, measured)
    assert index["bytes"] == 192000 * 128 * 2
    assert index["flops"] == 192000 * 32 * 128 * 2
    attn = counts.dsa_sparse_decode_attention(conf, measured)
    assert attn["bytes"] == 65536 * 576 * 2           # read ONCE, 576 not 640
    assert attn["flops"] == 64 * 65536 * 2 * (576 + 512)
    layer = counts.routed_experts(conf, measured)
    assert layer["bytes"] == 10 * (3 * 6144 * 2048 + 4.0 * (4096 + 6144))
    weights = counts.step_weights(conf, measured)
    # the issue's reckoning: 2.9-4.1 GB of weights a step (4.10 at 10 of 16)
    assert 2.9e9 < weights["bytes"] < 4.2e9
    step = counts.decode_step(conf, measured)
    assert step["bytes"] == weights["bytes"] + 7 * (index["bytes"]
                                                    + attn["bytes"])
    # every held weight, int8 + f32 scales, against the program's own count
    from cyberfabric_core_tpu.models import get_config

    cfg = get_config(serving["model_config"])
    every = {**measured, "experts_touched_share": 1.0}
    held = counts.step_weights(conf, every)["bytes"] \
        + 19360 * (6144 + 4)                     # the embedding's rows
    assert abs(held - sum(cfg.weight_bytes(1).values())) < 3e6


def _trace(ops):
    """A device plane with ``ops`` (name, calls, us a call) back to back
    inside one program execution, reduced as a run's trace is."""
    events, t = [], 1000
    for name, calls, us in ops:
        for i in range(calls):
            events.append((f"%{name}.{10 + i % 3} = bf16[32,64,512]"
                           f"{{2,1,0}} custom-call(...)", t, us * 1000))
            t += us * 1000 + 50
    return reduce_trace.reduce_events({"/device:TPU:0": {
        "XLA Ops": events,
        "XLA Modules": [("jit_paged_decode_chunk(77)", 900, t)]}})


def _read(name, ctx):
    spec = json.loads((REPO / f"benchmark/layer_metrics/{name}.json")
                      .read_text())
    reader = layer_readers.resolve(spec.pop("kind"))
    spec.pop("what")
    return reader(ctx, **spec)


def test_every_new_metric_file_reads_its_own_call_site_and_counter():
    """On a reduced trace each ``_us`` file finds its kernel under its own
    name (the top-k: all ``%sort`` time over the index passes' calls) and
    nothing of kimi's; the rooflines are the counts' least time over it; the
    counter metrics read a window's two scrapes; on a program without the
    names (the parent's) every one returns nothing and does not raise."""
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    trace = _trace([("dsa_index_scores", 14, 200),
                    ("dsa_index_scores_ragged", 7, 240),
                    ("sort", 21, 1000),
                    ("dsa_sparse_decode_attention", 14, 400),
                    ("dsa_ragged_attention", 7, 3000),
                    ("mla_decode_attention", 4, 50),
                    ("fusion", 10, 700)])
    scrapes = {"start": {}, "end": {
        "llm_dsa_decode_keys_scored_total": 7 * 8 * 192000.0,
        "llm_dsa_decode_keys_selected_total": 7 * 8 * 65536.0,
        "llm_dsa_decode_calls_total": 7 * 8.0,
        "llm_dsa_decode_queries_total": 7 * 8 * 32.0,
        "llm_dsa_decode_queries_binding_total": 7 * 8 * 32.0}}
    scrapes["start"] = {k: 0.0 for k in scrapes["end"]}
    ctx = {"trace": trace, "config": conf, "values": {}, "scrapes": scrapes,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    got = {name: _read(name, ctx) for name in NEW_METRICS[:4]}
    assert {k: round(v) for k, v in got.items()} == {
        "dsa_index_scores_us": 200, "dsa_topk_us": 1000,
        "dsa_sparse_decode_attention_us": 400,
        "dsa_ragged_attention_us": 3000}
    share = _read("dsa_kernels_time_share", ctx)
    named = 14 * 200 + 7 * 240 + 21 * 1000 + 14 * 400 + 7 * 3000
    assert abs(share - 100 * named / (named + 4 * 50 + 7000)) < 0.01
    assert abs(_read("dsa_selected_share", ctx) - 65536 / 192000) < 1e-9
    assert _read("dsa_binding_share", ctx) == 1.0
    assert _read("dsa_decode_keys_scored_per_call", ctx) == 192000.0
    assert _read("dsa_decode_keys_selected_per_call", ctx) == 65536.0
    # the rooflines: fed by the metrics read before them
    ctx["values"] = {**got, "dsa_decode_keys_scored_per_call": 192000.0,
                     "dsa_decode_keys_selected_per_call": 65536.0,
                     "moe_decode_experts_touched_share": 0.625,
                     "moe_assignments_local_share": 16 / 256,
                     "decode_step_ms": 16.0}
    index = _read("dsa_index_scores_roofline", ctx)
    assert abs(index - 100 * (192000 * 256 / 819e9) / 200e-6) < 0.01
    attn = _read("dsa_sparse_decode_attention_roofline", ctx)
    assert abs(attn - 100 * (65536 * 1152 / 819e9) / 400e-6) < 0.01
    step = _read("dsa_moe_step_roofline", ctx)
    assert 25 < step < 40 and index < 100 and attn < 100
    # the parent's program: no such op, no such series: nothing, no raise.
    # (Its expert layers' sorts are %sort too: the top-k's time needs an
    # index pass to be a call of, and the time share, which is listed for
    # this cell alone, would read them as a fraction of a percent.)
    bare = {"trace": _trace([("mla_decode_attention", 4, 50),
                             ("sort", 6, 10)]),
            "config": conf, "peaks": ctx["peaks"], "values": {},
            "scrapes": {"start": {}, "end": {}}}
    assert all(_read(name, bare) is None for name in NEW_METRICS
               if name != "dsa_kernels_time_share")
    assert _read("dsa_kernels_time_share", bare) < 25


def test_the_share_passes_and_its_controls_are_caught():
    """The tiny share through the judge: chunks of 32 so that rows A, B and C
    pass ``index_topk`` 12, the row resumed from another row's pages in both
    arrays, the rider (attended whole), the idle row, decode steps; the
    reference attends over the keys the program chose, each within the
    epsilon and every count exact; the five controls each over the limit."""
    rc, result = judge("tiny-glm-dsa", "--control")
    assert rc == 0 and result["ok"]
    assert result["adapter"] == "benchmark.adapters.glm_dsa"
    limit = result["limit"]
    for r in result["readings"]:
        assert r["program"]["worst_row_rms"] <= limit
        assert r["program"]["rows"] >= 20
        assert r["idle_rows_touched"] == []
        assert r["control_int4"]["worst_row_rms"] > 3 * limit
        assert r["control_fp8"]["worst_row_rms"] > 2 * limit
        for control in ("no_select", "no_relu", "index_unweighted"):
            assert r[f"control_{control}"]["worst_row_rms"] > 4 * limit
        assert "control_latent_int8" in r


def test_the_cell_runs_through_the_harness():
    """Every request gets its ``max_tokens``; one line carries the expert
    counters and the selection's: with prompts of 16-64 and ``index_topk``
    12 every decode query binds, and a call attends 12 keys a row of the
    30-100 it scores; without a device in the trace the kernels' metrics are
    left out."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--benchmark-file", BENCH,
         "--workload", CELL, "--seed", str(2**31 + 58), "--seconds", "5",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    result = json.loads(last[len("REHEARSAL "):])
    assert result["correct"] and result["failed"] == 0
    assert "correctness: adapter benchmark.adapters.glm_dsa" in proc.stdout
    metrics = result["metrics"]
    assert 0.3 < metrics["moe_assignments_local_share"]["value"] < 0.7
    assert 0 < metrics["moe_decode_experts_touched_share"]["value"] <= 1
    assert metrics["dsa_binding_share"]["value"] == 1.0
    assert 0.1 < metrics["dsa_selected_share"]["value"] < 0.45
    selected = metrics["dsa_decode_keys_selected_per_call"]["value"]
    scored = metrics["dsa_decode_keys_scored_per_call"]["value"]
    assert 12 <= selected <= 4 * 12 and scored > 2 * selected
    assert abs(metrics["moe_layers_share"]["value"] - 60.0) < 0.01
    assert not {"dsa_index_scores_us", "dsa_topk_us",
                "dsa_sparse_decode_attention_us", "dsa_ragged_attention_us",
                "dsa_kernels_time_share", "dsa_index_scores_roofline",
                "dsa_sparse_decode_attention_roofline",
                "dsa_moe_step_roofline", "mla_decode_attention_us"} \
        & set(metrics)
