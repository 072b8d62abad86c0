"""What the Laguna configuration brings to the yardstick, shown at no chip
cost on ``tiny-laguna-share4`` (``rehearsal/BENCHMARK-laguna.json``, a
rehearsal benchmark file of its own: no file that was there is edited):
every name in its data files resolves, in the rehearsal's file and in the
real one; the judge passes the tiny stack through
``benchmark/adapters/laguna.py`` with window pages of K and V freed and
reused, and catches its five controls; its cell runs through the harness
with the expert counters and both page groups' gauges on one line; the
configuration file carries the catalog's keys unchanged; the counts module
answers the roles the readers ask and agrees with a count by hand; every new
metric file reads what a trace or a scrape holds.

**The entries are pinned by name and by what stands BEFORE them, never as
the last of a list**: a later PR appends behind them and this file holds."""

import json
import os
import subprocess
import sys

from benchmark import laguna_counts as counts
from benchmark import layer_readers, reduce_trace
from benchmark.tests import test_names_resolve as resolve
from benchmark.tests.test_seam import REPO, judge

BENCH = "benchmark/tests/rehearsal/BENCHMARK-laguna.json"
CELL = "tiny-laguna.decode-closed"
REAL = "laguna-s-2.1-int8"
REAL_CELL = "laguna-s-2.1-int8.longtail-closed-64"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "max_position_embeddings"]
NEW_METRICS = ("gqa_full_decode_attention_us",
               "gqa_window_decode_attention_us",
               "gqa_full_decode_attention_roofline",
               "gqa_window_decode_attention_roofline",
               "gqa_kernels_time_share", "gqa_full_ragged_attention_us",
               "gqa_window_ragged_attention_us",
               "gqa_window_moe_step_roofline")
#: accepted metrics the cell reads under the names they have
APPENDED = ("moe_experts_us", "moe_kernel_time_share",
            "moe_experts_touched_share", "moe_assignments_local_share",
            "moe_decode_experts_touched_share", "routed_experts_roofline",
            "moe_compact_share", "moe_item_rows_per_touched_expert",
            "attn_window_pages_walked_share", "window_layers_share",
            "window_pages_per_row")
#: two accepted gauge shares the cell has something to read for (3 of 12
#: layers in the full group's pool, 11 of 12 with experts)
GAUGE_SHARES = ("kv_layers_share", "moe_layers_share")
#: the window layers' twin of ``attn_pages_per_program``: what reads the
#: counter of the second work list's programs (motif's cell and this one)
WINDOW_GROUPS = "attn_window_pages_per_program"
#: accepted metrics the cell must NOT be listed for: its call sites carry
#: other names, and the llama count reads one head count
ABSENT = ("paged_decode_attention_us", "attn_kernels_time_share",
          "decode_step_roofline", "gdla_kernels_time_share",
          "gdla_moe_step_roofline", "mla_kernels_time_share")


def _published() -> dict:
    """The catalog's ``config`` of Laguna-S-2.1, as published."""
    kinds = ["full_attention" if i % 4 == 0 else "sliding_attention"
             for i in range(48)]
    return {
        "model_type": "laguna", "hidden_size": 3072,
        "intermediate_size": 12288, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "layer_types": kinds, "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [
            48 if k == "full_attention" else 72 for k in kinds],
        "moe_router_logit_softcapping": 0}


def test_the_rehearsal_files_names_resolve():
    bench = json.loads((REPO / BENCH).read_text())
    assert [w["name"] for w in bench["workloads"]] == [CELL]
    resolve.test_configuration_resolves(BENCH, "tiny-laguna")
    resolve.test_traffic_file_resolves(BENCH, "decode-closed")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL] for name in
               NEW_METRICS + APPENDED + GAUGE_SHARES + (WINDOW_GROUPS,))
    assert not set(ABSENT) & set(listed)
    for name in listed:
        resolve.test_layer_metric_resolves(BENCH, name)


def test_the_real_files_names_resolve_and_only_add():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    entry = bench["configs"][configs.index(REAL)]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == ("https://huggingface.co/poolside/"
                               "Laguna-S-2.1/blob/main/config.json")
    cell = bench["workloads"][cells.index(REAL_CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL, "longtail-closed-64", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # what stands before them: the ten configurations and cells PR 52 left,
    # in their order (whatever a later PR appends stands behind)
    assert configs[: configs.index(REAL)] == [
        "mistral-7b-int8", "qwen2-7b-int8", "falcon-h1-34b-int8",
        "sdar-30b-a3b-int8", "kimi-k2.5-int8", "granite-4.0-h-small-int8",
        "nemotron-3-super-int8", "solar-open2-int8", "motif-3-beta-int8",
        "ouro-2.6b-int8"]
    assert cells[: cells.index(REAL_CELL)] == [
        "mistral-7b-int8.decode-closed", "qwen2-7b-int8.decode-closed",
        "falcon-h1-34b-int8.decode-closed", "sdar-30b-a3b-int8.decode-closed",
        "kimi-k2.5-int8.reason-closed-64",
        "granite-4.0-h-small-int8.reason-closed-64",
        "nemotron-3-super-int8.reason-closed-64",
        "solar-open2-int8.reason-closed-64",
        "motif-3-beta-int8.longtail-closed-64",
        "ouro-2.6b-int8.decode-closed-8"]
    resolve.test_configuration_resolves("BENCHMARK.json", REAL)
    resolve.test_traffic_file_resolves("BENCHMARK.json", "longtail-closed-64")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    order = [m["name"] for m in bench["per_layer"]]
    # the eight new metrics in the issue's order, in one piece, behind PR
    # 53's three
    at = order.index(NEW_METRICS[0])
    assert order[at: at + len(NEW_METRICS)] == list(NEW_METRICS)
    assert order[at - 3: at] == ["mla_ragged_attention_us",
                                 "gdla_full_ragged_attention_us",
                                 "gdla_window_ragged_attention_us"]
    # a reader comes after every metric whose value it is fed
    for fed in ("attn_pages_walked_share", "attn_window_pages_walked_share",
                "moe_decode_experts_touched_share",
                "moe_assignments_local_share", "decode_step_ms"):
        assert order.index(fed) < order.index("gqa_window_moe_step_roofline")
    for site, fed in (("full", "attn_pages_walked_share"),
                      ("window", "attn_window_pages_walked_share")):
        assert order.index(f"gqa_{site}_decode_attention_us") \
            < order.index(f"gqa_{site}_decode_attention_roofline")
        assert order.index(fed) \
            < order.index(f"gqa_{site}_decode_attention_roofline")
    for name in APPENDED:
        behind = listed[name][: listed[name].index(REAL_CELL)]
        assert "motif-3-beta-int8.longtail-closed-64" in behind, name
    for name in GAUGE_SHARES:
        assert listed[name][-1] == REAL_CELL and len(listed[name]) > 1
    # behind the eight, the counter metric of the window layers' programs:
    # motif's cell and this one, read by the existing ``counter`` reader
    assert order[at + len(NEW_METRICS)] == WINDOW_GROUPS
    assert listed[WINDOW_GROUPS] == [
        "motif-3-beta-int8.longtail-closed-64", REAL_CELL]
    entry = bench["per_layer"][order.index(WINDOW_GROUPS)]
    assert (entry["moves"], entry["source"], entry["layer"],
            entry["unit"]) == ("out_tokens_per_s", "program_counter",
                               "kernels", "pages")
    resolve.test_layer_metric_resolves("BENCHMARK.json", WINDOW_GROUPS)
    for name in ABSENT:
        assert REAL_CELL not in listed[name]
    for name in NEW_METRICS:
        assert listed[name][0] == REAL_CELL
        entry = bench["per_layer"][order.index(name)]
        assert (entry["moves"], entry["source"]) == ("out_tokens_per_s",
                                                     "device_trace")
        assert entry["unit"] == ("us" if name.endswith("_us") else "%")
        assert entry["layer"] == ("model step" if "step" in name
                                  else "kernels")
        resolve.test_layer_metric_resolves("BENCHMARK.json", name)
    # the traffic is the file motif's cell uses, unchanged
    traffic = json.loads(
        (REPO / "benchmark/traffic/longtail-closed-64.json").read_text())
    assert (traffic["kind"], traffic["clients"], traffic["cycle"],
            traffic["temperature"], traffic["lead_in_s"]) == (
                "closed", 64, 256, 0.0, 60)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.8, "min": 256,
        "max": 5120}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 1536,
                                        "max": 2560}


def test_the_configuration_carries_the_published_keys_unchanged():
    """Every key of the catalog's ``config`` is in the file with its value,
    but the four under ``reduced``; the deployment and every inference are
    stated; the program's preset is the file's numbers."""
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    assert sorted(conf["reduced"]) == sorted(REDUCED)
    for key, value in _published().items():
        assert conf[key] == value, key
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"], conf["max_position_embeddings"]) == (
                12, 32, 12544, 8192)
    # the floors: three whole periods, 11 layers after the dense one,
    # 32 >= 8 experts, an eighth of the vocabulary's rows
    assert conf["vocab_size"] * 8 == conf["serving"]["vocab_published"]
    assert conf["num_experts"] * 8 == conf["serving"]["experts_routed"] == 256
    deployment = conf["deployment"]
    assert (deployment["chips"], deployment["pipeline_stages"],
            deployment["chips_sharing_a_layer"]) == (32, 4, 8)
    assert "2.5" in deployment["expert_load"]
    serving = conf["serving"]
    assert (serving["expert_offset"], serving["vocab_published"],
            serving["layers_published"]) == (0, 100352, 48)
    assert (serving["max_batch"], serving["max_seq_len"], serving["page"],
            serving["decode_chunk"], serving["prefill_budget_tokens"],
            serving["max_input_tokens"], serving["max_output_tokens"]) == (
                64, 8192, 64, 8, 512, 5120, 2560)
    assert serving["pool_pages"] == 64 * (8192 // 64)
    assert serving["mixed_widths"] == [16, 32, 64, 128, 256, 512]
    assert serving["programs"] == ["mixed_step", "paged_decode_chunk"]
    yaml = (REPO / serving["yaml"]).read_text()
    assert f"prefix_cache_pages: {serving['pool_pages'] + 1}" in yaml
    assert "window_cache_pages" not in yaml     # no option: from shapes
    assert "architecture: laguna" in yaml
    assumed = " ".join(conf["assumed"])
    for said in ("pre-norm", "no q/k norm", "FIRST 64", "attention_factor",
                 "t-511..t", "j // (H_l / 8)", "BEFORE W_o", "hidden_act silu",
                 "softmax over all 256", "added ungated", "byte fallback",
                 "eos_token_ids", "no checkpoint"):
        assert said in assumed, said
    cc = conf["correctness"]
    # the judged depth: the dense full layer, three window layers, a full
    # expert layer and a window layer behind it: every body the served 12
    # layers compile; row A of the scenario passes two windows and a chunk
    assert cc["depth"] == 6 and cc["chunk"] == 512
    assert cc["controls"] == {
        "caught": ["int4", "fp8", "no_window", "one_rope", "no_head_gate"],
        "read_only": ["kv_int8"]}
    assert 0.005 < cc["limit"] < 0.05 and "1." in cc["limit_why"]
    from cyberfabric_core_tpu.models import get_config

    cfg = get_config(serving["model_config"])
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.shared_width, cfg.num_heads,
            cfg.window_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.num_experts, cfg.experts_per_token,
            cfg.routed_scaling_factor, cfg.sliding_window,
            cfg.sliding_window_period, cfg.full_layer_phase,
            cfg.first_k_dense, cfg.experts_held, cfg.vocab_held,
            cfg.num_layers, cfg.rope_theta, cfg.rope_factor,
            cfg.rope_original_max, cfg.partial_rotary_factor, cfg.window_rope_theta,
            cfg.rms_norm_eps, cfg.head_gate) == (
                3072, 12288, 1024, 1024, 48, 72, 8, 128, 256, 10, 2.5, 512,
                4, 0, 1, 32, 12544, 12, 500000.0, 128.0, 8192, 0.5, 10000.0,
                1e-6, True)
    # the published attention_factor is what the program's tables carry,
    # derived from the factor (no field states it a second time)
    from cyberfabric_core_tpu.ops.rope import rope_tables

    assert abs(float(rope_tables(cfg, 1)[0][0][0, 0])
               / conf["rope_parameters"]["full_attention"]["attention_factor"]
               - 1) < 1e-6
    assert [cfg.layer_is_full(i) for i in range(12)] == [
        t == "full_attention" for t in conf["layer_types"][:12]]
    # the window group's pages are what the scheduler derives from these
    # shapes (10 + 1 a slot through a ring of 24 tokens, two chunks of 17,
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
    assert built == serving["window_pool_pages"] + 1 == 739


def test_the_counts_answer_the_roles_and_agree_with_a_count_by_hand():
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    serving = conf["serving"]
    # by hand: q and o at 48 or 72 heads of 128, k and v at 8, the gate
    assert counts.attention_params(conf, 48) == (
        2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48,
        6144 + 2 * 1024 + 3072 + 48)
    assert counts.attention_params(conf, 72)[0] == 63_135_744
    assert counts.expert_params(conf) == (9_437_184, 2 * 1024 + 3072)
    assert counts.kv_row_bytes(conf) == 4096
    assert counts.cache_bytes_per_token(conf) == 3 * 4096
    assert counts.window_bytes_per_token(conf) == 9 * 4096
    # nothing from shapes alone where a counter has to say it
    for role in ("gqa_full_decode_attention", "gqa_window_decode_attention",
                 "routed_experts", "decode_step"):
        assert getattr(counts, role)(conf, serving) is None, role
    # 64 rows of 2 400 tokens: 38 pages of 128 a row in a full layer, 9 in a
    # window layer; 29.4 of 32 held experts touched
    measured = {**serving, "attn_pages_walked_share": 38 / 128,
                "window_pages_walked_share": 9 / 128,
                "experts_touched_share": 0.92,
                "assignments_local_share": 32 / 256}
    full = counts.gqa_full_decode_attention(conf, measured)
    assert full["bytes"] == 38 * 64 * 64 * 4096         # whole pages, K and V
    assert full["flops"] == 48 * 38 * 64 * 64 * 4.0 * 128
    window = counts.gqa_window_decode_attention(conf, measured)
    assert window["bytes"] == 9 * 64 * 64 * 4096
    assert window["flops"] == 72 * 9 * 64 * 64 * 4.0 * 128
    layer = counts.routed_experts(conf, measured)
    assert layer["bytes"] == 0.92 * 32 * (9_437_184 + 4.0 * 5120)
    assert layer["flops"] == 2.0 * 9_437_184 * 64 * 10 / 8
    step = counts.decode_step(conf, measured)
    # the issue's reckoning: weights 4.05 GB of which the touched experts
    # 3.05, K/V 1.9 + 1.4 GB: 7.3 GB a step
    weights = counts.step_weights(conf, measured)
    assert 4.0e9 < weights["bytes"] < 4.2e9
    assert 7.2e9 < step["bytes"] < 7.5e9
    assert step["bytes"] == weights["bytes"] + 3 * full["bytes"] \
        + 9 * window["bytes"]
    from cyberfabric_core_tpu.models import get_config

    cfg = get_config(serving["model_config"])
    assert counts.cache_bytes_per_token(conf) == cfg.cache_bytes_per_token()
    assert counts.window_bytes_per_token(conf) == \
        cfg.window_bytes_per_token()
    # every held weight, int8 + f32 scales, against the program's own count
    every = {**measured, "experts_touched_share": 1.0}
    held = counts.step_weights(conf, every)["bytes"] \
        + 12544 * (3072 + 4)                     # the embedding's rows
    assert abs(held - sum(cfg.weight_bytes(1).values())) < 2e6


def _trace(ops):
    """A device plane with ``ops`` (name, calls, us a call) back to back
    inside one program execution, reduced as a run's trace is."""
    events, t = [], 1000
    for name, calls, us in ops:
        for i in range(calls):
            events.append((f"%{name}.{10 + i % 3} = bf16[64,48,128]"
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
    """On a reduced trace each ``_us`` file finds its call site's kernel
    under its own name and nothing of the llama family's or motif's; the
    rooflines are the counts' least time over it; on a program without the
    names (the parent's) every one returns nothing and does not raise."""
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    peaks = json.loads((REPO / "benchmark/peaks.json").read_text())
    trace = _trace([("gqa_full_decode_attention", 6, 1000),
                    ("gqa_window_decode_attention", 18, 300),
                    ("gqa_full_ragged_attention", 3, 2000),
                    ("gqa_window_ragged_attention", 9, 800),
                    ("paged_decode_attention", 4, 50),
                    ("gdla_full_decode_attention", 4, 50),
                    ("fusion", 10, 700)])
    ctx = {"trace": trace, "config": conf,
           "peaks": next(iter(peaks.values())) if "hbm_bytes_per_s"
           not in peaks else peaks, "values": {}}
    got = {name: _read(name, ctx) for name in NEW_METRICS if "_us" in name}
    assert {k: round(v) for k, v in got.items()} == {
        "gqa_full_decode_attention_us": 1000,
        "gqa_window_decode_attention_us": 300,
        "gqa_full_ragged_attention_us": 2000,
        "gqa_window_ragged_attention_us": 800}
    share = _read("gqa_kernels_time_share", ctx)
    busy = 6 * 1000 + 18 * 300 + 3 * 2000 + 9 * 800 + 8 * 50 + 7000
    assert abs(share - 100 * (busy - 7400) / busy) < 0.01
    # the rooflines: fed by the metrics read before them
    ctx["values"] = {**got, "attn_pages_walked_share": 38 / 128,
                     "attn_window_pages_walked_share": 9 / 128,
                     "moe_decode_experts_touched_share": 0.92,
                     "moe_assignments_local_share": 0.125,
                     "decode_step_ms": 18.0}
    ctx["peaks"] = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    full = _read("gqa_full_decode_attention_roofline", ctx)
    assert abs(full - 100 * (38 * 64 * 64 * 4096 / 819e9) / 1000e-6) < 0.01
    window = _read("gqa_window_decode_attention_roofline", ctx)
    assert abs(window - 100 * (9 * 64 * 64 * 4096 / 819e9) / 300e-6) < 0.01
    step = _read("gqa_window_moe_step_roofline", ctx)
    assert 45 < step < 55 and full < 100 and window < 100
    # the parent's program: no such op, no such value: nothing, no raise
    bare = {"trace": _trace([("paged_decode_attention", 4, 50)]),
            "config": conf, "peaks": ctx["peaks"], "values": {},
            "scrapes": {"start": {"llm_attn_window_pages_walked_total": 0.0},
                        "end": {"llm_attn_window_pages_walked_total": 90.0}}}
    assert all(_read(name, bare) is None
               for name in NEW_METRICS + (WINDOW_GROUPS,))
    # the window layers' pages over their programs: 9 pages a row in three
    # groups of four
    bare["scrapes"]["start"]["llm_attn_window_page_groups_total"] = 6.0
    bare["scrapes"]["end"]["llm_attn_window_page_groups_total"] = 36.0
    assert _read(WINDOW_GROUPS, bare) == 3.0


def test_the_stack_passes_and_its_controls_are_caught():
    """Depth 6 of the tiny stack (a dense full layer, three window layers,
    a full expert layer, a window layer) through the judge: chunks, the row
    that prefills another row's first tokens itself, the rider, the idle
    row, decode steps through both page groups, window pages of K and V
    freed and written again on the way; the five controls each over the
    limit."""
    rc, result = judge("tiny-laguna", "--control")
    assert rc == 0 and result["ok"]
    assert result["adapter"] == "benchmark.adapters.laguna"
    limit = result["limit"]
    for r in result["readings"]:
        assert r["program"]["worst_row_rms"] <= limit
        assert r["program"]["rows"] >= 20
        assert r["idle_rows_touched"] == []
        assert r["control_int4"]["worst_row_rms"] > 3 * limit
        assert r["control_fp8"]["worst_row_rms"] > 2 * limit
        for control in ("no_window", "one_rope", "no_head_gate"):
            assert r[f"control_{control}"]["worst_row_rms"] > 4 * limit


def test_the_cell_runs_through_the_harness():
    """Every request gets its ``max_tokens``; one line carries the expert
    counters and the gauges of what the two page groups were built with;
    without a device in the trace the kernels' metrics are left out."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--benchmark-file", BENCH,
         "--workload", CELL, "--seed", str(2**31 + 54), "--seconds", "5",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    result = json.loads(last[len("REHEARSAL "):])
    assert result["correct"] and result["failed"] == 0
    assert "correctness: adapter benchmark.adapters.laguna" in proc.stdout
    metrics = result["metrics"]
    assert 0.3 < metrics["moe_assignments_local_share"]["value"] < 0.7
    assert 0 < metrics["moe_decode_experts_touched_share"]["value"] <= 1
    assert abs(metrics["window_layers_share"]["value"] - 200 / 3) < 0.01
    # a window of 8 in pages of 4: 2-3 pages of a table of 64 a row
    assert 0.02 < metrics["attn_window_pages_walked_share"]["value"] < 0.06
    assert metrics["attn_pages_walked_share"]["value"] > \
        2 * metrics["attn_window_pages_walked_share"]["value"]
    assert not set(NEW_METRICS) & set(metrics)
    # the gauges of what was built: 2 of 6 layers in the full group's pool,
    # 5 of 6 with experts; the window layers' programs walk 2-3 pages each
    # (9 query heads on 3 kv heads: a group holds the whole window)
    assert abs(metrics["kv_layers_share"]["value"] - 100 / 3) < 0.01
    assert abs(metrics["moe_layers_share"]["value"] - 500 / 6) < 0.01
    assert 1 <= metrics[WINDOW_GROUPS]["value"] <= 3
