"""What the Ouro configuration brings to the yardstick, shown at no chip cost
on ``tiny-ouro`` (``rehearsal/BENCHMARK-ouro.json``, a rehearsal benchmark
file of its own: no file that was there is edited): every name in its data
files resolves, in the rehearsal's file and in the real one; the judge passes
the tiny stack (3 layers x 3 passes) through ``benchmark/adapters/ouro.py``
on the program's own pool of 9 cache layers and catches its FOUR controls;
its cell runs through the harness with the loop's counters and the pool's
gauges on one line; the configuration file carries the catalog's keys
unchanged; the counts module answers its roles and agrees with a count by
hand."""

import json
import os
import subprocess
import sys

from benchmark import ouro_counts as counts
from benchmark.tests import test_names_resolve as resolve
from benchmark.tests.test_seam import REPO, judge

BENCH = "benchmark/tests/rehearsal/BENCHMARK-ouro.json"
CELL = "tiny-ouro.decode-closed"
REAL = "ouro-2.6b-int8"
REAL_CELL = "ouro-2.6b-int8.decode-closed-8"
NEW_METRICS = ("loop_step_roofline", "paged_decode_attention_roofline",
               "loop_passes_per_forward", "loop_exit_pass_mean")
#: accepted metrics the cell reads under the names they have
APPENDED = ("paged_decode_attention_us", "attn_kernels_time_share",
            "kv_layers_share")
#: the accepted count reads a layer ONCE: ``loop_step_roofline`` takes its
#: place in this cell, as ``block_forward_roofline`` did for sdar
ABSENT = ("decode_step_roofline",)
#: the catalog's ``config`` of Ouro-2.6B, as published
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def test_the_rehearsal_files_names_resolve():
    bench = json.loads((REPO / BENCH).read_text())
    assert [w["name"] for w in bench["workloads"]] == [CELL]
    resolve.test_configuration_resolves(BENCH, "tiny-ouro")
    resolve.test_traffic_file_resolves(BENCH, "decode-closed")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL] for name in NEW_METRICS + APPENDED)
    assert not set(ABSENT) & set(listed)
    for name in listed:
        resolve.test_layer_metric_resolves(BENCH, name)


def test_the_real_files_names_resolve_and_only_add():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [c for c in bench["configs"] if c["name"] == REAL]
    assert entry["reduced"] == ["max_position_embeddings"]
    assert entry["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                               "blob/main/config.json")
    assert bench["configs"][-1] is entry
    (cell,) = [w for w in bench["workloads"] if w["name"] == REAL_CELL]
    assert bench["workloads"][-1] is cell
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL, "decode-closed-8", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    resolve.test_configuration_resolves("BENCHMARK.json", REAL)
    resolve.test_traffic_file_resolves("BENCHMARK.json", "decode-closed-8")
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    order = [m["name"] for m in bench["per_layer"]]
    # a reader comes after every metric whose value it is fed
    for fed in ("attn_pages_walked_share", "decode_step_ms"):
        assert order.index(fed) < order.index("loop_step_roofline")
    for fed in ("attn_pages_walked_share", "paged_decode_attention_us"):
        assert order.index(fed) < order.index(
            "paged_decode_attention_roofline")
    for name in APPENDED:
        assert listed[name][-1] == REAL_CELL and len(listed[name]) >= 4
    for name in ABSENT:
        assert REAL_CELL not in listed[name]
    for name in NEW_METRICS:
        assert listed[name] == [REAL_CELL]
        resolve.test_layer_metric_resolves("BENCHMARK.json", name)
    # new entries at the end of their list, in the issue's order
    assert order[-4:] == list(NEW_METRICS)
    # the cells that were there keep their lines, in their order
    assert [w["name"] for w in bench["workloads"]][:9] == [
        "mistral-7b-int8.decode-closed", "qwen2-7b-int8.decode-closed",
        "falcon-h1-34b-int8.decode-closed", "sdar-30b-a3b-int8.decode-closed",
        "kimi-k2.5-int8.reason-closed-64",
        "granite-4.0-h-small-int8.reason-closed-64",
        "nemotron-3-super-int8.reason-closed-64",
        "solar-open2-int8.reason-closed-64",
        "motif-3-beta-int8.longtail-closed-64"]
    # the traffic is the issue's: decode-closed's own lengths at 8 clients
    traffic = json.loads(
        (REPO / "benchmark/traffic/decode-closed-8.json").read_text())
    accepted = json.loads(
        (REPO / "benchmark/traffic/decode-closed.json").read_text())
    assert (traffic["kind"], traffic["clients"], traffic["cycle"],
            traffic["temperature"]) == ("closed", 8, 256, 0.0)
    for key in ("prompt_tokens", "output_tokens", "cycle", "temperature"):
        assert traffic[key] == accepted[key]
    assert traffic["prompt_tokens"] == {"dist": "uniform", "min": 64,
                                        "max": 256}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 384,
                                        "max": 512}
    assert 8.0 <= traffic["lead_in_s"] <= 20.0


def test_the_configuration_carries_the_published_keys_unchanged():
    """Every key of the catalog's ``config`` is in the file with its value,
    but ``max_position_embeddings``, the one key under ``reduced``: all 48
    layers, 4 passes, 16/16 heads and the whole vocabulary are held; every
    line the config does not settle is under ``assumed``; the program's
    preset is the file's numbers; the pool is the slot minimum."""
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    assert list(conf["reduced"]) == ["max_position_embeddings"]
    for key, value in PUBLISHED.items():
        assert conf[key] == value, key
    assert conf["max_position_embeddings"] == 832
    assert conf["published"]["max_position_embeddings"] == 65536
    assert conf["deployment"]["chips"] == 1
    serving = conf["serving"]
    assert (serving["max_batch"], serving["max_seq_len"], serving["page"],
            serving["decode_chunk"], serving["prefill_budget_tokens"],
            serving["max_output_tokens"], serving["loop_steps"]) == (
                8, 832, 64, 8, 512, 512, 4)
    # 8 slots x 13 pages + scratch: the scheduler's slot minimum
    assert serving["pool_pages"] + 1 == 8 * 13 + 1 == 105
    assert serving["mixed_widths"] == [16, 32, 64, 128, 256, 512]
    assert serving["programs"] == ["mixed_step", "paged_decode_chunk"]
    yaml = (REPO / serving["yaml"]).read_text()
    for said in ("prefix_cache_pages: 105", "architecture: ouro",
                 "max_seq_len: 832", "max_batch: 8", "eos_token_ids: [49152]",
                 "decode_chunk: 8", "prefill_budget_tokens: 512"):
        assert said in yaml, said
    assumed = " ".join(conf["assumed"])
    for said in ("no bias", "sandwich norms", "after EVERY pass",
                 "Linear(2048, 1)", "cache layer (t - 1) x 48 + l",
                 "early_exit_threshold 1", "NOT served", "rotate-half",
                 "synthetic", "byte fallback", "eos_token_ids"):
        assert said in assumed, said
    cc = conf["correctness"]
    assert cc["adapter"] == "benchmark.adapters.ouro"
    assert (cc["depth"], cc["chunk"]) == (4, 512) and 4 <= cc["decode_steps"] <= 6
    assert cc["controls"] == {
        "caught": ["int4", "fp8", "loop_3", "no_pass_norm"],
        "read_only": ["kv_int8"]}
    assert conf["counts"] == "benchmark.ouro_counts"
    from cyberfabric_core_tpu.models import get_config

    cfg = get_config(serving["model_config"])
    assert (cfg.architecture, cfg.hidden_size, cfg.intermediate_size,
            cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.vocab_size, cfg.max_position, cfg.rope_theta,
            cfg.rms_norm_eps, cfg.loop_steps, cfg.early_exit_threshold,
            cfg.sandwich_norm, cfg.tie_embeddings, cfg.attention_bias,
            cfg.sliding_window) == (
                "ouro", 2048, 5632, 48, 16, 16, 128, 49152, 65536, 1e6, 1e-6,
                4, 1.0, True, False, False, None)
    # the bytes the file reckons are the program's own
    assert cfg.cache_bytes_per_token() == 1_572_864
    assert 105 * 64 * cfg.cache_bytes_per_token() == 10_569_646_080
    assert counts.cache_bytes_per_token(conf) == cfg.cache_bytes_per_token()
    assert counts.layer_params(conf)[0] * 48 + 2 * 49152 * 2048 == \
        cfg.param_count() - 48 * 4 * 2048 - 2048 - 2049


def test_the_counts_answer_the_roles_and_agree_with_a_count_by_hand():
    conf = json.loads((REPO / f"benchmark/configs/{REAL}.json").read_text())
    serving = conf["serving"]
    weights, scales = counts.layer_params(conf)
    assert weights == 4 * 2048 * 2048 + 3 * 2048 * 5632 == 51_380_224
    assert scales == 2048 * 4 + 2 * 5632 + 2048 == 21_504
    assert counts.cache_layers(conf) == 192
    # nothing from shapes alone where a counter has to say it
    assert counts.paged_decode_attention(conf, serving) is None
    assert counts.loop_step(conf, serving) is None
    # 8 rows of about 420 tokens: 7 pages of a table of 13 a row
    measured = {**serving, "attn_pages_walked_share": 7 / 13}
    call = counts.paged_decode_attention(conf, measured)
    tokens = 8 * 7 * 64
    assert call["bytes"] == tokens * 2 * 2048 * 2 == 29_360_128
    assert call["flops"] == tokens * 16 * 4 * 128
    step = counts.loop_step(conf, measured)
    by_hand = (4 * 48 * (51_380_224 + 4 * 21_504)     # the layers, 4 times
               + 2048 * 49152 + 4 * 49152              # the head, once
               + 192 * 29_360_128)                     # K/V as walked
    assert step["bytes"] == by_hand
    assert 9.87e9 < 4 * 48 * (51_380_224 + 4 * 21_504) < 9.89e9
    assert 15.5e9 < step["bytes"] < 15.7e9
    assert step["flops"] == 2.0 * 8 * (4 * 48 * 51_380_224 + 2048 * 49152) \
        + 192 * call["flops"]
    # memory-bound by two orders: 19 ms of bytes, 0.9 ms of FLOPs
    from benchmark import opcounts

    peaks = json.loads((REPO / "benchmark/peaks.json").read_text())[
        "by_device_kind"]["TPU v5 lite"]
    least, bound = opcounts.least_seconds(step, peaks)
    assert bound == "memory" and 0.0189 < least < 0.0192
    assert opcounts.count_function(conf, "loop_step") is counts.loop_step
    assert opcounts.count_function(conf, "decode_step_weights") is None


def test_the_stack_passes_and_its_four_controls_are_caught():
    """All 3 layers x 3 passes of the tiny stack through the judge: chunks,
    the row resumed from pages another row wrote, the rider, the idle row,
    decode steps through 9 cache layers."""
    rc, result = judge("tiny-ouro", "--control")
    assert rc == 0 and result["ok"]
    assert result["adapter"] == "benchmark.adapters.ouro"
    limit = result["limit"]
    for r in result["readings"]:
        assert r["program"]["worst_row_rms"] <= limit
        assert r["program"]["rows"] >= 20
        assert r["control_int4"]["worst_row_rms"] > 3 * limit
        assert r["control_fp8"]["worst_row_rms"] > 2 * limit
        assert r["control_loop_3"]["worst_row_rms"] > 5 * limit
        assert r["control_no_pass_norm"]["worst_row_rms"] > 5 * limit
        assert r["control_kv_int8"]["worst_row_rms"] <= limit


def test_the_cell_runs_through_the_harness():
    """Every request gets its ``max_tokens``; one line carries the loop's
    counters and the gauges of what the pool was built with; without a
    device in the trace the kernels' metrics and both rooflines are left
    out."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--benchmark-file", BENCH,
         "--workload", CELL, "--seed", str(2**31 + 52), "--seconds", "15",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    result = json.loads(last[len("REHEARSAL "):])
    assert result["correct"] and result["failed"] == 0
    assert "correctness: adapter benchmark.adapters.ouro" in proc.stdout
    metrics = result["metrics"]
    assert metrics["loop_passes_per_forward"]["value"] == 3.0
    assert 1.0 < metrics["loop_exit_pass_mean"]["value"] < 3.0
    assert metrics["kv_layers_share"]["value"] == 300.0
    assert not {"loop_step_roofline", "paged_decode_attention_roofline",
                "paged_decode_attention_us", "decode_step_roofline"
                } & set(metrics)
