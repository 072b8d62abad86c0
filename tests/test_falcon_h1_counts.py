"""The Falcon-H1 configuration's count functions and reader kinds
(``benchmark/falcon_h1_counts.py``, ``benchmark/falcon_h1_readers.py``)
against the arithmetic written down in ISSUE 27 and PERF.md section 4, and
their behaviour on a program that lacks the kernel (nothing, not an error).
No JAX is imported by either module: the harness's parent holds no chip."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import falcon_h1_counts as counts
from benchmark import falcon_h1_readers as readers

REPO = Path(__file__).resolve().parents[1]
CONF = json.loads((REPO / "benchmark/configs/falcon-h1-34b-int8.json").read_text())
PEAK = json.loads((REPO / "benchmark/peaks.json").read_text())[
    "by_device_kind"]["TPU v5 lite"]


def test_a_block_and_the_head_are_the_bytes_the_issue_counts():
    one = dict(CONF, num_hidden_layers=1)
    block = counts.matmul_params(one) - CONF["hidden_size"] * CONF["vocab_size"]
    assert block == 47_349_760 + 20_971_520 + 31_457_280 + 330_301_440
    assert round(block / 1e6, 1) == 430.1
    assert CONF["hidden_size"] * CONF["vocab_size"] == 1_336_934_400
    assert counts.state_bytes_per_row(CONF) == 4 * (32 * 128 * 256 + 3 * 5120)


def test_the_state_kernel_moves_134_mb_a_call_at_16_rows():
    call = counts.ssm_state_update(CONF, CONF["serving"])
    assert 134.2e6 < call["bytes"] < 135.5e6
    assert call["flops"] == 5.0 * 16 * 32 * 128 * 256


def test_the_least_decode_step_is_12_7_ms():
    step = counts.decode_step_weights(CONF, CONF["serving"])
    peak = PEAK
    bandwidth = peak["hbm_bytes_per_s"]
    assert 12.5e-3 < step["bytes"] / bandwidth < 12.9e-3
    # memory-bound at 16 rows: the FLOPs take far less than the bytes
    assert step["flops"] / peak["bf16_flops_per_s"] < 0.25 * step["bytes"] / bandwidth


@pytest.mark.parametrize("values,expect", [
    ({}, None), ({"ssm_state_update_us": 0.0}, None),
    ({"ssm_state_update_us": 328.0}, 50.0)])
def test_roofline_us_reads_a_share_or_nothing(values, expect):
    peak = PEAK
    ctx = {"values": values, "config": CONF, "peaks": peak}
    got = readers.roofline_us(ctx, "ssm_state_update", "ssm_state_update_us")
    if expect is None:
        assert got is None
    else:
        least_us = 1e6 * counts.ssm_state_update(
            CONF, CONF["serving"])["bytes"] / peak["hbm_bytes_per_s"]
        assert got == pytest.approx(100.0 * least_us / 328.0)
        assert 45.0 < got < 55.0


def test_a_configuration_that_counts_no_such_role_reads_nothing():
    llama = json.loads(
        (REPO / "benchmark/configs/mistral-7b-int8.json").read_text())
    ctx = {"values": {"ssm_state_update_us": 200.0}, "config": llama,
           "peaks": {"hbm_bytes_per_s": 1.0, "bf16_flops_per_s": 1.0}}
    assert readers.roofline_us(ctx, "ssm_state_update",
                               "ssm_state_update_us") is None


def test_gauge_percent_is_the_mean_share_times_100():
    scrapes = {"all": [{"llm_state_rows_in_use": 16.0, "llm_state_rows": 32.0},
                       {"llm_state_rows_in_use": 8.0, "llm_state_rows": 32.0}]}
    assert readers.gauge_percent({"scrapes": scrapes}, "llm_state_rows_in_use",
                                 "llm_state_rows") == pytest.approx(37.5)
    assert readers.gauge_percent({"scrapes": {}}, "llm_state_rows_in_use",
                                 "llm_state_rows") is None


def test_counts_and_readers_import_no_jax():
    code = ("import sys; import benchmark.falcon_h1_counts, "
            "benchmark.falcon_h1_readers; sys.exit('jax' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code], cwd=REPO).returncode == 0


def test_the_configuration_file_states_every_published_value():
    """Every key of the source's config is in the file; only the two listed
    under ``reduced`` differ (the published values are in ModelConfig)."""
    from cyberfabric_core_tpu.models import get_config

    full, cut = get_config("falcon-h1-34b"), get_config("falcon-h1-34b-16l")
    assert CONF["num_hidden_layers"] == cut.num_layers == 16
    assert full.num_layers == 72 and full.max_position == 262144
    assert set(CONF["reduced"]) == {"num_hidden_layers",
                                    "max_position_embeddings"}
    for key, value in {
            "hidden_size": full.hidden_size, "vocab_size": full.vocab_size,
            "intermediate_size": full.intermediate_size,
            "num_attention_heads": full.num_heads,
            "num_key_value_heads": full.num_kv_heads,
            "head_dim": full.head_dim, "mamba_d_ssm": full.ssm_inner,
            "mamba_n_heads": full.ssm_heads, "mamba_d_head": full.ssm_head_dim,
            "mamba_d_state": full.ssm_state, "mamba_n_groups": full.ssm_groups,
            "mamba_d_conv": full.ssm_conv, "mamba_chunk_size": full.ssm_chunk,
            "rope_theta": full.rope_theta, "rms_norm_eps": full.rms_norm_eps,
            "embedding_multiplier": full.embedding_multiplier,
            "key_multiplier": full.key_multiplier,
            "attention_out_multiplier": full.attention_out_multiplier,
            "lm_head_multiplier": full.lm_head_multiplier,
            "ssm_in_multiplier": full.ssm_in_multiplier,
            "ssm_out_multiplier": full.ssm_out_multiplier}.items():
        assert CONF[key] == value, key
    assert tuple(CONF["ssm_multipliers"]) == full.ssm_multipliers
    assert tuple(CONF["mlp_multipliers"]) == full.mlp_multipliers
    assert CONF["serving"]["model_config"] == cut.name
