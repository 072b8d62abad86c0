"""``benchmark/sdar_counts.py``: the bytes and operations of one forward of
SDAR-30B-A3B's cut, against the arithmetic written in the configuration's
file and in ISSUE 31."""

import json
from pathlib import Path

import pytest

from benchmark import sdar_counts

CONF = json.loads((Path(__file__).resolve().parents[1]
                   / "benchmark/configs/sdar-30b-a3b-int8.json").read_text())
SERVING = CONF["serving"]


def test_a_forward_of_the_full_batch_is_64_tokens():
    assert sdar_counts.tokens_per_forward(SERVING) == 64


def test_experts_touched_in_expectation():
    touched = sdar_counts.experts_touched(CONF, SERVING)
    assert touched == pytest.approx(128 * (1 - (1 - 8 / 128) ** 64))
    assert touched == pytest.approx(126.0, abs=0.1)
    one_row = {**SERVING, "max_batch": 1, "block_length": 1}
    assert sdar_counts.experts_touched(CONF, one_row) == pytest.approx(8.0)


def test_one_layers_expert_matmuls():
    moe = sdar_counts.moe_experts(CONF, SERVING)
    per_expert = 3 * 2048 * 768
    assert moe["bytes"] == pytest.approx(
        sdar_counts.experts_touched(CONF, SERVING)
        * (per_expert + 4 * (2 * 768 + 2048)))
    assert moe["bytes"] == pytest.approx(596.3e6, rel=2e-3)
    assert moe["flops"] == 2.0 * per_expert * 8 * 64      # 4.8 GFLOP
    assert "125.9 of 128" in moe["what"]


def test_one_forward_reads_mostly_experts():
    """About 10.2 GB a forward, of which the expert matrices are 93%: 12.5 ms
    at 819 GB/s, so 4 tokens a row cost 5 x 12.5 ms at the roofline."""
    step = sdar_counts.forward_weights(CONF, SERVING)
    experts = CONF["num_hidden_layers"] * sdar_counts.moe_experts(
        CONF, SERVING)["bytes"]
    assert step["bytes"] == pytest.approx(10.2e9, rel=0.02)
    assert experts / step["bytes"] == pytest.approx(0.93, abs=0.01)
    assert step["bytes"] / 819e9 == pytest.approx(12.5e-3, rel=0.02)
    assert step["flops"] / 197e12 < step["bytes"] / 819e9   # memory bound
    assert "one forward of 64 positions" in step["what"]
    assert not hasattr(sdar_counts, "decode_step_weights")


def test_a_measured_share_takes_the_place_of_the_expectation():
    """What a forward streams is what its tokens touch: the reader hands the
    measured share over in ``serving`` (seeded weights under greedy decoding
    touched 44% on the chip where uniform routing would touch 98%)."""
    measured = {**SERVING, "experts_touched_share": 0.44}
    assert sdar_counts.experts_touched(CONF, measured) == pytest.approx(56.32)
    moe = sdar_counts.moe_experts(CONF, measured)
    assert moe["bytes"] == pytest.approx(
        56.32 * (3 * 2048 * 768 + 4 * (2 * 768 + 2048)))
    assert moe["flops"] == sdar_counts.moe_experts(CONF, SERVING)["flops"]
    step = sdar_counts.forward_weights(CONF, measured)
    assert step["bytes"] == pytest.approx(4.9e9, rel=0.02)


def test_the_files_bytes_are_the_shapes():
    """The weights as the configuration's ``bytes`` block reckons them."""
    layer = 128 * 3 * 2048 * 768 + 2 * 2048 * 4096 + 2 * 2048 * 512
    assert layer == pytest.approx(622.85e6, rel=1e-4)
    weights = 16 * (layer + 4 * 2048 * 128) + 2 * 151936 * 2048
    assert weights == pytest.approx(10.60e9, rel=2e-3)
    pages = 641 * 64 * 16 * 2 * 4 * 128 * 2
    assert pages == pytest.approx(1.34e9, rel=5e-3)
    assert (weights + pages + 0.03e9) / 16.9e9 == pytest.approx(0.71, abs=0.01)


def test_the_module_is_for_the_harness_parent():
    import sys

    assert "jax" not in sdar_counts.__dict__
    src = Path(sdar_counts.__file__).read_text()
    assert "import jax" not in src and "jax." not in src
