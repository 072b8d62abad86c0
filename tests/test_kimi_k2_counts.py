"""``benchmark/kimi_k2_counts.py``: the bytes and operations of one chip's
share of Kimi-K2.5, against the arithmetic of ISSUE 33 and of the
configuration's file, and (h) against bytes counted from a BUILT tiny model."""

import json
from pathlib import Path

import jax
import pytest

from benchmark import kimi_k2_counts as counts
from benchmark import kimi_k2_readers

ROOT = Path(__file__).resolve().parents[1]
CONF = json.loads((ROOT / "benchmark/configs/kimi-k2.5-int8.json").read_text())
TINY = json.loads((ROOT / "benchmark/tests/rehearsal/configs/tiny-kimi.json")
                  .read_text())
SERVING = CONF["serving"]


def test_a_latent_row_and_a_tokens_cache():
    assert counts.latent_row(CONF) == 576
    assert counts.cache_bytes_per_token(CONF) == 17280          # 17.28 KB
    # 64 heads of K (128 + 64) and V (128) in bf16 would be 614 KB, 36 times
    # that (ISSUE 33's 491 KB and 28 times count K at 128, without its 64
    # rotary numbers)
    assert 15 * 64 * (192 + 128) * 2 / 17280 == pytest.approx(35.6, abs=0.1)
    assert 15 * 64 * (128 + 128) * 2 / 17280 == pytest.approx(28.4, abs=0.1)


def test_the_files_bytes_are_the_shapes():
    attn, attn_scales = counts.attention_params(CONF)
    assert attn == 7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384 \
        + 8192 * 7168 == 101_122_048                            # 101.12 M
    expert, _ = counts.expert_params(CONF)
    assert expert == 3 * 7168 * 2048                            # 44.04 M
    router = 7168 * 384 * 4
    layer = 12 * expert + attn + expert + router
    assert layer == pytest.approx(684.65e6, rel=1e-4)
    dense = attn + 3 * 7168 * 18432
    assert dense == pytest.approx(497.48e6, rel=1e-4)
    weights = 14 * layer + dense + 2 * 20480 * 7168
    assert weights == pytest.approx(10.38e9, rel=1e-3)
    numbers = 3072 * 64 * 17280
    assert numbers == pytest.approx(3.40e9, rel=2e-3)
    stored = 3073 * 64 * 15 * 640 * 2
    assert stored == pytest.approx(3.78e9, rel=2e-3)
    assert (weights + stored + 0.02e9) / 16.9e9 == pytest.approx(0.84, abs=0.01)


def test_nothing_is_counted_without_what_was_measured():
    for role in ("mla_decode_attention", "routed_experts", "step_weights",
                 "latent_moe_step"):
        assert getattr(counts, role)(CONF, SERVING) is None
    assert not hasattr(counts, "decode_step_weights")


def test_one_call_of_the_latent_kernel():
    """64 rows at about 1.65k tokens: 27 of a row's 48 pages, so a share of
    0.56 of the table; a row's last page counts half."""
    measured = {**SERVING, "attn_pages_walked_share": 0.5625}
    attn = counts.mla_decode_attention(CONF, measured)
    tokens = (0.5625 * 64 * 48 - 32) * 64
    assert attn["bytes"] == tokens * 576 * 2
    assert attn["bytes"] == pytest.approx(125e6, rel=0.01)      # ~120 MB
    assert attn["flops"] == 64 * tokens * 2 * (576 + 512)
    # memory bound: 0.15 ms of bytes against 0.08 ms of FLOPs
    assert attn["bytes"] / 819e9 > attn["flops"] / 197e12


def test_one_expert_layer_and_a_whole_step():
    measured = {**SERVING, "experts_touched_share": 0.75,
                "assignments_local_share": 12 / 384,
                "attn_pages_walked_share": 0.5625}
    moe = counts.routed_experts(CONF, measured)
    assert moe["bytes"] == 9 * (3 * 7168 * 2048 + 4 * (2 * 2048 + 7168))
    assert moe["flops"] == 2.0 * 3 * 7168 * 2048 * 16            # 16 local
    step = counts.step_weights(CONF, measured)
    # dense 0.50 + 14 x (attention 0.10 + shared 0.044 + router 0.011
    # + 9 experts 0.396) + head 0.147 GB
    assert step["bytes"] == pytest.approx(8.38e9, rel=0.01)
    whole = counts.latent_moe_step(CONF, measured)
    attn = counts.mla_decode_attention(CONF, measured)
    assert whole["bytes"] == step["bytes"] + 15 * attn["bytes"]
    assert whole["bytes"] / 819e9 == pytest.approx(12.5e-3, rel=0.03)


def test_the_reader_hands_the_measured_values_over():
    ctx = {"config": CONF, "peaks": {"hbm_bytes_per_s": 819e9,
                                     "bf16_flops_per_s": 197e12},
           "values": {"decode_step_ms": 25.0, "attn_pages_walked_share": 0.5625,
                      "moe_decode_experts_touched_share": 0.75,
                      "moe_assignments_local_share": 0.03125,
                      "mla_decode_attention_us": 600.0}}
    spec = json.loads((ROOT / "benchmark/layer_metrics/"
                       "latent_moe_step_roofline.json").read_text())
    spec.pop("kind"), spec.pop("what")
    assert kimi_k2_readers.roofline_measured(ctx, **spec) == pytest.approx(
        100 * 12.5 / 25.0, rel=0.03)
    spec = json.loads((ROOT / "benchmark/layer_metrics/"
                       "mla_decode_attention_roofline.json").read_text())
    spec.pop("kind"), spec.pop("what")
    assert kimi_k2_readers.roofline_measured(ctx, **spec) == pytest.approx(
        100 * 125e6 / 819e9 / 600e-6, rel=0.02)
    # a program without the counters (the parent): nothing, and no raise
    bare = {**ctx, "values": {"decode_step_ms": 25.0}}
    assert kimi_k2_readers.roofline_measured(bare, **spec) is None
    llama = json.loads((ROOT / "benchmark/configs/mistral-7b-int8.json")
                       .read_text())
    assert kimi_k2_readers.roofline_measured({**ctx, "config": llama},
                                             **spec) is None


def test_counts_against_a_built_tiny_model():
    """(h) The bytes counted from shapes are the bytes of a BUILT tree: one
    chip's share of tiny-kimi in int8, leaf by leaf."""
    from cyberfabric_core_tpu.models import get_config
    from cyberfabric_core_tpu.runtime.quant import init_params_quantized

    cfg = get_config("tiny-kimi-share4")
    tree = init_params_quantized(cfg, jax.random.PRNGKey(0))

    def nbytes(node):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(node))

    def matrices(stack, names):
        return sum(nbytes(stack[n]) for n in names)

    attn_w, attn_s = counts.attention_params(TINY)
    names = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
    assert matrices(tree["dense"], names) == attn_w + 4 * attn_s
    assert matrices(tree["layers"], names) == 2 * (attn_w + 4 * attn_s)
    exp_w, exp_s = counts.expert_params(TINY)
    assert matrices(tree["layers"], ("moe_gate", "moe_up", "moe_down")) == \
        2 * 4 * (exp_w + 4 * exp_s)                 # 2 layers x 4 held
    # every held expert touched, every matrix: the step's weights are the
    # tree's, less the embedding (a gather) and the norms
    measured = {**TINY["serving"], "experts_touched_share": 1.0,
                "assignments_local_share": 0.25}
    step = counts.step_weights(TINY, measured)
    norms = sum(nbytes(tree[s][n]) for s in ("dense", "layers")
                for n in ("attn_norm", "q_a_norm", "kv_a_norm", "mlp_norm"))
    assert step["bytes"] == nbytes(tree) - nbytes(tree["embed"]) - norms \
        - nbytes(tree["final_norm"])
    assert cfg.cache_bytes_per_token() == 3 * 128 * 2    # stored: lane tiles
    assert counts.cache_bytes_per_token(TINY) == 3 * 48 * 2     # numbers


def test_the_modules_are_for_the_harness_parent():
    for module in (counts, kimi_k2_readers):
        src = Path(module.__file__).read_text()
        assert "import jax" not in src and "jax." not in src
