"""``models/falcon_h1.py`` against the plain reference
(``benchmark/falcon_h1_reference.py``: float32, ``highest``, no cache, the
recurrence one token at a time) at ``tiny-falcon-h1`` on seeded weights:
prefill in chunks and then decode, through pages and state, compared on
logits.

The number compared is the judge's: per logits row rms(program - reference) /
std(reference). The program rounds activations, pages and the mixer's x, B, C
to bfloat16 through 2 blocks; the reference is float32. On int8-grid weights
(the same tree for both, every branch at the residual's scale:
``falcon_h1_weights.py``) the rows read 0.005-0.012 over seeds and lengths up
to 140 tokens (a row of 64 channels averages less rounding away than one of
5120: the chip reads less, PERF.md section 2); the tolerance is 0.02, and
computing one precision lower reads 0.08-0.12 (float8 activations) and
0.32-0.41 (int4-grid weights): both far over it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import falcon_h1_reference, falcon_h1_weights
from cyberfabric_core_tpu.models import falcon_h1, get_config
from cyberfabric_core_tpu.runtime.quant import (dequantize_weight,
                                                init_params_quantized,
                                                quantize_llama_params)
from falcon_h1_helpers import PagedRun, published

TOLERANCE = 0.02
CFG = get_config("tiny-falcon-h1")
LENS, STEPS = [37, 20, 5], 3


def _worst(got, reference, weights, seqs, lower=None):
    worst = 0.0
    for r in range(len(seqs)):
        at = sorted(p for (rr, p) in got if rr == r)
        ref = np.asarray(reference(weights, jnp.asarray(seqs[r]),
                                   jnp.asarray(at, jnp.int32), lower=lower))
        for p, row in zip(at, ref):
            d = got[(r, p)] - row
            worst = max(worst, float(np.sqrt((d * d).mean()) / row.std()))
    return worst


def _seqs(seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, CFG.vocab_size, n + STEPS + 1).astype(np.int32)
            for n in LENS]


@pytest.mark.parametrize("seed", [1, 2147484001])
def test_int8_program_equals_the_reference_on_int8_grid_weights(seed):
    """Chunks of 16 (two mamba chunks of 8 each), a prompt that ends inside a
    chunk, one that ends on a boundary (row 1 at 20: no), a short one; then
    decode through the ``ssm_state_update`` path."""
    weights = falcon_h1_weights.make_weights(published(CFG), seed,
                                             CFG.num_layers)
    got = PagedRun(CFG, weights, rows=3).run(_seqs(seed), LENS, STEPS)
    reference = falcon_h1_reference.reference_logits(published(CFG),
                                                     CFG.num_layers)
    assert len(got) == 3 * (STEPS + 1)
    assert _worst(got, reference, weights, _seqs(seed)) < TOLERANCE


def test_a_lower_precision_reads_over_the_tolerance():
    """The tolerance tells precisions apart: the reference itself at float8
    activations, compared with the reference, is over it."""
    weights = falcon_h1_weights.make_weights(published(CFG), 3, CFG.num_layers)
    reference = falcon_h1_reference.reference_logits(published(CFG),
                                                     CFG.num_layers)
    seqs = _seqs(3)
    exact = {(r, p): np.asarray(reference(
        weights, jnp.asarray(seqs[r]), jnp.asarray([p], jnp.int32)))[0]
        for r in range(3) for p in (LENS[r] - 1, LENS[r])}
    assert _worst(exact, reference, weights, seqs, lower="fp8") > TOLERANCE
    assert _worst(exact, reference, weights, seqs, lower="state_bf16") \
        < TOLERANCE / 10


def test_bf16_program_equals_the_reference():
    """The unquantised tree (``init_params``, bfloat16) against the reference
    given the same values: each matrix as an int8-container-free float
    leaf is not what the reference reads, so it is handed the tree quantised
    and the program the SAME quantised tree dequantised to bfloat16: the
    extra rounding of the weights to bfloat16 (2^-9 relative) stays inside
    the tolerance."""
    tree = quantize_llama_params(
        falcon_h1.init_params(CFG, jax.random.PRNGKey(5)), bits=8)
    layers = {k: (dequantize_weight(v) if isinstance(v, dict) else v)
              for k, v in tree["layers"].items()}
    plain = {**tree, "layers": layers,
             "lm_head": dequantize_weight(tree["lm_head"])}
    got = PagedRun(CFG, plain, rows=3).run(_seqs(5), LENS, STEPS)
    reference = falcon_h1_reference.reference_logits(published(CFG),
                                                     CFG.num_layers)
    assert _worst(got, reference, tree, _seqs(5)) < TOLERANCE


def test_one_block_equals_the_reference():
    cfg = dataclasses.replace(CFG, num_layers=1)
    weights = falcon_h1_weights.make_weights(published(cfg), 11, 1)
    got = PagedRun(cfg, weights, rows=3).run(_seqs(11), LENS, STEPS)
    reference = falcon_h1_reference.reference_logits(published(cfg), 1)
    assert _worst(got, reference, weights, _seqs(11)) < TOLERANCE


def test_idle_and_masked_rows_keep_their_state_bit_for_bit():
    """A mixed row with q_len 0, a mixed row whose write_mask is False and a
    decode row whose write_mask is False come back unchanged: state, conv
    tail, and every row beyond the batch."""
    weights = falcon_h1_weights.make_weights(published(CFG), 2, CFG.num_layers)
    run = PagedRun(CFG, weights, rows=3)
    seqs = _seqs(2)
    ids = np.stack([np.resize(s, 16) for s in seqs])
    run.mixed_step(ids, [0, 0, 0], [16, 16, 5])          # every row holds something
    before = jax.tree.map(np.asarray, run.state)
    run.mixed_step(ids, [16, 16, 5], [16, 0, 16],
              write_mask=jnp.asarray([True, True, False]))
    run.decode(ids[:, :1], [32, 16, 5],
               write_mask=jnp.asarray([True, False, False]))
    after = jax.tree.map(np.asarray, run.state)
    for leaf in ("ssm", "conv"):
        assert not np.array_equal(after[leaf][:, 0], before[leaf][:, 0])
        for row in (1, 2, 3):
            assert np.array_equal(after[leaf][:, row], before[leaf][:, row]), \
                (leaf, row)


def test_a_row_with_no_history_starts_from_the_zero_state():
    """Admission clears nothing: a mixed row whose history is 0 ignores what
    its slab row held."""
    weights = falcon_h1_weights.make_weights(published(CFG), 4, CFG.num_layers)
    seqs = _seqs(4)
    ids = np.stack([np.resize(s, 16) for s in seqs])
    clean = PagedRun(CFG, weights, rows=3)
    first = clean.mixed_step(ids, [0, 0, 0], [16, 16, 5])
    dirty = PagedRun(CFG, weights, rows=3)
    dirty.state = jax.tree.map(lambda x: x + 3.0, dirty.state)
    again = dirty.mixed_step(ids, [0, 0, 0], [16, 16, 5])
    assert np.array_equal(first, again)


def test_quantised_init_and_param_count():
    params = init_params_quantized(CFG, jax.random.PRNGKey(0))
    layers = params["layers"]
    assert layers["ssm_in"]["q"].shape == (2, 64, CFG.ssm_proj_dim)
    assert layers["ssm_in"]["q"].dtype == jnp.int8
    assert layers["ssm_out"]["q"].shape == (2, CFG.ssm_inner, 64)
    for small in ("conv_w", "conv_b", "A_log", "D", "dt_bias", "ssm_norm"):
        assert layers[small].dtype == jnp.float32, small
    decay = np.exp(-np.log1p(np.exp(np.asarray(layers["dt_bias"])))
                   * np.exp(np.asarray(layers["A_log"])))
    assert 0.15 < decay.min() and decay.max() < 0.9995   # neither 0 nor 1
    leaves = jax.tree.leaves(falcon_h1.init_params(CFG, jax.random.PRNGKey(0)))
    assert sum(x.size for x in leaves) == CFG.param_count()
    big = get_config("falcon-h1-34b")
    assert 33.0e9 < big.param_count() < 34.5e9
    assert get_config("falcon-h1-34b-16l") == dataclasses.replace(
        big, name="falcon-h1-34b-16l", num_layers=16)


def test_feasibility_gate_counts_the_state_slab_beside_the_pool():
    """The benchmark cell's plan fits a v5e with its 32 state rows; the same
    plan at 128 rows does not, and the refusal names the state's bytes."""
    from cyberfabric_core_tpu.parallel.feasibility import (
        V5E_HBM_BYTES, InfeasiblePlanError, gate_engine_plan)

    cfg = get_config("falcon-h1-34b-16l")
    plan = dict(quantization="int8", max_batch=16, max_seq_len=2048,
                page_size=64, num_pages=641, hbm_bytes=V5E_HBM_BYTES)
    fits = gate_engine_plan(cfg, 1, state_rows=32, **plan)
    assert fits["state_bytes_per_device"] == 32 * cfg.state_bytes_per_row()
    assert 2.1e9 < fits["state_bytes_per_device"] < 2.2e9
    assert 9.5e9 < fits["param_bytes_per_device"] < 9.7e9
    assert fits["fits"] and 12.9e9 < fits["total_bytes_per_device"] < 13.4e9
    with pytest.raises(InfeasiblePlanError, match="state 8715"):
        gate_engine_plan(cfg, 1, state_rows=128, **plan)
    # a model without state pays nothing
    assert gate_engine_plan("mistral-7b", 1, **plan)["state_bytes_per_device"] == 0
