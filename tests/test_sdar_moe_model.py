"""``tiny-sdar`` (models/sdar_moe.py) against the benchmark's plain reference
(benchmark/sdar_reference.py, which imports nothing from the program): the
logits of prefill, denoise and commit forwards through the pages; the block
mask of both paged kernels against a dense mask; both unmasking rules against
the reference's loop; the checkpoint's tensor names."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import sdar_reference, sdar_weights
from benchmark.adapters import sdar as adapter
from cyberfabric_core_tpu.models import get_config, sdar_moe
from cyberfabric_core_tpu.ops.paged_attention import (
    paged_block_attention, paged_gather_dense,
    ragged_paged_attention)
from cyberfabric_core_tpu.ops.sampling import block_unmask

CONF = json.loads((Path(__file__).resolve().parents[1] / "benchmark/tests"
                   / "rehearsal/configs/tiny-sdar.json").read_text())
W, MASK, PAGE, CHUNK = 4, 511, 16, 64


@pytest.fixture(scope="module")
def judged():
    w = adapter.make_weights(CONF, 7, 2)
    return w, adapter.reference_logits(CONF, 2)


def _rms(got, want):
    return float(np.sqrt(((got - want) ** 2).mean()) / want.std())


@pytest.mark.parametrize("leftover", [0, 1, 2, 3])
def test_paged_forwards_match_the_reference(judged, leftover):
    """The judge's scenario in small: row 0 fresh, its prompt in two chunks
    and ``leftover`` tokens past its last whole block; row 1 resumed from row
    0's first page; row 2 a short prompt, then a decode rider; row 3 idle.
    Then decode steps that cross two block boundaries, so later rows read K/V
    a commit forward wrote over a denoise forward's. Every logits row
    against a whole forward of the reference."""
    w, reference = judged
    binding = adapter.bind(CONF, 2, 4)
    rng = np.random.default_rng(leftover)
    lens = [CHUNK + 16 + leftover, PAGE + 9, 6, 0]
    seqs = [rng.integers(3, 500, n + 12).astype(np.int32) for n in lens]
    seqs[1][:PAGE] = seqs[0][:PAGE]
    state = binding.share_prefix(binding.new_state(), 1, 0, PAGE)
    done = np.array([0, PAGE, 0, 0], np.int32)
    got = {}
    for call in range(3):
        q = np.zeros(4, np.int32)
        for r in range(3):
            if r == 1 and call == 0:
                continue
            left = lens[r] - done[r]
            q[r] = min(left, CHUNK) if left > 0 else (r == 2 and call < 3)
        ids = np.zeros((4, CHUNK), np.int32)
        for r in range(4):
            ids[r, : q[r]] = seqs[r][done[r]: done[r] + q[r]]
        idle_before = binding.row_state(state, 3)
        last, state = binding.mixed(w, ids, state, done, q)
        assert np.array_equal(idle_before, binding.row_state(state, 3))
        logits = binding.logits(w, last)
        for r in range(3):
            done[r] += q[r]
            if q[r] and done[r] >= lens[r]:
                got[(r, int(done[r]) - 1)] = logits[r]
    for _ in range(9):
        ids = np.asarray([[seqs[r][done[r]]] for r in range(4)], np.int32)
        last, state = binding.decode(w, ids, state, done)
        logits = binding.logits(w, last)
        for r in range(4):
            got[(r, int(done[r]))] = logits[r]
            done[r] += 1
    assert len(got) >= 36
    for r in range(4):
        at = sorted(p for rr, p in got if rr == r)
        want = reference(w, seqs[r], np.asarray(at))
        for p, row in zip(at, want):
            assert _rms(got[(r, p)], row) < 0.02, (r, p)


def _dense_attention(q, k, v, visible):
    """q [T, Hq, D], k/v [S, Hkv, D], visible [T, S] bool."""
    group = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, group, 1), np.repeat(v, group, 1)
    s = np.einsum("thd,shd->hts", q, k) / q.shape[-1] ** 0.5
    s = np.where(visible[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hts,shd->thd", p, v)


def _pool(rng, pages=12, page=8, hkv=2, d=16):
    k = rng.standard_normal((1, pages, page, hkv * d)).astype(np.float32)
    v = rng.standard_normal((1, pages, page, hkv * d)).astype(np.float32)
    return jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("block", [1, 4, 8])
def test_ragged_kernel_block_mask_against_a_dense_mask(block):
    """The lane's kernel: the query at ``pos`` sees keys up to the end of
    its block, ``pos | (block - 1)``; 1 is the causal kernel."""
    rng = np.random.default_rng(block)
    k_pool, v_pool = _pool(rng)
    table = jnp.asarray([[3, 5, 7, 9], [2, 4, 6, 8]], jnp.int32)
    hist, qlens = np.array([8, 16]), np.array([16, 8])
    q = rng.standard_normal((2, 16, 4, 16)).astype(np.float32)
    out = np.asarray(ragged_paged_attention(
        jnp.asarray(q), k_pool, v_pool, table, jnp.asarray(hist),
        jnp.asarray(qlens), 0, interpret=True, block=block))
    kd, vd = (np.asarray(a) for a in paged_gather_dense(k_pool, v_pool, table,
                                                        16))
    for r in range(2):
        pos = hist[r] + np.arange(qlens[r])
        visible = np.arange(kd.shape[1])[None, :] <= (pos | (block - 1))[:, None]
        want = _dense_attention(q[r, : qlens[r]], kd[r], vd[r], visible)
        np.testing.assert_allclose(out[r, : qlens[r]], want, rtol=2e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("block,trip", [(1, 1), (4, 1), (8, 1), (4, 2),
                                        (4, 4), (8, 4)])
def test_decode_kernel_block_fold_against_a_dense_mask(block, trip):
    """The open block's kernel: ``block`` queries a row, each seeing the
    kept keys and the whole block; 1 is the decode kernel as it was. The
    folded rows take a row's pages ``trip`` at a time like any others."""
    rng = np.random.default_rng(10 + block)
    k_pool, v_pool = _pool(rng)
    table = jnp.asarray([[3, 5, 7, 9], [2, 4, 6, 8]], jnp.int32)
    kept = np.array([8, 16])
    q = rng.standard_normal((2, block, 4, 16)).astype(np.float32)
    out = np.asarray(paged_block_attention(
        jnp.asarray(q), k_pool, v_pool, table, jnp.asarray(kept + block),
        0, interpret=True, trip=trip))
    kd, vd = (np.asarray(a) for a in paged_gather_dense(k_pool, v_pool, table,
                                                        16))
    for r in range(2):
        visible = np.broadcast_to(
            np.arange(kd.shape[1])[None, :] < kept[r] + block,
            (block, kd.shape[1]))
        want = _dense_attention(q[r], kd[r], vd[r], visible)
        np.testing.assert_allclose(out[r], want, rtol=2e-5, atol=2e-5)


def _unmask_case(name):
    """(block, logits) for one open block of 4 over a vocabulary of 8."""
    lg = np.full((4, 8), -4.0, np.float32)
    if name == "threshold":      # positions 1 and 3 over 0.9, 0 and 2 under
        lg[0, 1], lg[1, 2], lg[2, 3], lg[3, 4] = -2.0, 6.0, -3.0, 5.5
        return [MASK_T] * 4, lg
    if name == "at_least_n":     # nothing over the threshold: the best one
        lg[0, 1], lg[1, 2], lg[2, 3], lg[3, 4] = -3.5, -3.0, -2.0, -3.2
        return [MASK_T] * 4, lg
    if name == "ties":           # equal confidences: the earlier position
        lg[:, 5] = 1.0
        return [MASK_T, 2, MASK_T, MASK_T], lg
    lg[0, 1], lg[2, 6] = 3.0, 9.0    # "decided": position 1 keeps its token
    return [MASK_T, 5, MASK_T, 4], lg


MASK_T = 7


@pytest.mark.parametrize("dynamic", [False, True], ids=["static", "dynamic"])
@pytest.mark.parametrize("per_step", [1, 2])
@pytest.mark.parametrize("case", ["threshold", "at_least_n", "ties",
                                  "decided"])
def test_unmasking_rules_against_the_reference_loop(case, per_step, dynamic):
    block, lg = _unmask_case(case)
    got = np.asarray(block_unmask(
        jnp.asarray([block], jnp.int32), jnp.asarray(lg[None]),
        jax.random.split(jax.random.PRNGKey(0), 1), jnp.zeros(1),
        jnp.ones(1), jnp.zeros(1, jnp.int32), mask_id=MASK_T,
        per_step=per_step, dynamic=dynamic, threshold=0.9))[0]
    masked_out = lg.copy()
    masked_out[:, MASK_T] = -np.inf
    p = np.exp(masked_out - masked_out.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    x0 = masked_out.argmax(-1).tolist()
    want = sdar_reference.unmask_step(
        block, x0, [float(p[i, x0[i]]) for i in range(4)], MASK_T, per_step,
        dynamic, 0.9)
    assert got.tolist() == want
    assert (got == MASK_T).sum() <= max(block.count(MASK_T) - per_step, 0)


def test_checkpoint_names_of_sdar_moe(tmp_path):
    """A synthetic safetensors file under the published tensor names
    (``mlp.gate``, ``mlp.experts.{e}.gate_proj|up_proj|down_proj``,
    ``self_attn.q_norm|k_norm``) loads into the tree the model runs, the
    router in float32, and the round trip is exact."""
    from safetensors import safe_open

    from cyberfabric_core_tpu.runtime.weights import (load_llama_params,
                                                      save_llama_params)

    cfg = get_config("tiny-sdar")
    params = sdar_moe.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    path = save_llama_params(params, cfg, tmp_path)
    with safe_open(str(path), framework="numpy") as f:
        names = set(f.keys())
    for name in ("model.layers.1.mlp.gate.weight",
                 "model.layers.0.mlp.experts.7.gate_proj.weight",
                 "model.layers.0.mlp.experts.0.up_proj.weight",
                 "model.layers.1.mlp.experts.3.down_proj.weight",
                 "model.layers.0.self_attn.q_norm.weight",
                 "model.layers.1.self_attn.k_norm.weight"):
        assert name in names, name
    assert not any("block_sparse_moe" in n for n in names)
    loaded = load_llama_params(tmp_path, cfg, dtype=jnp.bfloat16)
    assert loaded["layers"]["router"].dtype == jnp.float32
    assert loaded["layers"]["moe_gate"].shape == (2, 8, 64, 32)
    again = load_llama_params(tmp_path, cfg, dtype=jnp.float32)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
