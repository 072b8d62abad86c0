"""``ops/ssd.py``: the chunked form and the decode step of the Mamba-2
recurrence against the recurrence written out token by token (float64, on
the host), and the Pallas kernel in interpret mode against the ``jax.numpy``
step. Shapes keep the ratios of falcon-h1-34b: more than one group, a state
size that is not the head size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.ops import ssd

B, H, P, N, G, CHUNK = 4, 4, 16, 32, 2, 8


def _inputs(T, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {
        "x": jax.random.normal(k[0], (B, T, H, P), dtype),
        "dt": jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 1.0),
        "a": -jnp.exp(jax.random.normal(k[2], (H,))),
        "b": jax.random.normal(k[3], (B, T, G, N), dtype),
        "c": jax.random.normal(k[4], (B, T, G, N), dtype),
        "state": jax.random.normal(k[5], (B, H, P, N), jnp.float32),
        "d": jnp.linspace(0.5, 1.5, H),
    }


def _recurrence(inp, q_lens):
    """S_t = exp(dt A) S_{t-1} + dt x B^T; y_t = S_t C + D x, per token."""
    x, dt, b, c = (np.asarray(inp[k], np.float64) for k in ("x", "dt", "b", "c"))
    a, d = np.asarray(inp["a"], np.float64), np.asarray(inp["d"], np.float64)
    S = np.array(inp["state"], np.float64)
    y = np.zeros(x.shape)
    rep = H // G
    for r in range(B):
        for t in range(int(q_lens[r])):
            for h in range(H):
                g = h // rep
                S[r, h] = (np.exp(dt[r, t, h] * a[h]) * S[r, h]
                           + dt[r, t, h] * np.outer(x[r, t, h], b[r, t, g]))
                y[r, t, h] = S[r, h] @ c[r, t, g] + d[h] * x[r, t, h]
    return y, S


@pytest.mark.parametrize("q_lens", [
    pytest.param([24, 0, 8, 13], id="ragged-zero-boundary_at_end-inside"),
    pytest.param([16, 16, 16, 16], id="all_on_a_chunk_boundary"),
    pytest.param([1, 23, 7, 9], id="one_token-last_chunk_partial"),
    pytest.param([0, 0, 0, 0], id="nothing_consumed"),
])
def test_chunked_form_equals_the_token_by_token_recurrence(q_lens):
    inp = _inputs(24)
    q = jnp.asarray(q_lens, jnp.int32)
    y, s = ssd.ssd_chunked(inp["x"], inp["dt"], inp["a"], inp["b"], inp["c"],
                           inp["d"], inp["state"], q, CHUNK)
    y_ref, s_ref = _recurrence(inp, q_lens)
    for r, n in enumerate(q_lens):
        # f32 sums of up to 24 terms of size ~10: 1e-4 absolute is 1e-5 of it
        np.testing.assert_allclose(np.asarray(y[r, :n]), y_ref[r, :n],
                                   atol=2e-4, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(s[r]), s_ref[r], atol=2e-4,
                                   rtol=1e-5)
        if n == 0:      # a row that consumed nothing: bit for bit
            assert np.array_equal(np.asarray(s[r]), np.asarray(inp["state"][r]))


def test_chunked_form_pads_a_width_that_is_not_whole_chunks():
    inp = _inputs(13)
    q = jnp.asarray([13, 5, 0, 9], jnp.int32)
    y, s = ssd.ssd_chunked(inp["x"], inp["dt"], inp["a"], inp["b"], inp["c"],
                           inp["d"], inp["state"], q, CHUNK)
    y_ref, s_ref = _recurrence(inp, [13, 5, 0, 9])
    assert y.shape == (B, 13, H, P)
    np.testing.assert_allclose(np.asarray(s), s_ref, atol=2e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y[0]), y_ref[0], atol=2e-4, rtol=1e-5)


def test_state_does_not_depend_on_the_padded_width():
    """The outgoing state is the state after the q_len-th token whatever the
    chunk's padded width: 24 columns and 16 columns agree."""
    inp = _inputs(24)
    q = jnp.asarray([9, 0, 16, 3], jnp.int32)
    _, wide = ssd.ssd_chunked(inp["x"], inp["dt"], inp["a"], inp["b"],
                              inp["c"], inp["d"], inp["state"], q, CHUNK)
    _, narrow = ssd.ssd_chunked(inp["x"][:, :16], inp["dt"][:, :16], inp["a"],
                                inp["b"][:, :16], inp["c"][:, :16], inp["d"],
                                inp["state"], q, CHUNK)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(narrow), atol=1e-6)


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
def test_decode_step_equals_one_token_of_the_recurrence(kernel):
    inp = _inputs(1, seed=3, dtype=jnp.bfloat16)
    slab = jnp.stack([jnp.zeros_like(inp["state"]), inp["state"]])  # 2 layers
    slab = jnp.concatenate([slab, slab[:, :2] + 1.0], axis=1)       # 6 rows
    mask = jnp.asarray([True, False, True, True])
    y, out = ssd.ssm_state_update(
        slab, jnp.int32(1), inp["x"][:, 0], inp["dt"][:, 0], inp["a"],
        inp["b"][:, 0], inp["c"][:, 0], mask, kernel=kernel, interpret=True)
    y_ref, s_ref = _recurrence({**inp, "d": jnp.zeros(H)}, [1, 1, 1, 1])
    np.testing.assert_allclose(np.asarray(y), y_ref[:, 0], atol=1e-4, rtol=1e-5)
    for r in (0, 2, 3):
        np.testing.assert_allclose(np.asarray(out[1, r]), s_ref[r], atol=1e-5,
                                   rtol=1e-5)
    # the masked row, the other layer and the rows beyond the batch: bitwise
    assert np.array_equal(np.asarray(out[1, 1]), np.asarray(slab[1, 1]))
    assert np.array_equal(np.asarray(out[0]), np.asarray(slab[0]))
    assert np.array_equal(np.asarray(out[1, 4:]), np.asarray(slab[1, 4:]))


def test_kernel_in_interpret_mode_equals_the_jnp_step():
    inp = _inputs(1, seed=5, dtype=jnp.bfloat16)
    slab = jnp.stack([inp["state"], inp["state"] * 0.5])
    mask = jnp.asarray([True, True, False, True])
    args = (slab, jnp.int32(0), inp["x"][:, 0], inp["dt"][:, 0], inp["a"],
            inp["b"][:, 0], inp["c"][:, 0], mask)
    y_j, s_j = ssd.ssm_state_update(*args, kernel=False)
    y_k, s_k = ssd.ssm_state_update(*args, kernel=True, interpret=True)
    # the same f32 operations, summed in another order
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_j), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_j), atol=1e-6)


def _step_args(rows, heads, p, n, groups, seed):
    """A decode step's operands at any shape: layer 1 of a slab of two layers
    and one row more than the batch, activations in bfloat16 as served."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    slab = jax.random.normal(k[0], (2, rows + 1, heads, p, n))
    return (slab, jnp.int32(1),
            jax.random.normal(k[1], (rows, heads, p), jnp.bfloat16),
            jax.nn.softplus(jax.random.normal(k[2], (rows, heads)) - 1.0),
            -jnp.exp(jax.random.normal(k[3], (heads,))),
            jax.random.normal(k[4], (rows, groups, n), jnp.bfloat16),
            jax.random.normal(k[5], (rows, groups, n), jnp.bfloat16))


def _assert_kernel_equals_the_jnp_step(args, mask):
    """y and state to this file's tolerances; the masked rows, the other
    layer and the rows past the batch bit for bit."""
    slab, layer, rows = args[0], int(args[1]), len(mask)
    mask = jnp.asarray(mask)
    y_j, s_j = ssd.ssm_state_update(*args, mask, kernel=False)
    y_k, s_k = ssd.ssm_state_update(*args, mask, kernel=True, interpret=True)
    scale = float(jnp.max(jnp.abs(y_j)))
    # the same f32 products, summed in another order (on the MXU since PR 46)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_j),
                               atol=2e-5 * max(1.0, scale / 8))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_j), atol=1e-6)
    for row in range(slab.shape[1]):
        if row >= rows or not bool(mask[row]):
            assert np.array_equal(np.asarray(s_k[layer, row]),
                                  np.asarray(slab[layer, row]))
        else:
            assert not np.array_equal(np.asarray(s_k[layer, row]),
                                      np.asarray(slab[layer, row]))
    assert np.array_equal(np.asarray(s_k[:layer]), np.asarray(slab[:layer]))
    return y_k


#: P, N, H, G of the three configurations the kernel serves, and the heads a
#: program takes there; the rows alone are scaled down
SERVED = {
    "granite: 128 heads of [64, 128], one group, 32 a program":
        ((64, 128, 128, 1), 32),
    "nemotron: 8 groups of 16 heads of [64, 128], two groups a program":
        ((64, 128, 128, 8), 32),
    "falcon-h1: 2 groups of 16 heads of [128, 256], 8 a program":
        ((128, 256, 32, 2), 8),
}


@pytest.mark.parametrize("shape", list(SERVED))
def test_kernel_at_the_served_shapes_equals_the_jnp_step(shape):
    """The three shapes differ in every number the read-out depends on: vregs
    a head 8 / 8 / 32, heads a program 32 / 32 / 8, groups 1 / 8 / 2."""
    (p, n, heads, groups), block = SERVED[shape]
    assert ssd._head_block(heads, groups, 4 * p * n) == block
    _assert_kernel_equals_the_jnp_step(
        _step_args(3, heads, p, n, groups, seed=11), [True, False, True])


def test_kernel_at_a_block_of_one_head(monkeypatch):
    p, n, heads, groups = 64, 128, 4, 2
    monkeypatch.setattr(ssd, "_STATE_BLOCK_BYTES", 4 * p * n + 4)
    assert ssd._STATE_BLOCK_BYTES // (4 * p * n) == 1
    assert ssd._head_block(heads, groups, 4 * p * n) == 1
    _assert_kernel_equals_the_jnp_step(
        _step_args(2, heads, p, n, groups, seed=12), [False, True])


def test_adjacent_groups_of_a_block_read_their_own_b_and_c(monkeypatch):
    """A program of several whole groups (nemotron's two): head ``j`` reads
    row ``j // heads_a_group`` of the block's B and C. With group 1's B and C
    moved and group 0's kept, exactly the heads of group 1 move, in both
    programs of a row of four groups."""
    p, n, heads, groups = 8, 128, 16, 4
    monkeypatch.setattr(ssd, "_STATE_BLOCK_BYTES", 8 * 4 * p * n)
    assert ssd._head_block(heads, groups, 4 * p * n) == 8       # two groups
    args = _step_args(2, heads, p, n, groups, seed=13)
    y = _assert_kernel_equals_the_jnp_step(args, [True, True])
    b, c = args[5], args[6]
    moved = (*args[:5], b.at[:, 1].set(b[:, 2]), c.at[:, 1].set(-c[:, 1]))
    y_moved = _assert_kernel_equals_the_jnp_step(moved, [True, True])
    differs = np.any(np.asarray(y_moved != y), axis=(0, 2))     # by head
    assert differs.tolist() == [False] * 4 + [True] * 4 + [False] * 8


def test_the_read_out_on_the_mxu_is_an_f32_sum():
    """``_row_sums``: three bfloat16 pieces of each product against a block
    of ones, f32 accumulation: every lane the row's sum, to a rounding of
    f32 where one bfloat16 pass reads a hundredth; a sum that cancels keeps
    its small terms."""
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 256)) * 7.0
    x = x.at[0].set(jnp.where(jnp.arange(256) % 2 == 0, 1e4, -1e4))
    x = x.at[0, 5].add(0.375)
    got = np.asarray(ssd._row_sums(x, 128))
    want = np.sum(np.asarray(x, np.float64), axis=1)
    assert got.shape == (16, 128) and np.all(got == got[:, :1])
    np.testing.assert_allclose(got[:, 0], want, atol=3e-5, rtol=0)
    one_pass = np.asarray(jnp.sum(x.astype(jnp.bfloat16).astype(jnp.float32),
                                  axis=1))
    assert np.max(np.abs(one_pass - want)) > 1e-2


def test_conv_over_a_chunk_equals_the_conv_token_by_token():
    K, C, T = 4, 12, 10
    k = jax.random.split(jax.random.PRNGKey(9), 4)
    u = jax.random.normal(k[0], (B, T, C))
    tail0 = jax.random.normal(k[1], (B, K - 1, C))
    w, bias = jax.random.normal(k[2], (K, C)), jax.random.normal(k[3], (C,))
    q = jnp.asarray([10, 0, 2, 7], jnp.int32)
    out, tail = ssd.causal_conv(u, tail0, w, bias, q)
    step_tail = tail0
    for t in range(T):
        o, nxt = ssd.causal_conv_step(u[:, t], step_tail, w, bias)
        live = np.asarray(t < q)
        np.testing.assert_allclose(np.asarray(out[live, t]),
                                   np.asarray(o[live]), atol=1e-5)
        step_tail = jnp.where(jnp.asarray(live)[:, None, None], nxt, step_tail)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(step_tail),
                               atol=1e-6)
    assert np.array_equal(np.asarray(tail[1]), np.asarray(tail0[1]))
