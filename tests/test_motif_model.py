"""``tiny-motif`` (models/motif.py) against the benchmark's plain reference
(benchmark/motif_reference.py: imports nothing from the program, does NOT
absorb attention, has no cache and masks the window): prefill in chunks and
decode through BOTH page groups with window pages freed and written again on
the way; absorbed against expanded attention on the same weights; ``lam = 0``
is plain grouped latent attention; Sinkhorn's output is doubly stochastic;
PolyNorm by hand; the share test."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import motif_reference as reference
from benchmark import motif_weights
from benchmark.adapters import motif as adapter
from cyberfabric_core_tpu.models import get_config, motif
from cyberfabric_core_tpu.models.llama import (moe_experts, moe_route,
                                               poly_norm)
from cyberfabric_core_tpu.ops import rope

CONF = json.loads((Path(__file__).resolve().parents[1] / "benchmark/tests"
                   / "rehearsal/configs/tiny-motif.json").read_text())
PAGE, CHUNK = 16, 64
#: dense, dense, a window layer, a unit (full, window x 3), a full layer:
#: every part of ``motif.layer_plan``
DEPTH = 8
LIMIT = CONF["correctness"]["limit"]


def _rms(got, want):
    return float(np.sqrt(((got - want) ** 2).mean()) / want.std())


def _scenario(w, seed=0, steps=6):
    """The judge's scenario in small: row 0 fresh, its prompt of 149 tokens
    in three chunks (six windows long); row 1 shares row 0's first three
    pages of tokens (no match: it prefills them itself); row 2 a short prompt, then a decode rider; row 3 idle in
    every mixed call. Then decode steps through both page groups. Returns
    {(row, position): logits}, the sequences, the binding's last state."""
    binding = adapter.bind(CONF, DEPTH, 4)
    rng = np.random.default_rng(seed)
    lens = [2 * CHUNK + 21, CHUNK + 9, 6, 0]
    shared = 3 * PAGE
    seqs = [rng.integers(3, 256, n + steps + 6).astype(np.int32)
            for n in lens]
    seqs[1][:shared] = seqs[0][:shared]
    state = binding.share_prefix(binding.new_state(), 1, 0, shared)
    done = np.array([0, shared, 0, 0], np.int32)
    got = {}
    for call in range(4):
        q = np.zeros(4, np.int32)
        for r in range(3):
            if r == 1 and call == 0:
                continue
            left = lens[r] - done[r]
            q[r] = min(left, CHUNK) if left > 0 else (r == 2)
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
    for _ in range(steps):
        ids = np.asarray([[seqs[r][done[r]]] for r in range(4)], np.int32)
        last, state = binding.decode(w, ids, state, done)
        logits = binding.logits(w, last)
        for r in range(4):
            got[(r, int(done[r]))] = logits[r]
            done[r] += 1
    return got, [s[: done[r]] for r, s in enumerate(seqs)], state


def _worst(got, seqs, w, ref):
    worst = 0.0
    for r in range(4):
        at = sorted(p for rr, p in got if rr == r)
        want = ref(w, seqs[r], np.asarray(at))
        worst = max([worst] + [_rms(got[(r, p)], row)
                               for p, row in zip(at, want)])
    return worst


@pytest.fixture(scope="module")
def judged():
    w = adapter.make_weights(CONF, 7, DEPTH)
    got, seqs, state = _scenario(w)
    return w, got, seqs, state


def test_chunked_prefill_and_decode_through_both_page_groups(judged):
    """Every logits row of the scenario against a whole forward of the
    reference, with window pages given back and handed out AGAIN on the way
    (the binding's pages are the program's own pool's, its window group
    kept short)."""
    w, got, seqs, state = judged
    assert len(got) >= 3 + 4 * 6
    assert state["reused"] >= 3                  # freed pages, written again
    assert not any(state["wchains"][0][:6])      # row 0 gave its first back
    assert _worst(got, seqs, w, adapter.reference_logits(CONF, DEPTH)) < LIMIT


def test_a_window_one_token_wider_fails_the_limit(judged):
    """A fault: the reference with a window of 25 where the program's is 24
    is another model, and the comparison says so (by routing first; with
    that set aside, by the logits)."""
    w, got, seqs, _ = judged
    loose = {**CONF, "correctness": {**CONF["correctness"],
                                     "routing_epsilon": 10.0}}
    wrong = adapter.reference_logits(loose, DEPTH, window=25)
    assert _worst(got, seqs, w, wrong) > 2 * LIMIT


def test_lam_zero_is_plain_grouped_latent_attention(judged):
    """With the differential gate off, the reference's attention is grouped
    latent attention over the signal heads alone, and the program differs
    from it by what the noise heads subtract: the limit says which."""
    w, got, seqs, _ = judged
    loose = {**CONF, "correctness": {**CONF["correctness"],
                                     "routing_epsilon": 10.0}}
    plain = adapter.reference_logits(loose, DEPTH, lam_scale=0.0)
    assert _worst(got, seqs, w, plain) > 2 * LIMIT


def _one_layer(seed=3):
    cfg = get_config("tiny-motif")
    w = motif_weights.make_weights(CONF, seed, 2)
    lp = jax.tree.map(lambda a: a[0], w["dense"])
    return cfg, jax.tree.map(lambda a: a.astype(jnp.float32)
                             if a.dtype == jnp.bfloat16 else a, lp)


@pytest.mark.parametrize("lam_scale", [1.0, 0.0])
def test_absorbed_attention_equals_expanded_on_the_same_weights(lam_scale):
    """One layer of GDLA in float32: the program's absorbed form (``q~ =
    q_nope W_uk,g^T`` against the cached row, group-major, the differential
    combine in the latent, ``W_uv`` for the signal heads) against K and V
    expanded from ``c`` for every position in the published head order, from
    the same int8 ``wkv_b`` and its scales; at ``lam = 0`` both are the
    signal heads' plain attention."""
    cfg, lp = _one_layer()
    if not lam_scale:       # w_lam zeroed: lam is 0.5 at every head
        lp = {**lp, "w_lam": {"q": jnp.zeros_like(lp["w_lam"]["q"]),
                              "s": lp["w_lam"]["s"]}}
    T, G = 40, cfg.num_kv_heads
    Hs = cfg.num_heads - cfg.num_noise_heads
    x = jax.random.normal(jax.random.PRNGKey(0), (1, T, cfg.hidden_size),
                          jnp.float32)
    cos, sin = rope.rope_tables(cfg, 64)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    with jax.default_matmul_precision("highest"):
        latent, q = motif.latent_and_query(lp, x, cfg, pos, cos, sin)
        scale = rope.attention_scale(cfg)
        causal = np.tril(np.ones((T, T), bool))
        s = jnp.einsum("thw,sw->hts", q, latent) * scale
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        o_lat = jnp.einsum("hts,sr->thr", p, latent[:, :rank])
        lam = jax.nn.sigmoid(motif._proj(x, lp["w_lam"]))[0]
        if not lam_scale:
            np.testing.assert_allclose(np.asarray(lam), 0.5)
            o_lat = o_lat.reshape(T, G, -1, rank).at[:, :, -1].set(0.0
                                                                   ).reshape(T, -1, rank)
        absorbed = motif.attention_out(lp, x, o_lat, cfg)

        # expanded, the reference's way, heads in the published order
        wkv = (lp["wkv_b"]["q"].astype(jnp.float32)
               * lp["wkv_b"]["s"][None, :]).reshape(rank, G, -1)
        kv = jnp.einsum("sr,rgd->sgd", latent[:, :rank], wkv)
        k_r = latent[:, rank: cfg.latent_width]
        c_q = motif.rms_norm(motif._proj(x, lp["wq_a"]), lp["q_a_norm"],
                             cfg.rms_norm_eps)
        qq = motif._proj(c_q, lp["wq_b"]).reshape(T, cfg.num_heads, -1)
        q_rope = rope.apply_rope(qq[None, ..., nope:], pos, cos, sin)[0]
        group_of = np.concatenate([np.arange(Hs) // (Hs // G),
                                   np.arange(cfg.num_noise_heads)])
        s2 = (jnp.einsum("thd,shd->hts", qq[..., :nope],
                         kv[:, group_of, :nope])
              + jnp.einsum("thd,sd->hts", q_rope, k_r)) * scale
        p2 = jax.nn.softmax(jnp.where(causal[None], s2, -jnp.inf), -1)
        A = jnp.einsum("hts,shd->thd", p2, kv[:, group_of, nope:])
        o = A[:, :Hs] - (lam_scale * lam)[:, :, None] * jnp.repeat(
            A[:, Hs:], Hs // G, axis=1)
        o = o.reshape(1, T, -1) * jax.nn.sigmoid(motif._proj(x, lp["w_gate"]))
        expanded = motif._proj(o, lp["wo"])[0]
    assert np.abs(np.asarray(q[..., cfg.latent_width:])).max() == 0
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("iters", [1, 20])
def test_sinkhorns_rows_and_columns_sum_to_one(iters):
    """The program's Sinkhorn (two scalings carried) against the reference's
    alternation of row and column normalisation; the columns sum to 1 after
    any alternation, the rows too after 20, and no entry is 0 or 1."""
    m = jnp.exp(jax.random.normal(jax.random.PRNGKey(1), (33, 4, 4)))
    got = np.asarray(motif.sinkhorn(m, iters))
    np.testing.assert_allclose(got, np.asarray(reference.sinkhorn(m, iters)),
                               rtol=1e-5)
    np.testing.assert_allclose(got.sum(axis=1), 1.0, rtol=1e-6)   # columns
    if iters == 20:
        np.testing.assert_allclose(got.sum(axis=2), 1.0, atol=1e-4)  # rows
        assert (got > 0).all() and got.max() < 1


def test_hyper_connection_maps_by_hand():
    """``H_pre`` in (0, 1), ``H_post`` in (0, 2), ``H_res`` doubly
    stochastic, from float32 maps over bfloat16 streams ``[N, S C]``; a
    sub-layer that returns zero leaves ``H_res X``."""
    cfg, lp = _one_layer(4)
    X = jax.random.normal(jax.random.PRNGKey(2), (9, 4 * cfg.hidden_size),
                          jnp.bfloat16)
    pre, post, res = motif._mhc_maps(lp, 1, X, cfg)
    assert pre.dtype == post.dtype == res.dtype == jnp.float32
    assert (0 < np.asarray(pre)).all() and (np.asarray(pre) < 1).all()
    assert (0 < np.asarray(post)).all() and (np.asarray(post) < 2).all()
    np.testing.assert_allclose(np.asarray(res).sum(axis=1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(res).sum(axis=2), 1.0, atol=1e-4)
    out, _ = motif.hyper_connected(
        lp, 1, X, cfg, lp["mlp_norm"],
        lambda x: (jnp.zeros((9, cfg.hidden_size), jnp.float32), None))
    assert out.shape == X.shape and out.dtype == X.dtype
    want = jnp.einsum("nst,ntc->nsc", res,
                      X.astype(jnp.float32).reshape(9, 4, -1))
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(want).reshape(9, -1),
                               rtol=0.02, atol=0.02)


#: (tokens, streams, channels): the tiny model's; the served decode step's;
#: a mixed step's 576 tokens at a width the CPU can afford
STREAM_SHAPES = [(9, 4, 64), (64, 4, 4096), (576, 4, 256)]


def _hyper_case(N, S, C, dtype, clamp=4.0):
    """A sub-layer's maps at ``(S, C)`` drawn as the benchmark's
    (``motif_weights._stack``), the streams ``[N, S C]`` in ``dtype``, the
    configuration that names the shape."""
    cfg = dataclasses.replace(get_config("tiny-motif"), hidden_size=C,
                              mhc_expansion_rate=S, hidden_clamp=clamp)
    k = jax.random.split(jax.random.PRNGKey(N + C), 6)
    M, wide = 2 * S + S * S, S * C
    lp = {"mhc_norm": 1 + 0.1 * jax.random.normal(k[0], (2, wide)),
          "mhc_phi": jax.random.normal(k[1], (2, wide, M)) * wide ** -0.5,
          "mhc_alpha": 1 + 0.1 * jax.random.normal(k[2], (2, 3)),
          "mhc_bias": 0.5 * jax.random.normal(k[3], (2, M)),
          "norm": 1 + 0.1 * jax.random.normal(k[4], (C,))}
    X = jax.random.normal(k[5], (N, wide), jnp.float32).astype(dtype)
    return cfg, lp, X


def _reference_lines(cfg, lp, sub, X, f):
    """``benchmark/motif_reference.py: connected`` line for line, in float32
    over the streams ``[N, S, C]``: (H_pre, H_post, H_res, u, the streams
    after the sub-layer)."""
    N, n, eps = X.shape[0], cfg.mhc_expansion_rate, cfg.rms_norm_eps
    X = X.astype(jnp.float32).reshape(N, n, -1)
    x = X.reshape(N, -1)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                          + eps) * lp["mhc_norm"][sub]
    z = x @ lp["mhc_phi"][sub]
    a, b = lp["mhc_alpha"][sub], lp["mhc_bias"][sub]
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    res = reference.sinkhorn(jnp.exp(a[2] * z[:, 2 * n:] + b[2 * n:]
                                     ).reshape(N, n, n),
                             cfg.mhc_sinkhorn_iters)
    u = jnp.einsum("ts,tsc->tc", pre, X)
    y = jnp.clip(f(u), -cfg.hidden_clamp, cfg.hidden_clamp)
    return pre, post, res, u, (jnp.einsum("tsu,tuc->tsc", res, X)
                               + post[:, :, None] * y[:, None, :])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", STREAM_SHAPES, ids=str)
def test_hyper_connection_over_lane_dense_streams(shape, dtype):
    """The maps, ``u = H_pre X`` (as the sub-layer is handed it) and ``H_res
    X + H_post y`` over ``[N, S C]`` against the reference's lines over ``[N,
    S, C]``; the maps float32 whatever the streams are."""
    N, S, C = shape
    cfg, lp, X = _hyper_case(N, S, C, dtype)
    with jax.default_matmul_precision("highest"):
        pre, post, res = motif._mhc_maps(lp, 1, X, cfg)
        seen = {}

        def f(x):       # what a sub-layer reads, and a y of the streams' size
            seen["x"] = x
            return 3.0 * x[0].astype(jnp.float32), None

        out, _ = motif.hyper_connected(lp, 1, X, cfg, lp["norm"], f)
        want_pre, want_post, want_res, u, want = _reference_lines(
            cfg, lp, 1, X, lambda u: 3.0 * np.asarray(seen["x"][0],
                                                      np.float32))
    assert pre.dtype == post.dtype == res.dtype == jnp.float32
    for got, ref in ((pre, want_pre), (post, want_post), (res, want_res)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6)
    tol = 1e-5 if dtype == jnp.float32 else 0.02
    normed = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True)
                               + cfg.rms_norm_eps) * lp["norm"]
    assert seen["x"].shape == (1, N, C) and seen["x"].dtype == dtype
    np.testing.assert_allclose(np.asarray(seen["x"][0], np.float32),
                               np.asarray(normed), rtol=tol, atol=tol)
    assert out.shape == (N, S * C) and out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want).reshape(N, -1),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", STREAM_SHAPES, ids=str)
def test_a_sub_layer_that_returns_zero_leaves_h_res_x(shape, dtype):
    N, S, C = shape
    cfg, lp, X = _hyper_case(N, S, C, dtype)
    out, _ = motif.hyper_connected(
        lp, 0, X, cfg, lp["norm"],
        lambda x: (jnp.zeros((N, C), jnp.float32), None))
    *_, res, _, want = _reference_lines(cfg, lp, 0, X,
                                        lambda u: jnp.zeros_like(u))
    np.testing.assert_allclose(
        np.asarray(want), np.asarray(jnp.einsum(
            "nst,ntc->nsc", res, X.astype(jnp.float32).reshape(N, S, C))),
        rtol=1e-6)
    tol = 1e-5 if dtype == jnp.float32 else 0.02
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want).reshape(N, -1),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("clamp", [0.5, 0.0], ids=["clamped", "no-clamp"])
@pytest.mark.parametrize("shape", STREAM_SHAPES, ids=str)
def test_a_sub_layers_output_is_clamped_before_the_mix(shape, clamp):
    """``y`` of +-3 beyond a ``hidden_clamp`` of 0.5 enters the streams as
    +-0.5 times ``H_post``; a configuration without a clamp (0) mixes it as
    it is."""
    N, S, C = shape
    cfg, lp, X = _hyper_case(N, S, C, jnp.float32, clamp)
    y = 3.0 * jnp.sign(jax.random.normal(jax.random.PRNGKey(9), (N, C)))
    out, _ = motif.hyper_connected(lp, 1, X, cfg, lp["norm"],
                                   lambda x: (y, None))
    zero, _ = motif.hyper_connected(
        lp, 1, X, cfg, lp["norm"], lambda x: (jnp.zeros_like(y), None))
    _, post, _ = motif._mhc_maps(lp, 1, X, cfg)
    entered = (clamp or 3.0) * jnp.sign(y)
    np.testing.assert_allclose(
        np.asarray(out - zero).reshape(N, S, C),
        np.asarray(post[:, :, None] * entered[:, None, :]),
        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_streams_enter_as_copies_and_leave_as_their_sum(dtype):
    """``_streams_in`` lays a token's S copies of its embedding side by side
    (``[N, S C]``), ``_streams`` reads stream ``s`` as columns ``s C .. (s +
    1) C`` and ``_streams_out`` sums them in float32."""
    cfg = get_config("tiny-motif")
    S, C = cfg.mhc_expansion_rate, cfg.hidden_size
    params = {"embed": jax.random.normal(jax.random.PRNGKey(0), (32, C),
                                         dtype),
              "final_norm": jnp.ones((C,), dtype)}
    ids = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
    X = motif._streams_in(params, cfg, ids)
    assert X.shape == (5, S * C) and X.dtype == dtype
    for s, xs in enumerate(motif._streams(X, S)):
        assert xs.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(xs), np.asarray(params["embed"][ids[0]], np.float32))
    X = jax.random.normal(jax.random.PRNGKey(1), (5, S * C), dtype)
    got = motif._streams_out(X, cfg)
    assert got.shape == (1, 5, C) and got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32),
        np.asarray(X, np.float32).reshape(5, S, C).sum(axis=1),
        rtol=1e-2 if dtype == jnp.bfloat16 else 1e-6)


def test_poly_norm_by_hand():
    """Each power over its own rms, the scale on the polynomial, the bias
    clamped and added after the scale; the program's and the reference's are
    one function of a row."""
    cfg = get_config("tiny-motif")
    z = jax.random.normal(jax.random.PRNGKey(3), (5, 32), jnp.float32) * 3
    coef = jnp.asarray([0.5, -0.25, 0.125], jnp.float32)
    for bias, kept in ((0.2, 0.2), (1.7, 0.5), (-3.0, -0.5)):
        got = np.asarray(poly_norm(z, coef, jnp.asarray(bias), cfg))
        zz = np.asarray(z, np.float64)
        want = 0.5 * sum(
            c * zz ** k / np.sqrt((zz ** (2 * k)).mean(-1, keepdims=True)
                                  + cfg.rms_norm_eps)
            for k, c in zip((1, 2, 3), (0.5, -0.25, 0.125))) + kept
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            got, np.asarray(reference.poly_norm(
                z, coef, jnp.asarray(bias), 0.5, 0.5, cfg.rms_norm_eps)),
            rtol=1e-6, atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST. At 4 shares of 16 experts: the routed part each
    share's expert layer gives (``moe_experts`` under ``experts_held`` 4 at
    offsets 0, 4, 8, 12, PolyNorm over each expert's whole row), plus the
    shared expert counted ONCE, is the uncut layer: every expert computed
    under a gate that is zero off the chosen, gates ``2 s / sum`` over all
    the chosen."""
    full = get_config("tiny-motif")
    H, I, E, K = (full.hidden_size, full.moe_intermediate_size,
                  full.num_experts, full.experts_per_token)
    keys = jax.random.split(jax.random.PRNGKey(5), 8)
    x = jax.random.normal(keys[0], (40, H), jnp.float32)
    router = jax.random.normal(keys[1], (H, E), jnp.float32) * H ** -0.5
    gate, up = (jax.random.normal(k, (1, E, H, I), jnp.float32) * H ** -0.5
                for k in keys[3:5])
    down = jax.random.normal(keys[5], (1, E, I, H), jnp.float32) * I ** -0.5
    sg, su = (jax.random.normal(k, (H, I), jnp.float32) * H ** -0.5
              for k in keys[6:8])
    sd = jax.random.normal(keys[2], (I, H), jnp.float32) * I ** -0.5
    routed = (jnp.asarray([0.4, 0.3, 0.2], jnp.float32), jnp.asarray(0.9))
    shared_unit = (jnp.asarray([0.3, 0.3, 0.4], jnp.float32),
                   jnp.asarray(-0.1))
    gamma = full.routed_scaling_factor
    assert gamma == 2.0

    def act(z, unit):
        return reference.poly_norm(z, *unit, 0.5, 0.5, full.rms_norm_eps)

    with jax.default_matmul_precision("highest"):
        top_idx, gates = moe_route(x, router, K, sigmoid=True, scale=gamma)
        np.testing.assert_allclose(np.asarray(gates).sum(1), 2.0, rtol=1e-6)
        parts = []
        for offset in range(0, E, 4):
            cfg = dataclasses.replace(full, experts_held=4,
                                      expert_offset=offset)
            held = {"moe_gate": gate[:, offset: offset + 4],
                    "moe_up": up[:, offset: offset + 4],
                    "moe_down": down[:, offset: offset + 4]}
            parts.append(np.asarray(moe_experts(x, top_idx, gates, held, cfg,
                                                0, poly=routed)))
        shared = (act(x @ sg, shared_unit) * (x @ su)) @ sd
        s = jax.nn.sigmoid(x @ router)
        _, chosen = jax.lax.top_k(s, K)
        s_top = jnp.take_along_axis(s, chosen, 1)
        g = jnp.zeros_like(s).at[jnp.arange(40)[:, None], chosen].set(
            gamma * s_top / s_top.sum(1, keepdims=True))
        want = shared + sum(
            g[:, e: e + 1] * ((act(x @ gate[0, e], routed) * (x @ up[0, e]))
                              @ down[0, e]) for e in range(E))
    assert all(np.abs(p).max() > 0 for p in parts)     # every share has work
    np.testing.assert_allclose(sum(parts) + np.asarray(shared),
                               np.asarray(want), rtol=2e-4, atol=2e-5)


def test_the_tree_and_the_plan_are_the_configurations():
    """int8 matrices, float32 router, maps and PolyNorm coefficients, rows
    and experts HELD; the served share's stack is four bodies."""
    from cyberfabric_core_tpu.runtime.quant import init_params_quantized

    cfg = get_config("tiny-motif-share4")
    tree = init_params_quantized(cfg, jax.random.PRNGKey(0))
    layers, dense = tree["layers"], tree["dense"]
    assert layers["router"].dtype == jnp.float32
    assert layers["router"].shape == (9, 64, 16)               # all routed
    assert "router_bias" not in layers
    assert layers["moe_gate"]["q"].shape == (9, 4, 64, 32)     # held
    assert layers["wkv_b"]["q"].shape == (9, 32, 2 * 64)       # 2 kv groups
    assert layers["w_lam"]["q"].shape == (9, 64, 8)            # signal heads
    assert layers["wo"]["q"].shape == (9, 8 * 32, 64)
    assert layers["mhc_phi"].shape == (9, 2, 256, 24)
    assert layers["mhc_phi"].dtype == layers["poly_coef"].dtype == jnp.float32
    assert layers["poly_coef"].shape == (9, 2, 3)
    assert dense["poly_coef"].shape == (2, 1, 3)
    assert dense["gate"]["q"].shape == (2, 64, 128)
    assert tree["embed"]["qe"].shape == (256, 64)              # rows held
    served = get_config("motif-3-beta-share32-27l")
    assert motif.layer_plan(served) == ([(0, 2), (2, 1)], (3, 6), [])
    assert (served.kv_layers, served.window_layers) == (6, 21)
    assert [i for i in range(27) if served.layer_is_full(i)] == \
        [3, 7, 11, 15, 19, 23]
    assert served.cache_bytes_per_token() == 6 * 640 * 2
    assert motif.layer_plan(get_config("motif-3-beta"))[1:] == (
        (3, 12), [(51, 1), (52, 1)])
