"""forward_paged_decode vs dense forward: decode parity over a paged pool;
the 5-D pool entry of forward_paged_*; and the guard of PR 25's gain: the
pool reaches the kernels as it is stored, with no pool-sized op in between."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.models import llama
from cyberfabric_core_tpu.models.configs import get_config
from cyberfabric_core_tpu.ops.rope import rope_frequencies


def _pool_from_dense(cache, page_size, num_pages, merged=True):
    """Copy a dense [L, B, S, Hkv, D] cache into a paged pool + page tables.
    Slot b's pages are laid out at distinct physical ids (reversed order to
    prove the table indirection is honored). ``merged``: the engine's
    [L, N, page, Hkv*D], else the 5-D shape the forwards also accept."""
    k_cache, v_cache = cache
    L, B, S, Hkv, D = k_cache.shape
    assert S % page_size == 0
    pmax = S // page_size
    k_pool = np.zeros((L, num_pages, page_size, Hkv, D), np.float32)
    v_pool = np.zeros((L, num_pages, page_size, Hkv, D), np.float32)
    pt = np.zeros((B, pmax), np.int32)
    next_id = num_pages - 1  # descending: physical order != logical order
    for b in range(B):
        for p in range(pmax):
            pt[b, p] = next_id
            k_pool[:, next_id] = np.asarray(
                k_cache[:, b, p * page_size:(p + 1) * page_size])
            v_pool[:, next_id] = np.asarray(
                v_cache[:, b, p * page_size:(p + 1) * page_size])
            next_id -= 1
    if merged:
        k_pool = k_pool.reshape(L, num_pages, page_size, Hkv * D)
        v_pool = v_pool.reshape(L, num_pages, page_size, Hkv * D)
    return (jnp.asarray(k_pool), jnp.asarray(v_pool)), jnp.asarray(pt)


@pytest.mark.parametrize("merged", [True, False], ids=["merged", "5d-entry"])
@pytest.mark.parametrize("model", ["tiny-llama", "tiny-moe"])
def test_paged_decode_matches_dense(model, merged):
    cfg = get_config(model)
    rope = rope_frequencies(cfg.head_dim, cfg.max_position, cfg.rope_theta)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)

    B, S, page = 2, 64, 16
    prompt_lens = [11, 23]
    ids = np.zeros((B, 32), np.int32)
    rng = np.random.default_rng(1)
    for b, L in enumerate(prompt_lens):
        ids[b, :L] = rng.integers(1, cfg.vocab_size, L)

    # dense prefill
    cache = llama.init_cache(cfg, B, S, jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(32)[None, :], (B, 32)).astype(jnp.int32)
    hidden, cache = llama.forward(
        params, cfg, jnp.asarray(ids), positions, cache,
        jnp.zeros((B,), jnp.int32), rope)
    lengths = jnp.asarray(prompt_lens, jnp.int32)

    pools, pt = _pool_from_dense(cache, page, num_pages=B * (S // page) + 1,
                                 merged=merged)

    # 5 decode steps, both paths, same tokens in
    toks = rng.integers(1, cfg.vocab_size, (5, B)).astype(np.int32)
    dense_lens = lengths
    paged_lens = lengths
    for step in range(5):
        t = jnp.asarray(toks[step])[:, None]
        hd, cache = llama.forward(
            params, cfg, t, dense_lens[:, None], cache, dense_lens, rope)
        hp, pools = llama.forward_paged_decode(
            params, cfg, t, pools, pt, paged_lens, rope, interpret=True)
        np.testing.assert_allclose(
            np.asarray(hd), np.asarray(hp), rtol=2e-4, atol=2e-4)
        dense_lens = dense_lens + 1
        paged_lens = paged_lens + 1
        assert pools[0].ndim == (4 if merged else 5)


def _tiny_step_inputs(seed=0):
    cfg = get_config("tiny-llama")
    rope = rope_frequencies(cfg.head_dim, cfg.max_position, cfg.rope_theta)
    params = llama.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    B, page, pmax = 3, 8, 4
    n_pages = B * pmax + 1
    rng = np.random.default_rng(seed)
    shape = (cfg.num_layers, n_pages, page, cfg.num_kv_heads, cfg.head_dim)
    pools5 = tuple(jnp.asarray(rng.standard_normal(shape, np.float32))
                   for _ in range(2))
    table = jnp.asarray(1 + np.arange(B * pmax).reshape(B, pmax), jnp.int32)
    return cfg, rope, params, rng, pools5, table


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_5d_pool_entry_is_bitwise_the_merged_path(step):
    """benchmark/adapters/llama.py (the llama family's binding, which the
    judge benchmark/correctness.py drives) hands forward_paged_* a
    [L, N, page, Hkv, D] pool: it is merged once on entry and comes back 5-D,
    and hidden states and pool are bit for bit what the merged path gives.
    (benchmark/adapters/falcon_h1.py builds merged pools.)"""
    cfg, rope, params, rng, pools5, table = _tiny_step_inputs()
    pools4 = tuple(p.reshape(*p.shape[:3], -1) for p in pools5)
    B = table.shape[0]
    if step == "decode":
        ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, 1)), jnp.int32)
        lens = jnp.asarray([5, 8, 23], jnp.int32)
        run = lambda pools: llama.forward_paged_decode(  # noqa: E731
            params, cfg, ids, pools, table, lens, rope, interpret=True,
            write_mask=jnp.asarray([True, True, False]))
    else:
        ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, 16)), jnp.int32)
        hist = jnp.asarray([5, 0, 16], jnp.int32)
        q_lens = jnp.asarray([1, 13, 0], jnp.int32)
        run = lambda pools: llama.forward_paged_mixed(  # noqa: E731
            params, cfg, ids, pools, table, hist, q_lens, rope,
            interpret=True)
    h5, out5 = run(pools5)
    h4, out4 = run(pools4)
    np.testing.assert_array_equal(np.asarray(h5), np.asarray(h4))
    for got, want, sent in zip(out5, out4, pools5):
        assert got.shape == sent.shape
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want).reshape(sent.shape))
        assert not np.array_equal(np.asarray(got), np.asarray(sent))


def _producers(jaxpr, known=None):
    """var -> the equation that makes it, through every nested jaxpr; an
    argument of a nested jit -> the variable passed for it (a scan body's
    arguments stay unmapped: there the carry begins)."""
    known = {} if known is None else known
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            known[v] = eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            if eqn.primitive.name in ("jit", "pjit"):
                known.update(zip(sub.invars, eqn.invars))
            _producers(sub, known)
    return known


def _find(jaxpr, name):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _find(sub, name)


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_pool_reaches_the_kernel_uncopied(step):
    """The gain of PR 25, guarded where no chip is needed: in the traced
    compiled-path step (two_d_dots, no lowering) the K and V operands of the
    pallas_call have the stored pool's shape and nothing but the scatter of
    the step's own rows made them. A reshape, dynamic_slice, squeeze or
    gather of pool size in front of the kernel was a copy of a layer's pool,
    twice a layer a step, a third of a decode step on the chip."""
    from unittest import mock

    from cyberfabric_core_tpu.ops import paged_attention

    cfg, rope, params, rng, pools5, table = _tiny_step_inputs()
    pools = tuple(p.reshape(*p.shape[:3], -1) for p in pools5)
    B = table.shape[0]
    rows = jnp.asarray([5, 8, 23], jnp.int32)
    name = "paged_decode_attention" if step == "decode" \
        else "ragged_paged_attention"
    kernel = getattr(paged_attention, name)
    compiled_form = lambda *a, **kw: kernel(  # noqa: E731
        *a, **{**kw, "two_d_dots": True})
    with mock.patch.object(paged_attention, name, compiled_form):
        if step == "decode":
            jaxpr = jax.make_jaxpr(lambda pools: llama.forward_paged_decode(
                params, cfg, jnp.ones((B, 1), jnp.int32), pools, table, rows,
                rope, interpret=True))(pools)
        else:
            jaxpr = jax.make_jaxpr(lambda pools: llama.forward_paged_mixed(
                params, cfg, jnp.ones((B, 8), jnp.int32), pools, table, rows,
                jnp.asarray([1, 8, 0], jnp.int32), rope,
                interpret=True))(pools)

    eqn, = _find(jaxpr.jaxpr, "pallas_call")   # one, inside the layer scan
    made_by = _producers(jaxpr.jaxpr)
    pool_shape = pools[0].shape
    # scalar prefetch (table, rows..., layer), then q, K, V
    k_op, v_op = eqn.invars[-2:]
    for op in (k_op, v_op):
        assert op.aval.shape == pool_shape
        seen = []
        while op in made_by:                    # walk back to the scan carry
            src = made_by[op]
            if not hasattr(src, "primitive"):   # a jit's argument
                op = src
                continue
            seen.append(src.primitive.name)
            op = next(v for v in src.invars
                      if getattr(v.aval, "shape", None) == pool_shape)
        assert seen == ["scatter"], seen


def _layer_scans(jaxpr, num_layers):
    """The scans over the model's layers: of that length and around the
    kernels (a kernel that walks its pages itself has loops of its own, over
    its kv heads and its ring, which may be as long)."""
    return [e for e in _find(jaxpr, "scan")
            if e.params["length"] == num_layers
            and any(_find(e.params["jaxpr"].jaxpr, "pallas_call"))]


def _mixed_step_operands(eng, width):
    """``mixed_step``'s operands with an empty lane of ``width``."""
    from cyberfabric_core_tpu.runtime.programs import LANE_ROWS, lane_words

    lane = jnp.zeros((lane_words(eng.n_slots, eng._block, LANE_ROWS,
                                 width),), jnp.int32)
    return (eng.params, *eng.pool.cache_operands(), eng._rows_dev, lane,
            eng._last_tokens, eng._lengths_dev, eng._finished_dev,
            eng._slot_keys)


@pytest.mark.parametrize("program", ["paged_decode_chunk", "mixed_step"])
@pytest.mark.parametrize("model", ["tiny-llama", "tiny-falcon-h1",
                                   "tiny-sdar"])
def test_the_decode_kernel_takes_the_table_and_the_lengths(model, program):
    """The decode kernel walks a row's pages itself: in the scheduler's own
    programs nothing is laid out from the rows' lengths in front of it (no
    cumsum over the rows, inside the scan over layers or outside), and the
    kernel inside the scan is a program a row that takes the page table,
    the lengths and the layer as scalar-prefetch operands and both pools
    whole, as the carry holds them."""
    from cyberfabric_core_tpu.runtime import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    n, width = 4, 32
    eng = ContinuousBatchingEngine(EngineConfig(
        model=model, max_seq_len=128, max_batch=n, decode_chunk=3,
        prefix_cache_pages=16, prefix_page_size=16,
        prefill_budget_tokens=width), seed=0)
    try:
        if program == "paged_decode_chunk":
            jaxpr = jax.make_jaxpr(eng._paged_decode_fn)(
                eng.params, *eng.pool.cache_operands(), eng._rows_dev,
                eng._last_tokens, eng._lengths_dev, eng._active_dev,
                eng._finished_dev, eng._slot_keys)
        else:
            jaxpr = jax.make_jaxpr(eng._mixed_step_fn)(
                *_mixed_step_operands(eng, width))
        layers, = _layer_scans(jaxpr.jaxpr, eng.model_config.num_layers)
        def over_rows(jaxpr):     # an expert layer and a mixer have others
            return [e for e in _find(jaxpr, "cumsum")
                    if e.outvars[0].aval.shape == (n,)
                    and e.outvars[0].aval.dtype == jnp.int32]

        body = layers.params["jaxpr"].jaxpr
        assert not over_rows(jaxpr.jaxpr) and not over_rows(body)
        slots = eng.page_table.shape[1]
        decode, = [e for e in _find(body, "pallas_call")
                   if e.params["grid_mapping"].grid == (n,)]
        pool = eng.pool.cache_operands()[0].shape
        pool = (*pool[:3], pool[3] * pool[4]) if len(pool) == 5 else pool
        assert [v.aval.shape for v in decode.invars[:3]] == [
            (n, slots), (n,), (1,)]
        assert [v.aval.shape for v in decode.invars[4:6]] == [pool, pool]
    finally:
        eng.shutdown()


@pytest.mark.parametrize("model", ["tiny-llama", "tiny-falcon-h1"])
def test_mixed_step_computes_the_tokens_it_has(model):
    """The guard S11 lacked: in the scheduler's own ``mixed_step`` at 16
    slots and a 256-wide chunk, every matmul over a layer weight runs over
    16 + 256 token rows — the decode rows' one token each and the lane's
    chunk — and none over 16 x 256; and a served round of that shape records
    those positions."""
    import threading

    from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    n, width = 16, 256
    eng = ContinuousBatchingEngine(EngineConfig(
        model=model, max_seq_len=512, max_batch=n, decode_chunk=4,
        prefix_cache_pages=48, prefix_page_size=16,
        prefill_budget_tokens=width), seed=0)
    try:
        jaxpr = jax.make_jaxpr(eng._mixed_step_fn)(
            *_mixed_step_operands(eng, width))
        weights = {w.shape[1:] for w in jax.tree.leaves(eng.params["layers"])
                   if w.ndim == 3}
        scan, = _layer_scans(jaxpr.jaxpr, eng.model_config.num_layers)
        rows = []
        for eqn in _find(scan.params["jaxpr"].jaxpr, "dot_general"):
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            if rhs in weights:
                (contract, _), _ = eqn.params["dimension_numbers"]
                rows.append(int(np.prod(lhs)) // int(np.prod(
                    [lhs[d] for d in contract])))
        assert len(rows) >= 7, rows       # q k v o gate up down at the least
        assert set(rows) == {n + width}, rows

        done = threading.Event()
        eng.submit(list(range(1, 201)), SamplingParams(max_tokens=2),
                   lambda ev: done.set() if ev.finished else None)
        assert done.wait(120)
        mixed = [r for r in eng.round_timings if r["mixed"]]
        assert [r["chunk_tokens"] for r in mixed] == [200]
        assert mixed[0]["positions"] == n + width
        stats = eng.stats()["pipeline"]
        assert stats["mixed_positions"] == n + width
        assert stats["mixed_useful_share"] == round(
            (200 + mixed[0]["active"]) / (n + width), 4)
    finally:
        eng.shutdown()
