"""Ring attention — sequence-parallel causal attention over a mesh axis.

Long-context design (SURVEY §5 "long-context": handled on-device; ring attention
over the ICI mesh for >1-chip contexts): the sequence axis is sharded across the
``sp`` mesh axis; each device holds one Q/K/V block and the K/V blocks rotate
around the ring via ppermute while every device accumulates attention for its
local queries with a numerically-stable online softmax (flash-style m/l
carries in f32). Peak memory per device is O(T/P · T/P) scores instead of
O(T · T), and the K/V transfer rides ICI concurrently with compute.

Causality is enforced with *global* positions, so the result equals single-device
causal attention bit-for-tolerance; blocks wholly in the future are masked to
zero contribution (their correction terms are identity).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG_INF = -1e30


def _ring_attention_local(
    q: jnp.ndarray,  # [B, Tl, Hq, D] local query block
    k: jnp.ndarray,  # [B, Tl, Hkv, D] local key block (rotates)
    v: jnp.ndarray,  # [B, Tl, Hkv, D]
    axis_name: str,
    lengths: Optional[jnp.ndarray] = None,  # [B] global valid lengths
) -> jnp.ndarray:
    B, Tl, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)

    qg = q.astype(jnp.float32).reshape(B, Tl, Hkv, G, D)
    q_pos = my_idx * Tl + jnp.arange(Tl, dtype=jnp.int32)  # [Tl] global positions

    # online-softmax accumulators (f32), marked device-varying over the ring axis
    # so the fori_loop carry type matches its (axis_index-dependent) outputs
    def _varying(x):
        return jax.lax.pcast(x, to="varying", axis_name=axis_name)

    acc = _varying(jnp.zeros((B, Tl, Hkv, G, D), jnp.float32))
    m = _varying(jnp.full((B, Tl, Hkv, G), _NEG_INF, jnp.float32))
    l = _varying(jnp.zeros((B, Tl, Hkv, G), jnp.float32))

    def body(step, carry):
        acc, m, l, k_cur, v_cur = carry
        # the block currently held started at device (my_idx - step) mod n
        src = jax.lax.rem(my_idx - step + n, n)
        k_pos = src * Tl + jnp.arange(Tl, dtype=jnp.int32)

        scores = jnp.einsum("bthgd,bshd->bthgs", qg, k_cur.astype(jnp.float32))
        scores = scores * (1.0 / (D ** 0.5))
        mask = k_pos[None, None, :] <= q_pos[None, :, None]  # [1, Tl, Tl]
        if lengths is not None:
            mask = mask & (k_pos[None, None, :] < lengths[:, None, None])
        scores = jnp.where(mask[:, :, None, None, :], scores, _NEG_INF)

        m_blk = jnp.max(scores, axis=-1)                      # [B, Tl, Hkv, G]
        m_new = jnp.maximum(m, m_blk)
        # guard: all-masked blocks keep accumulators untouched
        correction = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        p = jnp.where(mask[:, :, None, None, :], p, 0.0)
        l_new = l * correction + jnp.sum(p, axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum(
            "bthgs,bshd->bthgd", p, v_cur.astype(jnp.float32))

        k_next = jax.lax.ppermute(
            k_cur, axis_name, [(i, (i + 1) % n) for i in range(n)])
        v_next = jax.lax.ppermute(
            v_cur, axis_name, [(i, (i + 1) % n) for i in range(n)])
        return acc_new, m_new, l_new, k_next, v_next

    acc, m, l, _, _ = jax.lax.fori_loop(0, n, body, (acc, m, l, k, v))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Tl, Hq, D).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, T, Hq, D] — T sharded over `axis` under shard_map
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
    lengths: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """shard_map wrapper: global [B, T, H, D] in/out, T sharded over ``axis``."""
    spec = P(None, axis, None, None)
    if lengths is None:
        return jax.shard_map(
            lambda q, k, v: _ring_attention_local(q, k, v, axis),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        )(q, k, v)
    return jax.shard_map(
        lambda q, k, v, ln: _ring_attention_local(q, k, v, axis, ln),
        mesh=mesh, in_specs=(spec, spec, spec, P(None)), out_specs=spec,
    )(q, k, v, lengths)
