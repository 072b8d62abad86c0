"""Seeded int8 weights of the SDAR-MoE block for the correctness check, made
by the benchmark: the attention half, embedding and head as ``weights.py``
draws the llama family's (same arithmetic, same leaf layout), q and k head
norms near 1, a float32 router, and the expert stacks in place of the dense
MLP.

Nothing here comes from the program but the *layout* of the tree
(``models/sdar_moe.py``): ``moe_gate`` / ``moe_up`` ``{"q": int8 [L, E, H, I],
"s": f32 [L, E, I]}`` and ``moe_down`` ``{"q": [L, E, I, H], "s": [L, E, H]}``
are matmul leaves like any other, so ``weights.to_int4_grid`` walks them too;
``router`` [L, H, E] stays float32 and is never quantized, as published
checkpoints keep it in the activations' precision.

Every matrix is drawn at ``fan_in^-1/2``, the router too: a token's scores
over the 128 experts are then of unit spread, its 8 largest are close to each
other as a trained router's are, and the expert layer adds about a third of
what the residual holds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import weights as base

seed_key = base.seed_key
to_int4_grid = base.to_int4_grid


def _expert_leaf(key, layers: int, experts: int, fan_in: int,
                 fan_out: int) -> dict:
    """One stack ``[layers, experts, fan_in, fan_out]``, a layer at a time
    (the f32 draw of one layer of SDAR-30B's experts is 0.8 GB)."""
    def one_layer(k):
        w = jax.random.normal(k, (experts, fan_in, fan_out),
                              jnp.float32) * fan_in ** -0.5
        return base._quantize(w, axis=1)

    q, s = jax.lax.map(one_layer, jax.random.split(key, layers))
    return {"q": q, "s": s}


@functools.partial(jax.jit, static_argnames=(
    "hidden", "inter", "experts", "layers", "head_dim"))
def _experts(key, *, hidden, inter, experts, layers, head_dim):
    k = jax.random.split(key, 6)

    def norm(kk, *shape):
        return (1.0 + 0.1 * jax.random.normal(kk, shape, jnp.float32)
                ).astype(jnp.bfloat16)

    return {
        "q_norm": norm(k[0], layers, head_dim),
        "k_norm": norm(k[1], layers, head_dim),
        "router": jax.random.normal(k[2], (layers, hidden, experts),
                                    jnp.float32) * hidden ** -0.5,
        "moe_gate": _expert_leaf(k[3], layers, experts, hidden, inter),
        "moe_up": _expert_leaf(k[4], layers, experts, hidden, inter),
        "moe_down": _expert_leaf(k[5], layers, experts, inter, hidden),
    }


def make_weights(cfg: dict, seed: int, layers: int) -> dict:
    """The whole tree on the device from the seed. ``cfg`` is the published
    configuration (HF key names). The dense MLP that ``weights.make_weights``
    draws is made at width 8 and dropped: every layer is sparse."""
    tree = base.make_weights({**cfg, "intermediate_size": 8}, seed, layers)
    lw = tree["layers"]
    for name in ("gate", "up", "down"):
        del lw[name]
    lw.update(_experts(
        jax.random.fold_in(seed_key(seed), 0x5DA2),
        hidden=cfg["hidden_size"], inter=cfg["moe_intermediate_size"],
        experts=cfg["num_experts"], layers=layers, head_dim=cfg["head_dim"]))
    return tree


@jax.jit
def router_on_int8_grid(weights: dict) -> dict:
    """The control ``router_int8``: the router's weights rounded to an int8
    grid, one scale an expert, and kept in float32. A router one precision
    below what the architecture states moves scores by more than the
    epsilon that the adapter allows between the program's choice of experts
    and the reference's own scores."""
    r = weights["layers"]["router"]
    q, s = base._quantize(r, axis=1)
    lowered = q.astype(jnp.float32) * s[:, None, :]
    return {**weights, "layers": {**weights["layers"], "router": lowered}}
