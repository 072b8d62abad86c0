"""Seeded int8 weights of the Falcon-H1 block for the correctness check, made
by the benchmark: the llama-family tree of ``weights.py`` (same arithmetic,
same leaf layout) plus the mixer's leaves.

Nothing here comes from the program but the *layout* of the tree
(``models/falcon_h1.py``): ``ssm_in`` / ``ssm_out`` are ``{"q", "s"}`` matmul
leaves like any other, so ``weights.to_int4_grid`` walks them too; the small
leaves stay float32. They are drawn so that a synthetic model's decays are
neither 0 nor 1: ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of
a step log-uniform in [1e-3, 1e-1] (``exp(Δ A)`` between about 0.2 and 0.999),
``D`` near 1, conv taps ``U(±K^-1/2)``, a conv bias of std 0.1, the gated
norm's weight near 1.

The published multipliers go with trained weights. Applied to matrices drawn
at ``fan_in^-1/2`` they would shrink every branch to a few percent of the
residual (keys to 0.011 of a unit score, the MLP's output to 0.011, the
embedding up by 5.66), and a comparison of logits would then see little but
the embedding and the head: a wrong state or a wrong page would pass. So each
matrix that a multiplier follows is drawn larger by that multiplier's inverse
(its f32 scales are: int8 rounding is unchanged), so that with the published
multipliers applied every branch adds about as much as the residual holds,
attention scores are of order 1, and the logits have unit scale. The
multipliers themselves stay as published, in program and reference alike.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import weights as base

seed_key = base.seed_key
to_int4_grid = base.to_int4_grid


@functools.partial(jax.jit, static_argnames=(
    "hidden", "layers", "proj", "d_ssm", "ssm_heads", "conv_dim", "d_conv"))
def _mixer(key, *, hidden, layers, proj, d_ssm, ssm_heads, conv_dim, d_conv):
    k = jax.random.split(key, 8)
    step = jnp.exp(jax.random.uniform(k[2], (layers, ssm_heads), jnp.float32,
                                      math.log(1e-3), math.log(1e-1)))
    return {
        "ssm_in": base._matmul_leaf(k[0], layers, hidden, proj),
        "ssm_out": base._matmul_leaf(k[1], layers, d_ssm, hidden),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(k[3], (layers, ssm_heads),
                                            jnp.float32, 1.0, 16.0)),
        "D": 1.0 + 0.1 * jax.random.normal(k[4], (layers, ssm_heads),
                                           jnp.float32),
        "conv_w": jax.random.uniform(k[5], (layers, d_conv, conv_dim),
                                     jnp.float32, -d_conv ** -0.5,
                                     d_conv ** -0.5),
        "conv_b": 0.1 * jax.random.normal(k[6], (layers, conv_dim),
                                          jnp.float32),
        "ssm_norm": 1.0 + 0.1 * jax.random.normal(k[7], (layers, d_ssm),
                                                  jnp.float32),
    }


@jax.jit
def _undo(scales: dict, factors: dict) -> dict:
    return {k: s / factors[k] for k, s in scales.items()}


def make_weights(cfg: dict, seed: int, layers: int) -> dict:
    """The whole tree on the device from the seed. ``cfg`` is the published
    configuration (HF key names)."""
    tree = base.make_weights(cfg, seed, layers)
    d_ssm = cfg["mamba_d_ssm"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    conv_dim = d_ssm + 2 * gn
    lw = tree["layers"]
    lw.update(_mixer(
        jax.random.fold_in(seed_key(seed), 0x55D),
        hidden=cfg["hidden_size"], layers=layers,
        proj=d_ssm + conv_dim + cfg["mamba_n_heads"], d_ssm=d_ssm,
        ssm_heads=cfg["mamba_n_heads"], conv_dim=conv_dim,
        d_conv=cfg["mamba_d_conv"]))
    # each matrix drawn larger by the inverse of the multiplier that follows
    # it (module docstring): divide its scales
    z, x, b, c, dt = cfg["ssm_multipliers"]
    widths = (d_ssm, d_ssm, gn, gn, cfg["mamba_n_heads"])
    ssm_in = cfg["ssm_in_multiplier"] * jnp.concatenate(
        [jnp.full((n,), m, jnp.float32)
         for n, m in zip(widths, (z, x, b, c, dt))])
    gate, down = cfg["mlp_multipliers"]
    factors = {"wk": cfg["key_multiplier"] * cfg["attention_in_multiplier"],
               "wq": cfg["attention_in_multiplier"],
               "wv": cfg["attention_in_multiplier"],
               "wo": cfg["attention_out_multiplier"], "ssm_in": ssm_in,
               "ssm_out": cfg["ssm_out_multiplier"], "gate": gate,
               "down": down}
    scaled = _undo({k: lw[k]["s"] for k in factors},
                   {k: jnp.asarray(v, jnp.float32) for k, v in factors.items()})
    for k, s in scaled.items():
        lw[k] = {**lw[k], "s": s}
    tree["embed"] = {**tree["embed"],
                     "se": tree["embed"]["se"] / cfg["embedding_multiplier"]}
    tree["lm_head"] = {**tree["lm_head"],
                       "s": tree["lm_head"]["s"] / cfg["lm_head_multiplier"]}
    return tree
