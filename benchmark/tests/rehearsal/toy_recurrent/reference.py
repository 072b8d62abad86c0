"""The toy block's plain reference: the llama reference's logits plus the
state's, one token after another in float32. ``s_t = decay * s_(t-1) +
state_in[token_t]``; ``logits_t += s_t @ state_out``. No chunks, no
snapshots, no batching. Imports nothing from the program."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import reference as llama_reference


@functools.partial(jax.jit, static_argnames=("lower",))
def state_logits(weights: dict, ids, at, lower=None):
    table = weights["state_in"]
    rows = table["qe"][ids].astype(jnp.float32) * table["se"][ids][:, None]

    def step(s, row):
        s = weights["decay"] * s + row
        return s, s

    _, states = jax.lax.scan(step, jnp.zeros_like(weights["decay"]), rows)
    s = states[at]
    if lower == "fp8":      # the input of a matrix product, as in the llama half
        s = s.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    out = weights["state_out"]
    with jax.default_matmul_precision("highest"):
        return s @ (out["q"].astype(jnp.float32) * out["s"][None, :])


def reference_logits(conf: dict, depth: int):
    kw = llama_reference.reference_kwargs(conf, depth)

    def logits(w, ids, at, lower=None):
        return (llama_reference.forward_logits(w["base"], ids, at, lower=lower,
                                               **kw)
                + state_logits(w, ids, at, lower=lower))

    return logits
