"""Seeded weights of the toy block: the llama tree under ``base`` (made by
the yardstick's ``benchmark/weights.py``) and three leaves of its own: the
per-token state input ``state_in`` (an int8 row and a scale a token, as an
embedding), the read-out ``state_out`` (a matmul leaf, so the int4 control
walks it) and the state's ``decay``. Nothing here comes from the program."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights as llama_weights

from . import STATE


def _int8(w: jnp.ndarray, axis: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0,
                        1e-12)
    return (jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8),
            jnp.squeeze(scale, axis).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("vocab",))
def _own(key, *, vocab: int) -> dict:
    k_in, k_out, k_decay = jax.random.split(key, 3)
    qe, se = _int8(0.15 * jax.random.normal(k_in, (vocab, STATE), jnp.float32),
                   axis=1)
    q, s = _int8(jax.random.normal(k_out, (STATE, vocab), jnp.float32)
                 * STATE ** -0.5, axis=0)
    return {"state_in": {"qe": qe, "se": se}, "state_out": {"q": q, "s": s},
            "decay": jax.random.uniform(k_decay, (STATE,), jnp.float32,
                                        0.96, 0.995)}


def make_weights(conf: dict, seed: int, layers: int) -> dict:
    key = jax.random.fold_in(llama_weights.seed_key(seed), 7)
    return {"base": llama_weights.make_weights(conf, seed, layers),
            **_own(key, vocab=conf["vocab_size"])}
