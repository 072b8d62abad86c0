"""Single-device toy LM training: Markov corpus + AdamW loop.

What needs actually-TRAINED tiny checkpoints rather than random init:
weight-realism tests, since trained weights develop the non-Gaussian
structure (outlier channels) that random init lacks. Nothing imports this
module at present (ROADMAP D22).

The corpus is a first-order Markov chain over the tiny vocab: enough
structure to learn in seconds on CPU, stochastic enough that sampling at
temperature > 0 exercises rejection paths.

Reference analogue: none — the reference (an inference platform) trains
nothing in-repo; this is test scaffolding.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp

from . import llama
from .configs import ModelConfig


def markov_sampler(vocab_size: int, seed: int, branch: int = 4,
                   skew: tuple[float, ...] = (0.55, 0.25, 0.15, 0.05)
                   ) -> Callable[[int, int, np.random.Generator], np.ndarray]:
    """A fixed random Markov chain: every token has ``branch`` successors with
    probabilities ``skew``. Returns sample(batch, length, rng) -> int32 ids.

    The chain is a function of ``seed`` alone — draft and target train on the
    SAME language while their parameter seeds differ.
    """
    chain_rng = np.random.default_rng(seed)
    successors = np.stack([
        chain_rng.choice(vocab_size, size=branch, replace=False)
        for _ in range(vocab_size)
    ])  # [V, branch]
    probs = np.asarray(skew, np.float64)
    probs = probs / probs.sum()

    def sample(batch: int, length: int, rng: np.random.Generator) -> np.ndarray:
        out = np.empty((batch, length), np.int32)
        out[:, 0] = rng.integers(0, vocab_size, batch)
        for t in range(1, length):
            pick = rng.choice(branch, size=batch, p=probs)
            out[:, t] = successors[out[:, t - 1], pick]
        return out

    return sample


def train_lm(cfg: ModelConfig, *, steps: int = 300, batch: int = 64,
             seq_len: int = 64, param_seed: int = 0, data_seed: int = 1234,
             lr: float = 3e-3, dtype=jnp.float32,
             log: Callable[[str], None] | None = None):
    """AdamW next-token training of a tiny llama on the Markov corpus.

    Returns (params, final_loss). float32 training (bf16 optimizer noise
    swamps these widths), cast to the caller's serving dtype afterwards.
    """
    import optax

    from ..parallel.pipeline import reference_loss_fn

    sample = markov_sampler(cfg.vocab_size, seed=data_seed)
    data_rng = np.random.default_rng(data_seed + 1)
    params = llama.init_params(cfg, jax.random.PRNGKey(param_seed), dtype)
    loss_fn = reference_loss_fn(cfg)
    tx = optax.adamw(lr)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, ids, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids, targets)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    loss = None
    for i in range(steps):
        seqs = sample(batch, seq_len + 1, data_rng)
        ids = jnp.asarray(seqs[:, :-1])
        targets = jnp.asarray(seqs[:, 1:])
        params, opt_state, loss = step(params, opt_state, ids, targets)
        if log is not None and (i + 1) % 100 == 0:
            log(f"{cfg.name}: step {i + 1}/{steps} loss={float(loss):.3f}")
    return params, float(loss) if loss is not None else float("nan")


def cast_params(params, dtype):
    """Cast a float tree to the serving dtype (e.g. bf16) leaf-by-leaf."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if hasattr(x, "astype") else x, params)
