"""Seeded int8 weights of the Laguna block for the correctness check, made by
the benchmark.

Nothing here comes from the program but the *layout* of the tree
(``models/laguna.py``): ``full`` and ``window`` stack the attention matrices
of the layers of their kind (``wq`` [n, hidden, heads x D] and ``wo`` differ
in shape between the two; ``wk``, ``wv``; the gate a head ``w_gate`` [n,
hidden, heads]) as ``{"q": int8 [n, in, out], "s": f32 [n, out]}``;
``dense`` the leading dense layers' two norms and ``gate``/``up``/``down``;
``layers`` the expert layers' two norms, ``shared_*``, ``moe_*`` for the
experts HELD and the float32 ``router`` over ALL the experts routed over (no
selection bias: the config names none). ``weights.to_int4_grid`` walks every
``{"q", "s"}`` node of it.

Every matrix is drawn at ``fan_in^-1/2``, the router and the gate too: a
token's router logits over the 256 experts are then of unit spread, and a
head's gate logit is of unit spread around 0 (no bias), so the gates lie in
about 0.1-0.9 and a gate left out, or applied to the wrong head, moves every
logits row. Norm weights are near 1, so a dropped one shows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import weights as base
from .kimi_k2_weights import _norm, _stack_leaf
from .laguna_reference import layer_kinds

seed_key = base.seed_key
to_int4_grid = base.to_int4_grid


def _attention(key, n: int, hidden: int, heads: int, kv: int, dim: int,
               gate: bool) -> dict:
    k = jax.random.split(key, 5)
    tree = {"wq": _stack_leaf(k[0], n, (), hidden, heads * dim),
            "wk": _stack_leaf(k[1], n, (), hidden, kv * dim),
            "wv": _stack_leaf(k[2], n, (), hidden, kv * dim),
            "wo": _stack_leaf(k[3], n, (), heads * dim, hidden)}
    if gate:
        tree["w_gate"] = _stack_leaf(k[4], n, (), hidden, heads)
    return tree


@functools.partial(jax.jit, static_argnames=(
    "hidden", "inter", "moe_inter", "shared", "vocab", "n_full", "n_window",
    "n_dense", "n_moe", "heads_full", "heads_window", "kv", "dim", "gate",
    "experts", "held"))
def _make(key, *, hidden, inter, moe_inter, shared, vocab, n_full, n_window,
          n_dense, n_moe, heads_full, heads_window, kv, dim, gate, experts,
          held):
    k = jax.random.split(key, 24)

    def norms(at: int, n: int) -> dict:
        return {"attn_norm": _norm(k[at], n, hidden),
                "mlp_norm": _norm(k[at + 1], n, hidden)}

    tree = {
        "full": _attention(k[0], n_full, hidden, heads_full, kv, dim, gate),
        "window": _attention(k[1], n_window, hidden, heads_window, kv, dim,
                             gate),
        "dense": {**norms(2, n_dense),
                  "gate": _stack_leaf(k[4], n_dense, (), hidden, inter),
                  "up": _stack_leaf(k[5], n_dense, (), hidden, inter),
                  "down": _stack_leaf(k[6], n_dense, (), inter, hidden)}}
    if n_moe:
        tree["layers"] = {
            **norms(7, n_moe),
            "router": jax.random.normal(k[9], (n_moe, hidden, experts),
                                        jnp.float32) * hidden ** -0.5,
            "shared_gate": _stack_leaf(k[10], n_moe, (), hidden, shared),
            "shared_up": _stack_leaf(k[11], n_moe, (), hidden, shared),
            "shared_down": _stack_leaf(k[12], n_moe, (), shared, hidden),
            "moe_gate": _stack_leaf(k[13], n_moe, (held,), hidden, moe_inter),
            "moe_up": _stack_leaf(k[14], n_moe, (held,), hidden, moe_inter),
            "moe_down": _stack_leaf(k[15], n_moe, (held,), moe_inter, hidden)}
    embed = jax.random.normal(k[16], (vocab, hidden), jnp.float32)
    qe, se = base._quantize(embed, axis=1)
    head = _stack_leaf(k[17], 1, (), hidden, vocab)
    return {**tree, "embed": {"qe": qe, "se": se},
            "final_norm": _norm(k[18], hidden),
            "lm_head": {"q": head["q"][0], "s": head["s"][0]}}


def make_weights(cfg: dict, seed: int, layers: int) -> dict:
    """The whole tree on the device from the seed, ``layers`` deep. ``cfg``
    is the configuration file: the published keys, of which ``num_experts``
    and ``vocab_size`` are the chip's share (experts held, vocabulary rows
    held) and ``serving.experts_routed`` the router's published width."""
    full, heads, dense = layer_kinds(cfg, layers)
    by_kind = {kind: {h for f, h in zip(full, heads) if f == kind}
               for kind in (True, False)}
    if any(len(v) > 1 for v in by_kind.values()):
        raise ValueError(f"layers of one kind differ in their query heads: "
                         f"{by_kind}")
    return _make(
        seed_key(seed), hidden=cfg["hidden_size"],
        inter=cfg["intermediate_size"],
        moe_inter=cfg["moe_intermediate_size"],
        shared=cfg["shared_expert_intermediate_size"],
        vocab=cfg["vocab_size"], n_full=sum(full),
        n_window=layers - sum(full), n_dense=sum(dense),
        n_moe=layers - sum(dense),
        heads_full=next(iter(by_kind[True]), cfg["num_attention_heads"]),
        heads_window=next(iter(by_kind[False]), cfg["num_attention_heads"]),
        kv=cfg["num_key_value_heads"], dim=cfg["head_dim"],
        gate=cfg.get("gating") == "per-head",
        experts=cfg["serving"]["experts_routed"], held=cfg["num_experts"])
