"""The toy block's adapter: the llama binding for the pages, and beside it a
recurrent state ``[rows, STATE]`` that this file carries through mixed calls
(a chunk at a time, in closed form), the shared prefix and decode steps. As a
real recurrent program would, it has a row's state only where a call ended:
``prefix_unit`` is the chunk, and the snapshot for the resumed row is the
source row's state as the mixed call that reached the shared boundary
returned it."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as llama_weights
from benchmark.adapters import llama as llama_adapter

from . import STATE, reference, weights

make_weights = weights.make_weights
reference_logits = reference.reference_logits
PROGRAM_CONTROLS = {"int4": llama_weights.to_int4_grid}


@jax.jit
def _advance(params, s, ids, n):
    """Each row's state after the first ``n[r]`` tokens of ``ids[r]``:
    ``decay**n * s + sum_t decay**(n-1-t) * state_in[ids_t]``."""
    table, decay = params["state_in"], params["decay"]
    rows = table["qe"][ids].astype(jnp.float32) * table["se"][ids][..., None]
    t = jnp.arange(ids.shape[1])[None, :, None]
    left = (n[:, None, None] - 1 - t).astype(jnp.float32)
    weight = jnp.where(left >= 0, decay[None, None, :] ** jnp.maximum(left, 0.0),
                       0.0)
    return (decay[None, :] ** n[:, None].astype(jnp.float32) * s
            + (weight * rows).sum(axis=1))


@jax.jit
def _state_logits(params, s):
    out = params["state_out"]
    return s @ (out["q"].astype(jnp.float32) * out["s"][None, :])


class Binding:
    def __init__(self, conf: dict, depth: int, rows: int) -> None:
        self.base = llama_adapter.bind(conf, depth, rows)
        self.rows = rows
        #: a prefix can be handed on where a chunk ended, nowhere else
        self.prefix_unit = conf["correctness"]["chunk"]

    def new_state(self) -> dict:
        return {"base": self.base.new_state(),
                "s": jnp.zeros((self.rows, STATE), jnp.float32),
                "plan": None, "snapshot": None}

    def share_prefix(self, state: dict, row: int, source: int,
                     tokens: int) -> dict:
        return {**state, "plan": (row, source, tokens),
                "base": self.base.share_prefix(state["base"], row, source,
                                               tokens)}

    def mixed(self, params, ids, state: dict, hist, qlens):
        last, base = self.base.mixed(params["base"], ids, state["base"], hist,
                                     qlens)
        s, snapshot = state["s"], state["snapshot"]
        row, source, tokens = state["plan"] or (None, None, None)
        if tokens and qlens[row] and hist[row] == tokens:
            if snapshot is None:                    # the resumed row's first chunk
                raise ValueError(f"no call of row {source} ended at token "
                                 f"{tokens}: no state to resume from")
            s = s.at[row].set(snapshot)
        s = _advance(params, s, jnp.asarray(ids), jnp.asarray(qlens))
        if tokens and qlens[source] and hist[source] + qlens[source] == tokens:
            snapshot = s[source]                    # as this call returned it
        return (last, s), {**state, "base": base, "s": s, "snapshot": snapshot}

    def decode(self, params, ids, state: dict, lens):
        last, base = self.base.decode(params["base"], ids, state["base"], lens)
        s = _advance(params, state["s"], jnp.asarray(ids),
                     jnp.ones(self.rows, jnp.int32))
        return (last, s), {**state, "base": base, "s": s}

    def logits(self, params, out):
        last, s = out
        return self.base.logits(params["base"], last) + _state_logits(params, s)

    def row_state(self, state: dict, row: int):
        return state["s"][row]


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
