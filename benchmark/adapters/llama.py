"""The llama-family adapter: the block of ``models/llama.py`` (mistral-7b,
qwen2-7b). A configuration whose ``correctness`` block names no adapter gets
this one.

Its two yardstick halves are ``benchmark/weights.py`` (the seeded int8 tree
and the int4 grid) and ``benchmark/reference.py`` (the plain forward), which
import nothing from the program. The binding below is the one place that
does: the paged forward passes as ``runtime/scheduler.py`` drives them, over
two K/V pools ``(layers, pages, page, Hkv, D)`` and a page table.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference, weights
from cyberfabric_core_tpu.models import get_config, llama
from cyberfabric_core_tpu.ops.rope import rope_frequencies

make_weights = weights.make_weights
#: controls that are the program itself, given lower weights
PROGRAM_CONTROLS = {"int4": weights.to_int4_grid}


def reference_logits(conf: dict, depth: int):
    """``(weights, ids [T], at [n], lower=None) -> logits [n, V]`` of one
    sequence; ``lower`` is one of reference.py's controls."""
    kw = reference.reference_kwargs(conf, depth)

    def logits(w, ids, at, lower=None):
        return reference.forward_logits(w, ids, at, lower=lower, **kw)

    return logits


class Binding:
    """The program at the cut depth, for ``rows`` rows of ``max_seq_len``."""

    def __init__(self, conf: dict, depth: int, rows: int) -> None:
        serving = conf["serving"]
        self.cfg = dataclasses.replace(get_config(serving["model_config"]),
                                       num_layers=depth)
        self.page, self.rows = serving["page"], rows
        self.pmax = serving["max_seq_len"] // self.page
        self.rope = rope_frequencies(self.cfg.head_dim, serving["max_seq_len"],
                                     self.cfg.rope_theta)
        self.pool_shape = (depth, rows * self.pmax + 1, self.page,
                           self.cfg.num_kv_heads, self.cfg.head_dim)
        cfg = self.cfg
        self._head = jax.jit(lambda p, h: llama.lm_head_logits(p, cfg, h))
        self._programs: dict[bytes, tuple] = {}

    def new_state(self) -> dict:
        """Empty pools, and a page table in which every row has its own pages
        (page 0 is scratch)."""
        table = 1 + np.arange(self.rows * self.pmax, dtype=np.int32).reshape(
            self.rows, self.pmax)
        return {"pools": (jnp.zeros(self.pool_shape, jnp.bfloat16),
                          jnp.zeros(self.pool_shape, jnp.bfloat16)),
                "table": table}

    def share_prefix(self, state: dict, row: int, source: int,
                     tokens: int) -> dict:
        """``row``'s first pages are ``source``'s: a prefix-cache hit as the
        pool hands it out."""
        table = state["table"].copy()
        table[row, : tokens // self.page] = table[source, : tokens // self.page]
        return {**state, "table": table}

    def _compiled(self, table: np.ndarray) -> tuple:
        """The page table is a constant of the jitted programs (made inside
        the traced function, as before the seam: a device array closed over
        would be hoisted to an argument, another program): one pair of
        programs a table."""
        key = table.tobytes()
        if key not in self._programs:
            cfg, rope = self.cfg, self.rope
            self._programs[key] = (
                jax.jit(lambda p, ids, pools, hist, qlens:
                        llama.forward_paged_mixed(p, cfg, ids, pools,
                                                  jnp.asarray(table), hist,
                                                  qlens, rope)),
                jax.jit(lambda p, ids, pools, lens:
                        llama.forward_paged_decode(p, cfg, ids, pools,
                                                   jnp.asarray(table), lens,
                                                   rope)))
        return self._programs[key]

    def mixed(self, params, ids: np.ndarray, state: dict, hist: np.ndarray,
              qlens: np.ndarray):
        """One mixed call; returns each row's hidden at its last position."""
        hidden, pools = self._compiled(state["table"])[0](
            params, jnp.asarray(ids), state["pools"], jnp.asarray(hist),
            jnp.asarray(qlens))
        return (llama.gather_last_hidden(hidden, jnp.asarray(qlens)),
                {**state, "pools": pools})

    def decode(self, params, ids: np.ndarray, state: dict, lens: np.ndarray):
        hidden, pools = self._compiled(state["table"])[1](
            params, jnp.asarray(ids), state["pools"], jnp.asarray(lens))
        return hidden[:, 0], {**state, "pools": pools}

    def logits(self, params, out):
        return self._head(params, out)

    # no ``row_state``: a row's pages may be another row's by design (the
    # shared prefix), so "this row's state" is not a thing the pools can show


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
