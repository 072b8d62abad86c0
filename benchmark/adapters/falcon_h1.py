"""The Falcon-H1 adapter: the block of ``models/falcon_h1.py`` (a Mamba-2
mixer beside GQA attention in every block).

Its two yardstick halves are ``benchmark/falcon_h1_weights.py`` (the seeded
int8 tree; ``weights.to_int4_grid`` walks it) and
``benchmark/falcon_h1_reference.py`` (the plain forward), which import nothing
from the program. The binding below is the one place that does: the paged
forward passes as ``runtime/scheduler.py`` drives them, over two K/V pools
``(layers, pages, page, Hkv*D)``, a page table, and the state slab ``{"ssm":
(layers, rows + 1, H, P, N), "conv": (layers, rows + 1, K-1, C)}`` whose last
row is the one snapshot the scenario needs.

The program keeps a row's recurrent state only where a mixed call ended, so
``prefix_unit`` is the chunk: the resumed row takes the source row's pages by
aliasing page-table entries, as llama's does, and its state from a snapshot
of the source row's state **as the mixed call that reached the shared
boundary returned it** (``runtime/paged.py``'s ``state_copy_row``, the copy the
pool makes at a chunk boundary and again at admission). ``row_state`` shows a
row's state and conv tail, so the judge holds an idle row to coming back
unchanged.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import falcon_h1_reference, falcon_h1_weights
from cyberfabric_core_tpu.models import falcon_h1, get_config
from cyberfabric_core_tpu.ops.rope import rope_frequencies
from cyberfabric_core_tpu.runtime.paged import state_copy_row

make_weights = falcon_h1_weights.make_weights
reference_logits = falcon_h1_reference.reference_logits
#: controls that are the program itself, given lower weights
PROGRAM_CONTROLS = {"int4": falcon_h1_weights.to_int4_grid}


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
                           self.cfg.num_kv_heads * self.cfg.head_dim)
        #: a prefix can be handed on where a chunk ended, nowhere else
        self.prefix_unit = conf["correctness"]["chunk"]
        self.snapshot_row = rows        # the slab's one row beyond the batch
        cfg = self.cfg
        self._head = jax.jit(lambda p, h: falcon_h1.lm_head_logits(p, cfg, h))
        self._programs: dict[bytes, tuple] = {}

    def new_state(self) -> dict:
        """Empty pools and slab, and a page table in which every row has its
        own pages (page 0 is scratch)."""
        table = 1 + np.arange(self.rows * self.pmax, dtype=np.int32).reshape(
            self.rows, self.pmax)
        return {"pools": (jnp.zeros(self.pool_shape, jnp.bfloat16),
                          jnp.zeros(self.pool_shape, jnp.bfloat16)),
                "slab": falcon_h1.init_state(self.cfg, self.rows + 1),
                "table": table, "plan": None, "snapshot_taken": False}

    def share_prefix(self, state: dict, row: int, source: int,
                     tokens: int) -> dict:
        """``row``'s first pages are ``source``'s, and its state will be the
        snapshot taken when ``source`` reaches ``tokens``."""
        table = state["table"].copy()
        table[row, : tokens // self.page] = table[source, : tokens // self.page]
        return {**state, "table": table, "plan": (row, source, tokens)}

    def _compiled(self, table: np.ndarray) -> tuple:
        """The page table is a constant of the jitted programs, as in the
        llama adapter: one pair of programs a table."""
        key = table.tobytes()
        if key not in self._programs:
            cfg, rope = self.cfg, self.rope
            self._programs[key] = (
                jax.jit(lambda p, ids, pools, slab, hist, qlens:
                        falcon_h1.forward_paged_mixed(
                            p, cfg, ids, pools, jnp.asarray(table), hist,
                            qlens, rope, state=slab),
                        donate_argnums=(2, 3)),
                jax.jit(lambda p, ids, pools, slab, lens:
                        falcon_h1.forward_paged_decode(
                            p, cfg, ids, pools, jnp.asarray(table), lens,
                            rope, state=slab),
                        donate_argnums=(2, 3)))
        return self._programs[key]

    def mixed(self, params, ids: np.ndarray, state: dict, hist: np.ndarray,
              qlens: np.ndarray):
        """One mixed call; returns each row's hidden at its last position."""
        slab, taken = state["slab"], state["snapshot_taken"]
        row, source, tokens = state["plan"] or (None, None, None)
        if tokens and qlens[row] and hist[row] == tokens:
            if not taken:       # the resumed row's first chunk
                raise ValueError(f"no call of row {source} ended at token "
                                 f"{tokens}: no state to resume from")
            slab = state_copy_row(slab, self.snapshot_row, row)
        hidden, pools, slab = self._compiled(state["table"])[0](
            params, jnp.asarray(ids), state["pools"], slab, jnp.asarray(hist),
            jnp.asarray(qlens))
        if tokens and qlens[source] and hist[source] + qlens[source] == tokens:
            slab = state_copy_row(slab, source, self.snapshot_row)
            taken = True        # as this call returned it
        return (falcon_h1.gather_last_hidden(hidden, jnp.asarray(qlens)),
                {**state, "pools": pools, "slab": slab,
                 "snapshot_taken": taken})

    def decode(self, params, ids: np.ndarray, state: dict, lens: np.ndarray):
        hidden, pools, slab = self._compiled(state["table"])[1](
            params, jnp.asarray(ids), state["pools"], state["slab"],
            jnp.asarray(lens))
        return hidden[:, 0], {**state, "pools": pools, "slab": slab}

    def logits(self, params, out):
        return self._head(params, out)

    def row_state(self, state: dict, row: int) -> np.ndarray:
        """A row's recurrent state and conv tail, every layer, flat."""
        return np.concatenate([np.asarray(leaf[:, row]).ravel()
                               for leaf in state["slab"].values()])


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
