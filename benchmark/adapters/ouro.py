"""The Ouro adapter: the block of ``models/ouro.py`` (one stack of layers run
``total_ut_steps`` times a token, a cache layer a pass a layer, sandwich
norms, an exit gate).

Its two yardstick halves are ``benchmark/ouro_weights.py`` and
``benchmark/ouro_reference.py`` (every pass recomputed over the whole
sequence, no cache), which import nothing from the program. The binding below
is the one place that does: the paged forward passes as
``runtime/scheduler.py`` drives them, over K and V pools ``total_ut_steps x
depth`` cache layers deep and a page table.

**The binding's pages are the program's own pool's.** The pools are a
``runtime/paged.py: PrefixKVPool``'s, built for the cut configuration, so
their depth is the ``kv_layers`` the served pool is built with (a pool as
deep as the model's layers would fail here as it would there); every row's
chain comes from ``extend_chain`` before a call for the tokens it will
write, and the page table is read from the chains. The resumed row's first
pages are the source row's, taken by reference (``ref_pages``) as a prefix
hit hands them out.

The controls: ``int4`` is the program given the int4 grid; ``fp8``,
``kv_int8``, ``loop_3`` and ``no_pass_norm`` are the reference's ``lower=``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import ouro_reference, ouro_weights
from cyberfabric_core_tpu.models import get_config, ouro
from cyberfabric_core_tpu.ops.rope import rope_tables
from cyberfabric_core_tpu.runtime.paged import PrefixKVPool

make_weights = ouro_weights.make_weights
#: controls that are the program itself, given lower weights
PROGRAM_CONTROLS = {"int4": ouro_weights.to_int4_grid}


def reference_logits(conf: dict, depth: int):
    """``(weights, ids [T], at [n], lower=None) -> logits [n, V]`` of one
    sequence; ``lower`` is one of ``ouro_reference.CONTROLS``."""
    kw = ouro_reference.reference_kwargs(conf, depth)

    def logits(w, ids, at, lower=None):
        return ouro_reference.forward_logits(w, ids, at, lower=lower, **kw)

    return logits


class Binding:
    """The program at the cut depth, for ``rows`` rows of ``max_seq_len``."""

    def __init__(self, conf: dict, depth: int, rows: int) -> None:
        serving = conf["serving"]
        self.cfg = cfg = get_config(serving["model_config"]).cut_to(depth)
        self.page, self.rows = serving["page"], rows
        # the judge's scenario is the chunk's (its longest row two chunks
        # and a seventh, then the decode steps), the served context the
        # chip's memory's: the table covers the longer of the two
        window = max(serving["max_seq_len"],
                     3 * conf["correctness"]["chunk"])
        self.pmax = -(-window // self.page)
        rope = rope_tables(cfg, window)
        self._head = jax.jit(lambda p, h: ouro.lm_head_logits(p, cfg, h))
        self._programs = (
            jax.jit(lambda p, ids, pools, table, hist, qlens:
                    ouro.forward_paged_mixed(p, cfg, ids, pools, table, hist,
                                             qlens, rope),
                    donate_argnums=(2,)),
            jax.jit(lambda p, ids, pools, table, lens:
                    ouro.forward_paged_decode(p, cfg, ids, pools, table,
                                              lens, rope),
                    donate_argnums=(2,)))
        #: the last call's gate values ``[passes, tokens of the call]``
        self.last_lam = None

    def new_state(self) -> dict:
        """The program's pool (a scratch page and every row's whole window)
        and no chain yet."""
        return {"pool": PrefixKVPool(
                    self.cfg, num_pages=self.rows * self.pmax + 1,
                    page_size=self.page),
                "chains": [[] for _ in range(self.rows)], "plan": None}

    def share_prefix(self, state: dict, row: int, source: int,
                     tokens: int) -> dict:
        """``row``'s first pages will be ``source``'s (taken when ``row``
        first runs: ``source`` has written them by then)."""
        return {**state, "plan": (row, source, tokens // self.page)}

    def _table(self, state: dict) -> jnp.ndarray:
        table = np.zeros((self.rows, self.pmax), np.int32)
        for r, chain in enumerate(state["chains"]):
            table[r, : len(chain)] = chain
        return jnp.asarray(table)

    def _grow(self, state: dict, row: int, end: int) -> None:
        plan = state["plan"]
        if plan and plan[0] == row and not state["chains"][row]:
            shared = state["chains"][plan[1]][: plan[2]]
            state["pool"].ref_pages(shared)     # a prefix hit's pages
            state["chains"][row] = list(shared)
        state["pool"].extend_chain(state["chains"][row], end)

    def mixed(self, params, ids: np.ndarray, state: dict, hist: np.ndarray,
              qlens: np.ndarray):
        """One mixed call; returns each row's hidden at its last position."""
        pool = state["pool"]
        for r in range(self.rows):
            if qlens[r]:
                self._grow(state, r, int(hist[r] + qlens[r]))
        hidden, pools, aux = self._programs[0](
            params, jnp.asarray(ids), pool.cache_operands(),
            self._table(state), jnp.asarray(hist), jnp.asarray(qlens))
        pool.adopt(pools)
        self.last_lam = aux["lam"]
        return ouro.gather_last_hidden(hidden, jnp.asarray(qlens)), state

    def decode(self, params, ids: np.ndarray, state: dict, lens: np.ndarray):
        pool = state["pool"]
        for r in range(self.rows):
            self._grow(state, r, int(lens[r]) + 1)
        hidden, pools, aux = self._programs[1](
            params, jnp.asarray(ids), pool.cache_operands(),
            self._table(state), jnp.asarray(lens))
        pool.adopt(pools)
        self.last_lam = aux["lam"]
        return hidden[:, 0], state

    def logits(self, params, out):
        return self._head(params, out)

    # no ``row_state``: a row's pages may be another row's by design (the
    # shared prefix), as in ``adapters/llama.py``


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
