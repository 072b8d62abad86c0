"""The Granite-4.0-H adapter: the stack of ``models/granite_hybrid.py`` (mamba
layers and attention layers in one stack, routed experts beside a shared MLP
after each).

Its two yardstick halves are ``benchmark/granite_hybrid_weights.py`` (the
seeded int8 tree; ``weights.to_int4_grid`` walks it) and
``benchmark/granite_hybrid_reference.py`` (the plain forward), which import
nothing from the program. The binding below is the one place that does: the
paged forward passes as ``runtime/scheduler.py`` drives them, over two K/V
pools ``(attention layers, pages, page, Hkv*D)``, a page table, and the state
slab ``{"ssm": (mamba layers, rows + 1, H, P, N), "conv": (mamba layers, rows
+ 1, K-1, C)}`` whose last row is the one snapshot the scenario needs. **Each
cache is as deep as the layers of its kind at the judged depth**: at depth 6,
one pool layer and five slab layers.

As for falcon_h1 the program keeps a row's recurrent state only where a mixed
call ended, so ``prefix_unit`` is the chunk: the resumed row takes the source
row's pages by aliasing page-table entries and its state from a snapshot of
the source row's state as the mixed call that reached the shared boundary
returned it (``runtime/paged.py``'s ``state_copy_row``). ``row_state`` shows a
row's state and conv tail, so the judge holds an idle row to coming back
unchanged.

**Routing is discontinuous, and the limit is not set by it** (as for sdar and
kimi): a token's 10th and 11th largest router logits lie within bfloat16's
rounding of each other for some percent of tokens a layer. So the program
hands over its choices (``aux["experts"]``), the reference computes with
THOSE experts and its own float32 logits as gates, and the adapter holds each
choice to the reference's own logits: the lowest logit among the chosen may
lie at most ``correctness.routing_epsilon`` under the reference's own 10th
largest. The reference's ``lower=`` controls route by their own logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import granite_hybrid_reference, granite_hybrid_weights
from cyberfabric_core_tpu.models import get_config, granite_hybrid
from cyberfabric_core_tpu.ops.rope import rope_tables
from cyberfabric_core_tpu.runtime.paged import state_copy_row

#: controls that are the program itself, given lower weights
PROGRAM_CONTROLS = {"int4": granite_hybrid_weights.to_int4_grid}

#: what the two halves share inside one judge process: the program's expert
#: choices for each row of its LAST run (keyed by the row's tokens), and the
#: largest shortfall the reference has read against them
_SHARED: dict = {"choices": {}, "worst_shortfall": 0.0}

_PAD = 128      # the reference compiles at sequence lengths of whole _PADs


def make_weights(conf: dict, seed: int, depth: int) -> dict:
    _SHARED.update(choices={}, worst_shortfall=0.0)
    return granite_hybrid_weights.make_weights(conf, seed, depth)


def _key(tokens: np.ndarray) -> bytes:
    return np.asarray(tokens, np.int32).tobytes()


def reference_logits(conf: dict, depth: int):
    """``(weights, ids [T], at [n], lower=None) -> logits [n, V]``: one whole
    forward of the sequence. Without ``lower`` the forward uses the experts
    the program chose for that sequence and holds them to the epsilon."""
    kw = granite_hybrid_reference.reference_kwargs(conf, depth)
    eps = conf["correctness"]["routing_epsilon"]

    def logits(w, ids, at, lower=None):
        ids, at = np.asarray(ids, np.int32), np.asarray(at, np.int32)
        T = len(ids)
        total = -(-T // _PAD) * _PAD
        mine = None if lower is not None else _SHARED["choices"].get(_key(ids))
        if lower is None and mine is None:
            raise ValueError(f"no expert choices recorded for a sequence of "
                             f"{T} tokens: the program did not run it")
        seq = np.zeros(total, np.int32)
        seq[:T] = ids
        full = None
        if mine is not None:
            full = np.zeros((mine.shape[0], total, mine.shape[2]), np.int32)
            full[:, :T] = mine
        out, short = granite_hybrid_reference.forward_logits(
            w, jnp.asarray(seq), jnp.asarray(at),
            None if full is None else jnp.asarray(full), lower=lower, **kw)
        if mine is not None:
            worst = float(np.asarray(short)[:, :T].max())
            _SHARED["worst_shortfall"] = max(_SHARED["worst_shortfall"], worst)
            if worst > eps:
                raise ValueError(
                    f"routing: an expert the program chose lies {worst:.4f} "
                    f"under the reference's own {kw['top_k']}th largest "
                    f"router logit (epsilon {eps}) in the sequence of {T} "
                    "tokens")
            print(f"correctness: granite_hybrid adapter: largest shortfall "
                  f"of a chosen expert under the reference's "
                  f"{kw['top_k']}th logit so far "
                  f"{_SHARED['worst_shortfall']:.5f} (epsilon {eps})",
                  flush=True)
        return np.asarray(out)

    return logits


class Binding:
    """The program at the cut depth, for ``rows`` rows of ``max_seq_len``."""

    def __init__(self, conf: dict, depth: int, rows: int) -> None:
        serving = conf["serving"]
        self.cfg = get_config(serving["model_config"]).cut_to(depth)
        self.page, self.rows = serving["page"], rows
        self.pmax = serving["max_seq_len"] // self.page
        self.rope = rope_tables(self.cfg, serving["max_seq_len"])
        #: as deep as the attention layers at this depth, not as the model
        self.pool_shape = (self.cfg.kv_layers, rows * self.pmax + 1,
                           self.page, self.cfg.num_kv_heads * self.cfg.head_dim)
        #: a prefix can be handed on where a chunk ended, nowhere else
        self.prefix_unit = conf["correctness"]["chunk"]
        self.snapshot_row = rows        # the slab's one row beyond the batch
        cfg = self.cfg
        self._head = jax.jit(
            lambda p, h: granite_hybrid.lm_head_logits(p, cfg, h))
        self._programs: dict[bytes, tuple] = {}

    def new_state(self) -> dict:
        """Empty pools and slab, a page table in which every row has its own
        pages (page 0 is scratch), and per row the tokens given so far with
        the experts each chose."""
        table = 1 + np.arange(self.rows * self.pmax, dtype=np.int32).reshape(
            self.rows, self.pmax)
        empty = np.zeros((self.cfg.num_layers, 0, self.cfg.experts_per_token),
                         np.int32)
        return {"pools": (jnp.zeros(self.pool_shape, jnp.bfloat16),
                          jnp.zeros(self.pool_shape, jnp.bfloat16)),
                "slab": granite_hybrid.init_state(self.cfg, self.rows + 1),
                "table": table, "plan": None, "snapshot_taken": False,
                "tokens": [np.zeros(0, np.int32) for _ in range(self.rows)],
                "experts": [empty for _ in range(self.rows)]}

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
                        granite_hybrid.forward_paged_mixed(
                            p, cfg, ids, pools, jnp.asarray(table), hist,
                            qlens, rope, state=slab),
                        donate_argnums=(2, 3)),
                jax.jit(lambda p, ids, pools, slab, lens:
                        granite_hybrid.forward_paged_decode(
                            p, cfg, ids, pools, jnp.asarray(table), lens,
                            rope, state=slab),
                        donate_argnums=(2, 3)))
        return self._programs[key]

    def _took(self, state: dict, r: int, tokens: np.ndarray,
              experts: np.ndarray) -> None:
        state["tokens"][r] = np.concatenate(
            [state["tokens"][r], tokens]).astype(np.int32)
        state["experts"][r] = np.concatenate([state["experts"][r], experts], 1)
        _SHARED["choices"][_key(state["tokens"][r])] = state["experts"][r]

    def mixed(self, params, ids: np.ndarray, state: dict, hist: np.ndarray,
              qlens: np.ndarray):
        """One mixed call, every row a lane; returns each row's hidden at
        its last position."""
        state = {**state, "tokens": list(state["tokens"]),
                 "experts": list(state["experts"])}
        slab, taken = state["slab"], state["snapshot_taken"]
        row, source, tokens = state["plan"] or (None, None, None)
        if tokens and qlens[row] and hist[row] == tokens:
            if not taken:       # the resumed row's first chunk
                raise ValueError(f"no call of row {source} ended at token "
                                 f"{tokens}: no state to resume from")
            slab = state_copy_row(slab, self.snapshot_row, row)
            state["tokens"][row] = state["tokens"][source][:tokens].copy()
            state["experts"][row] = state["experts"][source][:, :tokens]
        hidden, pools, slab, aux = self._compiled(state["table"])[0](
            params, jnp.asarray(ids), state["pools"], slab, jnp.asarray(hist),
            jnp.asarray(qlens))
        if tokens and qlens[source] and hist[source] + qlens[source] == tokens:
            slab = state_copy_row(slab, source, self.snapshot_row)
            taken = True        # as this call returned it
        experts = np.asarray(aux["experts"])
        width = ids.shape[1]
        for r in range(self.rows):
            if qlens[r]:
                at = r * width
                self._took(state, r, ids[r, : qlens[r]],
                           experts[:, at: at + qlens[r]])
        return (granite_hybrid.gather_last_hidden(hidden, jnp.asarray(qlens)),
                {**state, "pools": pools, "slab": slab,
                 "snapshot_taken": taken})

    def decode(self, params, ids: np.ndarray, state: dict, lens: np.ndarray):
        state = {**state, "tokens": list(state["tokens"]),
                 "experts": list(state["experts"])}
        hidden, pools, slab, aux = self._compiled(state["table"])[1](
            params, jnp.asarray(ids), state["pools"], state["slab"],
            jnp.asarray(lens))
        experts = np.asarray(aux["experts"])
        for r in range(self.rows):
            self._took(state, r, ids[r], experts[:, r: r + 1])
        return hidden[:, 0], {**state, "pools": pools, "slab": slab}

    def logits(self, params, out):
        return self._head(params, out)

    def row_state(self, state: dict, row: int) -> np.ndarray:
        """A row's recurrent state and conv tail, every mamba layer, flat."""
        return np.concatenate([np.asarray(leaf[:, row]).ravel()
                               for leaf in state["slab"].values()])


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
