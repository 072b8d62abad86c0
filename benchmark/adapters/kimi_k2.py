"""The Kimi-K2 adapter: the block of ``models/kimi_k2.py`` (latent attention
on a latent page, a leading dense layer, sigmoid-routed experts beside a
shared expert, run as one chip's share of the experts and the vocabulary).

Its two yardstick halves are ``benchmark/kimi_k2_weights.py`` (the seeded
int8 tree; ``weights.to_int4_grid`` walks it) and
``benchmark/kimi_k2_reference.py`` (the plain forward: attention NOT absorbed,
no cache), which import nothing from the program. The binding below is the
one place that does: the paged forward passes as ``runtime/scheduler.py``
drives them, over ONE latent pool ``(layers, pages, page, 512 + 64)`` and a
page table. The program's attention is absorbed and reads the latent cache
in every call, a chunk's too; the reference expands K and V for every
position, so the comparison holds the absorption, the cache and the kernels
at once.

**Routing is discontinuous, and the limit is not set by it** (as for sdar):
a token's 8th and 9th largest ``s + b`` lie within bfloat16's rounding of
each other for some percent of tokens a layer. So the program hands over its
choices (``aux["experts"]``), the reference computes with THOSE experts and
its own float32 scores as gates, and the adapter holds each choice to the
reference's own scores: the lowest ``s + b`` among the chosen may lie at most
``correctness.routing_epsilon`` under the reference's own 8th largest. The
reference's ``lower=`` controls route by their own scores.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import kimi_k2_reference, kimi_k2_weights
from cyberfabric_core_tpu.models import get_config, kimi_k2
from cyberfabric_core_tpu.ops.rope import rope_tables

#: controls that are the program itself, given lower weights
PROGRAM_CONTROLS = {"int4": kimi_k2_weights.to_int4_grid,
                    "router_int8": kimi_k2_weights.router_on_int8_grid}

#: what the two halves share inside one judge process: the program's expert
#: choices for each row of its LAST run (keyed by the row's tokens), and the
#: largest shortfall the reference has read against them
_SHARED: dict = {"choices": {}, "worst_shortfall": 0.0}

_PAD = 128      # the reference compiles at sequence lengths of whole _PADs


def make_weights(conf: dict, seed: int, depth: int) -> dict:
    _SHARED.update(choices={}, worst_shortfall=0.0)
    return kimi_k2_weights.make_weights(conf, seed, depth)


def _key(tokens: np.ndarray) -> bytes:
    return np.asarray(tokens, np.int32).tobytes()


def reference_logits(conf: dict, depth: int, **overrides):
    """``(weights, ids [T], at [n], lower=None) -> logits [n, V]``: one whole
    forward of the sequence. Without ``lower`` the forward uses the experts
    the program chose for that sequence and holds them to the epsilon."""
    kw = {**kimi_k2_reference.reference_kwargs(conf, depth), **overrides}
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
        out, short = kimi_k2_reference.forward_logits(
            w, jnp.asarray(seq), jnp.asarray(at),
            None if full is None else jnp.asarray(full), lower=lower, **kw)
        if mine is not None and mine.shape[0]:
            worst = float(np.asarray(short)[:, :T].max())
            _SHARED["worst_shortfall"] = max(_SHARED["worst_shortfall"], worst)
            if worst > eps:
                raise ValueError(
                    f"routing: an expert the program chose lies {worst:.4f} "
                    f"under the reference's own 8th largest s + b (epsilon "
                    f"{eps}) in the sequence of {T} tokens")
            print(f"correctness: kimi_k2 adapter: largest shortfall of a "
                  f"chosen expert under the reference's 8th s + b so far "
                  f"{_SHARED['worst_shortfall']:.5f} (epsilon {eps})",
                  flush=True)
        return np.asarray(out)

    return logits


class Binding:
    """The program at the cut depth, for ``rows`` rows of ``max_seq_len``."""

    def __init__(self, conf: dict, depth: int, rows: int) -> None:
        serving = conf["serving"]
        self.cfg = dataclasses.replace(get_config(serving["model_config"]),
                                       num_layers=depth)
        self.page, self.rows = serving["page"], rows
        self.pmax = serving["max_seq_len"] // self.page
        self.rope = rope_tables(self.cfg, serving["max_seq_len"])
        self.pool_shape = (depth, rows * self.pmax + 1, self.page,
                           self.cfg.latent_lanes)
        cfg = self.cfg
        self._head = jax.jit(lambda p, h: kimi_k2.lm_head_logits(p, cfg, h))
        self._programs: dict[bytes, tuple] = {}

    def new_state(self) -> dict:
        table = 1 + np.arange(self.rows * self.pmax, dtype=np.int32).reshape(
            self.rows, self.pmax)
        empty = np.zeros((self.cfg.num_moe_layers, 0,
                          self.cfg.experts_per_token), np.int32)
        return {"pools": (jnp.zeros(self.pool_shape, jnp.bfloat16),),
                "table": table,
                # per row: the tokens given so far and the experts each chose
                "tokens": [np.zeros(0, np.int32) for _ in range(self.rows)],
                "experts": [empty for _ in range(self.rows)]}

    def share_prefix(self, state: dict, row: int, source: int,
                     tokens: int) -> dict:
        """``row``'s first pages are ``source``'s: a prefix-cache hit as the
        pool hands it out (the allocator counts pages, whatever they hold)."""
        table = state["table"].copy()
        table[row, : tokens // self.page] = table[source, : tokens // self.page]
        return {**state, "table": table, "plan": (row, source, tokens)}

    def _compiled(self, table: np.ndarray) -> tuple:
        key = table.tobytes()
        if key not in self._programs:
            cfg, rope = self.cfg, self.rope
            self._programs[key] = (
                jax.jit(lambda p, ids, pools, hist, qlens:
                        kimi_k2.forward_paged_mixed(
                            p, cfg, ids, pools, jnp.asarray(table), hist,
                            qlens, rope), donate_argnums=(2,)),
                jax.jit(lambda p, ids, pools, lens:
                        kimi_k2.forward_paged_decode(
                            p, cfg, ids, pools, jnp.asarray(table), lens,
                            rope), donate_argnums=(2,)))
        return self._programs[key]

    def _took(self, state: dict, r: int, tokens: np.ndarray,
              experts: np.ndarray) -> None:
        state["tokens"][r] = np.concatenate(
            [state["tokens"][r], tokens]).astype(np.int32)
        state["experts"][r] = np.concatenate([state["experts"][r], experts], 1)
        _SHARED["choices"][_key(state["tokens"][r])] = state["experts"][r]

    def _copy(self, state: dict) -> dict:
        return {**state, "tokens": list(state["tokens"]),
                "experts": list(state["experts"])}

    def mixed(self, params, ids: np.ndarray, state: dict, hist: np.ndarray,
              qlens: np.ndarray):
        """One mixed call, every row a lane; returns each row's hidden at
        its last position."""
        state = self._copy(state)
        plan = state.get("plan")
        if plan and hist[plan[0]] and not len(state["tokens"][plan[0]]):
            row, source, n = plan       # the resumed row's first call
            state["tokens"][row] = state["tokens"][source][:n].copy()
            state["experts"][row] = state["experts"][source][:, :n]
        hidden, pools, aux = self._compiled(state["table"])[0](
            params, jnp.asarray(ids), state["pools"], jnp.asarray(hist),
            jnp.asarray(qlens))
        state["pools"] = pools
        experts = np.asarray(aux["experts"])
        width = ids.shape[1]
        for r in range(self.rows):
            if qlens[r]:
                at = r * width
                self._took(state, r, ids[r, : qlens[r]],
                           experts[:, at: at + qlens[r]])
        return (kimi_k2.gather_last_hidden(hidden, jnp.asarray(qlens)), state)

    def decode(self, params, ids: np.ndarray, state: dict, lens: np.ndarray):
        state = self._copy(state)
        hidden, pools, aux = self._compiled(state["table"])[1](
            params, jnp.asarray(ids), state["pools"], jnp.asarray(lens))
        state["pools"] = pools
        experts = np.asarray(aux["experts"])
        for r in range(self.rows):
            self._took(state, r, ids[r], experts[:, r: r + 1])
        return hidden[:, 0], state

    def logits(self, params, out):
        return np.asarray(self._head(params, out), np.float32)

    def row_state(self, state: dict, row: int) -> np.ndarray:
        """What an idle row must get back unchanged: the latent rows of the
        tokens it has been given."""
        kept = len(state["tokens"][row])
        pages = state["table"][row, : -(-kept // self.page) or 1]
        rows = np.asarray(state["pools"][0][:, pages]).reshape(
            self.pool_shape[0], -1, self.pool_shape[-1])[:, :kept]
        return rows.ravel()


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
