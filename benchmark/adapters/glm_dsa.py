"""The GLM-5 adapter: the block of ``models/glm_dsa.py`` (kimi_k2's latent
attention with a learned indexer in front of it: a query attends over the
``index_topk`` keys its index heads score highest; leading dense layers,
sigmoid-routed experts beside a shared expert, run as one chip's share of the
experts and the vocabulary).

Its two yardstick halves are ``benchmark/glm_dsa_weights.py`` (the seeded
int8 tree; ``weights.to_int4_grid`` walks it) and
``benchmark/glm_dsa_reference.py`` (the plain forward: attention NOT
absorbed, no cache, every key scored and the chosen set a mask), which import
nothing from the program. The binding below is the one place that does: the
paged forward passes as ``runtime/scheduler.py`` drives them, over the latent
pool ``(layers, pages, page, 512 + 64 in 640 lanes)``, the index pool
``(layers, pages, page, 128)`` under the same page ids, and a page table.

**Routing is discontinuous, and so is the selection; the limit is set by
neither.** With seeded weights a query's 2048th and 2049th largest ``I(t,
s)`` lie closer than bfloat16 rounds the index key, as a token's 8th and 9th
``s + b`` do. So the program hands over its choices (``aux["experts"]``,
``aux["chosen"]``), the reference computes with THOSE experts and attends
over THOSE keys, and the adapter holds each choice to the reference's own
scores: the lowest ``s + b`` among the chosen experts may lie at most
``correctness.routing_epsilon`` under the reference's own 8th largest; the
lowest ``I(t, s)`` among the chosen keys at most
``correctness.selection_epsilon`` under the reference's own
``index_topk``-th largest, no chosen key lies past its query, and the COUNT
of keys chosen (and of distinct ones among them) is ``min(t + 1,
index_topk)`` exactly. The reference's ``lower=`` controls route and select
by their own scores.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import glm_dsa_reference, glm_dsa_weights
from cyberfabric_core_tpu.models import get_config, glm_dsa
from cyberfabric_core_tpu.ops.rope import rope_tables

#: controls that are the program itself, given lower weights
PROGRAM_CONTROLS = {"int4": glm_dsa_weights.to_int4_grid}

#: what the two halves share inside one judge process: the program's expert
#: choices and the keys its queries chose, for each row of its LAST run
#: (keyed by the row's tokens), and the largest shortfalls the reference has
#: read against them
_SHARED: dict = {"choices": {}, "worst_shortfall": 0.0, "worst_key": 0.0}

_PAD = 128      # the reference compiles at sequence lengths of whole _PADs


def make_weights(conf: dict, seed: int, depth: int) -> dict:
    _SHARED.update(choices={}, worst_shortfall=0.0, worst_key=0.0)
    return glm_dsa_weights.make_weights(conf, seed, depth)


def _key(tokens: np.ndarray) -> bytes:
    return np.asarray(tokens, np.int32).tobytes()


def reference_logits(conf: dict, depth: int, **overrides):
    """``(weights, ids [T], at [n], lower=None) -> logits [n, V]``: one whole
    forward of the sequence. Without ``lower`` the forward uses the experts
    and the keys the program chose for that sequence and holds them to the
    two epsilons and the count."""
    kw = {**glm_dsa_reference.reference_kwargs(conf, depth), **overrides}
    eps = conf["correctness"]["routing_epsilon"]
    key_eps = conf["correctness"]["selection_epsilon"]
    topk = kw["index_topk"]

    def logits(w, ids, at, lower=None):
        ids, at = np.asarray(ids, np.int32), np.asarray(at, np.int32)
        T = len(ids)
        total = -(-T // _PAD) * _PAD
        mine = None if lower is not None else _SHARED["choices"].get(_key(ids))
        if lower is None and mine is None:
            raise ValueError(f"no choices recorded for a sequence of {T} "
                             "tokens: the program did not run it")
        seq = np.zeros(total, np.int32)
        seq[:T] = ids
        experts = keys = None
        if mine is not None:
            chose, picked = mine
            experts = np.zeros((chose.shape[0], total, chose.shape[2]),
                               np.int32)
            experts[:, :T] = chose
            keys = np.full((picked.shape[0], total, topk), -1, np.int32)
            keys[:, :T, : picked.shape[2]] = picked
        out, short, key_short, miscount = glm_dsa_reference.forward_logits(
            w, jnp.asarray(seq), jnp.asarray(at),
            None if experts is None else jnp.asarray(experts),
            None if keys is None else jnp.asarray(keys), lower=lower, **kw)
        if mine is not None:
            off = int(np.asarray(miscount)[:, :T].max())
            if off:
                raise ValueError(
                    f"selection: a query's count of chosen keys (or of "
                    f"distinct visible ones among them) is {off} off "
                    f"min(t + 1, {topk}) in the sequence of {T} tokens")
            worst_key = float(np.asarray(key_short)[:, :T].max())
            _SHARED["worst_key"] = max(_SHARED["worst_key"], worst_key)
            if worst_key > key_eps:
                raise ValueError(
                    f"selection: a key the program chose lies {worst_key:.5f} "
                    f"under the reference's own {topk}th largest I(t, s) "
                    f"(epsilon {key_eps}) in the sequence of {T} tokens")
            worst = float(np.asarray(short)[:, :T].max()) \
                if short.shape[0] else 0.0
            _SHARED["worst_shortfall"] = max(_SHARED["worst_shortfall"], worst)
            if worst > eps:
                raise ValueError(
                    f"routing: an expert the program chose lies {worst:.4f} "
                    f"under the reference's own 8th largest s + b (epsilon "
                    f"{eps}) in the sequence of {T} tokens")
            print(f"correctness: glm_dsa adapter: largest shortfall so far "
                  f"of a chosen key under the reference's {topk}th I(t, s) "
                  f"{_SHARED['worst_key']:.6f} (epsilon {key_eps}), of a "
                  f"chosen expert under its 8th s + b "
                  f"{_SHARED['worst_shortfall']:.5f} (epsilon {eps}); every "
                  f"count exact", flush=True)
        return np.asarray(out)

    return logits


class Binding:
    """The program at the cut depth, for ``rows`` rows of ``max_seq_len``."""

    def __init__(self, conf: dict, depth: int, rows: int) -> None:
        serving = conf["serving"]
        base = get_config(serving["model_config"])
        self.cfg = dataclasses.replace(
            base, num_layers=depth,
            first_k_dense=min(base.first_k_dense, depth))
        self.page, self.rows = serving["page"], rows
        self.pmax = serving["max_seq_len"] // self.page
        self.rope = rope_tables(self.cfg, serving["max_seq_len"])
        pages = (depth, rows * self.pmax + 1, self.page)
        self.pool_shapes = ((*pages, self.cfg.latent_lanes),
                            (*pages, self.cfg.index_lanes))
        cfg = self.cfg
        self._head = jax.jit(lambda p, h: glm_dsa.lm_head_logits(p, cfg, h))
        self._programs: dict[bytes, tuple] = {}

    def new_state(self) -> dict:
        table = 1 + np.arange(self.rows * self.pmax, dtype=np.int32).reshape(
            self.rows, self.pmax)
        cfg = self.cfg
        experts = np.zeros((cfg.num_moe_layers, 0, cfg.experts_per_token),
                           np.int32)
        chosen = np.zeros((cfg.num_layers, 0, cfg.index_topk), np.int32)
        return {"pools": tuple(jnp.zeros(s, jnp.bfloat16)
                               for s in self.pool_shapes),
                "table": table,
                # per row: the tokens given so far, the experts each chose
                # and the keys each attended
                "tokens": [np.zeros(0, np.int32) for _ in range(self.rows)],
                "experts": [experts for _ in range(self.rows)],
                "chosen": [chosen for _ in range(self.rows)]}

    def share_prefix(self, state: dict, row: int, source: int,
                     tokens: int) -> dict:
        """``row``'s first pages are ``source``'s, in both arrays: a
        prefix-cache hit as the pool hands it out (one page id names the
        latent rows and the index keys)."""
        table = state["table"].copy()
        table[row, : tokens // self.page] = table[source, : tokens // self.page]
        return {**state, "table": table, "plan": (row, source, tokens)}

    def _compiled(self, table: np.ndarray) -> tuple:
        key = table.tobytes()
        if key not in self._programs:
            cfg, rope = self.cfg, self.rope
            self._programs[key] = (
                jax.jit(lambda p, ids, pools, hist, qlens:
                        glm_dsa.forward_paged_mixed(
                            p, cfg, ids, pools, jnp.asarray(table), hist,
                            qlens, rope), donate_argnums=(2,)),
                jax.jit(lambda p, ids, pools, lens:
                        glm_dsa.forward_paged_decode(
                            p, cfg, ids, pools, jnp.asarray(table), lens,
                            rope), donate_argnums=(2,)))
        return self._programs[key]

    def _took(self, state: dict, r: int, tokens: np.ndarray,
              experts: np.ndarray, chosen: np.ndarray) -> None:
        state["tokens"][r] = np.concatenate(
            [state["tokens"][r], tokens]).astype(np.int32)
        state["experts"][r] = np.concatenate([state["experts"][r], experts], 1)
        state["chosen"][r] = np.concatenate([state["chosen"][r], chosen], 1)
        _SHARED["choices"][_key(state["tokens"][r])] = (
            state["experts"][r], state["chosen"][r])

    def _copy(self, state: dict) -> dict:
        return {**state, "tokens": list(state["tokens"]),
                "experts": list(state["experts"]),
                "chosen": list(state["chosen"])}

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
            state["chosen"][row] = state["chosen"][source][:, :n]
        hidden, pools, aux = self._compiled(state["table"])[0](
            params, jnp.asarray(ids), state["pools"], jnp.asarray(hist),
            jnp.asarray(qlens))
        state["pools"] = pools
        experts, chosen = np.asarray(aux["experts"]), np.asarray(aux["chosen"])
        width = ids.shape[1]
        for r in range(self.rows):
            if qlens[r]:
                span = slice(r * width, r * width + qlens[r])
                self._took(state, r, ids[r, : qlens[r]], experts[:, span],
                           chosen[:, span])
        return (glm_dsa.gather_last_hidden(hidden, jnp.asarray(qlens)), state)

    def decode(self, params, ids: np.ndarray, state: dict, lens: np.ndarray):
        state = self._copy(state)
        hidden, pools, aux = self._compiled(state["table"])[1](
            params, jnp.asarray(ids), state["pools"], jnp.asarray(lens))
        state["pools"] = pools
        experts, chosen = np.asarray(aux["experts"]), np.asarray(aux["chosen"])
        for r in range(self.rows):
            self._took(state, r, ids[r], experts[:, r: r + 1],
                       chosen[:, r: r + 1])
        return hidden[:, 0], state

    def logits(self, params, out):
        return np.asarray(self._head(params, out), np.float32)

    def row_state(self, state: dict, row: int) -> np.ndarray:
        """What an idle row must get back unchanged: the latent rows and the
        index keys of the tokens it has been given."""
        kept = len(state["tokens"][row])
        pages = state["table"][row, : -(-kept // self.page) or 1]
        return np.concatenate([
            np.asarray(pool[:, pages]).reshape(
                pool.shape[0], -1, pool.shape[-1])[:, :kept].ravel()
            for pool in state["pools"]])


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
