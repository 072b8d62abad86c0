"""The SDAR-MoE adapter: the block of ``models/sdar_moe.py`` (128 routed
experts top-8, generation by diffusion over blocks).

Its two yardstick halves are ``benchmark/sdar_weights.py`` (the seeded int8
tree; ``weights.to_int4_grid`` walks it) and ``benchmark/sdar_reference.py``
(the plain forward under the block mask), which import nothing from the
program. The binding below is the one place that does: the paged forward
passes as ``runtime/scheduler.py`` drives them, over two merged K/V pools
``(layers, pages, page, Hkv*D)`` and a page table.

**What a logits row is.** The judge keys a row by ``(row, p)``: the program
has been given the tokens up to and including position ``p``. A model that
generates by blocks has no next-token logits; what a left-to-right denoising
run produces there is **the logits at position ``p + 1`` of the sequence
``seq[:p+1]`` followed by ``[MASK]`` up to the end of ``p + 1``'s block** (a
whole block of masks where ``p + 1`` opens one). Both sides are held to that:
the reference by a whole forward of that sequence, the program by what its
scheduler would do. The binding keeps every row's OPEN BLOCK (the tokens
past its last whole block) in the opaque state: ``mixed`` sends a chunk's
whole blocks through the lane (their K/V is kept), a one-token rider through
the decode group (a commit forward, where the token fills its block), holds
the leftover, and reads the row from a denoise forward of leftover + masks,
whose own K/V the next forward overwrites; ``decode`` puts the forced token
into the open block, runs a commit forward where that filled it, then the
denoise forward.

**Routing is discontinuous, and the limit is not set by it.** A token's 8th
and 9th router scores lie within bfloat16's rounding of each other for some
percent of tokens a layer; the program (bfloat16 activations) then picks
another expert than float32 would, which moves that logits row by several
times what rounding alone does, on some seeds and not on others. So the
program hands over its choices (``aux["experts"]``), the reference computes
with THOSE experts and its own float32 gates, and the adapter holds each
choice to the reference's own scores: the lowest-scored chosen expert may lie
at most ``correctness.routing_epsilon`` (router logits) under the reference's
own 8th score. A choice further out fails the run: a forward that kept the
wrong K/V reads 2 and more (``tests/rehearsal/sdar_broken.py``). The
``router_int8`` control is NOT caught there: its largest shortfall reads
0.038-0.050 on the chip where the sound runs read 0.015-0.023 (the
configuration's ``limit_why``); the logits limit catches it, since it flips
experts for many tokens. The reference's ``lower=`` controls route by their
own scores.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import sdar_reference, sdar_weights
from cyberfabric_core_tpu.models import get_config, sdar_moe
from cyberfabric_core_tpu.models.llama import DecodeGroup
from cyberfabric_core_tpu.ops.rope import rope_frequencies

#: controls that are the program itself, given lower weights
PROGRAM_CONTROLS = {"int4": sdar_weights.to_int4_grid,
                    "router_int8": sdar_weights.router_on_int8_grid}

#: what the two halves of the adapter share inside one judge process: the
#: program's expert choices for each logits row of its LAST run (keyed by the
#: row's tokens), and the largest shortfall the reference has read against them
_SHARED: dict = {"choices": {}, "worst_shortfall": 0.0}


def make_weights(conf: dict, seed: int, depth: int) -> dict:
    _SHARED.update(choices={}, worst_shortfall=0.0)
    return sdar_weights.make_weights(conf, seed, depth)


def _key(tokens: np.ndarray) -> bytes:
    return np.asarray(tokens, np.int32).tobytes()


def _padded(ids: np.ndarray, p: int, total: int, mask_id: int,
            choices: np.ndarray | None):
    """``seq[:p+1]`` then masks to ``total`` (whole blocks; what lies past
    ``p + 1``'s block is invisible to it), and the choices padded alike.
    ``total`` is a multiple of 128, so a judge's run compiles the forward at
    a handful of lengths."""
    total = -(-total // 128) * 128
    seq = np.full(total, mask_id, np.int32)
    seq[: p + 1] = ids[: p + 1]
    if choices is None:
        return seq, None
    full = np.zeros((choices.shape[0], total, choices.shape[2]), np.int32)
    full[:, : choices.shape[1]] = choices
    return seq, full


def reference_logits(conf: dict, depth: int):
    """``(weights, ids [T], at [n], lower=None) -> logits [n, V]``: row ``p``
    of ``at`` is the logits at ``p + 1`` of ``ids[:p+1]`` + masks, one whole
    forward each. Without ``lower`` the forward uses the experts the program
    chose for that row and holds them to the epsilon."""
    kw = sdar_reference.reference_kwargs(conf, depth)
    block = kw["block"]
    mask_id = conf["serving"]["mask_token_id"]
    eps = conf["correctness"]["routing_epsilon"]

    def logits(w, ids, at, lower=None):
        ids, at = np.asarray(ids), [int(p) for p in np.asarray(at)]
        total = -(-(max(at) + 2) // block) * block
        rows = []
        for p in at:
            mine = None if lower is not None else \
                _SHARED["choices"].get(_key(ids[: p + 1]))
            if lower is None and mine is None:
                raise ValueError(f"no expert choices recorded for a row of "
                                 f"{p + 1} tokens: the program did not run it")
            seq, full = _padded(ids, p, total, mask_id, mine)
            out, short = sdar_reference.forward_logits(
                w, jnp.asarray(seq), jnp.asarray([p + 1]),
                None if full is None else jnp.asarray(full), lower=lower, **kw)
            if mine is not None:
                worst = float(np.asarray(short)[:, : mine.shape[1]].max())
                _SHARED["worst_shortfall"] = max(_SHARED["worst_shortfall"],
                                                 worst)
                if worst > eps:
                    raise ValueError(
                        f"routing: an expert the program chose lies {worst:.4f}"
                        f" under the reference's own 8th score (epsilon {eps})"
                        f" in the row of {p + 1} tokens")
            rows.append(np.asarray(out[0]))
        if lower is None:
            print(f"correctness: sdar adapter: largest shortfall of a chosen "
                  f"expert under the reference's 8th score so far "
                  f"{_SHARED['worst_shortfall']:.5f} (epsilon {eps})",
                  flush=True)
        return np.stack(rows)

    return logits


class Binding:
    """The program at the cut depth, for ``rows`` rows of ``max_seq_len``."""

    def __init__(self, conf: dict, depth: int, rows: int) -> None:
        serving = conf["serving"]
        self.conf, self.depth = conf, depth
        self.cfg = dataclasses.replace(get_config(serving["model_config"]),
                                       num_layers=depth)
        self.W, self.mask_id = self.cfg.block_length, self.cfg.mask_token_id
        self.page, self.rows = serving["page"], rows
        self.pmax = serving["max_seq_len"] // self.page
        self.rope = rope_frequencies(self.cfg.head_dim, serving["max_seq_len"],
                                     self.cfg.rope_theta)
        self.pool_shape = (depth, rows * self.pmax + 1, self.page,
                           self.cfg.num_kv_heads * self.cfg.head_dim)
        cfg = self.cfg
        self._head = jax.jit(lambda p, h: sdar_moe.lm_head_logits(p, cfg, h))
        self._programs: dict[bytes, tuple] = {}

    def new_state(self) -> dict:
        table = 1 + np.arange(self.rows * self.pmax, dtype=np.int32).reshape(
            self.rows, self.pmax)
        empty = np.zeros((self.depth, 0, self.cfg.experts_per_token), np.int32)
        return {"pools": (jnp.zeros(self.pool_shape, jnp.bfloat16),
                          jnp.zeros(self.pool_shape, jnp.bfloat16)),
                "table": table,
                # per row: tokens given so far, of which ``kept`` have K/V,
                # and the experts the forward that kept them chose
                "tokens": [np.zeros(0, np.int32) for _ in range(self.rows)],
                "kept": np.zeros(self.rows, np.int32),
                "experts": [empty for _ in range(self.rows)]}

    def share_prefix(self, state: dict, row: int, source: int,
                     tokens: int) -> dict:
        """``row``'s first pages are ``source``'s (whole blocks: a page is
        16 of them); its kept tokens and their experts are copied when the
        source has written them (``_adopt_shared``)."""
        table = state["table"].copy()
        table[row, : tokens // self.page] = table[source, : tokens // self.page]
        return {**state, "table": table, "plan": (row, source, tokens)}

    def _compiled(self, table: np.ndarray) -> tuple:
        key = table.tobytes()
        if key not in self._programs:
            cfg, rope = self.cfg, self.rope
            self._programs[key] = (
                jax.jit(lambda p, ids, pools, hist, qlens, blk, lens, run:
                        sdar_moe.forward_paged_mixed(
                            p, cfg, ids, pools, jnp.asarray(table), hist,
                            qlens, rope, decode=DecodeGroup(blk, lens, run)),
                        donate_argnums=(2,)),
                jax.jit(lambda p, blk, pools, lens, run:
                        sdar_moe.forward_paged_decode(
                            p, cfg, blk, pools, jnp.asarray(table), lens,
                            rope, write_mask=run),
                        donate_argnums=(2,)))
        return self._programs[key]

    # -- bookkeeping -------------------------------------------------------
    def _open(self, state: dict, r: int, ahead: int = 0) -> np.ndarray:
        """Row ``r``'s open block: its tokens past the kept ones (and past
        the ``ahead`` more that this call's lane is keeping), then masks."""
        blk = np.full(self.W, self.mask_id, np.int32)
        left = state["tokens"][r][state["kept"][r] + ahead:]
        blk[: len(left)] = left
        return blk

    def _adopt_shared(self, state: dict) -> None:
        plan = state.get("plan")
        if plan and state["kept"][plan[1]] >= plan[2] and not len(
                state["tokens"][plan[0]]):
            row, source, n = plan
            state["tokens"][row] = state["tokens"][source][:n].copy()
            state["kept"][row] = n
            state["experts"][row] = state["experts"][source][:, :n]

    def _keep(self, state: dict, r: int, experts: np.ndarray) -> None:
        """A forward kept ``experts.shape[1]`` more tokens of row ``r``."""
        state["experts"][r] = np.concatenate([state["experts"][r], experts], 1)
        state["kept"][r] += experts.shape[1]

    def _denoise(self, params, state: dict, rows: list[int]):
        """A denoise forward of the open blocks of ``rows``: the hidden at
        each row's first open position, and the choices recorded for the
        reference under the row's tokens."""
        blocks = np.stack([self._open(state, r) for r in range(self.rows)])
        run = np.zeros(self.rows, bool)
        run[rows] = True
        hidden, pools, aux = self._compiled(state["table"])[1](
            params, jnp.asarray(blocks), state["pools"],
            jnp.asarray(state["kept"]), jnp.asarray(run))
        state["pools"] = pools
        experts = np.asarray(aux["experts"]).reshape(
            self.depth, self.rows, self.W, -1)
        at = [len(state["tokens"][r]) - state["kept"][r] for r in range(self.rows)]
        for r in rows:
            _SHARED["choices"][_key(state["tokens"][r])] = np.concatenate(
                [state["experts"][r], experts[:, r]], 1)
        return hidden[jnp.arange(self.rows), jnp.asarray(at, jnp.int32)]

    # -- the judge's calls -------------------------------------------------
    def mixed(self, params, ids: np.ndarray, state: dict, hist: np.ndarray,
              qlens: np.ndarray):
        """One mixed call. A row with a chunk sends the whole blocks of
        (leftover + chunk) through the lane; a rider (one token) rides in the
        decode group, which commits its block where the token filled it."""
        state = {**state, "tokens": list(state["tokens"]),
                 "kept": state["kept"].copy(),
                 "experts": list(state["experts"])}
        self._adopt_shared(state)
        B, W = self.rows, self.W
        lane = np.zeros(B, np.int32)
        rider = np.zeros(B, bool)
        for r in range(B):
            if not qlens[r]:
                continue
            state["tokens"][r] = np.concatenate(
                [state["tokens"][r], ids[r, : qlens[r]]]).astype(np.int32)
            whole = (len(state["tokens"][r]) - state["kept"][r]) // W * W
            if qlens[r] == 1:
                rider[r] = whole > 0
            else:
                lane[r] = whole
        width = 16
        while width < lane.max():
            width *= 2
        span = np.zeros((B, width), np.int32)
        for r in range(B):
            k = state["kept"][r]
            span[r, : lane[r]] = state["tokens"][r][k: k + lane[r]]
        blocks = np.stack([self._open(state, r, lane[r]) for r in range(B)])
        if lane.any() or rider.any():
            _, pools, aux = self._compiled(state["table"])[0](
                params, jnp.asarray(span), state["pools"],
                jnp.asarray(state["kept"]), jnp.asarray(lane),
                jnp.asarray(blocks), jnp.asarray(state["kept"]),
                jnp.asarray(rider))
            state["pools"] = pools
            experts = np.asarray(aux["experts"])
            nd = B * W
            for r in range(B):
                if rider[r]:
                    self._keep(state, r, experts[:, r * W:(r + 1) * W])
                elif lane[r]:
                    at = nd + r * width
                    self._keep(state, r, experts[:, at: at + lane[r]])
        last = self._denoise(params, state,
                             [r for r in range(B) if qlens[r]])
        return last, state

    def decode(self, params, ids: np.ndarray, state: dict, lens: np.ndarray):
        """Every row takes its forced token into its open block; a commit
        forward of the rows it filled, then the denoise forward."""
        state = {**state, "tokens": list(state["tokens"]),
                 "kept": state["kept"].copy(),
                 "experts": list(state["experts"])}
        B, W = self.rows, self.W
        for r in range(B):
            state["tokens"][r] = np.append(state["tokens"][r],
                                           ids[r, 0]).astype(np.int32)
        full = np.asarray([len(state["tokens"][r]) - state["kept"][r] == W
                           for r in range(B)])
        if full.any():
            blocks = np.stack([self._open(state, r) for r in range(B)])
            _, pools, aux = self._compiled(state["table"])[1](
                params, jnp.asarray(blocks), state["pools"],
                jnp.asarray(state["kept"]), jnp.asarray(full))
            state["pools"] = pools
            experts = np.asarray(aux["experts"]).reshape(self.depth, B, W, -1)
            for r in np.flatnonzero(full):
                self._keep(state, int(r), experts[:, r])
        return self._denoise(params, state, list(range(B))), state

    def logits(self, params, out):
        return np.asarray(self._head(params, out), np.float32)

    def row_state(self, state: dict, row: int) -> np.ndarray:
        """What an idle row must get back unchanged: its kept pages (the
        open block's own positions are scratch until a commit forward)."""
        kept = int(state["kept"][row])
        pages = state["table"][row, : -(-kept // self.page) or 1]
        k = np.asarray(state["pools"][0][:, pages]).reshape(
            self.depth, -1, self.pool_shape[-1])[:, :kept]
        return k.ravel()


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
