"""The Motif adapter: the block of ``models/motif.py`` (grouped differential
attention on a latent page in two page groups, hyper-connected streams,
PolyNorm units, a chip's share of sigmoid-routed experts).

Its two yardstick halves are ``benchmark/motif_weights.py`` and
``benchmark/motif_reference.py`` (attention NOT absorbed, no cache, the
window a mask), which import nothing from the program. The binding below is
the one place that does: the paged forward passes as ``runtime/scheduler.py``
drives them, over the full group's pool and the window group's and a page
table that holds both groups' runs.

**The binding's pages are the program's own pool's.** Every row's two chains
come from a ``runtime/paged.py: PrefixKVPool`` built for the cut
configuration, as the scheduler takes them: ``extend_window`` and
``extend_chain`` before a call for the tokens it will write,
``trim_window`` after it for the row's new length; the page table is read
from the chains. So the freeing that is judged is the freeing that is
served, and there is no second copy of its arithmetic here. The window group
is kept SHORT (``window_pages``: what every row needs through one chunk and
two pages more), so a long row's early window pages are written again by
other rows while it is still judged (row A of the judge's scenario is longer
than the window and two chunks): a kernel that read left of its span, or a
``trim_window`` that gave a page back too soon, moves the row's logits to
about 1.

**A shared prefix is no match**, as in the served pool (``match_prefix``
hands a model with a window group no pages: the window pages before a
prefix's boundary are not kept past their row). The judge's resumed row
therefore prefills its shared tokens itself, in a call of its own ahead of
the call the judge asked for, and then goes on from the boundary as a later
chunk of its prompt.

Routing is held as ``adapters/kimi_k2.py`` holds it: the reference computes
with the experts the program chose and its own float32 scores as gates, each
choice at most ``correctness.routing_epsilon`` under the reference's own K-th
largest score. ``row_state`` reads the full group's rows AND the window rows
still held.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import motif_reference, motif_weights
from benchmark.adapters import kimi_k2 as kimi
from cyberfabric_core_tpu.models import get_config, motif
from cyberfabric_core_tpu.ops.rope import rope_tables
from cyberfabric_core_tpu.runtime.paged import PrefixKVPool

#: controls that are the program itself, given lower weights
PROGRAM_CONTROLS = {"int4": motif_weights.to_int4_grid}

_SHARED = kimi._SHARED      # one judge process, one adapter: the same record
_key, _PAD = kimi._key, kimi._PAD


def make_weights(conf: dict, seed: int, depth: int) -> dict:
    _SHARED.update(choices={}, worst_shortfall=0.0)
    return motif_weights.make_weights(conf, seed, depth)


def reference_logits(conf: dict, depth: int, **overrides):
    """``(weights, ids [T], at [n], lower=None) -> logits [n, V]``
    (``adapters/kimi_k2.reference_logits`` over this reference)."""
    kw = {**motif_reference.reference_kwargs(conf, depth), **overrides}
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
        out, short = motif_reference.forward_logits(
            w, jnp.asarray(seq), jnp.asarray(at),
            None if full is None else jnp.asarray(full), lower=lower, **kw)
        if mine is not None and mine.shape[0]:
            worst = float(np.asarray(short)[:, :T].max())
            _SHARED["worst_shortfall"] = max(_SHARED["worst_shortfall"], worst)
            if worst > eps:
                raise ValueError(
                    f"routing: an expert the program chose lies {worst:.4f} "
                    f"under the reference's own K-th largest score (epsilon "
                    f"{eps}) in the sequence of {T} tokens")
            print(f"correctness: motif adapter: largest shortfall of a "
                  f"chosen expert under the reference's K-th score so far "
                  f"{_SHARED['worst_shortfall']:.5f} (epsilon {eps}); window "
                  f"pages given back and handed out again in the last run "
                  f"of the program {_SHARED.get('window_pages_reused', 0)}",
                  flush=True)
        return np.asarray(out)

    return logits


class Binding(kimi.Binding):
    """The program at the cut depth, for ``rows`` rows of ``max_seq_len``."""

    def __init__(self, conf: dict, depth: int, rows: int) -> None:
        serving = conf["serving"]
        self.cfg = cfg = get_config(serving["model_config"]).cut_to(depth)
        self.page, self.rows = serving["page"], rows
        self.pmax = serving["max_seq_len"] // self.page
        self.rope = rope_tables(cfg, serving["max_seq_len"])
        self.chunk = conf["correctness"]["chunk"]
        #: window pages in all: what every row needs through one chunk, and
        #: two more, so that a freed page is written again at once
        self.window_pages = rows * cfg.window_pages(self.page, self.chunk) + 2
        self._head = jax.jit(lambda p, h: motif.lm_head_logits(p, cfg, h))
        rope = self.rope
        self._programs = (
            jax.jit(lambda p, ids, pools, table, hist, qlens:
                    motif.forward_paged_mixed(p, cfg, ids, pools, table, hist,
                                              qlens, rope),
                    donate_argnums=(2,)),
            jax.jit(lambda p, ids, pools, table, lens:
                    motif.forward_paged_decode(p, cfg, ids, pools, table,
                                               lens, rope),
                    donate_argnums=(2,)))

    def new_state(self) -> dict:
        empty = np.zeros((self.cfg.num_moe_layers, 0,
                          self.cfg.experts_per_token), np.int32)
        return {
            # the program's pool: both groups' arrays and allocators, a
            # scratch page in each; then every row's two chains
            "pool": PrefixKVPool(
                self.cfg, num_pages=self.rows * self.pmax + 1,
                page_size=self.page, window_pages=self.window_pages + 1),
            "chains": [[] for _ in range(self.rows)],
            "wchains": [[] for _ in range(self.rows)],
            # window pages given back so far, and how many of them were
            # handed out AGAIN (counted, not arranged: the allocator's order)
            "freed": set(), "reused": 0,
            # per row: the tokens given so far and the experts each chose
            "tokens": [np.zeros(0, np.int32) for _ in range(self.rows)],
            "experts": [empty for _ in range(self.rows)]}

    def share_prefix(self, state: dict, row: int, source: int,
                     tokens: int) -> dict:
        """No page of ``source`` becomes ``row``'s (module docstring):
        ``row`` will write its first ``tokens`` tokens itself."""
        return {**state, "plan": (row, source, tokens)}

    def _grow(self, state: dict, row: int, end: int) -> None:
        """Pages of both groups for ``row``'s tokens below ``end``."""
        wchain = state["wchains"][row]
        held = len(wchain)
        state["pool"].extend_window(wchain, end)
        state["pool"].extend_chain(state["chains"][row], end)
        state["reused"] += len(state["freed"].intersection(wchain[held:]))
        _SHARED["window_pages_reused"] = state["reused"]

    def _trim(self, state: dict, row: int, length: int) -> None:
        """``row`` has ``length`` tokens: the pool takes back what its rule
        says; which pages those were is noted, to count them when they are
        handed out again."""
        wchain = state["wchains"][row]
        was = set(wchain)
        state["pool"].trim_window(wchain, length)
        state["freed"] |= was - set(wchain)

    def _table(self, state: dict) -> jnp.ndarray:
        table = np.zeros((self.rows, 2 * self.pmax), np.int32)
        for r, (chain, wchain) in enumerate(zip(state["chains"],
                                                state["wchains"])):
            table[r, : len(chain)] = chain
            table[r, self.pmax: self.pmax + len(wchain)] = wchain
        return jnp.asarray(table)

    def mixed(self, params, ids: np.ndarray, state: dict, hist: np.ndarray,
              qlens: np.ndarray):
        state = self._copy(state)
        plan = state.get("plan")
        if plan and qlens[plan[0]]:
            row, source, n = plan       # the resumed row: its prefix first
            own = np.zeros((self.rows, max(16, 1 << (n - 1).bit_length())),
                           np.int32)
            own[row, :n] = state["tokens"][source][:n]
            lens = np.zeros(self.rows, np.int32)
            lens[row] = n
            _, state = self.mixed(params, own, {**state, "plan": None},
                                  np.zeros_like(lens), lens)
        pool = state["pool"]
        for r in range(self.rows):
            if qlens[r]:
                self._grow(state, r, int(hist[r] + qlens[r]))
        hidden, pools, aux = self._programs[0](
            params, jnp.asarray(ids), pool.cache_operands(),
            self._table(state), jnp.asarray(hist), jnp.asarray(qlens))
        pool.adopt(pools)
        experts = np.asarray(aux["experts"])
        width = ids.shape[1]
        for r in range(self.rows):
            if qlens[r]:
                at = r * width
                self._took(state, r, ids[r, : qlens[r]],
                           experts[:, at: at + qlens[r]])
                self._trim(state, r, int(hist[r] + qlens[r]))
        return (motif.gather_last_hidden(hidden, jnp.asarray(qlens)), state)

    def decode(self, params, ids: np.ndarray, state: dict, lens: np.ndarray):
        state = self._copy(state)
        pool = state["pool"]
        for r in range(self.rows):
            self._grow(state, r, int(lens[r]) + 1)
        hidden, pools, aux = self._programs[1](
            params, jnp.asarray(ids), pool.cache_operands(),
            self._table(state), jnp.asarray(lens))
        pool.adopt(pools)
        experts = np.asarray(aux["experts"])
        for r in range(self.rows):
            self._took(state, r, ids[r], experts[:, r: r + 1])
            self._trim(state, r, int(lens[r]) + 1)
        return hidden[:, 0], state

    def row_state(self, state: dict, row: int) -> np.ndarray:
        """What an idle row must get back unchanged: the full group's rows
        of the tokens it has been given, and its window pages still held."""
        kept = len(state["tokens"][row])
        full, window = state["pool"].pools
        pages = jnp.asarray(state["chains"][row] or [0], jnp.int32)
        rows = np.asarray(full[:, pages]).reshape(
            full.shape[0], -1, full.shape[-1])[:, :kept]
        held = jnp.asarray([p for p in state["wchains"][row] if p],
                           jnp.int32)
        return np.concatenate(
            [rows.ravel(), np.asarray(window[:, held]).ravel()])


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
