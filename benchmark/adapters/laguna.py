"""The Laguna adapter: the block of ``models/laguna.py`` (GQA attention on
K/V pages in two page groups, two kinds of attention layer that differ in
query heads, rotary tables and window, a gate a head, a chip's share of
softmax-routed experts).

Its two yardstick halves are ``benchmark/laguna_weights.py`` and
``benchmark/laguna_reference.py`` (no cache, the window a mask), which import
nothing from the program. The binding below is the one place that does, and
it is ``adapters/motif.py``'s over this program: the paged forward passes as
``runtime/scheduler.py`` drives them, over the full group's K and V pools and
the window group's and a page table that holds both groups' runs, **every
page taken from the program's own ``PrefixKVPool``** (``extend_window`` and
``extend_chain`` before a call, ``trim_window`` after it, the window group
kept SHORT so that a long row's early window pages are written again by other
rows while it is still judged), a shared prefix no match (the resumed row
prefills its shared tokens itself).

Routing is held as ``adapters/kimi_k2.py`` holds it: the reference computes
with the experts the program chose and its own float32 scores as gates, each
choice at most ``correctness.routing_epsilon`` (in router LOGITS) under the
reference's own K-th largest. The reference's ``lower=`` controls route by
their own scores. ``row_state`` reads the full group's rows AND the window
rows still held, of K and of V.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import laguna_reference, laguna_weights
from benchmark.adapters import kimi_k2 as kimi
from benchmark.adapters import motif as motif_adapter
from cyberfabric_core_tpu.models import get_config, laguna
from cyberfabric_core_tpu.ops.rope import rope_tables

#: controls that are the program itself, given lower weights
PROGRAM_CONTROLS = {"int4": laguna_weights.to_int4_grid}

_SHARED = kimi._SHARED      # one judge process, one adapter: the same record
_key, _PAD = kimi._key, kimi._PAD


def make_weights(conf: dict, seed: int, depth: int) -> dict:
    _SHARED.update(choices={}, worst_shortfall=0.0)
    return laguna_weights.make_weights(conf, seed, depth)


def reference_logits(conf: dict, depth: int, **overrides):
    """``(weights, ids [T], at [n], lower=None) -> logits [n, V]``
    (``adapters/kimi_k2.reference_logits`` over this reference)."""
    kw = {**laguna_reference.reference_kwargs(conf, depth), **overrides}
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
        out, short = laguna_reference.forward_logits(
            w, jnp.asarray(seq), jnp.asarray(at),
            None if full is None else jnp.asarray(full), lower=lower, **kw)
        if mine is not None and mine.shape[0]:
            worst = float(np.asarray(short)[:, :T].max())
            _SHARED["worst_shortfall"] = max(_SHARED["worst_shortfall"], worst)
            if worst > eps:
                raise ValueError(
                    f"routing: an expert the program chose lies {worst:.4f} "
                    f"under the reference's own K-th largest logit (epsilon "
                    f"{eps}) in the sequence of {T} tokens")
            print(f"correctness: laguna adapter: largest shortfall of a "
                  f"chosen expert under the reference's K-th logit so far "
                  f"{_SHARED['worst_shortfall']:.5f} (epsilon {eps}); window "
                  f"pages given back and handed out again in the last run "
                  f"of the program {_SHARED.get('window_pages_reused', 0)}",
                  flush=True)
        return np.asarray(out)

    return logits


class Binding(motif_adapter.Binding):
    """The program at the cut depth, for ``rows`` rows of ``max_seq_len``:
    motif's binding (the pool's own pages, both chains, the freeing that is
    served) over this model's forwards."""

    def __init__(self, conf: dict, depth: int, rows: int) -> None:
        serving = conf["serving"]
        self.cfg = cfg = get_config(serving["model_config"]).cut_to(depth)
        self.page, self.rows = serving["page"], rows
        self.pmax = serving["max_seq_len"] // self.page
        self.chunk = conf["correctness"]["chunk"]
        #: window pages in all: what every row needs through one chunk, and
        #: two more, so that a freed page is written again at once
        self.window_pages = rows * cfg.window_pages(self.page, self.chunk) + 2
        rope = rope_tables(cfg, serving["max_seq_len"])
        self._head = jax.jit(lambda p, h: laguna.lm_head_logits(p, cfg, h))
        self._programs = (
            jax.jit(lambda p, ids, pools, table, hist, qlens:
                    laguna.forward_paged_mixed(p, cfg, ids, pools, table,
                                               hist, qlens, rope),
                    donate_argnums=(2,)),
            jax.jit(lambda p, ids, pools, table, lens:
                    laguna.forward_paged_decode(p, cfg, ids, pools, table,
                                                lens, rope),
                    donate_argnums=(2,)))

    def row_state(self, state: dict, row: int) -> np.ndarray:
        """What an idle row must get back unchanged: the full group's K and
        V rows of the tokens it has been given, and its window pages still
        held."""
        kept = len(state["tokens"][row])
        pool = state["pool"]
        pages = jnp.asarray(state["chains"][row] or [0], jnp.int32)
        held = jnp.asarray([p for p in state["wchains"][row] if p],
                           jnp.int32)
        parts = [np.asarray(full[:, pages]).reshape(
            full.shape[0], -1, full.shape[-1])[:, :kept].ravel()
            for full in pool.pools[: 2]]
        parts += [np.asarray(window[:, held]).ravel()
                  for window in pool.window_pools]
        return np.concatenate(parts)


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
