"""The Solar-Open2 adapter: the stack of ``models/solar_open2.py`` (KDA layers
under a gated delta rule and gated attention layers without rotary,
sigmoid-routed experts beside a shared expert after each), run as one chip's
share of the experts and the vocabulary.

Its two yardstick halves are ``benchmark/solar_open2_weights.py`` (the seeded
int8 tree; ``weights.to_int4_grid`` walks it) and
``benchmark/solar_open2_reference.py`` (the plain forward), which import
nothing from the program. The binding is ``adapters/granite_hybrid.py``'s
(two K/V pools as deep as the GQA layers at the judged depth, the state slab
as deep as the KDA layers, its last row the one snapshot the scenario needs,
``prefix_unit`` the chunk, ``row_state``) over this module's forwards; the
experts chosen are ``[layers, tokens, K]``: at depth 4 (``A K K K``) one pool
layer, three slab layers, four expert layers.

**Routing is discontinuous, and the limit is not set by it** (as for kimi):
a token's 8th and 9th largest ``s + b`` of 320 lie within bfloat16's rounding
of each other for some percent of tokens a layer. So the program hands over
its choices (``aux["experts"]``), the reference computes with THOSE experts
and its own float32 scores as gates, and the adapter holds each choice to the
reference's own scores: the lowest ``s + b`` among the chosen may lie at most
``correctness.routing_epsilon`` under the reference's own 8th largest (units
of a sigmoid score). The reference's ``lower=`` controls route by their own
scores.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import solar_open2_reference, solar_open2_weights
from benchmark.adapters import granite_hybrid as base
from cyberfabric_core_tpu.models import solar_open2

#: controls that are the program itself, given lower weights
PROGRAM_CONTROLS = {"int4": solar_open2_weights.to_int4_grid}

#: the program's expert choices by sequence and the largest shortfall read,
#: where the binding (the granite adapter's) keeps them
_SHARED = base._SHARED
_PAD = 128      # the reference compiles at sequence lengths of whole _PADs


def make_weights(conf: dict, seed: int, depth: int) -> dict:
    _SHARED.update(choices={}, worst_shortfall=0.0)
    return solar_open2_weights.make_weights(conf, seed, depth)


def reference_logits(conf: dict, depth: int):
    """``(weights, ids [T], at [n], lower=None) -> logits [n, V held]``: one
    whole forward of the sequence. Without ``lower`` the forward uses the
    experts the program chose for that sequence and holds them to the
    epsilon."""
    kw = solar_open2_reference.reference_kwargs(conf, depth)
    eps = conf["correctness"]["routing_epsilon"]

    def logits(w, ids, at, lower=None):
        ids, at = np.asarray(ids, np.int32), np.asarray(at, np.int32)
        T = len(ids)
        total = -(-T // _PAD) * _PAD
        mine = None if lower is not None else _SHARED["choices"].get(
            base._key(ids))
        if lower is None and mine is None:
            raise ValueError(f"no expert choices recorded for a sequence of "
                             f"{T} tokens: the program did not run it")
        seq = np.zeros(total, np.int32)
        seq[:T] = ids
        full = None
        if mine is not None:
            full = np.zeros((mine.shape[0], total, mine.shape[2]), np.int32)
            full[:, :T] = mine
        out, short = solar_open2_reference.forward_logits(
            w, jnp.asarray(seq), jnp.asarray(at),
            None if full is None else jnp.asarray(full), lower=lower, **kw)
        if mine is not None:
            worst = float(np.asarray(short)[:, :T].max())
            _SHARED["worst_shortfall"] = max(_SHARED["worst_shortfall"], worst)
            if worst > eps:
                raise ValueError(
                    f"routing: an expert the program chose lies {worst:.4f} "
                    f"under the reference's own {kw['top_k']}th largest "
                    f"s + b (epsilon {eps}) in the sequence of {T} tokens")
            print(f"correctness: solar_open2 adapter: largest shortfall of a "
                  f"chosen expert under the reference's {kw['top_k']}th "
                  f"s + b so far {_SHARED['worst_shortfall']:.5f} "
                  f"(epsilon {eps})", flush=True)
        return np.asarray(out)

    return logits


class Binding(base.Binding):
    """The granite binding over ``models/solar_open2.py``'s forwards and its
    own slab (the conv tails flat: ``solar_open2.init_state``)."""

    def new_state(self) -> dict:
        return {**super().new_state(),
                "slab": solar_open2.init_state(self.cfg, self.rows + 1)}

    def _compiled(self, table: np.ndarray) -> tuple:
        key = table.tobytes()
        if key not in self._programs:
            cfg, rope = self.cfg, self.rope
            self._programs[key] = (
                jax.jit(lambda p, ids, pools, slab, hist, qlens:
                        solar_open2.forward_paged_mixed(
                            p, cfg, ids, pools, jnp.asarray(table), hist,
                            qlens, rope, state=slab),
                        donate_argnums=(2, 3)),
                jax.jit(lambda p, ids, pools, slab, lens:
                        solar_open2.forward_paged_decode(
                            p, cfg, ids, pools, jnp.asarray(table), lens,
                            rope, state=slab),
                        donate_argnums=(2, 3)))
        return self._programs[key]


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
