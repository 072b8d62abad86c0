"""The toy adapter with its mixed call broken: a row that takes no part in a
call has its state advanced all the same (by one padding token). The judge's
look at idle rows has to see it (tests/test_seam.py)."""

from __future__ import annotations

import numpy as np

from . import adapter

make_weights = adapter.make_weights
reference_logits = adapter.reference_logits
PROGRAM_CONTROLS = adapter.PROGRAM_CONTROLS


class Binding(adapter.Binding):
    def mixed(self, params, ids, state, hist, qlens):
        (last, _), after = super().mixed(params, ids, state, hist, qlens)
        s = adapter._advance(params, after["s"], ids[:, :1],
                             np.asarray(qlens == 0, np.int32))
        return (last, s), {**after, "s": s}


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
