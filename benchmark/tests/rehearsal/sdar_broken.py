"""The SDAR adapter with its commit forward broken: a block that has filled
is not run again, so the K/V a DENOISE forward wrote for it (its later
positions still masked) is what later rows read. The judge has to fail it
(tests/test_sdar.py)."""

from __future__ import annotations

import numpy as np

from benchmark.adapters import sdar

make_weights = sdar.make_weights
reference_logits = sdar.reference_logits
PROGRAM_CONTROLS = sdar.PROGRAM_CONTROLS


class Binding(sdar.Binding):
    def __init__(self, conf: dict, depth: int, rows: int) -> None:
        super().__init__(conf, depth, rows)
        #: row -> the experts its last denoise forward chose
        self._last: dict[int, np.ndarray] = {}

    def _denoise(self, params, state, rows):
        out = super()._denoise(params, state, rows)
        for r in rows:
            self._last[r] = sdar._SHARED["choices"][
                sdar._key(state["tokens"][r])]
        return out

    def decode(self, params, ids, state, lens):
        """Every forced token goes into its open block and the denoise
        forward runs; a block that filled is counted as kept on the strength
        of the denoise forward that ran it last, with no commit forward."""
        state = {**state, "tokens": list(state["tokens"]),
                 "kept": state["kept"].copy(),
                 "experts": list(state["experts"])}
        for r in range(self.rows):
            state["tokens"][r] = np.append(state["tokens"][r],
                                           ids[r, 0]).astype(np.int32)
            if len(state["tokens"][r]) - state["kept"][r] == self.W:
                kept = int(state["kept"][r])
                self._keep(state, r, self._last[r][:, kept: kept + self.W])
        return self._denoise(params, state, list(range(self.rows))), state


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
