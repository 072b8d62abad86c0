"""The toy adapter with its shared-prefix hook broken: the pages are shared,
the state is dropped, so the resumed row starts from an empty state. The
judge has to fail it at that row (tests/test_seam.py)."""

from __future__ import annotations

from . import adapter

make_weights = adapter.make_weights
reference_logits = adapter.reference_logits
PROGRAM_CONTROLS = adapter.PROGRAM_CONTROLS


class Binding(adapter.Binding):
    def share_prefix(self, state, row, source, tokens):
        return {**state, "base": self.base.share_prefix(state["base"], row,
                                                        source, tokens)}


def bind(conf: dict, depth: int, rows: int) -> Binding:
    return Binding(conf, depth, rows)
