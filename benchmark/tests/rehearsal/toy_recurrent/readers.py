"""A reader kind brought as a file: one field of a count, so that a run with
no device trace (the rehearsal) still shows whose count a configuration got."""

from __future__ import annotations

from typing import Optional

from benchmark import opcounts


def count_field(ctx: dict, count_function: str, field: str) -> Optional[float]:
    count = opcounts.count_function(ctx["config"], count_function)
    if count is None:
        return None
    return float(count(ctx["config"], ctx["config"]["serving"])[field])
