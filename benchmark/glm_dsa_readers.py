"""Reader kinds the GLM-5 configuration brings (a layer-metric file names one
as ``benchmark.glm_dsa_readers:<function>``). Imported by the harness's
parent process: no JAX."""

from __future__ import annotations

import re
from typing import Optional


def per_call_us(ctx: dict, pattern: str, calls_of: str) -> Optional[float]:
    """Device time of the ops whose kind matches ``pattern`` over the CALLS
    of the ops whose kind matches ``calls_of``, in microseconds: for an op
    that the trace names by its opcode alone (a sort) and that runs once
    behind every call of a kernel the trace names (an index pass). Ops of
    the same opcode elsewhere in the program are in the sum: say in the
    metric's file how much they are. Nothing where the trace shows neither,
    as on a program without the kernel."""
    kinds = (ctx.get("trace") or {}).get("op_kinds") or {}
    total = sum(v["total_s"] for k, v in kinds.items() if re.search(pattern, k))
    calls = sum(v["count"] for k, v in kinds.items() if re.search(calls_of, k))
    if not calls or not total:
        return None
    return 1e6 * total / calls
