"""Reader kinds the SDAR-MoE configuration brings (a layer-metric file names
one as ``benchmark.sdar_readers:<function>``). Imported by the harness's
parent process: no JAX."""

from __future__ import annotations

import re
from typing import Optional

from benchmark import layer_readers


def per_layer_us(ctx: dict, pattern: str, calls_a_layer: int) -> Optional[float]:
    """Mean device time of ONE LAYER's calls of the kernels whose op kind
    matches ``pattern``, where a layer makes ``calls_a_layer`` of them in a
    forward (the expert layer's three grouped matmuls): their total time over
    their count, times the calls a layer. Nothing where the trace shows no
    such op, as on a program without the kernel."""
    trace = ctx.get("trace") or {}
    hits = [v for k, v in (trace.get("op_kinds") or {}).items()
            if re.search(pattern, k)]
    count = sum(h["count"] for h in hits)
    if not count:
        return None
    return 1e6 * sum(h["total_s"] for h in hits) / count * calls_a_layer


def roofline_touched(ctx: dict, count_function: str, time_metric: str,
                     share_metric: str, time_unit: str = "ms"
                     ) -> Optional[float]:
    """``layer_readers.roofline`` with the experts a forward streams counted
    as MEASURED: ``share_metric`` (experts touched over experts offered, read
    before this one from the program's counters) goes to the count function
    as ``serving["experts_touched_share"]``. A count from shapes alone would
    assume uniform routing, which seeded weights under greedy decoding are
    far from, and a share of a roofline counted too high reads over 100%.
    ``time_unit``: ``time_metric``'s, ``ms`` or ``us``. Nothing where the time
    or the share was not read, as on a program without the kernel or the
    counters."""
    values = ctx.get("values") or {}
    measured, share = values.get(time_metric), values.get(share_metric)
    if not measured or not share:
        return None
    conf = ctx["config"]
    counted = {**conf, "serving": {**conf["serving"],
                                   "experts_touched_share": share}}
    ms = measured / 1e3 if time_unit == "us" else measured
    return layer_readers.roofline(
        {**ctx, "config": counted, "values": {time_metric: ms}},
        count_function, time_metric)
