"""Reader kinds the Kimi-K2 configuration brings (a layer-metric file names
one as ``benchmark.kimi_k2_readers:<function>``). Imported by the harness's
parent process: no JAX."""

from __future__ import annotations

from typing import Optional

from benchmark import opcounts


def roofline_measured(ctx: dict, count_function: str, time_metric: str,
                      measured: dict, time_unit: str = "ms"
                      ) -> Optional[float]:
    """``layer_readers.roofline`` for a count that needs what the program's
    counters measured: ``measured`` maps a key of ``serving`` to the metric
    (read before this one) whose value the count function finds there, as
    ``kimi_k2_counts.py`` lists them. The share is the least time the chip
    could take for the counted work over ``time_metric`` (``time_unit``
    ``ms`` or ``us``). Nothing where the time or one of the measured values
    was not read, as on a program without the kernel or the counters, or
    where the configuration's counts module has no such function."""
    values = ctx.get("values") or {}
    took = values.get(time_metric)
    found = {key: values.get(metric) for key, metric in measured.items()}
    if not took or not ctx.get("peaks") or any(
            v is None for v in found.values()):
        return None
    conf = ctx["config"]
    count = opcounts.count_function(conf, count_function)
    if count is None:
        return None
    serving = {**conf["serving"], **found}
    counts = count({**conf, "serving": serving}, serving)
    if counts is None:
        return None
    least_s, _ = opcounts.least_seconds(counts, ctx["peaks"])
    took_s = took / 1e6 if time_unit == "us" else took / 1e3
    return 100.0 * least_s / took_s
