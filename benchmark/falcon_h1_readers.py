"""Reader kinds the Falcon-H1 configuration brings (a layer-metric file names
one as ``benchmark.falcon_h1_readers:<function>``). Imported by the harness's
parent process: no JAX."""

from __future__ import annotations

from typing import Optional

from benchmark import layer_readers


def roofline_us(ctx: dict, count_function: str,
                time_metric: str) -> Optional[float]:
    """``layer_readers.roofline`` for a time read in microseconds (a kernel's
    ``mean_us``): the least time the chip could take for what
    ``count_function`` counts, over the measured time of ``time_metric``.
    Nothing where the time was not read (the kernel is not in the trace, as
    on a program without it) or the configuration counts no such role."""
    measured_us = (ctx.get("values") or {}).get(time_metric)
    if not measured_us:
        return None
    return layer_readers.roofline(
        {**ctx, "values": {time_metric: measured_us / 1e3}}, count_function,
        time_metric)


def gauge_percent(ctx: dict, series: str, over: str) -> Optional[float]:
    """The ``counter`` kind's gauge form (the mean of ``series``'s scrapes
    over the mean of ``over``'s), as a percentage."""
    share = layer_readers.counter(ctx, series, over=over, gauge=True)
    return None if share is None else 100.0 * share
