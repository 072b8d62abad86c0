"""Per-layer metric readers. A per-layer metric is a file
``benchmark/layer_metrics/<name>.json``: a reader ``kind`` and its arguments.
A new metric over these kinds is a new file and an entry in BENCHMARK.json.
A kind is one of this file's (``READERS``) or, written ``package.module:function``,
a reader that a later PR brought as a file of its own (``resolve``).

A reader is given the traced run's context and returns a number, or None
where it finds nothing to read (the harness then leaves the metric out).

The context (``ctx``): ``requests`` (the client's record of every measured
request), ``flight`` (request id -> the program's flight record), ``rounds``
(the scheduler's round records inside the window), ``samples`` (series the
harness sampled during the window, by name: ``pool_pages``), ``scrapes``
(the server's ``/metrics`` as the harness read it: ``start`` and ``end`` at
the window's two ends, ``all`` every scrape from start to end in order;
unlabelled series, name -> value), ``server_log``, ``trace`` (reduce_trace's
output), ``config`` (the configuration file), ``peaks`` (this device's row of
peaks.json), ``values`` (metrics already read, for readers that build on
another).
"""

from __future__ import annotations

import re
from typing import Callable, Optional

from . import metrics, names, opcounts
from .server import HarnessFailure

_CLIENT_FIELDS: dict[str, Callable] = {
    "ttft_ms": metrics.ttft_ms, "tpot_ms": metrics.tpot_ms,
}


def client_stat(ctx: dict, field: str, stat: str) -> Optional[float]:
    vals = [v for v in map(_CLIENT_FIELDS[field], ctx["requests"]) if v is not None]
    return metrics.stat(vals, stat)


def client_minus_flight(ctx: dict, client_field: str, flight_field: str,
                        stat: str) -> Optional[float]:
    """Per request: what the client saw minus what the program's flight
    record says, matched by X-Request-Id."""
    vals = []
    for r in ctx["requests"]:
        rec = ctx["flight"].get(r.plan.rid)
        mine = _CLIENT_FIELDS[client_field](r)
        theirs = ((rec or {}).get("derived") or {}).get(flight_field)
        if mine is not None and theirs is not None:
            vals.append(mine - theirs)
    return metrics.stat(vals, stat)


def flight_records(ctx: dict, field: str, stat: str) -> Optional[float]:
    ids = {r.plan.rid for r in ctx["requests"]}
    vals = [rec["derived"][field] for rid, rec in ctx["flight"].items()
            if rid in ids and (rec.get("derived") or {}).get(field) is not None]
    return metrics.stat(vals, stat)


def server_log(ctx: dict, pattern: str, reduce: str = "sum",
               only: Optional[str] = None) -> Optional[float]:
    """Numbers matched by ``pattern``'s last group; ``only`` filters on the
    first group (a program name) by regular expression. Consecutive repeats
    count once (two log handlers print each record)."""
    found = re.findall(pattern, ctx["server_log"])
    found = [f if isinstance(f, tuple) else (f,) for f in found]
    found = [x for i, x in enumerate(found) if i == 0 or x != found[i - 1]]
    vals = [float(f[-1]) for f in found
            if only is None or re.search(only, f[0])]
    return metrics.stat(vals, reduce)


def rounds(ctx: dict, fields: list[str], stat: str,
           kinds: Optional[list[str]] = None,
           percent_of_serving: Optional[str] = None) -> Optional[float]:
    """A statistic over the sum of ``fields`` of each round record."""
    vals = [sum(float(r.get(f) or 0.0) for f in fields) for r in ctx["rounds"]
            if kinds is None or r.get("kind") in kinds]
    out = metrics.stat(vals, stat)
    if out is not None and percent_of_serving:
        out = 100.0 * out / ctx["config"]["serving"][percent_of_serving]
    return out


def samples(ctx: dict, series: str, stat: str,
            percent_of_serving: Optional[str] = None) -> Optional[float]:
    """A statistic over a series the harness sampled during the window."""
    out = metrics.stat(list(ctx["samples"].get(series) or []), stat)
    if out is not None and percent_of_serving:
        out = 100.0 * out / ctx["config"]["serving"][percent_of_serving]
    return out


def counter(ctx: dict, series: str, over: Optional[str] = None,
            gauge: bool = False) -> Optional[float]:
    """What a ``/metrics`` series did over the window: a counter's
    difference between the window's two ends, or with ``gauge`` the mean of a
    gauge's samples. ``over`` names a second such series to divide by. A
    series missing from a scrape, or a divisor that did not move, is nothing
    to read."""
    scrapes = ctx.get("scrapes") or {}

    def moved(name: str) -> Optional[float]:
        if gauge:
            return metrics.stat([s[name] for s in scrapes.get("all", ())
                                 if name in s], "mean")
        start, end = scrapes.get("start"), scrapes.get("end")
        if not start or not end or name not in start or name not in end:
            return None
        return end[name] - start[name]

    out = moved(series)
    if out is not None and over is not None:
        below = moved(over)
        out = out / below if below else None
    return out


def _matching(trace: dict, where: str, pattern: str) -> list[dict]:
    return [v for k, v in (trace.get(where) or {}).items()
            if re.search(pattern, k)]


def trace_ops(ctx: dict, where: str, pattern: str, stat: str,
              divide_by_serving: Optional[str] = None) -> Optional[float]:
    """``where`` is "modules" (one event an execution of a jitted program),
    "ops" (``%fusion.12``) or "op_kinds" (``%fusion``). ``stat``: "mean_us"
    (per event), "median_ms" / "max_ms" (modules only),
    "percent_of_busy" (share of the device's busy time)."""
    trace = ctx["trace"]
    hits = _matching(trace, where, pattern)
    if not hits or not trace.get("busy_s"):
        return None
    total = sum(h["total_s"] for h in hits)
    if stat == "percent_of_busy":
        return 100.0 * total / trace["busy_s"]
    if stat == "median_ms":
        d = sorted(x for h in hits for x in h.get("durations_ms", ()))
        out = d[len(d) // 2] if d else None
    elif stat == "max_ms":
        d = [x for h in hits for x in h.get("durations_ms", ())]
        out = max(d) if d else None
    elif stat == "mean_us":
        out = 1e6 * total / sum(h["count"] for h in hits)
    else:
        raise ValueError(f"unknown trace statistic {stat!r}")
    if out is not None and divide_by_serving:
        out /= ctx["config"]["serving"][divide_by_serving]
    return out


def trace_idle(ctx: dict) -> Optional[float]:
    trace = ctx["trace"]
    if not trace.get("window_s") or not trace.get("busy_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def roofline(ctx: dict, count_function: str, time_metric: str) -> Optional[float]:
    """Least time the chip could take for what ``count_function`` counts
    (a role; ``opcounts.count_function`` finds this configuration's), over
    the measured time of ``time_metric`` (ms, read before this one)."""
    measured_ms = ctx["values"].get(time_metric)
    count = opcounts.count_function(ctx["config"], count_function)
    if not measured_ms or not ctx.get("peaks") or count is None:
        return None
    counts = count(ctx["config"], ctx["config"]["serving"])
    least_s, _ = opcounts.least_seconds(counts, ctx["peaks"])
    return 100.0 * least_s * 1e3 / measured_ms


READERS: dict[str, Callable] = {
    "client_stat": client_stat, "client_minus_flight": client_minus_flight,
    "flight_records": flight_records, "server_log": server_log,
    "rounds": rounds, "samples": samples, "counter": counter,
    "trace_ops": trace_ops, "trace_idle": trace_idle, "roofline": roofline,
}


def resolve(kind: str) -> Callable:
    """The reader of a layer-metric file's ``kind``."""
    if ":" in kind:
        return names.load(kind)
    if kind not in READERS:
        raise HarnessFailure(f"unknown reader kind {kind!r}; this file's: "
                             f"{sorted(READERS)}")
    return READERS[kind]
