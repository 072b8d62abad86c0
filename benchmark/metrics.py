"""End-to-end metric arithmetic, from what the client saw.

Every metric is taken over all requests that finished inside the window (a
failed or refused request has no latency and counts in ``failed``), and a
rate over the whole window. Percentiles are the nearest-rank kind on the sorted sample, so
a value reported is a value observed.
"""

from __future__ import annotations

import math
from typing import Callable, Iterable, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    xs = sorted(values)
    if not xs:
        return None
    return xs[min(len(xs) - 1, max(0, math.ceil(q / 100.0 * len(xs)) - 1))]


def stat(values: list[float], name: str) -> Optional[float]:
    if not values:
        return None
    if name == "mean":
        return sum(values) / len(values)
    if name == "sum":
        return sum(values)
    if name == "max":
        return max(values)
    if name.startswith("p"):
        return percentile(values, float(name[1:]))
    raise ValueError(f"unknown statistic {name!r}")


def ttft_ms(rec) -> Optional[float]:
    return (rec.first - rec.due) * 1e3 if rec.ok and rec.first else None


def tpot_ms(rec) -> Optional[float]:
    """(last event - first event) / (output tokens - 1): robust to tokens
    arriving in bursts of decode_chunk."""
    if not rec.ok or rec.first is None or rec.output_tokens < 2:
        return None
    return (rec.last - rec.first) * 1e3 / (rec.output_tokens - 1)


def tpot_over_all_ms(recs) -> Optional[float]:
    """Decode time summed over requests over the tokens it produced: a time
    per token taken over all the work, where a percentile of few requests
    would be one request's number."""
    spans = [(r.last - r.first, r.output_tokens - 1) for r in recs
             if r.ok and r.first is not None and r.output_tokens >= 2]
    tokens = sum(n for _, n in spans)
    return 1e3 * sum(s for s, _ in spans) / tokens if tokens else None


def out_tokens_per_s(recs, start: float, end: float) -> float:
    """Output tokens of each request, times the share of its decode interval
    [first event, last event] that lies inside the window, over the window."""
    total = 0.0
    for r in recs:
        if not r.ok or r.first is None:
            continue
        span = r.last - r.first
        if span <= 0:
            total += r.output_tokens if start <= r.first < end else 0.0
            continue
        inside = max(0.0, min(end, r.last) - max(start, r.first))
        total += r.output_tokens * inside / span
    return total / (end - start)


#: name -> function of (measured requests, all requests, window start, end)
END_TO_END: dict[str, Callable] = {
    "tpot_ms_mean": lambda m, a, s, e: tpot_over_all_ms(m),
    "out_tokens_per_s": lambda m, a, s, e: out_tokens_per_s(a, s, e),
}
