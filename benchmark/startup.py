"""Readers of the program's start-up timeline (a layer-metric file names one
as ``benchmark.startup:<function>``): the stages the server logs as
``startup: {json}`` lines, one a closed top-level stage with its children
inside it and one a program event (cyberfabric_core_tpu/modkit/telemetry.py:
StartupTimeline), and the compile ledger's ``/metrics`` series in the scrape
taken at the window's start, which holds the totals of everything before it.
Imported by the harness's parent process: no JAX. A server without the
timeline (a parent commit's) logs no such line and has no such series: every
reader then returns None, and the harness leaves the metric out."""

from __future__ import annotations

import json
import re
from typing import Iterator, Optional

from .reduce_trace import union_ns

_LINE = re.compile(r"startup: (\{.*\})\s*$", re.MULTILINE)


def _records(ctx: dict) -> list[dict]:
    """The timeline's log lines in order, a line once (a second log handler
    prints a record twice; a record carries its own instants, so equal lines
    are one record)."""
    seen, out = set(), []
    for text in _LINE.findall(ctx.get("server_log") or ""):
        if text in seen:
            continue
        seen.add(text)
        try:
            out.append(json.loads(text))
        except ValueError:
            continue
    return out


def _walk(node: dict) -> Iterator[dict]:
    yield node
    for child in node.get("children") or ():
        yield from _walk(child)


def _stages(ctx: dict, name: str) -> list[dict]:
    return [n for r in _records(ctx) if r.get("kind") == "stage"
            for n in _walk(r) if n.get("name") == name
            and n.get("end_unix_ns") is not None]


def stage_s(ctx: dict, stage: str, served_model: bool = False
            ) -> Optional[float]:
    """Seconds of the closed stages named ``stage``, summed (an engine's
    weights may be two: read from a checkpoint, then placed); with
    ``served_model`` only those whose ``model`` attribute is the cell's
    ``serving.model_id``."""
    found = _stages(ctx, stage)
    if served_model:
        model = ctx["config"]["serving"]["model_id"]
        found = [n for n in found
                 if (n.get("attrs") or {}).get("model") == model]
    return sum(n["duration_s"] for n in found) if found else None


def programs_before_window(ctx: dict, series: list[str],
                           over: Optional[list[str]] = None
                           ) -> Optional[float]:
    """The sum of the ledger's ``series`` as the scrape at the window's start
    read them: everything set-up traced, lowered, compiled or loaded. With
    ``over`` divided by the sum of those series (a hit share); nothing where
    a series is missing or the divisor is 0."""
    start = (ctx.get("scrapes") or {}).get("start") or {}
    if any(s not in start for s in [*series, *(over or ())]):
        return None
    out = sum(start[s] for s in series)
    if over is not None:
        below = sum(start[s] for s in over)
        out = out / below if below else None
    return out


def named_share(ctx: dict) -> Optional[float]:
    """Of the server's age at the window's start, the percentage inside
    ``boot``, an ``engine.build`` or a program event (by their instants, as a
    union: an event inside a build counts once). What is left is warm-up
    traffic running on programs already up, and the lead-in."""
    start = (ctx.get("scrapes") or {}).get("start") or {}
    born, age = (start.get("process_start_time_seconds"),
                 start.get("process_uptime_seconds"))
    records = _records(ctx)
    if not born or not age or not records:
        return None
    lo, hi = int(born * 1e9), int((born + age) * 1e9)
    spans = [(n["start_unix_ns"], n["end_unix_ns"])
             for name in ("boot", "engine.build") for n in _stages(ctx, name)]
    spans += [(r["start_unix_ns"], r["end_unix_ns"]) for r in records
              if r.get("kind") == "program"]
    named, _ = union_ns([(max(a, lo), min(b, hi)) for a, b in spans
                         if min(b, hi) > max(a, lo)])
    return 100.0 * named / (hi - lo)
