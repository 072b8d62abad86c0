"""The scheduler's phases, read two ways: from the round records of the whole
window (no trace needed), and on the device trace's own clock.

The program's scheduler tiles its thread's time by phase (``sched.<phase>``:
wait, service, admit, capacity, plan, upload, launch, drain, commit, emit). A
round record carries, per phase that ran since the previous record, ``phases``
= ``[wall_ms, cpu_ms, starved_ms]`` and their walls' sum ``pass_ms``; starved
is the time in which nothing the scheduler had launched was undrained, so the
device waited for the host. With the profiler on, the same phases are
``jax.profiler.TraceAnnotation`` spans on the ``/host:CPU`` plane of the
``*.xplane.pb`` whose ``/device:TPU:0`` plane ``reduce_trace`` reads, named
``sched.<phase>.starved`` while that flag was up.

Readers over the records (``program_counter`` / ``program_span``):
``starved_share``, ``off_cpu_share``. Over the trace: ``traced_share``, which
runs this file as a child, once a run, after the server has exited

    python -m benchmark.host_phases <dir or file>

(``jax.profiler.ProfileData`` needs JAX; the harness's parent stays off it)
and prints what it found: the clock check and ``host phases: {...}``, the
device's idle seconds by the phase the host was in. ``lay_gaps``,
``clock_check`` and ``phases_on_trace`` are the pure part, tested on a
recorded sample. On a program without the spans or the record fields every
reader returns nothing.
"""

from __future__ import annotations

import bisect
import json
import re
import statistics
import sys
from pathlib import Path
from typing import Optional

from .reduce_trace import (DEVICE_PLANE, MODULES_LINE, OPS_LINE, clean,
                           find_xplane, union_ns)
from .server import run_child

HOST_PLANE = "/host:CPU"
SPAN = re.compile(r"^sched\.([a-z]+)(\.starved)?$")
#: a drain shorter than this did not wait for the device: its end says
#: nothing about where a program's end lies on the host's clock
BLOCKED_NS = 1_000_000
#: a launch into an idle device is matched with the first program that
#: starts no more than this before the launch's own start
EARLY_NS = 3_000_000
#: the planes share a clock where the host plane's offset, which lies
#: between minus the ``launch`` median and plus the ``drain`` median of
#: ``clock_check``, is bracketed inside +/- this
BRACKET_MS = 5.0

Event = tuple[str, int, int]            # name, start ns, duration ns
Span = tuple[str, bool, int, int]       # phase, starved, start ns, end ns


# ------------------------------------------------------------ round records
def _records(ctx: dict) -> list[dict]:
    return [r for r in ctx.get("rounds") or [] if r.get("phases")
            and r.get("pass_ms")]


def starved_share(ctx: dict, phases: Optional[list[str]] = None
                  ) -> Optional[float]:
    """Percent of the scheduler thread's time (the sum of ``pass_ms`` over
    the window's records) in which the device waited for the host, in
    ``phases``; in every phase but ``wait`` where none is given (an empty
    server is not the host's fault)."""
    records = _records(ctx)
    if not records:
        return None
    if phases is None:
        print("host phases: records: " + json.dumps(by_kind(records)),
              flush=True)
    starved = sum(v[2] for r in records for p, v in r["phases"].items()
                  if (p in phases if phases else p != "wait"))
    return 100.0 * starved / sum(r["pass_ms"] for r in records)


def by_kind(records: list[dict]) -> dict:
    """For ``PERF.md``'s tables: by round kind, the records' count, their
    mean ``pass_ms`` and each phase's mean ``[wall_ms, cpu_ms, starved_ms]``
    a record (so a kind's walls add up to its ``pass_ms``)."""
    out: dict = {}
    for kind in sorted({r.get("kind", "decode") for r in records}):
        of = [r for r in records if r.get("kind", "decode") == kind]
        sums: dict[str, list[float]] = {}
        for r in of:
            for p, v in r["phases"].items():
                acc = sums.setdefault(p, [0.0, 0.0, 0.0])
                for i in range(3):
                    acc[i] += v[i]
        out[kind] = {"records": len(of),
                     "pass_ms": round(sum(r["pass_ms"] for r in of) / len(of), 3),
                     "phases": {p: [round(x / len(of), 3) for x in v]
                                for p, v in sums.items()}}
    return out


def off_cpu_share(ctx: dict) -> Optional[float]:
    """Percent of the scheduler's own host time (every phase but ``wait``
    and ``drain``, which wait by design) in which its thread did not run:
    wall less thread CPU time, so a wait for the GIL or for a core."""
    walls = [v for r in _records(ctx) for p, v in r["phases"].items()
             if p not in ("wait", "drain")]
    wall = sum(v[0] for v in walls)
    if not wall:
        return None
    return 100.0 * sum(v[0] - v[1] for v in walls) / wall


# ------------------------------------------------------------ the trace
def scheduler_spans(host_lines: dict[str, list[Event]]) -> list[Span]:
    """The spans of the scheduler's line: the line of the host plane that
    holds ``sched.*`` events (the one with most, were there several)."""
    best: list[Span] = []
    for events in host_lines.values():
        spans = [(m.group(1), bool(m.group(2)), s, s + d)
                 for n, s, d in events if (m := SPAN.match(n))]
        if len(spans) > len(best):
            best = spans
    return sorted(best, key=lambda x: x[2])


def lay_gaps(gaps: list[tuple[int, int]], spans: list[Span]) -> dict:
    """Each idle gap ``(start ns, length ns)`` laid over the phases, which
    tile the scheduler thread's time: idle seconds by phase as ``[fed,
    starved]``, and what no span covers (``gaps`` and ``spans`` in order of
    start, each without overlaps)."""
    by_phase: dict[str, list[float]] = {}
    covered = 0
    i = 0
    for g0, length in gaps:
        g1 = g0 + length
        while i < len(spans) and spans[i][3] <= g0:
            i += 1
        j = i
        while j < len(spans) and spans[j][2] < g1:
            phase, starved, s0, s1 = spans[j]
            part = min(g1, s1) - max(g0, s0)
            if part > 0:
                by_phase.setdefault(phase, [0.0, 0.0])[starved] += part / 1e9
                covered += part
            j += 1
    idle = sum(length for _, length in gaps)
    return {"idle_s": idle / 1e9, "uncovered_s": (idle - covered) / 1e9,
            "by_phase": by_phase}


def _spread(values: list[float]) -> dict:
    return {"n": len(values), "min_ms": min(values),
            "median_ms": statistics.median(values), "max_ms": max(values)}


def clock_check(spans: list[Span], mods: list[Event]) -> dict:
    """Do the two planes share a clock? Two latencies that an offset of the
    host plane moves opposite ways: ``drain``: for every drain that waited
    for the device, its end less the end of the program execution nearest to
    it (the one it waited for): the completion's way to the host PLUS the
    offset; ``launch``: for every launch into an idle device
    (``sched.launch.starved``), the start of the program it launched less
    the START of its span: the call's way to the device LESS the offset.
    Neither latency is negative, so the offset (host plane ahead: positive)
    lies in ``offset_ms`` = [-launch median, +drain median]; the planes
    share a clock where that bracket lies inside +/- ``BRACKET_MS`` (a side
    the trace has no reading of is ``None`` and bounds nothing). What is
    laid over the spans is then wrong by the offset at a gap's two edges, of
    gaps of 13-20 ms. (Measured on the v5e, PERF.md section 6, PR 37:
    profiler's Python tracer on, [1.3, 2.6]: the host plane 1.5 ms ahead;
    off, [-0.7, 1.4], and [0.3, 2.3] in qwen2's runs.)"""
    ends = sorted(s + d for _, s, d in mods)
    starts = sorted(s for _, s, _ in mods)
    drains, launches = [], []
    for phase, starved, s0, s1 in spans:
        if phase == "drain" and s1 - s0 >= BLOCKED_NS and ends:
            k = bisect.bisect_left(ends, s1)
            near = min(ends[max(0, k - 1): k + 1], key=lambda e: abs(s1 - e))
            drains.append((s1 - near) / 1e6)
        elif phase == "launch" and starved:
            k = bisect.bisect_left(starts, s0 - EARLY_NS)
            if k < len(starts):
                launches.append((starts[k] - s0) / 1e6)
    out: dict = {"ok": False}
    if drains:
        out["drain"] = _spread(drains)
    if launches:
        out["launch"] = _spread(launches)
    if drains or launches:
        # a trace that holds one of the two (no arrival in its 3 s: no
        # launch into an idle device) bounds the offset from that side alone
        lo = -out["launch"]["median_ms"] if launches else None
        hi = out["drain"]["median_ms"] if drains else None
        out["offset_ms"] = [lo, hi]
        out["ok"] = (all(abs(x) <= BRACKET_MS for x in (lo, hi)
                         if x is not None)
                     and (lo is None or hi is None or lo <= hi))
    return out


def phases_on_trace(device_lines: dict[str, list[Event]],
                    host_lines: dict[str, list[Event]]) -> dict:
    """The device's idle gaps (between its first and its last op of the
    trace, as ``reduce_trace`` finds them) laid over the scheduler's spans."""
    ops = device_lines.get(OPS_LINE) or []
    mods = device_lines.get(MODULES_LINE) or []
    spans = scheduler_spans(host_lines)
    if not (ops or mods) or not spans:
        return {"spans": len(spans), "device_events": len(ops) + len(mods)}
    _, gaps = union_ns([(s, s + d) for _, s, d in (ops or mods)])
    out = lay_gaps(gaps, spans)
    # gaps between two ops of one program execution are the device's own
    _, between = union_ns([(s, s + d) for _, s, d in mods])
    out["between_programs_s"] = sum(n for _, n in between) / 1e9 if mods else None
    out["programs"] = sorted({clean(n) for n, _, _ in mods})
    out["spans"] = len(spans)
    out["clock"] = clock_check(spans, mods)
    named = sum(sum(v) for p, v in out["by_phase"].items() if p != "wait")
    seen = sum(v[1] for v in out["by_phase"].values())
    if out["idle_s"] > 0:
        out["shares"] = {"named": 100.0 * named / out["idle_s"],
                         "seen": 100.0 * seen / out["idle_s"]}
    return out


def read_planes(path: Path) -> tuple[dict, dict]:
    """The first device plane's lines, and of the host plane the lines that
    hold a ``sched.*`` event (its other lines are the profiler's record of
    every Python call: millions of events nobody here reads)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device: dict[str, list[Event]] = {}
    host: dict[str, list[Event]] = {}
    device_name = min((p.name for p in data.planes
                       if DEVICE_PLANE.match(p.name)), default=None)
    for plane in data.planes:
        if plane.name == device_name:
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    device[line.name] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for n, line in enumerate(plane.lines):
                found = [(e.name, int(e.start_ns), int(e.duration_ns))
                         for e in line.events if e.name.startswith("sched.")]
                if found:
                    host[f"{line.name}#{n}"] = found
    return device, host


def traced(ctx: dict) -> dict:
    """The child's result for this run's trace, made once."""
    if "host_phases" not in ctx:
        found: dict = {}
        where = (ctx.get("trace") or {}).get("file")
        if where:
            _, result, out = run_child("benchmark.host_phases", [where], True,
                                       600)
            found = result or {}
            clock = found.get("clock") or {}
            for side, what in (
                    ("drain", "sched.drain end less the nearest program end, "
                              "over the drains that waited"),
                    ("launch", "program start less the start of its "
                               "sched.launch.starved")):
                if side in clock:
                    c = clock[side]
                    print(f"host phases: clock check: {what} ({c['n']}): min "
                          f"{c['min_ms']:.3f} / median {c['median_ms']:.3f} / "
                          f"max {c['max_ms']:.3f} ms", flush=True)
            print(f"host phases: clock check: the host plane's offset lies "
                  f"in {clock.get('offset_ms')} ms; the planes share a "
                  f"clock: {clock.get('ok', False)}", flush=True)
            print("host phases: " + (json.dumps(found) if found
                                     else f"nothing read: {out[-300:]!r}"),
                  flush=True)
        ctx["host_phases"] = found
    return ctx["host_phases"]


def traced_share(ctx: dict, of: str) -> Optional[float]:
    """Percent of the device's idle time in the traced window that lies
    inside a ``sched.*`` span other than ``wait`` (``named``), or inside a
    ``sched.*.starved`` span (``seen``). Nothing where the trace has no such
    spans, or where its planes do not share a clock."""
    found = traced(ctx)
    if not (found.get("clock") or {}).get("ok"):
        return None
    return (found.get("shares") or {}).get(of)


def main() -> int:
    path = find_xplane(Path(sys.argv[1]))
    if path is None:
        print("RESULT " + json.dumps({"error": f"no *.xplane.pb under "
                                               f"{sys.argv[1]}"}))
        return 1
    print("RESULT " + json.dumps(phases_on_trace(*read_planes(path))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
