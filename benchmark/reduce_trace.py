"""From a profiler trace (``*.xplane.pb``) to the few numbers the per-layer
metrics read. Runs as a child on the CPU backend after the server has exited
(``jax.profiler.ProfileData`` needs JAX; the harness's parent stays off it).

    python -m benchmark.reduce_trace <dir or file> [seconds the profiler ran]

prints RESULT {json}. What it keeps, per device plane (``/device:TPU:n``):
- ``window_s``: the time the profiler ran, by the harness's clock from the
  return of its start call to its stop call, or first device event start to
  last device event end where that is longer (the profiler runs a little
  longer than the harness can see). Idle time before the first and after the
  last device op of the trace is therefore inside the window
- ``busy_s``: the union of the intervals in which an op ran (line "XLA Ops")
- ``modules``: per jitted program (line "XLA Modules"): count, total seconds
  and every duration — one event is one execution on the device
- ``ops`` per op (``%fusion.12``) and ``op_kinds`` per kind (``%fusion``):
  count and total seconds, containers (the layer scan, branches) left out
- ``gaps``: the longest idle gaps, as (start ns, length ns)
Averaged over devices where there are several. ``reduce_events`` is the pure
part, tested on a recorded sample (tests/trace_sample.json).
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def union_ns(intervals: list[tuple[int, int]]) -> tuple[int, list[tuple[int, int]]]:
    """Total covered ns and the gaps between covered stretches."""
    covered, gaps, end = 0, [], None
    for s, e in sorted(intervals):
        if end is None:
            covered, end = e - s, e
        elif s > end:
            gaps.append((end, s - end))
            covered, end = covered + e - s, e
        elif e > end:
            covered, end = covered + e - end, e
    return covered, gaps


def name_gaps(gaps: list[tuple[int, int]],
              mods: list[tuple[str, int, int]]) -> list[list]:
    """Idle seconds by the programs on either side of each gap: ``a -> b``
    is the device waiting between an execution of a and the next of b,
    ``inside a`` a gap between two ops of one execution. The trace's clock
    starts at the trace, not at the epoch, so the host's round records
    (wall-clock) cannot be lined up with it to a millisecond; the programs
    either side are what the trace itself can say."""
    import bisect

    mods = sorted(mods, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    acc: dict[str, list[float]] = {}
    for g0, length in gaps:
        i = bisect.bisect_right(starts, g0) - 1
        prev = mods[i] if i >= 0 else None
        nxt = mods[i + 1] if i + 1 < len(mods) else None
        if prev and prev[1] + prev[2] >= g0 + length:
            name = f"inside {clean(prev[0])}"
        else:
            name = (f"{clean(prev[0]) if prev else 'trace start'} -> "
                    f"{clean(nxt[0]) if nxt else 'trace end'}")
        a = acc.setdefault(name, [0.0, 0])
        a[0] += length / 1e9
        a[1] += 1
    return sorted(([k, s, n] for k, (s, n) in acc.items()), key=lambda x: -x[1])


def clean(name: str) -> str:
    """A program ``jit_paged_decode_chunk(1234567)`` -> ``jit_paged_decode_chunk``;
    an op, which the trace names by its whole HLO text ``%fusion.12 = bf16[..]
    fusion(...)``, -> ``%fusion.12``."""
    return re.sub(r"\(\d+\)$", "", name.split(" = ", 1)[0])


def kind_of(short: str) -> str:
    """``%fusion.12`` -> ``%fusion``: the same op of every layer and step."""
    return re.sub(r"[.\d]+$", "", short)


#: ops that only contain other ops (the layer scan, a branch): their time is
#: their children's, so they count for busy time but not among the ops
CONTAINERS = re.compile(r"^%(while|conditional|call)\b")


def reduce_events(planes: dict[str, dict[str, list[tuple[str, int, int]]]],
                  profiled_s: float = 0.0) -> dict:
    """``planes``: plane name -> line name -> [(event name, start ns, dur ns)];
    ``profiled_s``: how long the profiler ran, by the host's clock."""
    per_device = []
    for pname, lines in sorted(planes.items()):
        ops = lines.get(OPS_LINE) or []
        mods = lines.get(MODULES_LINE) or []
        every = ops + mods
        if not every:
            continue
        t0 = min(s for _, s, _ in every)
        t1 = max(s + d for _, s, d in every)
        busy, gaps = union_ns([(s, s + d) for _, s, d in (ops or mods)])
        window_ns = max(t1 - t0, int(profiled_s * 1e9))
        by_neighbours = name_gaps(gaps, mods)
        if window_ns > t1 - t0:
            by_neighbours = sorted(by_neighbours + [[
                "before the first or after the last device op of the trace",
                (window_ns - (t1 - t0)) / 1e9, 1]], key=lambda x: -x[1])
        op_sum: dict[str, list[float]] = {}
        kind_sum: dict[str, list[float]] = {}
        for n, _, d in ops:
            short = clean(n)
            if CONTAINERS.match(short):
                continue
            for table, key in ((op_sum, short), (kind_sum, kind_of(short))):
                acc = table.setdefault(key, [0, 0.0])
                acc[0] += 1
                acc[1] += d / 1e9
        mod_sum: dict[str, dict] = {}
        for n, _, d in mods:
            acc = mod_sum.setdefault(clean(n), {"count": 0, "total_s": 0.0,
                                                "durations_ms": []})
            acc["count"] += 1
            acc["total_s"] += d / 1e9
            acc["durations_ms"].append(d / 1e6)
        per_device.append({
            "plane": pname, "start_ns": t0, "window_s": window_ns / 1e9,
            "gaps_by_neighbours": by_neighbours,
            "busy_s": busy / 1e9,
            "ops": {k: {"count": c, "total_s": s} for k, (c, s) in op_sum.items()},
            "op_kinds": {k: {"count": c, "total_s": s}
                         for k, (c, s) in kind_sum.items()},
            "modules": mod_sum,
            "gaps": sorted(gaps, key=lambda g: -g[1])[:40]})
    if not per_device:
        return {"devices": 0, "window_s": 0.0, "busy_s": 0.0, "ops": {},
                "op_kinds": {},
                "modules": {}, "gaps": [], "start_ns": 0,
                "gaps_by_neighbours": []}
    n = len(per_device)
    first = per_device[0]
    return {"devices": n,
            "window_s": sum(d["window_s"] for d in per_device) / n,
            "busy_s": sum(d["busy_s"] for d in per_device) / n,
            # names and gaps of the first device; sums over the others differ
            # only where programs are not replicated
            "ops": first["ops"], "op_kinds": first["op_kinds"],
            "modules": first["modules"],
            "gaps": first["gaps"], "start_ns": first["start_ns"],
            "gaps_by_neighbours": first["gaps_by_neighbours"]}


def read_xplane(path: Path) -> tuple[dict, dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes: dict[str, dict[str, list]] = {}
    seen: dict[str, dict[str, int]] = {}
    for plane in data.planes:
        seen[plane.name] = {}
        for line in plane.lines:
            events = [(e.name, int(e.start_ns), int(e.duration_ns))
                      for e in line.events]
            seen[plane.name][line.name] = len(events)
            if DEVICE_PLANE.match(plane.name):
                planes.setdefault(plane.name, {})[line.name] = events
    return planes, seen


def find_xplane(where: Path) -> Path | None:
    if where.is_file():
        return where
    found = sorted(where.rglob("*.xplane.pb"))
    return found[-1] if found else None


def main() -> int:
    where = Path(sys.argv[1])
    path = find_xplane(where)
    if path is None:
        print("RESULT " + json.dumps({"error": f"no *.xplane.pb under {where}"}))
        return 1
    planes, seen = read_xplane(path)
    out = reduce_events(planes, float(sys.argv[2]) if len(sys.argv) > 2 else 0.0)
    out["file"] = str(path)
    out["bytes"] = path.stat().st_size
    out["planes_seen"] = seen
    print("RESULT " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
