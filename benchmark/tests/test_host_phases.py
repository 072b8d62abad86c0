"""``host_phases``: the readers over round records, and the pure part of the
child on hand-made events and on a sample recorded on the chip.

``host_phases_sample.json`` is a stretch of a 3 s trace of
``mistral-7b-int8.decode-closed`` on a TPU v5 lite (my chip run, PR 37) around
one arrival: the scheduler line's ``sched.*`` events of the ``/host:CPU``
plane, the program executions of ``/device:TPU:0`` ("XLA Modules") and its
"XLA Ops" line with touching ops merged into busy stretches (the gaps between
them are the trace's own)."""

import json
from pathlib import Path

import pytest

from benchmark import host_phases
from benchmark.reduce_trace import MODULES_LINE, OPS_LINE

SAMPLE = Path(__file__).with_name("host_phases_sample.json")
MS = 1_000_000


def span(phase, starved, start_ms, end_ms):
    return (phase, starved, int(start_ms * MS), int(end_ms * MS))


def event(name, start_ms, dur_ms):
    return (name, int(start_ms * MS), int(dur_ms * MS))


# ------------------------------------------------------------ round records
RECORDS = [
    {"kind": "decode", "pass_ms": 100.0, "phases": {
        "emit": [2.0, 1.0, 0.0], "service": [1.0, 1.0, 0.0],
        "admit": [1.0, 0.5, 0.0], "capacity": [2.0, 2.0, 0.0],
        "upload": [1.0, 1.0, 0.0], "launch": [3.0, 1.5, 0.0],
        "drain": [86.0, 0.1, 0.0], "commit": [4.0, 2.0, 0.0]}},
    {"kind": "mixed", "pass_ms": 100.0, "phases": {
        "emit": [10.0, 4.0, 6.0], "wait": [20.0, 0.0, 20.0],
        "service": [2.0, 2.0, 2.0], "admit": [3.0, 1.0, 3.0],
        "capacity": [2.0, 1.0, 2.0], "plan": [1.0, 1.0, 1.0],
        "upload": [1.0, 1.0, 1.0], "launch": [5.0, 2.0, 2.0],
        "drain": [50.0, 0.1, 0.0], "commit": [6.0, 3.0, 4.0]}},
    {"kind": "decode", "dispatch_ms": 3.0},     # a parent's record: no phases
]
GROUPS = {"emit": ["commit", "emit"], "admit": ["service", "admit"],
          "prepare": ["capacity", "plan", "upload"], "launch": ["launch"]}


def test_starved_share_and_its_groups():
    ctx = {"rounds": RECORDS}
    total = host_phases.starved_share(ctx)
    assert total == pytest.approx(100.0 * 21.0 / 200.0)   # wait left out
    parts = {g: host_phases.starved_share(ctx, phases=p)
             for g, p in GROUPS.items()}
    assert parts == pytest.approx({"emit": 5.0, "admit": 2.5, "prepare": 2.0,
                                   "launch": 1.0})
    assert sum(parts.values()) == pytest.approx(total)


def test_off_cpu_share_leaves_out_wait_and_drain():
    wall = 14.0 + 30.0                      # every phase but wait, drain
    cpu = 9.0 + 15.0
    assert host_phases.off_cpu_share({"rounds": RECORDS}) == pytest.approx(
        100.0 * (wall - cpu) / wall)


@pytest.mark.parametrize("reader, args", [
    (host_phases.starved_share, {}),
    (host_phases.starved_share, {"phases": ["launch"]}),
    (host_phases.off_cpu_share, {}),
])
def test_record_readers_return_nothing_on_a_parent(reader, args):
    assert reader({"rounds": [RECORDS[2]]}, **args) is None
    assert reader({"rounds": []}, **args) is None


# ------------------------------------------------------------ the pure part
SPANS = [span("drain", False, 0, 10), span("commit", True, 10, 12),
         span("emit", True, 12, 20), span("service", True, 20, 21),
         span("launch", True, 21, 23), span("launch", False, 23, 24),
         span("drain", False, 24, 60), span("wait", True, 60, 70)]


def test_gaps_are_laid_over_the_phases():
    gaps = [(int(9.5 * MS), 14 * MS),     # drain .5, commit 2, emit 8, 3.5
            (int(23.5 * MS), MS),         # launch fed .5, drain .5
            (65 * MS, 10 * MS)]           # wait 5, and 5 no span covers
    laid = host_phases.lay_gaps(gaps, SPANS)
    assert laid["idle_s"] == pytest.approx(0.025)
    assert laid["uncovered_s"] == pytest.approx(0.005)
    assert laid["by_phase"] == pytest.approx({
        "drain": [0.001, 0.0], "commit": [0.0, 0.002], "emit": [0.0, 0.008],
        "service": [0.0, 0.001], "launch": [0.001, 0.002],
        "wait": [0.0, 0.005]})


@pytest.mark.parametrize("shift_ms, ok", [(0.0, True), (1.5, True),
                                          (-1.5, True), (6.0, False)])
def test_clock_check_brackets_the_host_planes_offset(shift_ms, ok):
    """On a shared clock a drain that waited ends 0.3-0.6 ms after the
    program it waited for and a program launched into an idle device starts
    0.4 ms after its launch began, so the offset is bracketed by [-0.4,
    0.45]. A host plane 1.5 ms ahead (what the profiler's Python tracer does
    on the v5e) moves the bracket to [1.1, 1.95]: it no longer holds 0, and
    is still small, as is one 1.5 ms behind; one 6 ms ahead is out of
    bounds."""
    mods = [event("jit_paged_decode_chunk(1)", 100.0 * k, 100.0)
            for k in range(5)]
    mods += [event("jit_mixed_step(2)", 520.0, 30.0),
             event("jit_mixed_step(2)", 580.0, 30.0)]
    spans = [span("drain", False, 100.0 * k + 20 + shift_ms,
                  100.0 * k + 100.3 + 0.1 * k + shift_ms) for k in range(4)]
    # one that did not wait says nothing, wherever it lies
    spans.append(span("drain", False, 450.0, 450.2))
    # launches into an idle device, and one into a busy device (not starved)
    spans += [span("launch", True, 519.6 + shift_ms, 520.1 + shift_ms),
              span("launch", True, 579.6 + shift_ms, 580.2 + shift_ms),
              span("launch", False, 300.0 + shift_ms, 301.0 + shift_ms)]
    got = host_phases.clock_check(sorted(spans, key=lambda x: x[2]), mods)
    assert got["ok"] is ok
    assert got["drain"]["n"] == 4
    assert got["drain"]["median_ms"] == pytest.approx(0.45 + shift_ms)
    assert got["drain"]["min_ms"] == pytest.approx(0.3 + shift_ms)
    assert got["drain"]["max_ms"] == pytest.approx(0.6 + shift_ms)
    if shift_ms < 3.0:      # past EARLY_NS the next program is taken
        assert got["launch"]["n"] == 2
        assert got["offset_ms"] == pytest.approx([shift_ms - 0.4,
                                                  shift_ms + 0.45])


def test_clock_check_without_a_drain_that_waited():
    got = host_phases.clock_check([span("drain", False, 0.0, 0.2),
                                   span("emit", False, 0.2, 5.0)],
                                  [event("jit_mixed_step(7)", 0.0, 3.0)])
    assert got == {"ok": False}


def test_clock_check_with_one_side_bounds_the_offset_from_that_side():
    """A 3 s trace without an arrival has no launch into an idle device: the
    drains alone bound the offset from above."""
    mods = [event("jit_paged_decode_chunk(1)", 100.0 * k, 100.0)
            for k in range(3)]
    spans = [span("drain", False, 100.0 * k + 50.0, 100.0 * k + 100.5)
             for k in range(3)]
    got = host_phases.clock_check(spans, mods)
    assert got["ok"] and got["offset_ms"] == [None, pytest.approx(0.5)]
    late = [span("drain", False, s0, s1 + 6e6) for _, _, s0, s1 in spans]
    assert not host_phases.clock_check(late, mods)["ok"]


def test_the_schedulers_line_is_the_one_with_the_spans():
    lines = {"python#3": [event("$builtins len", 0, 1),
                          event("sched.emit.starved", 1, 2),
                          event("sched.service", 3, 1),
                          event("sched.up", 4, 1)],
             "python#4": [event("PjitFunction(f)", 0, 9)]}
    assert host_phases.scheduler_spans(lines) == [
        span("emit", True, 1, 3), span("service", False, 3, 4),
        span("up", False, 4, 5)]
    assert host_phases.scheduler_spans({"python#4": lines["python#4"]}) == []


def test_a_trace_without_spans_or_without_a_device_gives_no_shares():
    device = {OPS_LINE: [event("%fusion.1", 0, 1), event("%fusion.2", 2, 1)]}
    host = {"python#0": [event("sched.emit", 0, 3)]}
    assert "shares" not in host_phases.phases_on_trace(device, {})
    assert "shares" not in host_phases.phases_on_trace({}, host)
    assert host_phases.traced_share({"host_phases": {}}, "named") is None
    # spans and a device, but a clock that does not check: nothing either
    found = host_phases.phases_on_trace(device, host)
    assert found["shares"]["named"] == pytest.approx(100.0)
    assert found["clock"] == {"ok": False}
    assert host_phases.traced_share({"host_phases": found}, "named") is None


# ------------------------------------------------------------ the sample
@pytest.fixture(scope="module")
def sample():
    raw = json.loads(SAMPLE.read_text())
    device = {line: [tuple(e) for e in raw["device"][line]]
              for line in (OPS_LINE, MODULES_LINE)}
    host = {line: [tuple(e) for e in events]
            for line, events in raw["host"].items()}
    return host_phases.phases_on_trace(device, host)


def test_sample_idle_is_laid_over_the_phases(sample):
    by_phase = sample["by_phase"]
    laid = sum(sum(v) for v in by_phase.values())
    assert laid + sample["uncovered_s"] == pytest.approx(sample["idle_s"])
    assert sample["programs"] == ["jit_mixed_step", "jit_paged_decode_chunk"]
    # an arrival: the device waits through the emit of the ring's last
    # chunk, the admission and the mixed step's preparation, all starved
    starved = {p: v[1] for p, v in by_phase.items()}
    assert starved["emit"] > 0.0 and starved["admit"] > 0.0
    assert starved.get("drain", 0.0) == 0.0
    assert sample["shares"]["named"] >= sample["shares"]["seen"] > 0.0


def test_sample_clock_checks(sample):
    clock = sample["clock"]
    assert clock["ok"] and clock["drain"]["n"] >= 2 and clock["launch"]["n"] >= 1
    assert 0.0 <= clock["drain"]["median_ms"] <= 2.0
    assert 0.0 <= clock["launch"]["median_ms"] <= 2.0
    ctx = {"host_phases": sample}
    assert host_phases.traced_share(ctx, "named") == sample["shares"]["named"]
    assert host_phases.traced_share(ctx, "seen") == sample["shares"]["seen"]
