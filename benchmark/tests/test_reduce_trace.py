"""``reduce_trace.reduce_events`` on a sample recorded on the chip.

``trace_sample.json`` is the start of the device plane of a 3 s trace of the
mix chat-open on mistral-7b-int8 on a TPU v5 lite (PR 23): the first 3000
events of the line "XLA Ops" and the one program execution they belong to;
op names were cut to their short form
except every 150th, which keeps the whole HLO text the trace carries."""

import json
from pathlib import Path

import pytest

from benchmark import layer_readers, reduce_trace

SAMPLE = Path(__file__).with_name("trace_sample.json")


@pytest.fixture(scope="module")
def reduced():
    raw = json.loads(SAMPLE.read_text())
    planes = {p: {line: [tuple(e) for e in ev] for line, ev in lines.items()}
              for p, lines in raw.items()}
    return reduce_trace.reduce_events(planes)


def test_busy_idle_and_window_are_fixed(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.30925517, abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.088411225, abs=1e-9)
    assert reduced["busy_s"] < reduced["window_s"]


def test_per_op_sums_are_fixed_and_containers_left_out(reduced):
    kinds = reduced["op_kinds"]
    assert kinds["%reshape"]["count"] == 137
    assert kinds["%reshape"]["total_s"] == pytest.approx(0.030639235, abs=1e-9)
    assert kinds["%paged_decode_attention"]["count"] == 64
    assert kinds["%paged_decode_attention"]["total_s"] == pytest.approx(
        0.00906272, abs=1e-9)
    assert not any(k.startswith(("%while", "%conditional")) for k in kinds)
    # a long HLO text is cut to the op's short name
    assert all(" = " not in k for k in reduced["ops"])
    assert list(reduced["modules"]) == ["jit_paged_decode_chunk"]
    assert reduced["modules"]["jit_paged_decode_chunk"]["count"] == 1


def test_gaps_are_named_by_the_programs_either_side(reduced):
    names = [g[0] for g in reduced["gaps_by_neighbours"]]
    assert names == ["inside jit_paged_decode_chunk"]


def test_readers_on_the_sample(reduced):
    ctx = {"trace": reduced, "config": {"serving": {"decode_chunk": 8}}}
    share = layer_readers.trace_ops(ctx, "op_kinds", "^%reshape$",
                                    "percent_of_busy")
    assert share == pytest.approx(100 * 0.030639235 / 0.088411225)
    assert layer_readers.trace_idle(ctx) == pytest.approx(
        100 * (1 - 0.088411225 / 0.30925517))
    assert layer_readers.trace_ops(ctx, "modules", "nothing_like_this",
                                   "median_ms") is None


def test_the_window_is_the_time_the_profiler_ran():
    raw = json.loads(SAMPLE.read_text())
    planes = {p: {line: [tuple(e) for e in ev] for line, ev in lines.items()}
              for p, lines in raw.items()}
    wide = reduce_trace.reduce_events(planes, profiled_s=0.5)
    assert wide["window_s"] == pytest.approx(0.5)
    assert wide["busy_s"] == pytest.approx(0.088411225, abs=1e-9)
    name, secs, _ = wide["gaps_by_neighbours"][0]
    assert name.startswith("before the first or after the last")
    assert secs == pytest.approx(0.5 - 0.30925517, abs=1e-9)
    # a profiler span shorter than the device events does not cut them
    assert reduce_trace.reduce_events(planes, profiled_s=0.1)["window_s"] == \
        pytest.approx(0.30925517, abs=1e-9)


def test_union_counts_overlap_once():
    covered, gaps = reduce_trace.union_ns([(0, 10), (5, 12), (20, 30)])
    assert covered == 22 and gaps == [(12, 8)]
