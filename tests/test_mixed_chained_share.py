"""``mixed_chained_share`` (``benchmark/layer_metrics/mixed_chained_share.json``,
read by the benchmark's ``counter`` reader): ``llm_mixed_steps_chained_total``
over ``llm_mixed_steps_total``, differences between a window's two scrapes. A
window in which steps ran and none was chained reads 0, not nothing; nothing
is what a program without the series, or a window without a mixed step,
gives (the result line then leaves the metric out)."""

import json
from pathlib import Path

import pytest

from benchmark import layer_readers

REPO = Path(__file__).resolve().parents[1]
SPEC = json.loads((REPO / "benchmark" / "layer_metrics"
                   / "mixed_chained_share.json").read_text())
ALL, CHAINED = "llm_mixed_steps_total", "llm_mixed_steps_chained_total"


def _read(start, end):
    args = {k: v for k, v in SPEC.items() if k not in ("kind", "what")}
    return layer_readers.resolve(SPEC["kind"])(
        {"scrapes": {"start": start, "end": end}}, **args)


@pytest.mark.parametrize("start,end,want", [
    ({ALL: 3.0, CHAINED: 0.0}, {ALL: 10.0, CHAINED: 0.0}, 0.0),
    ({ALL: 3.0, CHAINED: 1.0}, {ALL: 10.0, CHAINED: 6.0}, 5.0 / 7.0),
    ({ALL: 0.0, CHAINED: 0.0}, {ALL: 4.0, CHAINED: 4.0}, 1.0),
    ({ALL: 3.0, CHAINED: 0.0}, {ALL: 3.0, CHAINED: 0.0}, None),
    ({ALL: 3.0}, {ALL: 10.0}, None),
    ({}, {}, None),
], ids=["none-chained", "some", "all", "no-step", "no-series", "parent"])
def test_the_share_is_zero_where_nothing_chained_and_nothing_where_unread(
        start, end, want):
    got = _read(start, end)
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_entry_is_the_schedulers_and_names_the_two_series():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry, = (m for m in bench["per_layer"]
              if m["name"] == "mixed_chained_share")
    assert entry == {"name": "mixed_chained_share", "unit": "ratio",
                     "better": "higher", "source": "program_counter",
                     "layer": "scheduler", "moves": "out_tokens_per_s"}
    assert (SPEC["series"], SPEC["over"]) == (CHAINED, ALL)
