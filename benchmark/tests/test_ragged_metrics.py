"""The three ragged latent kernel metrics of PR 53 (data files only:
``mla_ragged_attention_us`` in the kimi cell, ``gdla_full_ragged_attention_us``
and ``gdla_window_ragged_attention_us`` in the motif cell), shown at no chip
cost: their names resolve in the real ``BENCHMARK.json``, nothing that was
there moved, and on a reduced trace the reader finds each call site's kernel
under its own name and finds nothing of the other model's."""

import json

import pytest

from benchmark import layer_readers, reduce_trace
from benchmark.tests import test_names_resolve as resolve
from benchmark.tests.test_seam import REPO

KIMI = "kimi-k2.5-int8.reason-closed-64"
MOTIF = "motif-3-beta-int8.longtail-closed-64"
NEW = {"mla_ragged_attention_us": KIMI,
       "gdla_full_ragged_attention_us": MOTIF,
       "gdla_window_ragged_attention_us": MOTIF}


def test_the_entries_are_appended_and_resolve():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    tail = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)
    for m in tail:
        assert m == {"name": m["name"], "unit": "us", "better": "lower",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "out_tokens_per_s",
                     "workloads": [NEW[m["name"]]]}
        resolve.test_layer_metric_resolves("BENCHMARK.json", m["name"])
    # the decode kernels' metrics read other op names and keep their lists
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert listed["mla_decode_attention_us"] == [KIMI]
    assert listed["gdla_full_decode_attention_us"] == [MOTIF]


def _trace(ops):
    """A device plane with ``ops`` (name, calls, us a call) back to back
    inside one program execution, reduced as a run's trace is."""
    events, t = [], 1000
    for name, calls, us in ops:
        for i in range(calls):
            events.append((f"%{name}.{10 + i % 3} = bf16[1,64,512,512]"
                           f"{{3,2,1,0}} custom-call(...)", t, us * 1000))
            t += us * 1000 + 50
    return reduce_trace.reduce_events({"/device:TPU:0": {
        "XLA Ops": events, "XLA Modules": [("jit_mixed_step(77)", 900, t)]}})


def _read(name, trace):
    spec = json.loads((REPO / f"benchmark/layer_metrics/{name}.json")
                      .read_text())
    reader = layer_readers.resolve(spec.pop("kind"))
    spec.pop("what")
    return reader({"trace": trace, "config": {"serving": {}}}, **spec)


def test_each_call_sites_kernel_is_read_under_its_own_name():
    kimi = _trace([("mla_ragged_attention", 30, 450),
                   ("mla_decode_attention", 60, 210),
                   ("fusion", 5, 100)])
    motif = _trace([("gdla_full_ragged_attention", 12, 1100),
                    ("gdla_window_ragged_attention", 42, 190),
                    ("gdla_full_decode_attention", 12, 325),
                    ("gdla_window_decode_attention", 42, 53)])
    assert _read("mla_ragged_attention_us", kimi) == pytest.approx(450.0)
    assert _read("gdla_full_ragged_attention_us", motif) \
        == pytest.approx(1100.0)
    assert _read("gdla_window_ragged_attention_us", motif) \
        == pytest.approx(190.0)
    # the decode kernels' readers are not fooled by the new names, nor the
    # new readers by the decode kernels'
    assert _read("mla_decode_attention_us", kimi) == pytest.approx(210.0)
    assert _read("gdla_window_decode_attention_us", motif) \
        == pytest.approx(53.0)
    # kimi's names find nothing in motif's trace, and motif's none in kimi's
    assert _read("mla_ragged_attention_us", motif) is None
    assert _read("gdla_full_ragged_attention_us", kimi) is None
    assert _read("gdla_window_ragged_attention_us", kimi) is None
    # a trace with no device op at all (a rehearsal on the CPU): nothing
    assert _read("mla_ragged_attention_us", {}) is None
