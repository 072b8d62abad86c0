"""``ragged_paged_attention_us`` (PR 55, a data file only): the one reading
of the ragged K/V kernel's call for the eight cells that had none (laguna's
two call sites have their own names and their own files). Shown at no chip
cost: the entry stands behind laguna's in ``BENCHMARK.json`` (a later PR
appends behind it), its names resolve, its
cells are those of ``attn_kernels_time_share``, and on a reduced trace the
reader finds the kernel under its own name and nothing under another's."""

import json

import pytest

from benchmark import layer_readers, reduce_trace
from benchmark.tests import test_names_resolve as resolve
from benchmark.tests.test_seam import REPO

NAME = "ragged_paged_attention_us"


def test_the_entry_is_appended_and_resolves():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    order = [m["name"] for m in bench["per_layer"]]
    assert order[order.index(NAME) - 1] == "attn_window_pages_per_program"
    assert bench["per_layer"][order.index(NAME)] == {
        "name": NAME, "unit": "us", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "out_tokens_per_s",
        "workloads": listed["attn_kernels_time_share"]}
    assert len(listed[NAME]) == 8
    resolve.test_layer_metric_resolves("BENCHMARK.json", NAME)
    # every cell that calls the K/V kernel has a reading of it, and no other
    cells = {w["name"] for w in bench["workloads"]}
    assert cells - set(listed[NAME]) == {
        *listed["mla_ragged_attention_us"],
        *listed["gdla_full_ragged_attention_us"],
        *listed["gqa_full_ragged_attention_us"],
        *listed["dsa_ragged_attention_us"]}     # PR 58: its own names


def _trace(ops):
    """A device plane with ``ops`` (name, calls, us a call) back to back
    inside one program execution, reduced as a run's trace is."""
    events, t = [], 1000
    for name, calls, us in ops:
        for i in range(calls):
            events.append((f"%{name}.{10 + i % 3} = bf16[1,16,8,128,128]"
                           f"{{4,3,2,1,0}} custom-call(...)", t, us * 1000))
            t += us * 1000 + 50
    return reduce_trace.reduce_events({"/device:TPU:0": {
        "XLA Ops": events, "XLA Modules": [("jit_mixed_step(77)", 900, t)]}})


def _read(name, trace):
    spec = json.loads((REPO / f"benchmark/layer_metrics/{name}.json")
                      .read_text())
    reader = layer_readers.resolve(spec.pop("kind"))
    spec.pop("what")
    return reader({"trace": trace, "config": {"serving": {}}}, **spec)


def test_the_kernel_is_read_under_its_own_name_and_no_other():
    qwen2 = _trace([("ragged_paged_attention", 28, 130),
                    ("paged_decode_attention", 56, 95), ("fusion", 5, 100)])
    laguna = _trace([("gqa_full_ragged_attention", 3, 700),
                     ("gqa_window_ragged_attention", 9, 500),
                     ("gqa_window_decode_attention", 9, 60)])
    kimi = _trace([("mla_ragged_attention", 30, 450)])
    assert _read(NAME, qwen2) == pytest.approx(130.0)
    assert _read("paged_decode_attention_us", qwen2) == pytest.approx(95.0)
    # laguna's call sites keep their names and their files; the latent
    # twin's name is another kernel's
    assert _read(NAME, laguna) is None and _read(NAME, kimi) is None
    assert _read("gqa_window_ragged_attention_us", laguna) \
        == pytest.approx(500.0)
    assert _read("gqa_full_ragged_attention_us", qwen2) is None
    # a trace with no device op at all (a rehearsal on the CPU, or a parent
    # whose window held no mixed step): nothing, and no error
    assert _read(NAME, {}) is None
