"""Every name in the data files resolves: for ``BENCHMARK.json`` and the
rehearsal's, each configuration (its file parses, its adapter imports and has
the seam's members, its counts module imports, its server file is there),
each per-layer metric (its file parses, its reader kind resolves and takes the
file's arguments, the role of a ``roofline`` is counted for some
configuration) and each traffic file. No compile, no device: a broken name in
a later PR's data files fails here, not after 100 s of set-up on the chip.
And ``run.py`` itself resolves a cell's names before it starts a child: a
run with a broken one ends in seconds, with no child started."""

import inspect
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from benchmark import layer_readers, loadgen, names, opcounts
from benchmark.correctness import scenario
from benchmark.run import find_file

REPO = Path(__file__).resolve().parents[2]
BENCH_FILES = ("BENCHMARK.json", "benchmark/tests/rehearsal/BENCHMARK.json")
ADAPTER_MEMBERS = ("make_weights", "reference_logits", "bind",
                   "PROGRAM_CONTROLS")
BINDING_MEMBERS = ("new_state", "share_prefix", "mixed", "decode", "logits")


def entries(section: str) -> list:
    out = []
    for f in BENCH_FILES:
        bench = json.loads((REPO / f).read_text())
        things = (sorted({w["traffic"] for w in bench["workloads"]})
                  if section == "traffic" else
                  [e["name"] for e in bench[section]])
        out += [pytest.param(f, name, id=f"{Path(f).parent.name or 'root'}:{name}")
                for name in things]
    return out


def bench_of(f: str) -> dict:
    return json.loads((REPO / f).read_text())


@pytest.mark.parametrize("bench_file, name", entries("configs"))
def test_configuration_resolves(bench_file, name):
    bench = bench_of(bench_file)
    entry = next(c for c in bench["configs"] if c["name"] == name)
    conf = json.loads((REPO / entry["file"]).read_text())
    assert (REPO / conf["serving"]["yaml"]).is_file()
    assert all(isinstance(p, str) for p in conf["serving"].get("programs", []))
    cc = conf["correctness"]
    assert {"depth", "chunk", "decode_steps", "limit"} <= set(cc)
    adapter = names.load(names.adapter_of(conf))
    for member in ADAPTER_MEMBERS:
        assert hasattr(adapter, member), f"{adapter.__name__} lacks {member}"
    binding = adapter.bind(conf, cc["depth"], 4)    # builds no program yet
    for member in BINDING_MEMBERS:
        assert callable(getattr(binding, member, None)), \
            f"{adapter.__name__}: bind() returns no {member}"
    page = conf["serving"]["page"]       # the scenario meets its prefix unit
    scenario(cc["chunk"], page, getattr(binding, "prefix_unit", page))
    controls = cc.get("controls", {})
    assert set(controls) <= {"caught", "read_only"}
    if "counts" in conf:
        names.load(conf["counts"])
    assert any(w["config"] == name for w in bench["workloads"])


@pytest.mark.parametrize("bench_file, name", entries("per_layer"))
def test_layer_metric_resolves(bench_file, name):
    bench = bench_of(bench_file)
    spec = json.loads(find_file(bench["paths"],
                                f"layer_metrics/{name}.json").read_text())
    reader = layer_readers.resolve(spec.pop("kind"))
    spec.pop("what", None)
    inspect.signature(reader).bind({}, **spec)     # the file's arguments fit
    if "count_function" in spec:
        confs = [json.loads((REPO / c["file"]).read_text())
                 for c in bench["configs"]]
        assert any(opcounts.count_function(c, spec["count_function"])
                   for c in confs)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert set(entry.get("workloads", [])) <= {w["name"]
                                               for w in bench["workloads"]}


@pytest.mark.parametrize("bench_file, name", entries("traffic"))
def test_traffic_file_resolves(bench_file, name):
    bench = bench_of(bench_file)
    mix = json.loads(find_file(bench["paths"],
                               f"traffic/{name}.json").read_text())
    a, b = (loadgen.build_schedule(mix, seed)["items"] for seed in (1, 2**31 + 5))
    for size in ("prompt_tokens", "max_tokens"):    # every seed, the same work
        assert sorted(getattr(i, size) for i in a) \
            == sorted(getattr(i, size) for i in b)


TOY = "benchmark.tests.rehearsal.toy_recurrent"
BROKEN = {
    "adapter": ({"correctness": {"adapter": "benchmark.adapters.no_such"}},
                None, "names no module"),
    "counts": ({"counts": "benchmark.no_such_counts"}, None,
               "names nothing that can be loaded"),
    # the parent imports the counts module, and never JAX
    "counts-import-jax": ({"counts": f"{TOY}.weights"}, None, "imports JAX"),
    "reader-kind": ({}, {"kind": f"{TOY}.readers:no_such"},
                    "names nothing that can be loaded"),
    "reader-arguments": ({}, {"kind": "counter", "serie": "x"},
                         "does not fit its reader counter"),
}


@pytest.mark.parametrize("case", sorted(BROKEN))
def test_a_broken_name_ends_the_run_before_any_child(case, tmp_path):
    conf_patch, metric_file, told = BROKEN[case]
    bench = bench_of(BENCH_FILES[1])
    entry = next(c for c in bench["configs"] if c["name"] == "toy-recurrent")
    conf = json.loads((REPO / entry["file"]).read_text())
    for key, value in conf_patch.items():
        conf[key] = {**conf[key], **value} if isinstance(value, dict) else value
    entry["file"] = str(tmp_path / "conf.json")
    (tmp_path / "conf.json").write_text(json.dumps(conf))
    if metric_file:
        (tmp_path / "layer_metrics").mkdir()
        (tmp_path / "layer_metrics" / "mixed_prefill_tokens.json").write_text(
            json.dumps(metric_file))
        bench["paths"] = [str(tmp_path), *bench["paths"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--benchmark-file",
         str(tmp_path / "BENCHMARK.json"), "--workload",
         "toy-recurrent.decode-closed", "--seed", "1", "--seconds", "5",
         "--trace", "0", "--rehearse"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1 and time.monotonic() - t0 < 30
    assert told in proc.stderr, proc.stderr[-1500:]
    assert "correctness:" not in proc.stdout and not proc.stdout.strip()
