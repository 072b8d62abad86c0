"""The start-up timeline's readers (benchmark/startup.py) and the ten
per-layer metrics under ``setup_s`` that read through them: every file fits
its reader, the readers give the numbers a hand gives on a canned context and
nothing (never an exception) on a server without the timeline, the module
imports no JAX, and a rehearsed traced run prints all ten."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import layer_readers, startup
from benchmark.run import load_cell

REPO = Path(__file__).resolve().parents[2]
METRICS = ("server_boot_s", "engine_weights_s", "engine_pool_s",
           "cold_first_token_s", "jax_trace_lower_s", "jax_backend_s",
           "programs_before_window", "compile_cache_hit_share",
           "setup_named_share", "compile_in_window_s")
S = 10**9
BORN = 1_000_000


def stage(name, start_s, end_s, children=(), **attrs):
    return {"name": name, "start_unix_ns": int((BORN + start_s) * S),
            "end_unix_ns": None if end_s is None else int((BORN + end_s) * S),
            "duration_s": None if end_s is None else end_s - start_s,
            "self_s": 0.0, "attrs": attrs, "children": list(children)}


def program(name, event, start_s, end_s, **more):
    return {"kind": "program", "program": name, "event": event,
            "start_unix_ns": int((BORN + start_s) * S),
            "end_unix_ns": int((BORN + end_s) * S),
            "seconds": end_s - start_s, "thread": "t", "stage": "serving",
            **more}


def log_of(*records):
    lines = ["2026-01-01 00:00:00,000 INFO    worker [req=- trace=-]: engine "
             "for local::m built in 9.0 s"]
    for r in records:
        r = r if r.get("kind") else {"kind": "stage", **r}
        line = ("2026-01-01 00:00:00,000 INFO    telemetry [req=- trace=-]: "
                "startup: " + json.dumps(r))
        lines += [line, line]           # a second handler prints it again
    return "\n".join(lines) + "\n"


@pytest.fixture()
def ctx():
    """A server born at BORN: boot 0-20 s; the first request arrives at 30,
    builds 30-40 (weights 30-36 in two stages, pool 36-39), its programs come
    up 41-50, its first chunk leaves at 52; a small program at 60-61; the
    window starts at 100. Another model's first request was 70-72."""
    boot = stage("boot", 0, 20, [stage("boot.imports", 0, 12),
                                 stage("boot.init", 12, 19)], pid=1)
    build = stage("engine.build", 30, 40, [
        stage("engine.weights", 30, 34, source="checkpoint"),
        stage("engine.weights", 34, 36, source="checkpoint"),
        stage("engine.pool", 36, 39, pages=640)], model="local::m")
    first = stage("first_token", 30, 52, [build], model="local::m",
                  request_id="warm-first")
    other = stage("first_token", 70, 72, model="local::other")
    still_open = stage("first_token", 90, None, model="local::m")
    scrape = {"process_start_time_seconds": float(BORN),
              "process_uptime_seconds": 100.0,
              "jax_trace_seconds_total": 7.0, "jax_lower_seconds_total": 5.0,
              "jax_backend_compile_seconds_total": 9.5,
              "jax_backend_compiles_total": 40.0,
              "jax_compile_cache_hits_total": 30.0,
              "jax_compile_cache_misses_total": 10.0}
    return {"server_log": log_of(
                boot, program("_init", "compile", 31, 33, cache_hit=True),
                first, program("mixed_step", "trace", 41, 44),
                program("mixed_step", "compile", 44, 50, cache_hit=False),
                program("iota", "compile", 60, 61, cache_hit=True),
                other, still_open,
                program("late", "compile", 99.5, 101, cache_hit=True)),
            "scrapes": {"start": scrape,
                        "end": {**scrape,
                                "jax_backend_compile_seconds_total": 9.75},
                        "all": [scrape]},
            "config": {"serving": {"model_id": "local::m"}}, "values": {}}


@pytest.fixture(scope="module")
def cell():
    return load_cell(REPO / "BENCHMARK.json", "mistral-7b-int8.decode-closed")


@pytest.mark.parametrize("name", METRICS)
def test_the_metric_is_declared_and_its_file_fits_its_reader(cell, name):
    (entry,) = [m for m in cell["per_layer"] if m["name"] == name]
    assert entry["moves"] == "setup_s" and "workloads" not in entry
    reader, spec = cell["readers"][name]    # resolve_names bound the file
    if name == "compile_in_window_s":
        assert reader is layer_readers.counter
    else:
        assert reader.__module__ == "benchmark.startup"
    assert "what" not in spec and "kind" not in spec


WANT = {"server_boot_s": 20.0, "engine_weights_s": 6.0, "engine_pool_s": 3.0,
        "cold_first_token_s": 22.0, "jax_trace_lower_s": 12.0,
        "jax_backend_s": 9.5, "programs_before_window": 40.0,
        "compile_cache_hit_share": 0.75,
        # boot 20 + build 10 + programs outside both 41-50 and 60-61 + the
        # half second of the last one that lies before the window, of 100
        "setup_named_share": 40.5, "compile_in_window_s": 0.25}


@pytest.mark.parametrize("name", METRICS)
def test_the_reader_gives_the_number_a_hand_gives(cell, ctx, name):
    reader, spec = cell["readers"][name]
    assert reader(ctx, **spec) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", METRICS)
@pytest.mark.parametrize("server", ["parent", "no_scrape"])
def test_a_server_without_the_timeline_reads_nothing(cell, ctx, name, server):
    reader, spec = cell["readers"][name]
    bare = {**ctx, "server_log": "engine for local::m built in 9.0 s\n",
            "scrapes": {"start": {"llm_x": 1.0}, "end": {"llm_x": 2.0},
                        "all": []}}
    if server == "no_scrape":
        bare["scrapes"] = {}
    assert reader(bare, **spec) is None


def test_readers_read_what_is_there_of_a_half_kept_log(ctx):
    """Lines that are not JSON are passed over; a ledger that brought
    nothing up has no hit share."""
    ctx["server_log"] += "x: startup: {not json}\nstartup: \n"
    assert startup.stage_s(ctx, "boot") == 20.0
    assert startup.stage_s(ctx, "engine.thread") is None
    ctx["scrapes"]["start"].update(jax_compile_cache_hits_total=0.0,
                                   jax_compile_cache_misses_total=0.0)
    assert startup.programs_before_window(
        ctx, ["jax_compile_cache_hits_total"],
        over=["jax_compile_cache_hits_total",
              "jax_compile_cache_misses_total"]) is None
    assert startup.stage_s(ctx, "first_token") == 24.0      # both models'
    ctx["config"]["serving"]["model_id"] = "local::none"
    assert startup.stage_s(ctx, "first_token", served_model=True) is None


def test_the_module_imports_no_jax():
    proc = subprocess.run(
        [sys.executable, "-c", "import sys, benchmark.startup; "
         "sys.exit('jax' in sys.modules)"], cwd=REPO, capture_output=True)
    assert proc.returncode == 0, proc.stderr


def test_a_rehearsed_traced_run_prints_all_ten():
    """On the CPU at tiny-llama, over the rehearsal's own BENCHMARK file with
    the ten entries: a number, not None, on each ``layer metric:`` line, the
    old two beside them, and every stage a real server has."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--benchmark-file",
         "benchmark/tests/rehearsal/BENCHMARK-startup.json",
         "--workload", "tiny-llama.decode-closed", "--seed", str(2**31 + 50),
         "--seconds", "5", "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-2000:]
    lines = {ln.split()[2]: ln for ln in proc.stdout.splitlines()
             if ln.startswith("layer metric: ")}
    assert set(lines) == {*METRICS, "engine_build_s", "program_load_s"}
    result = json.loads(proc.stdout.strip().splitlines()[-1][len("REHEARSAL "):])
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(got) >= set(METRICS), {k: lines[k] for k in set(METRICS) - set(got)}
    assert 0 < got["server_boot_s"] < got["setup_named_share"] * 10
    assert 0 < got["setup_named_share"] <= 100.0
    assert got["cold_first_token_s"] > got["engine_weights_s"] > 0
    assert got["compile_in_window_s"] <= 0.5
    assert got["programs_before_window"] >= 3
    assert 0.0 <= got["compile_cache_hit_share"] <= 1.0
    # the same JAX event read two ways: the log's two serving programs are
    # part of the ledger's every program
    assert got["jax_backend_s"] >= got["program_load_s"] > 0
    # the build's stage and the worker's log line, one work timed twice
    log = (REPO / ".bench_work" / "server.log").read_text()
    (build,) = [n for r in startup._records({"server_log": log})
                if r["kind"] == "stage" for n in startup._walk(r)
                if n["name"] == "engine.build"]
    assert build["duration_s"] == pytest.approx(got["engine_build_s"], abs=0.2)
    assert {c["name"] for c in build["children"]} == {
        "engine.config", "engine.weights", "engine.pool", "engine.programs",
        "engine.thread"}
