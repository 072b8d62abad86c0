"""The harness end to end on the CPU at tiny-llama: without a TPU it fails
and prints no result; as a rehearsal it runs through, names the CPU, exits
non-zero, and its last line is not a result the driver could read. The cell
of the toy second architecture (rehearsal/toy_recurrent, named only by its
configuration file) runs through the same way, with its ``counter`` metric
and its own count on the line."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = "benchmark/tests/rehearsal/BENCHMARK.json"


def run(*extra, workload="tiny-llama.decode-closed"):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--benchmark-file", BENCH,
         "--workload", workload, "--seed", str(2**31 + 11),
         "--seconds", "5", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)


def rehearsed(proc) -> dict:
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    result = json.loads(last[len("REHEARSAL "):])
    # every number compared stands beside its limit at the end of stderr too
    told = proc.stderr.strip().splitlines()[-7:]
    assert all(line.startswith("compared: ") for line in told), told
    assert sum("(limit" in line for line in told) == 6
    assert told[-1] == f"compared: correct {result['correct']}"
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    return result


def test_without_a_tpu_there_is_no_result():
    proc = run("--trace", "0")
    assert proc.returncode != 0
    last = proc.stdout.strip().splitlines()[-1]
    try:
        parsed = json.loads(last)
    except ValueError:
        parsed = None
    assert not isinstance(parsed, dict) or "metrics" not in parsed


def test_rehearsal_runs_through_and_is_not_a_result():
    for trace in ("0", "1"):
        result = rehearsed(run("--trace", trace, "--rehearse"))
        if trace == "1":      # no device in the trace: no device metric
            for name in ("decode_step_ms", "decode_step_roofline",
                         "device_idle_share", "attn_kernels_time_share"):
                assert name not in result["metrics"]
            # the toy cell's metrics list that cell alone
            assert "mixed_prefill_tokens" not in result["metrics"]


def test_the_toy_architectures_cell_runs_through():
    proc = run("--trace", "1", "--rehearse",
               workload="toy-recurrent.decode-closed")
    result = rehearsed(proc)
    assert result["correct"]
    assert "correctness: adapter benchmark.tests.rehearsal.toy_recurrent.adapter" \
        in proc.stdout
    # its counter metric moved, and the count on the line is its own
    assert result["metrics"]["mixed_prefill_tokens"]["value"] > 0
    from benchmark import opcounts
    conf = json.loads((REPO / "benchmark/tests/rehearsal/configs"
                       / "toy-recurrent.json").read_text())
    llamas = opcounts.decode_step_weights(conf, conf["serving"])["bytes"]
    assert result["metrics"]["state_step_bytes"]["value"] > llamas
