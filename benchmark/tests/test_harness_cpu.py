"""The harness end to end on the CPU at tiny-llama: without a TPU it fails
and prints no result; as a rehearsal it runs through, names the CPU, exits
non-zero, and its last line is not a result the driver could read."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = "benchmark/tests/rehearsal/BENCHMARK.json"


def run(*extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--benchmark-file", BENCH,
         "--workload", "tiny-llama.decode-closed", "--seed", str(2**31 + 11),
         "--seconds", "5", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)


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
        proc = run("--trace", trace, "--rehearse")
        assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-2000:]
        last = proc.stdout.strip().splitlines()[-1]
        assert last.startswith("REHEARSAL ")
        result = json.loads(last[len("REHEARSAL "):])
        assert result["device"]["platform"] == "cpu"
        assert result["attempted"] > 0 and result["failed"] == 0
        if trace == "1":      # no device in the trace: no device metric
            for name in ("decode_step_ms", "decode_step_roofline",
                         "device_idle_share", "attn_kernels_time_share"):
                assert name not in result["metrics"]
