"""The comparison that decides ``correct``, with its control, at a size a
test run can hold: the program at int8 passes; the same program with
int4-grid weights and the reference at float8 activations are caught; the
reference with int8 K/V is read and, being below bfloat16's own rounding, is
not, on three seeds. (On the chip the same command
ran at the published widths; PERF.md section 2 has those readings.)"""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
TINY = "benchmark/tests/rehearsal/configs/tiny-llama.json"


def test_int8_passes_and_the_controls_are_caught():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.correctness", "--config", TINY,
         "--seeds", "3,4,4294967299", "--control", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    result = json.loads(next(line for line in proc.stdout.splitlines()
                             if line.startswith("RESULT "))[7:])
    limit = result["limit"]
    for r in result["readings"]:
        assert r["program"]["worst_row_rms"] <= limit
        assert r["control_int4"]["worst_row_rms"] > 3 * limit
        assert r["control_fp8"]["worst_row_rms"] > 2 * limit
        assert r["control_kv_int8"]["worst_row_rms"] < limit


def test_reference_imports_nothing_from_the_program():
    for name in ("reference.py", "weights.py"):
        text = (REPO / "benchmark" / name).read_text()
        assert "cyberfabric_core_tpu" not in text.replace(
            "``models/llama.py``", "")
