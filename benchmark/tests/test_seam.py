"""The seam between the judge (``correctness.py``) and what it judges, shown
wide enough at no chip cost: a toy second architecture with recurrent state
beside its pages (``rehearsal/toy_recurrent``), reached only through its
configuration file, passes the judge and has its controls caught; the same
adapter with its shared-prefix hook dropping the state fails at the resumed
row; one that advances an idle row's state is seen by the judge's look at
idle rows."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark.correctness import scenario

REPO = Path(__file__).resolve().parents[2]
CONFIGS = "benchmark/tests/rehearsal/configs"
RESUMED_ROW = 2


def judge(config: str, *extra: str) -> tuple[int, dict]:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.correctness", "--config",
         f"{CONFIGS}/{config}.json", "--seeds", "3,4,4294967299", "--rehearse",
         *extra], cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    lines = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert lines, proc.stdout[-2000:] + proc.stderr[-2000:]
    return proc.returncode, json.loads(lines[-1][7:])


def test_the_toy_architecture_passes_and_its_controls_are_caught():
    rc, result = judge("toy-recurrent", "--control")
    assert rc == 0 and result["ok"]
    assert result["adapter"].endswith("toy_recurrent.adapter")
    limit = result["limit"]
    for r in result["readings"]:
        assert r["program"]["worst_row_rms"] <= limit
        assert r["idle_rows_touched"] == []         # exposed, and untouched
        assert r["control_int4"]["worst_row_rms"] > 3 * limit
        assert r["control_fp8"]["worst_row_rms"] > 2 * limit
        assert r["control_kv_int8"]["worst_row_rms"] < limit


def test_a_hook_that_drops_the_state_fails_at_the_resumed_row():
    rc, result = judge("toy-recurrent-broken")
    assert rc != 0 and not result["ok"]
    for r in result["readings"]:
        assert r["program"]["worst_row_rms"] > 3 * result["limit"]
        assert r["program"]["row"][0] == RESUMED_ROW
        assert r["idle_rows_touched"] == []


def test_an_idle_row_whose_state_moved_is_not_ok():
    rc, result = judge("toy-recurrent-leaky")
    assert rc != 0 and not result["ok"]
    assert all(r["idle_rows_touched"] for r in result["readings"])


def test_the_llama_adapter_exposes_no_row_state():
    _, result = judge("tiny-llama")
    assert result["adapter"] == "benchmark.adapters.llama"
    assert all(r["idle_rows_touched"] is None for r in result["readings"])


def test_the_shared_boundary_is_one_the_adapter_can_serve():
    """A page-granular adapter keeps the boundary it had (a page short of row
    B's first chunk); one that has state only where a chunk ends gets that
    chunk whole; a unit the scenario cannot meet is refused."""
    assert scenario(512, 64, 64)["C_shared"] == 448
    assert scenario(64, 16, 16)["C_shared"] == 48
    assert scenario(512, 64, 128)["C_shared"] == 384
    whole = scenario(512, 64, 512)
    assert whole["C_shared"] == 512 and whole["C"] > 512 and whole["B"] > 512
    for unit in (1024, 96):
        with pytest.raises(ValueError):
            scenario(512, 64, unit)


@pytest.mark.parametrize("path", sorted(
    str(p.relative_to(REPO)) for p in (REPO / "benchmark").glob("*.py")))
def test_the_toy_is_named_by_no_file_of_the_harness(path):
    assert not re.search(r"toy", (REPO / path).read_text(), re.IGNORECASE)
