"""70B sharded-load rehearsal CLI → LOAD_70B.json (round-4 verdict item 7).

`FEASIBILITY_70B.json` proves the llama-3-70b tp=8 plan FITS; this tool
proves the plan EXECUTES: it synthesizes an HF-style sharded safetensors
checkpoint at a scaled llama-70b-like geometry (same 80-layer tensor
structure, narrower matrices — env-tunable up to full scale), runs the
per-rank read plan with timed parallel slice reads, KILLS the loader
mid-run and resumes it from the durable manifest, and asserts the bytes
landed per rank match the plan's expectation exactly.

The measured MB/s projects the full llama-3-70b per-rank read time (the
number an operator needs for restart budgets).

Usage: python -m cyberfabric_core_tpu.apps.load_rehearsal [workdir]
Env:   LOAD_SCALE_HIDDEN (default 1024), LOAD_WORKERS (4)

Reference: modules/model-registry/docs/PRD.md:200-224 (managed models,
safetensors sharded checkpoints); BASELINE #5.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

from ..models.configs import ModelConfig, get_config
from ..runtime import shard_loader

TP = 8


def _scaled_cfg(hidden: int) -> ModelConfig:
    """llama-3-70b tensor STRUCTURE (80 layers, GQA 8 kv heads, tied dims)
    at a narrower width — the read plan has the same shape and item count,
    only the bytes shrink."""
    big = get_config("llama-3-70b")
    return ModelConfig(
        name="llama-70b-rehearsal", architecture="llama",
        vocab_size=16384, hidden_size=hidden,
        intermediate_size=int(hidden * 3.5), num_layers=big.num_layers,
        num_heads=64, num_kv_heads=big.num_kv_heads,
        head_dim=hidden // 64, max_position=256, rope_theta=500000.0,
    )


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    work = Path(argv[0]) if argv else Path(tempfile.mkdtemp(prefix="load70b-"))
    work.mkdir(parents=True, exist_ok=True)
    hidden = int(os.environ.get("LOAD_SCALE_HIDDEN", "1024"))
    workers = int(os.environ.get("LOAD_WORKERS", "4"))
    cfg = _scaled_cfg(hidden)

    from ..parallel.feasibility import tp_plan

    plan_report = tp_plan(cfg, TP, quantization="int8")
    plan = plan_report["read_plan"]

    ckpt = work / "ckpt"
    stage = work / "stage"
    report: dict = {"note": (
        "sharded-load rehearsal (round-4 verdict item 7): the "
        "FEASIBILITY_70B read plan executed against real sharded "
        "safetensors on disk — timed parallel per-rank slice reads, a "
        "kill mid-load, a manifest resume, and a landed-bytes-vs-plan "
        "assertion"),
        "geometry": {"name": cfg.name, "layers": cfg.num_layers,
                     "hidden": cfg.hidden_size, "tp": TP}}
    try:
        t0 = time.monotonic()
        shard_loader.synthesize_hf_checkpoint(cfg, ckpt)
        ckpt_bytes = sum(p.stat().st_size
                         for p in ckpt.glob("*.safetensors"))
        report["checkpoint"] = {
            "bytes": ckpt_bytes,
            "shards": len(list(ckpt.glob("*.safetensors"))),
            "synthesize_s": round(time.monotonic() - t0, 1)}

        # ---- leg 1: cold load, killed mid-run (crash rehearsal). The
        # child calls os._exit after N items; exit code 41 is the plan.
        interrupt_at = 120
        code = (
            "import json, sys\n"
            "from cyberfabric_core_tpu.models.configs import ModelConfig\n"
            "from cyberfabric_core_tpu.runtime import shard_loader\n"
            "from cyberfabric_core_tpu.apps.load_rehearsal import _scaled_cfg\n"
            f"cfg = _scaled_cfg({hidden})\n"
            f"plan = json.load(open({str(work / 'plan.json')!r}))\n"
            f"shard_loader.execute_read_plan({str(ckpt)!r}, plan, cfg, {TP},"
            f" {str(stage)!r}, workers={workers},"
            f" interrupt_after_items={interrupt_at})\n"
        )
        (work / "plan.json").write_text(json.dumps(plan))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=1800)
        manifest_file = stage / "manifest.jsonl"
        report["interrupted_leg"] = {
            "exit_code": proc.returncode,
            "crashed_as_planned": proc.returncode == 41,
            "manifest_lines_surviving": (
                sum(1 for _ in open(manifest_file))
                if manifest_file.exists() else 0),
        }
        if proc.returncode != 41:
            # the real cause must land in the artifact, not vanish with the
            # captured pipe — an undiagnosable LOAD_70B.json helps no one
            report["interrupted_leg"]["stderr_tail"] = \
                (proc.stderr or "")[-400:]

        # ---- leg 2: resume in THIS process: skips completed work, reads
        # the rest, then the landed bytes must match the plan exactly
        stats = shard_loader.execute_read_plan(
            ckpt, plan, cfg, TP, stage, workers=workers)
        report["resume_leg"] = stats
        assert stats["items_skipped_resume"] >= interrupt_at, stats

        expected = shard_loader.expected_rank_bytes(plan, cfg, TP)
        landed = shard_loader.staged_rank_bytes(stage, TP)
        report["landed_vs_plan"] = {
            "expected_bytes_per_rank": expected,
            "landed_bytes_per_rank": landed,
            "exact_match": all(b == expected for b in landed),
        }

        # ---- projection to the real llama-3-70b checkpoint
        big_plan = tp_plan("llama-3-70b", TP, quantization="int8")
        big_expected = shard_loader.expected_rank_bytes(
            big_plan["read_plan"], get_config("llama-3-70b"), TP)
        mbs = stats["mb_per_s"]
        report["projection_llama_3_70b"] = {
            "per_rank_read_bytes_bf16": big_expected,
            "measured_mb_per_s": mbs,
            "projected_per_rank_read_s": round(
                big_expected / (mbs * 1e6), 1) if mbs else None,
            "basis": "per-rank slice reads at the rehearsal's measured "
                     "throughput; ranks read in parallel from shared "
                     "storage in production, so wall-clock depends on the "
                     "store's aggregate bandwidth",
        }
        report["pass"] = bool(
            report["interrupted_leg"]["crashed_as_planned"]
            and report["landed_vs_plan"]["exact_match"]
            and stats["items_skipped_resume"] >= interrupt_at)
    except Exception as e:  # noqa: BLE001 — artifact over traceback
        report["pass"] = False
        report["error"] = f"{type(e).__name__}: {e}"[:400]
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
        shutil.rmtree(stage, ignore_errors=True)

    out = Path(__file__).resolve().parents[2] / "LOAD_70B.json"
    out.write_text(json.dumps(report, indent=1))
    print(json.dumps(report))
    return 0 if report.get("pass") else 1


if __name__ == "__main__":
    sys.exit(main())
