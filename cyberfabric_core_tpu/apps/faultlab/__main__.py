"""faultlab CLI — run the deterministic chaos-scenario suite.

Usage:
    python -m cyberfabric_core_tpu.apps.faultlab                 # all builtin
    python -m cyberfabric_core_tpu.apps.faultlab --scenario NAME [--seed N]
    python -m cyberfabric_core_tpu.apps.faultlab --file chaos.yaml
    python -m cyberfabric_core_tpu.apps.faultlab --list
    python -m cyberfabric_core_tpu.apps.faultlab --repeat 2      # determinism

Exit code 0 iff every scenario verdict is green (and, with --repeat, every
repeat reproduced the same fingerprint). One JSON document on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv: list[str] | None = None) -> int:
    # CPU pinning BEFORE any jax-touching import: chaos scenarios are
    # host-logic rehearsals, not device work, and the federation scenarios
    # start worker children on the CPU — this process must sit there too
    if not os.environ.get("RUN_TPU_TESTS"):
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            # the pool scenarios need >= 2 virtual devices; the PD-split
            # scenario (2 prefill + 1 decode replicas) needs >= 3
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()

    from .runner import run_scenario
    from .scenarios import BUILTIN_SCENARIOS, load_scenario_file, scenario_by_name

    ap = argparse.ArgumentParser(prog="faultlab")
    ap.add_argument("--scenario", help="run one builtin scenario by name")
    ap.add_argument("--file", help="YAML/JSON file with a scenarios: list")
    ap.add_argument("--seed", type=int, help="override every scenario's seed")
    ap.add_argument("--repeat", type=int, default=1,
                    help="run the suite N times; fingerprints must agree")
    ap.add_argument("--list", action="store_true", help="list scenarios")
    args = ap.parse_args(argv)

    if args.list:
        for spec in BUILTIN_SCENARIOS:
            print(f"{spec['name']:28s} kind={spec['kind']:14s} "
                  f"seed={spec['seed']}")
        return 0

    if args.file:
        specs = load_scenario_file(args.file)
    elif args.scenario:
        specs = [scenario_by_name(args.scenario)]
    else:
        specs = BUILTIN_SCENARIOS

    runs: list[list[dict]] = []
    for _ in range(max(1, args.repeat)):
        results = []
        for spec in specs:
            if args.seed is not None:
                spec = {**spec, "seed": args.seed}
            results.append(run_scenario(spec).to_dict())
        runs.append(results)

    results = runs[0]
    deterministic = all(
        [r["fingerprint"] for r in run] == [r["fingerprint"] for r in runs[0]]
        for run in runs)
    ok = all(r["verdict"] for r in results) and deterministic
    doc = {
        "pass": ok,
        "deterministic": deterministic,
        "repeats": len(runs),
        "scenarios": results,
        "red": [r["name"] for r in results if not r["verdict"]],
    }
    print(json.dumps(doc, indent=1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
