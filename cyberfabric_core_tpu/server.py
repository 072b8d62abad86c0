"""hyperspot-server equivalent: the CLI entry point.

Reference: apps/hyperspot-server/src/main.rs:23-64 — subcommands run|check|migrate,
flags --print-config, --list-modules, --mock (in-memory DB).

Usage:
    python -m cyberfabric_core_tpu.server run --config config/quickstart.yaml
    python -m cyberfabric_core_tpu.server check --config ...
    python -m cyberfabric_core_tpu.server migrate --config ...
    python -m cyberfabric_core_tpu.server run --print-config / --list-modules
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import sys
from typing import Optional, Sequence

from .modkit import AppConfig, ClientHub, ModuleRegistry, RunOptions
from .modkit.db import DbManager
from .modkit.runtime import HostRuntime, Runner
from .modkit.telemetry import jax_devices, startup


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="tpu-fabric-server",
                                description="TPU-native modular service host")
    p.add_argument("command", choices=["run", "check", "migrate"], nargs="?",
                   default="run")
    p.add_argument("--config", "-c", help="YAML config path")
    p.add_argument("--mock", action="store_true",
                   help="in-memory DBs (reference --mock parity)")
    p.add_argument("--print-config", action="store_true",
                   help="dump the effective (redacted) config and exit")
    p.add_argument("--list-modules", action="store_true",
                   help="list registered modules and exit")
    p.add_argument("--log-level", default=None)
    return p


def _load_modules() -> None:
    """Import side effects register every module (registered_modules.rs parity)."""
    from . import modules  # noqa: F401


def _setup_logging(config: AppConfig, override: Optional[str]) -> None:
    from .modkit.logging_host import init_logging_unified

    section = dict(config.section("logging"))
    if override:
        section["level"] = override
    init_logging_unified(section)


def main(argv: Optional[Sequence[str]] = None) -> int:
    # the start-up timeline's root, from the process's start as the OS has
    # it: the interpreter and every import up to here are its first child
    startup.begin_boot()
    with startup.stage("boot.imports",
                       start_unix_ns=startup.process_start_unix_ns):
        args = build_parser().parse_args(argv)
        _load_modules()

    with startup.stage("boot.config"):
        try:
            config = AppConfig.load_or_default(args.config)
        except Exception as e:  # noqa: BLE001
            print(f"config error: {e}", file=sys.stderr)
            return 2
        _setup_logging(config, args.log_level)

    if args.print_config:
        print(json.dumps(config.dump_effective(), indent=2))
        return 0
    if args.list_modules:
        from .modkit.registry import registrations

        enabled = config.module_names()
        for reg in sorted(registrations(), key=lambda r: r.name):
            mark = "*" if (not enabled or reg.name in enabled) else " "
            print(f"{mark} {reg.name:<22} deps={list(reg.deps)} caps={list(reg.capabilities)}")
        return 0

    with startup.stage("boot.registry"):
        enabled = config.module_names() or None
        registry = ModuleRegistry.discover_and_build(enabled=enabled)
        db_manager = DbManager(
            home_dir=None if args.mock else config.home_dir(),
            in_memory=args.mock)
        opts = RunOptions(config=config, registry=registry,
                          client_hub=ClientHub(), db_manager=db_manager,
                          install_signal_handlers=True)

    if args.command == "check":
        # validate: config parsed, modules resolvable, routes registrable
        print(f"config OK ({len(registry.entries)} modules: "
              f"{', '.join(registry.names())})")
        return 0
    if args.command == "migrate":
        async def migrate() -> None:
            await HostRuntime(opts).run_migration_phases()

        asyncio.run(migrate())
        print("migrations applied")
        return 0

    from .ops.platform import enable_compile_cache

    with startup.stage("boot.jax"):
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            # enable_compile_cache() is about to ask on_tpu(): the backend
            # comes up here, under its own name (boot.device_init)
            jax_devices()
        enable_compile_cache()
        # the compile ledger: every trace, lowering and backend compile (or
        # cache load) from here on, from JAX's own events
        startup.install_jax_listeners()

    async def serve() -> None:
        await Runner.run(opts)

    try:
        asyncio.run(serve())
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
