"""HostRuntime — the phase orchestrator — and the Runner entry point.

Reference: libs/modkit/src/runtime/host_runtime.rs (phase list at :6-14;
run_pre_init_phase :130, run_db_phase :259, run_init_phase :295, run_post_init_phase
:326, run_rest_phase :356 — exactly-one ApiGatewayCapability enforced at :369-383,
run_grpc_phase :449, run_start_phase :521, run_stop_phase :563,
run_module_phases :678) and runtime/runner.rs (`RunOptions` :99, `run` :131).

Phases, in order:
  pre_init (system) → db (resolve + migrate) → init (topo order) → post_init (system)
  → rest (host.rest_prepare → each register_rest → host.rest_finalize)
  → grpc (collect installers) → start (system-first) → wait → stop (reverse order)
"""

from __future__ import annotations

import asyncio
import logging
import signal
import uuid
from dataclasses import dataclass, field
from typing import Any, Optional

from .cancellation import CancellationToken
from .client_hub import ClientHub
from .config import AppConfig
from .contracts import (
    ApiGatewayCapability,
    DatabaseCapability,
    GrpcServiceCapability,
    RestApiCapability,
    RunnableCapability,
    SystemCapability,
)
from .context import ModuleCtx
from .lifecycle import ReadySignal
from .registry import ModuleEntry, ModuleRegistry
from .telemetry import startup

logger = logging.getLogger(__name__)


@dataclass
class RunOptions:
    config: AppConfig
    registry: ModuleRegistry
    client_hub: ClientHub = field(default_factory=ClientHub)
    shutdown_token: Optional[CancellationToken] = None
    install_signal_handlers: bool = False
    db_manager: Optional[Any] = None  # modkit.db.DbManager


class HostRuntime:
    """Drives all modules through the lifecycle phases."""

    def __init__(self, opts: RunOptions) -> None:
        self.opts = opts
        self.registry = opts.registry
        self.hub = opts.client_hub
        self.config = opts.config
        self.instance_id = str(uuid.uuid4())
        self.root_token = opts.shutdown_token or CancellationToken()
        self._ctxs: dict[str, ModuleCtx] = {}
        self._started: list[ModuleEntry] = []
        self._rest_host: Optional[ModuleEntry] = None
        self.grpc_installers: list[tuple[str, Any]] = []

    # ------------------------------------------------------------------ contexts
    def ctx_for(self, entry: ModuleEntry) -> ModuleCtx:
        ctx = self._ctxs.get(entry.name)
        if ctx is None:
            ctx = ModuleCtx(
                module_name=entry.name,
                app_config=self.config,
                client_hub=self.hub,
                cancellation_token=self.root_token.child_token(),
                instance_id=self.instance_id,
            )
            self._ctxs[entry.name] = ctx
        return ctx

    # ------------------------------------------------------------------ phases
    async def run_pre_init_phase(self) -> None:
        for entry in self.registry.with_capability("system"):
            assert isinstance(entry.instance, SystemCapability)
            await entry.instance.pre_init(self.ctx_for(entry))

    async def run_db_phase(self) -> None:
        """Resolve a per-module isolated DB handle and run its migrations
        (host_runtime.rs:259; libs/modkit-db/src/migration_runner.rs)."""
        dbm = self.opts.db_manager
        for entry in self.registry.with_capability("db"):
            assert isinstance(entry.instance, DatabaseCapability)
            if dbm is None:
                raise RuntimeError(
                    f"module {entry.name} declares db capability but no DbManager given"
                )
            ctx = self.ctx_for(entry)
            ctx.db = dbm.db_for_module(entry.name)
            ctx.db.run_migrations(entry.instance.migrations())

    async def run_init_phase(self) -> None:
        for entry in self.registry.entries:  # already topo-sorted
            with startup.stage("boot.init." + entry.name):
                await entry.instance.init(self.ctx_for(entry))

    async def run_post_init_phase(self) -> None:
        for entry in self.registry.with_capability("system"):
            assert isinstance(entry.instance, SystemCapability)
            await entry.instance.post_init(self.ctx_for(entry))

    async def run_rest_phase(self) -> None:
        hosts = self.registry.with_capability("rest_host")
        providers = self.registry.with_capability("rest")
        if not hosts:
            if providers:
                raise RuntimeError(
                    f"modules {[e.name for e in providers]} provide REST routes "
                    "but no rest_host module is registered"
                )
            return
        if len(hosts) > 1:
            # exactly one REST host per process (host_runtime.rs:369-383)
            raise RuntimeError(
                f"exactly one rest_host allowed, found {[e.name for e in hosts]}"
            )
        host = hosts[0]
        self._rest_host = host
        assert isinstance(host.instance, ApiGatewayCapability)
        router, openapi = host.instance.rest_prepare(self.ctx_for(host))
        for entry in providers:
            assert isinstance(entry.instance, RestApiCapability)
            entry.instance.register_rest(self.ctx_for(entry), router, openapi)
        host.instance.rest_finalize(self.ctx_for(host), router, openapi)

    async def run_grpc_phase(self) -> None:
        """Collect gRPC installers; in-process modules install into the hub's
        server right away, OoP-configured ones install in their own process."""
        try:
            from .transport_grpc import JsonGrpcServer

            server = self.hub.try_get(JsonGrpcServer)
        except ImportError:  # grpc not available in this environment
            server = None
        for entry in self.registry.with_capability("grpc"):
            assert isinstance(entry.instance, GrpcServiceCapability)
            self.grpc_installers.append((entry.name, entry.instance))
            is_oop = self.config.module_entry(entry.name).get("runtime") == "oop"
            if server is not None and not is_oop:
                entry.instance.register_grpc(self.ctx_for(entry), server)

    async def run_start_phase(self) -> None:
        """Start runnables, system modules first (host_runtime.rs:521)."""
        runnables = self.registry.with_capability("stateful")
        ordered = [e for e in runnables if e.has_capability("system")] + [
            e for e in runnables if not e.has_capability("system")
        ]
        for entry in ordered:
            assert isinstance(entry.instance, RunnableCapability)
            ready = ReadySignal()
            ctx = self.ctx_for(entry)
            with startup.stage("boot.start." + entry.name):
                await entry.instance.start(ctx, ready)
                try:
                    await ready.wait(timeout=30.0)
                except asyncio.TimeoutError:
                    await self._abort_failed_start(entry)
                    raise RuntimeError(
                        f"module {entry.name} did not become ready in 30s")
                except Exception:
                    await self._abort_failed_start(entry)
                    raise
            self._started.append(entry)
            logger.info("module %s running", entry.name)

    async def _abort_failed_start(self, entry: ModuleEntry) -> None:
        """A module whose start() spawned work but never became ready must still be
        torn down — cancel its token and attempt stop() so nothing leaks."""
        ctx = self.ctx_for(entry)
        ctx.cancellation_token.cancel()
        try:
            await entry.instance.stop(ctx)  # type: ignore[union-attr]
        except Exception:
            logger.exception("module %s failed to stop after failed start", entry.name)

    async def run_oop_spawn_phase(self) -> None:
        """Spawn modules configured with ``runtime: oop`` as child processes
        (host_runtime.rs:577; the process boundary is crossed here). Requires the
        grpc_hub module for directory registration."""
        oop_modules = [
            name for name in self.config.module_names()
            if (self.config.module_entry(name).get("runtime") == "oop")
        ]
        if not oop_modules:
            return
        from .oop import LocalProcessBackend

        endpoint = None
        for entry in self.registry.entries:
            if entry.name == "grpc_hub":
                endpoint = getattr(entry.instance, "endpoint", None)
        if endpoint is None:
            raise RuntimeError(
                f"modules {oop_modules} configured runtime=oop but grpc_hub is "
                "not running (no directory endpoint)")
        self.oop_backend = LocalProcessBackend()
        for name in oop_modules:
            await self.oop_backend.spawn(
                name, endpoint, module_config=self.config.module_config(name))

    async def run_stop_phase(self) -> None:
        """Stop in reverse start order; OoP children first (host_runtime.rs:563)."""
        backend = getattr(self, "oop_backend", None)
        if backend is not None:
            await backend.stop_all()
            self.oop_backend = None
        for entry in reversed(self._started):
            assert isinstance(entry.instance, RunnableCapability)
            try:
                await entry.instance.stop(self.ctx_for(entry))
            except Exception:
                logger.exception("module %s failed to stop cleanly", entry.name)
        self._started.clear()

    # ------------------------------------------------------------------ drivers
    async def run_setup_phases(self) -> None:
        """Everything up to (and including) start — then the host is serving.
        Each phase is a stage of the start-up timeline (a child of ``boot``
        where ``server.py`` opened it), and its end is ``ready``."""
        for name, phase in (("pre_init", self.run_pre_init_phase),
                            ("db", self.run_db_phase),
                            ("init", self.run_init_phase),
                            ("post_init", self.run_post_init_phase),
                            ("rest", self.run_rest_phase),
                            ("grpc", self.run_grpc_phase),
                            ("start", self.run_start_phase),
                            ("oop_spawn", self.run_oop_spawn_phase)):
            with startup.stage("boot." + name):
                await phase()
        startup.ready()

    async def run_module_phases(self) -> None:
        """Full lifecycle: setup → wait for cancellation → stop
        (host_runtime.rs:678)."""
        try:
            await self.run_setup_phases()
            await self.root_token.cancelled()
        finally:
            await self.run_stop_phase()

    async def run_migration_phases(self) -> None:
        """`migrate` subcommand: pre_init + db phase only (host_runtime.rs:691)."""
        await self.run_pre_init_phase()
        await self.run_db_phase()


class Runner:
    """Thin wrapper mirroring runtime/runner.rs:131."""

    @staticmethod
    async def run(opts: RunOptions) -> HostRuntime:
        runtime = HostRuntime(opts)
        if opts.install_signal_handlers:
            loop = asyncio.get_running_loop()
            for sig in (signal.SIGTERM, signal.SIGINT):
                try:
                    loop.add_signal_handler(sig, runtime.root_token.cancel)
                except NotImplementedError:
                    pass
        await runtime.run_module_phases()
        return runtime
