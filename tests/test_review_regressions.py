"""Regression tests for review findings on the modkit core layer."""

import asyncio

import pytest

from cyberfabric_core_tpu.modkit import CancellationToken, WithLifecycle
from cyberfabric_core_tpu.modkit.contracts import Migration
from cyberfabric_core_tpu.modkit.db import Database, ScopableEntity
from cyberfabric_core_tpu.modkit.odata import ODataError
from cyberfabric_core_tpu.modkit.security import SecurityContext
from cyberfabric_core_tpu.modkit.sse import SseBroadcaster

NOTES = ScopableEntity(
    table="notes",
    field_map={"id": "id", "tenant_id": "tenant_id", "title": "title"},
)


@pytest.fixture()
def db():
    d = Database(":memory:")
    d.run_migrations([
        Migration("0001", lambda c: c.execute(
            "CREATE TABLE notes (id TEXT PRIMARY KEY, tenant_id TEXT NOT NULL, title TEXT)"))
    ])
    return d


def ctx():
    return SecurityContext(subject="u", tenant_id="t1")


def test_insert_rejects_unknown_columns(db):
    """Column names are allowlisted on every surface, not just select()."""
    conn = db.secure(ctx(), NOTES)
    with pytest.raises(ODataError, match="unknown column"):
        conn.insert({"title": "x", "body, tenant_id": "('y','t2')--"})
    with pytest.raises(ODataError, match="unknown column"):
        conn.update("someid", {"title = title--": "x"})
    with pytest.raises(ODataError, match="unknown column"):
        conn.count(where={"1=1; --": 1})


def test_failed_migration_rolls_back_ddl(db):
    """DDL inside a failing migration must not persist (explicit BEGIN/ROLLBACK)."""

    def bad(conn):
        conn.execute("CREATE TABLE half_done (id TEXT)")
        raise RuntimeError("second statement failed")

    with pytest.raises(RuntimeError):
        db.run_migrations([Migration("0002_bad", bad)])
    # the half-created table must be gone, and the migration not recorded
    import sqlite3
    with pytest.raises(sqlite3.OperationalError):
        db.raw_for_migrations().execute("SELECT * FROM half_done")
    assert "0002_bad" not in db.applied_migrations()
    # a fixed retry under the same version applies cleanly
    db.run_migrations([Migration("0002_bad", lambda c: c.execute("CREATE TABLE half_done (id TEXT)"))])
    assert "0002_bad" in db.applied_migrations()


def test_lifecycle_oneshot_run_fn_completes_start():
    """A run_fn that returns without calling notify_ready must not hang start()."""

    async def go():
        async def oneshot(token, ready):
            return  # never touches ready

        lc = WithLifecycle("oneshot", oneshot, ready_timeout=2.0)
        await asyncio.wait_for(lc.start(CancellationToken()), timeout=1.0)

    asyncio.run(go())


def test_sse_close_reaches_lagging_subscriber():
    """close() must land the sentinel even on a full queue; late sends can't evict it."""

    async def go():
        b = SseBroadcaster(capacity=4, keepalive_secs=0.05)
        received = []

        async def consume():
            async for ev in b.subscribe():
                received.append(ev)
                await asyncio.sleep(0)  # slow-ish consumer

        task = asyncio.ensure_future(consume())
        await asyncio.sleep(0)  # let it subscribe
        for i in range(20):  # overflow the queue
            b.send(i)
        b.close()
        b.send("late")  # post-close send must be dropped, not displace _CLOSE
        await asyncio.wait_for(task, timeout=2.0)
        assert "late" not in received

    asyncio.run(go())


def test_host_runtime_failed_start_tears_down(fresh_registry):
    """A module that never becomes ready is cancelled and stopped, not leaked."""
    from cyberfabric_core_tpu.modkit import Module, ReadySignal, RunnableCapability, module
    from cyberfabric_core_tpu.modkit.config import AppConfig
    from cyberfabric_core_tpu.modkit.registry import ModuleRegistry
    from cyberfabric_core_tpu.modkit.runtime import HostRuntime, RunOptions

    events = []

    @module(name="neverready", capabilities=["stateful"])
    class NeverReady(Module, RunnableCapability):
        async def init(self, ctx):
            pass

        async def start(self, ctx, ready: ReadySignal):
            events.append("started-bg")
            ready.notify_failed(RuntimeError("refuses to be ready"))

        async def stop(self, ctx):
            events.append("stopped")

    async def go():
        reg = ModuleRegistry.discover_and_build()
        rt = HostRuntime(RunOptions(config=AppConfig(), registry=reg))
        with pytest.raises(RuntimeError, match="refuses"):
            await rt.run_setup_phases()
        assert rt.ctx_for(reg.get("neverready")).cancellation_token.is_cancelled

    asyncio.run(go())
    assert events == ["started-bg", "stopped"]


def test_settings_publish_does_not_materialize_broadcasters():
    """Publish-to-nobody is a no-op and zero-subscriber broadcasters are
    evicted — the per-tenant map must stay bounded by tenants with live
    listeners, not grow with every tenant that ever wrote a setting
    (round-2 advisory)."""
    from cyberfabric_core_tpu.modules.user_settings import UserSettingsModule

    m = UserSettingsModule()
    for i in range(100):
        m._publish(f"tenant-{i}", {"type": "setting.created", "key": "k"})
    assert m._broadcasters == {}

    # a subscriber materializes one; publish reaches it
    b = m._broadcaster("t1")
    received = []

    async def consume():
        async for ev in b.subscribe():
            received.append(ev)
            break

    async def run():
        task = asyncio.ensure_future(consume())
        await asyncio.sleep(0.05)
        m._publish("t1", {"type": "setting.created", "key": "k"})
        await asyncio.wait_for(task, 5)

    asyncio.new_event_loop().run_until_complete(run())
    assert received and received[0]["key"] == "k"

    # last subscriber gone -> next publish evicts the broadcaster
    assert b.subscriber_count == 0
    m._publish("t1", {"type": "setting.deleted", "key": "k"})
    assert "t1" not in m._broadcasters


def test_profiler_stop_failure_recoverable(tmp_path, monkeypatch):
    """A stop_trace that raises must not wedge the profiler endpoints: the
    next /start best-effort clears JAX's possibly-live global tracer instead
    of 500ing forever (round-2 advisory)."""
    import types

    import jax

    from cyberfabric_core_tpu.modkit.errors import ProblemError
    from cyberfabric_core_tpu.modules.monitoring import MonitoringModule

    m = MonitoringModule()
    handlers = {}

    class FakeOp:
        def __init__(self, method, path):
            self._key = (method, path)

        def __getattr__(self, name):
            def chain(*a, **kw):
                if name == "handler":
                    handlers[self._key] = a[0]
                return self
            return chain

    router = types.SimpleNamespace(
        operation=lambda method, path, **kw: FakeOp(method, path))
    ctx = types.SimpleNamespace(
        app_config=types.SimpleNamespace(home_dir=lambda: tmp_path))
    m.register_rest(ctx, router, None)
    start = handlers[("POST", "/v1/monitoring/profiler/start")]
    stop = handlers[("POST", "/v1/monitoring/profiler/stop")]

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **options: calls.append(("start", d)))

    def failing_stop():
        calls.append(("stop",))
        raise RuntimeError("collector died")

    monkeypatch.setattr(jax.profiler, "stop_trace", failing_stop)

    loop = asyncio.new_event_loop()
    try:
        assert loop.run_until_complete(start(None))["status"] == "started"
        with pytest.raises(ProblemError):
            loop.run_until_complete(stop(None))
        assert m._profile_dir is None  # state says stopped, not wedged
        assert m._tracer_maybe_live is True
        # next start must best-effort stop the live tracer, then succeed
        out = loop.run_until_complete(start(None))
        assert out["status"] == "started"
        assert ("stop",) in calls[-3:]
        assert m._tracer_maybe_live is False
    finally:
        loop.close()
