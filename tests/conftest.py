"""Test configuration: force JAX onto a virtual 8-device CPU mesh BEFORE jax imports.

Mirrors the reference's testcontainers strategy (SURVEY §4.3) — multi-device behavior
is tested without fixed TPU infra by forcing XLA's host platform to expose 8 virtual
devices; sharding/collective code paths compile and execute for real.
"""

import os

# Both variables are read when JAX first initialises a backend, which no import
# above this line has done.
if not os.environ.get("RUN_TPU_TESTS"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    assert jax.devices()[0].platform == "cpu", "tests must run on the virtual CPU mesh"

import faulthandler  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

#: The one time limit of every test, for each of its set-up, call and tear-down:
#: about five times the slowest item measured under six workers (a 43 s
#: module-scoped fixture, which counts against the item that builds it). A test
#: that waits on a socket, a subprocess or a thread states its own, shorter one.
TEST_TIME_LIMIT_S = 240.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running gates excluded from the tier-1 `-m 'not slow'` run")


def _time_limited(item, phase):
    """Fail `item` when one phase outlasts TEST_TIME_LIMIT_S and let the worker
    go on. SIGALRM, because tests run in the main thread of each worker and
    pytest-timeout is not installed where the driver runs. The alarm repeats:
    asyncio's `Handle._run` logs and drops an exception raised inside a
    callback, so a shot that lands there is followed by another."""
    def on_alarm(signum, frame):
        faulthandler.dump_traceback(file=sys.__stderr__)  # every thread, not only ours
        pytest.fail(f"{item.nodeid}: {phase} passed the time limit of "
                    f"{TEST_TIME_LIMIT_S:g} s", pytrace=True)

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_TIME_LIMIT_S, TEST_TIME_LIMIT_S / 8)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _time_limited(item, "set-up"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _time_limited(item, "call"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    return (yield from _time_limited(item, "tear-down"))


#: How long a test waits for one websocket message: a first chat of a cold server
#: compiles for over 30 s beside five other workers.
WS_RECEIVE_TIMEOUT_S = 120.0

#: The doctor of every test server that does not state its own: latency
#: objectives and watchdog floors past anything a test lives through, and an
#: error budget that cannot reach `critical_burn`. At the shipped objectives a
#: cold compile on a loaded CPU burns `ttft_p95` and the server sheds the
#: test's own tenant (429). Tests about the SLO engine, the doctor or shedding
#: give their server a `doctor` block of their own, which is left alone.
_OUT_OF_REACH_S = 4 * TEST_TIME_LIMIT_S
QUIET_DOCTOR = {
    "objectives": {
        "ttft_p95": {"threshold_ms": _OUT_OF_REACH_S * 1000},
        "itl_p99": {"threshold_ms": _OUT_OF_REACH_S * 1000},
        "queue_wait_p95": {"threshold_ms": _OUT_OF_REACH_S * 1000},
        "error_rate": {"budget": 1.0},
    },
    "round_stall_floor_s": _OUT_OF_REACH_S, "stream_stall_s": _OUT_OF_REACH_S,
    "queue_deadline_s": _OUT_OF_REACH_S, "loop_stall_s": _OUT_OF_REACH_S,
}


async def boot_stack(overrides, *, extra=None, db_manager=None):
    """Boot the modules `overrides` names (or the `extra` registrations alone)
    behind a gateway on an ephemeral port; returns `(runtime, base_url)`.
    Where the stack boots `monitoring` without a `doctor` block it gets
    QUIET_DOCTOR. The database is in memory unless `db_manager` says otherwise."""
    import copy

    from cyberfabric_core_tpu.modkit import (AppConfig, ClientHub, ModuleRegistry,
                                             RunOptions)
    from cyberfabric_core_tpu.modkit.db import DbManager
    from cyberfabric_core_tpu.modkit.runtime import HostRuntime

    overrides = copy.deepcopy(overrides)
    if "monitoring" in overrides.get("modules", {}):
        entry = overrides["modules"]["monitoring"]
        entry.setdefault("config", {}).setdefault("doctor", QUIET_DOCTOR)
    cfg = AppConfig.load_or_default(environ={}, cli_overrides=overrides)
    if extra is None:
        import cyberfabric_core_tpu.modules  # noqa: F401 — registers everything
        registry = ModuleRegistry.discover_and_build(enabled=cfg.module_names())
    else:
        registry = ModuleRegistry.discover_and_build(extra=extra)
    rt = HostRuntime(RunOptions(
        config=cfg, registry=registry, client_hub=ClientHub(),
        db_manager=db_manager or DbManager(in_memory=True)))
    await rt.run_setup_phases()
    port = registry.get("api_gateway").instance.bound_port
    return rt, f"http://127.0.0.1:{port}"


async def stop_stack(rt):
    """Tear down what `boot_stack` booted (the oagw's client session first,
    where the stack has one)."""
    if "oagw" in rt.registry.names():
        await rt.registry.get("oagw").instance.service.close()
    rt.root_token.cancel()
    await rt.run_stop_phase()


async def ws_event(ws, seen, timeout=WS_RECEIVE_TIMEOUT_S):
    """The next data message of a websocket: the parsed event of a text frame,
    the bytes of a binary one. A socket that ends or stays silent for
    `timeout` fails the test with `seen`, to which every message is added."""
    import asyncio
    import json

    import aiohttp

    try:
        msg = await ws.receive(timeout=timeout)
    except asyncio.TimeoutError:
        pytest.fail(f"no websocket message within {timeout:g} s after {seen!r}")
    if msg.type not in (aiohttp.WSMsgType.TEXT, aiohttp.WSMsgType.BINARY):
        # CLOSE/CLOSING/CLOSED/ERROR: receive() would return the same at once,
        # for ever (ping and pong never reach the caller)
        pytest.fail(f"websocket ended with {msg!r} after {seen!r}")
    event = json.loads(msg.data) if msg.type == aiohttp.WSMsgType.TEXT else msg.data
    seen.append(event)
    return event


@pytest.fixture()
def client_hub():
    from cyberfabric_core_tpu.modkit import ClientHub

    return ClientHub()


@pytest.fixture()
def retrace():
    """``runtime/programs.py`` keeps a key's step programs for the process.
    A test that asserts a compile is seen, or patches what a program reads
    while it is traced, starts with none kept; called after a patch, the
    next engine of the key traces anew; what the test traced goes with it."""
    from cyberfabric_core_tpu.runtime.programs import step_programs

    step_programs.cache_clear()
    yield step_programs.cache_clear
    step_programs.cache_clear()


def run_request(sched, prompt, sampling, timeout=120.0):
    """One request through a scheduler (or a pool): ``(tokens, finish)``."""
    import threading

    done = threading.Event()
    tokens: list[int] = []
    finish: list[str] = []

    def emit(ev):
        if ev.token_id >= 0:
            tokens.append(ev.token_id)
        if ev.finished:
            finish.append(ev.finished)
            done.set()

    sched.submit(prompt, sampling, emit)
    assert done.wait(timeout), "request did not finish"
    return tokens, finish[0]


def greedy_oracle(params, cfg, prompt, max_tokens, stop_ids=()):
    """The plain reference of greedy decoding: ``llama.forward`` over a dense
    cache, the prompt in one pass and a token a pass after it, no engine, no
    pages, no kernel. Returns ``(tokens, finish)`` as the scheduler emits
    them: a stop token is the last token, ``finish`` is ``stop`` or
    ``length``. The request is taken to fit the served window."""
    import jax.numpy as jnp

    from cyberfabric_core_tpu.models import llama
    from cyberfabric_core_tpu.runtime.programs import serving_rope_tables

    total = len(prompt) + max_tokens
    rope = serving_rope_tables(cfg, total)
    cache = llama.init_cache(cfg, 1, total, params["final_norm"].dtype)
    ids, start, tokens = list(prompt), 0, []
    while True:
        positions = jnp.arange(start, start + len(ids), dtype=jnp.int32)[None]
        hidden, cache = llama.forward(
            params, cfg, jnp.asarray([ids], jnp.int32), positions, cache,
            jnp.asarray([start], jnp.int32), rope)
        tokens.append(int(jnp.argmax(
            llama.lm_head_logits(params, cfg, hidden[:, -1]), axis=-1)[0]))
        if tokens[-1] in stop_ids:
            return tokens, "stop"
        if len(tokens) == max_tokens:
            return tokens, "length"
        start, ids = start + len(ids), tokens[-1:]


#: A worker keeps every program it compiled (``runtime/programs.py``'s memo,
#: jax's own caches), and a compiled program is hundreds of memory mappings of
#: its code: three scheduler files leave 31 390 of the 65 530 the kernel
#: allows a process (``vm.max_map_count``; the same at PR 46's parent), and a
#: worker that xdist hands a few more dies of SIGABRT inside XLA's CPU compile
#: when a mapping is refused (PR 46: two workers of six at 92% of a whole
#: run, which was then cut by its time limit). Above this share of the limit
#: the end of a test module forgets the programs (21 281 -> 855 measured)
_MAPPINGS_SHARE_KEPT = 0.4


def _mappings(path="/proc/self/maps"):
    try:
        with open(path) as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


@pytest.fixture(autouse=True, scope="module")
def _bounded_code_mappings():
    yield
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            allowed = int(f.read())
    except (OSError, ValueError):
        return
    if _mappings() > _MAPPINGS_SHARE_KEPT * allowed:
        import gc

        import jax
        from cyberfabric_core_tpu.runtime.programs import step_programs

        step_programs.cache_clear()
        jax.clear_caches()
        gc.collect()


@pytest.fixture()
def fresh_registry():
    """Isolate module registrations per test."""
    # ensure the full decorator inventory exists BEFORE saving — otherwise a
    # first-in-process user of this fixture snapshots an empty registry and
    # teardown wipes the registrations for every later test
    import cyberfabric_core_tpu.modules  # noqa: F401
    from cyberfabric_core_tpu.modkit import registry as reg

    saved = list(reg._REGISTRATIONS)
    reg._REGISTRATIONS.clear()
    yield reg
    reg._REGISTRATIONS[:] = saved


class _CountedCopy:
    """A DMA whose start and wait say so to the host as they RUN (under the
    kernel's conditions, not as they are traced); ``seen`` also counts the
    trips the kernel attended over, by the pages of the block it took."""
    seen: dict = {}

    def __init__(self, *args):
        from jax.experimental.pallas import tpu as pltpu

        self._copy = pltpu.make_async_copy(*args)

    @staticmethod
    def count(*whats):
        import jax

        def bump():
            for what in whats:
                _CountedCopy.seen[what] = _CountedCopy.seen.get(what, 0) + 1
        jax.debug.callback(bump)

    def start(self):
        self.count("start")
        self._copy.start()

    def wait(self):
        self.count("wait")
        self._copy.wait()


@pytest.fixture()
def counted_copies(monkeypatch):
    """``run(name, *args, kernel=, **kwargs)``: one interpret-mode call of a
    kernel that walks its pages itself (``ops/page_walk.py``), and what it
    did as it ran: ``start`` / ``wait`` the DMAs (one a page a pool), ``trips``
    the key blocks it attended over, ``trips_of_<pages>`` by block size."""
    import types

    import jax
    from jax.experimental.pallas import tpu as pltpu

    from cyberfabric_core_tpu.ops import page_walk
    from cyberfabric_core_tpu.ops.mla_attention import mla_decode_attention

    proxy = types.SimpleNamespace(**{n: getattr(pltpu, n) for n in dir(pltpu)
                                     if not n.startswith("__")})
    proxy.make_async_copy = _CountedCopy
    monkeypatch.setattr(page_walk, "pltpu", proxy)
    _CountedCopy.seen = {}
    walk_run = page_walk._Walk.run

    def counted_run(self, item, attend):
        def counted_trip(*args, pages, **kwargs):
            _CountedCopy.count("trips", f"trips_of_{pages}")
            attend(*args, pages=pages, **kwargs)
        walk_run(self, item, counted_trip)
    monkeypatch.setattr(page_walk._Walk, "run", counted_run)

    def run(name, *args, kernel=mla_decode_attention, **kwargs):
        _CountedCopy.seen.clear()
        # a name of its own: a trace of its own
        jax.block_until_ready(kernel(
            *args, interpret=True, name=name, **kwargs))
        jax.effects_barrier()
        return dict(_CountedCopy.seen)
    return run
