"""fabric-lint engine + AS/JP/LK rule-family tests (dylint ui-test parity).

Every semantic rule carries one minimal FAILING snippet and one PASSING
snippet (mirroring test_DE03_fixture_fails), plus engine-level coverage for
the inline-waiver syntax, the committed baseline, and the emitters. The
repo-wide gate (the analyzer exits 0 on cyberfabric_core_tpu) runs last —
it is the `make lint` contract.
"""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

from cyberfabric_core_tpu.apps.fabric_lint import Engine, all_rules
from cyberfabric_core_tpu.apps.fabric_lint.emitters import emit_json, emit_sarif

REPO = Path(__file__).resolve().parents[1]
PKG = REPO / "cyberfabric_core_tpu"


def lint(source: str, tier: str = "modules", select: tuple[str, ...] = ()):
    """Run the engine over an in-memory snippet; return unwaived findings."""
    engine = Engine(all_rules())
    if select:
        engine = engine.select(select)
    findings = engine.run_source(source, relpath=f"{tier}/snippet.py",
                                 tier=tier)
    return [f for f in findings if not f.suppressed]


def rule_ids(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------- AS family


def test_AS01_blocking_call_in_async_def_fails():
    bad = lint(
        "import time\n"
        "async def handler():\n"
        "    time.sleep(1)\n", select=("AS01",))
    assert rule_ids(bad) == ["AS01"] and bad[0].line == 3


def test_AS01_sleep_in_sync_serving_code_fails():
    # even outside async def: serving-tier sync helpers run on the loop
    bad = lint("import time\n"
               "def helper():\n"
               "    time.sleep(0.1)\n", select=("AS01",))
    assert rule_ids(bad) == ["AS01"]


def test_AS01_async_sleep_passes():
    ok = lint(
        "import asyncio\n"
        "async def handler():\n"
        "    await asyncio.sleep(1)\n", select=("AS01",))
    assert ok == []


def test_AS01_compute_tier_sleep_passes():
    # runtime/ spins dedicated scheduler threads; AS01 is a serving-tier rule
    ok = lint("import time\n"
              "def loop():\n"
              "    time.sleep(0.01)\n", tier="runtime", select=("AS01",))
    assert ok == []


def test_AS02_fire_and_forget_fails():
    bad = lint(
        "import asyncio\n"
        "async def go(coro):\n"
        "    asyncio.ensure_future(coro)\n", select=("AS02",))
    assert rule_ids(bad) == ["AS02"]


def test_AS02_underscore_discard_fails():
    bad = lint(
        "import asyncio\n"
        "async def go(coro):\n"
        "    _ = asyncio.create_task(coro)\n", select=("AS02",))
    assert rule_ids(bad) == ["AS02"]


def test_AS02_taskgroup_spawn_passes():
    # TaskGroup retains its children and propagates their exceptions — the
    # recommended safe pattern must not be flagged
    ok = lint(
        "import asyncio\n"
        "async def go(work):\n"
        "    async with asyncio.TaskGroup() as tg:\n"
        "        tg.create_task(work())\n", select=("AS02",))
    assert ok == []


def test_AS02_loop_create_task_fails():
    bad = lint(
        "import asyncio\n"
        "async def go(work):\n"
        "    loop = asyncio.get_running_loop()\n"
        "    loop.create_task(work())\n", select=("AS02",))
    assert rule_ids(bad) == ["AS02"]


def test_AS02_retained_task_passes():
    ok = lint(
        "import asyncio\n"
        "class M:\n"
        "    async def go(self, coro):\n"
        "        self._task = asyncio.ensure_future(coro)\n", select=("AS02",))
    assert ok == []


def test_AS03_await_under_sync_lock_fails():
    bad = lint(
        "class M:\n"
        "    async def go(self):\n"
        "        with self._lock:\n"
        "            await self.flush()\n", select=("AS03",))
    assert rule_ids(bad) == ["AS03"] and bad[0].line == 4


def test_AS03_async_lock_passes():
    ok = lint(
        "class M:\n"
        "    async def go(self):\n"
        "        async with self._lock:\n"
        "            await self.flush()\n", select=("AS03",))
    assert ok == []


def test_AS03_nested_def_resets_lock_context():
    # the nested coroutine body runs AFTER the with-block exits
    ok = lint(
        "class M:\n"
        "    def go(self):\n"
        "        with self._lock:\n"
        "            async def later():\n"
        "                await self.flush()\n"
        "            return later\n", select=("AS03",))
    assert ok == []


_AS04_CLASS = (
    "import numpy as np\n"
    "class Sched:\n"
    "    def _run_loop(self):\n"
    "        while True:\n"
    "            self._decode_round()\n"
)


def test_AS04_unsanctioned_sync_in_decode_loop_fails():
    bad = lint(
        _AS04_CLASS +
        "    def _decode_round(self):\n"
        "        chunk = np.asarray(self._chunk_dev)\n",
        tier="runtime", select=("AS04",))
    assert rule_ids(bad) == ["AS04"]
    assert "sync-point" in bad[0].message


def test_AS04_block_until_ready_in_emit_fails():
    bad = lint(
        _AS04_CLASS +
        "    def _emit_chunk(self, chunk):\n"
        "        chunk.block_until_ready()\n",
        tier="runtime", select=("AS04",))
    assert rule_ids(bad) == ["AS04"]


def test_AS04_sanctioned_sync_point_passes():
    ok = lint(
        _AS04_CLASS +
        "    def _decode_round(self):\n"
        "        chunk = np.asarray(self._chunk_dev)  # sync-point: one read per round\n",
        tier="runtime", select=("AS04",))
    assert ok == []


def test_AS04_second_sync_point_in_one_method_fails():
    # the deep-lookahead discipline: ONE blocking drain per round method —
    # a second marker is an extra host<-device serialization, not a waiver
    bad = lint(
        _AS04_CLASS +
        "    def _decode_round(self):\n"
        "        a = np.asarray(self._a_dev)  # sync-point: drain oldest\n"
        "        b = np.asarray(self._b_dev)  # sync-point: and another\n",
        tier="runtime", select=("AS04",))
    assert rule_ids(bad) == ["AS04"]
    assert "second" in bad[0].message


def test_AS04_one_sync_point_per_method_passes():
    # separate round methods each own their single drain (paged vs mixed
    # vs dense rounds in the real scheduler)
    ok = lint(
        _AS04_CLASS +
        "    def _decode_round(self):\n"
        "        a = np.asarray(self._a_dev)  # sync-point: paged drain\n"
        "    def _decode_round_mixed(self):\n"
        "        b = np.asarray(self._b_dev)  # sync-point: mixed drain\n",
        tier="runtime", select=("AS04",))
    assert ok == []


def test_AS04_marker_mention_in_docstring_not_counted():
    # a docstring/comment MENTIONING "sync-point:" is not a drain — only
    # lines that also carry a device-sync call count toward the one-drain
    # budget (else the real drain below would be flagged as a second one)
    ok = lint(
        _AS04_CLASS +
        "    def _decode_round(self):\n"
        '        """the one `# sync-point:` drain happens below"""\n'
        "        # the sync-point: marker is explained here too\n"
        "        chunk = np.asarray(self._chunk_dev)  # sync-point: drain oldest\n",
        tier="runtime", select=("AS04",))
    assert ok == []


def test_AS04_nonblocking_transfer_start_passes():
    # copy_to_host_async is a transfer ENQUEUE, not a sync: the new
    # discipline allows starting it anywhere in the hot loop, with the
    # blocking read only at the single sanctioned drain
    ok = lint(
        _AS04_CLASS +
        "    def _dispatch_chunk(self):\n"
        "        self._chunk_dev.copy_to_host_async()\n"
        "    def _decode_round(self):\n"
        "        self._dispatch_chunk()\n"
        "        chunk = np.asarray(self._chunk_dev)  # sync-point: drain oldest\n",
        tier="runtime", select=("AS04",))
    assert ok == []


def test_AS04_sync_outside_loop_methods_passes():
    # admission-path syncs (first-token readback) are inherent, not hot-loop
    ok = lint(
        _AS04_CLASS +
        "    def _admit_prefill_slot(self, slot, req):\n"
        "        tok = int(np.asarray(self._first)[0])\n",
        tier="runtime", select=("AS04",))
    assert ok == []


def test_AS04_requires_scheduler_class():
    # a _decode_round on a class WITHOUT _run_loop is not a scheduler thread
    ok = lint(
        "import numpy as np\n"
        "class Helper:\n"
        "    def _decode_round(self):\n"
        "        return np.asarray(self.x)\n",
        tier="runtime", select=("AS04",))
    assert ok == []


def test_AS04_only_applies_to_runtime_tier():
    ok = lint(
        _AS04_CLASS +
        "    def _decode_round(self):\n"
        "        chunk = np.asarray(self._chunk_dev)\n",
        tier="modules", select=("AS04",))
    assert ok == []


# ---------------------------------------------------------------- JP family


def test_JP01_print_in_jit_fails():
    bad = lint(
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    print(x)\n"
        "    return x\n", tier="runtime", select=("JP01",))
    assert rule_ids(bad) == ["JP01"]


def test_JP01_logging_in_jit_fails():
    bad = lint(
        "import jax, logging\n"
        "logger = logging.getLogger(__name__)\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    logger.info('tracing %s', x)\n"
        "    return x\n", tier="runtime", select=("JP01",))
    assert rule_ids(bad) == ["JP01"]


def test_JP01_print_outside_jit_passes():
    ok = lint(
        "import jax\n"
        "def host_side(x):\n"
        "    return x\n"
        "def report(x):\n"
        "    print(x)\n", tier="runtime", select=("JP01",))
    assert ok == []


def test_JP02_host_np_on_traced_arg_fails():
    bad = lint(
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return np.sum(x)\n", tier="ops", select=("JP02",))
    assert rule_ids(bad) == ["JP02"]


def test_JP02_np_on_static_config_passes():
    # trace-time shape arithmetic on python values is legitimate
    ok = lint(
        "import jax\n"
        "import numpy as np\n"
        "SHAPE = (8, 128)\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    n = np.prod(SHAPE)\n"
        "    return x * n\n", tier="ops", select=("JP02",))
    assert ok == []


def test_JP02_jit_call_pattern_detected():
    # the scheduler spelling: local def handed to jax.jit(fn)
    bad = lint(
        "import jax\n"
        "import numpy as np\n"
        "def build():\n"
        "    def decode(tokens):\n"
        "        return np.argmax(tokens)\n"
        "    return jax.jit(decode)\n", tier="runtime", select=("JP02",))
    assert rule_ids(bad) == ["JP02"]


def test_JP03_self_mutation_in_jit_fails():
    bad = lint(
        "import jax\n"
        "from functools import partial\n"
        "class Engine:\n"
        "    @partial(jax.jit, static_argnums=(0,))\n"
        "    def step(self, x):\n"
        "        self.cache = x\n"
        "        return x\n", tier="runtime", select=("JP03",))
    assert rule_ids(bad) == ["JP03"]


def test_JP03_captured_list_append_fails():
    bad = lint(
        "import jax\n"
        "trace_log = []\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    trace_log.append(x)\n"
        "    return x\n", tier="runtime", select=("JP03",))
    assert rule_ids(bad) == ["JP03"]


def test_JP03_functional_update_passes():
    # optax-style pure tx.update: the result is consumed, not a mutation
    ok = lint(
        "import jax\n"
        "@jax.jit\n"
        "def step(tx, grads, opt_state, params):\n"
        "    updates, opt_state = tx.update(grads, opt_state, params)\n"
        "    local = []\n"
        "    local.append(updates)\n"
        "    return local, opt_state\n", tier="parallel", select=("JP03",))
    assert ok == []


def test_JP_method_sharing_local_def_name_not_marked():
    # regression: jax.jit(prefill) on a LOCAL def must not mark the METHOD
    # prefill of the same class — methods are referenced as self.name
    ok = lint(
        "import jax\n"
        "class Draft:\n"
        "    def __init__(self):\n"
        "        def prefill(x):\n"
        "            return x\n"
        "        self._prefill = jax.jit(prefill)\n"
        "    def prefill(self, ids):\n"
        "        self.cache = ids\n", tier="runtime", select=("JP03",))
    assert ok == []


# ---------------------------------------------------------------- LK family

_LK_CLASS = (
    "import threading\n"
    "class Pool:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._requests = {}\n"          # init writes are exempt
    "    def submit(self, rid, req):\n"
    "        with self._lock:\n"
    "            self._requests[rid] = req\n"
)


def test_LK01_unlocked_write_to_guarded_attr_fails():
    bad = lint(
        _LK_CLASS +
        "    def drop(self, rid):\n"
        "        self._requests.pop(rid, None)\n",   # no lock!
        tier="runtime", select=("LK01",))
    assert rule_ids(bad) == ["LK01"]
    assert "drop" in bad[0].message


def test_LK01_locked_writes_pass():
    ok = lint(
        _LK_CLASS +
        "    def drop(self, rid):\n"
        "        with self._lock:\n"
        "            self._requests.pop(rid, None)\n",
        tier="runtime", select=("LK01",))
    assert ok == []


def test_LK01_unguarded_attrs_are_free():
    # attrs never written under the lock are not part of the declared scope
    ok = lint(
        _LK_CLASS +
        "    def bump(self):\n"
        "        self.stats_counter = 1\n",
        tier="runtime", select=("LK01",))
    assert ok == []


def test_LK01_only_applies_to_runtime_tier():
    ok = lint(
        _LK_CLASS +
        "    def drop(self, rid):\n"
        "        self._requests.pop(rid, None)\n",
        tier="modules", select=("LK01",))
    assert ok == []


# ---------------------------------------------------------------- FP family


def test_FP01_unregistered_name_fails():
    # no local catalog in the fixture: the real package catalog is the
    # authority, and "totally.made_up" is not in it
    bad = lint("from cyberfabric_core_tpu.modkit.failpoints import failpoint\n"
               "def f():\n"
               "    failpoint('totally.made_up')\n", select=("FP01",))
    assert rule_ids(bad) == ["FP01"] and "not registered" in bad[0].message


def test_FP01_duplicate_call_site_fails():
    bad = lint("FAILPOINT_CATALOG = {'a.b': ('modules', 'x')}\n"
               "def f():\n"
               "    failpoint('a.b')\n"
               "def g():\n"
               "    failpoint('a.b')\n", select=("FP01",))
    assert rule_ids(bad) == ["FP01"]
    assert len(bad) == 1 and bad[0].line == 5  # the SECOND site is the error
    assert "already has a call site" in bad[0].message


def test_FP01_non_literal_name_fails():
    bad = lint("FAILPOINT_CATALOG = {'a.b': ('modules', 'x')}\n"
               "def f(name):\n"
               "    failpoint(name)\n", select=("FP01",))
    assert rule_ids(bad) == ["FP01"] and "literal" in bad[0].message


def test_FP01_registered_unique_call_site_passes():
    ok = lint("FAILPOINT_CATALOG = {'a.b': ('modules', 'x')}\n"
              "async def f():\n"
              "    await failpoint_async('a.b')\n", select=("FP01",))
    assert ok == []


def test_FP01_repo_catalog_and_call_sites_agree():
    """Every catalog name has exactly one call site in the package and the
    repo gate is clean (the docs table maps 1:1 to code)."""
    from cyberfabric_core_tpu.modkit.failpoints import FAILPOINT_CATALOG

    engine = Engine(all_rules()).select(["FP01"])
    findings = [f for f in engine.run(PKG) if not f.suppressed]
    assert findings == [], [f.to_dict() for f in findings]
    assert len(FAILPOINT_CATALOG) >= 12
    assert {layer for layer, _ in FAILPOINT_CATALOG.values()} >= {
        "runtime", "gateway", "modkit", "modules"}


# ---------------------------------------------------------------- TL family


def test_TL01_direct_recorder_emit_in_runtime_fails():
    bad = lint("from cyberfabric_core_tpu.modkit.flight_recorder import default_recorder\n"
               "def loop(rid):\n"
               "    default_recorder.record(rid, 'decode_chunk', tokens=8)\n",
               tier="runtime", select=("TL01",))
    assert rule_ids(bad) == ["TL01"] and bad[0].line == 3
    assert "record_event" in bad[0].message


def test_TL01_qualified_module_emit_fails():
    bad = lint("from cyberfabric_core_tpu.modkit import flight_recorder\n"
               "def loop(rid):\n"
               "    flight_recorder.default_recorder.record(rid, 'finished')\n",
               tier="runtime", select=("TL01",))
    assert rule_ids(bad) == ["TL01"]


def test_TL01_record_event_helper_passes():
    ok = lint("from cyberfabric_core_tpu.modkit.flight_recorder import record_event\n"
              "def loop(rid):\n"
              "    record_event(rid, 'decode_chunk', tokens=8)\n",
              tier="runtime", select=("TL01",))
    assert ok == []


def test_TL01_outside_runtime_passes():
    # the monitoring module READS the recorder and may call methods directly
    ok = lint("from cyberfabric_core_tpu.modkit.flight_recorder import default_recorder\n"
              "def scrape(rid):\n"
              "    default_recorder.record(rid, 'enqueued')\n",
              tier="modules", select=("TL01",))
    assert ok == []


def test_TL01_repo_runtime_tier_clean():
    """The gate: every flight-recorder emit under runtime/ goes through the
    never-raises helper."""
    engine = Engine(all_rules()).select(["TL01"])
    findings = [f for f in engine.run(PKG) if not f.suppressed]
    assert findings == [], [f.to_dict() for f in findings]


# ---------------------------------------------------------------- WD family


def test_WD01_blocking_sleep_in_evaluator_fails():
    bad = lint("import time\n"
               "class Doctor:\n"
               "    def evaluate(self):\n"
               "        time.sleep(0.1)\n",
               tier="modkit", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and bad[0].line == 4
    assert "blocking call" in bad[0].message


def test_WD01_network_call_in_watchdog_check_fails():
    bad = lint("import urllib.request\n"
               "class StallWatchdog:\n"
               "    def _check_round(self, url):\n"
               "        urllib.request.urlopen(url)\n",
               tier="modkit", select=("WD01",))
    assert rule_ids(bad) == ["WD01"]


def test_WD01_await_in_evaluator_fails():
    bad = lint("class Doctor:\n"
               "    async def evaluate(self, db):\n"
               "        await db.fetch('select 1')\n",
               tier="modkit", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and "await" in bad[0].message


def test_WD01_direct_recorder_emit_fails():
    bad = lint("class Doctor:\n"
               "    def _check_stream(self, recorder, rid):\n"
               "        recorder.record(rid, 'stalled')\n",
               tier="modkit", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and "record_event" in bad[0].message


def test_WD01_direct_metric_mutate_fails():
    bad = lint("class Doctor:\n"
               "    def evaluate(self, registry):\n"
               "        registry.counter('watchdog_trips_total')"
               ".inc(watchdog='x')\n",
               tier="modkit", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and "bump_counter" in bad[0].message


def test_WD01_never_raises_helpers_pass():
    ok = lint("from cyberfabric_core_tpu.modkit.metrics import bump_counter\n"
              "from cyberfabric_core_tpu.modkit.flight_recorder import "
              "record_event\n"
              "import time\n"
              "class Doctor:\n"
              "    def evaluate(self):\n"
              "        now = time.time()\n"
              "        bump_counter('watchdog_trips_total', watchdog='x')\n"
              "        record_event('rid', 'stalled')\n"
              "        return now\n"
              "    def _loop(self):\n"
              "        self._stop.wait(1.0)\n",
              tier="modkit", select=("WD01",))
    assert ok == []


def test_WD01_outside_doctor_classes_passes():
    # the rule targets the evaluator contract, not every sleep in modkit
    ok = lint("import time\n"
              "class RetryHelper:\n"
              "    def evaluate(self):\n"
              "        time.sleep(0.1)\n",
              tier="modkit", select=("WD01",))
    assert ok == []


def test_WD01_supervisor_tick_blocking_sleep_fails():
    # the lifecycle supervisor's tick holds the same contract as the doctor
    # evaluator: it is the only thing that can HEAL a broken pool
    bad = lint("import time\n"
               "class ReplicaLifecycleManager:\n"
               "    def tick(self, now=None):\n"
               "        time.sleep(0.1)\n",
               tier="runtime", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and bad[0].line == 4


def test_WD01_supervisor_terminal_hook_direct_metric_fails():
    # on_terminal runs on scheduler-emit hot paths — a raising metric
    # mutate there would break serving, not just supervision
    bad = lint("class EngineSupervisor:\n"
               "    def on_terminal(self, idx, ok, registry):\n"
               "        registry.counter('llm_replica_rebuilds_total')"
               ".inc(outcome='ok')\n",
               tier="runtime", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and "bump_counter" in bad[0].message


def test_WD01_supervisor_rebuild_helpers_exempt():
    # the deliberately-blocking engine operations (close/build/start) live
    # OUTSIDE the tick-prefixed decision pass — the rule's scope encodes
    # that split, so rebuild helpers may block
    ok = lint("import time\n"
              "class ReplicaLifecycleManager:\n"
              "    def _do_rebuild(self, idx):\n"
              "        time.sleep(0.1)\n"
              "class PoolHelper:\n"
              "    def tick(self):\n"
              "        time.sleep(0.1)\n",  # not a supervisor class
              tier="runtime", select=("WD01",))
    assert ok == []


def test_WD01_registry_heartbeat_blocking_sleep_fails():
    # every worker heartbeat serializes through the registry lock — a
    # sleeping heartbeat handler stalls the whole federation lease plane
    bad = lint("import time\n"
               "class WorkerRegistry:\n"
               "    def heartbeat(self, instance_id, census):\n"
               "        time.sleep(0.1)\n",
               tier="runtime", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and bad[0].line == 4


def test_WD01_federated_route_await_fails():
    # routing runs on the admission path of every request; an await means
    # it can park mid-decision while holding routing state
    bad = lint("class FederatedRouter:\n"
               "    async def route(self, model_key, chain):\n"
               "        await self._refresh()\n",
               tier="runtime", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and "await" in bad[0].message


def test_WD01_lease_expiry_callback_direct_metric_fails():
    # on_lease_expired fans out from inside the eviction sweep — a raising
    # metric mutate there would wedge eviction, not just metrics
    bad = lint("class PoolRegistry:\n"
               "    def on_lease_expired(self, row, registry):\n"
               "        registry.counter('llm_remote_worker_evictions_total')"
               ".inc(reason='lease')\n",
               tier="runtime", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and "bump_counter" in bad[0].message


def test_WD01_registry_heartbeat_never_raises_helpers_pass():
    ok = lint("from cyberfabric_core_tpu.modkit.metrics import bump_counter\n"
              "from cyberfabric_core_tpu.modkit.flight_recorder import "
              "record_event\n"
              "class WorkerRegistry:\n"
              "    def heartbeat(self, instance_id, census):\n"
              "        bump_counter('llm_remote_worker_heartbeats_total')\n"
              "        record_event(instance_id, 'heartbeat')\n"
              "        return True\n",
              tier="runtime", select=("WD01",))
    assert ok == []


def test_WD01_registry_client_wire_heartbeat_exempt():
    # a *RegistryClient* is the worker-side WIRE caller of the hub — its
    # heartbeat IS a network call by definition, so the fed group skips it
    ok = lint("class WorkerRegistryClient:\n"
              "    async def heartbeat(self, census):\n"
              "        return await self._call('Heartbeat', census)\n",
              tier="runtime", select=("WD01",))
    assert ok == []


def test_WD01_fleet_doctor_on_report_blocking_sleep_fails():
    # on_report runs once per heartbeat per host on the census refresh
    # path — a sleeping fold stalls every fleet read (/readyz, routing)
    bad = lint("import time\n"
               "class FleetDoctor:\n"
               "    def on_report(self, host, payload, stale=False):\n"
               "        time.sleep(0.1)\n",
               tier="modkit", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and bad[0].line == 4


def test_WD01_fleet_view_merge_await_fails():
    # merge* feeds the router's health rung and /readyz — the fold over
    # remote payloads is a sync in-memory pass, never a wire call
    bad = lint("class FleetView:\n"
               "    async def merge_reports(self, rows):\n"
               "        return await self._pull(rows)\n",
               tier="runtime", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and "await" in bad[0].message


def test_WD01_fleet_doctor_merge_direct_metric_fails():
    bad = lint("class FleetDoctor:\n"
               "    def merge(self, rows, registry):\n"
               "        registry.gauge('llm_fleet_state')"
               ".set(1.0)\n",
               tier="modkit", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and "bump_counter" in bad[0].message


def test_WD01_fleet_callbacks_with_helpers_pass():
    ok = lint("from cyberfabric_core_tpu.modkit.metrics import bump_counter\n"
              "class FleetDoctor:\n"
              "    def on_report(self, host, payload, stale=False):\n"
              "        bump_counter('llm_fleet_reports_total', host=host)\n"
              "        return dict(payload or {})\n"
              "    def merge(self, rows=None):\n"
              "        return {'state': 'healthy', 'reasons': []}\n"
              "class FleetViewHelper:\n"
              "    def refresh(self, client):\n"
              "        client.fetch()\n",  # not a merge/on_report callback
              tier="modkit", select=("WD01",))
    assert ok == []


def test_WD01_fleet_repo_gate_clean():
    """The gate: the repo's own FleetDoctor/FleetView merge and on_report
    callbacks honor the non-blocking never-raises contract."""
    engine = Engine(all_rules()).select(["WD01"])
    findings = [f for f in engine.run(PKG) if not f.suppressed]
    assert findings == [], [f.to_dict() for f in findings]


def test_WD01_cancel_callback_blocking_sleep_fails():
    # cancel() runs on gateway event-loop threads (an SSE disconnect) and
    # the expiry sweep runs between decode rounds — neither may block
    bad = lint("import time\n"
               "class ContinuousBatchingEngine:\n"
               "    def cancel(self, request_id, reason='cancelled'):\n"
               "        time.sleep(0.1)\n",
               tier="runtime", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and bad[0].line == 4


def test_WD01_cancel_sweep_direct_recorder_emit_fails():
    bad = lint("class ContinuousBatchingEngine:\n"
               "    def _cancel_finalize(self, recorder, rid):\n"
               "        recorder.record(rid, 'cancelled')\n",
               tier="runtime", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and "record_event" in bad[0].message


def test_WD01_pool_cancel_device_sync_fails():
    # a device sync inside the pool's cancel would stall the event loop
    # behind the accelerator exactly when a disconnect storm hits
    bad = lint("import jax\n"
               "class DataParallelServingPool:\n"
               "    def cancel(self, request_id, reason='cancelled'):\n"
               "        jax.block_until_ready(self._state)\n",
               tier="runtime", select=("WD01",))
    assert rule_ids(bad) == ["WD01"]


def test_WD01_cancel_callbacks_with_helpers_pass():
    ok = lint("from cyberfabric_core_tpu.modkit.metrics import bump_counter\n"
              "from cyberfabric_core_tpu.modkit.flight_recorder import "
              "record_event\n"
              "class ContinuousBatchingEngine:\n"
              "    def cancel(self, request_id, reason='cancelled'):\n"
              "        self._cancel_requests[request_id] = reason\n"
              "        self._wake.set()\n"
              "    def _service_cancellations(self):\n"
              "        record_event('rid', 'cancelled', reason='x')\n"
              "        bump_counter('llm_cancellations_total', reason='x')\n",
              tier="runtime", select=("WD01",))
    assert ok == []


def test_WD01_fair_queue_pop_blocking_sleep_fails():
    # the fair queue's pop runs inside the scheduler's admission pass —
    # one sleep there stalls every tenant at once
    bad = lint("import time\n"
               "class TenantFairQueue:\n"
               "    def pop_fair(self, blocked=None):\n"
               "        time.sleep(0.05)\n",
               tier="runtime", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and bad[0].line == 4


def test_WD01_tenant_cap_sweep_direct_metric_fails():
    # the round-boundary cap sweep is bookkeeping-only: a raising metric
    # mutate there would turn a quota mark into an engine crash
    bad = lint("class ContinuousBatchingEngine:\n"
               "    def _service_tenant_caps(self, registry):\n"
               "        registry.counter('llm_tenant_soft_yields_total')"
               ".inc(tenant='t')\n",
               tier="runtime", select=("WD01",))
    assert rule_ids(bad) == ["WD01"] and "bump_counter" in bad[0].message


def test_WD01_tenant_charge_device_sync_fails():
    # the per-token charge path sits inside _emit_token — a device sync
    # there would re-serialize host and device every token
    bad = lint("import numpy as np\n"
               "class ContinuousBatchingEngine:\n"
               "    def _charge_tenant(self, tenant, tokens):\n"
               "        np.asarray(self._lengths_dev)\n",
               tier="runtime", select=("WD01",))
    assert rule_ids(bad) == ["WD01"]


def test_WD01_fairness_callbacks_with_helpers_pass():
    ok = lint("from cyberfabric_core_tpu.modkit.metrics import bump_counter\n"
              "from cyberfabric_core_tpu.modkit.flight_recorder import "
              "record_event\n"
              "class TenantFairQueue:\n"
              "    def put(self, req):\n"
              "        with self._lock:\n"
              "            self._queues[req.tenant].append(req)\n"
              "    def charge(self, tenant, tokens, weight):\n"
              "        with self._lock:\n"
              "            self._vtc[tenant] = tokens / weight\n"
              "class ContinuousBatchingEngine:\n"
              "    def _service_tenant_caps(self):\n"
              "        self._soft_yield.add(0)\n"
              "        bump_counter('llm_tenant_soft_yields_total',"
              " tenant='t')\n"
              "        record_event('rid', 'soft_yield_marked', slot=0)\n",
              tier="runtime", select=("WD01",))
    assert ok == []


def test_WD01_repo_gate_clean():
    """The gate: the shipped doctor's evaluators, the lifecycle
    supervisor's tick/routing callbacks, the scheduler/pool cancellation
    callbacks, AND the tenant fairness/quota surface (fair-queue
    put/pop/charge + the cap sweep) hold their own contract."""
    engine = Engine(all_rules()).select(["WD01"])
    findings = [f for f in engine.run(PKG) if not f.suppressed]
    assert findings == [], [f.to_dict() for f in findings]


# ---------------------------------------------------------------- SH family


def test_SH01_bare_device_put_in_mesh_class_fails():
    bad = lint(
        "import jax\n"
        "class Engine:\n"
        "    def __init__(self, tp):\n"
        "        self.mesh = object()\n"
        "    def upload(self, x):\n"
        "        return jax.device_put(x)\n",
        tier="runtime", select=("SH01",))
    assert rule_ids(bad) == ["SH01"] and bad[0].line == 6
    assert "FULL-REPLICATES" in bad[0].message


def test_SH01_bare_device_put_in_mesh_function_fails():
    bad = lint(
        "import jax\n"
        "def shard_tree(params, mesh):\n"
        "    return jax.device_put(params)\n",
        tier="runtime", select=("SH01",))
    assert rule_ids(bad) == ["SH01"]


def test_SH01_explicit_sharding_passes():
    ok = lint(
        "import jax\n"
        "class Engine:\n"
        "    def __init__(self, mesh, repl):\n"
        "        self.mesh = mesh\n"
        "        self._repl = repl\n"
        "    def upload(self, x):\n"
        "        return jax.device_put(x, self._repl)\n"
        "    def upload_kw(self, x):\n"
        "        return jax.device_put(x, device=self._repl)\n",
        tier="runtime", select=("SH01",))
    assert ok == []


def test_SH01_non_mesh_class_passes():
    # single-device code may device_put without a destination — the rule
    # scopes to mesh-mode classes/functions only
    ok = lint(
        "import jax\n"
        "class Plain:\n"
        "    def upload(self, x):\n"
        "        return jax.device_put(x)\n",
        tier="runtime", select=("SH01",))
    assert ok == []


def test_SH01_outside_runtime_tier_passes():
    ok = lint(
        "import jax\n"
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self.mesh = object()\n"
        "    def upload(self, x):\n"
        "        return jax.device_put(x)\n",
        tier="modules", select=("SH01",))
    assert ok == []


def test_SH01_waiver_roundtrip():
    ok = lint(
        "import jax\n"
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self.mesh = object()\n"
        "    def upload(self, x):\n"
        "        # fabric-lint: waive SH01 reason=staging-host copy\n"
        "        return jax.device_put(x)\n",
        tier="runtime", select=("SH01",))
    assert ok == []


# ------------------------------------- SH02–SH04 + AK01 (fabric-shard)

#: the SH01 blind spot, distilled: the bare device_put lives in a module
#: helper OUTSIDE any mesh scope, and only the interprocedural pass can
#: see that a mesh-mode engine routes its uploads through it
SH02_HELPER_UPLOAD = """
import jax

def _stage(batch):
    return jax.device_put(batch)

class Engine:
    def __init__(self, mesh):
        self.mesh = mesh

    def upload(self, batch):
        return _stage(batch)
"""

#: the pre-PR-7 AOT-key shape, distilled: device_stop_width flows through
#: a derived attribute into a device-array shape constructor, but the AOT
#: cache key (serving_programs' parameter tuple) never names it — the
#: artifact deserializes and the first dispatch donates mismatched buffers
AK01_PRE_PR7 = """
import jax.numpy as jnp

class EngineConfig:
    model: str = "llama"
    max_batch: int = 8
    device_stop_width: int = 4

class Engine:
    def __init__(self, config):
        self.config = config
        self._stop_width = max(1, config.device_stop_width)
        self.stop_row = jnp.full((config.max_batch, self._stop_width), -1)

    def _build_programs(self):
        return self.config.max_batch

def serving_programs(model, max_batch):
    return (model, max_batch)
"""


def test_SH02_helper_routed_bare_upload_must_flag():
    """Acceptance regression: a bare jax.device_put reached only through a
    helper call from a mesh-mode scope must flag under SH02 (SH01 cannot
    see through the call)."""
    bad = lint(SH02_HELPER_UPLOAD, tier="runtime", select=("SH02",))
    assert rule_ids(bad) == ["SH02"]
    assert "_stage" in bad[0].message and "device_put" in bad[0].message


def test_SH02_transitive_chain_reported():
    # two frames down: the witness chain names every hop
    bad = lint(
        "import jax\n"
        "def _upload(x):\n"
        "    return jax.device_put(x)\n"
        "def _stage(x):\n"
        "    return _upload(x)\n"
        "class Engine:\n"
        "    def __init__(self, mesh):\n"
        "        self.mesh = mesh\n"
        "    def upload(self, x):\n"
        "        return _stage(x)\n",
        tier="runtime", select=("SH02",))
    assert rule_ids(bad) == ["SH02"]
    assert "_stage" in bad[0].message and "_upload" in bad[0].message


def test_SH02_explicit_destination_helper_passes():
    ok = lint(
        "import jax\n"
        "def _stage(batch, sharding):\n"
        "    return jax.device_put(batch, sharding)\n"
        "class Engine:\n"
        "    def __init__(self, mesh, repl):\n"
        "        self.mesh = mesh\n"
        "        self._repl = repl\n"
        "    def upload(self, batch):\n"
        "        return _stage(batch, self._repl)\n",
        tier="runtime", select=("SH02",))
    assert ok == []


def test_SH02_non_mesh_caller_passes():
    # single-device code may route through a bare-upload helper
    ok = lint(
        "import jax\n"
        "def _stage(batch):\n"
        "    return jax.device_put(batch)\n"
        "class Plain:\n"
        "    def upload(self, batch):\n"
        "        return _stage(batch)\n",
        tier="runtime", select=("SH02",))
    assert ok == []


def test_SH02_outside_spmd_tiers_passes():
    ok = lint(SH02_HELPER_UPLOAD, tier="modules", select=("SH02",))
    assert ok == []


_SH02_DISPATCH_PREFIX = (
    "import jax\n"
    "import numpy as np\n"
    "class Engine:\n"
    "    def __init__(self, mesh):\n"
    "        self.mesh = mesh\n"
    "        self._decode_fn = jax.jit(lambda x: x)\n"
)


def test_SH02_host_array_into_jitted_dispatch_fails():
    bad = lint(
        _SH02_DISPATCH_PREFIX +
        "    def step(self):\n"
        "        tokens = np.zeros((8,), dtype=np.int32)\n"
        "        return self._decode_fn(tokens)\n",
        tier="runtime", select=("SH02",))
    assert rule_ids(bad) == ["SH02"]
    assert "tokens" in bad[0].message and "_decode_fn" in bad[0].message


def test_SH02_host_attr_provenance_inherited_across_methods():
    # cross-function inheritance: the host provenance assigned in __init__
    # reaches the dispatch call in step() through the attribute lattice
    bad = lint(
        "import jax\n"
        "import numpy as np\n"
        "class Engine:\n"
        "    def __init__(self, mesh):\n"
        "        self.mesh = mesh\n"
        "        self.page_table = np.zeros((8, 16))\n"
        "        self._decode_fn = jax.jit(lambda x: x)\n"
        "    def step(self):\n"
        "        return self._decode_fn(self.page_table)\n",
        tier="runtime", select=("SH02",))
    assert rule_ids(bad) == ["SH02"]
    assert "page_table" in bad[0].message


def test_SH02_dev_helper_routing_passes():
    # the blessed upload path: self._dev() commits replicated-on-mesh
    ok = lint(
        _SH02_DISPATCH_PREFIX +
        "    def step(self):\n"
        "        tokens = self._dev(np.zeros((8,), dtype=np.int32))\n"
        "        return self._decode_fn(tokens)\n",
        tier="runtime", select=("SH02",))
    assert ok == []


def test_SH02_device_array_dispatch_passes():
    ok = lint(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "class Engine:\n"
        "    def __init__(self, mesh):\n"
        "        self.mesh = mesh\n"
        "        self._decode_fn = jax.jit(lambda x: x)\n"
        "    def step(self):\n"
        "        tokens = jnp.zeros((8,), dtype=jnp.int32)\n"
        "        return self._decode_fn(tokens)\n",
        tier="runtime", select=("SH02",))
    assert ok == []


def test_SH02_unknown_provenance_never_flags():
    # join of host and device evidence is `unknown` — silence over noise
    ok = lint(
        _SH02_DISPATCH_PREFIX +
        "    def step(self, flag):\n"
        "        import jax.numpy as jnp\n"
        "        tokens = np.zeros(8) if flag else jnp.zeros(8)\n"
        "        return self._decode_fn(tokens)\n",
        tier="runtime", select=("SH02",))
    assert ok == []


_SH03_MESH_PREFIX = (
    "import jax\n"
    "from jax.sharding import Mesh, PartitionSpec as P\n"
    "def build(devices):\n"
    "    return Mesh(devices, ('dp', 'tp'))\n"
)


def test_SH03_unknown_axis_name_fails():
    bad = lint(
        _SH03_MESH_PREFIX +
        "def spec():\n"
        "    return P('tpx', None)\n",
        tier="runtime", select=("SH03",))
    assert rule_ids(bad) == ["SH03"]
    assert "'tpx'" in bad[0].message and "dp, tp" in bad[0].message


def test_SH03_declared_axis_passes():
    ok = lint(
        _SH03_MESH_PREFIX +
        "def spec():\n"
        "    return P('tp', None)\n",
        tier="runtime", select=("SH03",))
    assert ok == []


def test_SH03_no_mesh_in_program_is_silent():
    # without any mesh the axis universe is empty — no basis to judge
    ok = lint(
        "from jax.sharding import PartitionSpec as P\n"
        "def spec():\n"
        "    return P('whatever')\n",
        tier="runtime", select=("SH03",))
    assert ok == []


def test_SH03_shard_map_in_specs_arity_mismatch_fails():
    bad = lint(
        _SH03_MESH_PREFIX +
        "def body(a, b):\n"
        "    return a\n"
        "def run(mesh, xs):\n"
        "    f = jax.shard_map(body, mesh=mesh,\n"
        "                      in_specs=(P(), P(), P()), out_specs=P())\n"
        "    return f(*xs)\n",
        tier="runtime", select=("SH03",))
    assert rule_ids(bad) == ["SH03"]
    assert "3 spec(s)" in bad[0].message and "body" in bad[0].message


def test_SH03_shard_map_out_specs_arity_mismatch_fails():
    bad = lint(
        _SH03_MESH_PREFIX +
        "def body(a, b):\n"
        "    return a, b\n"
        "def run(mesh, xs):\n"
        "    f = jax.shard_map(body, mesh=mesh,\n"
        "                      in_specs=(P(), P()),\n"
        "                      out_specs=(P(), P(), P()))\n"
        "    return f(*xs)\n",
        tier="runtime", select=("SH03",))
    assert rule_ids(bad) == ["SH03"]
    assert "out_specs" in bad[0].message and "2-tuple" in bad[0].message


def test_SH03_shard_map_matched_specs_pass():
    # incl. the pipeline.py idiom: in_specs bound to a local name one
    # assignment above the shard_map call
    ok = lint(
        _SH03_MESH_PREFIX +
        "def body(a, b):\n"
        "    return a, b\n"
        "def run(mesh, xs):\n"
        "    in_specs = (P('tp'), P())\n"
        "    f = jax.shard_map(body, mesh=mesh,\n"
        "                      in_specs=in_specs, out_specs=(P(), P()))\n"
        "    return f(*xs)\n",
        tier="runtime", select=("SH03",))
    assert ok == []


def test_SH03_vararg_wrapped_fn_skipped():
    ok = lint(
        _SH03_MESH_PREFIX +
        "def body(*arrs):\n"
        "    return arrs[0]\n"
        "def run(mesh, xs):\n"
        "    f = jax.shard_map(body, mesh=mesh,\n"
        "                      in_specs=(P(), P(), P()), out_specs=P())\n"
        "    return f(*xs)\n",
        tier="runtime", select=("SH03",))
    assert ok == []


_SH04_PREFIX = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "from jax.sharding import NamedSharding, PartitionSpec as P\n"
)


def test_SH04_conflicting_specs_combined_fails():
    bad = lint(
        _SH04_PREFIX +
        "def combine(mesh, x, y):\n"
        "    a = jax.device_put(x, NamedSharding(mesh, P('tp', None)))\n"
        "    b = jax.device_put(y, NamedSharding(mesh, P(None, 'tp')))\n"
        "    return jnp.concatenate([a, b])\n",
        tier="runtime", select=("SH04",))
    assert rule_ids(bad) == ["SH04"]
    assert "all-gather" in bad[0].message


def test_SH04_binop_combine_fails():
    bad = lint(
        _SH04_PREFIX +
        "def combine(mesh, x, y):\n"
        "    a = jax.device_put(x, NamedSharding(mesh, P('tp')))\n"
        "    b = jax.device_put(y, NamedSharding(mesh, P('dp')))\n"
        "    return a + b\n",
        tier="runtime", select=("SH04",))
    assert rule_ids(bad) == ["SH04"]


def test_SH04_agreeing_specs_pass():
    ok = lint(
        _SH04_PREFIX +
        "def combine(mesh, x, y):\n"
        "    a = jax.device_put(x, NamedSharding(mesh, P('tp', None)))\n"
        "    b = jax.device_put(y, NamedSharding(mesh, P('tp', None)))\n"
        "    return jnp.concatenate([a, b])\n",
        tier="runtime", select=("SH04",))
    assert ok == []


def test_SH04_replicated_with_sharded_is_broadcast_not_conflict():
    # P() vs P('tp') is the normal broadcast case — silent by design
    ok = lint(
        _SH04_PREFIX +
        "def combine(mesh, x, y):\n"
        "    a = jax.device_put(x, NamedSharding(mesh, P('tp')))\n"
        "    b = jax.device_put(y, NamedSharding(mesh, P()))\n"
        "    return a * b\n",
        tier="runtime", select=("SH04",))
    assert ok == []


def test_SH04_sharding_constraint_sanctions_the_combine():
    ok = lint(
        _SH04_PREFIX +
        "def combine(mesh, x, y):\n"
        "    a = jax.device_put(x, NamedSharding(mesh, P('tp', None)))\n"
        "    b = jax.device_put(y, NamedSharding(mesh, P(None, 'tp')))\n"
        "    return jax.lax.with_sharding_constraint(\n"
        "        jnp.concatenate([a, b]), NamedSharding(mesh, P('tp', None)))\n",
        tier="runtime", select=("SH04",))
    assert ok == []


def test_AK01_pre_pr7_stop_width_shape_must_flag():
    """Acceptance regression: the pre-PR-7 hardcoded-device_stop_width
    AOT-key shape — a config field that shapes a device array through a
    derived attribute but is absent from the serving_programs key — must
    flag under AK01."""
    bad = lint(AK01_PRE_PR7, tier="runtime", select=("AK01",))
    assert rule_ids(bad) == ["AK01"]
    assert "device_stop_width" in bad[0].message
    assert "serving_programs" in bad[0].message


def test_AK01_keyed_field_passes():
    fixed = AK01_PRE_PR7.replace(
        "def serving_programs(model, max_batch):",
        "def serving_programs(model, max_batch, device_stop_width):")
    assert fixed != AK01_PRE_PR7, "fixture drifted"
    ok = lint(fixed, tier="runtime", select=("AK01",))
    assert ok == []


def test_AK01_affix_match_covers_derived_key_names():
    # scheduler_spec_k covers key spec_k; prefix_page_size covers page_size
    fixed = AK01_PRE_PR7.replace(
        "    device_stop_width: int = 4",
        "    scheduler_spec_k: int = 2").replace(
        "max(1, config.device_stop_width)",
        "max(1, config.scheduler_spec_k)")
    ok = lint(
        fixed.replace("def serving_programs(model, max_batch):",
                      "def serving_programs(model, max_batch, spec_k):"),
        tier="runtime", select=("AK01",))
    assert ok == []


def test_AK01_non_shape_field_not_required_in_key():
    # a field the engine never reads into a shape or _build_programs does
    # not need a key slot (log levels, host-side toggles...)
    ok = lint(
        "import jax.numpy as jnp\n"
        "class EngineConfig:\n"
        "    max_batch: int = 8\n"
        "    log_level: str = 'info'\n"
        "class Engine:\n"
        "    def __init__(self, config):\n"
        "        self.config = config\n"
        "    def _build_programs(self):\n"
        "        return jnp.zeros((self.config.max_batch,))\n"
        "def serving_programs(model, max_batch):\n"
        "    return (model, max_batch)\n",
        tier="runtime", select=("AK01",))
    assert ok == []


def test_SHAK_waiver_round_trips():
    """SH02 and AK01 suppress through the standard inline waiver."""
    bad = lint(SH02_HELPER_UPLOAD, tier="runtime", select=("SH02",))
    lines = SH02_HELPER_UPLOAD.splitlines()
    for f in Engine(all_rules()).select(["SH02"]).run_source(
            SH02_HELPER_UPLOAD, relpath="runtime/snippet.py", tier="runtime"):
        lines[f.line - 1] += "  # fabric-lint: waive SH02 reason=fixture"
    waived = Engine(all_rules()).select(["SH02"]).run_source(
        "\n".join(lines), relpath="runtime/snippet.py", tier="runtime")
    assert len(waived) == len(bad) and all(f.waived for f in waived)

    lines = AK01_PRE_PR7.splitlines()
    for f in Engine(all_rules()).select(["AK01"]).run_source(
            AK01_PRE_PR7, relpath="runtime/snippet.py", tier="runtime"):
        lines[f.line - 1] += "  # fabric-lint: waive AK01 reason=fixture"
    waived = Engine(all_rules()).select(["AK01"]).run_source(
        "\n".join(lines), relpath="runtime/snippet.py", tier="runtime")
    assert waived and all(f.waived for f in waived)


def test_SHAK_baseline_round_trips():
    baseline = {("runtime/snippet.py", "SH02"): 1}
    engine = Engine(all_rules(), baseline).select(["SH02"])
    first = engine.run_source(SH02_HELPER_UPLOAD,
                              relpath="runtime/snippet.py", tier="runtime")
    second = engine.run_source(SH02_HELPER_UPLOAD,
                               relpath="runtime/snippet.py", tier="runtime")
    assert first and first[0].baselined
    assert second and not second[0].baselined  # the budget is finite

    baseline = {("runtime/snippet.py", "AK01"): 1}
    findings = Engine(all_rules(), baseline).select(["AK01"]).run_source(
        AK01_PRE_PR7, relpath="runtime/snippet.py", tier="runtime")
    assert findings and findings[0].baselined


def test_SHAK_sarif_round_trip():
    findings = Engine(all_rules()).select(["AK01"]).run_source(
        AK01_PRE_PR7, relpath="runtime/snippet.py", tier="runtime")
    doc = json.loads(emit_sarif(findings, all_rules()))
    run = doc["runs"][0]
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} >= {
        "SH02", "SH03", "SH04", "AK01"}
    assert run["results"][0]["ruleId"] == "AK01"


def test_SHAK_repo_gate_clean():
    """The tentpole acceptance: SH02–SH04 + AK01 run clean on the live
    package (the two real AK01 gaps — use_flash, prefix_cache_pages — were
    threaded into the AOT key in this PR; no waivers, no baseline)."""
    engine = Engine(all_rules()).select(["SH02", "SH03", "SH04", "AK01"])
    findings = [f for f in engine.run(PKG) if not f.suppressed]
    assert findings == [], "\n".join(
        f"{f.path}:{f.line} {f.rule} {f.message}" for f in findings)


# ----------------------------------------------- RC family (fabric-race)

#: the PR-8 pre-fix shape, distilled: _fail_all_inflight drains the pending
#: queue UNDER _submit_lock and hands each request to the pool's failover,
#: which (under its own lock) resubmits into a sibling engine's submit —
#: submit takes _submit_lock again. Two same-round teardowns deadlock ABBA.
PR8_ABBA_PREFIX = """
import threading

class ServingPool:
    def __init__(self, engine: "Engine"):
        self._lock = threading.Lock()
        self.engine = engine

    def failover(self, req):
        with self._lock:
            self.engine.submit(req)

class Engine:
    def __init__(self):
        self._submit_lock = threading.Lock()
        self._pending = []
        self.pool = ServingPool(self)

    def submit(self, req):
        with self._submit_lock:
            self._pending.append(req)

    def _fail_all_inflight(self):
        with self._submit_lock:
            for req in list(self._pending):
                self.pool.failover(req)
"""

#: the PR-10 pre-fix shape: charge() RMWs the virtual counters without the
#: queue lock that guards every other write to them
PR10_CHARGE_PREFIX = """
import threading

class TenantFairQueue:
    def __init__(self):
        self._lock = threading.Lock()
        self._vtc = {}

    def put(self, tenant):
        with self._lock:
            self._vtc[tenant] = max(self._vtc.get(tenant, 0.0), 1.0)

    def charge(self, tenant, tokens, weight):
        self._vtc[tenant] = self._vtc.get(tenant, 0.0) + tokens / weight
"""


def test_RC01_pr8_abba_prefix_shape_must_flag():
    """Acceptance regression: the PR-8 ABBA deadlock's pre-fix shape is a
    lock-order cycle RC01 must report, with both witness paths."""
    bad = lint(PR8_ABBA_PREFIX, tier="runtime", select=("RC01",))
    assert "RC01" in rule_ids(bad)
    msg = " ".join(f.message for f in bad)
    assert "_submit_lock" in msg
    assert "_fail_all_inflight" in msg and "failover" in msg  # witness paths


def test_RC01_emits_outside_lock_passes():
    """The shipped fix: drain under the lock, hand off after releasing it —
    no call is made while _submit_lock is held, so no cycle exists."""
    ok = lint("""
import threading

class ServingPool:
    def __init__(self, engine: "Engine"):
        self._lock = threading.Lock()
        self.engine = engine

    def failover(self, req):
        with self._lock:
            self.engine.submit(req)

class Engine:
    def __init__(self):
        self._submit_lock = threading.Lock()
        self._pending = []
        self.pool = ServingPool(self)

    def submit(self, req):
        with self._submit_lock:
            self._pending.append(req)

    def _fail_all_inflight(self):
        stranded = []
        with self._submit_lock:
            stranded.extend(self._pending)
            self._pending = []
        for req in stranded:
            self.pool.failover(req)
""", tier="runtime", select=("RC01",))
    assert ok == []


def test_RC01_self_reacquire_through_helper_fails():
    # a non-reentrant lock re-acquired two frames down self-deadlocks
    bad = lint("""
import threading

class Pool:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def _bump(self):
        with self._lock:
            self.n += 1

    def tick(self):
        with self._lock:
            self._bump()
""", tier="runtime", select=("RC01",))
    assert rule_ids(bad) == ["RC01"]


def test_RC01_rlock_reentry_passes():
    ok = lint("""
import threading

class Pool:
    def __init__(self):
        self._lock = threading.RLock()
        self.n = 0

    def _bump(self):
        with self._lock:
            self.n += 1

    def tick(self):
        with self._lock:
            self._bump()
""", tier="runtime", select=("RC01",))
    assert ok == []


def test_RC01_consistent_order_passes():
    # A-then-B from two call paths is a hierarchy, not an inversion
    ok = lint("""
import threading

class Queue:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = []

    def put(self, x):
        with self._lock:
            self.items.append(x)

class Engine:
    def __init__(self):
        self._submit_lock = threading.Lock()
        self._pending = Queue()

    def submit(self, req):
        with self._submit_lock:
            self._pending.put(req)

    def drain(self):
        with self._submit_lock:
            self._pending.put(None)
""", tier="runtime", select=("RC01",))
    assert ok == []


def test_RC02_pr10_unlocked_charge_prefix_shape_must_flag():
    """Acceptance regression: the PR-10 lock-free charge() RMW is exactly
    the mixed-guard shape RC02 must report."""
    bad = lint(PR10_CHARGE_PREFIX, tier="runtime", select=("RC02",))
    assert rule_ids(bad) == ["RC02"]
    assert "charge" in bad[0].message and "_vtc" in bad[0].message


def test_RC02_locked_charge_passes():
    ok = lint("""
import threading

class TenantFairQueue:
    def __init__(self):
        self._lock = threading.Lock()
        self._vtc = {}

    def put(self, tenant):
        with self._lock:
            self._vtc[tenant] = max(self._vtc.get(tenant, 0.0), 1.0)

    def charge(self, tenant, tokens, weight):
        with self._lock:
            self._vtc[tenant] = self._vtc.get(tenant, 0.0) + tokens / weight
""", tier="runtime", select=("RC02",))
    assert ok == []


def test_RC02_helper_called_under_lock_inherits_context():
    """The LK01 false-positive class: a private helper only ever called
    with the lock held inherits that context interprocedurally."""
    ok = lint("""
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self._stats = {}

    def _bump(self, key):
        self._stats[key] = self._stats.get(key, 0) + 1

    def note(self, key):
        with self._lock:
            self._bump(key)

    def note_two(self, key):
        with self._lock:
            self._bump(key)
            self._stats[key] = self._stats.get(key, 0) + 1
""", tier="runtime", select=("RC02",))
    assert ok == []


def test_RC02_init_writes_free():
    # __init__ happens-before thread start; so do helpers only it calls
    ok = lint("""
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self._stats = {}
        self._seed()

    def _seed(self):
        self._stats["boot"] = 1

    def note(self, key):
        with self._lock:
            self._stats[key] = self._stats.get(key, 0) + 1
""", tier="runtime", select=("RC02",))
    assert ok == []


def test_RC02_advisory_plain_store_not_inferred():
    # one locked plain store vs one unlocked plain store: the sanctioned
    # last-writer-wins advisory idiom (last_round_at) — no guard inferred
    ok = lint("""
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self.last_round_at = 0.0

    def submit(self, now):
        with self._lock:
            self.last_round_at = now

    def round_done(self, now):
        self.last_round_at = now
""", tier="runtime", select=("RC02",))
    assert ok == []


def test_RC03_sleep_under_lock_fails():
    bad = lint("""
import threading
import time

class Pool:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def tick(self):
        with self._lock:
            time.sleep(0.1)
""", tier="runtime", select=("RC03",))
    assert rule_ids(bad) == ["RC03"]
    assert "time.sleep" in bad[0].message


def test_RC03_transitive_block_through_helper_fails():
    # the blocking call two frames below the lock is the RacerD case the
    # single-function families cannot see
    bad = lint("""
import threading
import time

class Pool:
    def __init__(self):
        self._lock = threading.Lock()

    def _backoff(self):
        self._wait()

    def _wait(self):
        time.sleep(0.5)

    def tick(self):
        with self._lock:
            self._backoff()
""", tier="runtime", select=("RC03",))
    assert rule_ids(bad) == ["RC03"]
    assert "_backoff" in bad[0].message and "_wait" in bad[0].message


def test_RC03_emit_under_lock_fails():
    # the PR-8 decree generalized: emit callbacks are foreign code
    bad = lint("""
import threading

class Engine:
    def __init__(self):
        self._submit_lock = threading.Lock()
        self._pending = []

    def _fail_all(self):
        with self._submit_lock:
            for req in list(self._pending):
                req.emit(None)
""", tier="runtime", select=("RC03",))
    assert rule_ids(bad) == ["RC03"]


def test_RC03_blocking_outside_lock_passes():
    ok = lint("""
import threading
import time

class Pool:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def tick(self):
        with self._lock:
            self.n += 1
        time.sleep(0.1)
""", tier="runtime", select=("RC03",))
    assert ok == []


def test_RC03_only_shared_tier_locks_gate():
    # a modules-tier helper class may block under its own lock — RC03 is a
    # runtime/modkit data-plane rule
    ok = lint("""
import threading
import time

class Cache:
    def __init__(self):
        self._lock = threading.Lock()

    def refresh(self):
        with self._lock:
            time.sleep(0.1)
""", tier="modules", select=("RC03",))
    assert ok == []


def test_RC04_unguarded_iteration_fails():
    bad = lint("""
import threading
from collections import deque

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self._suspended = deque()
        self._thread = threading.Thread(target=self._loop)

    def _loop(self):
        self._suspended.append(1)

    def probe(self, rid):
        return rid in list(self._suspended)
""", tier="runtime", select=("RC04",))
    assert rule_ids(bad) == ["RC04"]
    assert "_suspended" in bad[0].message


def test_RC04_runtime_error_guard_passes():
    ok = lint("""
import threading
from collections import deque

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self._suspended = deque()
        self._thread = threading.Thread(target=self._loop)

    def _loop(self):
        self._suspended.append(1)

    def probe(self, rid):
        try:
            return rid in list(self._suspended)
        except RuntimeError:
            return False
""", tier="runtime", select=("RC04",))
    assert ok == []


def test_RC04_locked_snapshot_helper_passes():
    ok = lint("""
import threading
from collections import deque

from cyberfabric_core_tpu.modkit.concurrency import locked_snapshot

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self._suspended = deque()
        self._thread = threading.Thread(target=self._loop)

    def _loop(self):
        self._suspended.append(1)

    def probe(self, rid):
        return rid in list(locked_snapshot(self._suspended))
""", tier="runtime", select=("RC04",))
    assert ok == []


def test_RC04_iteration_under_guard_passes():
    ok = lint("""
import threading

class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._metrics = {}

    def add(self, name):
        with self._lock:
            self._metrics[name] = 1

    def render(self):
        with self._lock:
            return sorted(self._metrics)
""", tier="modkit", select=("RC04",))
    assert ok == []


def test_RC04_fixed_key_dict_update_not_a_resize():
    # constant-key stores into a literal-initialized dict update in place;
    # they cannot raise `changed size during iteration` in a reader
    ok = lint("""
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self._stats = {"hits": 0, "misses": 0}
        self._thread = threading.Thread(target=self._loop)

    def _loop(self):
        self._stats["hits"] = self._stats["hits"] + 1

    def stats(self):
        return dict(self._stats)
""", tier="runtime", select=("RC04",))
    assert ok == []


def test_RC04_same_thread_iteration_passes():
    # iterate and resize on the SAME owning thread: sequential, not a race
    ok = lint("""
import threading
from collections import deque

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self._q = deque()
        self._thread = threading.Thread(target=self._loop)

    def _loop(self):
        self._q.append(1)
        self._drain()

    def _drain(self):
        for item in list(self._q):
            pass
""", tier="runtime", select=("RC04",))
    assert ok == []


def test_RC_waiver_round_trips():
    """Each RC family suppresses through the standard inline waiver."""
    waived_charge = PR10_CHARGE_PREFIX.replace(
        "        self._vtc[tenant] = self._vtc.get(tenant, 0.0) + "
        "tokens / weight",
        "        # fabric-lint: waive RC02 reason=fixture\n"
        "        self._vtc[tenant] = self._vtc.get(tenant, 0.0) + "
        "tokens / weight")
    assert waived_charge != PR10_CHARGE_PREFIX, "fixture drifted"
    findings = Engine(all_rules()).select(["RC02"]).run_source(
        waived_charge, relpath="runtime/snippet.py", tier="runtime")
    assert findings and all(f.waived for f in findings)

    bad = lint(PR8_ABBA_PREFIX, tier="runtime", select=("RC01",))
    lines = PR8_ABBA_PREFIX.splitlines()
    for f in Engine(all_rules()).select(["RC01"]).run_source(
            PR8_ABBA_PREFIX, relpath="runtime/snippet.py", tier="runtime"):
        lines[f.line - 1] += \
            "  # fabric-lint: waive RC01 reason=fixture"
    waived = Engine(all_rules()).select(["RC01"]).run_source(
        "\n".join(lines), relpath="runtime/snippet.py", tier="runtime")
    assert len(waived) == len(bad) and all(f.waived for f in waived)

    rc03 = Engine(all_rules()).select(["RC03"]).run_source(
        "import threading\n"
        "import time\n"
        "class Pool:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def tick(self):\n"
        "        with self._lock:\n"
        "            time.sleep(0.1)"
        "  # fabric-lint: waive RC03 reason=fixture\n",
        relpath="runtime/snippet.py", tier="runtime")
    assert rc03 and all(f.waived for f in rc03)

    rc04 = Engine(all_rules()).select(["RC04"]).run_source(
        "import threading\n"
        "from collections import deque\n"
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._q = deque()\n"
        "        self._thread = threading.Thread(target=self._loop)\n"
        "    def _loop(self):\n"
        "        self._q.append(1)\n"
        "    def probe(self):\n"
        "        return list(self._q)"
        "  # fabric-lint: waive RC04 reason=fixture\n",
        relpath="runtime/snippet.py", tier="runtime")
    assert rc04 and all(f.waived for f in rc04)


def test_RC_baseline_round_trips():
    baseline = {("runtime/snippet.py", "RC02"): 1}
    findings = Engine(all_rules(), baseline).select(["RC02"]).run_source(
        PR10_CHARGE_PREFIX, relpath="runtime/snippet.py", tier="runtime")
    assert findings and findings[0].baselined
    # the budget is finite: a second identical engine run is NOT absorbed
    engine = Engine(all_rules(), baseline).select(["RC02"])
    first = engine.run_source(PR10_CHARGE_PREFIX,
                              relpath="runtime/snippet.py", tier="runtime")
    second = engine.run_source(PR10_CHARGE_PREFIX,
                               relpath="runtime/snippet.py", tier="runtime")
    assert first[0].baselined and not second[0].baselined


def test_RC_repo_gate_clean():
    """The tentpole acceptance: RC01–RC04 run clean on the live package
    (real findings fixed in this PR, sanctioned patterns carry reasoned
    waivers)."""
    engine = Engine(all_rules()).select(["RC"])
    findings = [f for f in engine.run(PKG) if not f.suppressed]
    assert findings == [], "\n".join(
        f"{f.path}:{f.line} {f.rule} {f.message}" for f in findings)


def test_RC_repo_waivers_are_reasoned():
    """Every RC waiver in the package carries a written reason (WV01 makes
    a reasonless one a finding, so this is belt-and-braces documentation)."""
    engine = Engine(all_rules()).select(["RC"])
    waived = [f for f in engine.run(PKG) if f.waived]
    assert waived, "expected the sanctioned RC03 waivers to exist"
    assert all(f.waive_reason for f in waived)


# ----------------------------------------------------------- lock graph


def test_lock_graph_dict_shape():
    from cyberfabric_core_tpu.apps.fabric_lint.engine import (
        FileContext, ProjectContext)
    from cyberfabric_core_tpu.apps.fabric_lint.project_model import (
        build_project_model, lock_graph_dict, lock_graph_dot)

    ctx = FileContext(Path("runtime/snippet.py"), Path("."),
                      source=PR8_ABBA_PREFIX)
    ctx.relpath, ctx.tier = "runtime/snippet.py", "runtime"
    model = build_project_model(ProjectContext(Path("."), [ctx]))
    graph = lock_graph_dict(model)
    labels = {n["lock"] for n in graph["nodes"]}
    assert {"Engine._submit_lock", "ServingPool._lock"} <= labels
    pairs = {(e["src"], e["dst"]) for e in graph["edges"]}
    assert ("Engine._submit_lock", "ServingPool._lock") in pairs
    assert ("ServingPool._lock", "Engine._submit_lock") in pairs
    assert graph["cycles"], "the ABBA fixture must show up as a cycle"
    dot = lock_graph_dot(model)
    assert dot.startswith("digraph lock_order") and "color=\"red\"" in dot


def test_lock_graph_refuses_partial_scan(tmp_path):
    """A file that fails to parse must fail --lock-graph (exit 2) instead of
    silently regenerating a hierarchy missing that file's locks."""
    import io
    from contextlib import redirect_stderr, redirect_stdout

    from cyberfabric_core_tpu.apps.fabric_lint.__main__ import main

    (tmp_path / "bad.py").write_text("def broken(:\n")
    err = io.StringIO()
    with redirect_stdout(io.StringIO()), redirect_stderr(err):
        rc = main([str(tmp_path), "--lock-graph", "json"])
    assert rc == 2 and "syntax error" in err.getvalue()


def test_lock_graph_cli_json_and_drift():
    """--lock-graph regenerates the committed artifact byte-for-byte (the
    CI drift check) and exits 0 because the committed hierarchy is
    acyclic."""
    import io
    from contextlib import redirect_stdout

    from cyberfabric_core_tpu.apps.fabric_lint.__main__ import main

    out = io.StringIO()
    with redirect_stdout(out):
        rc = main([str(PKG), "--lock-graph", "json"])
    assert rc == 0
    regenerated = json.loads(out.getvalue())
    committed = json.loads((REPO / "docs" / "lock_graph.json").read_text())
    assert regenerated == committed, (
        "docs/lock_graph.json is stale — run `make lock-graph` and commit "
        "the regenerated hierarchy")
    assert regenerated["cycles"] == []


# ----------------------------------------------------------- shard graph


def test_shard_graph_dict_shape():
    from cyberfabric_core_tpu.apps.fabric_lint.engine import (
        FileContext, ProjectContext)
    from cyberfabric_core_tpu.apps.fabric_lint.spmd_model import (
        build_spmd_model, shard_graph_dict, shard_graph_dot)

    src = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "def build_mesh(devices):\n"
        "    return Mesh(devices, ('dp', 'tp'))\n"
        "class Engine:\n"
        "    def __init__(self, devices):\n"
        "        self.mesh = build_mesh(devices)\n"
        "        self.page_table = np.zeros((8, 16))\n"
        "        self._decode_fn = jax.jit(lambda x: x)\n"
    )
    ctx = FileContext(Path("runtime/snippet.py"), Path("."), source=src)
    ctx.relpath, ctx.tier = "runtime/snippet.py", "runtime"
    model = build_spmd_model(ProjectContext(Path("."), [ctx]))
    graph = shard_graph_dict(model)
    assert graph["axes"] == ["dp", "tp"]
    # the build_mesh call site INHERITS the axes from the builder's body
    builder_sites = [m for m in graph["meshes"] if m["ctor"] == "build_mesh"]
    assert builder_sites and builder_sites[0]["axes"] == ["dp", "tp"]
    assert {"path": "runtime/snippet.py", "class": "Engine"} in \
        graph["mesh_classes"]
    assert any(d["attr"] == "_decode_fn" for d in graph["dispatches"])
    assert {"path": "runtime/snippet.py", "class": "Engine",
            "attr": "page_table", "prov": "host"} in graph["provenance"]
    dot = shard_graph_dot(model)
    assert dot.startswith("digraph shard_world") and '"axis:tp"' in dot


def test_shard_graph_refuses_partial_scan(tmp_path):
    """A file that fails to parse must fail --shard-graph (exit 2) instead
    of silently regenerating an axis universe missing that file's meshes."""
    import io
    from contextlib import redirect_stderr, redirect_stdout

    from cyberfabric_core_tpu.apps.fabric_lint.__main__ import main

    (tmp_path / "bad.py").write_text("def broken(:\n")
    err = io.StringIO()
    with redirect_stdout(io.StringIO()), redirect_stderr(err):
        rc = main([str(tmp_path), "--shard-graph", "json"])
    assert rc == 2 and "syntax error" in err.getvalue()


def test_shard_graph_cli_json_and_drift():
    """--shard-graph regenerates the committed artifact byte-for-byte (the
    CI drift check) and exits 0 because the AOT key is complete."""
    import io
    from contextlib import redirect_stdout

    from cyberfabric_core_tpu.apps.fabric_lint.__main__ import main

    out = io.StringIO()
    with redirect_stdout(out):
        rc = main([str(PKG), "--shard-graph", "json"])
    assert rc == 0
    regenerated = json.loads(out.getvalue())
    committed = json.loads((REPO / "docs" / "shard_graph.json").read_text())
    assert regenerated == committed, (
        "docs/shard_graph.json is stale — run `make shard-graph` and commit "
        "the regenerated SPMD world")
    assert regenerated["aot_key"]["uncovered"] == []
    assert "tp" in regenerated["axes"]
    assert any(d["attr"] == "_paged_decode_fn"
               for d in regenerated["dispatches"])


def test_max_seconds_budget_exceeded(tmp_path):
    """--max-seconds 0 forces the wall-clock guard to trip (exit 3)."""
    import io
    from contextlib import redirect_stderr, redirect_stdout

    from cyberfabric_core_tpu.apps.fabric_lint.__main__ import main

    (tmp_path / "ok.py").write_text("x = 1\n")
    err = io.StringIO()
    with redirect_stdout(io.StringIO()), redirect_stderr(err):
        rc = main([str(tmp_path), "--max-seconds", "0"])
    assert rc == 3 and "wall-clock budget exceeded" in err.getvalue()


def test_max_seconds_budget_met_keeps_exit_code(tmp_path):
    import io
    from contextlib import redirect_stdout

    from cyberfabric_core_tpu.apps.fabric_lint.__main__ import main

    (tmp_path / "ok.py").write_text("x = 1\n")
    with redirect_stdout(io.StringIO()):
        rc = main([str(tmp_path), "--max-seconds", "600"])
    assert rc == 0


# ------------------------------------------------------- waivers + baseline


def test_waiver_suppresses_finding():
    findings = Engine(all_rules()).select(["AS01"]).run_source(
        "import time\n"
        "def helper():\n"
        "    # fabric-lint: waive AS01 reason=dedicated sync thread\n"
        "    time.sleep(0.1)\n",
        relpath="modules/snippet.py", tier="modules")
    assert [f.rule for f in findings] == ["AS01"]
    assert findings[0].waived and findings[0].waive_reason == \
        "dedicated sync thread"


def test_waiver_same_line_suppresses():
    findings = Engine(all_rules()).select(["AS01"]).run_source(
        "import time\n"
        "def helper():\n"
        "    time.sleep(0.1)  # fabric-lint: waive AS01 reason=sync thread\n",
        relpath="modules/snippet.py", tier="modules")
    assert findings[0].waived


def test_waiver_for_other_rule_does_not_suppress():
    bad = lint(
        "import time\n"
        "def helper():\n"
        "    time.sleep(0.1)  # fabric-lint: waive AS03 reason=wrong rule\n",
        select=("AS01",))
    assert rule_ids(bad) == ["AS01"]


def test_waiver_without_reason_is_WV01_and_suppresses_nothing():
    findings = Engine(all_rules()).select(["AS01"]).run_source(
        "import time\n"
        "def helper():\n"
        "    time.sleep(0.1)  # fabric-lint: waive AS01\n",
        relpath="modules/snippet.py", tier="modules")
    ids = [f.rule for f in findings if not f.suppressed]
    assert "AS01" in ids and "WV01" in ids


def test_baseline_respected():
    baseline = {("modules/snippet.py", "AS01"): 1}
    findings = Engine(all_rules(), baseline).select(["AS01"]).run_source(
        "import time\n"
        "def helper():\n"
        "    time.sleep(0.1)\n",
        relpath="modules/snippet.py", tier="modules")
    assert findings[0].baselined and findings[0].suppressed


def test_baseline_budget_is_finite():
    # one baselined slot does not absorb a SECOND new finding
    baseline = {("modules/snippet.py", "AS01"): 1}
    findings = Engine(all_rules(), baseline).select(["AS01"]).run_source(
        "import time\n"
        "def helper():\n"
        "    time.sleep(0.1)\n"
        "    time.sleep(0.2)\n",
        relpath="modules/snippet.py", tier="modules")
    assert [f.baselined for f in findings] == [True, False]


def test_WV01_cannot_be_waived_or_baselined():
    # waiver hygiene is engine-level: neither an inline waiver nor a
    # baseline slot may silence it
    baseline = {("modules/snippet.py", "WV01"): 5}
    findings = Engine(all_rules(), baseline).select(["AS01"]).run_source(
        "import time\n"
        "def helper():\n"
        "    # fabric-lint: waive WV01 reason=shush\n"
        "    time.sleep(0.1)  # fabric-lint: waive AS01\n",
        relpath="modules/snippet.py", tier="modules")
    wv = [f for f in findings if f.rule == "WV01"]
    assert wv and all(not f.suppressed for f in wv)


def test_baseline_budget_shared_across_runs():
    # the CLI lints each path argument in its own run(); the committed
    # budget must not be replenished per run
    baseline = {("modules/snippet.py", "AS01"): 1}
    engine = Engine(all_rules(), baseline).select(["AS01"])
    src = "import time\ndef helper():\n    time.sleep(0.1)\n"
    first = engine.run_source(src, relpath="modules/snippet.py", tier="modules")
    second = engine.run_source(src, relpath="modules/snippet.py", tier="modules")
    assert first[0].baselined and not second[0].baselined


def test_subdirectory_run_keeps_package_tier():
    """Regression: scanning a package SUBdirectory must apply the same
    tier-gated rules as a whole-package scan."""
    engine = Engine(all_rules()).select(["AS01", "JP", "LK"])
    findings = [f for f in engine.run(PKG / "runtime") if not f.suppressed]
    assert findings == []  # and NOT false AS01s on scheduler-thread sleeps
    # tier must resolve to "runtime", not ""
    from cyberfabric_core_tpu.apps.fabric_lint.engine import FileContext
    resolved = FileContext(PKG / "runtime" / "scheduler.py", PKG)
    assert resolved.tier == "runtime"


def test_single_file_run_keeps_package_tier():
    """Regression: linting one file must apply the same tier-gated rules as
    a whole-package scan (a lone runtime/ file must not draw serving-tier
    AS01 findings, and must still get runtime-tier rules)."""
    engine = Engine(all_rules()).select(["AS01"])
    findings = engine.run(PKG / "runtime" / "scheduler.py")
    assert [f for f in findings if f.rule == "AS01"] == []
    # and a serving-tier file linted alone still carries its waived findings
    engine = Engine(all_rules()).select(["AS01"])
    findings = engine.run(PKG / "modkit" / "db_engine.py")
    assert len([f for f in findings if f.waived]) == 2


def test_committed_baseline_parses():
    from cyberfabric_core_tpu.apps.fabric_lint import load_baseline

    baseline = load_baseline(REPO / "config" / "fabric_lint_baseline.json")
    assert baseline == {}, "committed baseline must stay empty — fix or " \
        "waive findings instead of baselining new debt"


# --------------------------------------------------------------- emitters


def test_sarif_emitter_shape():
    findings = Engine(all_rules()).select(["AS01"]).run_source(
        "import time\n"
        "async def h():\n"
        "    time.sleep(1)\n",
        relpath="modules/snippet.py", tier="modules")
    doc = json.loads(emit_sarif(findings, all_rules()))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "fabric-lint"
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} >= {"AS01", "LK01"}
    res = run["results"][0]
    assert res["ruleId"] == "AS01"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "modules/snippet.py"
    assert loc["region"]["startLine"] == 3


def test_json_emitter_roundtrip():
    findings = Engine(all_rules()).select(["AS02"]).run_source(
        "import asyncio\n"
        "async def go(c):\n"
        "    asyncio.ensure_future(c)\n",
        relpath="modules/snippet.py", tier="modules")
    doc = json.loads(emit_json(findings))
    assert doc["findings"][0]["rule"] == "AS02"
    assert doc["findings"][0]["waived"] is False


# ------------------------------------------------------------- repo gates


@pytest.mark.slow
def test_cli_exits_zero_on_repo():
    """The acceptance gate: zero unwaivered findings across the package."""
    proc = subprocess.run(
        [sys.executable, "-m", "cyberfabric_core_tpu.apps.fabric_lint",
         str(PKG)],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_engine_clean_on_repo_semantic_families():
    """In-process equivalent for the new families (fast enough for tier-1):
    AS/JP/LK produce no unwaived findings on the live package."""
    engine = Engine(all_rules()).select(["AS", "JP", "LK"])
    findings = [f for f in engine.run(PKG) if not f.suppressed]
    assert findings == [], "\n".join(
        f"{f.path}:{f.line} {f.rule} {f.message}" for f in findings)


def test_db_engine_waivers_are_canonical():
    """The two sanctioned retry-loop sleeps carry reasoned waivers — the
    documented example of the waiver syntax."""
    engine = Engine(all_rules()).select(["AS01"])
    findings = engine.run(PKG, [PKG / "modkit" / "db_engine.py"])
    waived = [f for f in findings if f.waived]
    assert len(waived) == 2
    assert all("sync engine thread" in f.waive_reason for f in waived)
