"""faultlab: failpoint registry semantics, the deterministic chaos-scenario
suite (every catalogued failpoint exercised), and the satellites that ride
with it (max_pending backpressure → 429 + Retry-After, failover metrics).

The scenario tests ARE the acceptance surface: same seed → same verdict,
invariant checkers green, streams bit-identical across injected preempt and
failover. `make chaos` runs this file plus the CLI.
"""

from __future__ import annotations

import asyncio

import pytest

from cyberfabric_core_tpu.modkit import failpoints as fp
from cyberfabric_core_tpu.apps.faultlab import run_scenario
from cyberfabric_core_tpu.apps.faultlab.scenarios import (
    BUILTIN_SCENARIOS, covered_points, scenario_by_name)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.reset()
    yield
    fp.reset()


# ------------------------------------------------------------- registry unit


def test_disarmed_failpoint_is_inert_and_returns_none():
    assert fp.failpoint("scheduler.readback") is None
    assert fp.stats()["armed"] == {}


def test_arm_rejects_unknown_names_and_bad_specs():
    with pytest.raises(KeyError):
        fp.arm("no.such.point", "raise")
    with pytest.raises(ValueError):
        fp.arm("scheduler.readback", "explode")
    with pytest.raises(ValueError):
        fp.arm("scheduler.readback", {"kind": "raise", "exc": "SystemExit"})


def test_parse_action_spec_language():
    a = fp.parse_action("2*raise(MemoryError)")
    assert (a.kind, a.mode, a.n, a.exc) == ("raise", "once", 2, "MemoryError")
    a = fp.parse_action("delay(0.05)")
    assert (a.kind, a.delay_s) == ("delay", 0.05)
    a = fp.parse_action("25%raise")
    assert (a.mode, a.p) == ("prob", 0.25)
    a = fp.parse_action("3:raise")
    assert (a.mode, a.n) == ("every_nth", 3)
    a = fp.parse_action("return(503)")
    assert (a.kind, a.value) == ("return", 503)
    assert fp.parse_action("off").kind == "off"


def test_once_mode_fires_n_then_stops():
    with fp.scoped("db_engine.commit", "2*raise"):
        for expect_raise in (True, True, False, False):
            if expect_raise:
                with pytest.raises(fp.FaultInjected):
                    fp.failpoint("db_engine.commit")
            else:
                assert fp.failpoint("db_engine.commit") is None
        st = fp.stats()["armed"]["db_engine.commit"]
        assert (st["hits"], st["injected"]) == (4, 2)


def test_every_nth_and_after():
    with fp.scoped("db_engine.commit",
                   {"kind": "return", "value": 1, "mode": "every_nth",
                    "n": 2, "after": 1}):
        got = [fp.failpoint("db_engine.commit") for _ in range(5)]
    # hits 1 is skipped (after=1); eligible hits 2,4 fire (every 2nd)
    assert got == [None, None, 1, None, 1]


def test_prob_mode_is_seed_deterministic():
    def draw(seed):
        fp.reset()
        fp.configure(seed)
        with fp.scoped("db_engine.commit",
                       {"kind": "return", "value": 1, "mode": "prob",
                        "p": 0.5}):
            return [fp.failpoint("db_engine.commit") is not None
                    for _ in range(32)]

    a, b, c = draw(7), draw(7), draw(8)
    assert a == b
    assert a != c  # different seed, different schedule
    assert any(a) and not all(a)


def test_return_action_and_recovery_stats():
    fp.record_recovery("scheduler.resume", 0.25)
    st = fp.stats()
    assert st["recoveries"]["scheduler.resume"]["count"] == 1
    assert st["recoveries"]["scheduler.resume"]["last_s"] == 0.25


# --------------------------------------------------------- scenario coverage


def test_every_catalogued_failpoint_has_a_scenario():
    """A failpoint cannot land without an owning chaos scenario."""
    missing = set(fp.FAILPOINT_CATALOG) - covered_points()
    assert not missing, f"failpoints without a scenario: {sorted(missing)}"
    assert len(fp.FAILPOINT_CATALOG) >= 12
    layers = {layer for layer, _ in fp.FAILPOINT_CATALOG.values()}
    assert layers >= {"runtime", "gateway", "modkit", "modules"}


# fleet-doctor-shed boots a full REST stack + two worker subprocesses and
# waits out a real burn/recovery cycle — too heavy for the tier-1 budget;
# `make chaos` and the CI faultlab leg (--repeat 2) still run it
@pytest.mark.parametrize("name", [
    pytest.param(s["name"], marks=[pytest.mark.slow]
                 if s["kind"] == "fleet_doctor_shed" else [])
    for s in BUILTIN_SCENARIOS])
def test_scenario(name):
    result = run_scenario(scenario_by_name(name))
    red = {k: v for k, v in result.invariants.items() if v}
    assert result.verdict, f"{name}: {red} (details={result.details})"


@pytest.mark.parametrize("name", ["db-commit-fault", "http-retry-storm",
                                  "grpc-evict-tick", "forced-preempt",
                                  "stream-stall-watchdog"])
def test_scenario_repeatable_same_seed_same_fingerprint(name):
    spec = scenario_by_name(name)
    a = run_scenario(spec)
    b = run_scenario(spec)
    assert a.verdict and b.verdict
    assert a.fingerprint == b.fingerprint


@pytest.mark.slow
def test_slo_burn_repeatable_same_seed_same_fingerprint():
    """The acceptance-cycle scenario is deterministic end to end: two boots
    of the faulted server walk the same state sequence and produce the same
    fingerprint (also held by the CI `faultlab --repeat 2` leg)."""
    spec = scenario_by_name("slo-burn-shed-recover")
    a = run_scenario(spec)
    b = run_scenario(spec)
    assert a.verdict and b.verdict
    assert a.fingerprint == b.fingerprint


def test_cli_single_scenario():
    from cyberfabric_core_tpu.apps.faultlab.__main__ import main

    assert main(["--scenario", "db-commit-fault"]) == 0
    assert main(["--list"]) == 0


def test_scenario_file_roundtrip(tmp_path):
    from cyberfabric_core_tpu.apps.faultlab.scenarios import load_scenario_file

    path = tmp_path / "chaos.yaml"
    path.write_text(
        "scenarios:\n"
        "  - name: file-db-fault\n"
        "    kind: db_commit\n"
        "    seed: 9\n"
        "    faults:\n"
        "      - point: db_engine.commit\n"
        "        spec: '1*raise'\n")
    specs = load_scenario_file(path)
    result = run_scenario(specs[0])
    assert result.verdict, result.invariants


# ------------------------------------------------- satellite: max_pending 429


def test_scheduler_max_pending_rejects_with_saturated():
    from cyberfabric_core_tpu.runtime.engine import (EngineConfig,
                                                     SamplingParams,
                                                     SchedulerSaturated)
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    cfg = EngineConfig(model="tiny-llama", max_seq_len=64, max_batch=2,
                       decode_chunk=4, prefix_cache_pages=64,
                       prefix_page_size=16, max_pending=2)
    engine = ContinuousBatchingEngine(cfg, seed=0)
    engine.start = lambda: None  # freeze admission: nothing drains the queue
    for _ in range(2):
        engine.submit([1, 2, 3], SamplingParams(max_tokens=2),
                      lambda ev: None)
    with pytest.raises(SchedulerSaturated) as ei:
        engine.submit([1, 2, 3], SamplingParams(max_tokens=2),
                      lambda ev: None)
    assert ei.value.retry_after_s > 0
    assert engine.stats()["rejected_saturated"] == 1


def test_worker_maps_saturation_to_429_problem():
    from cyberfabric_core_tpu.modkit.errors import ProblemError
    from cyberfabric_core_tpu.modules.llm_gateway.worker import LocalTpuWorker
    from cyberfabric_core_tpu.modules.sdk import ModelInfo

    async def go():
        worker = LocalTpuWorker({})
        model = ModelInfo(
            canonical_id="local::saturate", provider_slug="local",
            provider_model_id="saturate",
            engine_options={"model_config": "tiny-llama", "max_seq_len": 64,
                            "max_batch": 1, "decode_chunk": 4,
                            "max_pending": 1})
        entry = await worker._entry_for(model)
        # freeze admission (the worker starts the thread at the build)
        entry.scheduler.shutdown()
        entry.scheduler.start = lambda: None
        # first request fills the one pending slot ...
        agen = worker.completion_stream(model, "a", {"max_tokens": 2})
        first = asyncio.ensure_future(agen.__anext__())
        await asyncio.sleep(0.05)
        # ... the second must surface as a 429 problem with a retry hint
        with pytest.raises(ProblemError) as ei:
            async for _ in worker.completion_stream(model, "b",
                                                    {"max_tokens": 2}):
                pass
        first.cancel()
        try:
            await first
        except (asyncio.CancelledError, StopAsyncIteration):
            pass
        return ei.value.problem

    problem = asyncio.run(go())
    assert problem.status == 429
    assert problem.code == "scheduler_saturated"
    assert problem.extensions.get("retry_after_s", 0) > 0


def test_problem_response_carries_retry_after_header():
    from cyberfabric_core_tpu.gateway.middleware import _problem_response
    from cyberfabric_core_tpu.modkit.errcat import ERR

    resp = _problem_response(
        ERR.llm.scheduler_saturated.problem("queue full", retry_after_s=2.0))
    assert resp.status == 429
    assert resp.headers["Retry-After"] == "2"
    # non-429 problems carry no Retry-After
    resp = _problem_response(ERR.core.not_found.problem("nope"))
    assert "Retry-After" not in resp.headers


# --------------------------------------- satellite: failover metric exported


def test_failover_increments_prometheus_counter():
    """_failover (unit-level: stub replicas) bumps
    llm_replica_failovers_total and the pool's host-side counters."""
    from cyberfabric_core_tpu.modkit.metrics import default_registry
    from cyberfabric_core_tpu.runtime.engine import SamplingParams
    from cyberfabric_core_tpu.runtime.replicas import (DataParallelServingPool,
                                                       _Tracked)

    class _StubReplica:
        def __init__(self):
            self.submitted = []

        def stats(self):
            return {"broken": None, "active": 0, "pending": 0}

        def submit(self, prompt_ids, sampling, emit, request_id=None,
                   trace=None):
            self.submitted.append(list(prompt_ids))
            return "rid"

    pool = DataParallelServingPool.__new__(DataParallelServingPool)
    import threading

    pool._lock = threading.Lock()
    pool._requests = {}
    pool.max_retries = 1
    pool.failovers = 0
    pool.failovers_failed = 0
    pool.replicas = [_StubReplica(), _StubReplica()]

    counter = default_registry.counter("llm_replica_failovers_total")
    before = sum(counter._values.values())
    tracked = _Tracked([1, 2, 3], SamplingParams(max_tokens=8),
                       lambda ev: None, [5, 6], replica=0, retries_left=1)
    assert pool._failover("rid", tracked)
    assert pool.failovers == 1
    assert sum(counter._values.values()) == before + 1
    # the continuation carried prompt + already-emitted tokens
    resubmitted = (pool.replicas[0].submitted + pool.replicas[1].submitted)[0]
    assert resubmitted == [1, 2, 3, 5, 6]


def test_pool_stats_surface_failover_counters():
    from cyberfabric_core_tpu.runtime.replicas import DataParallelServingPool

    pool = DataParallelServingPool.__new__(DataParallelServingPool)
    pool.failovers = 3
    pool.failovers_failed = 1
    pool.replicas = []
    pool._requests = {}
    stats = pool.stats()
    assert stats["failovers"] == 3 and stats["failovers_failed"] == 1
