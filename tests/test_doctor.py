"""fabric-doctor: SLO burn-rate engine, stall watchdogs, degradation state
machine, and the health surfaces they feed (/healthz, /readyz,
/v1/monitoring/slo, llm.load_shed admission).

The full acceptance cycle (readyz 200→503→200 over a live faulted server)
lives in the faultlab scenario `slo-burn-shed-recover`; these tests pin the
engine's math and the per-layer contracts.
"""

from __future__ import annotations

import asyncio
import time

import pytest

from cyberfabric_core_tpu.modkit.doctor import (DEFAULT_OBJECTIVES, Doctor,
                                                DoctorConfig, default_doctor,
                                                shed_retry_after)
from cyberfabric_core_tpu.modkit.errors import ProblemError
from cyberfabric_core_tpu.modkit.flight_recorder import FlightRecorder


@pytest.fixture(autouse=True)
def _clean_default_doctor():
    """Tests that poison the process-global doctor must hand the next test
    (and the gateway fixtures elsewhere) a healthy one."""
    yield
    default_doctor.stop()  # a later monitoring boot restarts the thread
    default_doctor.configure(DoctorConfig())


def _doctor(**overrides) -> tuple[Doctor, FlightRecorder]:
    cfg = DoctorConfig(**{"min_samples": 2, "fast_window_s": 5.0,
                          "slow_window_s": 10.0, "shed_after": 2,
                          "recover_after": 2, **overrides})
    rec = FlightRecorder()
    doctor = Doctor(cfg, recorder=rec)
    rec.add_listener(doctor.on_record)
    return doctor, rec


def _finish_request(rec: FlightRecorder, rid: str, itl_gap_s: float = 0.0,
                    error: bool = False) -> None:
    rec.record(rid, "enqueued", prompt_tokens=4)
    if error:
        rec.record(rid, "error", detail="boom")
        return
    rec.record(rid, "admitted", queue_wait_ms=1.0)
    rec.record(rid, "prefill", slot=0, dur_ms=1.0)
    rec.record(rid, "decode_chunk", slot=0, tokens=8)
    if itl_gap_s:
        time.sleep(itl_gap_s)
    rec.record(rid, "decode_chunk", slot=0, tokens=8)
    rec.record(rid, "finished", reason="stop", tokens=17)


# --------------------------------------------------------------- config


def test_config_rejects_unknown_fields():
    with pytest.raises(ValueError, match="unknown fields"):
        DoctorConfig.from_config({"evle_interval_s": 1.0})


def test_config_objective_overrides_and_per_model():
    cfg = DoctorConfig.from_config({
        "objectives": {"itl_p99": {"threshold_ms": 25.0}},
        "per_model": {"local::tiny": {"ttft_p95": {"threshold_ms": 100.0}}},
    })
    objs = {o.name: o for o in cfg.build_objectives()}
    assert set(DEFAULT_OBJECTIVES) <= set(objs)
    assert objs["itl_p99"].threshold_ms == 25.0
    assert objs["ttft_p95[local::tiny]"].model == "local::tiny"
    assert objs["ttft_p95[local::tiny]"].threshold_ms == 100.0
    assert objs["ttft_p95"].threshold_ms == 2000.0  # global untouched


def test_config_rejects_bad_objective():
    with pytest.raises(ValueError, match="budget"):
        DoctorConfig(objectives={"error_rate": {"budget": 0.0}}) \
            .build_objectives()
    with pytest.raises(ValueError, match="unknown objective"):
        DoctorConfig(per_model={"m": {"nope": {}}}).build_objectives()
    # typo'd keys INSIDE a spec get the deny-unknown-fields treatment too,
    # not a bare TypeError at boot
    with pytest.raises(ValueError, match=r"objectives\['ttft_p95'\].*threshold"):
        DoctorConfig(objectives={"ttft_p95": {"threshold": 100.0}}) \
            .build_objectives()
    with pytest.raises(ValueError, match=r"per_model\['m'\]\['itl_p99'\]"):
        DoctorConfig(per_model={"m": {"itl_p99": {"thresh": 1.0}}}) \
            .build_objectives()


# --------------------------------------------------------------- capacity


def _capacity(replicas, serving, healthy=None, benched=0):
    return {"replicas": replicas, "serving": serving,
            "healthy": serving if healthy is None else healthy,
            "benched": benched}


def test_capacity_zero_serving_is_a_degradation_reason():
    doctor, _rec = _doctor()
    doctor.set_capacity_provider(lambda: _capacity(2, 0))
    report = doctor.evaluate()
    assert "capacity:no_serving_replicas" in report["reasons"]
    assert report["state"] == "degraded"
    assert report["capacity"]["capacity_frac"] == 0.0
    # capacity restored → the machine walks home
    doctor.set_capacity_provider(lambda: _capacity(2, 2))
    for _ in range(3):
        report = doctor.evaluate()
    assert report["state"] == "healthy"
    assert report["capacity"]["effective_shed_after"] == 2


def test_capacity_scales_shedding_hysteresis():
    """At half capacity the survivors carry the dead replicas' load: the
    same burn escalates to shedding after proportionally fewer bad
    evaluations (shed_after 4 → 2 at 2/4 replicas)."""
    doctor, rec = _doctor(shed_after=4)
    doctor.set_capacity_provider(lambda: _capacity(4, 2))
    for i in range(6):
        _finish_request(rec, f"err-{i}", error=True)
    report = doctor.evaluate()
    assert report["capacity"]["effective_shed_after"] == 2
    assert report["state"] == "degraded"
    doctor.evaluate()
    report = doctor.evaluate()  # 2 bad evals IN degraded suffice at half cap
    assert report["state"] == "shedding"
    # full capacity would still be degraded after the same walk
    doctor2, rec2 = _doctor(shed_after=4)
    doctor2.set_capacity_provider(lambda: _capacity(4, 4))
    for i in range(6):
        _finish_request(rec2, f"err-{i}", error=True)
    for _ in range(3):
        report2 = doctor2.evaluate()
    assert report2["state"] == "degraded"
    assert report2["capacity"]["effective_shed_after"] == 4


def test_capacity_provider_is_optional_and_hostile_safe():
    doctor, _rec = _doctor()
    report = doctor.evaluate()
    assert report["capacity"] is None  # no provider wired
    doctor.set_capacity_provider(lambda: (_ for _ in ()).throw(RuntimeError))
    report = doctor.evaluate()  # a hostile provider cannot kill the pass
    assert report["state"] == "healthy" and report["capacity"] is None
    doctor.set_capacity_provider(lambda: "not-a-dict")
    assert doctor.evaluate()["capacity"] is None


def test_capacity_feeds_replica_gauges():
    from cyberfabric_core_tpu.modkit.metrics import default_registry

    doctor, _rec = _doctor()
    doctor.set_capacity_provider(lambda: _capacity(3, 2, healthy=2,
                                                   benched=1))
    doctor.evaluate()
    text = default_registry.render()
    assert "llm_replicas_healthy 2" in text
    assert "llm_replicas_benched 1" in text


# --------------------------------------------------------------- slo engine


def test_insufficient_samples_read_ok():
    doctor, rec = _doctor(min_samples=5)
    _finish_request(rec, "r1", error=True)  # 1 < min_samples
    report = doctor.evaluate()
    assert all(row["verdict"] == "ok" for row in report["objectives"])
    assert report["state"] == "healthy"


def test_error_burn_goes_critical_and_feeds_reasons():
    doctor, rec = _doctor()
    for i in range(4):
        _finish_request(rec, f"e{i}", error=True)
    report = doctor.evaluate()
    row = {r["name"]: r for r in report["objectives"]}["error_rate"]
    # 100% errors against a 1% budget: burn 100 on both windows
    assert row["verdict"] == "critical" and row["burn_fast"] > 50
    assert "slo:error_rate" in report["reasons"]


def test_slow_window_only_burn_is_warning_not_critical():
    doctor, rec = _doctor(fast_window_s=0.2, slow_window_s=30.0)
    for i in range(4):
        _finish_request(rec, f"e{i}", error=True)
    time.sleep(0.3)  # bad samples age out of the FAST window only
    report = doctor.evaluate()
    row = {r["name"]: r for r in report["objectives"]}["error_rate"]
    assert row["burn_slow"] > 50 and row["samples_fast"] < 2
    assert row["verdict"] == "warning"  # one window is not an emergency
    assert report["state"] == "healthy"  # warnings do not degrade


def test_per_model_objective_sees_only_its_model():
    doctor, rec = _doctor(per_model={
        "m-a": {"error_rate": {"budget": 0.5}}})
    for i in range(3):
        rec.record(f"a{i}", "enqueued")
        rec.annotate(f"a{i}", model="m-a")
        rec.record(f"a{i}", "error")
    for i in range(3):
        rec.record(f"b{i}", "enqueued")
        rec.annotate(f"b{i}", model="m-b")
        _finish_request(rec, f"b{i}-fin")
    report = doctor.evaluate()
    rows = {r["name"]: r for r in report["objectives"]}
    assert rows["error_rate[m-a]"]["samples_fast"] == 3
    assert rows["error_rate[m-a]"]["burn_fast"] == pytest.approx(2.0)


# ------------------------------------------------------------ state machine


def test_full_cycle_healthy_degraded_shedding_recovering_healthy():
    doctor, rec = _doctor(fast_window_s=0.3, slow_window_s=0.5)
    for i in range(4):
        _finish_request(rec, f"e{i}", error=True)
    for _ in range(4):
        doctor.evaluate()
    assert doctor.state == "shedding"
    assert doctor.shed_retry_after() == doctor.config.shed_retry_after_s
    ready, state, reasons = doctor.readiness()
    assert not ready and state == "shedding" and reasons
    time.sleep(0.6)  # both windows drain
    for _ in range(5):
        doctor.evaluate()
    assert doctor.state_sequence() == [
        "healthy", "degraded", "shedding", "recovering", "healthy"]
    assert doctor.readiness()[0] and doctor.shed_retry_after() is None


def test_single_bad_eval_does_not_shed_and_recovering_falls_back():
    doctor, rec = _doctor(fast_window_s=0.25, slow_window_s=0.25,
                          shed_after=3)
    for i in range(3):
        _finish_request(rec, f"e{i}", error=True)
    doctor.evaluate()
    assert doctor.state == "degraded"  # one bad eval never sheds
    time.sleep(0.3)
    doctor.evaluate()
    doctor.evaluate()
    doctor.evaluate()
    assert doctor.state == "healthy"  # hysteresis satisfied, recovered
    # drive to shedding, then a bad eval during recovering falls back
    for i in range(3):
        _finish_request(rec, f"f{i}", error=True)
    for _ in range(4):
        doctor.evaluate()
    assert doctor.state == "shedding"
    time.sleep(0.3)
    doctor.evaluate()
    doctor.evaluate()
    assert doctor.state == "recovering"
    for i in range(3):
        _finish_request(rec, f"g{i}", error=True)
    doctor.evaluate()
    assert doctor.state == "degraded"


# ---------------------------------------------------------------- watchdogs


class _FakeSched:
    def __init__(self, round_age=0.0, pending=0, active=0, oldest=None):
        self._beat = {"last_round_age_s": round_age, "round_p95_ms": 1.0,
                      "rounds": 5, "active": active, "pending": pending,
                      "suspended": 0}
        self._oldest = oldest

    def heartbeat(self):
        return dict(self._beat)

    def pending_depth(self):
        return self._beat["pending"]

    def pending_oldest_age_s(self):
        return self._oldest


def test_scheduler_round_watchdog_requires_pending_work():
    doctor, _rec = _doctor(round_stall_floor_s=0.1, round_stall_mult=1.0)
    doctor.set_scheduler_provider(lambda: [("m", _FakeSched(round_age=5.0))])
    report = doctor.evaluate()
    assert not report["watchdog_trips"]  # idle engine: stale rounds are fine
    doctor.set_scheduler_provider(
        lambda: [("m", _FakeSched(round_age=5.0, active=2))])
    report = doctor.evaluate()
    assert report["watchdog_trips"].get("scheduler_round") == 1
    assert "watchdog:scheduler_round" in report["reasons"]


def test_scheduler_round_watchdog_trips_on_wedged_first_round():
    """rounds == 0 is not exempt: a device wedged inside its first-ever
    prefill never completes a round, so the age since construction must trip
    at the floor — the boot-time wedge is exactly this watchdog's case."""
    doctor, _rec = _doctor(round_stall_floor_s=0.1, round_stall_mult=1.0)
    sched = _FakeSched(round_age=5.0, active=1)
    sched._beat["rounds"] = 0
    sched._beat["round_p95_ms"] = 0.0  # no round ever finished
    doctor.set_scheduler_provider(lambda: [("m", sched)])
    report = doctor.evaluate()
    assert report["watchdog_trips"].get("scheduler_round") == 1


def test_evaluate_survives_hostile_heartbeat():
    """schedulers() is a public SDK contract: a heartbeat() that returns a
    non-dict must not raise out of evaluate() (it would kill the eval
    thread and freeze the state machine at its last state)."""
    doctor, _rec = _doctor(round_stall_floor_s=0.1)

    class Hostile:
        def heartbeat(self):
            return ["not", "a", "dict"]

        def pending_depth(self):
            return 0

        def pending_oldest_age_s(self):
            return None

    doctor.set_scheduler_provider(lambda: [("m", Hostile())])
    report = doctor.evaluate()
    assert not report["watchdog_trips"]


def test_eval_loop_survives_raising_evaluate(monkeypatch):
    """The backstop for evaluator bugs the contract checks miss: one
    exception from evaluate() must not terminate the doctor thread —
    nothing restarts it, and a frozen `shedding` would 503 forever."""
    doctor, _rec = _doctor(eval_interval_s=0.01)
    calls: list[int] = []

    def boom(now=None):
        calls.append(1)
        raise RuntimeError("hostile evaluator")

    monkeypatch.setattr(doctor, "evaluate", boom)
    doctor.ensure_started()
    try:
        deadline = time.time() + 5.0
        while len(calls) < 3 and time.time() < deadline:
            time.sleep(0.02)
        assert len(calls) >= 3  # kept ticking after the raises
        assert doctor._thread is not None and doctor._thread.is_alive()
    finally:
        doctor.stop()


def test_submit_after_idle_gap_restarts_round_stall_clock():
    """last_round_at is only refreshed by completed rounds, so after an idle
    gap the scheduler_round watchdog would read the whole gap as stall age
    and trip on the first request of the day. submit() on an idle engine
    must restart the clock: age measures time-with-work-but-no-round."""
    from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    cfg = EngineConfig(model="tiny-llama", max_seq_len=64, max_batch=2,
                       decode_chunk=4)
    eng = ContinuousBatchingEngine(cfg, seed=0)
    try:
        eng.last_round_at -= 300.0  # fake a long idle gap
        eng.submit([5, 6, 7], SamplingParams(max_tokens=4), lambda ev: None)
        assert eng.heartbeat()["last_round_age_s"] < 60.0
    finally:
        eng.shutdown()


def test_queue_age_watchdog_and_gauges():
    from cyberfabric_core_tpu.modkit.metrics import default_registry

    doctor, _rec = _doctor(queue_deadline_s=0.5)
    doctor.set_scheduler_provider(
        lambda: [("m", _FakeSched(pending=3, oldest=2.0))])
    report = doctor.evaluate()
    assert report["watchdog_trips"].get("queue_age") == 1
    rendered = default_registry.render()
    assert 'llm_queue_depth{model="m"} 3.0' in rendered
    assert 'llm_queue_oldest_age_seconds{model="m"} 2.0' in rendered


def test_stream_stall_marks_record_and_clears_on_progress():
    doctor, rec = _doctor(stream_stall_s=0.05, watchdog_cooldown_s=0.01)
    rec.record("slow", "enqueued")
    rec.record("slow", "prefill", slot=0)
    time.sleep(0.08)
    doctor.evaluate()
    rows = rec.inflight(stalled_only=True)
    assert [r["request_id"] for r in rows] == ["slow"]
    assert rows[0]["phase"] == "stalled" and rows[0]["stalled"]
    assert rows[0]["last_event_age_s"] >= 0.0 and "age_s" in rows[0]
    # a decode chunk proves the stream moved: the mark clears
    rec.record("slow", "decode_chunk", slot=0, tokens=8)
    assert rec.inflight(stalled_only=True) == []
    assert rec.inflight()[0]["stalled"] is False


def test_watchdog_cooldown_limits_repeat_trips():
    doctor, _rec = _doctor(queue_deadline_s=0.1, watchdog_cooldown_s=60.0)
    doctor.set_scheduler_provider(
        lambda: [("m", _FakeSched(pending=1, oldest=2.0))])
    doctor.evaluate()
    doctor.evaluate()
    doctor.evaluate()
    assert doctor.report()["watchdog_trips"]["queue_age"] == 1


def test_persistent_watchdog_condition_outlasts_cooldown():
    """A wedged queue must keep the evaluation bad on EVERY pass even while
    the trip emissions sit inside their cooldown — otherwise the state
    machine reads cooldown silence as recovery and flaps healthy around a
    live stall (and shedding is unreachable via watchdogs)."""
    doctor, _rec = _doctor(queue_deadline_s=0.1, watchdog_cooldown_s=60.0,
                           shed_after=3)
    doctor.set_scheduler_provider(
        lambda: [("m", _FakeSched(pending=1, oldest=2.0))])
    for _ in range(4):
        report = doctor.evaluate()
        assert "watchdog:queue_age" in report["reasons"]
    # the counter/log emission is rate-limited; the verdict is not
    assert doctor.report()["watchdog_trips"]["queue_age"] == 1
    assert doctor.state == "shedding"


def test_persistent_stream_stall_keeps_evaluations_bad():
    """The trip's own ``stalled`` event resets the record's phase and
    last_event_at; the watchdog must still read the unprogressed stream as
    an active condition, or a wedged stream would 'recover' after one
    trip."""
    doctor, rec = _doctor(stream_stall_s=0.05, watchdog_cooldown_s=0.01)
    rec.record("wedge", "enqueued")
    rec.record("wedge", "decode_chunk", slot=0, tokens=1)
    time.sleep(0.08)
    for _ in range(3):
        report = doctor.evaluate()
        assert "watchdog:stream_stall" in report["reasons"]
    assert doctor.state != "healthy"
    # a preemption is legitimate backpressure, not an active stall: the
    # triage mark stays but the condition releases the state machine
    rec.record("wedge", "preempted", slot=0)
    report = doctor.evaluate()
    assert "watchdog:stream_stall" not in report["reasons"]
    assert rec.inflight(stalled_only=True)  # mark kept for ?stalled=true
    # progress (resume + chunk) clears the mark — and with it the condition
    rec.record("wedge", "resumed", slot=0)
    rec.record("wedge", "decode_chunk", slot=0, tokens=1)
    report = doctor.evaluate()
    assert "watchdog:stream_stall" not in report["reasons"]
    assert rec.inflight(stalled_only=True) == []


def test_stop_then_ensure_started_always_leaves_an_evaluator():
    """stop() immediately followed by ensure_started() (the faultlab
    teardown → next-monitoring-boot sequence) must always leave a live
    evaluation thread, whether the dying thread won or lost the race to
    observe the stop event."""
    doctor, _rec = _doctor(eval_interval_s=0.01)
    for _ in range(10):
        doctor.ensure_started()
        doctor.stop()
        doctor.ensure_started()  # immediate restart: the racy window
    before = doctor.report()["evals"]
    deadline = time.monotonic() + 2.0
    while doctor.report()["evals"] <= before and time.monotonic() < deadline:
        time.sleep(0.02)
    assert doctor.report()["evals"] > before
    doctor.stop()


def test_real_scheduler_heartbeat_surface():
    from cyberfabric_core_tpu.runtime.engine import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import \
        ContinuousBatchingEngine

    engine = ContinuousBatchingEngine(EngineConfig(
        model="tiny-llama", max_seq_len=64, max_batch=2, decode_chunk=4,
        prefix_cache_pages=64, prefix_page_size=16))
    try:
        beat = engine.heartbeat()
        assert {"last_round_age_s", "round_p95_ms", "rounds", "active",
                "pending", "suspended", "oldest_pending_age_s",
                "broken"} <= set(beat)
        assert engine.pending_depth() == 0
        assert engine.pending_oldest_age_s() is None
    finally:
        engine.shutdown()


# ---------------------------------------------------------- admission shed


def test_llm_gateway_sheds_pre_enqueue_while_shedding():
    from cyberfabric_core_tpu.modules.llm_gateway.module import \
        LlmGatewayModule

    doctor, rec = _doctor(shed_after=1, shed_retry_after_s=7.0)
    for i in range(3):
        rec.record(f"shed{i}", "enqueued")
        rec.record(f"shed{i}", "error")
    doctor.evaluate()
    doctor.evaluate()
    assert doctor.state == "shedding"
    assert doctor.shed_retry_after() == 7.0
    module = LlmGatewayModule()
    # a module whose stack never booted monitoring has no doctor: open
    module._check_load_shed()  # no raise
    module._doctor = doctor  # hub resolution, short-circuited
    with pytest.raises(ProblemError) as exc:
        module._check_load_shed()
    problem = exc.value.problem
    assert problem.status == 429 and problem.code == "load_shed"
    assert problem.extensions["retry_after_s"] == 7.0
    # recovery reopens admission
    doctor.configure(DoctorConfig())
    module._check_load_shed()  # no raise


def test_default_doctor_shed_helper():
    rec = default_doctor._recorder
    default_doctor.configure(DoctorConfig(
        min_samples=2, shed_after=1, shed_retry_after_s=7.0))
    default_doctor.attach_recorder()  # normally done by ensure_started()
    for i in range(3):
        rec.record(f"shedh{i}", "enqueued")
        rec.record(f"shedh{i}", "error")
    default_doctor.evaluate()
    default_doctor.evaluate()
    assert default_doctor.state == "shedding"
    assert shed_retry_after() == 7.0
    default_doctor.configure(DoctorConfig())
    assert shed_retry_after() is None


# ------------------------------------------------------------ REST surfaces


def test_health_surfaces_over_rest():
    """Boot gateway+monitoring; /healthz (liveness JSON), /readyz flipping
    with the global doctor's state, /v1/monitoring/slo document, and the
    ?stalled=true filter on the live request table."""
    import aiohttp

    from cyberfabric_core_tpu.apps.faultlab.runner import (_boot_stack,
                                                           _stop_stack)
    from cyberfabric_core_tpu.modkit.flight_recorder import default_recorder

    # the process's recorder: a file this worker ran before may have left
    # requests in flight, and the table below is held to be empty
    default_recorder.reset()

    async def go():
        rt, base = await _boot_stack(
            ["monitoring"],
            {"monitoring": {"config": {"doctor": {
                "min_samples": 2, "shed_after": 1,
                "eval_interval_s": 30.0}}}})  # evals driven by hand below
        out = {}
        try:
            async with aiohttp.ClientSession() as s:
                async def get(path):
                    async with s.get(f"{base}{path}") as r:
                        return r.status, await r.json()

                out["healthz"] = await get("/healthz")
                out["readyz_healthy"] = await get("/readyz")
                out["slo"] = await get("/v1/monitoring/slo")
                # force shedding on the global doctor, re-probe
                for i in range(3):
                    default_recorder.record(f"rest{i}", "enqueued")
                    default_recorder.record(f"rest{i}", "error")
                default_doctor.evaluate()
                default_doctor.evaluate()
                out["readyz_shedding"] = await get("/readyz")
                out["requests_stalled"] = await get(
                    "/v1/monitoring/requests?stalled=true")
                out["requests_bad_param"] = await get(
                    "/v1/monitoring/requests?stalled=banana")
        finally:
            await _stop_stack(rt)
        return out

    out = asyncio.run(go())
    status, doc = out["healthz"]
    assert status == 200 and doc["status"] == "ok" and "uptime_s" in doc
    status, doc = out["readyz_healthy"]
    assert status == 200 and doc["state"] == "healthy"
    status, doc = out["slo"]
    assert status == 200 and doc["state"] == "healthy"
    assert {"state_history", "watchdog_trips", "config"} <= set(doc)
    status, doc = out["readyz_shedding"]
    assert status == 503 and doc["code"] == "not_ready"
    assert doc["state"] == "shedding" and "slo:error_rate" in doc["reasons"]
    status, doc = out["requests_stalled"]
    assert status == 200 and doc["in_flight"] == []
    status, doc = out["requests_bad_param"]
    assert status == 400
    # monitoring.stop() tore the doctor down with the stack: neither the
    # provider closure over the dead worker pool nor the recorder listener
    # may leak into the next boot / keep taxing the serving path
    assert default_doctor._scheduler_provider is None
    assert not default_doctor._listener_attached


def test_doctor_cli_probe(tmp_path):
    """The apps/doctor probe against a live stack: exit codes follow the
    state (0 ready, 1 shedding), and the document carries all three legs."""
    import aiohttp  # noqa: F401 — _boot_stack needs the event loop anyway

    from cyberfabric_core_tpu.apps.doctor.__main__ import probe
    from cyberfabric_core_tpu.apps.faultlab.runner import (_boot_stack,
                                                           _stop_stack)
    from cyberfabric_core_tpu.modkit.flight_recorder import default_recorder

    async def go():
        rt, base = await _boot_stack(
            ["monitoring"],
            {"monitoring": {"config": {"doctor": {
                "min_samples": 2, "shed_after": 1,
                "eval_interval_s": 30.0}}}})
        try:
            loop = asyncio.get_running_loop()
            code_ok, doc_ok = await loop.run_in_executor(
                None, probe, base, None)
            for i in range(3):
                default_recorder.record(f"cli{i}", "enqueued")
                default_recorder.record(f"cli{i}", "error")
            default_doctor.evaluate()
            default_doctor.evaluate()
            code_shed, doc_shed = await loop.run_in_executor(
                None, probe, base, None)
        finally:
            await _stop_stack(rt)
        return code_ok, doc_ok, code_shed, doc_shed

    code_ok, doc_ok, code_shed, doc_shed = asyncio.run(go())
    assert code_ok == 0 and doc_ok["readiness"]["state"] == "healthy"
    assert doc_ok["slo"]["state"] == "healthy"  # auth-disabled stack
    assert code_shed == 1 and doc_shed["readiness"]["http_status"] == 503
    assert doc_ok["liveness"]["http_status"] == 200
    assert doc_ok["liveness"]["status"] == "ok"  # body status not masked
    # unreachable server → exit 2
    code_dead, doc_dead = probe("http://127.0.0.1:9", None)
    assert code_dead == 2 and doc_dead["liveness"]["http_status"] is None
