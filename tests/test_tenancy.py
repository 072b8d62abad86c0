"""Tenant isolation under overload — weighted fair scheduling, per-tenant
quotas, and selective shedding.

Three layers under test:

- ``TenantFairQueue`` (pure unit): per-tenant FIFO, weighted VTC pop order,
  the new-backlog lift, tenant-blind degradation, remove_if/drain_all.
- ``ContinuousBatchingEngine`` with tenancy armed: fair admission under a
  flood, per-tenant caps (slots / pending 429 / hard page quota / soft-quota
  yield), single-tenant stream bit-identity (tenant_fair on vs off), the
  ``stats()["queue"]``/``stats()["tenants"]`` surfaces, and the drain-rate
  derived Retry-After.
- Doctor selective shedding (fake scheduler provider) and the UsageTracker
  budget hook wired to the scheduler-side live accounting.
"""

from __future__ import annotations

import threading
import time

import pytest

from cyberfabric_core_tpu.runtime.engine import (EngineConfig, SamplingParams,
                                                 SchedulerSaturated,
                                                 TenantQuotaExceeded,
                                                 TenantSaturated)
from cyberfabric_core_tpu.runtime.scheduler import (ContinuousBatchingEngine,
                                                    TenantFairQueue, _Pending)

TINY = dict(model="tiny-llama", max_seq_len=64, max_batch=2, decode_chunk=4,
            prefix_cache_pages=64, prefix_page_size=16)


def _req(rid: str, tenant: str = "default", enq: float = 0.0) -> _Pending:
    req = _Pending(rid, [1, 2, 3], SamplingParams(max_tokens=4),
                   emit=lambda ev: None, tenant=tenant)
    req.enqueued_at = enq or time.monotonic()
    return req


# ------------------------------------------------------------ fair queue


def test_fair_queue_fifo_within_tenant():
    q = TenantFairQueue()
    for i in range(4):
        q.put(_req(f"a{i}", "a", enq=float(i)))
    assert [q.pop_fair().request_id for _ in range(4)] == \
        ["a0", "a1", "a2", "a3"]
    assert q.empty()


def test_fair_queue_weighted_pop_tracks_charges():
    """With tenant A charged heavily, a backlogged tenant B wins the pop
    until its weighted counter catches up — and a 2x weight entitles a
    tenant to 2x the tokens before losing priority."""
    q = TenantFairQueue()
    for i in range(3):
        q.put(_req(f"a{i}", "a", enq=1.0 + i))
        q.put(_req(f"b{i}", "b", enq=1.0 + i))
    # equal counters: tie breaks on head arrival order then tenant id
    first = q.pop_fair()
    assert first.request_id == "a0"
    q.charge("a", 100, weight=1.0)
    assert q.pop_fair().request_id == "b0"
    q.charge("b", 40, weight=2.0)  # weighted: 40/2 = 20 < 100
    assert q.pop_fair().request_id == "b1"
    q.charge("b", 200, weight=2.0)  # now b at 120 > a's 100
    assert q.pop_fair().request_id == "a1"


def test_fair_queue_new_backlog_lift():
    """A tenant that sat idle while others consumed cannot bank credit:
    its counter lifts to the backlogged minimum when it re-enters."""
    q = TenantFairQueue()
    q.put(_req("a0", "a", enq=1.0))
    q.charge("a", 500, weight=1.0)
    # b arrives fresh (counter 0) — lifted to min over backlogged = a's 500
    q.put(_req("b0", "b", enq=2.0))
    assert q.vtc_snapshot()["b"] == pytest.approx(500.0)
    # FIFO tie-break: a0 enqueued first
    assert q.pop_fair().request_id == "a0"


def test_fair_queue_blocked_tenants_are_skipped():
    q = TenantFairQueue()
    q.put(_req("a0", "a", enq=1.0))
    q.put(_req("b0", "b", enq=2.0))
    assert q.pop_fair(blocked={"a"}).request_id == "b0"
    assert q.pop_fair(blocked={"a"}) is None  # only a's work remains
    assert q.pop_fair().request_id == "a0"


def test_fair_queue_tenant_blind_mode_is_one_fifo():
    q = TenantFairQueue(fair=False)
    q.put(_req("a0", "a", enq=1.0))
    q.put(_req("b0", "b", enq=2.0))
    q.put(_req("a1", "a", enq=3.0))
    q.charge("b", 10 ** 6, weight=1.0)  # charges all land on one key
    assert [q.pop_fair().request_id for _ in range(3)] == ["a0", "b0", "a1"]
    assert list(q.depths()) == []  # drained
    assert q.charged_snapshot() == {"default": 10 ** 6}


def test_fair_queue_remove_if_preserves_survivor_order():
    q = TenantFairQueue()
    for i in range(4):
        q.put(_req(f"a{i}", "a", enq=float(i)))
    removed = q.remove_if(lambda r: r.request_id in ("a1", "a3"))
    assert sorted(r.request_id for r in removed) == ["a1", "a3"]
    assert q.qsize() == 2
    assert [q.pop_fair().request_id for _ in range(2)] == ["a0", "a2"]


def test_fair_queue_put_front_and_drain_all():
    q = TenantFairQueue()
    q.put(_req("a1", "a", enq=2.0))
    q.put_front(_req("a0", "a", enq=1.0))
    assert q.oldest_age() is not None
    drained = q.drain_all()
    assert [r.request_id for r in drained] == ["a0", "a1"]
    assert q.empty() and q.oldest_age() is None


# ----------------------------------------------------------- engine level


def _drive(engine, loads, done_timeout=120.0):
    """Submit (rid, tenant, prompt, max_tokens) tuples; returns
    {rid: [tokens...]}, waits for every terminal."""
    streams: dict[str, list[int]] = {}
    done = threading.Event()
    lock = threading.Lock()
    remaining = [len(loads)]

    def mk_emit(rid):
        streams[rid] = []

        def emit(ev):
            with lock:
                if ev.token_id >= 0:
                    streams[rid].append(ev.token_id)
                if ev.finished:
                    remaining[0] -= 1
                    if remaining[0] == 0:
                        done.set()
        return emit

    for rid, tenant, prompt, max_tokens in loads:
        engine.submit(prompt, SamplingParams(max_tokens=max_tokens),
                      mk_emit(rid), request_id=rid, tenant=tenant)
    assert done.wait(done_timeout), "streams never drained"
    return streams


def test_single_tenant_streams_identical_fair_vs_blind():
    """The single-tenant overhead/compat contract: with one (default)
    tenant, tenant-fair scheduling admits in exactly the FIFO order and
    every stream is bit-identical to the tenant-blind scheduler."""
    loads = [(f"r{i}", "default", [7 + i, 11, 13 + i, 17], 6)
             for i in range(6)]
    fair = ContinuousBatchingEngine(EngineConfig(**TINY), seed=0)
    a = _drive(fair, loads)
    fair.shutdown()
    blind = ContinuousBatchingEngine(
        EngineConfig(**TINY, tenant_fair=False), seed=0)
    b = _drive(blind, loads)
    blind.shutdown()
    assert a == b


def test_fair_admission_under_flood_and_stats_surfaces():
    """Heavy floods 12, light sends 2 behind them: both light requests
    admit while heavy backlog remains, charges land per tenant, and the
    stats surfaces expose the ledger."""
    from cyberfabric_core_tpu.modkit.flight_recorder import default_recorder

    default_recorder.reset()
    engine = ContinuousBatchingEngine(EngineConfig(**TINY), seed=0)
    loads = [(f"h{i}", "heavy", [5 + i, 9, 12, 19], 6) for i in range(12)]
    loads += [(f"l{j}", "light", [6 + j, 8, 21], 6) for j in range(2)]
    _drive(engine, loads)
    stats = engine.stats()
    tenants = stats["tenants"]
    assert set(tenants) >= {"heavy", "light"}
    assert tenants["heavy"]["charged_tokens"] > \
        tenants["light"]["charged_tokens"] > 0
    assert stats["queue"]["pending"] == 0
    assert "drain_rate_per_s" in stats["queue"]
    # admission order: each light request admitted before the heavy
    # backlog fully drained (tenant-blind FIFO admits all 12 heavy first)
    admitted_at = {}
    for rid, *_ in loads:
        rec = default_recorder.lookup(rid) or {}
        for ev in rec.get("timeline", ()):
            if ev.get("event") == "admitted":
                admitted_at[rid] = ev["ts"]
                assert ev.get("tenant") in ("heavy", "light")
    for j in range(2):
        before = sum(1 for i in range(12)
                     if admitted_at.get(f"h{i}", 0) < admitted_at[f"l{j}"])
        assert before <= 8, f"l{j} admitted after {before} heavy requests"
    engine.shutdown()


def test_tenant_max_pending_raises_tenant_saturated():
    cfg = EngineConfig(**TINY, tenant_max_pending=2, max_pending=100)
    engine = ContinuousBatchingEngine(cfg, seed=0)
    # park the engine so the queue actually builds: never start the thread
    engine.start = lambda: None  # type: ignore[method-assign]
    ok = 0
    with pytest.raises(TenantSaturated) as exc:
        for i in range(5):
            engine.submit([3, 4, 5], SamplingParams(max_tokens=2),
                          lambda ev: None, request_id=f"t{i}", tenant="spam")
            ok += 1
    assert ok == 2
    assert exc.value.tenant == "spam"
    assert exc.value.retry_after_s >= 1.0
    # the SchedulerSaturated contract still holds (worker catch order)
    assert isinstance(exc.value, SchedulerSaturated)
    # other tenants keep admitting — the whole point
    engine.submit([3, 4, 5], SamplingParams(max_tokens=2), lambda ev: None,
                  request_id="other", tenant="polite")
    assert engine.stats()["queue"]["per_tenant"] == {"spam": 2, "polite": 1}
    assert engine.tenant_snapshot()["spam"]["rejections"]["pending"] >= 1
    engine._fail_all_inflight("test teardown")


def test_tenant_hard_page_quota_rejects_at_submit():
    cfg = EngineConfig(**TINY, tenant_max_pages=2)  # 2 pages = 32 tokens
    engine = ContinuousBatchingEngine(cfg, seed=0)
    with pytest.raises(TenantQuotaExceeded) as exc:
        engine.submit(list(range(3, 40)), SamplingParams(max_tokens=20),
                      lambda ev: None, tenant="greedy")
    assert exc.value.tenant == "greedy"
    # a quota-fitting request is accepted
    streams = _drive(engine, [("ok", "greedy", [3, 4, 5], 4)])
    assert len(streams["ok"]) >= 1
    assert engine.tenant_snapshot()["greedy"]["rejections"]["quota"] == 1
    engine.shutdown()


def test_tenant_max_slots_blocks_admission_not_others():
    """A tenant at its slot cap is skipped; the other tenant takes the
    second slot immediately."""
    from cyberfabric_core_tpu.modkit.flight_recorder import default_recorder

    default_recorder.reset()
    cfg = EngineConfig(**TINY, tenant_max_slots=1)
    engine = ContinuousBatchingEngine(cfg, seed=0)
    loads = [(f"h{i}", "hog", [5, 9, 12], 8) for i in range(4)]
    loads += [("lite", "light", [6, 8, 21], 8)]
    _drive(engine, loads)
    # at no admitted instant may the hog hold 2 slots: reconstruct
    # occupancy from the recorder (admitted → finished intervals overlap)
    spans = []
    for i in range(4):
        rec = default_recorder.lookup(f"h{i}") or {}
        t_adm = t_fin = None
        for ev in rec.get("timeline", ()):
            if ev.get("event") == "admitted":
                t_adm = ev["ts"]
            if ev.get("event") == "finished":
                t_fin = ev["ts"]
        assert t_adm is not None and t_fin is not None
        spans.append((t_adm, t_fin))
    for i in range(4):
        for j in range(i + 1, 4):
            a, b = spans[i], spans[j]
            overlap = min(a[1], b[1]) - max(a[0], b[0])
            assert overlap <= 0.0, \
                f"hog held two slots concurrently ({i} vs {j})"
    engine.shutdown()


def test_tenant_soft_page_quota_yields_under_contention():
    """An over-soft-cap tenant is preempted to host when another tenant is
    backlogged; the yielded request stays PARKED while the starved tenant
    has pending work (resume priority must not hand the freed slot straight
    back — the preempt/restore livelock the review pinned), then resumes
    and finishes with zero leaks."""
    from cyberfabric_core_tpu.modkit.flight_recorder import default_recorder

    default_recorder.reset()
    cfg = EngineConfig(**TINY, tenant_soft_pages=1)
    engine = ContinuousBatchingEngine(cfg, seed=0)
    # hog grows past 1 page (16 tokens) mid-stream; the polite tenant's
    # queued request creates the contention that triggers the yield
    loads = [("hog0", "hog", list(range(3, 15)), 24),
             ("hog1", "hog", list(range(3, 15)), 24),
             ("p0", "polite", [3, 4, 5], 4),
             ("p1", "polite", [3, 4, 5], 4),
             ("p2", "polite", [3, 4, 5], 4)]
    streams = _drive(engine, loads)
    assert all(len(v) >= 1 for v in streams.values())
    snap = engine.tenant_snapshot()
    assert snap["hog"]["soft_yields"] >= 1, snap
    assert engine.stats()["preemptions"] >= 1
    # the yield deferral: the first starved-tenant admission lands BEFORE
    # the first yielded hog resume — the freed capacity served the starved
    # tenant instead of bouncing straight back to the over-quota one
    # (resume outranks admission, so without the deferral the yielded
    # request would reclaim its own freed slot). Later resumes may
    # legitimately interleave: the deferral re-judges the LIVE cap, so a
    # hog whose other streams finished resumes even while polite work is
    # still pending — a yielded stream's stall is bounded by its tenant's
    # overshoot, never by another tenant's backlog.
    resumed_ts = []
    for rid in ("hog0", "hog1"):
        rec = default_recorder.lookup(rid) or {}
        resumed_ts += [ev["ts"] for ev in rec.get("timeline", ())
                       if ev.get("event") == "resumed"]
    assert resumed_ts, "no yield/resume ever happened"
    p0 = default_recorder.lookup("p0") or {}
    p0_admitted = [ev["ts"] for ev in p0.get("timeline", ())
                   if ev.get("event") == "admitted"]
    assert p0_admitted and p0_admitted[0] <= min(resumed_ts), \
        "the starved tenant never admitted before the yielded hog resumed"
    # zero leaks after drain
    assert len(engine._free_slots) == engine.n_slots
    engine.shutdown()


def test_caps_disarmed_with_tenant_blind_queue(caplog):
    """Per-tenant caps need per-tenant attribution: with tenant_fair=False
    the queue collapses every tenant onto one key, so caps are DISARMED
    (loudly) instead of enforced wrongly (the blocked-set keys would never
    match, and the soft-quota sweep would read a tenant's own backlog as
    contention and thrash its only tenant)."""
    import logging

    with caplog.at_level(logging.WARNING, logger="scheduler"):
        cfg = EngineConfig(**TINY, tenant_fair=False, tenant_max_pending=1,
                           tenant_max_pages=1, tenant_soft_pages=1)
        engine = ContinuousBatchingEngine(cfg, seed=0)
    assert any("DISARMED" in r.message for r in caplog.records)
    assert engine._tenant_caps_armed is False
    # neither the pending bound nor the hard quota fires
    streams = _drive(engine, [(f"r{i}", "t", list(range(3, 30)), 8)
                              for i in range(4)])
    assert all(len(v) >= 1 for v in streams.values())
    engine.shutdown()


def test_saturation_retry_after_derives_from_drain_rate():
    engine = ContinuousBatchingEngine(EngineConfig(**TINY), seed=0)
    # synthetic drain history: the first event anchors the span (its count
    # landed BEFORE the span), the second contributes 20 over 10s → 2/s
    t0 = time.monotonic()
    engine._admit_events.append((t0 - 10.0, 999))
    engine._admit_events.append((t0, 20))
    assert engine._drain_rate_per_s() == pytest.approx(2.0, rel=1e-3)
    assert engine._saturation_retry_after(10) == pytest.approx(5.0, rel=1e-3)
    assert engine._saturation_retry_after(1000) == 30.0  # clamped
    # stale observations (outside the 60s window) read as unknown — an
    # overnight idle gap must not produce a near-zero "drain rate"
    engine._admit_events.clear()
    engine._admit_events.append((t0 - 3600.0, 50))
    engine._admit_events.append((t0 - 3599.0, 50))
    assert engine._drain_rate_per_s() == 0.0
    engine._admit_events.clear()
    assert engine._saturation_retry_after(50) == 1.0  # unknown rate
    engine.shutdown()


# ------------------------------------------------------ doctor + gateway


class _FakeTenantSched:
    def __init__(self):
        self.rows = {}

    def tenant_snapshot(self):
        return self.rows


def _tenant_doctor(**over):
    from cyberfabric_core_tpu.modkit.doctor import Doctor, DoctorConfig

    cfg = DoctorConfig(min_samples=10 ** 6, shed_after=10 ** 6,
                       tenant_over_share=1.5, tenant_min_activity=8,
                       tenant_shed_retry_after_s=3.0,
                       stream_stall_s=10 ** 6, round_stall_floor_s=10 ** 6,
                       queue_deadline_s=10 ** 6, **over)
    return Doctor(cfg)


def test_doctor_sheds_over_share_tenant_selectively():
    doctor = _tenant_doctor()
    sched = _FakeTenantSched()
    doctor.set_scheduler_provider(lambda: [("m", sched)])
    sched.rows = {
        "heavy": {"charged_tokens": 0, "weight": 1.0, "pending": 0,
                  "active_slots": 1},
        "light": {"charged_tokens": 0, "weight": 1.0, "pending": 0,
                  "active_slots": 1},
    }
    doctor.evaluate()  # baseline pass records prev counters
    # heavy consumed 90% of the delta AND hogs the queue while burning
    sched.rows = {
        "heavy": {"charged_tokens": 900, "weight": 1.0, "pending": 20,
                  "active_slots": 2},
        "light": {"charged_tokens": 100, "weight": 1.0, "pending": 1,
                  "active_slots": 0},
    }
    # force a bad evaluation via a tripped-capacity reason: use the
    # capacity provider (zero serving replicas is a degradation reason)
    doctor.set_capacity_provider(lambda: {"replicas": 1, "serving": 0})
    report = doctor.evaluate()
    assert report["tenants"]["shed"] == ["heavy"]
    assert report["tenants"]["shares"]["heavy"]["over_share"] is True
    assert doctor.tenant_shed_retry_after("heavy") == 3.0
    assert doctor.tenant_shed_retry_after("light") is None
    # clean evaluation clears the set within one pass
    doctor.set_capacity_provider(lambda: {"replicas": 1, "serving": 1})
    doctor.evaluate()
    assert doctor.tenant_shed_retry_after("heavy") is None


def test_doctor_shed_mark_expires_while_burn_persists():
    """A shed tenant's 429s suppress exactly the activity that marked it —
    the mark must expire after the hold window even while the burn
    continues for unrelated reasons, or the tenant is never exonerated."""
    doctor = _tenant_doctor(tenant_shed_hold_s=0.2)
    sched = _FakeTenantSched()
    doctor.set_scheduler_provider(lambda: [("m", sched)])
    sched.rows = {
        "heavy": {"charged_tokens": 0, "weight": 1.0, "pending": 0,
                  "active_slots": 1},
        "light": {"charged_tokens": 0, "weight": 1.0, "pending": 0,
                  "active_slots": 1},
    }
    doctor.evaluate()
    doctor.set_capacity_provider(lambda: {"replicas": 1, "serving": 0})
    sched.rows["heavy"] = {"charged_tokens": 900, "weight": 1.0,
                           "pending": 20, "active_slots": 2}
    sched.rows["light"] = {"charged_tokens": 100, "weight": 1.0,
                           "pending": 1, "active_slots": 0}
    doctor.evaluate()
    assert doctor.tenant_shed_retry_after("heavy") is not None
    # heavy backs off completely (shed 429s): no new tokens AND its queue
    # drains; the burn persists (capacity reason still active) — the mark
    # holds briefly, then expires
    sched.rows["heavy"] = {"charged_tokens": 900, "weight": 1.0,
                           "pending": 0, "active_slots": 0}
    time.sleep(0.25)
    doctor.evaluate()  # heavy's delta is 0 now; still burning
    assert doctor.tenant_shed_retry_after("heavy") is None
    # and within the hold window the mark would have survived (anti-flap):
    doctor.evaluate()
    assert doctor.tenant_shed_retry_after("heavy") is None


def test_doctor_no_selective_shed_with_single_tenant():
    doctor = _tenant_doctor()
    sched = _FakeTenantSched()
    doctor.set_scheduler_provider(lambda: [("m", sched)])
    sched.rows = {"only": {"charged_tokens": 0, "weight": 1.0,
                           "pending": 50, "active_slots": 2}}
    doctor.evaluate()
    sched.rows = {"only": {"charged_tokens": 10 ** 6, "weight": 1.0,
                           "pending": 50, "active_slots": 2}}
    doctor.set_capacity_provider(lambda: {"replicas": 1, "serving": 0})
    report = doctor.evaluate()
    # one tenant = 100% share by definition; there is nobody to be fair
    # between, so selective shedding must never engage
    assert report["tenants"]["shed"] == []
    assert doctor.tenant_shed_retry_after("only") is None


def test_doctor_disabled_tenant_shedding():
    doctor = _tenant_doctor(tenant_shed_enabled=False)
    assert doctor.tenant_shed_retry_after("anyone") is None


def test_usage_tracker_budget_reads_scheduler_live_counters():
    from cyberfabric_core_tpu.modkit.errors import ProblemError
    from cyberfabric_core_tpu.modkit.metrics import default_registry
    from cyberfabric_core_tpu.modkit.security import SecurityContext
    from cyberfabric_core_tpu.modules.llm_gateway.module import UsageTracker

    tracker = UsageTracker({"acme": 100}, retry_after_s=17.0)
    ctx = SecurityContext.anonymous("acme")
    tracker.check_budget(ctx)  # nothing reported, nothing live → fine
    # the scheduler-side ledger says the tenant burned its budget even
    # though no gateway usage report landed yet (streams still open)
    tracker.attach_live_source(
        lambda: {"acme": {"charged_tokens": 150}})
    with pytest.raises(ProblemError) as exc:
        tracker.check_budget(ctx)
    problem = exc.value.problem
    assert problem.code == "budget_exceeded"
    assert problem.extensions["retry_after_s"] == 17.0
    assert problem.extensions["tenant"] == "acme"
    rendered = default_registry.render()
    assert "llm_tenant_budget_rejections_total" in rendered
    # a hostile live source never breaks serving
    tracker.attach_live_source(lambda: (_ for _ in ()).throw(RuntimeError()))
    tracker.check_budget(ctx)


def test_worker_tenant_usage_aggregates_schedulers():
    from cyberfabric_core_tpu.modules.llm_gateway.worker import LocalTpuWorker

    worker = LocalTpuWorker({})
    sched = _FakeTenantSched()
    sched.rows = {"a": {"weight": 2.0, "active_slots": 1, "pages": 3,
                        "pending": 2, "virtual_counter": 5.0,
                        "charged_tokens": 10, "soft_yields": 0,
                        "rejections": {"pending": 1}}}

    class _E:
        scheduler = sched
        pool = None

    worker._entries["m"] = _E()  # type: ignore[assignment]
    usage = worker.tenant_usage()
    assert usage["a"]["charged_tokens"] == 10
    assert usage["a"]["pending"] == 2
    assert usage["a"]["rejections"] == {"pending": 1}
    assert "m" in usage["a"]["per_model"]
