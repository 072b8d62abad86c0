"""End-to-end cancellation & deadlines (PR 9).

The contract under test: a request can be let go of in EVERY phase —
pending-queue removal pre-admit, mid-chunked-prefill abort, mid-decode row
deactivation, suspended drop — with exactly one terminal, leak-free
slot/page/pin release, and deadline lapses that never occupy a slot. The
gateway/worker half: an abandoned stream (client disconnect, half-consumed
generator) cancels the engine-side work instead of decoding to max_tokens
for a dead consumer.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

from cyberfabric_core_tpu.modkit.doctor import Doctor, DoctorConfig
from cyberfabric_core_tpu.modkit.errcat import ERR
from cyberfabric_core_tpu.modkit.flight_recorder import FlightRecorder
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.engine import StepEvent
from cyberfabric_core_tpu.runtime.replicas import (DataParallelServingPool,
                                                   _Tracked)
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine


@pytest.fixture(autouse=True, scope="module")
def _recorder_hygiene():
    """The flight recorder is process-global: a live record left behind by
    an engine shut down mid-flight reads as a permanently-stalled stream to
    the doctor's watchdogs in LATER test modules (walking the global state
    machine to `shedding`). Start and leave this module clean."""
    from cyberfabric_core_tpu.modkit.flight_recorder import default_recorder

    default_recorder.reset()
    yield
    default_recorder.reset()


def _cfg(**over):
    base = dict(model="tiny-llama", max_seq_len=256, max_batch=2,
                decode_chunk=4,
                prefix_cache_pages=80, prefix_page_size=16)
    base.update(over)
    return EngineConfig(**base)


def _slow(point="scheduler.readback"):
    """Every decode round (or, ``scheduler.prefill_chunk``, every chunk of a
    prompt) takes at least 50 ms. A deadline test states how slow the server
    is; it must not count on a first compile to be that (a process that
    holds the step programs has none)."""
    from cyberfabric_core_tpu.modkit import failpoints as fp

    return fp.scoped(point, "delay(0.05)")


class _Collector:
    def __init__(self, n):
        self.tokens = {i: [] for i in range(n)}
        self.finishes = {}
        self.done = threading.Event()
        self._lock = threading.Lock()
        self._n = n

    def emit_for(self, i):
        def emit(ev):
            with self._lock:
                if ev.token_id >= 0:
                    self.tokens[i].append(ev.token_id)
                if ev.finished:
                    self.finishes[i] = ev.finished
                    if len(self.finishes) == self._n:
                        self.done.set()
        return emit


def _assert_clean(sched):
    assert len(sched._free_slots) == sched.n_slots
    assert all(s is None for s in sched.slots)
    assert sched._pending.qsize() == 0
    assert not sched._suspended
    if sched.pool is not None:
        st = sched.pool.stats()
        assert st.get("pages_referenced", 0) == 0, st
        assert st.get("orphan_pages", 0) == 0, st


# ------------------------------------------------------------- scheduler


def test_cancel_pending_request_never_takes_a_slot():
    """A cancel landing while the request still queues removes it from the
    pending queue pre-admit: zero tokens, one 'cancelled' terminal, full
    budget reclaimed."""
    cfg = _cfg(max_batch=1)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(2)
    try:
        sched.submit([5] * 8, SamplingParams(max_tokens=120),
                     col.emit_for(0), request_id="runner")
        # wait for the runner to hold the only slot
        deadline = time.monotonic() + 60
        while sched.active_slots + len(sched._prefill_slots) == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        sched.submit([6] * 8, SamplingParams(max_tokens=50),
                     col.emit_for(1), request_id="queued")
        assert sched.cancel("queued", "changed_mind") is True
        assert col.done.wait(240), (col.finishes, sched.stats())
        stats = sched.stats()
    finally:
        sched.shutdown()
    assert col.finishes[1] == "cancelled"
    assert col.tokens[1] == [], "a cancelled pending request emitted tokens"
    assert stats["cancellations"] == {"changed_mind": 1}
    assert stats["reclaimed_tokens"] >= 50
    _assert_clean(sched)


def test_cancel_unknown_id_is_noop():
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    try:
        assert sched.cancel("never-submitted") is False
        col = _Collector(1)
        sched.submit([3, 4, 5], SamplingParams(max_tokens=6), col.emit_for(0))
        assert col.done.wait(240)
        # the stale cancel request is consumed without effect
        assert sched.stats()["cancellations"] == {}
    finally:
        sched.shutdown()
    _assert_clean(sched)


@_slow()       # 200 tokens: 50 rounds, 2.5 s at the least
def test_deadline_lapses_mid_decode():
    """An admitted stream whose deadline passes mid-generation gets a
    'deadline' terminal within a round — partial output, slot freed."""
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    col = _Collector(1)
    try:
        sched.submit([7] * 8, SamplingParams(max_tokens=200),
                     col.emit_for(0), request_id="slow",
                     deadline=time.monotonic() + 0.5)
        assert col.done.wait(240), (col.finishes, sched.stats())
        stats = sched.stats()
    finally:
        sched.shutdown()
    assert col.finishes[0] == "deadline"
    assert 0 < len(col.tokens[0]) < 200
    assert stats["cancellations"] == {"deadline": 1}
    _assert_clean(sched)


@_slow()       # the runner is busy for 2.5 s at the least
def test_deadline_admission_estimate_rejects_unfillable_budget():
    """White-box: while the engine is BUSY and the best observed prefill
    rate says this request cannot possibly prefill inside its remaining
    budget, it lapses at the take — never admitted, even with a free slot.
    (An IDLE engine always admits: a wrong estimate then costs one prefill
    and the fresh observation keeps the rate honest.)"""
    sched = ContinuousBatchingEngine(_cfg(), seed=0)  # max_batch 2
    col = _Collector(2)
    try:
        sched.submit([5] * 8, SamplingParams(max_tokens=200),
                     col.emit_for(0), request_id="runner")
        deadline = time.monotonic() + 60
        while not col.tokens[0] and time.monotonic() < deadline:
            time.sleep(0.005)
        # pin the estimate: 1 tok/s → a 40-token prompt ≈ 40 s ≫ 2 s budget
        # (the runner's own fast prefill sample must not win the max: it is
        # in by the runner's first token)
        sched._prefill_rates.clear()
        sched._prefill_rates.append(1.0)
        sched.submit([9] * 40, SamplingParams(max_tokens=10),
                     col.emit_for(1), request_id="doomed",
                     deadline=time.monotonic() + 2.0)
        assert col.done.wait(240), (col.finishes, sched.stats())
        stats = sched.stats()
    finally:
        sched.shutdown()
    assert col.finishes[1] == "deadline"
    assert col.tokens[1] == [], "the doomed request was admitted"
    assert col.finishes[0] in ("stop", "length")
    assert stats["cancellations"] == {"deadline": 1}
    _assert_clean(sched)


def test_cancel_mid_chunked_prefill_releases_chain():
    """Mixed-batch mode: a slot cancelled while still in PREFILL phase
    (its prompt only partially chunked in) releases the slot and its chain
    without ever sampling a token."""
    # budget 3 forces several chunks per prompt; a long prompt keeps the
    # slot in prefill phase across rounds
    cfg = _cfg(prefill_budget_tokens=3, max_seq_len=256)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    col = _Collector(1)
    try:
        sched.submit(list(range(3, 43)), SamplingParams(max_tokens=20),
                     col.emit_for(0), request_id="chunky")
        deadline = time.monotonic() + 60
        while not sched._prefill_slots and time.monotonic() < deadline:
            time.sleep(0.002)
        sched.cancel("chunky", "disconnect")
        assert col.done.wait(240), (col.finishes, sched.stats())
        stats = sched.stats()
    finally:
        sched.shutdown()
    # the cancel either caught the slot mid-prefill (no tokens) or just
    # after the flip — one terminal either way, and never a full stream
    assert col.finishes[0] == "cancelled"
    assert len(col.tokens[0]) < 20
    assert stats["cancellations"] == {"disconnect": 1}
    _assert_clean(sched)


# ------------------------------------------------------------ replica pool
# (bare-instance doubles — the tests/test_replicas.py pattern)


def _bare_pool():
    pool = DataParallelServingPool.__new__(DataParallelServingPool)
    pool._lock = threading.Lock()
    pool._requests = {}
    pool.replicas = []
    pool.max_retries = 1
    pool.failovers = 0
    pool.failovers_failed = 0
    return pool


class _FakeReplica:
    def __init__(self):
        self.submissions = []
        self.cancels = []

    def stats(self):
        return {"broken": None, "closed": False, "active": 0, "pending": 0}

    def submit(self, prompt_ids, sampling, emit, request_id=None,
               trace=None, deadline=None):
        self.submissions.append((list(prompt_ids), request_id, deadline))

    def cancel(self, request_id, reason="cancelled"):
        self.cancels.append((request_id, reason))
        return True


def test_pool_cancel_forwards_and_blocks_failover():
    """pool.cancel marks the tracking record and forwards to the owning
    replica; a later error terminal (replica break racing the cancel) is
    surfaced as 'cancelled' — NEVER resubmitted."""
    pool = _bare_pool()
    corpse, survivor = _FakeReplica(), _FakeReplica()
    pool.replicas = [corpse, survivor]
    events = []
    tracked = _Tracked([1, 2, 3], SamplingParams(max_tokens=16),
                       events.append, [7, 8], replica=0, retries_left=2)
    pool._requests["rid"] = tracked
    assert pool.cancel("rid", "client_disconnect") is True
    assert corpse.cancels == [("rid", "client_disconnect")]
    # the replica breaks before the engine-side cancel applies: its error
    # terminal reaches the wrapper, which must not fail over
    emit = pool._wrap("rid", tracked)
    emit(StepEvent(0, -1, "error"))
    assert [(e.token_id, e.finished) for e in events] == [(-1, "cancelled")]
    assert survivor.submissions == [], "cancelled request was resubmitted"
    assert "rid" not in pool._requests
    assert pool.failovers == 0


def test_pool_cancel_unknown_id_false():
    pool = _bare_pool()
    assert pool.cancel("ghost") is False


def test_failover_skips_resubmission_when_deadline_gone():
    """A failover for a request whose deadline already lapsed closes out
    with the deadline terminal instead of burning a survivor's slot."""
    pool = _bare_pool()
    survivor = _FakeReplica()
    pool.replicas = [_FakeReplica(), survivor]
    events = []
    tracked = _Tracked([1, 2], SamplingParams(max_tokens=16), events.append,
                       [5], replica=0, retries_left=2,
                       deadline=time.monotonic() - 1.0)
    pool._requests["rid"] = tracked
    assert pool._failover("rid", tracked) is True
    assert [(e.token_id, e.finished) for e in events] == [(-1, "deadline")]
    assert survivor.submissions == []
    assert "rid" not in pool._requests


def test_failover_resubmission_carries_deadline():
    pool = _bare_pool()
    survivor = _FakeReplica()
    pool.replicas = [_FakeReplica(), survivor]
    deadline = time.monotonic() + 60.0
    tracked = _Tracked([1, 2], SamplingParams(max_tokens=16),
                       lambda ev: None, [5], replica=0, retries_left=2,
                       deadline=deadline)
    pool._requests["rid"] = tracked
    assert pool._failover("rid", tracked) is True
    assert survivor.submissions == [([1, 2, 5], "rid", deadline)]


# ------------------------------------------------------- worker teardown


def _tiny_model():
    from cyberfabric_core_tpu.modules.sdk import ModelInfo

    return ModelInfo(
        canonical_id="local::cancel-tiny", provider_slug="local",
        provider_model_id="cancel-tiny",
        engine_options={"model_config": "tiny-llama", "max_seq_len": 128,
                        "max_batch": 2, "decode_chunk": 4})


def test_half_consumed_stream_cancels_engine_side():
    """The satellite regression: an HTTP-layer abandonment (generator
    closed after one chunk — the SSE consumer vanished) must cancel the
    worker-side queue consumer AND the engine-side work, freeing the slot
    within a round instead of decoding to max_tokens."""
    from cyberfabric_core_tpu.modkit import failpoints as fp
    from cyberfabric_core_tpu.modules.llm_gateway.worker import LocalTpuWorker

    async def go():
        worker = LocalTpuWorker({})
        model = _tiny_model()
        agen = worker.completion_stream(model, "hello cancellation",
                                        {"max_tokens": 200})
        first = await agen.__anext__()
        assert first.text
        await agen.aclose()  # the client is gone
        entry = next(iter(worker._entries.values()))
        sched = entry.scheduler
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if sched.active_slots == 0 and \
                    len(sched._free_slots) == sched.n_slots:
                break
            await asyncio.sleep(0.02)
        stats = sched.stats()
        sched.shutdown()
        return sched, stats

    # 50 ms a readback keeps the engine decoding for over a second: on a loaded
    # CPU it otherwise finishes its (short) answer before this thread is
    # scheduled to close the generator, and a finished request is not cancelled
    fp.configure(0)
    fp.arm("scheduler.readback", "delay(0.05)")
    try:
        sched, stats = asyncio.run(go())
    finally:
        fp.disarm("scheduler.readback")
    assert stats["cancellations"].get("client_disconnect") == 1, stats
    assert stats["reclaimed_tokens"] > 0
    _assert_clean(sched)


def test_worker_deadline_maps_to_408_when_never_started():
    """A request that lapses in the queue (never admitted, zero output)
    surfaces as the llm.request_timeout 408 problem."""
    from cyberfabric_core_tpu.modkit.errors import ProblemError
    from cyberfabric_core_tpu.modules.llm_gateway.worker import LocalTpuWorker

    async def go():
        worker = LocalTpuWorker({})
        model = _tiny_model()
        # pin both slots
        g1 = worker.completion_stream(model, "aaaa", {"max_tokens": 300})
        g2 = worker.completion_stream(model, "bbbb", {"max_tokens": 300})
        await g1.__anext__()
        await g2.__anext__()
        status = code = None
        try:
            async for _ in worker.completion_stream(
                    model, "cccc", {"max_tokens": 20, "_deadline_ms": 80}):
                pass
        except ProblemError as e:
            status, code = e.problem.status, e.problem.code
        await g1.aclose()
        await g2.aclose()
        entry = next(iter(worker._entries.values()))
        sched = entry.scheduler
        # let the teardown cancels APPLY (closing their flight records)
        # before the engine goes away — shutdown first would strand two
        # live records forever
        deadline = time.monotonic() + 30.0
        while sched.active_slots and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        sched.shutdown()
        return status, code

    status, code = asyncio.run(go())
    assert (status, code) == (408, "request_timeout")


@_slow("scheduler.prefill_chunk")     # 20 chunks: 1 s at the least
def test_worker_deadline_maps_to_504_when_admitted_but_no_output():
    """A deadline lapsing AFTER admission (mid-chunked-prefill — the slot
    was claimed, the server just ran out of time) but before any output
    maps to llm.deadline_exceeded 504, not the queued-lapse 408."""
    from cyberfabric_core_tpu.modkit.errors import ProblemError
    from cyberfabric_core_tpu.modules.llm_gateway.worker import LocalTpuWorker
    from cyberfabric_core_tpu.modules.sdk import ModelInfo

    async def go():
        worker = LocalTpuWorker({})
        model = ModelInfo(
            canonical_id="local::cancel-tiny-504", provider_slug="local",
            provider_model_id="cancel-tiny-504",
            engine_options={"model_config": "tiny-llama", "max_seq_len": 128,
                            "max_batch": 2, "decode_chunk": 4,
                            # 2-token chunks stretch a 40-token prompt over
                            # ~20 mixed rounds: the tight deadline reliably
                            # lapses MID-prefill, after the slot was claimed
                            "prefill_budget_tokens": 2})
        status = code = None
        try:
            async for _ in worker.completion_stream(
                    model, "x" * 40, {"max_tokens": 20, "_deadline_ms": 250}):
                pass
        except ProblemError as e:
            status, code = e.problem.status, e.problem.code
        entry = next(iter(worker._entries.values()))
        entry.scheduler.shutdown()
        return status, code

    status, code = asyncio.run(go())
    assert (status, code) == (504, "deadline_exceeded")


@_slow()       # the window holds 30 rounds: 1.5 s at the least
def test_worker_mid_stream_deadline_finishes_with_reason():
    """A deadline lapsing after output started closes the stream with
    finish_reason=deadline_exceeded and honest usage (no re-status on an
    open SSE stream)."""
    from cyberfabric_core_tpu.modules.llm_gateway.worker import LocalTpuWorker

    async def go():
        worker = LocalTpuWorker({})
        model = _tiny_model()
        chunks = []
        async for chunk in worker.completion_stream(
                model, "dddd", {"max_tokens": 500, "_deadline_ms": 600}):
            chunks.append(chunk)
        entry = next(iter(worker._entries.values()))
        entry.scheduler.shutdown()
        return chunks

    chunks = asyncio.run(go())
    final = chunks[-1]
    assert final.finish_reason == "deadline_exceeded"
    assert 0 < final.usage["output_tokens"] < 500


# ------------------------------------------- recorder / doctor integration


def test_recorder_cancelled_terminal_closes_record():
    rec = FlightRecorder()
    rec.record("r1", "enqueued", prompt_tokens=4)
    rec.record("r1", "cancelled", reason="client_disconnect", tokens=3)
    assert not rec.is_live("r1")
    doc = rec.lookup("r1")
    assert doc["phase"] == "cancelled"
    assert [e["event"] for e in doc["timeline"]] == ["enqueued", "cancelled"]
    # duplicate terminal suppressed
    rec.record("r1", "deadline_exceeded")
    assert len(rec.lookup("r1")["timeline"]) == 2


def test_doctor_excludes_cancels_from_error_burn():
    """Cancellations feed the cancellation-rate signal but neither the
    error-rate numerator nor its denominator."""
    doctor = Doctor(DoctorConfig(min_samples=1), recorder=FlightRecorder())
    for kind in ("cancelled", "deadline_exceeded", "finished", "error"):
        doctor.on_record({"kind": kind, "model": None, "derived": {}})
    with doctor._lock:
        err = doctor._windows["error"].samples
        cancel = doctor._windows["cancel"].samples
    # error window: only finished + error landed (bad fraction 1/2)
    assert len(err) == 2 and sum(v for _, v, _ in err) == 1.0
    # cancel window: all four terminals, two of them cancels
    assert len(cancel) == 4 and sum(v for _, v, _ in cancel) == 2.0
    report = doctor.evaluate()
    assert report["cancellation"] == {"rate_fast": 0.5,
                                      "cancelled_fast": 2,
                                      "terminals_fast": 4}


def test_error_catalog_has_cancellation_codes():
    assert ERR.llm.client_closed_request.problem().status == 499
    assert ERR.llm.request_timeout.problem().status == 408
    assert ERR.llm.deadline_exceeded.problem().status == 504
