"""Flight recorder + end-to-end trace propagation (request observability PR).

Covers the ISSUE-4 test satellite: ring eviction / bounded memory, derived
figures, trace-propagation bit-identity (streams unchanged with tracing on vs
off, reusing the PR-2 golden-stream harness), metrics thread-safety, the
chrome-trace round export, and faultlab-style scenarios asserting that
preempt/resume and failover land in the timeline.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from cyberfabric_core_tpu.modkit import failpoints as fp
from cyberfabric_core_tpu.modkit.flight_recorder import (FlightRecorder,
                                                         default_recorder,
                                                         record_event)
from cyberfabric_core_tpu.modkit.telemetry import (Span, SpanExporter, Tracer,
                                                   get_global_tracer,
                                                   set_global_tracer,
                                                   startup, traceparent_ids)
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine


@pytest.fixture(autouse=True)
def _clean_recorder():
    default_recorder.reset()
    yield
    default_recorder.reset()


class _CollectExporter(SpanExporter):
    def __init__(self):
        self.spans: list[tuple[Span, float]] = []
        self._lock = threading.Lock()

    def export(self, span: Span, duration_ms: float) -> None:
        if span.trace_id == startup.trace_id:
            return      # an engine's build: the start-up timeline's own trace
        with self._lock:
            self.spans.append((span, duration_ms))

    def names(self) -> set[str]:
        with self._lock:
            return {s.name for s, _ in self.spans}


@pytest.fixture()
def collect_tracer():
    exporter = _CollectExporter()
    prev = get_global_tracer()
    set_global_tracer(Tracer(exporter=exporter))
    yield exporter
    set_global_tracer(prev)


# ------------------------------------------------------------ recorder unit


def test_lifecycle_events_and_derived_figures():
    rec = FlightRecorder()
    rec.record("r1", "enqueued", prompt_tokens=12, trace_id="t" * 32)
    rec.record("r1", "admitted", queue_wait_ms=5.0)
    rec.record("r1", "prefill", slot=3, coalesced=False, cached_len=0,
               dur_ms=9.0)
    for _ in range(4):
        rec.record("r1", "decode_chunk", slot=3, tokens=8)
    assert rec.inflight()[0]["phase"] == "decode"
    assert rec.inflight()[0]["slot"] == 3
    assert rec.inflight()[0]["tokens"] == 1 + 4 * 8  # prefill emits token 1
    rec.record("r1", "finished", reason="stop", tokens=33)
    assert rec.inflight() == []
    out = rec.lookup("r1")
    assert out is not None and out["phase"] == "finished"
    kinds = [e["event"] for e in out["timeline"]]
    assert kinds[0] == "enqueued" and kinds[-1] == "finished"
    d = out["derived"]
    assert d["queue_wait_ms"] is not None and d["ttft_ms"] is not None
    assert d["e2e_ms"] >= d["ttft_ms"]
    assert d["itl_ms"] is not None  # >=2 chunk events
    assert out["trace_id"] == "t" * 32


def test_finished_ring_evicts_oldest():
    rec = FlightRecorder(max_finished=4)
    for i in range(10):
        rec.record(f"r{i}", "enqueued")
        rec.record(f"r{i}", "finished", reason="stop")
    assert rec.stats() == {"live": 0, "finished": 4, "evicted_live": 0}
    assert rec.lookup("r0") is None          # aged out
    assert rec.lookup("r9") is not None      # newest survives
    assert len(rec.recent(50)) == 4


def test_live_table_bound_force_closes_oldest():
    rec = FlightRecorder(max_live=3, max_finished=8)
    for i in range(6):
        rec.record(f"r{i}", "enqueued")
    st = rec.stats()
    assert st["live"] == 3 and st["evicted_live"] == 3
    evicted = rec.lookup("r0")
    assert evicted is not None and evicted["phase"] == "evicted"


def test_per_record_event_cap_drops_middle_keeps_ends():
    rec = FlightRecorder(max_events=16)
    rec.record("r", "enqueued")
    for i in range(100):
        rec.record("r", "decode_chunk", tokens=1, seq=i)
    rec.record("r", "finished", reason="length")
    out = rec.lookup("r")
    assert len(out["timeline"]) == 16
    assert out["dropped_events"] == 86  # 102 recorded - 16 kept
    assert out["timeline"][0]["event"] == "enqueued"
    assert out["timeline"][-1]["event"] == "finished"


def test_record_event_helper_never_raises(monkeypatch):
    monkeypatch.setattr(default_recorder, "record",
                        lambda *a, **k: 1 / 0)
    record_event("r", "enqueued")  # must swallow


def test_terminal_observes_prometheus_histograms():
    from cyberfabric_core_tpu.modkit.metrics import default_registry

    hist = default_registry.histogram("llm_queue_wait_seconds")
    key = ()
    before = hist._totals.get(key, 0)
    rec = FlightRecorder()
    rec.record("r", "enqueued")
    rec.record("r", "admitted")
    rec.record("r", "prefill", slot=0)
    rec.record("r", "finished", reason="stop")
    assert hist._totals.get(key, 0) == before + 1


def test_reopen_on_failover_keeps_one_timeline():
    """A non-terminal event after a terminal (the failover resubmission
    pattern) REOPENS the closed record instead of shadowing it."""
    rec = FlightRecorder()
    rec.record("r", "enqueued")
    rec.record("r", "error", detail="replica died")
    rec.record("r", "failover", from_replica=0, to_replica=1)
    rec.record("r", "enqueued")
    rec.record("r", "prefill", slot=0)
    rec.record("r", "finished", reason="stop")
    out = rec.lookup("r")
    kinds = [e["event"] for e in out["timeline"]]
    assert kinds == ["enqueued", "error", "failover", "enqueued", "prefill",
                     "finished"]
    assert rec.stats()["live"] == 0
    # a duplicate terminal for the (now re-closed) record is still dropped
    rec.record("r", "finished", reason="stop")
    assert len(rec.lookup("r")["timeline"]) == 6


def test_client_retry_of_finished_id_starts_fresh_record():
    """Only the failover continuation reopens a closed record; a client
    retrying with a finished X-Request-Id gets a FRESH timeline (merging two
    requests would corrupt every derived figure)."""
    rec = FlightRecorder()
    rec.record("r", "enqueued")
    rec.record("r", "finished", reason="stop")
    rec.record("r", "enqueued")  # the retry
    rec.record("r", "prefill", slot=1)
    out = rec.lookup("r")  # live record preferred
    kinds = [e["event"] for e in out["timeline"]]
    assert kinds == ["enqueued", "prefill"]
    assert rec.stats() == {"live": 1, "finished": 1, "evicted_live": 0}


def test_stalled_emit_never_creates_a_record():
    """A watchdog ``stalled`` emit racing a terminal (the stream finished
    between the doctor's inflight() snapshot and the emit) must not build a
    fresh live record: nothing would ever close it, and a phase='stalled'
    ghost reads as a permanent stall that pins the state machine degraded."""
    rec = FlightRecorder()
    rec.record("r", "enqueued")
    rec.record("r", "finished", reason="stop")
    rec.record("r", "stalled", watchdog="stream_stall")  # lost the race
    assert rec.stats() == {"live": 0, "finished": 1, "evicted_live": 0}
    # a stalled emit for an id the recorder never saw is dropped too
    rec.record("ghost", "stalled", watchdog="stream_stall")
    assert rec.stats() == {"live": 0, "finished": 1, "evicted_live": 0}
    assert rec.lookup("r")["timeline"][-1]["event"] == "finished"


def test_error_terminal_does_not_feed_latency_histograms():
    from cyberfabric_core_tpu.modkit.metrics import default_registry

    hist = default_registry.histogram("llm_queue_wait_seconds")
    before = hist._totals.get((), 0)
    rec = FlightRecorder()
    rec.record("r", "enqueued")
    rec.record("r", "admitted")
    rec.record("r", "error", detail="boom")
    assert hist._totals.get((), 0) == before


# ----------------------------------------------------- metrics thread-safety


def test_metrics_concurrent_rmw_loses_nothing():
    """The satellite bug: unlocked read-modify-write dropped increments under
    scheduler/scrape contention. With per-metric locks the totals are exact."""
    from cyberfabric_core_tpu.modkit.metrics import Counter, Gauge, Histogram

    c = Counter("t_total", "")
    h = Histogram("t_seconds", "")
    g = Gauge("t_gauge", "")
    N, T = 2000, 8

    def work(tid):
        for i in range(N):
            c.inc(point="x")
            h.observe(0.01 * (i % 7), point="x")
            g.set(float(i), thread=str(tid))

    threads = [threading.Thread(target=work, args=(t,)) for t in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    key = (("point", "x"),)
    assert c._values[key] == N * T
    assert h._totals[key] == N * T
    # render while nothing mutates: all samples present
    assert f"t_total{{point=\"x\"}} {float(N * T)}" in "\n".join(c.render())


def test_gauge_labeled_set_function():
    from cyberfabric_core_tpu.modkit.metrics import Gauge

    g = Gauge("g", "")
    g.set_function(lambda: 7.0)
    g.set_function(lambda: 3.0, device="0")
    text = "\n".join(g.render())
    assert "g 7.0" in text
    assert 'g{device="0"} 3.0' in text


# ------------------------------------------------- scheduler timeline + spans


def _cfg(**over):
    base = dict(model="tiny-llama", max_seq_len=128, max_batch=2,
                decode_chunk=4,
                prefix_cache_pages=64, prefix_page_size=8)
    base.update(over)
    return EngineConfig(**base)


def _collect(sched, prompt, max_tokens=12, trace=None, rid=None):
    done = threading.Event()
    out = {"tokens": [], "finish": None}

    def emit(ev):
        if ev.token_id >= 0:
            out["tokens"].append(ev.token_id)
        if ev.finished is not None:
            out["finish"] = ev.finished
            done.set()

    rid = sched.submit(prompt, SamplingParams(max_tokens=max_tokens,
                                              temperature=0.0),
                       emit, request_id=rid, trace=trace)
    assert done.wait(120), sched.stats()
    return rid, out


def test_scheduler_emits_full_timeline():
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    try:
        prompt = np.random.default_rng(0).integers(3, 900, 12).tolist()
        rid, out = _collect(sched, prompt)
    finally:
        sched.shutdown()
    rec = default_recorder.lookup(rid)
    assert rec is not None, default_recorder.stats()
    kinds = [e["event"] for e in rec["timeline"]]
    for expected in ("enqueued", "admitted", "prefill", "decode_chunk",
                     "finished"):
        assert expected in kinds, kinds
    assert kinds.index("enqueued") < kinds.index("admitted") \
        < kinds.index("prefill") < kinds.index("decode_chunk")
    assert kinds[-1] == "finished"
    d = rec["derived"]
    assert d["ttft_ms"] is not None and d["ttft_ms"] >= 0
    assert rec["prompt_tokens"] == 12
    # round timings now carry wall-clock for the Perfetto export
    assert all("ts" in r for r in sched.round_timings)


def test_sampled_trace_emits_prefill_and_decode_spans(collect_tracer):
    trace = f"00-{'ab' * 16}-{'cd' * 8}-01"  # sampled
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    try:
        prompt = np.random.default_rng(1).integers(3, 900, 10).tolist()
        rid, _ = _collect(sched, prompt, trace=trace)
    finally:
        sched.shutdown()
    names = collect_tracer.names()
    assert {"llm.prefill", "llm.decode_chunk"} <= names, names
    for span, _dur in collect_tracer.spans:
        assert span.trace_id == "ab" * 16  # same trace as the caller
    rec = default_recorder.lookup(rid)
    assert rec["trace_id"] == "ab" * 16


def test_unsampled_trace_emits_no_spans(collect_tracer):
    trace = f"00-{'ab' * 16}-{'cd' * 8}-00"  # explicit unsampled
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    try:
        prompt = np.random.default_rng(1).integers(3, 900, 10).tolist()
        _collect(sched, prompt, trace=trace)
    finally:
        sched.shutdown()
    assert collect_tracer.names() == set()


def test_trace_propagation_streams_bit_identical():
    """The PR-2 golden-stream contract extended to tracing: a sampled
    traceparent changes WHAT is exported, never what any request receives."""
    prompts = [np.random.default_rng(7).integers(3, 900, 8 + 4 * i).tolist()
               for i in range(3)]

    def run(trace_for):
        sched = ContinuousBatchingEngine(_cfg(max_batch=4), seed=0)
        outs = []
        try:
            for i, p in enumerate(prompts):
                _, out = _collect(sched, p, trace=trace_for(i))
                outs.append(out["tokens"])
        finally:
            sched.shutdown()
        return outs

    traced = run(lambda i: f"00-{format(i, '032x')}-{'0d' * 8}-01")
    untraced = run(lambda i: None)
    assert traced == untraced


# ------------------------------------------------------ faultlab scenarios


def test_preempt_resume_lands_in_timeline(collect_tracer):
    """Injected pool pressure (the faultlab preempt scenario's failpoint)
    must surface as preempted → resumed in the request timeline, with the
    llm.preempt span carrying the pause."""
    trace = f"00-{'ee' * 16}-{'cd' * 8}-01"
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    try:
        prompt = np.random.default_rng(3).integers(3, 900, 16).tolist()
        with fp.scoped("scheduler.page_alloc", "2*raise(MemoryError)"):
            rid, out = _collect(sched, prompt, max_tokens=20, trace=trace)
    finally:
        sched.shutdown()
        fp.reset()
    assert out["finish"] in ("stop", "length")
    rec = default_recorder.lookup(rid)
    kinds = [e["event"] for e in rec["timeline"]]
    assert "preempted" in kinds and "resumed" in kinds, kinds
    assert kinds.index("preempted") < kinds.index("resumed")
    assert rec["derived"]["recovery_ms"] is not None
    assert "llm.preempt" in collect_tracer.names()


def test_failover_lands_in_timeline():
    """A replica dying mid-stream records error (attempt 1) + failover +
    re-enqueue on the SAME request id — one correlatable story."""
    from cyberfabric_core_tpu.runtime.replicas import DataParallelServingPool

    pool = DataParallelServingPool(
        _cfg(max_batch=1, decode_chunk=2), n_replicas=2, seed=0)
    try:
        prompt = np.random.default_rng(2).integers(3, 900, 10).tolist()
        first_tok = threading.Event()
        done = threading.Event()
        out = {"tokens": [], "finish": None}

        def emit(ev):
            if ev.token_id >= 0:
                out["tokens"].append(ev.token_id)
                first_tok.set()
            if ev.finished is not None:
                out["finish"] = ev.finished
                done.set()

        rid = pool.submit(prompt,
                          SamplingParams(max_tokens=10, temperature=0.0),
                          emit)
        assert first_tok.wait(60)
        victim = pool._requests[rid].replica

        def boom():
            raise RuntimeError("injected device fault")

        pool.replicas[victim]._decode_round = boom
        assert done.wait(120), (out, pool.stats())
        assert out["finish"] in ("stop", "length")
        rec = default_recorder.lookup(rid)
        assert rec is not None
        kinds = [e["event"] for e in rec["timeline"]]
        assert "failover" in kinds, kinds
        fo = next(e for e in rec["timeline"] if e["event"] == "failover")
        assert fo["from_replica"] == victim
        assert fo["to_replica"] != victim
    finally:
        pool.shutdown()


# -------------------------------------------------------- chrome-trace export


def test_chrome_trace_export_shape():
    from cyberfabric_core_tpu.modules.monitoring import _chrome_trace

    rounds = [{"ts": 1000.0, "admit_ms": 0.5, "dispatch_ms": 2.0,
               "sync_wait_ms": 7.0, "host_emit_ms": 1.0,
               "lookahead": True, "active": 3},
              {"admit_ms": 0.1, "dispatch_ms": 1.0, "sync_wait_ms": 2.0,
               "host_emit_ms": 0.2, "lookahead": False}]  # legacy: no ts
    doc = _chrome_trace({"local::tiny-llama": rounds})
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    slices = [e for e in events if e["ph"] == "X"]
    assert any(e["name"] == "process_name" for e in meta)
    assert {e["name"] for e in slices} == {"admit", "dispatch", "sync_wait",
                                           "host_emit"}
    # only the entry WITH a wall clock renders (4 stages), legacy is skipped
    assert len(slices) == 4
    disp = next(e for e in slices if e["name"] == "dispatch")
    sync = next(e for e in slices if e["name"] == "sync_wait")
    assert disp["ts"] == pytest.approx(1000.0 * 1e6)
    assert sync["ts"] == pytest.approx(1000.0 * 1e6 + 2000.0)
    assert sync["dur"] == pytest.approx(7000.0)
    assert all(isinstance(e["dur"], float) and e["dur"] >= 0 for e in slices)


def test_chrome_trace_draws_a_records_phases():
    """A record with ``phases`` draws them, in their recorded order and
    lengths, back to back over its pass, which ends where the round does."""
    from cyberfabric_core_tpu.modules.monitoring import _chrome_trace

    phases = {"emit": [0.25, 0.2, 0.0], "service": [0.5, 0.5, 0.5],
              "admit": [1.0, 0.4, 1.0], "capacity": [0.5, 0.5, 0.5],
              "launch": [1.5, 1.0, 1.25], "drain": [7.0, 0.1, 0.0],
              "commit": [0.75, 0.75, 0.0]}
    record = {"ts": 2000.0, "admit_ms": 1.0, "dispatch_ms": 2.0,
              "sync_wait_ms": 7.0, "host_emit_ms": 1.0, "lookahead": False,
              "active": 2, "phases": phases, "pass_ms": 11.5}
    doc = _chrome_trace({"local::tiny-llama": [record]})
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in slices] == list(phases)
    assert [e["dur"] for e in slices] == [v[0] * 1000.0
                                          for v in phases.values()]
    # back to back, and the last ends with the round: ts + 2 + 7 + 1 ms
    for a, b in zip(slices, slices[1:]):
        assert b["ts"] == pytest.approx(a["ts"] + a["dur"])
    assert slices[-1]["ts"] + slices[-1]["dur"] == pytest.approx(
        2000.0 * 1e6 + 10000.0)
    assert slices[0]["ts"] == pytest.approx(2000.0 * 1e6 + 10000.0 - 11500.0)
    launch = next(e for e in slices if e["name"] == "launch")
    assert launch["args"]["cpu_ms"] == 1.0
    assert launch["args"]["starved_ms"] == 1.25
    # one track a phase, named
    tracks = {e["args"]["name"]: e["tid"] for e in doc["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert set(tracks) == set(phases)
    assert all(e["tid"] == tracks[e["name"]] for e in slices)


def test_traceparent_ids_parser():
    tid, sampled = traceparent_ids(f"00-{'ab' * 16}-{'cd' * 8}-01")
    assert tid == "ab" * 16 and sampled is True
    tid, sampled = traceparent_ids(f"00-{'ab' * 16}-{'cd' * 8}-00")
    assert tid == "ab" * 16 and sampled is False
    assert traceparent_ids(None) == (None, False)
    assert traceparent_ids("garbage") == (None, False)
