"""OTLP span export + XLA cost analysis (SURVEY §5 observability parity)."""

import asyncio
import json
import time

import pytest
from aiohttp import web

from cyberfabric_core_tpu.modkit.telemetry import (
    OtlpHttpExporter, Tracer, tracer_from_config, xla_cost_summary)


@pytest.fixture()
def collector():
    """Local OTLP/HTTP collector capturing /v1/traces posts."""
    received: list[dict] = []
    loop = asyncio.new_event_loop()

    async def traces(request: web.Request):
        received.append(await request.json())
        return web.json_response({})

    app = web.Application()
    app.router.add_post("/v1/traces", traces)
    runner = web.AppRunner(app)
    loop.run_until_complete(runner.setup())
    site = web.TCPSite(runner, "127.0.0.1", 0)
    loop.run_until_complete(site.start())
    port = site._server.sockets[0].getsockname()[1]  # noqa: SLF001

    import threading

    stop = threading.Event()

    def pump():
        while not stop.is_set():
            loop.run_until_complete(asyncio.sleep(0.02))

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{port}", received
    stop.set()
    t.join(2)
    loop.run_until_complete(runner.cleanup())
    loop.close()


def test_otlp_export_span_tree(collector):
    endpoint, received = collector
    exporter = OtlpHttpExporter(endpoint, service_name="test-svc",
                                flush_interval_s=0.1)
    tracer = Tracer(exporter=exporter)
    with tracer.span("parent", route="/x") as parent:
        with tracer.span("child") as child:
            pass
    exporter.flush()
    deadline = time.time() + 5
    while not received and time.time() < deadline:
        time.sleep(0.05)
    assert received, "collector saw no spans"
    spans = received[0]["resourceSpans"][0]["scopeSpans"][0]["spans"]
    by_name = {s["name"]: s for s in spans}
    assert set(by_name) == {"parent", "child"}
    assert by_name["child"]["traceId"] == by_name["parent"]["traceId"]
    assert by_name["child"]["parentSpanId"] == by_name["parent"]["spanId"]
    assert by_name["parent"]["status"]["code"] == 1
    attrs = {a["key"]: a["value"] for a in by_name["parent"]["attributes"]}
    assert attrs["route"]["stringValue"] == "/x"
    res_attrs = {a["key"]: a["value"]["stringValue"]
                 for a in received[0]["resourceSpans"][0]["resource"]["attributes"]}
    assert res_attrs["service.name"] == "test-svc"
    exporter.shutdown()


def test_tracer_from_config_log_fallback():
    t = tracer_from_config({"enabled": True, "sample_ratio": 0.5})
    assert t.sample_ratio == 0.5
    with t.span("x"):
        pass  # log exporter path: no crash


def test_otlp_json_encoding_golden():
    """Golden shape of one encoded span — the OTLP/HTTP JSON contract a
    collector actually parses (field names, string-typed int64s, status
    codes, attribute value tagging)."""
    from cyberfabric_core_tpu.modkit.telemetry import Span

    exporter = OtlpHttpExporter.__new__(OtlpHttpExporter)  # no thread/queue
    span = Span(name="llm.prefill", trace_id="ab" * 16, span_id="cd" * 8,
                parent_id="ef" * 8,
                attributes={"slot": 3, "coalesced": True, "dur": 1.5,
                            "request_id": "req-1"},
                status="error")
    span.start_unix_ns = 1_700_000_000_000_000_000
    out = exporter._encode(span, duration_ms=12.5)
    assert out == {
        "traceId": "ab" * 16,
        "spanId": "cd" * 8,
        "parentSpanId": "ef" * 8,
        "name": "llm.prefill",
        "kind": 2,
        "startTimeUnixNano": "1700000000000000000",
        "endTimeUnixNano": str(1_700_000_000_000_000_000 + 12_500_000),
        "attributes": [
            {"key": "slot", "value": {"intValue": "3"}},
            {"key": "coalesced", "value": {"boolValue": True}},
            {"key": "dur", "value": {"doubleValue": 1.5}},
            {"key": "request_id", "value": {"stringValue": "req-1"}},
        ],
        "status": {"code": 2},
    }


def test_flush_deadline_on_blackholed_collector():
    """flush() against a collector that accepts connections and never
    answers must return within its budget — teardown cannot hang."""
    import socket
    import threading

    sink = socket.socket()
    sink.bind(("127.0.0.1", 0))
    sink.listen(8)
    port = sink.getsockname()[1]
    try:
        exporter = OtlpHttpExporter(f"http://127.0.0.1:{port}",
                                    flush_interval_s=60.0)
        with Tracer(exporter=exporter).span("doomed"):
            pass
        t0 = time.monotonic()
        exporter.flush(timeout_s=1.0)
        assert time.monotonic() - t0 < 3.0
        # shutdown flushes with its own bound and must not hang either
        t0 = time.monotonic()
        exporter.shutdown()
        assert time.monotonic() - t0 < 4.0
    finally:
        sink.close()


def test_sampled_flag_round_trip_and_emit_span():
    """The W3C flags byte carries the sampling decision across threads:
    traceparent() renders it, span()/emit_span() honor it."""
    from cyberfabric_core_tpu.modkit.telemetry import SpanExporter

    class Collect(SpanExporter):
        def __init__(self):
            self.names = []

        def export(self, span, duration_ms):
            self.names.append(span.name)

    sink = Collect()
    tracer = Tracer(exporter=sink, sample_ratio=0.0)  # roots: never sampled
    with tracer.span("root") as root:
        assert root.sampled is False
        assert root.traceparent().endswith("-00")
    assert sink.names == []  # unsampled root exported nothing

    sampled_tp = f"00-{'aa' * 16}-{'bb' * 8}-01"
    unsampled_tp = f"00-{'aa' * 16}-{'bb' * 8}-00"
    # span() with an explicit traceparent inherits ITS decision, not the dice
    with tracer.span("child", traceparent=sampled_tp) as child:
        assert child.sampled is True and child.trace_id == "aa" * 16
    assert sink.names == ["child"]

    sink.names.clear()
    assert tracer.emit_span("retro", traceparent=unsampled_tp) is None
    span = tracer.emit_span("retro", traceparent=sampled_tp,
                            start_unix_ns=123, duration_ms=4.0, slot=1)
    assert span is not None and span.parent_id == "bb" * 8
    assert sink.names == ["retro"]
    disabled = Tracer(enabled=False, exporter=sink)
    assert disabled.emit_span("x", traceparent=sampled_tp) is None


def test_engine_decode_cost_analysis():
    """XLA's cost analysis of the served decode program, lowered from an
    engine's own operands (a lowering runs nothing and donates nothing)."""
    from cyberfabric_core_tpu.runtime.engine import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(EngineConfig(
        model="tiny-llama", max_seq_len=64, max_batch=2, decode_chunk=2,
        dtype="float32"), seed=0)
    try:
        compiled = eng._paged_decode_fn.lower(
            eng.params, *eng.pool.cache_operands(), eng._rows_dev,
            eng._last_tokens, eng._lengths_dev, eng._active_dev,
            eng._finished_dev, eng._slot_keys).compile()
    finally:
        eng.shutdown()
    out = xla_cost_summary(compiled)
    # the CPU's XLA reports flops; a backend with no cost model gives {}
    assert out.get("flops", 1.0) > 0 and out.get("bytes_accessed", 1.0) > 0
