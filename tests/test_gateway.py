"""API-gateway end-to-end tests over a real bound socket.

Reference analogue: api-gateway middleware tests + e2e HTTP suite (SURVEY §4).
"""

import asyncio
import json

import aiohttp
import pytest

from conftest import boot_stack, stop_stack
from cyberfabric_core_tpu.modkit import (
    Module,
    RestApiCapability,
    module,
)
from cyberfabric_core_tpu.modkit.errors import ProblemError
from cyberfabric_core_tpu.modkit.security import SecurityContext
from cyberfabric_core_tpu.modkit.sse import SSE_DONE, format_sse_json
from cyberfabric_core_tpu.gateway.middleware import SECURITY_CONTEXT_KEY, AuthnApi
from cyberfabric_core_tpu.gateway.validation import read_json


@pytest.fixture()
def gateway_app(fresh_registry):
    """Boot a host with the gateway + a sample module on an ephemeral port."""
    from cyberfabric_core_tpu.gateway.module import ApiGatewayModule  # registers

    fresh_registry._REGISTRATIONS.clear()  # drop leaked registrations
    # re-register the gateway (import side effects were cleared)
    from cyberfabric_core_tpu.modkit.registry import Registration

    gw_reg = Registration(
        name="api_gateway", cls=ApiGatewayModule, deps=(),
        capabilities=("rest_host", "stateful", "system"),
    )

    @module(name="sample", capabilities=["rest"])
    class SampleModule(Module, RestApiCapability):
        async def init(self, ctx):
            pass

        def register_rest(self, ctx, router, openapi):
            async def echo(request):
                body = await read_json(request)
                return {"echo": body, "tenant": request[SECURITY_CONTEXT_KEY].tenant_id}

            async def whoami(request):
                sc: SecurityContext = request[SECURITY_CONTEXT_KEY]
                return {"subject": sc.subject, "tenant": sc.tenant_id}

            async def boom(request):
                raise ProblemError.not_found("nothing here", code="thing_missing")

            async def crash(request):
                raise ValueError("unexpected explosion")

            async def stream(request):
                from aiohttp import web

                resp = web.StreamResponse(headers={"Content-Type": "text/event-stream"})
                await resp.prepare(request)
                for i in range(3):
                    await resp.write(format_sse_json({"i": i}))
                await resp.write(SSE_DONE)
                await resp.write_eof()
                return resp

            async def slow(request):
                await asyncio.sleep(5)
                return {"done": True}

            router.operation("POST", "/v1/echo", module="sample").public().handler(echo).register()
            router.operation("GET", "/v1/whoami", module="sample").auth_required().handler(whoami).register()
            router.operation("GET", "/v1/boom", module="sample").public().handler(boom).register()
            router.operation("GET", "/v1/crash", module="sample").public().handler(crash).register()
            router.operation("GET", "/v1/stream", module="sample").public().sse_response().handler(stream).register()
            router.operation("GET", "/v1/slow", module="sample").public().handler(slow).register()
            router.operation("GET", "/v1/limited", module="sample").public().rate_limit(rps=0.0001, burst=2).handler(whoami).register()

    loop = asyncio.new_event_loop()
    rt, base = loop.run_until_complete(boot_stack({"modules": {
        "api_gateway": {"config": {
            "bind_addr": "127.0.0.1:0", "auth_disabled": True,
            "timeout_secs": 0.5, "max_body_bytes": 2048,
        }},
        "sample": {},
    }}, extra=[gw_reg]))
    yield loop, base
    loop.run_until_complete(stop_stack(rt))
    loop.close()


def _req(loop, method, url, **kw):
    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.request(method, url, **kw) as r:
                body = await r.read()
                return r.status, dict(r.headers), body

    return loop.run_until_complete(go())


def test_health_and_healthz(gateway_app):
    loop, base = gateway_app
    status, _, body = _req(loop, "GET", f"{base}/health")
    assert status == 200 and json.loads(body)["status"] == "ok"
    # /healthz is the doctor's LIVENESS document now: process uptime +
    # event-loop heartbeat lag (the gateway's heartbeat task feeds it)
    status, _, body = _req(loop, "GET", f"{base}/healthz")
    doc = json.loads(body)
    assert status == 200 and doc["status"] == "ok" and "uptime_s" in doc
    # /readyz is public (load balancers probe unauthenticated) and reads
    # the degradation state machine. This gateway-only stack never booted
    # the monitoring module, so pin the process-global doctor to a fresh
    # config — earlier test files may have driven it through a chaos cycle
    from cyberfabric_core_tpu.modkit.doctor import (DoctorConfig,
                                                    default_doctor)

    default_doctor.configure(DoctorConfig())
    status, _, body = _req(loop, "GET", f"{base}/readyz")
    doc = json.loads(body)
    assert status == 200 and doc["status"] == "ready"
    assert doc["state"] == "healthy" and doc["reasons"] == []


def test_echo_and_request_id(gateway_app):
    loop, base = gateway_app
    status, headers, body = _req(loop, "POST", f"{base}/v1/echo", json={"a": 1})
    assert status == 200
    assert json.loads(body) == {"echo": {"a": 1}, "tenant": "default"}
    assert "x-request-id" in {k.lower() for k in headers}


def test_request_id_propagation(gateway_app):
    loop, base = gateway_app
    _, headers, _ = _req(loop, "GET", f"{base}/v1/whoami", headers={"x-request-id": "rid-42"})
    assert headers.get("x-request-id") == "rid-42"


def test_problem_error_mapping(gateway_app):
    loop, base = gateway_app
    status, headers, body = _req(loop, "GET", f"{base}/v1/boom")
    doc = json.loads(body)
    assert status == 404 and doc["code"] == "thing_missing"
    assert headers["Content-Type"].startswith("application/problem+json")
    assert doc["trace_id"]


def test_unhandled_error_is_500_problem(gateway_app):
    loop, base = gateway_app
    status, _, body = _req(loop, "GET", f"{base}/v1/crash")
    doc = json.loads(body)
    assert status == 500 and doc["code"] == "internal_error"
    assert "explosion" not in body.decode()  # no internals leaked


def test_malformed_json_is_400(gateway_app):
    loop, base = gateway_app
    status, _, body = _req(loop, "POST", f"{base}/v1/echo",
                           data=b"{not json", headers={"Content-Type": "application/json"})
    assert status == 400 and json.loads(body)["code"] == "malformed_json"


def test_mime_validation_415(gateway_app):
    loop, base = gateway_app
    status, _, body = _req(loop, "POST", f"{base}/v1/echo",
                           data=b"x=1", headers={"Content-Type": "application/x-www-form-urlencoded"})
    assert status == 415 and json.loads(body)["code"] == "unsupported_media_type"


def test_body_limit_413(gateway_app):
    loop, base = gateway_app
    status, _, body = _req(loop, "POST", f"{base}/v1/echo",
                           data=b"x" * 4096, headers={"Content-Type": "application/json"})
    assert status == 413


def test_timeout_504(gateway_app):
    loop, base = gateway_app
    status, _, body = _req(loop, "GET", f"{base}/v1/slow")
    assert status == 504 and json.loads(body)["code"] == "timeout"


def test_rate_limit_429(gateway_app):
    loop, base = gateway_app
    results = [_req(loop, "GET", f"{base}/v1/limited")[0] for _ in range(4)]
    assert results.count(200) == 2  # burst capacity
    assert results.count(429) == 2


def test_sse_stream_contract(gateway_app):
    loop, base = gateway_app

    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/v1/stream") as r:
                assert r.headers["Content-Type"].startswith("text/event-stream")
                return (await r.read()).decode()

    text = loop.run_until_complete(go())
    events = [l for l in text.split("\n\n") if l.startswith("data: ")]
    assert events[-1] == "data: [DONE]"
    assert json.loads(events[0][6:]) == {"i": 0}


def test_openapi_document(gateway_app):
    loop, base = gateway_app
    status, _, body = _req(loop, "GET", f"{base}/openapi.json")
    doc = json.loads(body)
    assert status == 200
    assert "/v1/echo" in doc["paths"]
    post = doc["paths"]["/v1/echo"]["post"]
    assert "security" not in post  # public
    who = doc["paths"]["/v1/whoami"]["get"]
    assert who["security"] == [{"bearerAuth": []}]
    # SSE op documents the stream contract
    assert "text/event-stream" in str(doc["paths"]["/v1/stream"]["get"]["responses"])


def test_docs_page(gateway_app):
    loop, base = gateway_app
    status, _, body = _req(loop, "GET", f"{base}/docs")
    assert status == 200 and b"/v1/echo" in body


def test_unknown_route_404(gateway_app):
    # fixture runs auth_disabled=True → unmatched paths surface as 404
    # problem documents (with auth ENABLED they fail closed as 401 — see
    # test_unknown_route_fails_closed_with_auth)
    loop, base = gateway_app
    status, headers, body = _req(loop, "GET", f"{base}/v1/nope")
    assert status == 404
    # RFC-9457 document with a request id, and the miss is OBSERVED: 404s
    # must land in http_requests_total or scanners become invisible to
    # dashboards — under the fixed <unmatched> label, not one label per
    # probed path (cardinality bomb; round-5 review findings)
    assert json.loads(body)["status"] == 404
    assert "x-request-id" in {k.lower() for k in headers}
    from cyberfabric_core_tpu.gateway.middleware import UNMATCHED_ROUTE_LABEL
    from cyberfabric_core_tpu.modkit.metrics import default_registry

    counter = default_registry.counter("http_requests_total")
    assert any(
        dict(key).get("route") == UNMATCHED_ROUTE_LABEL
        and dict(key).get("status") == "404"
        for key in counter._values
    )
    assert not any(
        dict(key).get("route") == "/v1/nope" for key in counter._values
    )


def test_unknown_route_fails_closed_with_auth(fresh_registry):
    """With auth ENABLED, unmatched paths return the same 401 as
    unauthenticated matched paths — no route enumeration via 404 vs 401
    (round-5 review finding; old auth_mw spec-less branch parity)."""
    from cyberfabric_core_tpu.gateway.module import ApiGatewayModule
    from cyberfabric_core_tpu.modkit.registry import Registration

    fresh_registry._REGISTRATIONS.clear()
    gw_reg = Registration(
        name="api_gateway", cls=ApiGatewayModule, deps=(),
        capabilities=("rest_host", "stateful", "system"),
    )

    @module(name="sample", capabilities=["rest"])
    class SampleModule(Module, RestApiCapability):
        async def init(self, ctx):
            pass

        def register_rest(self, ctx, router, openapi):
            async def whoami(request):
                return {"ok": True}

            router.operation("GET", "/v1/secured", module="sample") \
                .auth_required().handler(whoami).register()

    loop = asyncio.new_event_loop()
    rt, base = loop.run_until_complete(boot_stack({"modules": {
        "api_gateway": {"config": {"bind_addr": "127.0.0.1:0"}},
        "sample": {},
    }}, extra=[gw_reg]))
    try:
        s_matched, _, _ = _req(loop, "GET", f"{base}/v1/secured")
        s_unmatched, _, _ = _req(loop, "GET", f"{base}/v1/does-not-exist")
        assert s_matched == 401
        assert s_unmatched == 401  # indistinguishable from the matched route
        # builtins stay public even with auth enabled
        s_health, _, _ = _req(loop, "GET", f"{base}/healthz")
        assert s_health == 200
    finally:
        loop.run_until_complete(stop_stack(rt))
        loop.close()


def test_cors_preflight_and_error_headers(fresh_registry):
    """CORS with the pre-composed stack (round-5 review finding): browsers
    preflight OPTIONS against routes that only register POST — that must
    204 with CORS headers, not 405 without them; and cross-origin error
    responses (404) need CORS headers to be readable by the caller."""
    from cyberfabric_core_tpu.gateway.module import ApiGatewayModule
    from cyberfabric_core_tpu.modkit.registry import Registration

    fresh_registry._REGISTRATIONS.clear()
    gw_reg = Registration(
        name="api_gateway", cls=ApiGatewayModule, deps=(),
        capabilities=("rest_host", "stateful", "system"),
    )

    @module(name="sample", capabilities=["rest"])
    class SampleModule(Module, RestApiCapability):
        async def init(self, ctx):
            pass

        def register_rest(self, ctx, router, openapi):
            async def echo(request):
                return {"ok": True}

            router.operation("POST", "/v1/only-post", module="sample") \
                .public().handler(echo).register()

    loop = asyncio.new_event_loop()
    rt, base = loop.run_until_complete(boot_stack({"modules": {
        "api_gateway": {"config": {
            "bind_addr": "127.0.0.1:0", "auth_disabled": True,
            "cors_allow_origin": "https://app.example"}},
        "sample": {},
    }}, extra=[gw_reg]))
    try:
        # preflight against a POST-only route: 204 + CORS headers
        status, headers, _ = _req(loop, "OPTIONS", f"{base}/v1/only-post")
        assert status == 204
        assert headers.get("Access-Control-Allow-Origin") == "https://app.example"
        # preflight against an unknown path behaves the same (old layer-5)
        status, headers, _ = _req(loop, "OPTIONS", f"{base}/does/not/exist")
        assert status == 204
        assert headers.get("Access-Control-Allow-Origin") == "https://app.example"
        # normal responses carry the header via the per-route layer
        status, headers, _ = _req(loop, "POST", f"{base}/v1/only-post", json={})
        assert status == 200
        assert headers.get("Access-Control-Allow-Origin") == "https://app.example"
        # 404 problem documents are readable cross-origin too
        status, headers, _ = _req(loop, "GET", f"{base}/missing")
        assert status == 404
        assert headers.get("Access-Control-Allow-Origin") == "https://app.example"
    finally:
        loop.run_until_complete(stop_stack(rt))
        loop.close()
