"""Device-side observability: jax.profiler trace endpoints + OAGW GTS type
provisioning (SURVEY §5 tracing triple; §2.3 oagw GTS provisioning row)."""

import asyncio

import aiohttp
import pytest

from conftest import boot_stack, stop_stack


@pytest.fixture()
def stack(tmp_path):
    async def boot():
        rt, base = await boot_stack({
            "server": {"home_dir": str(tmp_path)},
            "modules": {
                "api_gateway": {"config": {"bind_addr": "127.0.0.1:0",
                                           "auth_disabled": True}},
                "tenant_resolver": {}, "credstore": {},
                "types_registry": {}, "monitoring": {},
                "oagw": {"config": {"allow_insecure_http": True,
                                    "allow_private_upstreams": True}},
            }})
        await asyncio.sleep(0)  # let the rest-phase GTS provisioning task run
        return rt, base

    loop = asyncio.new_event_loop()
    rt, base = loop.run_until_complete(boot())
    yield loop, base
    loop.run_until_complete(stop_stack(rt))
    loop.close()


def _req(loop, method, url, **kw):
    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.request(method, url, **kw) as r:
                return r.status, await r.json(content_type=None)

    return loop.run_until_complete(go())


def test_oagw_gts_types_provisioned(stack):
    loop, base = stack
    s, body = _req(loop, "GET", f"{base}/v1/types/resolve",
                   params={"id": "gts.x.core.oagw.upstream.v1~"})
    assert s == 200, body
    assert body["kind"] == "schema"
    assert "base_url" in body["body"]["properties"]
    s, body = _req(loop, "GET", f"{base}/v1/types/resolve",
                   params={"id": "gts.x.core.oagw.route.v1~"})
    assert s == 200 and "upstream_slug" in body["body"]["properties"]


def test_profiler_start_stop_produces_trace(stack, tmp_path):
    loop, base = stack
    s, body = _req(loop, "POST", f"{base}/v1/monitoring/profiler/start")
    assert s == 200 and body["status"] == "started"
    assert body["dir"].startswith(str(tmp_path))
    # double-start is a 409, not a silent second trace
    s, dup = _req(loop, "POST", f"{base}/v1/monitoring/profiler/start")
    assert s == 409 and dup["code"] == "profiler_running"

    # some device work lands inside the trace window
    import jax.numpy as jnp

    (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()

    s, body = _req(loop, "POST", f"{base}/v1/monitoring/profiler/stop")
    assert s == 200 and body["status"] == "stopped"
    assert body["files"], "trace dump produced no files"
    # stop without a running trace errors cleanly
    s, body = _req(loop, "POST", f"{base}/v1/monitoring/profiler/stop")
    assert s == 400 and body["code"] == "profiler_not_running"
