"""External provider path: non-managed model → OAGW upstream → OpenAI-dialect
SSE normalized back to our chunk contract (mock provider, reference
mock-upstream pattern)."""

import asyncio
import json

import aiohttp
import pytest
from aiohttp import web

from conftest import boot_stack, stop_stack


@pytest.fixture()
def stack(fresh_registry):
    from cyberfabric_core_tpu.modkit.registry import Registration
    from cyberfabric_core_tpu.gateway.module import ApiGatewayModule
    from cyberfabric_core_tpu.modules.credstore import CredStoreModule
    from cyberfabric_core_tpu.modules.llm_gateway.module import LlmGatewayModule
    from cyberfabric_core_tpu.modules.model_registry import ModelRegistryModule
    from cyberfabric_core_tpu.modules.oagw import OagwModule
    from cyberfabric_core_tpu.modules.resolvers import TenantResolverModule

    fresh_registry._REGISTRATIONS.clear()
    regs = [
        Registration("api_gateway", ApiGatewayModule, (), ("rest_host", "stateful", "system")),
        Registration("tenant_resolver", TenantResolverModule, (), ("system",)),
        Registration("credstore", CredStoreModule, ("tenant_resolver",), ("db", "rest")),
        Registration("oagw", OagwModule, ("credstore",), ("db", "rest")),
        Registration("model_registry", ModelRegistryModule, (), ("db", "rest")),
        Registration("llm_gateway", LlmGatewayModule, ("model_registry",),
                     ("rest", "stateful")),
    ]

    seen_requests: list[dict] = []

    async def boot():
        # mock OpenAI-compatible provider
        mock = web.Application()

        async def chat(request):
            body = await request.json()
            seen_requests.append({"auth": request.headers.get("Authorization"),
                                  "body": body})
            resp = web.StreamResponse(headers={"Content-Type": "text/event-stream"})
            await resp.prepare(request)
            for piece in ("Hel", "lo!"):
                frame = {"choices": [{"delta": {"content": piece}}]}
                await resp.write(f"data: {json.dumps(frame)}\n\n".encode())
            final = {"choices": [{"delta": {}, "finish_reason": "stop"}],
                     "usage": {"prompt_tokens": 9, "completion_tokens": 2}}
            await resp.write(f"data: {json.dumps(final)}\n\n".encode())
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
            return resp

        mock.router.add_post("/v1/chat/completions", chat)
        runner = web.AppRunner(mock)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        mock_port = site._server.sockets[0].getsockname()[1]  # noqa: SLF001

        rt, base = await boot_stack({"modules": {
            "api_gateway": {"config": {"bind_addr": "127.0.0.1:0",
                                       "auth_disabled": True}},
            "tenant_resolver": {}, "credstore": {}, "oagw": {"config": {
                "allow_insecure_http": True, "allow_private_upstreams": True}},
            "model_registry": {"config": {
                "seed_tenant": "default",
                "models": [{"provider_slug": "openai-mock",
                            "provider_model_id": "gpt-x",
                            "approval_state": "approved", "managed": False}]}},
            "llm_gateway": {},
        }}, extra=regs)

        async with aiohttp.ClientSession() as s:
            # provider credential + upstream named by provider_slug
            await s.put(f"{base}/v1/credstore/secrets/openai-key",
                        json={"value": "sk-live-xyz"})
            await s.post(f"{base}/v1/oagw/upstreams", json={
                "slug": "openai-mock",
                "base_url": f"http://127.0.0.1:{mock_port}/v1",
                "auth": {"type": "bearer", "secret_ref": "openai-key"}})
        return rt, runner, base

    loop = asyncio.new_event_loop()
    rt, runner, base = loop.run_until_complete(boot())
    yield loop, base, seen_requests
    loop.run_until_complete(stop_stack(rt))
    loop.run_until_complete(runner.cleanup())
    loop.close()


def test_external_provider_chat(stack):
    loop, base, seen = stack

    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.post(f"{base}/v1/chat/completions", json={
                "model": "openai-mock::gpt-x",
                "messages": [{"role": "user",
                              "content": [{"type": "text", "text": "hi"},
                                          {"type": "text", "text": " there"}]}],
                "max_tokens": 16, "temperature": 0.5,
            }) as r:
                return r.status, json.loads(await r.read())

    status, body = loop.run_until_complete(go())
    assert status == 200, body
    assert body["content"][0]["text"] == "Hello!"
    assert body["model_used"] == "openai-mock::gpt-x"
    assert body["usage"] == {"input_tokens": 9, "output_tokens": 2}
    assert body["finish_reason"] == "stop"
    # provider saw injected credential + translated flat messages
    assert seen[0]["auth"] == "Bearer sk-live-xyz"
    assert seen[0]["body"]["messages"] == [{"role": "user", "content": "hi there"}]
    assert seen[0]["body"]["model"] == "gpt-x"
    assert seen[0]["body"]["temperature"] == 0.5


def test_external_provider_streaming(stack):
    loop, base, seen = stack

    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.post(f"{base}/v1/chat/completions", json={
                "model": "openai-mock::gpt-x", "stream": True,
                "messages": [{"role": "user",
                              "content": [{"type": "text", "text": "hi"}]}]},
            ) as r:
                assert r.headers["Content-Type"].startswith("text/event-stream")
                return (await r.read()).decode()

    text = loop.run_until_complete(go())
    frames = [f for f in text.split("\n\n") if f.startswith("data: ")]
    assert frames[-1] == "data: [DONE]"
    chunks = [json.loads(f[6:]) for f in frames[:-1]]
    joined = "".join(c["delta"].get("content", "") for c in chunks)
    assert joined == "Hello!"
    assert chunks[-1]["finish_reason"] == "stop"
