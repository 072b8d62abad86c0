"""OAGW hardening + auth depth integration suite.

Reference analogue: oagw/tests/proxy_integration.rs (mock upstream) and
libs/modkit-auth tests: SSRF guardrails, redirect non-following, route CRUD
with method allowlist + header hygiene, OAuth2 client-credentials injection
with refresh, remote JWKS fetch with mid-stream rotation.
"""

import asyncio
import json
import time
import zlib

import aiohttp
import pytest
from aiohttp import web

from conftest import boot_stack, stop_stack
from cyberfabric_core_tpu.modkit.jwt import encode_hs256
from cyberfabric_core_tpu.modkit.registry import Registration


@pytest.fixture(scope="module")
def stack():
    """Gateway + credstore + oagw + a mock upstream with auth/token endpoints."""
    from cyberfabric_core_tpu.modkit import registry as reg

    saved = list(reg._REGISTRATIONS)
    reg._REGISTRATIONS.clear()
    from cyberfabric_core_tpu.gateway.module import ApiGatewayModule
    from cyberfabric_core_tpu.modules.credstore import CredStoreModule
    from cyberfabric_core_tpu.modules.oagw import OagwModule
    from cyberfabric_core_tpu.modules.resolvers import TenantResolverModule

    state = {"tokens_issued": 0, "seen_headers": [], "auth_seen": [],
             "expires_in": 3600}

    async def boot():
        mock_app = web.Application()

        async def echo(request: web.Request):
            state["seen_headers"].append(dict(request.headers))
            state["auth_seen"].append(request.headers.get("Authorization"))
            return web.json_response({
                "path": request.path, "method": request.method,
                "auth": request.headers.get("Authorization"),
                "api_key": request.headers.get("X-Api-Key"),
                "cookie": request.headers.get("Cookie"),
                "x_internal": request.headers.get("X-Internal"),
            })

        async def token(request: web.Request):
            form = await request.post()
            if form["grant_type"] != "client_credentials" or \
                    form["client_secret"] != "s3cret":
                return web.json_response({"error": "invalid_client"}, status=401)
            state["tokens_issued"] += 1
            return web.json_response({
                "access_token": f"tok-{state['tokens_issued']}",
                "token_type": "Bearer", "expires_in": state["expires_in"]})

        async def redirector(request: web.Request):
            raise web.HTTPFound("http://169.254.169.254/latest/meta-data/")

        async def flaky(request: web.Request):
            return web.Response(status=503, text="boom")

        mock_app.router.add_route("*", "/api/echo", echo)
        mock_app.router.add_route("*", "/deep/api/echo", echo)
        mock_app.router.add_post("/oauth/token", token)
        mock_app.router.add_get("/redir", redirector)
        mock_app.router.add_get("/flaky", flaky)
        runner = web.AppRunner(mock_app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        mock_port = site._server.sockets[0].getsockname()[1]  # noqa: SLF001

        regs = [
            Registration("api_gateway", ApiGatewayModule, (),
                         ("rest_host", "stateful", "system")),
            Registration("tenant_resolver", TenantResolverModule, (), ("system",)),
            Registration("credstore", CredStoreModule, ("tenant_resolver",),
                         ("db", "rest")),
            Registration("oagw", OagwModule, ("credstore",), ("db", "rest")),
        ]
        rt, base = await boot_stack({"modules": {
            "api_gateway": {"config": {"bind_addr": "127.0.0.1:0",
                                       "auth_disabled": True}},
            "tenant_resolver": {}, "credstore": {},
            "oagw": {"config": {"allow_insecure_http": True,
                                "allow_private_upstreams": True}},
        }}, extra=regs)
        return rt, runner, base, mock_port

    loop = asyncio.new_event_loop()
    rt, runner, base, mock_port = loop.run_until_complete(boot())
    yield loop, base, mock_port, state, rt
    loop.run_until_complete(stop_stack(rt))
    loop.run_until_complete(runner.cleanup())
    loop.close()
    reg._REGISTRATIONS[:] = saved


def _req(loop, method, url, json_body=None, headers=None):
    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.request(method, url, json=json_body,
                                 headers=headers,
                                 allow_redirects=False) as resp:
                try:
                    return resp.status, await resp.json(content_type=None)
                except Exception:  # noqa: BLE001
                    return resp.status, await resp.text()

    return loop.run_until_complete(go())


def test_https_required_by_default():
    """A service configured WITHOUT allow_insecure_http refuses http:// (unit
    level: the stack fixture enables it, so check the validation directly)."""
    from cyberfabric_core_tpu.modkit.errors import ProblemError
    from cyberfabric_core_tpu.modules.oagw import OagwService

    svc = OagwService.__new__(OagwService)
    svc.allow_insecure_http = False
    svc.allow_private_upstreams = False
    svc._db = None
    with pytest.raises(ProblemError) as e:
        OagwService.create_upstream(svc, None, {
            "slug": "x", "base_url": "http://evil.internal"})
    assert "https" in str(e.value.problem.detail)


def test_private_destination_rejected():
    from cyberfabric_core_tpu.modkit.errors import ProblemError
    from cyberfabric_core_tpu.modules.oagw import _assert_public_destination

    loop = asyncio.new_event_loop()
    for host in ("127.0.0.1", "10.0.0.8", "169.254.169.254", "192.168.1.1",
                 "localhost"):
        with pytest.raises(ProblemError):
            loop.run_until_complete(_assert_public_destination(host))
    # a public address passes
    loop.run_until_complete(_assert_public_destination("93.184.216.34"))
    loop.close()


def test_route_crud_method_allowlist_and_header_hygiene(stack):
    loop, base, mock_port, state, _ = stack
    status, _ = _req(loop, "POST", f"{base}/v1/oagw/upstreams", json_body={
        "slug": "up1", "base_url": f"http://127.0.0.1:{mock_port}"})
    assert status == 201
    status, body = _req(loop, "POST", f"{base}/v1/oagw/routes", json_body={
        "slug": "narrow", "upstream_slug": "up1", "path_prefix": "deep",
        "methods": ["GET"], "strip_headers": ["x-internal"]})
    assert status == 201, body

    # allowed method + path prefix + extra header stripped
    status, body = _req(loop, "GET", f"{base}/v1/oagw/route/narrow/api/echo",
                        headers={"X-Internal": "secret-host-info",
                                 "Cookie": "session=abc"})
    assert status == 200
    assert body["path"] == "/deep/api/echo"
    assert body["x_internal"] is None        # route-level strip
    assert body["cookie"] is None            # baseline hygiene

    # disallowed method → 405
    status, body = _req(loop, "POST", f"{base}/v1/oagw/route/narrow/api/echo")
    assert status == 405

    # unknown upstream on route creation → 404
    status, _ = _req(loop, "POST", f"{base}/v1/oagw/routes", json_body={
        "slug": "ghost", "upstream_slug": "nope"})
    assert status == 404

    status, body = _req(loop, "GET", f"{base}/v1/oagw/routes")
    assert status == 200 and {r["slug"] for r in body["items"]} == {"narrow"}
    status, _ = _req(loop, "DELETE", f"{base}/v1/oagw/routes/narrow")
    assert status in (200, 204)


def test_redirects_not_followed(stack):
    loop, base, mock_port, state, _ = stack
    _req(loop, "POST", f"{base}/v1/oagw/upstreams", json_body={
        "slug": "redir", "base_url": f"http://127.0.0.1:{mock_port}"})
    status, _ = _req(loop, "GET", f"{base}/v1/oagw/proxy/redir/redir")
    assert status == 302  # passed through, never chased into the metadata IP


def test_oauth2_client_credentials_injection_and_cache(stack):
    loop, base, mock_port, state, _ = stack
    # put the client secret in credstore
    status, _ = _req(loop, "PUT", f"{base}/v1/credstore/secrets/oauth-client",
                     json_body={"value": "s3cret"})
    assert status in (200, 204)
    status, body = _req(loop, "POST", f"{base}/v1/oagw/upstreams", json_body={
        "slug": "oauth-up", "base_url": f"http://127.0.0.1:{mock_port}",
        "auth": {"type": "oauth2", "secret_ref": "oauth-client",
                 "token_url": f"http://127.0.0.1:{mock_port}/oauth/token",
                 "client_id": "svc-a", "scope": "read"}})
    assert status == 201, body

    before = state["tokens_issued"]
    status, body = _req(loop, "GET", f"{base}/v1/oagw/proxy/oauth-up/api/echo")
    assert status == 200
    assert body["auth"] == f"Bearer tok-{before + 1}"
    # second call reuses the cached token — no second token fetch
    status, body = _req(loop, "GET", f"{base}/v1/oagw/proxy/oauth-up/api/echo")
    assert body["auth"] == f"Bearer tok-{before + 1}"
    assert state["tokens_issued"] == before + 1


def test_oauth2_token_refresh_on_expiry(stack):
    loop, base, mock_port, state, rt = stack
    _req(loop, "PUT", f"{base}/v1/credstore/secrets/oauth-client2",
         json_body={"value": "s3cret"})
    state["expires_in"] = 1  # shorter than the refresh margin → always refetch
    _req(loop, "POST", f"{base}/v1/oagw/upstreams", json_body={
        "slug": "oauth-exp", "base_url": f"http://127.0.0.1:{mock_port}",
        "auth": {"type": "oauth2", "secret_ref": "oauth-client2",
                 "token_url": f"http://127.0.0.1:{mock_port}/oauth/token",
                 "client_id": "svc-b"}})
    status, body1 = _req(loop, "GET", f"{base}/v1/oagw/proxy/oauth-exp/api/echo")
    status, body2 = _req(loop, "GET", f"{base}/v1/oagw/proxy/oauth-exp/api/echo")
    assert body1["auth"] != body2["auth"], "expired token was not refreshed"
    state["expires_in"] = 3600


# --------------------------------------------------------------- JWKS


@pytest.fixture()
def jwks_server():
    """Local JWKS endpoint whose key set can be rotated mid-test."""
    state = {"kids": {"k1": "secret-one"}, "fetches": 0}

    async def jwks(request: web.Request):
        state["fetches"] += 1
        import base64

        keys = [{"kty": "oct", "kid": kid, "alg": "HS256",
                 "k": base64.urlsafe_b64encode(sec.encode()).decode().rstrip("=")}
                for kid, sec in state["kids"].items()]
        return web.json_response({"keys": keys})

    loop = asyncio.new_event_loop()
    app = web.Application()
    app.router.add_get("/jwks.json", jwks)
    runner = web.AppRunner(app)
    loop.run_until_complete(runner.setup())
    site = web.TCPSite(runner, "127.0.0.1", 0)
    loop.run_until_complete(site.start())
    port = site._server.sockets[0].getsockname()[1]  # noqa: SLF001
    yield loop, f"http://127.0.0.1:{port}/jwks.json", state
    loop.run_until_complete(runner.cleanup())
    loop.close()


def test_jwks_fetch_validate_and_rotate(jwks_server):
    loop, url, state = jwks_server
    from cyberfabric_core_tpu.modules.resolvers import JwtAuthnResolver

    resolver = JwtAuthnResolver({"jwks_url": url, "jwks_negative_cache_s": 0.0})
    now = int(time.time())

    tok1 = encode_hs256({"sub": "u1", "tenant_id": "t1", "exp": now + 60},
                        "secret-one", kid="k1")
    ctx = loop.run_until_complete(resolver.authenticate(tok1, {}))
    assert ctx.subject == "u1" and ctx.tenant_id == "t1"
    assert state["fetches"] == 1

    # cached: another validation does not refetch
    loop.run_until_complete(resolver.authenticate(tok1, {}))
    assert state["fetches"] == 1

    # ROTATION: IdP swaps to k2; a token with the new kid triggers a refetch
    state["kids"] = {"k2": "secret-two"}
    tok2 = encode_hs256({"sub": "u2", "tenant_id": "t1", "exp": now + 60},
                        "secret-two", kid="k2")
    ctx = loop.run_until_complete(resolver.authenticate(tok2, {}))
    assert ctx.subject == "u2"
    assert state["fetches"] == 2

    # the old kid is gone now — its token fails cleanly
    from cyberfabric_core_tpu.modkit.errors import ProblemError
    with pytest.raises(ProblemError):
        loop.run_until_complete(resolver.authenticate(tok1, {}))


def test_jwks_unknown_kid_negative_cache(jwks_server):
    loop, url, state = jwks_server
    from cyberfabric_core_tpu.modkit.jwks import JwksCache
    from cyberfabric_core_tpu.modkit.jwt import JwtError

    cache = JwksCache(jwks_url=url, negative_cache_s=60.0)
    loop.run_until_complete(cache.get_key("k1"))
    fetches = state["fetches"]
    # a bogus kid causes ONE rotation refetch, then is negative-cached
    for _ in range(3):
        with pytest.raises(JwtError):
            loop.run_until_complete(cache.get_key("bogus"))
    assert state["fetches"] == fetches + 1


def test_oauth2_token_url_validated(stack):
    """token_url is an outbound destination too — scheme rules apply at
    creation (and the resolver/destination check applies at fetch)."""
    from cyberfabric_core_tpu.modkit.errors import ProblemError
    from cyberfabric_core_tpu.modules.oagw import OagwService

    svc = OagwService.__new__(OagwService)
    svc.allow_insecure_http = False
    svc.allow_private_upstreams = False
    svc._db = None
    with pytest.raises(ProblemError) as e:
        OagwService.create_upstream(svc, None, {
            "slug": "x", "base_url": "https://api.example.com",
            "auth": {"type": "oauth2", "secret_ref": "k",
                     "token_url": "http://169.254.169.254/token",
                     "client_id": "c"}})
    assert e.value.problem.code == "insecure_upstream"


def test_oidc_discovery_resolves_token_endpoint():
    """token_url="" + issuer=… resolves the endpoint from the issuer's
    /.well-known/openid-configuration (ref: modkit-auth oauth2/discovery.rs),
    caches the result, and rejects an issuer-mismatched document."""
    from cyberfabric_core_tpu.modkit.oauth2 import (
        ClientCredentialsTokenSource, OAuth2Error)

    loop = asyncio.new_event_loop()
    state = {"discoveries": 0, "tokens": 0, "issuer_override": None}

    async def boot():
        app = web.Application()

        async def well_known(request: web.Request):
            state["discoveries"] += 1
            issuer = state["issuer_override"] or f"http://127.0.0.1:{port}"
            return web.json_response({
                "issuer": issuer,
                "token_endpoint": f"http://127.0.0.1:{port}/discovered/token"})

        async def token(request: web.Request):
            state["tokens"] += 1
            return web.json_response({
                "access_token": f"disc-tok-{state['tokens']}",
                "expires_in": 3600})

        app.router.add_get("/.well-known/openid-configuration", well_known)
        app.router.add_post("/discovered/token", token)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        return runner, site._server.sockets[0].getsockname()[1]

    runner, port = None, 0

    async def run_all():
        nonlocal runner, port
        runner, port = await boot()
        try:
            src = ClientCredentialsTokenSource(
                token_url="", client_id="svc", client_secret="s3cret",
                issuer=f"http://127.0.0.1:{port}")
            tok = await src.get_token()
            assert tok == "disc-tok-1"
            # a second refresh reuses the cached discovery document
            src.invalidate()
            assert await src.get_token() == "disc-tok-2"
            assert state["discoveries"] == 1

            # issuer mismatch in the metadata document is rejected
            state["issuer_override"] = "http://evil.example"
            bad = ClientCredentialsTokenSource(
                token_url="", client_id="svc", client_secret="s3cret",
                issuer=f"http://127.0.0.1:{port}")
            with pytest.raises(OAuth2Error, match="issuer mismatch"):
                await bad.get_token()

            # neither token_url nor issuer configured → loud error
            none = ClientCredentialsTokenSource(
                token_url="", client_id="svc", client_secret="s3cret")
            with pytest.raises(OAuth2Error, match="token_url or issuer"):
                await none.get_token()
        finally:
            await runner.cleanup()

    try:
        loop.run_until_complete(run_all())
    finally:
        loop.close()


def test_pdf_decompression_bomb_capped():
    from cyberfabric_core_tpu.modkit.errors import ProblemError
    """A small PDF inflating beyond the cap is rejected, not OOM'd."""
    bomb = zlib.compress(b"BT " + b"(x) Tj " * 1 + b"A" * (80 * 1024 * 1024), 9)
    pdf = (b"%PDF-1.4\n1 0 obj\n<< /Filter /FlateDecode >>\nstream\n"
           + bomb + b"endstream\nendobj\ntrailer\n%%EOF")
    from cyberfabric_core_tpu.modules.file_parser_backends import parse_pdf
    with pytest.raises(ProblemError):
        parse_pdf(pdf)


def test_jwks_same_kid_new_material_bumps_generation(jwks_server):
    """Round-3 advisory: a rotation that REUSES a kid with new key material
    must bump the cache generation (the validated-token cache keys on it), or
    tokens signed by the withdrawn key keep validating for token_cache_ttl_s."""
    loop, url, state = jwks_server
    from cyberfabric_core_tpu.modkit.jwks import JwksCache

    cache = JwksCache(jwks_url=url, cache_ttl_s=0.0, negative_cache_s=0.0)
    loop.run_until_complete(cache.get_key("k1"))
    gen0 = cache.generation
    # same kid set, same material: no bump on refetch
    loop.run_until_complete(cache.get_key("k1"))
    assert cache.generation == gen0
    # same kid, NEW secret: must bump
    state["kids"] = {"k1": "secret-two"}
    loop.run_until_complete(cache.get_key("k1"))
    assert cache.generation == gen0 + 1


def test_token_cache_hit_isolates_claims():
    """Round-3 advisory, strengthened in round 5: one handler's claims
    mutation must never leak into the next request's identity. The claims
    tree is now deep-frozen at validation (MappingProxyType + tuples), so
    mutation attempts RAISE instead of being absorbed by a per-hit deepcopy
    — stronger isolation at zero per-request copy cost."""
    import asyncio as _asyncio

    import pytest

    from cyberfabric_core_tpu.modules.resolvers import JwtAuthnResolver

    resolver = JwtAuthnResolver(
        {"keys": {"k1": {"alg": "HS256", "secret": "s"}}})
    now = int(time.time())
    tok = encode_hs256({"sub": "u1", "tenant_id": "t1", "exp": now + 60,
                        "extra": "orig",
                        "realm_access": {"roles": ["user"]}}, "s", kid="k1")
    loop = _asyncio.new_event_loop()
    try:
        ctx1 = loop.run_until_complete(resolver.authenticate(tok, {}))
        with pytest.raises(TypeError):
            ctx1.claims["extra"] = "TAMPERED"
        with pytest.raises(TypeError):
            ctx1.claims["injected"] = True
        # nested containers must be frozen too (IdP claims nest)
        with pytest.raises((TypeError, AttributeError)):
            ctx1.claims["realm_access"]["roles"].append("admin")
        ctx2 = loop.run_until_complete(resolver.authenticate(tok, {}))
        assert ctx2.claims.get("extra") == "orig"
        assert "injected" not in ctx2.claims
        assert tuple(ctx2.claims["realm_access"]["roles"]) == ("user",)
        # a cache HIT hands out the same frozen identity, still untainted
        ctx3 = loop.run_until_complete(resolver.authenticate(tok, {}))
        assert tuple(ctx3.claims["realm_access"]["roles"]) == ("user",)
    finally:
        loop.close()
