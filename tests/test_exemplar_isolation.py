"""users-info-grade isolation batteries over the FULL HTTP stack.

Reference: examples/modkit/users-info — its tests_tenant_scoping.rs,
tests_pdp_deny.rs and tests_resource_scoping.rs define what "tenant isolation
works" means (SURVEY §8.9/§8.10). Ported against this platform's real
modules: static-token authn (distinct subjects/roles/tenants), authz PDP
deny + owner_only constraint compiled into the AccessScope, and the secure
ORM enforcing it all the way down.
"""

import asyncio
import json

import aiohttp
import pytest

from conftest import boot_stack, stop_stack

TOKENS = {
    "tok-alice": {"subject": "alice", "tenant_id": "acme",
                  "roles": ["member"]},
    "tok-bob": {"subject": "bob", "tenant_id": "acme", "roles": ["member"]},
    "tok-admin": {"subject": "root-admin", "tenant_id": "acme",
                  "roles": ["admin"]},
    "tok-eve": {"subject": "eve", "tenant_id": "evil-corp",
                "roles": ["member"]},
    "tok-aud": {"subject": "auditor", "tenant_id": "acme",
                "roles": ["auditor"]},
}

AUTHZ_RULES = {
    # members may not touch the model registry's write side; auditors are
    # read-only everywhere it matters; owner_only pins members to their rows
    "member": {"deny": ["post_v1_model_registry_models",
                        "delete_v1_settings_key"],
               "owner_only": True},
    "auditor": {"deny": ["put_v1_settings_key", "delete_v1_settings_key",
                         "post_v1_model_registry_models"]},
}


@pytest.fixture(scope="module")
def stack():
    loop = asyncio.new_event_loop()
    rt, base = loop.run_until_complete(boot_stack({"modules": {
        "api_gateway": {"config": {"bind_addr": "127.0.0.1:0"}},
        "tenant_resolver": {"config": {"tenants": {
            "acme": {}, "evil-corp": {}}}},
        "authn_resolver": {"config": {"mode": "static", "tokens": TOKENS}},
        "authz_resolver": {"config": {"rules": AUTHZ_RULES}},
        "types_registry": {}, "module_orchestrator": {},
        "nodes_registry": {}, "model_registry": {},
        "llm_gateway": {}, "file_storage": {}, "credstore": {},
        "file_parser": {}, "serverless_runtime": {}, "monitoring": {},
        "user_settings": {},
    }}))
    yield loop, base
    loop.run_until_complete(stop_stack(rt))
    loop.close()


def _req(loop, method, url, token, json_body=None):
    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.request(method, url, json=json_body, headers={
                "Authorization": f"Bearer {token}"}) as r:
                try:
                    return r.status, await r.json(content_type=None)
                except Exception:  # noqa: BLE001
                    return r.status, await r.text()

    return loop.run_until_complete(go())


# ----------------------------------------------------------- tenant scoping
def test_tenant_scoping_settings(stack):
    loop, base = stack
    s, _ = _req(loop, "PUT", f"{base}/v1/settings/theme", "tok-alice",
                {"value": "dark"})
    assert s in (200, 204)
    # same tenant, same subject sees it
    s, body = _req(loop, "GET", f"{base}/v1/settings/theme", "tok-alice")
    assert s == 200 and body["value"] == "dark"
    # ANOTHER TENANT sees nothing — not a 403, a clean 404 (no existence leak)
    s, _ = _req(loop, "GET", f"{base}/v1/settings/theme", "tok-eve")
    assert s == 404


def test_tenant_scoping_credstore(stack):
    loop, base = stack
    s, _ = _req(loop, "PUT", f"{base}/v1/credstore/secrets/api-key",
                "tok-admin", {"value": "acme-secret"})
    assert s in (200, 204)
    s, body = _req(loop, "GET", f"{base}/v1/credstore/secrets/api-key",
                   "tok-admin")
    assert s == 200 and body["value"] == "acme-secret"
    s, _ = _req(loop, "GET", f"{base}/v1/credstore/secrets/api-key", "tok-eve")
    assert s == 404


def test_tenant_scoping_model_registry(stack):
    loop, base = stack
    s, _ = _req(loop, "POST", f"{base}/v1/model-registry/models", "tok-admin",
                {"provider_slug": "p", "provider_model_id": "m",
                 "approval_state": "approved"})
    assert s == 201
    s, body = _req(loop, "GET", f"{base}/v1/model-registry/models/p::m",
                   "tok-admin")
    assert s == 200
    # evil-corp neither resolves nor lists acme's model
    s, _ = _req(loop, "GET", f"{base}/v1/model-registry/models/p::m", "tok-eve")
    assert s == 404
    s, body = _req(loop, "GET", f"{base}/v1/model-registry/models", "tok-eve")
    assert s == 200 and body["items"] == []


# ----------------------------------------------------------- PDP deny
def test_pdp_deny_by_operation(stack):
    loop, base = stack
    # member role is denied registry writes by the PDP rule
    s, body = _req(loop, "POST", f"{base}/v1/model-registry/models",
                   "tok-alice", {"provider_slug": "x", "provider_model_id": "y"})
    assert s == 403, body
    # ...but reads pass
    s, _ = _req(loop, "GET", f"{base}/v1/model-registry/models", "tok-alice")
    assert s == 200
    # auditor may read settings but every mutation is denied
    s, _ = _req(loop, "GET", f"{base}/v1/settings", "tok-aud")
    assert s == 200
    s, _ = _req(loop, "PUT", f"{base}/v1/settings/x", "tok-aud", {"value": "v"})
    assert s == 403
    s, _ = _req(loop, "DELETE", f"{base}/v1/settings/x", "tok-aud")
    assert s == 403


def test_pdp_deny_does_not_leak_other_roles(stack):
    loop, base = stack
    # the admin role carries no deny rules: the same operations succeed
    s, _ = _req(loop, "PUT", f"{base}/v1/settings/admin-key", "tok-admin",
                {"value": "1"})
    assert s in (200, 204)
    s, _ = _req(loop, "DELETE", f"{base}/v1/settings/admin-key", "tok-admin")
    assert s in (200, 204)


# ----------------------------------------------------------- owner scoping
def test_owner_scoping_rows(stack):
    loop, base = stack
    # alice and bob share tenant acme; owner_only pins each to their rows
    s, _ = _req(loop, "PUT", f"{base}/v1/settings/private-a", "tok-alice",
                {"value": "alices"})
    assert s in (200, 204)
    s, _ = _req(loop, "PUT", f"{base}/v1/settings/private-b", "tok-bob",
                {"value": "bobs"})
    assert s in (200, 204)
    # each sees only their own rows in the list
    s, body = _req(loop, "GET", f"{base}/v1/settings", "tok-alice")
    keys = {i["key"] for i in body["items"]}
    assert "private-a" in keys and "private-b" not in keys
    # a direct read of the other's row: 404, not 403 (no existence oracle)
    s, _ = _req(loop, "GET", f"{base}/v1/settings/private-b", "tok-alice")
    assert s == 404
    s, body = _req(loop, "GET", f"{base}/v1/settings/private-b", "tok-bob")
    assert s == 200 and body["value"] == "bobs"


def test_owner_scoping_admin_sees_tenant(stack):
    loop, base = stack
    # the admin role has no owner_only constraint: whole-tenant visibility
    s, body = _req(loop, "GET", f"{base}/v1/settings", "tok-admin")
    assert s == 200
    keys = {i["key"] for i in body["items"]}
    assert {"private-a", "private-b"} <= keys


def test_unknown_token_rejected(stack):
    loop, base = stack
    s, _ = _req(loop, "GET", f"{base}/v1/settings", "tok-mallory")
    assert s == 401


# ----------------------------------------------------------- SSE events
def test_sse_setting_events_tenant_isolated(stack):
    """users-info sse_tests.rs parity: change events stream over SSE and are
    tenant-isolated — an acme subscriber sees acme writes, never evil-corp's."""
    loop, base = stack

    async def go():
        received = []
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/v1/settings/events", headers={
                    "Authorization": "Bearer tok-alice"}) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith("text/event-stream")

                async def reader():
                    async for raw in resp.content:
                        line = raw.decode().strip()
                        if line.startswith("data:"):
                            received.append(json.loads(line[5:]))

                task = asyncio.ensure_future(reader())
                await asyncio.sleep(0.2)  # subscription active
                # same-tenant write (bob@acme) and cross-tenant write (eve)
                async with s.put(f"{base}/v1/settings/sse-probe",
                                 json={"value": "x"},
                                 headers={"Authorization": "Bearer tok-bob"}) as r:
                    assert r.status in (200, 204)
                async with s.put(f"{base}/v1/settings/evil-probe",
                                 json={"value": "y"},
                                 headers={"Authorization": "Bearer tok-eve"}) as r:
                    assert r.status in (200, 204)
                async with s.delete(f"{base}/v1/settings/sse-probe", headers={
                        "Authorization": "Bearer tok-admin"}) as r:
                    # admin may delete; members are denied (AUTHZ_RULES)
                    assert r.status in (200, 204, 404)
                deadline = asyncio.get_event_loop().time() + 5
                while len(received) < 2 and asyncio.get_event_loop().time() < deadline:
                    await asyncio.sleep(0.05)
                task.cancel()
        return received

    events = loop.run_until_complete(go())
    kinds = {(e["type"], e["key"]) for e in events}
    assert ("setting.created", "sse-probe") in kinds
    # the cross-tenant write never reaches the acme stream
    assert all(e["key"] != "evil-probe" for e in events)
