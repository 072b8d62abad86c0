"""Durable llm-gateway jobs/batches (round-3 verdict item 7): async-job and
batch state lives in the module's sqlite DB, and a host restart RESUMES
pending work (or fails it loudly) instead of vanishing it.

Restart is simulated for real: boot the full stack on a file-backed DbManager,
shut it down, seed/inspect rows, boot a second runtime over the same files.
Ref: modules/llm-gateway/docs/DESIGN.md:884-889 (async-job state must
survive in a shared store, not process memory)."""

import asyncio
import json

import aiohttp
import pytest

from conftest import boot_stack, stop_stack


def _config(home_dir):
    return {
        "server": {"home_dir": str(home_dir)},
        "modules": {
            "api_gateway": {"config": {"bind_addr": "127.0.0.1:0",
                                       "auth_disabled": True}},
            "tenant_resolver": {}, "authn_resolver": {}, "authz_resolver": {},
            "model_registry": {"config": {"models": [
                {"provider_slug": "local", "provider_model_id": "tiny-llama",
                 "approval_state": "approved", "managed": True,
                 "architecture": "llama",
                 "capabilities": {"chat": True, "streaming": True},
                 "engine_options": {"model_config": "tiny-llama",
                                    "max_seq_len": 128, "max_batch": 2}},
            ]}},
            "llm_gateway": {},
        }}


async def _boot(home_dir):
    """A file-backed DbManager, so that a second boot finds the first one's rows."""
    from cyberfabric_core_tpu.modkit.db import DbManager

    return await boot_stack(_config(home_dir),
                            db_manager=DbManager(home_dir=home_dir))


def test_jobs_and_batches_survive_restart(tmp_path):
    async def first_boot():
        rt, base = await _boot(tmp_path)
        try:
            async with aiohttp.ClientSession() as s:
                # a completed job (runs to completion while we wait)
                async with s.post(f"{base}/v1/chat/completions", json={
                    "model": "local::tiny-llama", "async": True,
                    "messages": [{"role": "user", "content": [
                        {"type": "text", "text": "hi"}]}],
                    "max_tokens": 4,
                }) as r:
                    assert r.status == 202, await r.text()
                    job = await r.json()
                for _ in range(600):
                    async with s.get(f"{base}/v1/jobs/{job['id']}") as r:
                        j = await r.json()
                    if j["status"] in ("completed", "failed"):
                        break
                    await asyncio.sleep(0.1)
                assert j["status"] == "completed", j
                # a batch that completes too
                async with s.post(f"{base}/v1/batches", json={
                    "requests": [{"custom_id": "a", "request": {
                        "model": "local::tiny-llama",
                        "messages": [{"role": "user", "content": [
                            {"type": "text", "text": "x"}]}],
                        "max_tokens": 2}}],
                }) as r:
                    assert r.status == 202, await r.text()
                    batch = await r.json()
                for _ in range(600):
                    async with s.get(f"{base}/v1/batches/{batch['id']}") as r:
                        b = await r.json()
                    if b["status"] in ("completed", "failed"):
                        break
                    await asyncio.sleep(0.1)
                assert b["status"] == "completed", b
            return job["id"], batch["id"]
        finally:
            await stop_stack(rt)

    loop = asyncio.new_event_loop()
    try:
        job_id, batch_id = loop.run_until_complete(first_boot())
    finally:
        loop.close()

    # the rows are on disk between boots
    db_file = tmp_path / "db" / "llm_gateway.sqlite"
    assert db_file.exists()

    # simulate a crash leftover: one job mid-flight, one still pending, and
    # an in-progress batch with one item already done, one not
    import sqlite3

    conn = sqlite3.connect(db_file)
    req = json.dumps({"model": "local::tiny-llama",
                      "messages": [{"role": "user", "content": [
                          {"type": "text", "text": "resume me"}]}],
                      "max_tokens": 2})
    conn.execute(
        "INSERT INTO llm_jobs (id, tenant_id, status, request, created_at, "
        "expires_at) VALUES ('job-interrupted', 'default', 'running', ?, "
        "'2026-01-01T00:00:00', '2099-01-01T00:00:00')", (req,))
    # pending leftover carries the submitter's durable principal (round-4
    # advisory: recovery must run AS the submitter, not tenant-anonymous)
    principal = json.dumps({"subject": "user-42", "roles": ["llm-user"],
                            "scopes": ["llm.run"]})
    conn.execute(
        "INSERT INTO llm_jobs (id, tenant_id, status, request, created_at, "
        "expires_at, principal) VALUES ('job-pending', 'default', 'pending', "
        "?, '2026-01-01T00:00:00', '2099-01-01T00:00:00', ?)",
        (req, principal))
    reqs = json.dumps([
        {"custom_id": "done", "request": json.loads(req),
         "result": {"content": [{"type": "text", "text": "KEPT"}]},
         "error": None},
        {"custom_id": "todo", "request": json.loads(req),
         "result": None, "error": None},
    ])
    conn.execute(
        "INSERT INTO llm_batches (id, tenant_id, status, requests, created_at)"
        " VALUES ('batch-resume', 'default', 'in_progress', ?, "
        "'2026-01-01T00:00:00')", (reqs,))
    conn.commit()
    conn.close()

    async def second_boot():
        rt, base = await _boot(tmp_path)
        try:
            async with aiohttp.ClientSession() as s:
                # completed work from the first boot is still visible
                async with s.get(f"{base}/v1/jobs/{job_id}") as r:
                    assert r.status == 200
                    assert (await r.json())["status"] == "completed"
                async with s.get(f"{base}/v1/batches/{batch_id}") as r:
                    assert r.status == 200
                    assert (await r.json())["status"] == "completed"
                # mid-flight job fails LOUDLY, not silently re-run
                async with s.get(f"{base}/v1/jobs/job-interrupted") as r:
                    j = await r.json()
                assert j["status"] == "failed"
                assert "restarted" in j["error"]["detail"]
                # pending job RESUMES and completes
                for _ in range(600):
                    async with s.get(f"{base}/v1/jobs/job-pending") as r:
                        j = await r.json()
                    if j["status"] in ("completed", "failed"):
                        break
                    await asyncio.sleep(0.1)
                assert j["status"] == "completed", j
                # batch resumes: finished item keeps its result, the other runs
                for _ in range(600):
                    async with s.get(f"{base}/v1/batches/batch-resume") as r:
                        b = await r.json()
                    if b["status"] in ("completed", "failed"):
                        break
                    await asyncio.sleep(0.1)
                assert b["status"] == "completed", b
                done = next(i for i in b["requests"]
                            if i["custom_id"] == "done")
                assert done["result"]["content"][0]["text"] == "KEPT"
                todo = next(i for i in b["requests"]
                            if i["custom_id"] == "todo")
                assert todo["result"] is not None
        finally:
            await stop_stack(rt)

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(second_boot())
    finally:
        loop.close()

    # the submit path persisted a principal with the durable row (round-4
    # advisory) — check the first boot's job row directly
    conn = sqlite3.connect(db_file)
    row = conn.execute("SELECT principal FROM llm_jobs WHERE id=?",
                       (job_id,)).fetchone()
    conn.close()
    assert row is not None and row[0] is not None
    assert json.loads(row[0])["subject"] == "anonymous"


def test_ctx_from_principal_reconstruction():
    """Recovery rebuilds the submitter's identity from the persisted
    principal; legacy rows (no principal) fall back to tenant-anonymous."""
    from cyberfabric_core_tpu.modules.llm_gateway.module import (
        _ctx_from_principal, _principal_of)
    from cyberfabric_core_tpu.modkit.security import SecurityContext

    ctx = SecurityContext(subject="user-42", tenant_id="acme",
                          token_scopes=("llm.run",), roles=("llm-user",))
    rebuilt = _ctx_from_principal("acme", _principal_of(ctx))
    assert rebuilt.subject == "user-42"
    assert rebuilt.tenant_id == "acme"
    assert rebuilt.roles == ("llm-user",)
    assert rebuilt.token_scopes == ("llm.run",)
    # tenant scoping still enforced — no bearer token is resurrected
    assert rebuilt.bearer_token is None
    legacy = _ctx_from_principal("acme", None)
    assert legacy.subject == "anonymous" and legacy.tenant_id == "acme"
