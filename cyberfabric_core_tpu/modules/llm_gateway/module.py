"""llm-gateway module — the REST surface + application layer.

Implements the chat-completion flow of DESIGN.md:348-367 for real:
validate (GTS schemas) → rate/budget hooks → provider resolution via model-registry
(exists/approval, fallback ranking DESIGN.md:323-346) → local TPU worker →
stream normalization to the StreamChunk SSE contract with `data: [DONE]`
(DESIGN.md:289-311) → TTFT + total timeouts with fallback chains (DESIGN.md:680-741)
→ usage reporting.

Endpoints (DESIGN.md:262-271): POST /v1/chat/completions, POST /v1/completions
(raw text, no chat template — the BASELINE metric surface), POST /v1/embeddings,
POST/GET/DELETE /v1/jobs, POST/GET /v1/batches, media endpoints, GET /v1/realtime.
"""

from __future__ import annotations

import asyncio
import contextlib
import datetime
import json
import uuid
from typing import Any, AsyncIterator, Optional

import aiohttp
from aiohttp import web

from ...modkit import Module, module
from ...modkit.contracts import (DatabaseCapability, GrpcServiceCapability,
                                 Migration, RestApiCapability,
                                 RunnableCapability)
from ...modkit.context import ModuleCtx
from ...modkit.db import ScopableEntity
from ...modkit.errcat import ERR
from ...modkit.errors import Problem, ProblemError
from ...modkit.lifecycle import ReadySignal
from ...modkit.logging_host import observe_task
from ...modkit.security import SecurityContext
from ...modkit.sse import SSE_DONE, format_sse_json
from ...gateway.middleware import SECURITY_CONTEXT_KEY
from ...gateway.validation import read_json, validate_against
from ..sdk import ChatStreamChunk, LlmHookApi, LlmWorkerApi, ModelInfo, ModelRegistryApi
from . import schemas
from .worker import LocalTpuWorker


class UsageTracker:
    """Per-tenant token accounting + budget check hook (DESIGN.md:820-855).

    The budget check reads TWO ledgers and takes the max: the gateway-side
    usage reports (stream-end accounting, the only ledger external
    providers have) and the scheduler-side live counters
    (``LlmWorkerApi.tenant_usage`` — prefill + decode tokens actually
    consumed, charged mid-stream). One source of truth: a tenant cannot
    dodge its budget by holding streams open (the report lands at stream
    end) or by hammering cached prefixes (the scheduler charges only real
    compute)."""

    def __init__(self, budgets: Optional[dict[str, int]] = None,
                 retry_after_s: float = 60.0) -> None:
        self._usage: dict[str, dict[str, int]] = {}
        self._budgets = budgets or {}
        self._retry_after_s = retry_after_s
        #: scheduler-side live accounting source (the worker's
        #: ``tenant_usage``), attached by the module once the worker exists
        self._live_source = None

    def attach_live_source(self, fn) -> None:
        """``fn() -> {tenant: {"charged_tokens": n, ...}}`` — the
        scheduler-side accounting the budget check folds in."""
        self._live_source = fn

    def _live_tokens(self, tenant_id: str) -> int:
        if self._live_source is None:
            return 0
        try:
            return int((self._live_source().get(tenant_id) or {})
                       .get("charged_tokens", 0))
        except Exception:  # noqa: BLE001 — accounting must not fail serving
            return 0

    def check_budget(self, ctx: SecurityContext) -> None:
        budget = self._budgets.get(ctx.tenant_id)
        if budget is None:
            return
        reported = self._usage.get(ctx.tenant_id, {}).get("total_tokens", 0)
        used = max(reported, self._live_tokens(ctx.tenant_id))
        if used >= budget:
            from ...modkit.metrics import bump_counter

            bump_counter("llm_tenant_budget_rejections_total",
                         tenant=ctx.tenant_id)
            raise ERR.llm.budget_exceeded.error(
                f"tenant token budget {budget} exhausted ({used} used)",
                retry_after_s=self._retry_after_s, tenant=ctx.tenant_id)

    def report(self, ctx: SecurityContext, usage: dict[str, int]) -> None:
        entry = self._usage.setdefault(
            ctx.tenant_id, {"input_tokens": 0, "output_tokens": 0, "total_tokens": 0,
                            "requests": 0})
        entry["input_tokens"] += usage.get("input_tokens", 0)
        entry["output_tokens"] += usage.get("output_tokens", 0)
        entry["total_tokens"] += usage.get("input_tokens", 0) + usage.get("output_tokens", 0)
        entry["requests"] += 1
        # media counters (images, media_requests, ...) accumulate generically
        for k, v in usage.items():
            if k in ("input_tokens", "output_tokens") or not isinstance(v, int):
                continue
            entry[k] = entry.get(k, 0) + v
        from ...modkit.metrics import default_registry

        default_registry.counter(
            "llm_tokens_total", "LLM tokens processed").inc(
            usage.get("input_tokens", 0), direction="input", tenant=ctx.tenant_id)
        default_registry.counter(
            "llm_tokens_total", "LLM tokens processed").inc(
            usage.get("output_tokens", 0), direction="output", tenant=ctx.tenant_id)

    def snapshot(self, ctx: SecurityContext) -> dict[str, int]:
        return dict(self._usage.get(ctx.tenant_id, {}))


def _migrate_0001(c):
    c.execute(
        "CREATE TABLE llm_jobs ("
        "id TEXT PRIMARY KEY, tenant_id TEXT NOT NULL, status TEXT NOT NULL, "
        "request TEXT, result TEXT, error TEXT, "
        "created_at TEXT, expires_at TEXT)")
    c.execute("CREATE INDEX idx_llm_jobs ON llm_jobs (tenant_id, status)")
    c.execute(
        "CREATE TABLE llm_batches ("
        "id TEXT PRIMARY KEY, tenant_id TEXT NOT NULL, status TEXT NOT NULL, "
        "requests TEXT, created_at TEXT)")
    c.execute("CREATE INDEX idx_llm_batches ON llm_batches (tenant_id, status)")


def _migrate_0002(c):
    # round-4 advisory: recovery ran durable work as tenant-anonymous,
    # dropping the submitter's roles/scopes — persist the minimal principal
    # with the row so recovery reconstructs the submitting identity
    c.execute("ALTER TABLE llm_jobs ADD COLUMN principal TEXT")
    c.execute("ALTER TABLE llm_batches ADD COLUMN principal TEXT")


#: websocket message types after which nothing more arrives
_WS_ENDED = (aiohttp.WSMsgType.CLOSE, aiohttp.WSMsgType.CLOSING,
             aiohttp.WSMsgType.CLOSED, aiohttp.WSMsgType.ERROR)

_MIGRATIONS = [Migration("0001_llm_jobs", _migrate_0001),
               Migration("0002_job_principal", _migrate_0002)]


def _principal_of(ctx: SecurityContext) -> dict:
    """Minimal durable identity: enough to reconstruct authorization-relevant
    state (subject, roles, token scopes) without persisting the bearer token."""
    return {"subject": ctx.subject, "roles": list(ctx.roles),
            "scopes": list(ctx.token_scopes)}


def _ctx_from_principal(tenant_id: str, principal: Optional[dict]) -> SecurityContext:
    """Rebuild the submitter's SecurityContext at recovery. Rows written
    before the principal column existed fall back to tenant-scoped anonymous
    (the pre-round-5 behavior, now the exception rather than the rule)."""
    from ...modkit.security import AccessScope

    if not principal:
        return SecurityContext.anonymous(tenant_id)
    return SecurityContext(
        subject=principal.get("subject") or "anonymous",
        tenant_id=tenant_id,
        token_scopes=tuple(principal.get("scopes") or ()),
        roles=tuple(principal.get("roles") or ()),
        access_scope=AccessScope.for_tenants([tenant_id]),
    )

#: durable async-job state (round-3 verdict item 7: DESIGN.md:884-889 expects
#: job state in a distributed cache — here the module's own DB, like the
#: serverless module's invocations; a restart RESUMES pending work instead of
#:  vanishing it)
JOBS = ScopableEntity(
    table="llm_jobs",
    field_map={"id": "id", "tenant_id": "tenant_id", "status": "status",
               "request": "request", "result": "result", "error": "error",
               "created_at": "created_at", "expires_at": "expires_at",
               "principal": "principal"},
    json_cols=("request", "result", "error", "principal"),
)

BATCHES = ScopableEntity(
    table="llm_batches",
    field_map={"id": "id", "tenant_id": "tenant_id", "status": "status",
               "requests": "requests", "created_at": "created_at",
               "principal": "principal"},
    json_cols=("requests", "principal"),
)


class JobStore:
    """Async jobs, DB-durable: every transition persists to the module's
    sqlite row; an in-memory map keeps hot handles (incl. the asyncio task
    under the non-persisted "_task" key)."""

    def __init__(self, db=None) -> None:
        self.jobs: dict[str, dict[str, Any]] = {}
        self._db = db
        self._last_sweep = 0.0

    def _conn(self, ctx: SecurityContext):
        return self._db.secure(ctx, JOBS) if self._db is not None else None

    def persist(self, ctx: SecurityContext, job: dict) -> None:
        conn = self._conn(ctx)
        if conn is None:
            return
        row = {k: v for k, v in job.items() if not k.startswith("_")}
        if conn.get(job["id"]) is None:
            conn.insert(row)
        else:
            conn.update(job["id"], {k: v for k, v in row.items()
                                    if k not in ("id", "tenant_id")})

    def _evict_expired(self, ctx: SecurityContext) -> None:
        now = datetime.datetime.now(datetime.timezone.utc).isoformat()
        expired = [jid for jid, j in self.jobs.items()
                   if j.get("expires_at", "") < now
                   and j["status"] not in ("pending", "running")]
        for jid in expired:
            del self.jobs[jid]
        # the DB sweep scans the tenant's rows — throttle it off the request
        # hot path (review finding: O(history) sqlite work per job create)
        import time as _time

        if _time.monotonic() - self._last_sweep < 60.0:
            return
        self._last_sweep = _time.monotonic()
        conn = self._conn(ctx)
        if conn is not None:
            for row in conn.select(where={}):
                if row.get("expires_at", "") < now and \
                        row["status"] not in ("pending", "running"):
                    conn.delete(row["id"])

    def create(self, ctx: SecurityContext, request: dict) -> dict:
        self._evict_expired(ctx)
        job_id = f"job-{uuid.uuid4().hex[:20]}"
        now = datetime.datetime.now(datetime.timezone.utc)
        job = {
            "id": job_id, "tenant_id": ctx.tenant_id, "status": "pending",
            "request": request, "result": None, "error": None,
            "principal": _principal_of(ctx),
            "created_at": now.isoformat(),
            "expires_at": (now + datetime.timedelta(hours=24)).isoformat(),
        }
        self.jobs[job_id] = job
        self.persist(ctx, job)
        return job

    def get(self, ctx: SecurityContext, job_id: str) -> dict:
        job = self.jobs.get(job_id)
        if job is None and self._db is not None:
            row = self._db.secure(ctx, JOBS).get(job_id)
            if row is not None:
                now = datetime.datetime.now(datetime.timezone.utc).isoformat()
                if row.get("expires_at", "") < now and \
                        row["status"] not in ("pending", "running"):
                    # expiry holds on reads too: the sweep is best-effort,
                    # the contract is not (review finding)
                    self._db.secure(ctx, JOBS).delete(job_id)
                else:
                    job = self.jobs[job_id] = row
        if job is None or job["tenant_id"] != ctx.tenant_id:
            raise ERR.llm.job_not_found.error(f"job {job_id} not found")
        return job

    def public_view(self, job: dict) -> dict:
        return {k: v for k, v in job.items()
                if k not in ("tenant_id", "principal")
                and not k.startswith("_") and v is not None}


@module(name="llm_gateway", deps=["model_registry"],
        capabilities=["rest", "stateful", "grpc", "db"])
class LlmGatewayModule(Module, RestApiCapability, RunnableCapability,
                       GrpcServiceCapability, DatabaseCapability):
    def migrations(self):
        return _MIGRATIONS

    def __init__(self) -> None:
        self.worker: Optional[LlmWorkerApi] = None
        self.registry: Optional[ModelRegistryApi] = None
        self.usage = UsageTracker()
        self.jobs = JobStore()
        self.batches: dict[str, dict] = {}
        self.ttft_timeout_s = 120.0
        self.total_timeout_s = 600.0
        self.default_deadline_ms = 0.0
        self._video_poll_interval_s = 2.0
        self._video_poll_timeout_s = 120.0
        self._external = None
        self._doctor = None  # hub-resolved lazily (fabric-doctor admission)
        self._db = None
        self._job_tasks: set[asyncio.Task] = set()

    async def init(self, ctx: ModuleCtx) -> None:
        cfg = ctx.raw_config()
        self._db = ctx.db
        self.jobs = JobStore(self._db)
        self.registry = ctx.client_hub.get(ModelRegistryApi)
        # allow a pre-registered worker (test seam per client_hub.rs:16)
        self.worker = ctx.client_hub.try_get(LlmWorkerApi)
        if self.worker is None:
            fed = cfg.get("federation") or {}
            remote = cfg.get("remote_worker_endpoint")
            if fed.get("enabled"):
                # route-remote before route-local: the federated pool places
                # each request on the best registered worker HOST (prefix >
                # load > random) over the typed llmworker.v1 wire, with
                # mid-stream host-crash failover — docs/ARCHITECTURE.md
                # "Cross-host federation"
                self.worker = self._build_federated_pool(ctx, cfg, fed)
            elif remote:
                # OoP worker on another host: typed llmworker.v1 wire
                # (proto/llmworker/v1/llm_worker.proto)
                from .grpc_service import GrpcLlmWorkerClient

                self.worker = GrpcLlmWorkerClient(
                    endpoint=remote,
                    auth_token=(cfg.get("worker_service") or {}).get("token"))
            else:
                self.worker = LocalTpuWorker(cfg.get("worker", {}))
            ctx.client_hub.register(LlmWorkerApi, self.worker)
        self.usage = UsageTracker(
            cfg.get("budgets"),
            retry_after_s=float(cfg.get("budget_retry_after_s", 60.0)))
        # budget checks fold in the scheduler-side live token counters —
        # the gateway hook and the engine accounting read one truth
        worker_ref = self.worker
        self.usage.attach_live_source(
            lambda: worker_ref.tenant_usage()
            if hasattr(worker_ref, "tenant_usage") else {})
        self.ttft_timeout_s = float(cfg.get("ttft_timeout_s", 120.0))
        self.total_timeout_s = float(cfg.get("total_timeout_s", 600.0))
        #: default per-request TTL (ms) threaded into the scheduler as a
        #: deadline when the client sends no X-Request-Deadline-Ms header;
        #: 0 disables. Unlike ttft/total timeouts (gateway-side waits), the
        #: deadline propagates END-TO-END: a lapsed request is lapsed in the
        #: scheduler itself — removed from the queue pre-admit or
        #: deactivated mid-decode — not just abandoned at the HTTP layer.
        self.default_deadline_ms = float(cfg.get("default_deadline_ms", 0.0))
        self._video_poll_interval_s = float(cfg.get("video_poll_interval_s", 2.0))
        self._video_poll_timeout_s = float(cfg.get("video_poll_timeout_s", 120.0))
        #: worker-plane exposure policy (review finding: an inference plane
        #: must be opt-in and tokened — see grpc_service trust boundary)
        ws = cfg.get("worker_service") or {}
        self._worker_service_expose = bool(ws.get("expose", False))
        self._worker_service_token = ws.get("token")
        self._hub = ctx.client_hub  # external adapter resolves lazily (oagw may
        #                             init after this module — no dep ordering)

    def _build_federated_pool(self, ctx: ModuleCtx, cfg: dict,
                              fed: dict) -> Any:
        """Wire the transport-free FederatedServingPool (runtime tier) to
        this process's gRPC stack: the WorkerRegistry resolves LAZILY through
        the ClientHub (grpc_hub may init after this module — no dep
        ordering), each placed host gets a cached GrpcLlmWorkerClient, and
        synthesized terminals use the SDK's ChatStreamChunk."""
        from ...modkit.doctor import default_doctor
        from ...runtime.federation import (FederatedServingPool,
                                           FederationConfig)
        from ..sdk import ChatStreamChunk, WorkerRegistryApi
        from .grpc_service import (GrpcLlmWorkerClient,
                                   WorkerObservabilityClient)

        # the pool is runtime-tier (transport-free, no modules import), so
        # it satisfies the worker contract as an abc VIRTUAL subclass —
        # isinstance passes in ClientHub.register without inverting tiers
        LlmWorkerApi.register(FederatedServingPool)
        hub = ctx.client_hub
        auth = fed.get("worker_auth_token") or \
            (cfg.get("worker_service") or {}).get("token")

        def client_factory(w: Any) -> GrpcLlmWorkerClient:
            return GrpcLlmWorkerClient(endpoint=w.endpoint, auth_token=auth)

        def obs_client_factory(w: Any) -> WorkerObservabilityClient:
            return WorkerObservabilityClient(w.endpoint, auth_token=auth)

        obs = dict(fed.get("observability") or {})
        config = FederationConfig(
            prefix_slack=int(fed.get("prefix_slack", 2)),
            max_failovers=int(fed.get("max_failovers", 2)),
            failover_backoff_s=float(fed.get("failover_backoff_s", 0.05)),
            block_chars=int(fed.get("block_chars", 48)),
            max_blocks=int(fed.get("max_blocks", 64)),
            seed=int(fed.get("seed", 0)),
            stitch_timeout_s=float(obs.get("stitch_timeout_s", 2.0)),
            host_metrics=bool(obs.get("host_metrics", True)),
        )
        pool = FederatedServingPool(
            lambda: hub.try_get(WorkerRegistryApi),
            client_factory, ChatStreamChunk, config,
            obs_client_factory=obs_client_factory)
        # /readyz tells the whole-fleet truth: host-level doctor reasons
        # from the heartbeat fold ride along with the local state (cleared
        # in stop() — a dead stack's fleet must not haunt the next one)
        default_doctor.set_fleet_provider(pool.fleet.readiness_reasons)
        return pool

    def register_grpc(self, ctx: ModuleCtx, server: Any) -> None:
        """Expose the worker as llmworker.v1.LlmWorkerService (typed proto)
        so OTHER hosts' gateways can consume this node's TPU engines. A
        remote-worker PROXY is never re-exported — advertising someone
        else's engines would add a hop per call and lets two hosts pointing
        at each other recurse (review finding); the federated pool is a
        router over OTHER hosts' engines, so the same rule applies."""
        from ...runtime.federation import FederatedServingPool
        from .grpc_service import GrpcLlmWorkerClient, register_llm_worker_service

        if self._worker_service_expose and self.worker is not None and \
                not isinstance(self.worker,
                               (GrpcLlmWorkerClient, FederatedServingPool)):
            register_llm_worker_service(server, self.worker,
                                        auth_token=self._worker_service_token)

    async def start(self, ctx: ModuleCtx, ready: ReadySignal) -> None:
        try:
            recovered = await self._recover_on_start()
            if recovered:
                import logging

                logging.getLogger("llm_gateway").info(
                    "recovered %d interrupted job(s)/batch(es) after restart",
                    recovered)
        except Exception:  # noqa: BLE001 — recovery must never block startup
            import logging

            logging.getLogger("llm_gateway").exception("job recovery failed")
        ready.notify_ready()

    async def _recover_on_start(self) -> int:
        """Restart semantics (round-3 verdict item 7): pending jobs/batches
        RESUME (their request is durable, re-resolve and run); jobs caught
        mid-flight ('running') fail LOUDLY with a restart error — their
        partial generation is gone and silently re-running a maybe-side-
        effectful chat is worse than an honest failure. Batches resume
        per-item: completed items keep their results."""
        if self._db is None:
            return 0
        sysctx = SecurityContext.system()
        recovered = 0
        jobs_conn = self._db.secure(sysctx, JOBS)
        for row in jobs_conn.select(where={"status": "running"}):
            jobs_conn.update(row["id"], {
                "status": "failed",
                "error": {"code": "interrupted",
                          "detail": "host restarted while the job was "
                                    "running; resubmit"}})
            recovered += 1
        for row in jobs_conn.select(where={"status": "pending"}):
            if row["id"] in self.jobs.jobs:
                continue  # owned by this process, not a crash leftover
            # recovered work runs AS the submitter (persisted principal), not
            # tenant-anonymous — resolution/tool access that becomes
            # role-gated later must see the same identity as the original
            # request (round-4 advisory)
            tenant_ctx = _ctx_from_principal(
                row["tenant_id"], row.get("principal"))
            self.jobs.jobs[row["id"]] = row
            # per-row isolation: one malformed leftover must not strand the
            # rest of the queue in 'pending' forever (review finding)
            try:
                models = await self._resolve_with_fallback(
                    tenant_ctx, row["request"])
                self._spawn_job(tenant_ctx, row, models)
            except ProblemError as e:
                row["status"], row["error"] = "failed", e.problem.to_dict()
                self.jobs.persist(tenant_ctx, row)
            except Exception as e:  # noqa: BLE001
                row["status"] = "failed"
                row["error"] = {"code": "unrecoverable",
                                "detail": f"recovery failed: {e}"[:300]}
                self.jobs.persist(tenant_ctx, row)
            recovered += 1
        batches_conn = self._db.secure(sysctx, BATCHES)
        for row in batches_conn.select(where={"status": "pending"}) + \
                batches_conn.select(where={"status": "in_progress"}):
            if row["id"] in self.batches:
                continue
            tenant_ctx = _ctx_from_principal(
                row["tenant_id"], row.get("principal"))
            self.batches[row["id"]] = row
            try:
                self._run_batch(tenant_ctx, row)
            except Exception as e:  # noqa: BLE001
                row["status"] = "failed"
                self._persist_batch(tenant_ctx, row)
                import logging

                logging.getLogger("llm_gateway").warning(
                    "batch %s unrecoverable: %s", row["id"], e)
            recovered += 1
        return recovered

    async def stop(self, ctx: ModuleCtx) -> None:
        for t in list(self._job_tasks):
            t.cancel()
        fleet = getattr(self.worker, "fleet", None)
        if fleet is not None:
            # detach the fleet feed from the process-global doctor so a
            # torn-down federated stack's hosts never color the next
            # stack's /readyz
            from ...modkit.doctor import default_doctor

            default_doctor.set_fleet_provider(None)

    async def _resolve_media(self, ctx: SecurityContext, body: dict) -> dict:
        """Media via FileStorage (DESIGN ADR-0003 + vision/document UCs):
        document parts referencing file-storage URLs are fetched, parsed to
        markdown by the file-parser, and inlined as text before the model sees
        the prompt. Image/audio/video parts pass through untouched (multimodal
        decode is a model capability, not a gateway one)."""
        from ..sdk import FileStorageApi

        storage = self._hub.try_get(FileStorageApi)
        if storage is None:
            return body
        from ..sdk import FileParserApi

        parser = self._hub.try_get(FileParserApi)

        changed = False
        messages = []
        for message in body["messages"]:
            parts = []
            for part in message.get("content", []):
                if isinstance(part, dict) and part.get("type") == "document" \
                        and str(part.get("url", "")).startswith("/v1/files/"):
                    try:
                        data = await storage.fetch(ctx, part["url"])
                        meta = await storage.metadata(ctx, part["url"])
                    except ProblemError:
                        raise ERR.llm.media_not_found.error(
                            f"document part references missing file {part['url']}")
                    if parser is not None:
                        text, _title = parser.parse_to_markdown(
                            data, part.get("mime_type") or meta.mime_type)
                    else:
                        text = data.decode("utf-8", errors="replace")
                    parts.append({"type": "text",
                                  "text": f"[document {meta.filename or meta.file_id}]\n{text}"})
                    changed = True
                else:
                    parts.append(part)
            messages.append({**message, "content": parts})
        if not changed:
            return body
        return {**body, "messages": messages}

    def _get_external(self):
        if self._external is None and getattr(self, "_hub", None) is not None:
            from ..sdk import OagwApi
            from .external import ExternalProviderAdapter

            oagw = self._hub.try_get(OagwApi)
            if oagw is not None:
                self._external = ExternalProviderAdapter(oagw)
        return self._external

    # ------------------------------------------------------------- application layer
    async def _resolve_with_fallback(
        self, ctx: SecurityContext, body: dict
    ) -> list[tuple[bool, ModelInfo]]:
        """Primary + fallback chain as (is_primary, model) pairs; resolution
        errors are skipped so a dead primary still falls through
        (DESIGN.md:323-346)."""
        assert self.registry is not None
        names = [body["model"]]
        fb = body.get("fallback") or {}
        names += [n for n in fb.get("models", []) if n not in names]
        max_attempts = int(fb.get("max_attempts", len(names)))
        resolved: list[tuple[bool, ModelInfo]] = []
        errors: list[str] = []
        for pos, name in enumerate(names[:max_attempts]):
            try:
                resolved.append((pos == 0, await self.registry.resolve(ctx, name)))
            except ProblemError as e:
                errors.append(f"{name}: {e.problem.detail or e.problem.title}")
        if not resolved:
            raise ERR.llm.model_not_found.error(
                "no usable model in request chain: " + "; ".join(errors))
        return resolved

    async def _chat_once(
        self, ctx: SecurityContext, model: ModelInfo, body: dict,
        mode: str = "chat",
    ) -> AsyncIterator[ChatStreamChunk]:
        """One model attempt with TTFT + total timeout enforcement
        (DESIGN.md:706-741). Managed models run on the local TPU worker;
        external ones route through the OAGW provider adapter.
        ``mode="completion"``: raw prompt, no chat template on the local
        worker; external providers see it as one user message."""
        assert self.worker is not None
        external = None if model.managed else self._get_external()
        if mode == "completion":
            if external is None:
                agen = self.worker.completion_stream(model, body["prompt"], body)
            else:
                agen = external.chat_stream(ctx, model, [
                    {"role": "user", "content": [
                        {"type": "text", "text": body["prompt"]}]}], body)
        elif external is None:
            agen = self.worker.chat_stream(model, body["messages"], body)
        else:
            agen = external.chat_stream(ctx, model, body["messages"], body)
        deadline = asyncio.get_event_loop().time() + self.total_timeout_s
        t_start = asyncio.get_event_loop().time()
        first = True
        try:
            while True:
                timeout = self.ttft_timeout_s if first else max(
                    0.05, deadline - asyncio.get_event_loop().time())
                try:
                    chunk = await asyncio.wait_for(agen.__anext__(), timeout)
                except StopAsyncIteration:
                    return
                except asyncio.TimeoutError:
                    raise (ERR.llm.ttft_timeout if first
                           else ERR.llm.total_timeout).error(
                        f"model {model.canonical_id} "
                        f"{'TTFT' if first else 'total'} timeout")
                if first:
                    self._observe_ttft(
                        model, body, asyncio.get_event_loop().time() - t_start)
                first = False
                yield chunk
        finally:
            # deterministic teardown on EVERY exit — timeout, client
            # disconnect closing this generator (GeneratorExit), handler
            # cancellation: the worker generator's own finally cancels the
            # engine-side work, so a dead consumer stops burning decode
            # rounds instead of waiting for GC to reap the chain
            await agen.aclose()

    @staticmethod
    def _observe_ttft(model: ModelInfo, body: dict, wall_s: float) -> None:
        """llm_ttft_seconds{model=…}: derived from the flight-recorder
        timeline when this request has one (managed models — enqueued →
        prefill, the engine truth instead of ad-hoc wall-clock sampling);
        external providers never touch the recorder, so their sample stays
        the gateway-side wall clock."""
        from ...modkit.flight_recorder import default_recorder
        from ...modkit.metrics import default_registry

        ttft_s = wall_s
        rid = body.get("_request_id")
        if model.managed and rid:
            try:
                rec = default_recorder.lookup(rid)
                derived = (rec or {}).get("derived", {}).get("ttft_ms")
                if derived is not None:
                    ttft_s = derived / 1000.0
            except Exception:  # noqa: BLE001 — telemetry must not fail serving
                pass
        default_registry.histogram(
            "llm_ttft_seconds", "Time to first token").observe(
            ttft_s, model=model.canonical_id)

    # ------------------------------------------------------------- REST handlers
    def _get_doctor(self):
        """The fabric-doctor, hub-resolved (the monitoring module registers
        it; it may init after this module — no dep ordering, the oagw
        pattern). Stacks that never boot monitoring have no doctor and
        therefore never shed — admission policy belongs to deployments that
        actually run the evaluator."""
        if getattr(self, "_doctor", None) is None and \
                getattr(self, "_hub", None) is not None:
            from ..sdk import DoctorApi

            self._doctor = self._hub.try_get(DoctorApi)
        return getattr(self, "_doctor", None)

    def _check_load_shed(self, ctx: Optional[SecurityContext] = None) -> None:
        """fabric-doctor admission gate, tenant-selective first. While the
        doctor attributes SLO burn / queue pressure to an over-fair-share
        tenant, only THAT tenant's new requests are rejected (429 +
        Retry-After, ``llm.tenant_shed``) — compliant tenants keep
        streaming. Global shedding (the degradation state machine reaching
        ``shedding``) remains the last resort and rejects everyone
        (``llm.load_shed``). Pre-enqueue is the point: streams already in
        flight keep decoding untouched."""
        doctor = self._get_doctor()
        if doctor is None:
            return
        retry_after = doctor.shed_retry_after()
        if retry_after is not None:
            raise ERR.llm.load_shed.error(
                "serving is load-shedding (SLO burn/stall watchdogs); "
                "retry later", retry_after_s=retry_after, state="shedding")
        if ctx is None:
            return
        tenant_gate = getattr(doctor, "tenant_shed_retry_after", None)
        tenant_retry = (tenant_gate(ctx.tenant_id)
                        if tenant_gate is not None else None)
        if tenant_retry is not None:
            raise ERR.llm.tenant_shed.error(
                f"tenant {ctx.tenant_id!r} is consuming over its fair "
                "share while serving burns SLO budget; this tenant's new "
                "requests are shed first (compliant tenants keep serving)",
                retry_after_s=tenant_retry, tenant=ctx.tenant_id)

    async def handle_chat(self, request: web.Request):
        body = await read_json(request, schemas.REQUEST)
        ctx: SecurityContext = request[SECURITY_CONTEXT_KEY]
        self._check_load_shed(ctx)
        self.usage.check_budget(ctx)
        # pre_call hook: allow / block / override (DESIGN.md:743-766)
        hook = self._hub.try_get(LlmHookApi)
        if hook is not None:
            verdict = await hook.pre_call(ctx, body)
            action = (verdict or {}).get("action", "allow")
            if action == "block":
                raise ProblemError.forbidden(
                    (verdict or {}).get("reason", "blocked by pre-call hook"))
            if action == "override":
                body = verdict["body"]
                validate_against(schemas.REQUEST, body)
        body = await self._resolve_media(ctx, body)
        if body.get("tools"):
            # UC-010 step 3: resolve all three tool encodings (references via
            # the types registry) BEFORE provider dispatch
            from ..sdk import TypesRegistryApi
            from .tools import normalize_tools

            body["_resolved_tools"] = await normalize_tools(
                ctx, body["tools"], self._hub.try_get(TypesRegistryApi))
        self._inject_observability(request, body, ctx)
        self._inject_deadline(request, body)
        models = await self._resolve_with_fallback(ctx, body)

        if body.get("async"):
            job = self.jobs.create(ctx, body)
            self._spawn_job(ctx, job, models)
            return self.jobs.public_view(job), 202
        if body.get("stream"):
            return await self._stream_response(request, ctx, body, models)
        return await self._sync_response(ctx, body, models)

    async def handle_completions(self, request: web.Request):
        """POST /v1/completions — raw text completion (the BASELINE metric
        surface): no chat template, prompt tokens in verbatim. Shares the
        chat path's budget/fallback/timeout/SSE machinery."""
        body = await read_json(request, schemas.COMPLETION_REQUEST)
        ctx: SecurityContext = request[SECURITY_CONTEXT_KEY]
        self._check_load_shed(ctx)
        self.usage.check_budget(ctx)
        # same pre_call policy hook as chat (DESIGN.md:743-766) — a raw
        # prompt must not bypass content moderation
        hook = self._hub.try_get(LlmHookApi)
        if hook is not None:
            verdict = await hook.pre_call(ctx, body)
            action = (verdict or {}).get("action", "allow")
            if action == "block":
                raise ProblemError.forbidden(
                    (verdict or {}).get("reason", "blocked by pre-call hook"))
            if action == "override":
                body = verdict["body"]
                validate_against(schemas.COMPLETION_REQUEST, body)
        self._inject_observability(request, body, ctx)
        self._inject_deadline(request, body)
        models = await self._resolve_with_fallback(ctx, body)
        if body.get("stream"):
            return await self._stream_response(request, ctx, body, models,
                                               mode="completion")
        return await self._sync_response(ctx, body, models, mode="completion")

    @staticmethod
    def _inject_observability(request: web.Request, body: dict,
                              ctx: Optional[SecurityContext] = None) -> None:
        """Thread the gateway's X-Request-Id, the live HTTP span's
        traceparent, and the authenticated tenant into the worker params
        (underscore keys ride beside ``_resolved_tools``): the engine keys
        its flight-recorder timeline by the id the client already holds,
        scheduler spans join the HTTP trace — one OTLP trace from socket to
        tokens — and ``_tenant_id`` makes tenancy a first-class scheduling
        dimension (weighted-fair queues, per-tenant caps, selective
        shedding)."""
        from ...modkit.telemetry import Tracer

        rid = request.get("request_id")
        if rid and "_request_id" not in body:
            body["_request_id"] = rid
        span = Tracer.current()
        if span is not None:
            body["_traceparent"] = span.traceparent()
        elif request.headers.get("traceparent"):
            body["_traceparent"] = request.headers["traceparent"]
        if ctx is not None:
            # the AUTHENTICATED identity, never a client-controlled header:
            # the worker trusts this value to key fair-queue accounting
            body["_tenant_id"] = ctx.tenant_id

    def _inject_deadline(self, request: web.Request, body: dict) -> None:
        """Per-request deadline: the ``X-Request-Deadline-Ms`` header (the
        client's total budget for this request, in milliseconds) takes
        precedence over the config default TTL (``default_deadline_ms``;
        0 disables). The relative budget rides to the worker as
        ``_deadline_ms`` and becomes an absolute monotonic deadline at
        scheduler submit — from there the per-round expiry sweep owns it in
        every phase (queued, prefilling, decoding, suspended)."""
        hdr = request.headers.get("X-Request-Deadline-Ms")
        if hdr is not None:
            try:
                ms = float(hdr)
            except ValueError:
                ms = float("nan")
            if not ms > 0 or ms != ms or ms == float("inf"):
                raise ProblemError.bad_request(
                    "X-Request-Deadline-Ms must be a positive, finite "
                    "number of milliseconds")
            body["_deadline_ms"] = ms
        elif self.default_deadline_ms > 0:
            body["_deadline_ms"] = self.default_deadline_ms

    async def _sync_response(self, ctx: SecurityContext, body: dict,
                             models: list[tuple[bool, ModelInfo]],
                             mode: str = "chat") -> dict:
        last_err: Optional[ProblemError] = None
        for is_primary, model in models:
            pieces: list[str] = []
            usage = {"input_tokens": 0, "output_tokens": 0}
            finish = "stop"
            try:
                async for chunk in self._chat_once(ctx, model, body, mode):
                    if chunk.text:
                        pieces.append(chunk.text)
                    if chunk.finish_reason:
                        finish = chunk.finish_reason
                        usage = chunk.usage or usage
                cost = self._cost(model, usage)
                if cost is not None:
                    usage["cost_estimate"] = cost
                self.usage.report(ctx, usage)
                text = "".join(pieces)
                resp = {
                    "usage": usage,
                    "model_used": model.canonical_id,
                    "fallback_used": not is_primary,
                    "finish_reason": finish,
                }
                tool_calls = None
                if body.get("_resolved_tools"):
                    from .tools import build_tool_calls_response, extract_tool_call

                    call = extract_tool_call(text)
                    if call is not None:
                        tool_calls = build_tool_calls_response(
                            call, body["_resolved_tools"])
                if tool_calls is not None:
                    resp["tool_calls"] = tool_calls
                    resp["finish_reason"] = "tool_calls"
                else:
                    if body.get("response_schema"):
                        from .tools import validate_structured_output

                        validate_structured_output(text, body["response_schema"])
                    resp["content"] = [{"type": "text", "text": text}]
                hook = self._hub.try_get(LlmHookApi) if hasattr(self, "_hub") else None
                if hook is not None:
                    resp = await hook.post_response(ctx, body, resp)
                validate_against(schemas.RESPONSE, resp)
                return resp
            except ProblemError as e:
                last_err = e
                if e.problem.code in ("request_timeout", "deadline_exceeded"):
                    # the CLOCK failed, not the model: a fallback attempt
                    # inherits the same lapsed budget and can only lapse too
                    break
                continue
        assert last_err is not None
        raise last_err

    async def _stream_response(self, request: web.Request, ctx: SecurityContext,
                               body: dict,
                               models: list[tuple[bool, ModelInfo]],
                               mode: str = "chat") -> web.StreamResponse:
        """SSE per the chunk contract: role-bearing first delta, content deltas,
        final chunk with finish_reason + usage, then data: [DONE]."""
        resp: Optional[web.StreamResponse] = None
        completion_id = (f"chatcmpl-{uuid.uuid4().hex[:20]}" if mode == "chat"
                         else f"cmpl-{uuid.uuid4().hex[:20]}")
        last_err: Optional[ProblemError] = None
        for is_primary, model in models:
            try:
                agen = self._chat_once(ctx, model, body, mode)
                first_chunk = await agen.__anext__()
            except StopAsyncIteration:
                continue
            except ProblemError as e:
                last_err = e
                if e.problem.code in ("request_timeout", "deadline_exceeded"):
                    break  # a lapsed deadline lapses on every fallback too
                continue  # fallback BEFORE the stream starts; after TTFT we're committed
            headers = {
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "X-Model-Used": model.canonical_id,
            }
            # the request-id middleware echoes X-Request-Id AFTER the handler
            # returns — too late for an SSE response that is already prepared
            # and streamed; set it here so streaming clients can correlate
            # with GET /v1/monitoring/requests/{id}
            rid = request.get("request_id")
            if rid:
                headers["X-Request-Id"] = rid
            resp = web.StreamResponse(headers=headers)
            await resp.prepare(request)

            async def send(payload: dict) -> None:
                validate_against(schemas.STREAM_CHUNK, payload)
                await resp.write(format_sse_json(payload))

            role_sent = False

            async def emit(chunk: ChatStreamChunk) -> None:
                nonlocal role_sent
                delta: dict[str, Any] = {}
                if not role_sent:
                    delta["role"] = "assistant"
                    role_sent = True
                if chunk.text:
                    delta["content"] = chunk.text
                payload: dict[str, Any] = {
                    "id": completion_id, "model": model.canonical_id, "delta": delta,
                }
                if chunk.finish_reason:
                    payload["finish_reason"] = chunk.finish_reason
                    usage = dict(chunk.usage or {})
                    cost = self._cost(model, usage)
                    if cost is not None:
                        usage["cost_estimate"] = cost
                    payload["usage"] = usage
                    self.usage.report(ctx, usage)
                await send(payload)

            try:
                try:
                    await emit(first_chunk)
                    async for chunk in agen:
                        await emit(chunk)
                except ProblemError as e:
                    # mid-stream failure: emit a terminal error event (can't re-status)
                    await resp.write(format_sse_json(
                        {"error": e.problem.to_dict()}, event="error"))
                except (ConnectionResetError, asyncio.CancelledError):
                    # the SSE consumer is gone (socket reset, or aiohttp
                    # cancelled the handler on disconnect): the finally's
                    # aclose propagates into the worker generator, whose
                    # teardown cancels the engine-side work — the 499-style
                    # disconnect-abort path. Re-raise: there is nobody left
                    # to write [DONE] to.
                    from ...modkit.metrics import bump_counter

                    bump_counter("llm_client_disconnects_total")
                    raise
            finally:
                # deterministic even on the non-exception paths — aclose is
                # idempotent and the generator is normally already exhausted
                await agen.aclose()
            await resp.write(SSE_DONE)
            await resp.write_eof()
            return resp
        raise last_err or ProblemError.service_unavailable("no model produced a stream")

    def _spawn_job(self, ctx: SecurityContext, job: dict,
                   models: list[tuple[bool, ModelInfo]]) -> None:
        async def run() -> None:
            job["status"] = "running"
            self.jobs.persist(ctx, job)
            try:
                result = await self._sync_response(ctx, job["request"], models)
                job["status"], job["result"] = "completed", result
            except asyncio.CancelledError:
                job["status"] = "cancelled"
                self.jobs.persist(ctx, job)
                raise
            except ProblemError as e:
                job["status"], job["error"] = "failed", e.problem.to_dict()
            except Exception as e:  # noqa: BLE001
                job["status"], job["error"] = "failed", {"detail": str(e)}
            self.jobs.persist(ctx, job)

        # run() persists terminal state itself, but a failure in persist (or
        # anything after the except arms) would be swallowed at GC time —
        # observe_task routes it through the logging host
        task = observe_task(asyncio.ensure_future(run()),
                            f"llm_gateway.job.{job['id']}", logger="llm_gateway")
        job["_task"] = task
        self._job_tasks.add(task)
        task.add_done_callback(self._job_tasks.discard)

    async def handle_get_job(self, request: web.Request):
        ctx = request[SECURITY_CONTEXT_KEY]
        job = self.jobs.get(ctx, request.match_info["job_id"])
        return self.jobs.public_view(job)

    async def handle_cancel_job(self, request: web.Request):
        ctx = request[SECURITY_CONTEXT_KEY]
        job = self.jobs.get(ctx, request.match_info["job_id"])
        task: Optional[asyncio.Task] = job.get("_task")
        if job["status"] in ("pending", "running") and task is not None:
            task.cancel()
            job["status"] = "cancelled"
            self.jobs.persist(ctx, job)
        return self.jobs.public_view(job)

    async def handle_create_batch(self, request: web.Request):
        """Batch API (async/batch.v1 + batch_request.v1): items run concurrently
        against the worker (bounded), per-item results/errors recorded."""
        body = await read_json(request, {
            "type": "object", "required": ["requests"],
            "properties": {"requests": {
                "type": "array", "minItems": 1, "maxItems": 128,
                "items": {"type": "object",
                          "required": ["custom_id", "request"],
                          "properties": {"custom_id": {"type": "string"},
                                         "request": schemas.REQUEST},
                          "additionalProperties": False}}},
            "additionalProperties": False})
        ctx: SecurityContext = request[SECURITY_CONTEXT_KEY]
        self.usage.check_budget(ctx)
        batch_id = f"batch-{uuid.uuid4().hex[:20]}"
        batch = {
            "id": batch_id, "tenant_id": ctx.tenant_id, "status": "pending",
            "requests": [{"custom_id": it["custom_id"], "request": it["request"],
                          "result": None, "error": None}
                         for it in body["requests"]],
            "principal": _principal_of(ctx),
            "created_at": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        }
        self.batches[batch_id] = batch
        self._persist_batch(ctx, batch)
        self._run_batch(ctx, batch)
        return self._batch_view(batch), 202

    #: finished batches older than this are evicted by the periodic sweep
    BATCH_RETENTION = datetime.timedelta(days=7)

    def _persist_batch(self, ctx: SecurityContext, batch: dict) -> None:
        if self._db is None:
            return
        conn = self._db.secure(ctx, BATCHES)
        row = {k: v for k, v in batch.items() if not k.startswith("_")}
        if conn.get(batch["id"]) is None:
            self._sweep_batches(conn)
            conn.insert(row)
        else:
            conn.update(batch["id"], {"status": batch["status"],
                                      "requests": batch["requests"]})

    def _sweep_batches(self, conn) -> None:
        """Retention for terminal batches (each row carries full request
        payloads + results — unbounded growth otherwise)."""
        cutoff = (datetime.datetime.now(datetime.timezone.utc)
                  - self.BATCH_RETENTION).isoformat()
        for row in conn.select(where={"status": "completed"}) + \
                conn.select(where={"status": "failed"}):
            if row.get("created_at", "") < cutoff:
                conn.delete(row["id"])
                self.batches.pop(row["id"], None)

    def _run_batch(self, ctx: SecurityContext, batch: dict) -> None:
        """Run (or, after a restart, RESUME) a batch: entries that already
        carry a result/error are kept; only unfinished ones execute."""

        async def run() -> None:
            batch["status"] = "in_progress"
            self._persist_batch(ctx, batch)
            sem = asyncio.Semaphore(8)

            finished = 0

            async def one(item: dict) -> None:
                nonlocal finished
                if item.get("result") is not None or item.get("error"):
                    return  # finished before the restart — keep it
                async with sem:
                    try:
                        models = await self._resolve_with_fallback(ctx, item["request"])
                        item["result"] = await self._sync_response(
                            ctx, item["request"], models)
                    except ProblemError as e:
                        item["error"] = e.problem.to_dict()
                    except Exception as e:  # noqa: BLE001
                        item["error"] = {"detail": str(e)[:500]}
                    # durability checkpoint every few items (full-array
                    # rewrite per item would be O(n^2) sqlite work — review
                    # finding); a crash loses at most the last window
                    finished += 1
                    if finished % 8 == 0:
                        self._persist_batch(ctx, batch)

            await asyncio.gather(*(one(it) for it in batch["requests"]))
            failed = sum(1 for it in batch["requests"] if it["error"])
            batch["status"] = "failed" if failed == len(batch["requests"]) else "completed"
            self._persist_batch(ctx, batch)

        task = observe_task(asyncio.ensure_future(run()),
                            f"llm_gateway.batch.{batch['id']}",
                            logger="llm_gateway")
        self._job_tasks.add(task)
        task.add_done_callback(self._job_tasks.discard)

    async def handle_get_batch(self, request: web.Request):
        ctx = request[SECURITY_CONTEXT_KEY]
        batch = self.batches.get(request.match_info["batch_id"])
        if batch is None and self._db is not None:
            batch = self._db.secure(ctx, BATCHES).get(
                request.match_info["batch_id"])
        if batch is None or batch["tenant_id"] != ctx.tenant_id:
            raise ERR.llm.batch_not_found.error("batch not found")
        return self._batch_view(batch)

    @staticmethod
    def _batch_view(batch: dict) -> dict:
        return {k: v for k, v in batch.items()
                if k not in ("tenant_id", "principal")}

    async def handle_embeddings(self, request: web.Request):
        body = await read_json(request, schemas.EMBEDDING_REQUEST)
        ctx: SecurityContext = request[SECURITY_CONTEXT_KEY]
        self.usage.check_budget(ctx)
        assert self.registry is not None and self.worker is not None
        model = await self.registry.resolve(ctx, body["model"])
        inputs = body["input"] if isinstance(body["input"], list) else [body["input"]]
        vectors, input_tokens = await self.worker.embed(model, inputs, body)
        usage = {"input_tokens": input_tokens, "output_tokens": 0}
        self.usage.report(ctx, usage)
        data = [{"index": i, "embedding": v} for i, v in enumerate(vectors)]
        return {"data": data, "model": model.canonical_id, "usage": usage}

    async def handle_realtime(self, request: web.Request):
        """WS /realtime (DESIGN.md:262-271): bidirectional session — client sends
        `{type: "chat.create", request: {...}}` frames, server streams
        `{type: "token", ...}` / `{type: "done", usage}` / `{type: "error"}`
        events; binary frames fill an audio buffer that `audio.commit`
        transcribes. Frames are served one at a time, in order. Every
        `audio.commit` and `chat.create` ends with one terminal event that
        carries the frame's `id` (docs/MODULES.md, "Realtime session")."""
        ctx: SecurityContext = request[SECURITY_CONTEXT_KEY]
        ws = web.WebSocketResponse(heartbeat=20.0)
        await ws.prepare(request)
        audio_buf = bytearray()  # realtime audio frames (PRD audio modality)
        # The socket is read for the whole session, also while a frame is
        # served: aiohttp counts heartbeat pongs only inside receive(), so a
        # frame served without reading (a cold compile, a slow provider) is
        # cut by the server's own ping after heartbeat x 1.5 = 30 s and its
        # next send raises with no event delivered. Frames the client sends
        # ahead wait in `inbox`; past 16 of them reading pauses.
        inbox: asyncio.Queue = asyncio.Queue(maxsize=16)
        reader = asyncio.ensure_future(self._realtime_read(ws, inbox))
        try:
            while True:
                msg = await inbox.get()
                if msg.type in _WS_ENDED:
                    break
                if msg.type == aiohttp.WSMsgType.BINARY:
                    # binary frames append to the session's input audio buffer
                    # (the spec's input_audio_buffer.append, bytes instead of
                    # b64); bounded like every other input path
                    if len(audio_buf) + len(msg.data) > 16 * 1024 * 1024:
                        await ws.send_json({"type": "error", "error": {
                            "code": "audio_buffer_full",
                            "detail": "audio buffer limit 16MiB; commit or clear"}})
                        continue
                    audio_buf.extend(msg.data)
                    await ws.send_json({"type": "audio.appended",
                                        "buffered_bytes": len(audio_buf)})
                    continue
                if msg.type != aiohttp.WSMsgType.TEXT:
                    continue
                try:
                    frame = json.loads(msg.data)
                except json.JSONDecodeError:
                    await ws.send_json({"type": "error",
                                        "error": {"code": "malformed_json"}})
                    continue
                kind = frame.get("type")
                if kind == "session.close":
                    break
                if kind == "audio.clear":
                    audio_buf.clear()
                    await ws.send_json({"type": "audio.cleared"})
                    continue
                event_id = frame.get("id") or f"rt-{uuid.uuid4().hex[:12]}"
                if kind == "audio.commit":
                    work = self._realtime_commit(ws, ctx, frame, event_id,
                                                 audio_buf)
                elif kind == "chat.create":
                    work = self._realtime_chat(ws, ctx, frame, event_id)
                else:
                    await ws.send_json({"type": "error", "error": {
                        "code": "unknown_frame_type", "detail": f"{kind!r}"}})
                    continue
                await self._realtime_serve(ws, event_id, work, reader)
        finally:
            reader.cancel()
        return ws

    @staticmethod
    async def _realtime_read(ws: web.WebSocketResponse,
                             inbox: asyncio.Queue) -> None:
        """Every message of the session into ``inbox``, the one that ends it
        last."""
        while True:
            msg = await ws.receive()
            await inbox.put(msg)
            if msg.type in _WS_ENDED:
                return

    async def _realtime_serve(self, ws: web.WebSocketResponse, event_id: str,
                              work, reader: asyncio.Future) -> None:
        """One frame's work, ended the way the REST error mapping ends a
        request: a ProblemError, or any other exception as
        ``core.internal_error``, becomes the frame's ``error`` event. A peer
        that leaves mid-frame (``reader`` done) cancels the work, and with it
        the engine-side request."""
        task = asyncio.ensure_future(work)
        try:
            await asyncio.wait({task, reader},
                               return_when=asyncio.FIRST_COMPLETED)
            if not task.done():
                task.cancel()
            await task
            return
        except ProblemError as e:
            problem = e.problem
        except ConnectionError:
            return  # the peer is gone: nobody to tell
        except asyncio.CancelledError:
            if asyncio.current_task().cancelling():  # not ours: the handler's
                task.cancel()
                raise
            return
        except Exception:  # noqa: BLE001 — session boundary, as the gateway's
            import logging
            logging.getLogger("llm_gateway").exception(
                "unhandled error in realtime frame %s", event_id)
            problem = ERR.core.internal_error.problem()
        with contextlib.suppress(ConnectionError):
            await ws.send_json({"type": "error", "id": event_id,
                                "error": problem.to_dict()})

    async def _realtime_commit(self, ws, ctx: SecurityContext, frame: dict,
                               event_id: str, audio_buf: bytearray) -> None:
        """committed audio → STT via the provider adapter, transcript
        returned to the client (who typically folds it into the next
        chat.create) — the session protocol of DESIGN.md realtime. Ends
        with ``transcript`` (or the caller's ``error``)."""
        if not audio_buf:
            raise ProblemError.bad_request("audio buffer is empty")
        self.usage.check_budget(ctx)
        model = await self.registry.resolve(ctx, frame.get("model") or "")
        out = await self._media_required().transcribe(
            ctx, model, bytes(audio_buf),
            frame.get("mime_type", "audio/wav"),
            {"language": frame.get("language")})
        self.usage.report(ctx, {"media_requests": 1,
                                "stt_bytes": len(audio_buf)})
        audio_buf.clear()
        # incremental transcript deltas (DESIGN.md realtime surface):
        # clients consume a uniform delta stream; the relay chunks at word
        # boundaries today, and a streaming STT provider refines granularity
        # without a protocol change. The final `transcript` event stays
        # authoritative.
        words = out["text"].split(" ")
        chunk_words = 8
        for wi in range(0, len(words), chunk_words):
            await ws.send_json({
                "type": "transcript.delta", "id": event_id,
                "delta": (" " if wi else "")
                + " ".join(words[wi:wi + chunk_words])})
        await ws.send_json({"type": "transcript", "id": event_id,
                            "text": out["text"],
                            "model_used": out["model_used"]})

    async def _realtime_chat(self, ws, ctx: SecurityContext, frame: dict,
                             event_id: str) -> None:
        """One chat exchange: ``token`` events, then ``done``. With
        ``response_audio`` the exchange goes on to speak the reply and ends
        with exactly one of ``audio.out.done`` or the caller's ``error``: a
        reply with no text (a lapsed deadline, an immediate stop) is
        ``audio.out.done`` with ``bytes: 0`` and no ``audio.out.begin`` —
        nothing was sent to the TTS provider and nothing billed."""
        body = frame.get("request") or {}
        validate_against(schemas.REQUEST, body)
        self._check_load_shed(ctx)
        self.usage.check_budget(ctx)
        # WS frames carry no per-request header; the config default
        # TTL still bounds each chat.create end-to-end (a vanished
        # WS peer's frame cannot decode to max_tokens forever)
        if self.default_deadline_ms > 0:
            body.setdefault("_deadline_ms", self.default_deadline_ms)
        body.setdefault("_tenant_id", ctx.tenant_id)
        models = await self._resolve_with_fallback(ctx, body)
        _, model = models[0]
        reply_parts: list[str] = []
        async for chunk in self._chat_once(ctx, model, body):
            if chunk.text:
                reply_parts.append(chunk.text)
                await ws.send_json({"type": "token", "id": event_id,
                                    "content": chunk.text})
            if chunk.finish_reason:
                usage = dict(chunk.usage or {})
                self.usage.report(ctx, usage)
                await ws.send_json({
                    "type": "done", "id": event_id,
                    "finish_reason": chunk.finish_reason,
                    "usage": usage, "model_used": model.canonical_id})
        # TTS out-leg (DESIGN.md:262-271 bidirectional audio loop):
        # frame-level `response_audio` asks the session to speak the
        # reply — audio.out.begin, binary frames, audio.out.done
        audio_out = frame.get("response_audio")
        if not audio_out:
            return
        reply = "".join(reply_parts)
        audio = b""
        if reply:
            tts_model = await self.registry.resolve(
                ctx, audio_out.get("model") or "")
            audio, mime = await self._media_required().speech_raw(
                ctx, tts_model, {
                    "input": reply,
                    "voice": audio_out.get("voice", "alloy"),
                    "response_format": audio_out.get("format", "mp3")})
            self.usage.report(ctx, {"media_requests": 1,
                                    "tts_chars": len(reply)})
            await ws.send_json({"type": "audio.out.begin",
                                "id": event_id, "mime_type": mime,
                                "model_used": tts_model.canonical_id})
            for off in range(0, len(audio), 32768):
                await ws.send_bytes(audio[off:off + 32768])
        await ws.send_json({"type": "audio.out.done", "id": event_id,
                            "bytes": len(audio)})

    # ------------------------------------------------------------- media (PRD FRs)
    def _get_media(self):
        if getattr(self, "_media", None) is None and \
                getattr(self, "_hub", None) is not None:
            from ..sdk import FileStorageApi, OagwApi
            from .media import MediaAdapter

            oagw = self._hub.try_get(OagwApi)
            if oagw is not None:
                self._media = MediaAdapter(
                    oagw, self._hub.try_get(FileStorageApi),
                    video_poll_interval_s=self._video_poll_interval_s,
                    video_poll_timeout_s=self._video_poll_timeout_s)
        return getattr(self, "_media", None)

    def _media_required(self):
        media = self._get_media()
        if media is None:
            raise ERR.llm.oagw_missing.error(
                "media modalities require the oagw module")
        return media

    async def handle_image_generation(self, request: web.Request):
        body = await read_json(request, schemas.IMAGE_REQUEST)
        ctx: SecurityContext = request[SECURITY_CONTEXT_KEY]
        self.usage.check_budget(ctx)
        model = await self.registry.resolve(ctx, body["model"])
        out = await self._media_required().generate_image(ctx, model, body)
        self.usage.report(ctx, {"input_tokens": 0, "output_tokens": 0,
                                "images": len(out["data"])})
        return out

    async def handle_video_generation(self, request: web.Request):
        body = await read_json(request, schemas.VIDEO_REQUEST)
        ctx: SecurityContext = request[SECURITY_CONTEXT_KEY]
        self.usage.check_budget(ctx)
        model = await self.registry.resolve(ctx, body["model"])
        out = await self._media_required().generate_video(ctx, model, body)
        self.usage.report(ctx, {"input_tokens": 0, "output_tokens": 0,
                                "videos": len(out["data"])})
        return out

    async def handle_speech(self, request: web.Request):
        body = await read_json(request, schemas.SPEECH_REQUEST)
        ctx: SecurityContext = request[SECURITY_CONTEXT_KEY]
        self.usage.check_budget(ctx)
        model = await self.registry.resolve(ctx, body["model"])
        out = await self._media_required().speech(ctx, model, body)
        self.usage.report(ctx, {"media_requests": 1,
                                "tts_bytes": out.get("size_bytes", 0)})
        return out

    async def handle_transcription(self, request: web.Request):
        ctx: SecurityContext = request[SECURITY_CONTEXT_KEY]
        self.usage.check_budget(ctx)
        model_name = request.query.get("model")
        if not model_name:
            raise ProblemError.bad_request("model query parameter required")
        model = await self.registry.resolve(ctx, model_name)
        audio = await request.read()
        if not audio:
            raise ProblemError.bad_request("request body must be audio bytes")
        # aiohttp defaults a missing Content-Type to octet-stream — map that
        # to the wav default, since STT providers reject octet-stream files
        mime = request.content_type
        if not mime or mime == "application/octet-stream":
            mime = "audio/wav"
        out = await self._media_required().transcribe(
            ctx, model, audio, mime,
            {"language": request.query.get("language")})
        self.usage.report(ctx, {"media_requests": 1,
                                "stt_bytes": len(audio)})
        return out

    async def handle_usage(self, request: web.Request):
        ctx = request[SECURITY_CONTEXT_KEY]
        out = {"tenant_id": ctx.tenant_id, "usage": self.usage.snapshot(ctx)}
        # the scheduler-side live ledger (the budget hook's second source
        # of truth): tokens actually consumed, including still-open streams
        try:
            engine_row = self.worker.tenant_usage().get(ctx.tenant_id) \
                if hasattr(self.worker, "tenant_usage") else None
        except Exception:  # noqa: BLE001 — accounting must not fail the view
            engine_row = None
        if engine_row is not None:
            out["engine"] = {k: engine_row[k] for k in
                            ("charged_tokens", "active_slots", "pages",
                             "pending") if k in engine_row}
        return out

    @staticmethod
    def _cost(model: ModelInfo, usage: dict[str, int]) -> Optional[float]:
        if not model.cost:
            return None
        cin = model.cost.get("input_per_1k", 0.0) * usage.get("input_tokens", 0) / 1000.0
        cout = model.cost.get("output_per_1k", 0.0) * usage.get("output_tokens", 0) / 1000.0
        return round(cin + cout, 8)

    # ------------------------------------------------------------- registration
    def register_rest(self, ctx: ModuleCtx, router, openapi) -> None:
        m = "llm_gateway"
        openapi.register_schema("LlmRequest", schemas.REQUEST)
        openapi.register_schema("LlmResponse", schemas.RESPONSE)
        openapi.register_schema("StreamChunk", schemas.STREAM_CHUNK)
        openapi.register_schema("EmbeddingRequest", schemas.EMBEDDING_REQUEST)
        openapi.register_schema("Job", schemas.JOB)

        router.operation("POST", "/v1/chat/completions", module=m).auth_required() \
            .summary("Chat completion (sync, SSE stream, or async job)") \
            .request_schema(schemas.REQUEST).response_schema(schemas.RESPONSE) \
            .sse_response().handler(self.handle_chat).register()
        openapi.register_schema("CompletionRequest", schemas.COMPLETION_REQUEST)
        router.operation("POST", "/v1/completions", module=m).auth_required() \
            .summary("Raw text completion (sync or SSE stream; no chat template)") \
            .request_schema(schemas.COMPLETION_REQUEST) \
            .response_schema(schemas.RESPONSE) \
            .sse_response().handler(self.handle_completions).register()
        router.operation("POST", "/v1/embeddings", module=m).auth_required() \
            .summary("Text embeddings").request_schema(schemas.EMBEDDING_REQUEST) \
            .handler(self.handle_embeddings).register()
        router.operation("GET", "/v1/jobs/{job_id}", module=m).auth_required() \
            .summary("Async job status/result").response_schema(schemas.JOB) \
            .handler(self.handle_get_job).register()
        router.operation("DELETE", "/v1/jobs/{job_id}", module=m).auth_required() \
            .summary("Cancel an async job").handler(self.handle_cancel_job).register()
        router.operation("GET", "/v1/usage", module=m).auth_required() \
            .summary("Tenant usage counters").handler(self.handle_usage).register()
        router.operation("POST", "/v1/images/generations", module=m).auth_required() \
            .summary("Generate images (provider-backed; stored via file-storage)") \
            .handler(self.handle_image_generation).register()
        router.operation("POST", "/v1/videos/generations", module=m).auth_required() \
            .summary("Generate video (provider-backed, job-polling; stored via file-storage)") \
            .handler(self.handle_video_generation).register()
        router.operation("POST", "/v1/audio/speech", module=m).auth_required() \
            .summary("Text-to-speech (provider-backed; audio via file-storage)") \
            .handler(self.handle_speech).register()
        router.operation("POST", "/v1/audio/transcriptions", module=m).auth_required() \
            .accepts("*/*") \
            .summary("Speech-to-text (?model=...; body = audio bytes)") \
            .handler(self.handle_transcription).register()
        openapi.register_schema("Batch", schemas.BATCH)
        router.operation("POST", "/v1/batches", module=m).auth_required() \
            .summary("Submit a request batch").response_schema(schemas.BATCH) \
            .handler(self.handle_create_batch).register()
        router.operation("GET", "/v1/batches/{batch_id}", module=m).auth_required() \
            .summary("Batch status + per-item results").response_schema(schemas.BATCH) \
            .handler(self.handle_get_batch).register()
        router.operation("GET", "/v1/realtime", module=m).auth_required() \
            .summary("Realtime WebSocket session (chat.create -> token/done events)") \
            .sse_response().handler(self.handle_realtime).register()
