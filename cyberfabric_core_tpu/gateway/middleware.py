"""The gateway middleware stack — 12 layers, in the reference's documented order.

Reference: docs/MODULES.md:664-677 and api-gateway/src/module.rs:162-341:
  1 RequestID → 2 Trace → 3 Timeout → 4 BodyLimit → 5 CORS → 6 MIME validation
  → 7 RateLimit (RPS bucket + in-flight semaphore) → 8 error mapping (RFC-9457)
  → 9 Auth (token → SecurityContext) → 10 policy injection → 11 License validation
  → 12 Router/handler.

Composition model: the reference builds its tower layer stack ONCE at router
construction (module.rs:162-341 chains `ServiceBuilder::layer` calls before any
request arrives) — not per request. This does the same: `RouteStackBuilder`
composes the 12 layers around each route's handler at registration time, with
the matched ``OperationSpec`` bound in the closures. Layers that are no-ops for
a given spec (no CORS configured, no MIME list, SSE timeout exemption, no
license feature, …) are elided at BUILD time, so the per-request path pays only
for the layers the route actually uses. aiohttp's per-request middleware
re-wrapping (one partial + coroutine per layer per request) is bypassed; only a
single app-level fallback middleware remains to map router-raised 404/405 into
RFC-9457 documents.
"""

from __future__ import annotations

import asyncio
import os
import time
from typing import Any, Awaitable, Callable, Optional

from aiohttp import web

from ..modkit.errcat import ERR
from ..modkit.errors import Problem, ProblemError
from ..modkit.failpoints import failpoint_async
from ..modkit.security import SecurityContext
from ..modkit.telemetry import (Tracer, reset_log_context, set_log_context)
from .router import AuthPolicy, OperationSpec, RateLimitSpec

REQUEST_ID_HEADER = "x-request-id"
#: endpoints served by the gateway itself, always public (module.rs /docs,
#: /openapi.json, /health, /healthz; /readyz is the doctor's readiness
#: surface — load balancers probe it unauthenticated). Source of truth for
#: the auth surface: module.py asserts its builtin registrations match this
#: set exactly.
BUILTIN_PUBLIC_PATHS = frozenset({"/health", "/healthz", "/readyz",
                                  "/openapi.json", "/docs"})
SPEC_KEY = web.AppKey("operation_spec", object)
SECURITY_CONTEXT_KEY = "security_context"
REQUEST_ID_KEY = "request_id"


class AuthnApi:
    """Inbound authn contract resolved from the ClientHub
    (authn-resolver SDK: modules/system/authn-resolver/authn-resolver-sdk)."""

    async def authenticate(self, bearer_token: Optional[str], request_meta: dict[str, Any]) -> SecurityContext:
        raise NotImplementedError


class LicenseApi:
    """License validation contract (api-gateway/src/middleware/license_validation.rs)."""

    async def check_feature(self, ctx: SecurityContext, feature: str) -> bool:
        raise NotImplementedError


class AuthzApi:
    """PDP contract: returns (possibly narrowed) access scope for a request
    (modules/system/authz-resolver)."""

    async def authorize(self, ctx: SecurityContext, operation_id: str) -> SecurityContext:
        return ctx


class _TokenBucket:
    def __init__(self, rps: float, burst: int) -> None:
        self.rate = rps
        self.capacity = float(max(burst, 1))
        self.tokens = self.capacity
        self.last = time.monotonic()

    def try_acquire(self) -> bool:
        now = time.monotonic()
        self.tokens = min(self.capacity, self.tokens + (now - self.last) * self.rate)
        self.last = now
        if self.tokens >= 1.0:
            self.tokens -= 1.0
            return True
        return False


class RateLimiterMap:
    """Per-route limiter state (RateLimiterMap::from_specs, middleware/rate_limit.rs)."""

    def __init__(self) -> None:
        self._buckets: dict[str, _TokenBucket] = {}
        self._semaphores: dict[str, asyncio.Semaphore] = {}

    def for_spec(self, spec: OperationSpec) -> tuple[Optional[_TokenBucket], Optional[asyncio.Semaphore]]:
        rl = spec.rate_limit
        if rl is None:
            return None, None
        key = f"{spec.method} {spec.path}"
        if key not in self._buckets:
            self._buckets[key] = _TokenBucket(rl.rps, rl.burst)
            self._semaphores[key] = asyncio.Semaphore(rl.max_in_flight)
        return self._buckets[key], self._semaphores[key]


def _problem_response(problem: Problem, request_id: Optional[str] = None) -> web.Response:
    if request_id and problem.trace_id is None:
        problem.trace_id = request_id
    resp = web.json_response(
        problem.to_dict(), status=problem.status, content_type=Problem.CONTENT_TYPE
    )
    # backpressure contract: a 429 carries Retry-After so well-behaved
    # clients pace instead of hammering (scheduler saturation, rate limits);
    # the hint rides in the problem's extensions as ``retry_after_s``
    if problem.status == 429:
        retry_after = problem.extensions.get("retry_after_s", 1)
        try:
            resp.headers["Retry-After"] = str(max(1, int(float(retry_after))))
        except (TypeError, ValueError):
            resp.headers["Retry-After"] = "1"
    return resp


#: next-layer type: the composed chain passes only the request
Handler = Callable[[web.Request], Awaitable[web.StreamResponse]]


class RouteStackBuilder:
    """Composes the 12-layer stack around one route's handler at build time.

    Mirrors the reference's `ServiceBuilder::layer` chain (module.rs:162-341),
    which is also assembled once per router, not per request. ``compose`` binds
    the route's OperationSpec into the layer closures and drops layers that are
    statically no-ops for that route.
    """

    def __init__(
        self,
        *,
        tracer: Tracer,
        timeout_secs: float = 30.0,
        max_body_bytes: int = 64 * 1024 * 1024,
        cors_allow_origin: Optional[str] = None,
        auth_disabled: bool = False,
        default_tenant: str = "default",
        authn: Optional[AuthnApi] = None,
        authz: Optional[AuthzApi] = None,
        license_api: Optional[LicenseApi] = None,
        limiter: Optional[RateLimiterMap] = None,
    ) -> None:
        self.tracer = tracer
        self.timeout_secs = timeout_secs
        self.max_body_bytes = max_body_bytes
        self.cors_allow_origin = cors_allow_origin
        self.auth_disabled = auth_disabled
        self.default_tenant = default_tenant
        self.authn = authn
        self.authz = authz
        self.license_api = license_api
        self.limiter = limiter or RateLimiterMap()
        # metric objects hoisted out of the per-request path (name→object
        # lookup plus help-text interning per request showed up in the
        # overhead profile)
        from ..modkit.metrics import default_registry

        self._req_counter = default_registry.counter(
            "http_requests_total", "HTTP requests served")
        self._req_latency = default_registry.histogram(
            "http_request_duration_seconds", "Request latency")

    def compose(self, spec: Optional[OperationSpec], endpoint: Handler,
                *, builtin_public: bool = False) -> Handler:
        """Wrap ``endpoint`` in layers 1-11 for ``spec``.

        ``spec=None`` is only legal for the gateway's own builtin public
        endpoints (auth.rs public-route matchers :31,120-127); any other
        spec-less composition fails closed in the auth layer.
        """
        h = endpoint
        h = self._license_layer(spec, h)          # 11
        h = self._policy_layer(spec, h)           # 10
        h = self._auth_layer(spec, h, builtin_public)  # 9
        h = self._error_layer(spec, h)            # 8
        h = self._rate_layer(spec, h)             # 7
        h = self._mime_layer(spec, h)             # 6
        h = self._cors_layer(h)                   # 5
        h = self._body_layer(h)                   # 4
        h = self._timeout_layer(spec, h)          # 3
        h = self._trace_layer(spec, h)            # 2
        h = self._request_id_layer(spec, h)       # 1 (outermost)
        return h

    # ------------------------------------------------------------ layers 1-2
    def _request_id_layer(self, spec: Optional[OperationSpec], inner: Handler) -> Handler:
        # layer 1: SetRequestId/PropagateRequestId (module.rs:331-336); also
        # attaches the matched spec (the request-extensions pattern) for any
        # handler/tooling that introspects request["spec"]
        async def request_id(request: web.Request) -> web.StreamResponse:
            rid = request.headers.get(REQUEST_ID_HEADER) or os.urandom(16).hex()
            request[REQUEST_ID_KEY] = rid
            request["spec"] = spec
            resp = await inner(request)
            resp.headers[REQUEST_ID_HEADER] = rid
            return resp

        return request_id

    def _trace_layer(self, spec: Optional[OperationSpec], inner: Handler) -> Handler:
        # layer 2: TraceLayer span with method/uri/request_id (module.rs:276-281)
        # + serving metrics (request counter, latency histogram per route)
        tracer = self.tracer
        counter, latency = self._req_counter, self._req_latency
        route_label = spec.path if spec is not None else None

        async def trace(request: web.Request) -> web.StreamResponse:
            start = time.monotonic()
            with tracer.span(
                f"http {request.method} {request.path}",
                traceparent=request.headers.get("traceparent"),
                method=request.method,
                path=request.path,
                request_id=request.get(REQUEST_ID_KEY),
            ) as span:
                request["trace_id"] = span.trace_id
                # log correlation for every line this request's task emits
                # (handlers, llm_gateway worker, module code) — the scheduler
                # thread sets its own context per request operation
                log_token = set_log_context(request.get(REQUEST_ID_KEY),
                                            span.trace_id)
                try:
                    resp = await inner(request)
                finally:
                    reset_log_context(log_token)
                span.set_attribute("status", resp.status)
                route = route_label if route_label is not None else request.path
                counter.inc(
                    route=route, method=request.method, status=str(resp.status))
                latency.observe(time.monotonic() - start, route=route)
                return resp

        return trace

    # ------------------------------------------------------------ layers 3-5
    def _timeout_layer(self, spec: Optional[OperationSpec], inner: Handler) -> Handler:
        # layer 3: TimeoutLayer, 30s default (module.rs:265). SSE streams exempt —
        # the timeout guards handler completion, and streaming handlers return
        # a prepared StreamResponse quickly or not at all.
        if spec is not None and spec.sse:
            return inner
        timeout_secs = self.timeout_secs

        async def timeout(request: web.Request) -> web.StreamResponse:
            try:
                # asyncio.timeout over wait_for: no per-request wrapper Task
                # (~50 µs saved on the hot path, same cancel semantics)
                async with asyncio.timeout(timeout_secs):
                    return await inner(request)
            except asyncio.TimeoutError:
                return _problem_response(
                    ERR.core.timeout.problem(f"request exceeded {timeout_secs}s"),
                    request.get(REQUEST_ID_KEY),
                )

        return timeout

    def _body_layer(self, inner: Handler) -> Handler:
        # layer 4: RequestBodyLimitLayer (module.rs:261)
        max_body_bytes = self.max_body_bytes

        async def body_limit(request: web.Request) -> web.StreamResponse:
            cl = request.content_length
            if cl is not None and cl > max_body_bytes:
                return _problem_response(
                    ERR.core.body_too_large.problem(
                        f"body exceeds {max_body_bytes} bytes"),
                    request.get(REQUEST_ID_KEY),
                )
            return await inner(request)

        return body_limit

    def _cors_layer(self, inner: Handler) -> Handler:
        # layer 5: CORS (optional; cors.rs) — elided entirely when unconfigured
        origin = self.cors_allow_origin
        if origin is None:
            return inner

        async def cors(request: web.Request) -> web.StreamResponse:
            # OPTIONS preflight never reaches here — the app-level fallback
            # middleware short-circuits it to 204 (make_router_fallback_mw)
            return _apply_cors_headers(await inner(request), origin)

        return cors

    # ------------------------------------------------------------ layers 6-8
    def _mime_layer(self, spec: Optional[OperationSpec], inner: Handler) -> Handler:
        # layer 6: per-route MIME validation (middleware/mime_validation.rs);
        # elided for bodyless methods — spec.method is fixed at build time
        if (spec is None or not spec.accepted_mime or "*/*" in spec.accepted_mime
                or spec.method not in ("POST", "PUT", "PATCH")):
            return inner
        accepted = tuple(spec.accepted_mime)

        async def mime(request: web.Request) -> web.StreamResponse:
            if request.content_length:
                ctype = (request.content_type or "").lower()
                if not any(
                    ctype == m or (m.endswith("/*") and ctype.startswith(m[:-1]))
                    for m in accepted
                ):
                    return _problem_response(
                        ERR.core.unsupported_media_type.problem(
                            f"expected one of {list(accepted)}, got {ctype!r}"),
                        request.get(REQUEST_ID_KEY),
                    )
            return await inner(request)

        return mime

    def _rate_layer(self, spec: Optional[OperationSpec], inner: Handler) -> Handler:
        # layer 7: RPS bucket + in-flight semaphore (middleware/rate_limit.rs);
        # limiter state resolved at build time — route hot-swap recomposes
        if spec is None:
            return inner
        bucket, sem = self.limiter.for_spec(spec)
        if bucket is None and sem is None:
            return inner

        async def rate_limit(request: web.Request) -> web.StreamResponse:
            if bucket is not None and not bucket.try_acquire():
                return _problem_response(
                    ERR.core.rate_limited.problem("per-route rate limit exceeded"),
                    request.get(REQUEST_ID_KEY),
                )
            if sem is not None:
                if sem.locked():
                    return _problem_response(
                        ERR.core.too_many_in_flight.problem(
                            "per-route in-flight limit reached"),
                        request.get(REQUEST_ID_KEY),
                    )
                async with sem:
                    return await inner(request)
            return await inner(request)

        return rate_limit

    def _error_layer(self, spec: Optional[OperationSpec],
                     inner: Handler) -> Handler:
        # layer 8: error mapping → RFC-9457 (libs/modkit/src/api/error_layer.rs)
        # The failpoint control plane is EXEMPT from its own fault injection:
        # arming gateway.request with an always-raise must never brick the
        # disarm/reset endpoints an operator needs to recover a live server.
        faultable = not (spec is not None
                         and spec.path.startswith("/v1/monitoring/failpoints"))

        async def error_mapping(request: web.Request) -> web.StreamResponse:
            try:
                # chaos rehearsals arm this to fault/delay live requests
                # INSIDE the error-mapping boundary: an injected raise comes
                # back as an RFC-9457 5xx, an injected delay hits the timeout
                # layer — exactly what a misbehaving handler would do
                if faultable:
                    await failpoint_async("gateway.request")
                return await inner(request)
            except ProblemError as e:
                return _problem_response(e.problem, request.get(REQUEST_ID_KEY))
            except web.HTTPException as e:
                if e.status >= 400:
                    # framework 404/405/… become RFC-9457 documents too
                    return _problem_response(
                        Problem(status=e.status, title=e.reason or "Error",
                                code=(e.reason or "error").lower().replace(" ", "_")),
                        request.get(REQUEST_ID_KEY))
                raise
            except asyncio.CancelledError:
                raise
            except Exception:
                import logging
                logging.getLogger("gateway").exception(
                    "unhandled error in %s", request.path)
                return _problem_response(
                    ERR.core.internal_error.problem(),
                    request.get(REQUEST_ID_KEY),
                )

        return error_mapping

    # ----------------------------------------------------------- layers 9-11
    def _auth_layer(self, spec: Optional[OperationSpec], inner: Handler,
                    builtin_public: bool) -> Handler:
        # layer 9: route policy → token verify → SecurityContext (middleware/auth.rs:83-127)
        if spec is None and builtin_public:
            # gateway's own public endpoints run without a SecurityContext
            # (auth.rs public-route matchers :31,120-127)
            return inner
        default_tenant = self.default_tenant
        if spec is None:
            if self.auth_disabled:
                async def anon(request: web.Request) -> web.StreamResponse:
                    request[SECURITY_CONTEXT_KEY] = SecurityContext.anonymous(default_tenant)
                    return await inner(request)

                return anon

            # fail CLOSED: a spec-less non-builtin composition is a routing bug
            async def unauthorized(request: web.Request) -> web.StreamResponse:
                raise ProblemError.unauthorized("no route policy for this path")

            return unauthorized
        if spec.auth == AuthPolicy.PUBLIC or self.auth_disabled:
            # dev-mode parity: auth_disabled: true (quickstart.yaml:108)
            async def public(request: web.Request) -> web.StreamResponse:
                request[SECURITY_CONTEXT_KEY] = SecurityContext.anonymous(default_tenant)
                return await inner(request)

            return public
        authn = self.authn
        required_scopes = tuple(spec.required_scopes)

        async def auth(request: web.Request) -> web.StreamResponse:
            authz_header = request.headers.get("Authorization", "")
            token = authz_header[7:] if authz_header.lower().startswith("bearer ") else None
            if authn is None:
                raise ProblemError.unauthorized("no authn resolver configured")
            sec_ctx = await authn.authenticate(
                token, {"path": request.path, "method": request.method,
                        "tenant_header": request.headers.get("x-tenant-id")}
            )
            missing = [s for s in required_scopes if not sec_ctx.has_scope(s)]
            if missing:
                raise ProblemError.forbidden(f"missing required scopes: {missing}")
            request[SECURITY_CONTEXT_KEY] = sec_ctx
            return await inner(request)

        return auth

    def _policy_layer(self, spec: Optional[OperationSpec], inner: Handler) -> Handler:
        # layer 10: policy-engine (PDP) injection (module.rs:213)
        authz = self.authz
        if spec is None or authz is None:
            return inner
        operation_id = spec.operation_id

        async def policy(request: web.Request) -> web.StreamResponse:
            sec_ctx: Optional[SecurityContext] = request.get(SECURITY_CONTEXT_KEY)
            if sec_ctx is not None:
                request[SECURITY_CONTEXT_KEY] = await authz.authorize(sec_ctx, operation_id)
            return await inner(request)

        return policy

    def _license_layer(self, spec: Optional[OperationSpec], inner: Handler) -> Handler:
        # layer 11: license validation per OperationSpec (middleware/license_validation.rs)
        if spec is None or spec.license_feature is None:
            return inner
        license_api = self.license_api
        feature = spec.license_feature

        async def license_check(request: web.Request) -> web.StreamResponse:
            sec_ctx = request.get(SECURITY_CONTEXT_KEY)
            if license_api is None or not await license_api.check_feature(sec_ctx, feature):
                raise ERR.core.license_required.error(
                    f"feature '{feature}' is not licensed")
            return await inner(request)

        return license_check


def _apply_cors_headers(resp: web.StreamResponse, origin: str) -> web.StreamResponse:
    """The one place CORS response headers are written — the per-route layer
    and the app-level preflight/error paths must never diverge."""
    resp.headers["Access-Control-Allow-Origin"] = origin
    resp.headers["Access-Control-Allow-Methods"] = "GET,POST,PUT,PATCH,DELETE,OPTIONS"
    resp.headers["Access-Control-Allow-Headers"] = "authorization,content-type,x-request-id"
    return resp


#: metric label for requests that matched no route: 404-scan traffic must be
#: VISIBLE in aggregate but must not mint one label set per probed path
#: (unbounded cardinality); the per-request trace span keeps the exact path
UNMATCHED_ROUTE_LABEL = "<unmatched>"


def make_router_fallback_mw(*, tracer: Tracer,
                            cors_allow_origin: Optional[str] = None,
                            auth_disabled: bool = False):
    """App-level fallback: the only per-request aiohttp middleware left.

    Matched routes are fully pre-composed, so for them this does nothing but
    await the composed handler. It owns two cross-route concerns the old
    global stack provided:

    - CORS preflight: when CORS is configured, EVERY ``OPTIONS`` request
      short-circuits to 204 with the CORS headers (the old layer-5 behavior —
      browsers preflight against routes that only register POST/GET, which
      would otherwise 405 without CORS headers and block the real request).
    - UNMATCHED routes: aiohttp's dispatcher raises HTTPNotFound /
      HTTPMethodNotAllowed. With auth ENABLED these fail closed as 401 —
      exactly what the old spec-less auth_mw branch did (auth.rs:120-127
      parity) — so an unauthenticated caller cannot distinguish existing
      routes from absent ones (route enumeration). With auth disabled they
      come back as RFC-9457 404/405 documents. Either way the response
      carries an x-request-id, lands in http_requests_total / the latency
      histogram (under a fixed ``<unmatched>`` route label), and gets a
      trace span — a 404 scan that's invisible to dashboards is an
      observability hole.
    """
    from ..modkit.metrics import default_registry

    req_counter = default_registry.counter(
        "http_requests_total", "HTTP requests served")
    req_latency = default_registry.histogram(
        "http_request_duration_seconds", "Request latency")

    def _observe(request: web.Request, resp: web.StreamResponse,
                 start_ns: int, rid: str) -> web.StreamResponse:
        scope = tracer.span(
            f"http {request.method} {request.path}",
            traceparent=request.headers.get("traceparent"),
            method=request.method, path=request.path, request_id=rid,
        )
        # backdate to middleware entry so the exported span carries the real
        # request duration, not the microseconds this epilogue takes
        elapsed_ns = time.monotonic_ns() - start_ns
        scope.span.start_ns -= elapsed_ns
        scope.span.start_unix_ns -= elapsed_ns
        with scope as span:
            span.set_attribute("status", resp.status)
        resp.headers[REQUEST_ID_HEADER] = rid
        req_counter.inc(route=UNMATCHED_ROUTE_LABEL, method=request.method,
                        status=str(resp.status))
        req_latency.observe(elapsed_ns / 1e9, route=UNMATCHED_ROUTE_LABEL)
        return resp

    @web.middleware
    async def router_fallback_mw(request: web.Request, handler):
        start_ns = time.monotonic_ns()
        if cors_allow_origin is not None and request.method == "OPTIONS":
            rid = request.headers.get(REQUEST_ID_HEADER) or os.urandom(16).hex()
            request[REQUEST_ID_KEY] = rid
            return _observe(
                request,
                _apply_cors_headers(web.Response(status=204), cors_allow_origin),
                start_ns, rid)
        try:
            return await handler(request)
        except web.HTTPException as e:
            if e.status < 400:
                raise
            rid = request.headers.get(REQUEST_ID_HEADER) or os.urandom(16).hex()
            request[REQUEST_ID_KEY] = rid
            if not auth_disabled:
                # fail CLOSED: unmatched paths are indistinguishable from
                # unauthenticated ones (the old auth_mw spec-less branch)
                problem = ProblemError.unauthorized(
                    "no route policy for this path").problem
            else:
                problem = Problem(
                    status=e.status, title=e.reason or "Error",
                    code=(e.reason or "error").lower().replace(" ", "_"))
            resp = _problem_response(problem, rid)
            if cors_allow_origin is not None:
                _apply_cors_headers(resp, cors_allow_origin)
            return _observe(request, resp, start_ns, rid)

    return router_fallback_mw
