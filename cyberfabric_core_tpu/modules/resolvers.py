"""Tenant / authn / authz resolver gateways with static plugins.

Reference: modules/system/{tenant-resolver, authn-resolver, authz-resolver} —
gateway+plugin pattern. Plugins implemented here:

- **static tenant plugin**: config-defined tenant tree (config/quickstart.yaml:188-228
  pattern); single-tenant mode when no tree given.
- **static authn plugin**: modes ``accept_all`` (dev) and ``static`` (configured
  token → identity map) (authn-resolver static plugin).
- **static authz plugin**: role → scope-constraint rules compiled into AccessScope
  narrowing (the SDK-side PEP, authz-resolver-sdk/src/pep/).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from types import MappingProxyType
from typing import Any, Optional

from ..modkit import Module, module
from ..modkit.contracts import SystemCapability
from ..modkit.context import ModuleCtx
from ..modkit.errcat import ERR
from ..modkit.errors import Problem, ProblemError
from ..modkit.security import AccessScope, Dimension, ScopeFilter, SecretString, SecurityContext
from ..gateway.middleware import AuthnApi, AuthzApi
from .sdk import TenantResolverApi


def _deep_freeze(value: Any) -> Any:
    """Recursively freeze a JSON-ish claims tree: dict → MappingProxyType,
    list/tuple → tuple. The result is safely shareable across requests — the
    validated-token cache hands out ONE instance instead of deep-copying per
    hit, and any handler that tries to mutate identity state gets a TypeError
    instead of silently poisoning the next request."""
    if isinstance(value, dict):
        return MappingProxyType({k: _deep_freeze(v) for k, v in value.items()})
    if isinstance(value, (list, tuple)):
        return tuple(_deep_freeze(v) for v in value)
    return value


class StaticTenantResolver(TenantResolverApi):
    """Tenant tree from config: {tenant_id: {parent: ..}} or nested children."""

    def __init__(self, tree: Optional[dict[str, Any]] = None,
                 single_tenant: Optional[str] = None) -> None:
        self._parent: dict[str, Optional[str]] = {}
        self._children: dict[str, list[str]] = {}
        if single_tenant is not None:
            self._parent[single_tenant] = None
        for tenant, spec in (tree or {}).items():
            parent = (spec or {}).get("parent")
            self._parent[tenant] = parent
            if parent is not None:
                self._children.setdefault(parent, []).append(tenant)

    async def parent_of(self, tenant_id: str) -> Optional[str]:
        return self._parent.get(tenant_id)

    async def children_of(self, tenant_id: str) -> list[str]:
        return sorted(self._children.get(tenant_id, []))

    async def subtree_of(self, tenant_id: str) -> list[str]:
        out = [tenant_id]
        queue = list(self._children.get(tenant_id, []))
        while queue:
            t = queue.pop()
            out.append(t)
            queue.extend(self._children.get(t, []))
        return sorted(out)

    def knows(self, tenant_id: str) -> bool:
        return tenant_id in self._parent

    async def exists(self, tenant_id: str) -> bool:
        return self.knows(tenant_id)


class JwtAuthnResolver(AuthnApi):
    """mode: jwt — real token validation (modkit-auth parity): HS256/RS256
    signatures, exp/nbf/iss/aud, configurable claims mapping.

    config: {keys: {kid: {alg, secret|public_key_pem}}, issuer, audience,
    tenant_claim (default "tenant_id"), scopes_claim ("scope", space-separated
    or list), roles_claim ("roles"), default_tenant}.
    """

    def __init__(self, cfg: dict) -> None:
        from ..modkit.jwt import JwtValidator

        self.validator = JwtValidator.from_config(cfg)
        #: statically configured keys keep working alongside a JWKS URL
        #: (e.g. service tokens signed with a local key + user tokens from
        #: the IdP) — JWKS lookups merge into this set, never replace it
        self._static_keys = dict(self.validator.keys)
        self.jwks = None
        if cfg.get("jwks_url"):
            # remote key set with rotation (modkit-auth providers/jwks.rs parity)
            from ..modkit.jwks import JwksCache

            self.jwks = JwksCache(
                jwks_url=cfg["jwks_url"],
                cache_ttl_s=float(cfg.get("jwks_cache_ttl_s", 300.0)),
                negative_cache_s=float(cfg.get("jwks_negative_cache_s", 30.0)))
        self.tenant_claim = cfg.get("tenant_claim", "tenant_id")
        self.scopes_claim = cfg.get("scopes_claim", "scope")
        self.roles_claim = cfg.get("roles_claim", "roles")
        self.default_tenant = cfg.get("default_tenant", "default")
        #: validated-token cache: signature+claims checks are pure functions
        #: of the token bytes, so a token that validated once stays valid
        #: until its exp (capped below, bounding revocation lag the same way
        #: the JWKS cache TTL does): a request on the gateway's hot path
        #: verifies no signature twice.
        self._cache: dict[str, tuple[float, SecurityContext]] = {}
        self._cache_ttl_s = float(cfg.get("token_cache_ttl_s", 120.0))
        self._cache_max = int(cfg.get("token_cache_max", 4096))
        #: JWKS generation the cache was filled under — a key ROTATION must
        #: invalidate tokens signed by withdrawn kids right away, not after
        #: token_cache_ttl_s (the TTL only bounds same-keyset revocation lag)
        self._cache_gen = -1

    async def authenticate(self, bearer_token: Optional[str],
                           request_meta: dict[str, Any]) -> SecurityContext:
        from ..modkit.jwt import JwtError, peek_header

        if not bearer_token:
            raise ProblemError.unauthorized("missing bearer token")
        if self._cache_ttl_s > 0:
            if self.jwks is not None and self.jwks.generation != self._cache_gen:
                self._cache.clear()
                self._cache_gen = self.jwks.generation
            hit = self._cache.get(bearer_token)
            if hit is not None:
                good_until, ctx = hit
                if time.monotonic() < good_until:
                    # The cached ctx is fully immutable (frozen dataclass +
                    # deep-frozen claims, see _deep_freeze), so handing every
                    # request the SAME instance cannot leak one handler's
                    # mutation into the next request's identity — mutation
                    # attempts raise instead. Zero copies on the hot path
                    # (the per-hit deepcopy was ~15 calls/request in the
                    # gateway overhead profile).
                    return ctx
                del self._cache[bearer_token]
        try:
            if self.jwks is not None:
                kid = peek_header(bearer_token).get("kid")
                if kid is None or kid not in self._static_keys:
                    try:
                        key = await self.jwks.get_key(kid)
                    except JwtError:
                        raise
                    except Exception as e:  # noqa: BLE001 — IdP down, no cache
                        raise ERR.core.authn_unavailable.error(
                            f"JWKS endpoint unreachable: {e}")
                    self.validator.keys = {**self._static_keys, key.kid: key}
            claims = self.validator.validate(bearer_token)
        except JwtError as e:
            raise ProblemError.unauthorized(f"invalid token: {e}")
        tenant = str(claims.get(self.tenant_claim) or self.default_tenant)

        def as_str_tuple(value: Any) -> tuple[str, ...]:
            # tolerate the IdP claim zoo: null, space-separated string, single
            # string, list, or anything else (ignored) — never crash to a 500
            if isinstance(value, str):
                return tuple(value.split())
            if isinstance(value, (list, tuple)):
                return tuple(str(v) for v in value)
            return ()

        scopes = as_str_tuple(claims.get(self.scopes_claim))
        roles = as_str_tuple(claims.get(self.roles_claim))
        ctx = SecurityContext(
            subject=str(claims.get("sub", "unknown")),
            tenant_id=tenant,
            token_scopes=scopes,
            roles=roles,
            access_scope=AccessScope.for_tenants([tenant]),
            bearer_token=SecretString(bearer_token),
            # deep-frozen once at validation: every consumer (cached hits
            # included) shares one immutable claims tree — IdP claims nest
            # (realm_access.roles, aud lists), so freezing recurses
            claims=_deep_freeze(claims),
        )
        if self._cache_ttl_s > 0:
            ttl = self._cache_ttl_s
            try:
                # same coercion the validator applies (float() accepts the
                # string-typed exp some IdPs emit): the cache must never
                # outlive the token under ANY exp encoding the validator took
                ttl = min(ttl, float(claims["exp"]) - time.time())
            except (KeyError, TypeError, ValueError):
                pass  # no usable exp: fall back to the configured TTL
            if ttl > 0:
                if len(self._cache) >= self._cache_max:
                    self._cache.clear()  # bulk reset beats per-entry LRU here
                self._cache[bearer_token] = (time.monotonic() + ttl, ctx)
        return ctx


class StaticAuthnResolver(AuthnApi):
    """mode: accept_all → identity from headers/defaults; mode: static → token map
    {token: {subject, tenant_id, scopes, roles}}."""

    def __init__(self, mode: str = "accept_all", tokens: Optional[dict] = None,
                 default_tenant: str = "default",
                 known_tenants: Optional[TenantResolverApi] = None) -> None:
        if mode not in ("accept_all", "static"):
            raise ValueError(f"unknown authn mode {mode!r}")
        self.mode = mode
        self.tokens = tokens or {}
        self.default_tenant = default_tenant
        self.known_tenants = known_tenants
        if mode == "accept_all":
            # round-1 advisory: header-selected tenants silently removed
            # isolation if this dev default shipped — make it loud, and bound
            # the header to tenants the resolver actually knows
            logging.getLogger("authn").warning(
                "authn mode=accept_all: requests are UNAUTHENTICATED and the "
                "x-tenant-id header selects the tenant (restricted to tenants "
                "known to the tenant resolver). Dev/quickstart only — never "
                "production.")

    async def authenticate(self, bearer_token: Optional[str],
                           request_meta: dict[str, Any]) -> SecurityContext:
        if self.mode == "accept_all":
            tenant = request_meta.get("tenant_header") or self.default_tenant
            if tenant != self.default_tenant and self.known_tenants is not None:
                known = await self.known_tenants.exists(tenant)
                if not known:
                    raise ProblemError.unauthorized(
                        f"unknown tenant {tenant!r}")
            return SecurityContext(
                subject="anonymous", tenant_id=tenant,
                access_scope=AccessScope.for_tenants([tenant]),
                bearer_token=SecretString(bearer_token) if bearer_token else None,
            )
        if not bearer_token:
            raise ProblemError.unauthorized("missing bearer token")
        entry = self.tokens.get(bearer_token)
        if entry is None:
            raise ProblemError.unauthorized("invalid token")
        tenant = entry.get("tenant_id", self.default_tenant)
        return SecurityContext(
            subject=entry.get("subject", "user"),
            tenant_id=tenant,
            token_scopes=tuple(entry.get("scopes", ())),
            roles=tuple(entry.get("roles", ())),
            access_scope=AccessScope.for_tenants([tenant]),
            bearer_token=SecretString(bearer_token),
        )


class StaticAuthzResolver(AuthzApi):
    """PDP: per-role constraint rules narrow the access scope; the secure ORM
    enforces the result (the PEP chain of SURVEY §8.10).

    rules: {role: {"deny": [operation_id...], "owner_only": bool}}
    """

    def __init__(self, rules: Optional[dict[str, Any]] = None) -> None:
        self.rules = rules or {}

    async def authorize(self, ctx: SecurityContext, operation_id: str) -> SecurityContext:
        import dataclasses

        scope = ctx.access_scope
        for role in ctx.roles or ("_default",):
            rule = self.rules.get(role)
            if rule is None:
                continue
            if operation_id in rule.get("deny", ()):
                raise ProblemError.forbidden(
                    f"role {role} denied operation {operation_id}")
            if rule.get("owner_only"):
                scope = scope.merged_with(AccessScope(
                    filters=(ScopeFilter(Dimension.OWNER, (ctx.subject,)),)))
        return dataclasses.replace(ctx, access_scope=scope)


@module(name="tenant_resolver", capabilities=["system"])
class TenantResolverModule(Module, SystemCapability):
    async def init(self, ctx: ModuleCtx) -> None:
        cfg = ctx.raw_config()
        resolver = StaticTenantResolver(
            tree=cfg.get("tenants"),
            single_tenant=cfg.get("single_tenant", "default" if not cfg.get("tenants") else None),
        )
        ctx.client_hub.register(TenantResolverApi, resolver)


@module(name="authn_resolver", deps=["tenant_resolver"], capabilities=["system"])
class AuthnResolverModule(Module, SystemCapability):
    async def init(self, ctx: ModuleCtx) -> None:
        cfg = ctx.raw_config()
        mode = cfg.get("mode", "accept_all")
        if mode == "jwt":
            resolver: AuthnApi = JwtAuthnResolver(cfg)
        else:
            resolver = StaticAuthnResolver(
                mode=mode,
                tokens=cfg.get("tokens"),
                default_tenant=cfg.get("default_tenant", "default"),
                known_tenants=ctx.client_hub.try_get(TenantResolverApi),
            )
        ctx.client_hub.register(AuthnApi, resolver)


@module(name="authz_resolver", capabilities=["system"])
class AuthzResolverModule(Module, SystemCapability):
    async def init(self, ctx: ModuleCtx) -> None:
        ctx.client_hub.register(AuthzApi, StaticAuthzResolver(ctx.raw_config().get("rules")))
