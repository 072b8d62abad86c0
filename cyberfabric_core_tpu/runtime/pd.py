"""Prefill/decode disaggregated serving pool (PD split).

The residual decode-ITL tail of mixed rounds is prefill interference: a cold
prompt storm landing on a unified replica steals the decode round's dispatch
budget even with Sarathi-style chunking — the storm rounds are "mixed"/
"prefill" kinds in ``stats()["pipeline"]["dispatch_ms_by_kind"]``, and a
decode stream's ITL inherits their p99. The RTP-LLM production recipe
(PAPERS.md) removes the interference structurally: dedicated PREFILL-role
workers run only chunked prefill and hand each stream's KV to a DECODE-role
pool, so a decode engine's rounds are pure-decode by construction.

:class:`PDServingPool` is that recipe over the existing replica machinery
(runtime/replicas.py + runtime/lifecycle.py):

- **Roles.** ``n_prefill`` replicas run ``pd_role="prefill"`` engines
  (mixed-batch chunked prefill, prefix radix intact, speculation/lookahead
  off — no decode rows ever persist past the first token); ``n_decode``
  replicas run ``pd_role="decode"`` engines (deep ring + speculation
  intact, zero prefill work). Each engine feasibility-gates its own role
  config at build time.
- **Handoff.** After the first token samples on a prefill engine, its
  scheduler exports the request's committed KV pages + resume state
  (``PrefixKVPool.export_pages`` → host numpy, sharding-agnostic, so pages
  move between same-tp meshes) and calls :meth:`on_handoff`, which routes
  the record to the least-loaded decode engine's ``submit_handoff``. The
  decode scheduler admits it through the suspended-resume path — a
  "handoff phase" that restores pages (``import_pages``) and continues
  decoding with no prefill. One request id carries the whole story:
  enqueued → prefill_chunk* → prefill → handoff_export → handoff_import →
  decode_chunk* → finished.
- **Warm prefixes.** Prefill engines keep the radix tree (export leaves
  tree-shared pages cached), and role-aware ``_pick`` probes the PREFILL
  group's caches — a warm prefix routes to the prefill replica holding it
  and the handoff shrinks to the uncached suffix's cost.
- **Failure.** A prefill replica breaking mid-handoff (the
  ``scheduler.handoff`` failpoint) error-terminates the stream into the
  pool's existing failover, which re-prefills prompt+emitted on a
  surviving prefill replica — greedy streams stay bit-identical, nothing
  leaks (the broken engine's pool dies whole). A decode replica breaking
  mid-stream fails over the same way (the continuation re-prefills on the
  prefill group; a decode corpse in ``exclude`` is harmless).
- **Role flips.** :meth:`flip_role` retags a replica and drains it through
  the lifecycle manager; the rebuild (Tangram-style: params stay
  device-resident, rebuild cost is scheduler + program build) comes back
  in the new role. :meth:`rebalance` recommends a flip when one side
  saturates while the other idles.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Any, Optional

import jax

from ..modkit.flight_recorder import record_event
from ..modkit.metrics import bump_counter
from .engine import EngineConfig, StepEvent
from .lifecycle import LifecycleConfig, ReplicaLifecycleManager
from .replicas import DataParallelServingPool
from .scheduler import ContinuousBatchingEngine

logger = logging.getLogger("pd")


def _role_config(config: EngineConfig, role: str) -> EngineConfig:
    """Derive a role's engine config from the shared base. Prefill engines
    never decode past the first token: lookahead and speculation are decode
    machinery and only cost program builds there — force them off. Decode
    engines keep the base config (ring depth, spec_k) untouched."""
    if role == "prefill":
        return dataclasses.replace(config, pd_role="prefill",
                                   decode_lookahead=0, scheduler_spec_k=0)
    return dataclasses.replace(config, pd_role="decode")


class PDServingPool(DataParallelServingPool):
    """Role-split serving pool: prefill-role + decode-role replica groups
    with page-granularity KV handoff. Same submit()/cancel()/stats()
    surface as the unified pool — the split is invisible to callers apart
    from decode rounds that never carry prefill chunks."""

    def __init__(
        self,
        config: EngineConfig,
        n_prefill: int,
        n_decode: int,
        devices: Optional[list[Any]] = None,
        seed: int = 0,
        max_retries: int = 1,
        lifecycle: Any = None,
        params: Optional[Any] = None,
    ) -> None:
        if n_prefill < 1 or n_decode < 1:
            raise ValueError(
                f"PD split needs at least one replica per role, got "
                f"pd_prefill_replicas={n_prefill}, "
                f"pd_decode_replicas={n_decode}")
        devices = devices if devices is not None else jax.devices()
        n_total = n_prefill + n_decode
        if n_total > len(devices):
            raise ValueError(
                f"{n_total} PD replicas need {n_total} devices, have "
                f"{len(devices)}")
        self.config = config
        self.max_retries = max_retries
        self._seed = seed
        import random

        self._failover_rng = random.Random(seed ^ 0xFA17)
        self._lock = threading.Lock()
        self._requests = {}
        self.failovers = 0
        self.failovers_failed = 0
        self.placement_hint_hits = 0
        self.cache_affinity_slack = max(1, config.max_batch // 2)
        #: successful cross-engine KV handoffs / handoffs that found no
        #: decode target (the stream then error-terminates into failover)
        self.handoffs = 0
        self.handoffs_failed = 0
        #: authoritative role tags, index-aligned with ``replicas`` —
        #: groups are DERIVED from this list so flip_role stays one write
        self._roles: list[str] = (["prefill"] * n_prefill
                                  + ["decode"] * n_decode)
        self.replicas: list[ContinuousBatchingEngine] = []
        self.devices = devices[:n_total]
        for i, dev in enumerate(self.devices):
            eng = ContinuousBatchingEngine(
                _role_config(config, self._roles[i]), params=params,
                seed=seed, device=dev)
            if self._roles[i] == "prefill":
                eng._handoff_sink = self.on_handoff
            self.replicas.append(eng)
        if lifecycle:
            lc_cfg = LifecycleConfig.from_config(lifecycle)
            if lc_cfg.enabled:
                self.lifecycle = ReplicaLifecycleManager(self, lc_cfg)
                self.lifecycle.start()
        logger.info(
            "PD serving pool: %d prefill + %d decode replicas over %s "
            "(lifecycle %s)", n_prefill, n_decode,
            [str(d) for d in self.devices],
            "supervised" if self.lifecycle is not None else "off")

    # ------------------------------------------------------------------ roles
    def _prefill_group(self) -> list[int]:
        return [i for i, r in enumerate(self._roles) if r == "prefill"]

    def _decode_group(self) -> list[int]:
        return [i for i, r in enumerate(self._roles) if r == "decode"]

    def build_replica(self, idx: int) -> ContinuousBatchingEngine:
        """Role-aware rebuild: the fresh engine takes slot ``idx``'s CURRENT
        role tag (a pending flip_role lands here) and prefill rebuilds are
        re-wired to the handoff sink. Params reuse keeps the rebuild at
        scheduler + program-build cost (Tangram weight reuse)."""
        old = self.replicas[idx]
        eng = ContinuousBatchingEngine(
            _role_config(self.config, self._roles[idx]),
            params=getattr(old, "params", None),
            seed=self._seed, device=self.devices[idx])
        if self._roles[idx] == "prefill":
            eng._handoff_sink = self.on_handoff
        return eng

    def _pick(self, prompt_ids=None, exclude=(), group=None) -> int:
        """Role-aware routing: every pick defaults to the PREFILL group —
        fresh submits must prefill, and a failover continuation
        (prompt + emitted) must RE-prefill, both on a prefill engine. The
        cache-affinity probe therefore consults exactly the prefill
        radixes. Decode-group picks (handoff targets) pass the group
        explicitly from on_handoff."""
        if group is None:
            group = self._prefill_group()
        return super()._pick(prompt_ids, exclude=exclude, group=group)

    # ------------------------------------------------------------------ handoff
    def on_handoff(self, rec: Any) -> None:
        """Route a prefill engine's exported stream to a decode engine.
        Runs on the SOURCE engine's scheduler thread (the export hook) —
        non-blocking bookkeeping + one submit_handoff enqueue, and it never
        raises: a raise would break the prefill engine mid-round. No decode
        target (all broken/draining) error-terminates the stream through
        its wrapped emit, which drives the pool's normal failover —
        re-prefill on a survivor — so the client never sees the gap."""
        rid = rec.state.request_id
        with self._lock:
            tracked = self._requests.get(rid)
        old = tracked.replica if tracked is not None else None
        try:
            idx = super()._pick(group=self._decode_group())
            self._note_dispatch(idx)
            try:
                self.replicas[idx].submit_handoff(rec)
            except Exception:
                self._note_departed(idx)
                raise
        except Exception as e:  # noqa: BLE001 — includes "no healthy replicas"
            self.handoffs_failed += 1
            logger.warning("handoff of %s found no decode target (%s); "
                           "failing over to re-prefill", rid, e)
            record_event(rid, "error",
                         detail=f"handoff failed: {e}"[:200])
            try:
                rec.state.emit(StepEvent(0, -1, "error"))
            except Exception:  # noqa: BLE001 — the wrapper owns terminals
                pass
            return
        if tracked is not None:
            # the stream now lives on the decode replica: terminals and
            # cancels must target it, and the prefill replica's lifecycle
            # in-flight count releases (its work is done)
            tracked.replica = idx
            if old is not None:
                self._note_departed(old)
            if tracked.cancelled:
                # a cancel raced the handoff window: it was forwarded to
                # the prefill engine, but the request just moved — forward
                # to the new owner so the dead client's stream stops there
                try:
                    self.replicas[idx].cancel(rid, "cancelled")
                except Exception:  # noqa: BLE001 — best-effort forward
                    pass
        self.handoffs += 1
        bump_counter("llm_pd_handoffs_total")

    # ------------------------------------------------------------------ flips
    def flip_role(self, idx: int, role: str,
                  deadline_s: Optional[float] = None) -> dict[str, Any]:
        """Drain-based role flip: retag replica ``idx`` and recycle its
        engine into the new role. With a lifecycle manager the replica
        DRAINS first (in-flight streams finish; past ``deadline_s`` the
        stragglers fail over) and a small waiter restarts it once drained —
        the rebuild lands in the new role via build_replica. Without a
        manager the flip rebuilds inline (in-flight streams fail over,
        which the wrapped emits resolve). Each role keeps >= 1 replica —
        a PD pool with an empty side cannot serve."""
        if role not in ("prefill", "decode"):
            raise ValueError(f"role must be 'prefill' or 'decode', got "
                             f"{role!r}")
        if not 0 <= idx < len(self.replicas):
            raise IndexError(f"replica index {idx} out of range")
        if self._roles[idx] == role:
            return {"index": idx, "role": role, "flipped": False}
        old_group = [i for i in range(len(self._roles))
                     if self._roles[i] == self._roles[idx] and i != idx]
        if not old_group:
            raise ValueError(
                f"cannot flip replica {idx}: it is the last "
                f"{self._roles[idx]}-role replica")
        old_role = self._roles[idx]
        self._roles[idx] = role
        record_event(f"pd/replica{idx}", "role_flip", replica=idx,
                     from_role=old_role, to_role=role)
        logger.info("PD role flip: replica %d %s -> %s", idx, old_role, role)
        if self.lifecycle is None:
            # no supervisor: recycle inline. close() error-terminates any
            # in-flight work into the failover wrappers first.
            try:
                self.replicas[idx].close(timeout=5.0)
            except Exception:  # noqa: BLE001 — a corpse must not block the flip
                logger.exception("closing replica %d for role flip failed",
                                 idx)
            eng = self.build_replica(idx)
            eng.start()
            self.replicas[idx] = eng
            return {"index": idx, "role": role, "flipped": True,
                    "mode": "inline"}
        self.lifecycle.drain(idx, deadline_s)
        waiter = threading.Thread(
            target=self._await_drain_then_restart, args=(idx,),
            name=f"pd-flip-{idx}", daemon=True)
        waiter.start()
        return {"index": idx, "role": role, "flipped": True, "mode": "drain"}

    def _await_drain_then_restart(self, idx: int) -> None:
        """Background half of a supervised flip: poll the lifecycle state
        until the drain resolves, then restart so the supervisor rebuilds
        in the new role. Exits quietly if the drain is pre-empted (undrain,
        crash → quarantine): every other path to a rebuild already goes
        through build_replica, which reads the new role tag anyway."""
        lc = self.lifecycle
        while lc is not None:
            try:
                state = lc.status_row(idx)["state"]
            except Exception:  # noqa: BLE001 — manager stopped mid-flip
                return
            if state == "drained":
                try:
                    lc.restart(idx)
                except Exception:  # noqa: BLE001 — raced an operator action
                    pass
                return
            if state != "draining":
                return  # undrained / crashed; the flip lands at next rebuild
            time.sleep(0.05)

    def rebalance(self) -> dict[str, Any]:
        """Advisory flip recommendation off the live group loads: when one
        role's replicas are saturated (mean load >= max_batch) while the
        other side idles, recommend flipping the other side's least-loaded
        replica. Pure read — callers (doctor, operators) decide whether to
        act via flip_role."""
        def group_load(group: list[int]) -> float:
            loads = []
            for i in group:
                try:
                    s = self.replicas[i].stats()
                except Exception:  # noqa: BLE001 — broken reads as busy
                    loads.append(float(self.config.max_batch))
                    continue
                loads.append(s["active"] + s["pending"]
                             + s.get("prefilling", 0) + s.get("suspended", 0))
            return sum(loads) / max(1, len(loads))

        pg, dg = self._prefill_group(), self._decode_group()
        p_load, d_load = group_load(pg), group_load(dg)
        cap = float(self.config.max_batch)
        rec: Optional[dict[str, Any]] = None
        if p_load >= cap and d_load < cap / 2 and len(dg) > 1:
            rec = {"flip": min(dg), "to_role": "prefill"}
        elif d_load >= cap and p_load < cap / 2 and len(pg) > 1:
            rec = {"flip": min(pg), "to_role": "decode"}
        return {"prefill_load": round(p_load, 2),
                "decode_load": round(d_load, 2),
                "recommendation": rec}

    # ------------------------------------------------------------------ admin
    def stats(self) -> dict[str, Any]:
        out = super().stats()
        out["pd"] = {
            "roles": list(self._roles),
            "prefill_replicas": self._prefill_group(),
            "decode_replicas": self._decode_group(),
            "handoffs": self.handoffs,
            "handoffs_failed": self.handoffs_failed,
            "rebalance": self.rebalance(),
        }
        return out
