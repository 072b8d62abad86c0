"""Replica lifecycle supervision — self-healing serving capacity.

Before this module a broken replica was broken forever: the scheduler loop
crash set ``_broken``, failed every in-flight request, and the serving pool
silently routed around the corpse for the rest of the process lifetime — one
device fault permanently halved a 2-replica pool. The reference's resilience
FRs (llm-gateway DESIGN: provider failover / fallback chains) and RTP-LLM's
production recipe both assume capacity *recovers*; Tangram shows the rebuild
is fast when device-resident weights are reused instead of reloaded.

Two supervisors live here:

- :class:`ReplicaLifecycleManager` — the pool supervisor. A daemon thread
  walks every replica of a :class:`~.replicas.DataParallelServingPool` on a
  short cadence and drives the per-replica state machine::

      healthy ──break──▶ quarantined ──backoff──▶ rebuilding ──ok──▶ probation
         ▲                    ▲                        │                 │
         │                    └──── rebuild failed ────┘                 │
         │                    └──── canary errored ──────────────────────┤
         └──────────────────────── probation successes ──────────────────┘
      healthy ──drain──▶ draining ──idle/deadline──▶ drained ──restart──▶ …
      quarantined ── strikes > max ──▶ benched ──operator restart──▶ …

  Rebuild constructs a fresh ``ContinuousBatchingEngine`` on the SAME device
  reusing the old engine's already-committed ``params`` tree — O(scheduler
  start), not O(weight load). A rebuilt replica re-enters rotation through a
  half-open **probation**: the router sends it at most
  ``probation_max_inflight`` canary requests at a time, and only
  ``probation_successes`` clean terminals promote it back to ``healthy``; a
  canary error (or another loop crash) re-quarantines with exponential,
  jittered backoff. ``max_strikes`` consecutive failures bench the replica —
  a crash-looping device stops burning rebuild cycles until an operator
  ``restart`` clears the strikes.

  **Graceful drain** (rolling restarts): ``drain(i)`` removes the replica
  from routing and lets in-flight requests finish; past the deadline the
  engine is :meth:`~.scheduler.ContinuousBatchingEngine.close`\\ d, which
  error-terminates the stragglers — the pool's failover wrapper resubmits
  each one on a surviving replica carrying its emitted tokens, so client
  streams continue bit-identically (greedy) instead of dying with the
  restart. ``undrain`` returns a still-draining replica to rotation;
  ``restart`` closes + rebuilds from any state (the benched escape hatch).

- :class:`EngineSupervisor` — the single-engine analogue for the worker
  path (one scheduler per model entry, nowhere to canary): rebuild-in-place
  with the same strikes/backoff/bench policy, promotion by the first clean
  stream instead of a canary budget.

Discipline (the doctor/watchdog shape, enforced by fabric-lint WD01 for
``tick``-family callbacks): the supervisor tick never raises out (a hostile
``stats()`` cannot kill the one thread that can heal the pool) and every
emit routes through the never-raises helpers (``record_event`` /
``bump_counter`` / ``record_recovery``). Lifecycle transitions land in the
flight recorder as per-episode records — ``drain_begin`` →
``drain_end`` and single-shot ``replica_rebuilt`` events — so the same
``/v1/monitoring/requests`` surface that explains a request explains a
replica, and ``llm_replica_rebuilds_total{outcome}`` +
``fault_recovery_seconds{point="replicas.rebuild"}`` carry the fleet view.
"""

from __future__ import annotations

import itertools
import logging
import random
import threading
import time
from dataclasses import dataclass, field, fields
from typing import Any, Callable, Optional

from ..modkit.failpoints import failpoint, record_recovery
from ..modkit.flight_recorder import record_event
from ..modkit.metrics import bump_counter

__all__ = [
    "EngineSupervisor", "LifecycleConfig", "LifecycleStateError",
    "ReplicaLifecycleManager", "ReplicaUnavailable",
]

logger = logging.getLogger("lifecycle")

#: the per-replica states (status()/counts() vocabulary, mirrored in the
#: docs/ARCHITECTURE.md state diagram)
STATES = ("healthy", "quarantined", "rebuilding", "probation",
          "draining", "drained", "benched")

#: distinguishes pools in one process so recorder episode ids never collide
_POOL_SEQ = itertools.count(1)


def _rebuild_failpoint() -> None:
    """The ``replicas.rebuild`` failpoint, shared by the pool manager and the
    single-engine supervisor — an armed raise models a rebuild that cannot
    succeed (the device is still sick), driving the backoff/bench track. One
    literal call site keeps FP01's name↔site mapping 1:1."""
    failpoint("replicas.rebuild")


class LifecycleStateError(RuntimeError):
    """A control-plane action illegal from the replica's current state
    (e.g. draining an already-benched replica)."""


class ReplicaUnavailable(RuntimeError):
    """The supervised engine cannot serve right now (rebuild backoff in
    progress, or benched after repeated strikes). ``retry_after_s`` is
    None when only an operator restart can help."""

    def __init__(self, msg: str, retry_after_s: Optional[float] = None):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


@dataclass
class LifecycleConfig:
    """Supervision knobs (worker config: ``engine_options.lifecycle``;
    unknown keys rejected — the deny-unknown-fields convention)."""

    enabled: bool = True
    #: supervisor tick cadence — also bounds how stale a break can go
    #: unnoticed (the scheduler loop crash is detected by polling stats())
    check_interval_s: float = 0.2
    #: exponential backoff before rebuild attempt N: base · 2^(N-1), capped,
    #: with ±jitter so a fleet of breaking replicas never thunders in step
    rebuild_backoff_s: float = 0.5
    rebuild_backoff_max_s: float = 30.0
    backoff_jitter: float = 0.25
    #: consecutive failures (break / failed rebuild / canary error) before
    #: the replica is benched — a crash loop must not burn rebuilds forever
    max_strikes: int = 3
    #: half-open probation: clean terminals required to promote, and the
    #: canary admission bound while on probation
    probation_successes: int = 2
    probation_max_inflight: int = 1
    #: default drain deadline: in-flight requests past it are closed out and
    #: failed over to surviving replicas
    drain_deadline_s: float = 30.0
    #: jitter rng seed (deterministic chaos scenarios)
    seed: int = 0

    @classmethod
    def from_config(cls, raw: Any) -> "LifecycleConfig":
        if isinstance(raw, LifecycleConfig):
            return raw
        if raw is True or raw is None:
            return cls()
        if raw is False:
            return cls(enabled=False)
        if isinstance(raw, str):
            # registry options can arrive as strings — bool("false") is
            # True, so parse the words
            return cls(enabled=raw.strip().lower()
                       not in ("0", "false", "no", "off"))
        raw = dict(raw)
        known = {f.name for f in fields(cls)}
        unknown = set(raw) - known
        if unknown:
            raise ValueError(
                f"lifecycle: unknown fields {sorted(unknown)} "
                f"(allowed: {sorted(known)})")
        return cls(**raw)


@dataclass
class _ReplicaRecord:
    state: str = "healthy"
    strikes: int = 0
    backoff_until: float = 0.0
    last_error: str = ""
    rebuilds: int = 0
    probation_ok: int = 0
    probation_inflight: int = 0
    drain_deadline: float = 0.0
    drain_episode: int = 0
    rebuild_episode: int = 0
    #: set while a drain episode's recorder record is open
    drain_eid: Optional[str] = None
    history: list = field(default_factory=list)  # bounded (state, ts) walk

    def walk(self, state: str) -> None:
        self.state = state
        self.history.append((state, round(time.time(), 3)))
        del self.history[:-32]


class _BackoffPolicy:
    """Shared strikes/backoff math (pool manager + single-engine
    supervisor). Mutations happen under the owner's lock."""

    def __init__(self, cfg: LifecycleConfig) -> None:
        self.cfg = cfg
        self._rng = random.Random(cfg.seed)

    def backoff(self, strikes: int) -> float:
        base = min(self.cfg.rebuild_backoff_s * (2.0 ** max(0, strikes - 1)),
                   self.cfg.rebuild_backoff_max_s)
        j = self.cfg.backoff_jitter
        return base * (1.0 + j * (2.0 * self._rng.random() - 1.0))


class ReplicaLifecycleManager:
    """Supervises one :class:`~.replicas.DataParallelServingPool`.

    The pool is the only collaborator: ``pool.replicas`` (the engine list —
    item assignment is the rebuild commit), ``pool.build_replica(idx)``
    (fresh engine on the same device reusing the committed params). The
    routing hooks (:meth:`admit_allowed` / :meth:`note_dispatch` /
    :meth:`on_terminal` / :meth:`on_departed`) are called from the pool's
    submit/emit paths and stay O(1) under the lock; engine operations
    (close / build / start) always run OUTSIDE the lock so a multi-second
    rebuild can never block a scheduler thread's terminal notification."""

    def __init__(self, pool: Any,
                 config: Optional[LifecycleConfig] = None,
                 name: Optional[str] = None) -> None:
        self.pool = pool
        self.config = config or LifecycleConfig()
        self.name = name or f"pool{next(_POOL_SEQ)}"
        self._lock = threading.Lock()
        self._backoff = _BackoffPolicy(self.config)
        self._recs = [_ReplicaRecord() for _ in pool.replicas]
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # fleet counters (status() + /v1/monitoring/replicas)
        self.rebuilds_ok = 0
        self.rebuilds_failed = 0
        self.benched_total = 0
        self.drains_clean = 0
        self.drains_killed = 0
        self.probation_promotions = 0

    # ---------------------------------------------------------------- thread
    def start(self) -> None:
        if not self.config.enabled:
            return
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name=f"lifecycle-{self.name}", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(self.config.check_interval_s * 10 + 1.0)

    def _loop(self) -> None:
        while not self._stop.wait(self.config.check_interval_s):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 — the healer must not die
                logger.exception("lifecycle tick failed")

    # ------------------------------------------------------- routing surface
    def admit_allowed(self, idx: int) -> bool:
        """May the router place a NEW request on this replica? Healthy:
        always. Probation: within the canary budget. Everything else
        (quarantined / rebuilding / draining / drained / benched): no."""
        rec = self._recs[idx]
        if rec.state == "healthy":
            return True
        if rec.state == "probation":
            return rec.probation_inflight < self.config.probation_max_inflight
        return False

    def canary_wanted(self, idx: int) -> bool:
        """True when this replica is on probation WITH canary budget left —
        the router breaks load ties toward it so an idle probation replica
        actually receives the canaries it needs to be promoted."""
        rec = self._recs[idx]
        return (rec.state == "probation"
                and rec.probation_inflight < self.config.probation_max_inflight)

    def note_dispatch(self, idx: int) -> None:
        """A request was routed to replica ``idx`` (submit or failover)."""
        with self._lock:
            rec = self._recs[idx]
            if rec.state == "probation":
                rec.probation_inflight += 1

    def on_departed(self, idx: int) -> None:
        """A request LEFT replica ``idx`` without a client terminal (failed
        over elsewhere) — release its canary slot; the break itself is
        judged by the supervisor off ``stats()['broken']``."""
        with self._lock:
            rec = self._recs[idx]
            if rec.state == "probation":
                rec.probation_inflight = max(0, rec.probation_inflight - 1)

    def on_terminal(self, idx: int, ok: bool) -> None:
        """A request served by replica ``idx`` reached its client terminal.
        Probation canaries count toward promotion; a canary error
        re-quarantines immediately (no need to wait for the tick).
        ``cancelled``/``deadline`` terminals arrive with ``ok=True`` (the
        pool maps only ``error`` to False): a cancel is a client decision,
        not a replica fault — a disconnect storm must not strike a healthy
        canary, and a drain counts cancels as completions (the cancelled
        slot frees, so the drain's idle probe sees the replica empty)."""
        with self._lock:
            rec = self._recs[idx]
            if rec.state != "probation":
                return
            rec.probation_inflight = max(0, rec.probation_inflight - 1)
            if ok:
                rec.probation_ok += 1
                if rec.probation_ok >= self.config.probation_successes:
                    rec.walk("healthy")
                    rec.strikes = 0
                    rec.last_error = ""
                    self.probation_promotions += 1
                    logger.info("lifecycle %s: replica %d promoted to "
                                "healthy after %d clean canaries",
                                self.name, idx, rec.probation_ok)
            else:
                self._quarantine_locked(idx, rec, "probation canary errored")

    # ----------------------------------------------------------- supervision
    def tick(self, now: Optional[float] = None) -> None:
        """One supervision pass (the thread's body; tests/scenarios call it
        synchronously). Engine probes run BEFORE the lock and engine close /
        build / start AFTER it — the lock protects only the state-machine
        decisions, so the hot-path hooks (note_dispatch / on_terminal on
        submit and scheduler-emit threads) can never block behind a slow or
        hostile stats()."""
        if not self.config.enabled:
            return
        now = time.monotonic() if now is None else now
        snaps = [(idx, *self._probe(eng))
                 for idx, eng in enumerate(list(self.pool.replicas))]
        actions: list[tuple[str, int]] = []
        with self._lock:
            for idx, broken, idle in snaps:
                if idx >= len(self._recs):
                    continue
                rec = self._recs[idx]
                if rec.state in ("healthy", "probation") and broken:
                    self._quarantine_locked(idx, rec, broken)
                elif rec.state == "quarantined" and now >= rec.backoff_until:
                    rec.walk("rebuilding")
                    actions.append(("rebuild", idx))
                elif rec.state == "draining":
                    if broken:
                        # the drain target crashed under us: the loop-crash
                        # path already failed its streams over; the episode
                        # ends here and the replica follows the normal
                        # quarantine → rebuild track
                        self._end_drain_locked(idx, rec, "broke")
                        self._quarantine_locked(idx, rec, broken)
                    elif idle:
                        actions.append(("drain_close", idx))
                    elif now >= rec.drain_deadline:
                        actions.append(("drain_kill", idx))
        for kind, idx in actions:
            if kind == "rebuild":
                self._do_rebuild(idx)
            else:
                self._do_drain_close(idx, killed=kind == "drain_kill")

    @staticmethod
    def _probe(eng: Any) -> tuple[Optional[str], bool]:
        """(broken_reason, idle) off one stats() read. An engine that is
        CLOSED while the lifecycle record says it should be serving reads as
        broken — that is how the supervisor heals an undrain that raced the
        drain tick's close (the replica would otherwise sit lifecycle-
        healthy but unroutable forever); genuinely drained replicas never
        reach the healthy/probation arms that act on this."""
        try:
            st = eng.stats()
        except Exception as e:  # noqa: BLE001 — a dying engine IS broken
            return f"stats() failed: {type(e).__name__}", False
        broken = st.get("broken") or (
            "engine closed" if st.get("closed") else None)
        idle = not (st.get("active") or st.get("pending")
                    or st.get("prefilling") or st.get("suspended"))
        return broken, idle

    def _quarantine_locked(self, idx: int, rec: _ReplicaRecord,
                           why: Any) -> None:
        """Under lock: strike the replica; quarantine with exponential
        jittered backoff, or bench it past ``max_strikes``."""
        rec.strikes += 1
        rec.last_error = str(why)[:200]
        rec.probation_ok = 0
        rec.probation_inflight = 0
        if rec.strikes > self.config.max_strikes:
            rec.walk("benched")
            self.benched_total += 1
            logger.error(
                "lifecycle %s: replica %d BENCHED after %d strikes (%s) — "
                "operator restart required", self.name, idx, rec.strikes,
                rec.last_error)
            return
        backoff = self._backoff.backoff(rec.strikes)
        rec.backoff_until = time.monotonic() + backoff
        rec.walk("quarantined")
        logger.warning(
            "lifecycle %s: replica %d quarantined (strike %d/%d, rebuild in "
            "%.2fs): %s", self.name, idx, rec.strikes, self.config.max_strikes,
            backoff, rec.last_error)

    def _eid(self, idx: int, kind: str, episode: int) -> str:
        return f"{self.name}/replica{idx}/{kind}-{episode}"

    def _do_rebuild(self, idx: int) -> bool:
        """Close the spent engine, build + start a fresh one on the same
        device (reusing the committed params copy), and commit it into the
        pool. Runs on the supervisor thread (or a control-plane caller),
        never under the manager lock."""
        with self._lock:
            rec = self._recs[idx]
            rec.rebuild_episode += 1
            eid = self._eid(idx, "rebuild", rec.rebuild_episode)
        old = self.pool.replicas[idx]
        try:
            # a wedged/broken engine's close is cheap: the loop-crash path
            # already failed its streams; close only marks it spent
            old.close(timeout=5.0)
        except Exception:  # noqa: BLE001 — never let the corpse block rebuild
            logger.exception("lifecycle %s: closing replica %d failed",
                             self.name, idx)
        t0 = time.monotonic()
        try:
            _rebuild_failpoint()
            eng = self.pool.build_replica(idx)
            eng.start()
        except Exception as e:  # noqa: BLE001
            self.rebuilds_failed += 1
            bump_counter("llm_replica_rebuilds_total", outcome="failed")
            record_event(eid, "replica_rebuilt", replica=idx,
                         outcome="failed", error=str(e)[:200])
            with self._lock:
                self._quarantine_locked(idx, self._recs[idx],
                                        f"rebuild failed: {e}")
            return False
        dt = time.monotonic() - t0
        self.pool.replicas[idx] = eng
        with self._lock:
            rec = self._recs[idx]
            rec.rebuilds += 1
            rec.probation_ok = 0
            rec.probation_inflight = 0
            rec.walk("probation")
        self.rebuilds_ok += 1
        record_recovery("replicas.rebuild", dt)
        bump_counter("llm_replica_rebuilds_total", outcome="ok")
        record_event(eid, "replica_rebuilt", replica=idx, outcome="ok",
                     rebuild_ms=round(dt * 1000.0, 3))
        logger.info("lifecycle %s: replica %d rebuilt in %.2fs; on probation "
                    "(%d clean canaries to promote)", self.name, idx, dt,
                    self.config.probation_successes)
        return True

    def _do_drain_close(self, idx: int, killed: bool) -> None:
        eng = self.pool.replicas[idx]
        inflight = 0
        if killed:
            try:
                st = eng.stats()
                inflight = int(st.get("active", 0)) + int(st.get("pending", 0)) \
                    + int(st.get("prefilling", 0)) + int(st.get("suspended", 0))
            except Exception:  # noqa: BLE001
                pass
        try:
            # close() error-terminates stragglers; the pool's failover
            # wrapper resubmits each on a surviving replica carrying its
            # emitted tokens — the "preempt past the deadline" leg
            eng.close(timeout=5.0)
        except Exception:  # noqa: BLE001
            logger.exception("lifecycle %s: drain close of replica %d failed",
                             self.name, idx)
        with self._lock:
            rec = self._recs[idx]
            if rec.state != "draining":
                return  # an undrain/restart raced the tick; it owns the state
            self._end_drain_locked(
                idx, rec, "killed" if killed else "clean",
                failed_over=inflight)
            rec.walk("drained")
        if killed:
            self.drains_killed += 1
        else:
            self.drains_clean += 1

    def _end_drain_locked(self, idx: int, rec: _ReplicaRecord, outcome: str,
                          **attrs: Any) -> None:
        if rec.drain_eid is not None:
            record_event(rec.drain_eid, "drain_end", replica=idx,
                         outcome=outcome, **attrs)
            rec.drain_eid = None

    # ---------------------------------------------------------- control plane
    def _check_idx(self, idx: int) -> None:
        if not 0 <= idx < len(self._recs):
            raise IndexError(f"replica index {idx} out of range "
                             f"(pool has {len(self._recs)})")

    def drain(self, idx: int,
              deadline_s: Optional[float] = None) -> dict[str, Any]:
        """Remove replica ``idx`` from routing and let in-flight requests
        finish; past ``deadline_s`` the supervisor closes the engine and the
        stragglers fail over. Allowed from healthy/probation. Cancelled and
        deadline-lapsed requests count as completions here: each one frees
        its slot, so the drain's idle probe (and the clean-drain outcome)
        treats them exactly like finished streams."""
        self._check_idx(idx)
        deadline = (self.config.drain_deadline_s
                    if deadline_s is None else max(0.0, float(deadline_s)))
        with self._lock:
            rec = self._recs[idx]
            if rec.state not in ("healthy", "probation"):
                raise LifecycleStateError(
                    f"cannot drain replica {idx} from state {rec.state!r}")
            rec.drain_episode += 1
            rec.drain_eid = self._eid(idx, "drain", rec.drain_episode)
            rec.drain_deadline = time.monotonic() + deadline
            # recorded UNDER the lock: the supervisor tick must not be able
            # to close the episode (drain_end) before its begin exists — a
            # begin landing on an already-closed id would ghost a permanent
            # "draining" row in the live table
            record_event(rec.drain_eid, "drain_begin", replica=idx,
                         deadline_s=deadline)
            rec.walk("draining")
        logger.info("lifecycle %s: draining replica %d (deadline %.1fs)",
                    self.name, idx, deadline)
        return self.status_row(idx)

    def undrain(self, idx: int) -> dict[str, Any]:
        """Return a STILL-DRAINING replica to rotation (its engine never
        stopped serving in-flight work). A completed drain is past the point
        of no return — use :meth:`restart`."""
        self._check_idx(idx)
        with self._lock:
            rec = self._recs[idx]
            if rec.state != "draining":
                raise LifecycleStateError(
                    f"cannot undrain replica {idx} from state {rec.state!r} "
                    "(only 'draining'; a drained replica needs restart)")
            self._end_drain_locked(idx, rec, "undrained")
            rec.walk("healthy")
        logger.info("lifecycle %s: replica %d undrained", self.name, idx)
        return self.status_row(idx)

    def restart(self, idx: int) -> dict[str, Any]:
        """Operator restart: clear strikes/backoff and hand the replica to
        the supervisor for an immediate close + rebuild. Works from any
        state (the benched escape hatch; from healthy it is drain-with-
        deadline-zero semantics — in-flight requests fail over). Returns
        immediately; the rebuild runs on the supervisor thread."""
        self._check_idx(idx)
        with self._lock:
            rec = self._recs[idx]
            if rec.state == "rebuilding":
                raise LifecycleStateError(
                    f"replica {idx} is already rebuilding")
            if rec.state == "draining":
                self._end_drain_locked(idx, rec, "restarted")
            rec.strikes = 0
            rec.backoff_until = 0.0
            rec.probation_ok = 0
            rec.probation_inflight = 0
            rec.walk("quarantined")  # the supervisor rebuilds next tick
        logger.info("lifecycle %s: replica %d restart requested",
                    self.name, idx)
        return self.status_row(idx)

    # --------------------------------------------------------------- surface
    def counts(self) -> dict[str, Any]:
        """State census — the doctor's capacity feed. ``serving`` is what
        the router can actually use (healthy + probation-with-budget)."""
        with self._lock:
            by_state = {s: 0 for s in STATES}
            serving = 0
            for idx, rec in enumerate(self._recs):
                by_state[rec.state] += 1
                if rec.state == "healthy" or (
                        rec.state == "probation"
                        and rec.probation_inflight
                        < self.config.probation_max_inflight):
                    serving += 1
            return {"replicas": len(self._recs), "serving": serving,
                    **by_state}

    def status_row(self, idx: int) -> dict[str, Any]:
        with self._lock:
            rec = self._recs[idx]
            now = time.monotonic()
            return {
                "index": idx,
                "state": rec.state,
                "strikes": rec.strikes,
                "backoff_remaining_s": round(
                    max(0.0, rec.backoff_until - now), 3)
                if rec.state == "quarantined" else None,
                "rebuilds": rec.rebuilds,
                "probation_ok": rec.probation_ok,
                "probation_inflight": rec.probation_inflight,
                "last_error": rec.last_error or None,
                "history": [{"state": s, "ts": ts}
                            for s, ts in rec.history[-8:]],
            }

    def status(self) -> dict[str, Any]:
        rows = [self.status_row(i) for i in range(len(self._recs))]
        return {
            "name": self.name,
            "counts": self.counts(),
            "rebuilds_ok": self.rebuilds_ok,
            "rebuilds_failed": self.rebuilds_failed,
            "benched_total": self.benched_total,
            "drains_clean": self.drains_clean,
            "drains_killed": self.drains_killed,
            "probation_promotions": self.probation_promotions,
            "replicas": rows,
        }


class EngineSupervisor:
    """Single-engine self-healing (the worker's one-scheduler-per-model
    path): when the engine breaks, rebuild it in place under the shared
    strikes/backoff/bench policy. There is no pool to canary against, so
    "probation" degenerates to: the first clean stream (:meth:`note_ok`)
    clears the strikes. All methods are thread-safe; :meth:`ensure` blocks
    on the rebuild (callers run it off the event loop)."""

    def __init__(self, build: Callable[[Any], Any],
                 config: Optional[LifecycleConfig] = None,
                 name: str = "engine") -> None:
        self._build = build
        self.config = config or LifecycleConfig()
        self.name = name
        self._lock = threading.Lock()
        self._policy = _BackoffPolicy(self.config)
        self._rebuilding = False
        self.strikes = 0
        self.benched = False
        self.backoff_until = 0.0
        self.rebuilds_ok = 0
        self.rebuilds_failed = 0
        self.last_error = ""

    def ensure(self, engine: Any) -> Any:
        """Return a servable engine: ``engine`` itself when healthy, or a
        fresh rebuild. Raises :class:`ReplicaUnavailable` while benched or
        inside the rebuild backoff window."""
        broken = None
        try:
            st = engine.stats()
            broken = st.get("broken")
            closed = st.get("closed")
        except Exception as e:  # noqa: BLE001
            broken, closed = f"stats() failed: {type(e).__name__}", False
        if not broken and not closed:
            return engine
        if not self.config.enabled:
            raise ReplicaUnavailable(
                f"engine {self.name} is broken and supervision is disabled: "
                f"{broken}")
        now = time.monotonic()
        with self._lock:
            if self.benched:
                raise ReplicaUnavailable(
                    f"engine {self.name} is benched after {self.strikes} "
                    "strikes; operator restart required")
            if self._rebuilding:
                # an in-progress flag, not just the time window: a rebuild
                # slower than rebuild_backoff_s must not let later callers
                # stack duplicate compiles (leaking the superseded engines)
                # or spuriously strike a recovering engine toward the bench
                raise ReplicaUnavailable(
                    f"engine {self.name} rebuild already in progress",
                    retry_after_s=1.0)
            if now < self.backoff_until:
                raise ReplicaUnavailable(
                    f"engine {self.name} rebuild backing off "
                    f"({self.backoff_until - now:.2f}s left): "
                    f"{self.last_error}",
                    retry_after_s=round(self.backoff_until - now, 2) + 0.01)
            # claim the rebuild slot before releasing the lock: concurrent
            # callers back off instead of stacking N compiles
            self.strikes += 1
            strikes = self.strikes
            self.last_error = str(broken)[:200]
            self.backoff_until = now + self._policy.backoff(strikes)
            if strikes > self.config.max_strikes:
                # benched at CLAIM time, not only on rebuild failure: an
                # engine that rebuilds fine but crashes on first use (and
                # never reaches note_ok) must not hot-loop a full program
                # build per request forever
                self.benched = True
                raise ReplicaUnavailable(
                    f"engine {self.name} benched after {strikes} strikes "
                    f"(crash loop: {self.last_error}); operator restart "
                    "required")
            self._rebuilding = True
        try:
            engine.close(timeout=5.0)
        except Exception:  # noqa: BLE001
            logger.exception("supervisor %s: close failed", self.name)
        t0 = time.monotonic()
        try:
            _rebuild_failpoint()
            fresh = self._build(engine)
            fresh.start()
        except Exception as e:  # noqa: BLE001
            # strikes ≤ max_strikes here (the claim benches past it), so the
            # caller always gets a retry window, and the NEXT claim benches
            with self._lock:
                self._rebuilding = False
                self.rebuilds_failed += 1
                self.last_error = str(e)[:200]
            bump_counter("llm_replica_rebuilds_total", outcome="failed")
            record_event(f"{self.name}/rebuild-{self.rebuilds_failed}",
                         "replica_rebuilt", outcome="failed",
                         error=str(e)[:200])
            raise ReplicaUnavailable(
                f"engine {self.name} rebuild failed: {e}",
                retry_after_s=round(
                    max(0.0, self.backoff_until - time.monotonic()), 2))
        dt = time.monotonic() - t0
        with self._lock:
            self._rebuilding = False
            self.rebuilds_ok += 1
            n = self.rebuilds_ok
            # backoff_until deliberately stays: a crash-on-first-use engine
            # re-enters ensure() immediately, and the strike's backoff
            # window is what paces its next rebuild (note_ok never comes)
        record_recovery("replicas.rebuild", dt)
        bump_counter("llm_replica_rebuilds_total", outcome="ok")
        record_event(f"{self.name}/rebuild-ok-{n}", "replica_rebuilt",
                     outcome="ok", rebuild_ms=round(dt * 1000.0, 3))
        logger.info("supervisor %s: engine rebuilt in %.2fs", self.name, dt)
        return fresh

    def note_ok(self) -> None:
        """A stream served by the (possibly rebuilt) engine finished
        cleanly — the single-engine probation pass."""
        with self._lock:
            self.strikes = 0
            self.last_error = ""

    def reset(self) -> None:
        """Operator un-bench."""
        with self._lock:
            self.benched = False
            self.strikes = 0
            self.backoff_until = 0.0
            self._rebuilding = False

    def status(self) -> dict[str, Any]:
        with self._lock:
            return {
                "name": self.name,
                "strikes": self.strikes,
                "benched": self.benched,
                "backoff_remaining_s": round(
                    max(0.0, self.backoff_until - time.monotonic()), 3),
                "rebuilds_ok": self.rebuilds_ok,
                "rebuilds_failed": self.rebuilds_failed,
                "last_error": self.last_error or None,
            }
