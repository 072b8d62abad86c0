"""Monitoring module — the /metrics endpoint (Prometheus text format).

Reference: the Monitoring module exists only as a spec there
(docs/MODULES.md:475-491); here it is real, per SURVEY §5's mandate: serving
metrics (request counts/latency), LLM metrics (tokens, TTFT histograms, batch
occupancy), and device metrics (TPU count, HBM when the PJRT plugin reports it).
"""

from __future__ import annotations

import time

from aiohttp import web

from ..modkit import Module, module
from ..modkit.concurrency import locked_snapshot
from ..modkit.contracts import RestApiCapability, RunnableCapability
from ..modkit.context import ModuleCtx
from ..modkit.lifecycle import ReadySignal
from ..modkit.metrics import MetricsRegistry, default_registry
from ..modkit.telemetry import startup
from ..gateway.validation import read_json
from .sdk import LlmWorkerApi


#: the four stages of a round record that predates ``phases``
_ROUND_STAGES = ("admit", "dispatch", "sync_wait", "host_emit")


def _chrome_trace(per_model: dict[str, list[dict]]) -> dict:
    """Scheduler round records → Chrome trace-event JSON (the format Perfetto
    and chrome://tracing load directly). One process per engine, one thread
    track per phase of the scheduler's loop, "X" complete events in µs. A
    record draws its ``phases`` (the scheduler thread's time since the
    previous record, ``runtime/scheduler.py: _PhaseClock``) in their
    recorded order and lengths, back to back over its ``pass_ms``, which
    ends where the round's last stage does; a record without them draws its
    four stages from the round's start."""
    events: list[dict] = []
    for pid, name in enumerate(sorted(per_model), start=1):
        events.append({"ph": "M", "pid": pid, "name": "process_name",
                       "args": {"name": f"scheduler {name}"}})
        tids: dict[str, int] = {}

        def draw(track: str, start_us: float, dur_ms: float, r: dict,
                 **args) -> None:
            if track not in tids:
                tids[track] = len(tids) + 1
                events.append({"ph": "M", "pid": pid, "tid": tids[track],
                               "name": "thread_name",
                               "args": {"name": track}})
            events.append({
                "name": track, "ph": "X", "pid": pid, "tid": tids[track],
                "ts": round(start_us, 1),
                "dur": round(max(0.0, dur_ms) * 1000.0, 1),
                "args": {"lookahead": bool(r.get("lookahead")),
                         "active_slots": r.get("active"), **args}})

        for r in per_model[name]:
            ts = r.get("ts")
            if ts is None:  # entry predating the wall-clock column
                continue
            at_us = ts * 1e6
            if "phases" in r:
                at_us += (r["dispatch_ms"] + r["sync_wait_ms"]
                          + r["host_emit_ms"] - r["pass_ms"]) * 1000.0
                for phase, (wall, cpu, starved) in r["phases"].items():
                    draw(phase, at_us, wall, r, cpu_ms=cpu,
                         starved_ms=starved)
                    at_us += wall * 1000.0
                continue
            # admission ran just before the round's start; the other three
            # stages follow it in order
            at_us -= r["admit_ms"] * 1000.0
            for stage in _ROUND_STAGES:
                draw(stage, at_us, r[f"{stage}_ms"], r)
                at_us += r[f"{stage}_ms"] * 1000.0
    return {"traceEvents": events, "displayTimeUnit": "ms"}


@module(name="monitoring", capabilities=["rest", "stateful"])
class MonitoringModule(Module, RestApiCapability, RunnableCapability):
    def __init__(self) -> None:
        self.registry = default_registry
        self._profile_dir = None
        # True when a stop_trace raised after we cleared _profile_dir: JAX's
        # global tracer may still be active even though our state says stopped
        self._tracer_maybe_live = False

    async def init(self, ctx: ModuleCtx) -> None:
        ctx.client_hub.register(MetricsRegistry, self.registry)
        hub = ctx.client_hub
        #: fault-injection arming over REST is opt-in per deployment — a soak
        #: rehearsal flips `monitoring: {allow_fault_injection: true}`;
        #: production configs leave it off and the arming endpoints 403
        self._allow_fault_injection = bool(
            ctx.raw_config().get("allow_fault_injection", False))

        # fabric-doctor: configure the process-global health evaluator from
        # `monitoring.doctor` (objectives/windows/watchdog knobs), point its
        # watchdogs at the live scheduler pool, and start the evaluation
        # thread. configure() resets the state machine — every boot starts
        # healthy.
        from ..modkit.doctor import DoctorConfig, default_doctor
        from .sdk import DoctorApi

        doctor_cfg = DoctorConfig.from_config(
            ctx.raw_config().get("doctor", {}))
        default_doctor.configure(doctor_cfg)
        # hub-registered under the SDK contract so the llm-gateway admission
        # layer sheds only in stacks that actually run the evaluator
        # (contract-typed resolution, the MetricsRegistry pattern)
        ctx.client_hub.register(DoctorApi, default_doctor)

        def _doctor_schedulers():
            worker = hub.try_get(LlmWorkerApi)
            return worker.schedulers() if worker is not None else []

        default_doctor.set_scheduler_provider(_doctor_schedulers)

        def _doctor_capacity():
            # replica lifecycle census: the doctor scales its shedding
            # hysteresis with surviving capacity, and zero serving replicas
            # is a degradation reason in itself
            worker = hub.try_get(LlmWorkerApi)
            return worker.replica_capacity() if worker is not None else {}

        default_doctor.set_capacity_provider(_doctor_capacity)
        self.doctor = default_doctor

        # pre-register the doctor metric families so dashboards can alert
        # on them from the first scrape
        self.registry.counter(
            "watchdog_trips_total",
            "Stall-watchdog trips (scheduler_round/stream_stall/queue_age)"
        ).inc(0.0)
        self.registry.gauge(
            "slo_burn_rate",
            "SLO error-budget burn rate per objective and window")
        self.registry.gauge(
            "serving_state",
            "Degradation state (0 healthy, 1 degraded, 2 shedding, "
            "3 recovering)").set(0.0)
        self.registry.gauge("llm_queue_depth",
                            "Pending scheduler queue depth")
        self.registry.gauge("llm_queue_oldest_age_seconds",
                            "Age of the oldest pending request")

        # pre-register the faultlab metric families so they render (at zero)
        # before the first injection/failover — dashboards can alert on them
        # from the first scrape
        self.registry.counter(
            "fault_injected_total",
            "Faults injected via armed failpoints").inc(0.0)
        self.registry.histogram(
            "fault_recovery_seconds",
            "Recovery-path latency (preempt/resume, failover) in seconds")
        self.registry.counter(
            "llm_replica_failovers_total",
            "Mid-stream requests resubmitted to another replica").inc(0.0)
        self.registry.counter(
            "llm_cache_aware_placements_total",
            "Requests routed by the prefix-cache affinity hint").inc(0.0)
        self.registry.counter(
            "llm_pd_handoffs_total",
            "Streams handed prefill→decode across PD role groups").inc(0.0)

        # end-to-end cancellation: terminals by reason, the decode budget
        # reclaimed from dead clients, and the doctor's cancellation-rate
        # gauge — pre-registered so dashboards can alert from first scrape
        self.registry.counter(
            "llm_cancellations_total",
            "Requests cancelled end-to-end, by reason "
            "(client_disconnect/deadline/…)").inc(0.0)
        self.registry.counter(
            "llm_cancel_reclaimed_tokens_total",
            "max_tokens budget NOT generated thanks to cancellation "
            "(reclaimed decode capacity)").inc(0.0)
        self.registry.counter(
            "llm_client_disconnects_total",
            "SSE consumers that vanished mid-response (socket-level "
            "disconnects at the gateway writer; gateway-timeout aborts "
            "count only under llm_cancellations_total)").inc(0.0)
        self.registry.gauge(
            "llm_cancellation_rate",
            "Fraction of recent terminals that were cancelled/deadline-"
            "lapsed (fast window)").set(0.0)

        # replica lifecycle (self-healing pools): rebuild outcomes and the
        # healthy/benched census — pre-registered so dashboards can alert
        # from the first scrape; values are pushed by the lifecycle manager
        # (counter) and the doctor's evaluation pass (gauges)
        # tenant isolation: rejection/budget counters and the fairness
        # gauges (token share, per-tenant queue depth, selective-shed flag)
        # — pre-registered so dashboards can alert from the first scrape;
        # values are pushed by the scheduler (counters) and the doctor's
        # evaluation pass (gauges)
        self.registry.counter(
            "llm_tenant_rejections_total",
            "Per-tenant scheduler rejections by reason "
            "(pending/quota)").inc(0.0)
        self.registry.counter(
            "llm_tenant_budget_rejections_total",
            "Requests rejected at the gateway because the tenant's token "
            "budget is exhausted").inc(0.0)
        self.registry.counter(
            "llm_tenant_soft_yields_total",
            "Slots preempted to host by the tenant soft page-quota sweep "
            "under contention").inc(0.0)
        self.registry.gauge(
            "llm_tenant_queue_depth",
            "Pending scheduler queue depth per tenant")
        self.registry.gauge(
            "llm_tenant_token_share",
            "Tenant share of recently consumed tokens (0..1)")
        self.registry.gauge(
            "llm_tenant_shed",
            "1 while this tenant is selectively shed (over fair share "
            "during SLO burn)")
        # cross-host federation: worker-plane lease counters, placement
        # reasons, failovers, and the healthy-host gauge — pre-registered so
        # dashboards can alert from the first scrape; the gauge reads the
        # hub-registered WorkerRegistry at scrape time (non-federated stacks
        # simply scrape 0)
        from .sdk import WorkerRegistryApi

        self.registry.counter(
            "llm_remote_worker_announcements_total",
            "Worker processes announced to the federation registry").inc(0.0)
        self.registry.counter(
            "llm_remote_worker_heartbeats_total",
            "Worker lease renewals (heartbeats with gossip census)").inc(0.0)
        self.registry.counter(
            "llm_remote_worker_evictions_total",
            "Worker hosts evicted by reason "
            "(lease_expired/crash/withdrawn)").inc(0.0)
        self.registry.counter(
            "llm_federated_placements_total",
            "Federated host placements by routing reason "
            "(prefix/health/load/random)").inc(0.0)
        self.registry.counter(
            "llm_federated_failovers_total",
            "Mid-stream requests re-prefilled on a surviving host").inc(0.0)

        def remote_workers_healthy() -> float:
            reg = hub.try_get(WorkerRegistryApi)
            return float(reg.healthy()) if reg is not None else 0.0

        self.registry.gauge(
            "llm_remote_workers_healthy",
            "Worker hosts holding a live federation lease"
        ).set_function(remote_workers_healthy)

        self.registry.counter(
            "llm_replica_rebuilds_total",
            "Replica rebuilds by outcome (ok/failed)").inc(0.0)
        self.registry.gauge(
            "llm_replicas_healthy",
            "Replicas in lifecycle state healthy").set(0.0)
        self.registry.gauge(
            "llm_replicas_benched",
            "Replicas benched after repeated strikes").set(0.0)

        # device gauges, evaluated at scrape time
        def device_count() -> float:
            import jax

            return float(len(jax.devices()))

        self.registry.gauge(
            "tpu_devices", "Accelerator devices visible to this host"
        ).set_function(device_count)

        def hbm_in_use() -> float:
            import jax

            stats = jax.devices()[0].memory_stats() or {}
            return float(stats.get("bytes_in_use", 0))

        self.registry.gauge(
            "tpu_hbm_bytes_in_use", "HBM in use on device 0 (0 if unreported)"
        ).set_function(hbm_in_use)

        # tensor-parallel serving (docs/ARCHITECTURE.md "Tensor-parallel
        # serving"): the mesh width actually serving, and per-device HBM
        # utilization. Utilization prefers LIVE device stats (bytes_in_use /
        # bytes_limit per device); backends that report nothing (CPU, some
        # PJRT plugins) fall back to the engines' feasibility-plan figure —
        # the same per-device byte budget the build-time gate enforced.
        def mesh_devices() -> float:
            width = 1 if any(True for _ in _schedulers()) else 0
            for sched in _schedulers():
                info = _mesh_info(sched)
                width = max(width, int(info.get("devices", 1)))
            return float(width)

        def _mesh_info(sched) -> dict:
            fn = getattr(sched, "mesh_info", None)
            if fn is None:
                return {}
            try:
                return fn() or {}
            except Exception:  # noqa: BLE001 — scrape must not die on a dying engine
                return {}

        self.registry.gauge(
            "llm_mesh_devices",
            "Devices in the widest serving mesh (tp degree; 1 = unsharded)"
        ).set_function(mesh_devices)

        def hbm_utilization_per_device() -> float:
            import jax

            worst = 0.0
            try:
                for dev in jax.devices():
                    stats = dev.memory_stats() or {}
                    limit = float(stats.get("bytes_limit", 0) or 0)
                    if limit > 0:
                        worst = max(worst,
                                    float(stats.get("bytes_in_use", 0))
                                    / limit)
            except Exception:  # noqa: BLE001
                pass
            if worst > 0.0:
                return worst
            for sched in _schedulers():
                plan = _mesh_info(sched).get("plan") or {}
                # only ENFORCED plans report: an unenforced plan's fraction
                # is computed against the default v5e budget — fictional
                # hardware on CPU/forced-host backends, and a 400% reading
                # there would fire HBM alerts over nothing
                if plan.get("enforced"):
                    worst = max(worst,
                                float(plan.get("hbm_utilization", 0.0)))
            return worst

        self.registry.gauge(
            "llm_hbm_utilization_per_device",
            "Worst per-device HBM utilization (live device stats, or the "
            "feasibility plan's budgeted fraction when unreported)"
        ).set_function(hbm_utilization_per_device)

        def active_slots() -> float:
            worker = hub.try_get(LlmWorkerApi)
            pairs = worker.schedulers() if worker is not None else []
            return float(sum(s.active_slots for _, s in pairs))

        self.registry.gauge(
            "llm_batch_active_slots", "Active continuous-batching slots"
        ).set_function(active_slots)

        def _schedulers():
            worker = hub.try_get(LlmWorkerApi)
            for _name, sched in (worker.schedulers()
                                 if worker is not None else []):
                yield sched

        # scheduler pipeline health (the overlapped-decode tentpole): fraction
        # of decode rounds served by a pre-dispatched lookahead chunk, and how
        # long admitted requests waited in the pending queue
        def decode_overlap_ratio() -> float:
            rounds = ahead = 0
            for sched in _schedulers():
                rounds += sched.decode_rounds
                ahead += sched.lookahead_rounds
            return ahead / rounds if rounds else 0.0

        self.registry.gauge(
            "llm_decode_overlap_ratio",
            "Decode rounds served by a lookahead-dispatched chunk (0..1)"
        ).set_function(decode_overlap_ratio)

        # deep lookahead (the epoch ring): mean achieved ring depth at drain
        # time across schedulers, and what fraction of speculative dispatches
        # were discarded as stale — both read off the same counters
        # stats()["pipeline"] exposes, so REST and dashboards cannot drift
        def lookahead_depth() -> float:
            weighted = total = 0
            for sched in _schedulers():
                # scheduler thread inserts new depth keys mid-copy:
                # advisory snapshot, degrades to empty for this scrape
                hist = locked_snapshot(getattr(sched, "_depth_hist", {}))
                for d, n in hist.items():
                    weighted += int(d) * n
                    total += n
            return weighted / total if total else 0.0

        self.registry.gauge(
            "llm_lookahead_depth",
            "Mean lookahead-ring depth still in flight at chunk drain time"
        ).set_function(lookahead_depth)

        def lookahead_discard_ratio() -> float:
            dispatched = discarded = 0
            for sched in _schedulers():
                la = dict(getattr(sched, "_lookahead_stats", {}))
                dispatched += la.get("dispatched", 0)
                discarded += la.get("discarded", 0)
            return discarded / dispatched if dispatched else 0.0

        self.registry.gauge(
            "llm_lookahead_discard_ratio",
            "Speculative decode chunks discarded as stale / dispatched (0..1)"
        ).set_function(lookahead_discard_ratio)

        # the ring as counters (pushed by the scheduler): every decode chunk
        # dispatched, every one dropped undrained, and the loop passes that
        # held an admission back for the chunks in flight; and what the
        # decode kernel's grid walked in the dispatches that were drained
        for name, text in (
                ("llm_decode_chunks_dispatched_total",
                 "Decode chunks dispatched (the head of the lookahead ring "
                 "and every chunk chained behind it)"),
                ("llm_decode_chunks_discarded_total",
                 "Decode chunks dropped from the lookahead ring undrained: "
                 "computed, never emitted (a preemption, a host-detected "
                 "stop, no running row left)"),
                ("llm_admission_ring_waits_total",
                 "Scheduler passes in which a request could have been "
                 "admitted or resumed and waited for the chunks in flight"),
                ("llm_drains_ring_empty_total",
                 "Drains that left nothing the scheduler launched undrained "
                 "(the ring's last chunk ahead of an arrival, a prompt's "
                 "chunk that is not its last): their emit is held for the "
                 "next launch"),
                ("llm_emits_deferred_total",
                 "Held emits that ran behind the next launch, under the "
                 "device's work; the rest were flushed first (a cancel, a "
                 "preemption, nothing to launch)"),
                ("llm_mixed_steps_total",
                 "mixed_step dispatches (a step that carries a prompt's "
                 "chunk, or a speculating engine's draft spans)"),
                ("llm_mixed_steps_chained_total",
                 "Of them, those launched off the device outputs of a "
                 "mixed_step that was still undrained (a prompt's next "
                 "chunk, known before the drain)"),
                ("llm_control_rows_uploads_total",
                 "Dispatches that found the host-owned rows (a slot's "
                 "sampling and termination rows, the active mask) changed "
                 "and uploaded them whole"),
                ("llm_loose_row_programs_total",
                 "Programs dispatched beside the step programs to write a "
                 "slot's device rows (restore_row at a resume or a handoff "
                 "import; none in steady serving)"),
                ("llm_attn_pages_walked_total",
                 "Pages the paged decode kernel walked: every page "
                 "that holds tokens a row's query reads, summed over steps "
                 "and layers"),
                ("llm_attn_page_groups_total",
                 "Groups of pages the decode kernel took them in, every row at "
                 "least one: a trip each of a decode kernel's one program a "
                 "row (a key block of ops/paged_attention.py: "
                 "decode_trip_pages or ops/mla_attention.py: trip_pages "
                 "pages)"),
                ("llm_ragged_pages_walked_total",
                 "Pages the ragged kernel's programs copied for the prompt "
                 "chunks of mixed steps (latent pages; K/V pages counted "
                 "once for the pair): the pages every block of queries "
                 "sees (ops/page_walk.py: ragged_span), summed over "
                 "q-blocks, steps and layers, a window layer by its "
                 "window's span"),
                ("llm_ragged_trips_total",
                 "Key blocks they attended over those pages in (a trip: up "
                 "to ragged_trip_pages pages, one score dot a kv head): pages a trip "
                 "near that number say the blocks run full"),
                ("llm_attn_window_pages_walked_total",
                 "Pages the decode kernel's grid walked in the WINDOW layers "
                 "of a model with a window page group (a row's last "
                 "sliding_window tokens, rounded out to pages), summed over "
                 "steps and window layers; llm_attn_pages_walked_total then "
                 "counts the layers that attend over everything"),
                ("llm_attn_window_pages_offered_total",
                 "Slots of the window group's page table beside them (rows "
                 "x pages a row x window layers x steps)"),
                ("llm_attn_window_page_groups_total",
                 "Groups the decode kernel took the WINDOW layers' pages in "
                 "(llm_attn_page_groups_total then counts the layers that "
                 "attend over everything): one trip a row where a trip is "
                 "the pages a window spans"),
                ("llm_window_pages_freed_total",
                 "Window-group pages rows gave back while running: each "
                 "lay left of the window of its row's committed length"),
                ("llm_attn_pages_offered_total",
                 "Slots of the page table (rows x pages a row) beside "
                 "them, for the same calls"),
                ("llm_device_starved_seconds_total",
                 "Seconds in which nothing the scheduler had launched was "
                 "undrained (the device waited for the host: a lower bound "
                 "of its idle time), by model and by the phase the "
                 "scheduler thread was in; wait = no work to give")):
            self.registry.counter(name, text).inc(0.0)

        # pure-decode vs mixed vs prefill-only round dispatch percentiles,
        # read straight off the scheduler round_timings ring (advisory
        # snapshot; same entries stats()["pipeline"]["dispatch_ms_by_kind"]
        # renders, so REST and Prometheus agree by construction). A decode-
        # role engine must show ~zero mixed/prefill mass here — that IS the
        # disaggregation claim, attributable per kind.
        def round_dispatch_ms(kind: str, q: float):
            def read() -> float:
                samples: list[float] = []
                for sched in _schedulers():
                    for t in locked_snapshot(
                            getattr(sched, "round_timings", ())):
                        if t.get("kind", "decode") == kind:
                            samples.append(t["dispatch_ms"])
                if not samples:
                    return 0.0
                s = sorted(samples)
                return float(s[min(len(s) - 1, int(q * len(s)))])
            return read

        g = self.registry.gauge(
            "llm_round_dispatch_ms",
            "Scheduler round dispatch time by round kind "
            "(decode/mixed/prefill) and quantile")
        for _kind in ("decode", "mixed", "prefill"):
            for _q, _qname in ((0.50, "p50"), (0.99, "p99")):
                g.set_function(round_dispatch_ms(_kind, _q),
                               kind=_kind, quantile=_qname)

        # batched speculative decoding (k-token ragged verify in the
        # continuous scheduler): draft tokens proposed vs device-accepted
        # (pushed by the scheduler per spec round) plus the mean accepted
        # draft length per verify span. The gauge reads the scheduler's
        # accept-length histogram counters directly (the _depth_hist
        # advisory-snapshot pattern of the lookahead gauges above — one
        # dict copy per scrape, no stats() build); stats()["speculative"]
        # renders the SAME counters for REST, so the surfaces agree by
        # construction
        self.registry.counter(
            "llm_spec_tokens_proposed_total",
            "Draft tokens proposed to the scheduler's ragged verify spans"
        ).inc(0.0)
        self.registry.counter(
            "llm_spec_tokens_accepted_total",
            "Draft tokens the on-device greedy verify accepted").inc(0.0)

        def spec_accept_len() -> float:
            weighted = total = 0
            for sched in _schedulers():
                # scheduler thread inserts new accept-len keys mid-copy
                hist = locked_snapshot(
                    getattr(sched, "_spec_accept_hist", {}))
                for a, n in hist.items():
                    weighted += int(a) * n
                    total += n
            return weighted / total if total else 0.0

        self.registry.gauge(
            "llm_spec_accept_len",
            "Mean accepted draft length per speculative verify span"
        ).set_function(spec_accept_len)

        # prefix-cache effectiveness (ROADMAP item 1's metrics half): the
        # fraction of prefill tokens the radix cache let admission skip, and
        # the cumulative tokens saved — both read straight off the pools'
        # stats() so the REST surface and the dashboards cannot drift
        def _pool_stats():
            for sched in _schedulers():
                pool = getattr(sched, "pool", None)
                if pool is not None:
                    yield pool.stats()

        def prefix_hit_rate() -> float:
            saved = total = 0
            for st in _pool_stats():
                saved += st.get("prefill_tokens_saved", 0)
                total += st.get("prefill_tokens_total", 0)
            return saved / total if total else 0.0

        self.registry.gauge(
            "llm_prefix_cache_hit_rate",
            "Cached vs total prefill tokens across paged pools (0..1)"
        ).set_function(prefix_hit_rate)

        def prefill_tokens_saved() -> float:
            return float(sum(st.get("prefill_tokens_saved", 0)
                             for st in _pool_stats()))

        self.registry.gauge(
            "llm_prefill_tokens_saved_total",
            "Prefill tokens skipped via prefix-cache hits (cumulative)"
        ).set_function(prefill_tokens_saved)

        # recurrent state beside the pages (a model with a state-space mixer:
        # runtime/paged.py): snapshots taken at chunk boundaries, prefix hits
        # that resumed from one, snapshots freed (their page evicted, or the
        # bounded set full), rows restored after preemption — pushed by the
        # pool — and how much of the state slab holds something
        for name, text in (
                ("llm_state_snapshots_taken_total",
                 "Recurrent-state snapshots taken where a prefill chunk "
                 "ended on a snapshot boundary"),
                ("llm_state_snapshot_hits_total",
                 "Admissions whose prefix hit resumed from a state snapshot"),
                ("llm_state_snapshot_evictions_total",
                 "State snapshots freed: their page evicted, or the oldest "
                 "dropped from the full set"),
                ("llm_state_restores_total",
                 "State rows restored from host on resume after preemption")):
            self.registry.counter(name, text).inc(0.0)

        # a model that generates by diffusion over blocks, with routed
        # experts (sdar_moe): pushed by the scheduler from what each drained
        # program counted on the device
        for name, text in (
                ("llm_block_row_forwards_total",
                 "A running row's part in one forward of its open block "
                 "(denoise and commit forwards alike)"),
                ("llm_block_commit_row_forwards_total",
                 "Those of them that were commit forwards: the block had no "
                 "mask left and its K/V was kept"),
                ("llm_blocks_committed_total",
                 "Blocks the host took from drained programs and emitted"),
                ("llm_block_tokens_emitted_total",
                 "Tokens emitted out of committed blocks (a stop id or the "
                 "token limit cuts inside a block)"),
                ("llm_moe_experts_touched_total",
                 "Experts that received at least one token, summed over "
                 "layers and forwards"),
                ("llm_moe_experts_offered_total",
                 "Experts held here (all of them where a chip holds them "
                 "all), summed over expert layers and forwards"),
                # a chip's share of the experts (kimi_k2)
                ("llm_moe_assignments_total",
                 "Token-expert assignments the forwards routed (tokens x "
                 "experts a token), summed over expert layers"),
                ("llm_moe_assignments_local_total",
                 "Those of them that fell on experts held here: experts "
                 "held / experts routed of them under uniform routing"),
                ("llm_moe_layer_forwards_total",
                 "Expert layers the forwards ran (layers x forwards) where "
                 "a chip holds a share of the routed experts"),
                ("llm_moe_layer_forwards_compact_total",
                 "Those of them whose held assignments fitted the capacity "
                 "taken from shapes, so the layer ran over the compacted "
                 "list; the rest ran over every assignment, none dropped"),
                ("llm_moe_decode_experts_touched_total",
                 "Experts that received at least one token, summed over "
                 "layers and the forwards of decode chunks alone (a mixed "
                 "step's prompt chunk is left out)"),
                ("llm_moe_decode_experts_offered_total",
                 "Experts held here, summed over expert layers and the "
                 "forwards of decode chunks alone"),
                ("llm_moe_decode_assignments_local_total",
                 "Assignments that fell on experts held here, summed over "
                 "layers and the forwards of decode chunks alone: over the "
                 "decode-only touched, the rows a touched expert multiplies "
                 "(a model whose module counts them)"),
                ("llm_moe_item_rows_total",
                 "Rows ONE grouped matmul of an expert layer multiplied (its "
                 "work items x the row tile picked from the call's shapes), "
                 "summed over expert layers and forwards: over the experts "
                 "touched, the rows the MXU is fed for an expert"),
                # a model whose stack runs several times a token (ouro)
                ("llm_loop_forwards_total",
                 "Forwards drained of a model whose stack runs several "
                 "times a token (decode chunks' steps and mixed steps)"),
                ("llm_loop_passes_total",
                 "Passes of the whole stack those forwards ran: over "
                 "llm_loop_forwards_total the depth a token paid for "
                 "(loop_steps while every pass runs)"),
                ("llm_loop_exit_pass_sum_total",
                 "The pass at which the exit gate WOULD have let a decode "
                 "row out (sum over passes of t x p_t from the gate's own "
                 "values), summed over the decode rows that ran"),
                ("llm_loop_exit_rows_total",
                 "Those decode rows: llm_loop_exit_pass_sum_total over this "
                 "is the mean exit pass the gate asks for"),
                # a model whose queries attend over a chosen set
                ("llm_dsa_keys_scored_total",
                 "Keys the index passes scored (a decode row its length, a "
                 "chunk's query its visible keys), summed over layers and "
                 "forwards"),
                ("llm_dsa_keys_selected_total",
                 "Keys attended behind them: a query's visible keys, at "
                 "most index_topk"),
                ("llm_dsa_queries_total",
                 "Queries scored and attended, summed over layers and "
                 "forwards"),
                ("llm_dsa_queries_binding_total",
                 "Those of them that saw more than index_topk keys, so that "
                 "the selection left some out"),
                ("llm_dsa_decode_keys_scored_total",
                 "Keys the index passes scored over the forwards of decode "
                 "chunks alone (a mixed step's prompt chunk is left out)"),
                ("llm_dsa_decode_keys_selected_total",
                 "Keys attended over the forwards of decode chunks alone"),
                ("llm_dsa_decode_queries_total",
                 "Decode queries scored and attended, over layers and the "
                 "forwards of decode chunks alone"),
                ("llm_dsa_decode_queries_binding_total",
                 "Those of them that saw more than index_topk keys: over "
                 "the decode queries, 1 where every running row is past "
                 "index_topk"),
                ("llm_dsa_decode_calls_total",
                 "Index passes of decode chunks' forwards (steps x layers): "
                 "the two decode-only totals over this are one call's keys "
                 "scored and attended, the whole batch")):
            self.registry.counter(name, text).inc(0.0)

        def _state_stat(key: str) -> float:
            return float(sum(st.get(key, 0) for st in _pool_stats()))

        def state_rows_in_use() -> float:
            return float(sum(getattr(s, "state_rows_in_use", lambda: 0)()
                             for s in _schedulers()))

        self.registry.gauge(
            "llm_state_rows_in_use",
            "Rows of the recurrent-state slab in use: live slots plus "
            "snapshots held").set_function(state_rows_in_use)
        self.registry.gauge(
            "llm_state_rows",
            "Rows of the recurrent-state slab (slots plus snapshot rows)"
        ).set_function(lambda: _state_stat("state_rows"))
        self.registry.gauge(
            "llm_state_bytes", "Bytes of the recurrent-state slab"
        ).set_function(lambda: _state_stat("state_bytes"))

        # what the caches were built with (a pool sized by the layers that
        # attend, a slab by the layers that hold state), and their bytes
        for name, key, text in (
                ("llm_kv_layers", "kv_layers",
                 "Layers of the page pool: the model's layers that attend"),
                ("llm_state_layers", "state_layers",
                 "Layers of the recurrent-state slab: the model's layers "
                 "that hold state"),
                ("llm_kda_layers", "kda_layers",
                 "Layers of the recurrent-state slab that hold a matrix of "
                 "state a head under a gated delta rule (0 for a Mamba-2 "
                 "slab)"),
                ("llm_model_layers", "model_layers",
                 "Layers of the model the caches were built for"),
                ("llm_loop_steps", "loop_steps",
                 "Passes of the whole stack a token runs with one set of "
                 "weights (1: a stack runs once); llm_kv_layers is this "
                 "many times the layers that attend"),
                ("llm_window_layers", "window_layers",
                 "Layers of the window page group: the model's layers whose "
                 "pages a row gives back once they lie left of its window "
                 "(0: one page group)"),
                ("llm_window_pages_in_use", "window_pages_in_use",
                 "Pages of the window page group rows hold (over "
                 "llm_batch_active_slots: about sliding_window / page + 1 a "
                 "row at rest where the pool frees)"),
                ("llm_cache_bytes", "cache_bytes",
                 "Bytes of the page pool (both page groups where the model "
                 "has a window group; the index keys beside the latent rows "
                 "where it has an indexer) plus the recurrent-state slab"),
                ("llm_index_topk", "index_topk",
                 "Keys a query attends at most, chosen by its index heads' "
                 "scores (0: a query attends every key it may see)"),
                ("llm_index_heads", "index_heads",
                 "Index heads that score a key for the selection (0: no "
                 "indexer)"),
                ("llm_index_cache_bytes", "index_cache_bytes",
                 "Bytes of the index keys cached beside the latent rows, "
                 "part of llm_cache_bytes (0: no indexer)")):
            self.registry.gauge(name, text).set_function(
                lambda key=key: _state_stat(key))

        self.registry.gauge(
            "llm_moe_layers",
            "Layers of the stacked expert matrices: the model's layers that "
            "hold routed experts"
        ).set_function(lambda: float(sum(
            getattr(s, "moe_layers_built", lambda: 0)()
            for s in _schedulers())))

        # query heads by the kind of attention layer, as the weights were
        # built (one number twice where a model has one kind of layer). What
        # an operator reads them for: heads over kv heads is the query rows
        # of a kv head's slab in the decode kernel, a layer's attention
        # work beside llm_attn_pages_walked_total /
        # llm_attn_page_groups_total and the window pair
        for name, kind, text in (
                ("llm_attn_full_heads", 0,
                 "Query heads of a layer that attends over a row's whole "
                 "length; over the kv heads, the query rows of a kv head's "
                 "slab in the decode kernel, whose trips "
                 "llm_attn_page_groups_total counts"),
                ("llm_attn_window_heads", 1,
                 "Query heads of a layer behind a sliding window (as "
                 "llm_attn_full_heads where the model has one kind); the "
                 "same for llm_attn_window_page_groups_total")):
            self.registry.gauge(name, text).set_function(
                lambda kind=kind: float(sum(
                    getattr(s, "attn_heads_built", lambda: (0, 0))()[kind]
                    for s in _schedulers())))

        def mixed_chunk_tokens() -> float:
            return float(sum(getattr(s, "chunked_prefill_tokens", 0)
                             for s in _schedulers()))

        self.registry.gauge(
            "llm_prefill_chunk_tokens_total",
            "Prompt tokens prefilled via mixed-batch chunks piggybacked "
            "into decode rounds (cumulative)"
        ).set_function(mixed_chunk_tokens)

        def mixed_positions() -> float:
            return float(sum(getattr(s, "mixed_positions", 0)
                             for s in _schedulers()))

        self.registry.gauge(
            "llm_mixed_step_positions_total",
            "Positions the mixed-batch dispatches computed (cumulative): a "
            "lane step's decode rows plus its chunk's padded width; beside "
            "llm_prefill_chunk_tokens_total it shows how much was padding"
        ).set_function(mixed_positions)

        def queue_wait_p50_ms() -> float:
            waits: list[float] = []
            for sched in _schedulers():
                # deque resized mid-iteration: advisory snapshot
                waits.extend(locked_snapshot(sched.queue_wait_samples))
            if not waits:
                return 0.0
            return float(sorted(waits)[len(waits) // 2])

        self.registry.gauge(
            "llm_queue_wait_p50_ms",
            "p50 pending-queue wait of admitted requests (ms)"
        ).set_function(queue_wait_p50_ms)

        # the start-up timeline's three instants (the compile ledger's
        # counters are the listeners' own: StartupTimeline)
        self.registry.gauge(
            "process_start_time_seconds",
            "Unix time the OS started this process"
        ).set(startup.process_start_unix_ns / 1e9)
        self.registry.gauge(
            "process_uptime_seconds", "Seconds since the process started"
        ).set_function(
            lambda: time.time() - startup.process_start_unix_ns / 1e9)
        self.registry.gauge(
            "startup_ready_seconds",
            "Seconds from the process's start until /healthz could answer "
            "(the boot stage; 0 until then)"
        ).set_function(
            lambda: (startup.ready_unix_ns - startup.process_start_unix_ns)
            / 1e9 if startup.ready_unix_ns else 0.0)
        # what the ledger's listeners cost: JAX's calls into them and the
        # seconds inside (read at a scrape; nothing is counted a call)
        self.registry.gauge(
            "startup_listener_calls", "Calls of the compile ledger's listeners"
        ).set_function(lambda: startup.listener_calls)
        self.registry.gauge(
            "startup_listener_seconds",
            "Seconds inside the compile ledger's listeners"
        ).set_function(lambda: startup.listener_seconds)

    async def start(self, ctx: ModuleCtx, ready: ReadySignal) -> None:
        # the evaluation thread spins up in start (not init) so its lifetime
        # matches the stack's: stop() below is the teardown
        self.doctor.ensure_started()
        ready.notify_ready()

    async def stop(self, ctx: ModuleCtx) -> None:
        # the doctor thread and its scheduler-provider closure must not
        # outlive this stack — a leaked evaluator watching a dead worker's
        # schedulers would keep tripping watchdogs and shed a healthy NEXT
        # stack booted in the same process
        doctor = getattr(self, "doctor", None)
        if doctor is not None:
            doctor.stop()
            doctor.set_scheduler_provider(None)
            doctor.set_capacity_provider(None)
            doctor.detach_recorder()

    def register_rest(self, ctx: ModuleCtx, router, openapi) -> None:
        async def metrics(request: web.Request):
            # federated stacks merge worker heartbeat snapshots into the
            # exposition host-labeled (FleetView.render_with keeps one
            # HELP/TYPE block per family); any fold failure degrades to
            # the plain gateway-local render, never to a scrape error
            text = None
            fleet = getattr(ctx.client_hub.try_get(LlmWorkerApi),
                            "fleet", None)
            if fleet is not None:
                try:
                    text = fleet.render_with(self.registry)
                except Exception:  # noqa: BLE001
                    text = None
            return web.Response(text=text or self.registry.render(),
                                content_type="text/plain")

        router.operation("GET", "/metrics", module="monitoring").public() \
            .summary("Prometheus text exposition").handler(metrics).register()

        # jax.profiler device tracing (SURVEY §5: host spans + jax.profiler
        # traces + XLA cost-analysis dumps are the device-side observability
        # triple; cost analysis lives on the engine, this is the trace leg)
        async def profiler_start(request: web.Request):
            from ..modkit.errcat import ERR

            if self._profile_dir is not None:
                raise ERR.monitoring.profiler_running.error(
                    f"trace already running at {self._profile_dir}")
            import time

            import jax

            out = ctx.app_config.home_dir() / "profiles" / f"trace-{int(time.time())}"
            out.mkdir(parents=True, exist_ok=True)
            if self._tracer_maybe_live:
                # a prior stop_trace may have raised AFTER we cleared
                # _profile_dir, leaving JAX's global tracer active while our
                # state says stopped — best-effort stop so start can succeed
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
            # the Python tracer stays off: hooking every Python call slows
            # the host this trace is taken to judge, and with it on the
            # /host:CPU plane (where the scheduler's sched.* spans lie) runs
            # 1.5 ms ahead of the device planes' clock (PERF.md section 6,
            # PR 37); TraceMe events (the spans, the runtime's own) stay
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(out), profiler_options=options)
            # only a successful start proves the global tracer is ours again;
            # clearing the flag before this point would make a persistently
            # failing stop wedge every future /start
            self._tracer_maybe_live = False
            self._profile_dir = out
            return {"status": "started", "dir": str(out)}

        async def profiler_stop(request: web.Request):
            from ..modkit.errcat import ERR

            if self._profile_dir is None:
                raise ERR.monitoring.profiler_not_running.error("no trace running")
            import jax

            # clear state FIRST: a failing stop_trace must not wedge the
            # endpoints in "running" with no API path to reset — but remember
            # the tracer may still be live so the next /start can clear it
            out, self._profile_dir = self._profile_dir, None
            try:
                # ON the event loop, and it has to stay there: a trace of
                # 0.9 M device ops (a looped model's 3 s) takes half a minute
                # to write while no stream and no arrival moves; from a
                # thread, beside a server that keeps the device busy, the
                # same stop did not end in two minutes (PERF.md section 7,
                # PR 52)
                jax.profiler.stop_trace()
                self._tracer_maybe_live = False
            except Exception as e:
                self._tracer_maybe_live = True
                raise ERR.monitoring.profiler_stop_failed.error(str(e)[:200])
            files = sorted(str(p.relative_to(out))
                           for p in out.rglob("*") if p.is_file())
            return {"status": "stopped", "dir": str(out), "files": files}

        router.operation("POST", "/v1/monitoring/profiler/start",
                         module="monitoring").auth_required() \
            .summary("Start a jax.profiler device trace") \
            .handler(profiler_start).register()
        router.operation("POST", "/v1/monitoring/profiler/stop",
                         module="monitoring").auth_required() \
            .summary("Stop the device trace; returns the dump location") \
            .handler(profiler_stop).register()

        # ---- failpoint control plane (faultlab): soak rehearsals arm/disarm
        # fault injection against a LIVE server. Reads are always allowed;
        # arming is gated behind `monitoring: {allow_fault_injection: true}`
        # so a production deployment cannot be chaos-tested by accident.
        from ..modkit import failpoints as fp
        from ..modkit.errcat import ERR

        def _require_faultlab() -> None:
            if not self._allow_fault_injection:
                raise ERR.monitoring.faultlab_disabled.error(
                    "fault injection is disabled; set monitoring."
                    "allow_fault_injection: true for chaos rehearsals")

        async def list_failpoints(request: web.Request):
            return {
                "enabled": self._allow_fault_injection,
                "catalog": {name: {"layer": layer, "description": desc}
                            for name, (layer, desc)
                            in sorted(fp.FAILPOINT_CATALOG.items())},
                "armed": {name: action.__dict__
                          for name, action in fp.armed().items()},
                "stats": fp.stats(),
            }

        async def _remote_failpoint(host: str, action: str, name: str,
                                    spec, seed):
            """Forward a failpoint arm/disarm to a federated worker host
            over the observability service, mapping its refusal strings
            back onto the same problems the local path raises."""
            remote = getattr(ctx.client_hub.try_get(LlmWorkerApi),
                             "remote_failpoint", None)
            if remote is None:
                raise ERR.monitoring.unknown_host.error(
                    f"unknown worker host {host!r} (not a federated stack)")
            try:
                resp = await remote(host, action, name, spec, seed=seed)
            except KeyError:
                raise ERR.monitoring.unknown_host.error(
                    f"unknown worker host {host!r}")
            if not resp.get("ok"):
                err = str(resp.get("error") or "remote refusal")
                if "unknown failpoint" in err:
                    raise ERR.monitoring.unknown_failpoint.error(err)
                if "disabled" in err:
                    raise ERR.monitoring.faultlab_disabled.error(
                        f"worker host {host!r}: {err}")
                raise ERR.monitoring.bad_failpoint_spec.error(err[:200])
            return resp

        async def arm_failpoint(request: web.Request):
            _require_faultlab()
            name = request.match_info["name"]
            body = await read_json(request, {
                "type": "object",
                "properties": {"spec": {"type": ["string", "object"]},
                               "seed": {"type": "integer"},
                               "host": {"type": "string"}},
                "additionalProperties": False})
            if body.get("host"):
                # faultlab's cross-host arm: the failpoint fires in the
                # WORKER process, not here
                await _remote_failpoint(body["host"], "arm", name,
                                        body.get("spec", "raise"),
                                        body.get("seed"))
                return {"armed": name, "host": body["host"]}
            if "seed" in body:
                fp.configure(int(body["seed"]))
            try:
                fp.arm(name, body.get("spec", "raise"))
            except KeyError:
                raise ERR.monitoring.unknown_failpoint.error(
                    f"unknown failpoint {name!r}")
            except (ValueError, TypeError) as e:
                raise ERR.monitoring.bad_failpoint_spec.error(str(e)[:200])
            return {"armed": name, "stats": fp.stats()}

        async def disarm_failpoint(request: web.Request):
            _require_faultlab()
            name = request.match_info["name"]
            host = request.query.get("host")
            if host:
                await _remote_failpoint(host, "disarm", name, "off", None)
                return {"disarmed": True, "host": host}
            if name not in fp.FAILPOINT_CATALOG:
                raise ERR.monitoring.unknown_failpoint.error(
                    f"unknown failpoint {name!r}")
            return {"disarmed": fp.disarm(name)}

        async def reset_failpoints(request: web.Request):
            _require_faultlab()
            fp.reset()
            return {"reset": True}

        # ---- request flight recorder: live in-flight introspection + full
        # per-request phase timelines (enqueued → prefill → decode chunks →
        # preempt/resume → finished), keyed by the X-Request-Id the client
        # already holds. Recently finished requests stay queryable from the
        # recorder's bounded ring.
        from ..modkit.flight_recorder import default_recorder

        def _int_param(request: web.Request, name: str, default: int) -> int:
            raw = request.query.get(name)
            if raw is None:
                return default
            try:
                value = int(raw)
            except ValueError:
                raise ERR.core.bad_request.error(
                    f"query parameter {name!r} must be an integer, "
                    f"got {raw!r}")
            if value < 0:
                raise ERR.core.bad_request.error(
                    f"query parameter {name!r} must be >= 0")
            return value

        async def list_requests(request: web.Request):
            # ?stalled=true narrows to streams a stall watchdog flagged —
            # operators triage watchdog trips from the same table (each row
            # carries age_s + last_event_age_s for the how-stuck reading)
            stalled_raw = request.query.get("stalled", "")
            if stalled_raw.lower() not in ("", "true", "false", "1", "0"):
                raise ERR.core.bad_request.error(
                    "query parameter 'stalled' must be true or false, "
                    f"got {stalled_raw!r}")
            stalled_only = stalled_raw.lower() in ("true", "1")
            rows = default_recorder.inflight(stalled_only=stalled_only)
            rows.sort(key=lambda r: -r["age_s"])
            return {
                "in_flight": rows,
                "recent": default_recorder.recent(
                    _int_param(request, "recent", 20)),
                "recorder": default_recorder.stats(),
            }

        async def get_request_timeline(request: web.Request):
            rid = request.match_info["request_id"]
            rec = default_recorder.lookup(rid)
            if rec is None:
                raise ERR.monitoring.unknown_request.error(
                    f"no flight record for request {rid!r} (live table + "
                    "finished ring miss — it may have aged out)")
            # federated stacks: every host named by the gateway-side events
            # (worker_host on admitted/decode, from_host on failover) holds
            # the other half of this request's story — pull each segment
            # over the observability wire and stitch into ONE timeline
            # under the same X-Request-Id. Best-effort: a dead host's
            # segment is simply absent, never a 500.
            fetch = getattr(ctx.client_hub.try_get(LlmWorkerApi),
                            "fetch_remote_timeline", None)
            if fetch is None:
                return rec
            hosts: list[str] = []
            for ev in rec.get("timeline") or ():
                for key in ("worker_host", "from_host"):
                    h = ev.get(key)
                    if h and h not in hosts:
                        hosts.append(h)
            if not hosts:
                return rec
            from ..runtime.federation import stitch_timelines

            segments = {}
            for h in hosts:
                seg = await fetch(h, rid)
                if seg is not None:
                    segments[h] = seg
            return stitch_timelines(rec, segments) if segments else rec

        def _schedulers_named():
            worker = ctx.client_hub.try_get(LlmWorkerApi)
            for name, entry in getattr(worker, "_entries", {}).items():
                sched = getattr(entry, "scheduler", None)
                if sched is not None:
                    yield name, sched

        async def export_rounds(request: web.Request):
            fmt = request.query.get("format", "json")
            if fmt not in ("json", "chrome-trace"):
                raise ERR.monitoring.bad_export_format.error(
                    f"format {fmt!r} not supported; use json or chrome-trace")
            limit = _int_param(request, "limit", 512)
            per_model: dict[str, list[dict]] = {}
            for name, sched in _schedulers_named():
                # snapshot a deque the scheduler thread appends to
                rounds = locked_snapshot(sched.round_timings)
                rounds = rounds[-limit:] if limit else []
                per_model[name] = rounds
            if fmt == "json":
                return {"rounds": per_model}
            return web.json_response(
                _chrome_trace(per_model),
                headers={"Content-Disposition":
                         'attachment; filename="scheduler-rounds.json"'})

        router.operation("GET", "/v1/monitoring/requests",
                         module="monitoring").auth_required() \
            .summary("Live in-flight request table (flight recorder)") \
            .handler(list_requests).register()
        router.operation("GET", "/v1/monitoring/requests/{request_id}",
                         module="monitoring").auth_required() \
            .summary("Full phase timeline of one request (incl. recently "
                     "finished)") \
            .handler(get_request_timeline).register()
        router.operation("GET", "/v1/monitoring/rounds",
                         module="monitoring").auth_required() \
            .summary("Recent scheduler rounds; ?format=chrome-trace exports "
                     "Perfetto-loadable trace events") \
            .handler(export_rounds).register()

        # ---- the start-up timeline (modkit/telemetry.py StartupTimeline):
        # boot and each engine's build as a tree of stages with self time,
        # what the first user after a restart waited for, and the compile
        # ledger summed by program
        async def get_startup(request: web.Request):
            return startup.snapshot()

        router.operation("GET", "/v1/monitoring/startup",
                         module="monitoring").auth_required() \
            .summary("Start-up timeline: boot and engine-build stages with "
                     "self time, first_token a model, the compile ledger by "
                     "program (trace, lower, compile or cache load)") \
            .handler(get_startup).register()

        # ---- fabric-doctor: the full SLO/state document behind the public
        # /readyz verdict — objective table with fast/slow burn rates,
        # watchdog trip counters, and the degradation state history ring
        async def get_slo(request: web.Request):
            return self.doctor.report()

        router.operation("GET", "/v1/monitoring/slo",
                         module="monitoring").auth_required() \
            .summary("SLO objective table, burn rates, watchdog trips, and "
                     "degradation state history (fabric-doctor)") \
            .handler(get_slo).register()

        # ---- replica lifecycle control plane: the operator's rolling-
        # restart surface. GET lists every replica (pool replicas + single
        # engines) with lifecycle state and engine health; the POST actions
        # drive supervised pool replicas through drain → drained → restart
        # (restart is async: the handler walks the state machine and the
        # lifecycle supervisor performs the close + rebuild off-thread).
        from ..runtime.lifecycle import LifecycleStateError

        async def list_replicas(request: web.Request):
            worker = ctx.client_hub.try_get(LlmWorkerApi)
            return {
                "replicas": worker.replicas_view() if worker else [],
                "capacity": worker.replica_capacity() if worker else {},
            }

        def _replica_index(request: web.Request) -> int:
            raw = request.match_info["index"]
            try:
                return int(raw)
            except ValueError:
                raise ERR.core.bad_request.error(
                    f"replica index must be an integer, got {raw!r}")

        async def _replica_action(request: web.Request, action: str):
            worker = ctx.client_hub.try_get(LlmWorkerApi)
            if worker is None:
                raise ERR.monitoring.unknown_replica.error(
                    "no llm worker in this stack")
            index = _replica_index(request)
            # ?model= pins the action to the model the operator's listing
            # showed — the flat index space shifts under entry churn, and a
            # mismatch must 409 rather than drain the wrong replica
            expect_model = request.query.get("model")
            deadline_s = None
            if action == "drain" and request.content_length:
                body = await read_json(request, {
                    "type": "object",
                    "properties": {"deadline_s": {"type": "number",
                                                  "minimum": 0}},
                    "additionalProperties": False})
                deadline_s = body.get("deadline_s")
            try:
                return worker.replica_control(index, action,
                                              deadline_s=deadline_s,
                                              expect_model=expect_model)
            except (KeyError, IndexError) as e:
                raise ERR.monitoring.unknown_replica.error(
                    str(e).strip("'\""))
            except LifecycleStateError as e:
                raise ERR.monitoring.replica_conflict.error(str(e))

        async def drain_replica(request: web.Request):
            return await _replica_action(request, "drain")

        async def undrain_replica(request: web.Request):
            return await _replica_action(request, "undrain")

        async def restart_replica(request: web.Request):
            return await _replica_action(request, "restart")

        router.operation("GET", "/v1/monitoring/replicas",
                         module="monitoring").auth_required() \
            .summary("Replica lifecycle table: per-replica state, strikes, "
                     "rebuild counters, and the aggregated capacity census") \
            .handler(list_replicas).register()
        router.operation("POST", "/v1/monitoring/replicas/{index}/drain",
                         module="monitoring").auth_required() \
            .summary("Drain a pool replica: stop new admissions, let "
                     "in-flight finish; past deadline_s stragglers fail "
                     "over to surviving replicas") \
            .handler(drain_replica).register()
        router.operation("POST", "/v1/monitoring/replicas/{index}/undrain",
                         module="monitoring").auth_required() \
            .summary("Return a still-draining replica to rotation") \
            .handler(undrain_replica).register()
        router.operation("POST", "/v1/monitoring/replicas/{index}/restart",
                         module="monitoring").auth_required() \
            .summary("Close + rebuild a replica (clears strikes — the "
                     "benched escape hatch); rebuild runs on the "
                     "lifecycle supervisor thread") \
            .handler(restart_replica).register()

        # ---- tenant isolation: the per-tenant live view behind the
        # weighted-fair scheduler — slots, KV pages, queue depth, virtual
        # counter, charged tokens, and the doctor's selective-shed state.
        # The operator's first stop when one tenant's latency spikes: is it
        # over its fair share, capped, or being shed?
        def _tenant_rows() -> dict[str, dict]:
            worker = ctx.client_hub.try_get(LlmWorkerApi)
            usage = worker.tenant_usage() if worker is not None else {}
            shed = set()
            doc = getattr(self, "doctor", None)
            if doc is not None:
                try:
                    shed = set(doc.report().get("shed_tenants", ()))
                except Exception:  # noqa: BLE001 — view must not 500
                    shed = set()
            for tenant, row in usage.items():
                row["shed"] = tenant in shed
            return usage

        async def list_tenants(request: web.Request):
            rows = _tenant_rows()
            return {
                "tenants": [rows[t] for t in sorted(rows)],
                "count": len(rows),
            }

        async def get_tenant(request: web.Request):
            tenant_id = request.match_info["tenant_id"]
            rows = _tenant_rows()
            row = rows.get(tenant_id)
            if row is None:
                raise ERR.monitoring.unknown_tenant.error(
                    f"no live scheduler state for tenant {tenant_id!r} "
                    "(it has no pending, active, or previously charged "
                    "work on this node)")
            return row

        router.operation("GET", "/v1/monitoring/tenants",
                         module="monitoring").auth_required() \
            .summary("Per-tenant live scheduler state: slots, KV pages, "
                     "queue depth, virtual fairness counter, charged "
                     "tokens, and selective-shed state") \
            .handler(list_tenants).register()
        router.operation("GET", "/v1/monitoring/tenants/{tenant_id}",
                         module="monitoring").auth_required() \
            .summary("One tenant's live scheduler state (404 when the "
                     "tenant holds no state on this node)") \
            .handler(get_tenant).register()

        # ---- cross-host federation: the worker-plane census behind the
        # FederatedServingPool's routing decisions — per-host lease age,
        # roles, capacity, gossiped prefix-index size, and the bounded
        # evicted-host memory (why did capacity shrink?). The registry is
        # hub-registered by grpc_hub; non-federated stacks 404 per-worker
        # and list an empty table.
        from .sdk import WorkerRegistryApi

        async def list_workers(request: web.Request):
            reg = ctx.client_hub.try_get(WorkerRegistryApi)
            if reg is None:
                return {"workers": [], "evicted": [], "lease_ttl_s": 0.0,
                        "prefix_index_size": 0, "federation": False}
            body = reg.rows()
            body["federation"] = True
            return body

        async def get_worker(request: web.Request):
            instance_id = request.match_info["instance_id"]
            reg = ctx.client_hub.try_get(WorkerRegistryApi)
            w = reg.lookup(instance_id) if reg is not None else None
            if w is None:
                raise ERR.monitoring.unknown_worker.error(
                    f"no live federation lease for worker {instance_id!r} "
                    "(never announced, withdrawn, or evicted)")
            return w.row(lease_ttl_s=reg.lease_ttl_s)

        # ---- fleet observability (fabric-fleetscope): the health fold
        # over every worker's heartbeat payload — per-host doctor state,
        # burn-rate objective rows, and the worst-of fleet verdict that
        # also feeds /readyz and the router's health rung.
        async def get_fleet(request: web.Request):
            fleet = getattr(ctx.client_hub.try_get(LlmWorkerApi),
                            "fleet", None)
            host = request.query.get("host")
            if fleet is None:
                if host:
                    raise ERR.monitoring.unknown_host.error(
                        f"unknown worker host {host!r} (not a federated "
                        "stack)")
                return {"federation": False, "state": "unknown",
                        "reasons": [], "hosts": [], "objectives": [],
                        "workers": 0, "stale": 0, "lease_ttl_s": 0.0}
            doc = fleet.report()
            if host:
                rows = [r for r in doc["hosts"]
                        if host in (r.get("host"), r.get("instance_id"))]
                if not rows:
                    raise ERR.monitoring.unknown_host.error(
                        f"unknown worker host {host!r} (no live lease "
                        "carries that host name or instance id)")
                doc = {**doc, "hosts": rows}
            return doc

        router.operation("GET", "/v1/monitoring/fleet",
                         module="monitoring").auth_required() \
            .summary("Fleet health fold: per-host doctor state and burn "
                     "rates off worker heartbeats (?host= filters; 404 on "
                     "an unknown host)") \
            .handler(get_fleet).register()
        router.operation("GET", "/v1/monitoring/workers",
                         module="monitoring").auth_required() \
            .summary("Federated worker census: per-host lease age, roles, "
                     "capacity, prefix-index size, and recent evictions") \
            .handler(list_workers).register()
        router.operation("GET", "/v1/monitoring/workers/{instance_id}",
                         module="monitoring").auth_required() \
            .summary("One federated worker's census row (404 when it holds "
                     "no live lease)") \
            .handler(get_worker).register()

        router.operation("GET", "/v1/monitoring/failpoints",
                         module="monitoring").auth_required() \
            .summary("Failpoint catalog, armed actions, and fault stats") \
            .handler(list_failpoints).register()
        router.operation("PUT", "/v1/monitoring/failpoints/{name}",
                         module="monitoring").auth_required() \
            .summary("Arm a failpoint (guarded: allow_fault_injection)") \
            .handler(arm_failpoint).register()
        router.operation("DELETE", "/v1/monitoring/failpoints/{name}",
                         module="monitoring").auth_required() \
            .summary("Disarm a failpoint").handler(disarm_failpoint).register()
        router.operation("DELETE", "/v1/monitoring/failpoints",
                         module="monitoring").auth_required() \
            .summary("Disarm every failpoint and clear fault counters") \
            .handler(reset_failpoints).register()
