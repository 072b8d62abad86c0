"""The builtin chaos-scenario catalog + the scenario file format.

A scenario is a dict:

.. code-block:: yaml

    name: forced-preempt            # unique scenario name
    kind: engine                    # engine|pool|http_retry|db_commit|
                                    #   server_breaker|server_gateway|
                                    #   serverless|worker|grpc_evict|
                                    #   worker_host_crash
    seed: 1234                      # drives load gen + probability modes
    engine: {max_batch: 2, ...}     # EngineConfig overrides (engine/pool)
    load: {requests: 4, prompt_len: [4, 10], max_tokens: 10}
    faults:                         # the fault schedule, keyed on failpoint
      - point: scheduler.page_alloc #   names (modkit.failpoints catalog)
        spec: "1*raise(MemoryError)"  # fail-crate-style action spec
    invariants: [exactly_one_terminal, streams_match_baseline,
                 engine_accounting]
    expect_error: [0]               # request indices that MUST error
    expect_stats: {preemptions: [1, null]}   # [min, max] bounds

``spec`` strings: ``raise`` / ``raise(MemoryError)`` / ``delay(0.01)`` /
``return(503)`` / ``2*raise`` (first two hits) / ``3:raise`` (every 3rd) /
``25%raise`` (probability, deterministic under the scenario seed); dicts with
the Action fields also work. YAML files with a top-level ``scenarios:`` list
load via :func:`load_scenario_file`.

Every failpoint in ``modkit.failpoints.FAILPOINT_CATALOG`` is covered by at
least one builtin scenario below — tests/test_faultlab.py asserts that, so a
new failpoint cannot land without a chaos scenario exercising it.
"""

from __future__ import annotations

from pathlib import Path
from typing import Any

__all__ = ["BUILTIN_SCENARIOS", "load_scenario_file", "scenario_by_name"]

#: shared tiny-engine shape: one prefill bucket (prompts <= 10 → bucket 16),
#: paged pool, greedy decode — a handful of compiled programs serve every
#: engine/pool scenario, and the baseline cache is shared across them
_TINY = {"model": "tiny-llama", "max_seq_len": 64, "max_batch": 2,
         "decode_chunk": 4, "prefix_cache_pages": 64, "prefix_page_size": 16}
_LOAD = {"requests": 4, "prompt_len": [4, 10], "max_tokens": 10}

BUILTIN_SCENARIOS: list[dict[str, Any]] = [
    # ---- runtime / scheduler ------------------------------------------
    {
        "name": "readback-crash",
        "kind": "engine",
        "seed": 101,
        "engine": _TINY,
        "load": _LOAD,
        # fires on the 3rd decode-chunk readback: every stream is mid-flight
        # (max_tokens 10 needs ~3 chunks), so ALL requests must error-
        # terminate exactly once — none lost, none double-emitted
        "faults": [{"point": "scheduler.readback",
                    "spec": {"kind": "raise", "mode": "once", "after": 2}}],
        "invariants": ["exactly_one_terminal"],
        "expect_error": [0, 1, 2, 3],
        "deterministic_tokens": False,
    },
    {
        "name": "prefill-fault",
        "kind": "engine",
        "seed": 102,
        # fires in the FIFO-first request's admission (_admit_prefill_slot):
        # _place reclaims its slot and error-terminates only that request
        "engine": _TINY,
        "load": _LOAD,
        "faults": [{"point": "scheduler.prefill", "spec": "1*raise"}],
        "invariants": ["exactly_one_terminal", "expected_errors",
                       "streams_match_baseline", "engine_accounting"],
        "expect_error": [0],
    },
    {
        "name": "admit-delay",
        "kind": "engine",
        "seed": 103,
        "engine": _TINY,
        "load": _LOAD,
        # a slow admission path must change NOTHING but latency
        "faults": [{"point": "scheduler.admit", "spec": "delay(0.002)"}],
        "invariants": ["exactly_one_terminal", "expected_errors",
                       "streams_match_baseline", "engine_accounting"],
    },
    {
        "name": "forced-preempt",
        "kind": "engine",
        "seed": 104,
        "engine": _TINY,
        "load": _LOAD,
        # injected MemoryError on one page-chain extension forces a
        # preempt-to-host + resume round-trip with NO real pool pressure;
        # the resumed stream must be bit-identical to the unfaulted run
        "faults": [{"point": "scheduler.page_alloc",
                    "spec": "1*raise(MemoryError)"}],
        "invariants": ["exactly_one_terminal", "expected_errors",
                       "streams_match_baseline", "engine_accounting"],
        "expect_stats": {"preemptions": [1, None]},
    },
    {
        "name": "mixed-prefill-preempt",
        "kind": "engine",
        "seed": 107,
        # budget 3 forces every 4-10 token prompt through >= 2 mixed-batch
        # prefill chunks; the 3rd chunk-growth hit lands MID-prefill of a
        # partially-prefilled request (its first chunk already in pool pages)
        "engine": {**_TINY, "prefill_budget_tokens": 3},
        "load": _LOAD,
        # injected MemoryError on a prefill-chunk page growth preempts the
        # request mid-chunked-prefill; resume must continue chunking from the
        # saved position and reproduce the unfaulted stream bit-for-bit,
        # with no page refs or orphans leaked. Two asks in a row fail: the
        # one made for a chunk's step planned ahead of the drain before it
        # launches nothing, and the next round's own ask preempts
        "faults": [{"point": "scheduler.prefill_chunk",
                    "spec": {"kind": "raise", "exc": "MemoryError",
                             "mode": "once", "after": 1, "n": 2}}],
        "invariants": ["exactly_one_terminal", "expected_errors",
                       "streams_match_baseline", "engine_accounting"],
        "expect_stats": {"preemptions": [1, None]},
    },
    {
        "name": "deep-lookahead-fault",
        "kind": "engine",
        "seed": 108,
        # a 3-deep epoch ring with device-side termination: every readback
        # drain is delayed while up to 3 speculative chunks are in flight.
        # Streams must stay bit-identical to the fully SYNCHRONOUS scheduler
        # (baseline_engine pins depth 0 — the golden depth-equivalence
        # contract, exercised under fault pressure), every client gets
        # exactly one terminal, and nothing leaks with a ring in flight.
        "engine": {**_TINY, "decode_lookahead": 3},
        "baseline_engine": {"decode_lookahead": 0},
        "load": {**_LOAD, "max_tokens": 16},
        "faults": [{"point": "scheduler.readback", "spec": "delay(0.05)"}],
        "invariants": ["exactly_one_terminal", "expected_errors",
                       "streams_match_baseline", "engine_accounting"],
    },
    {
        "name": "mid-ring-preempt",
        "kind": "engine",
        "seed": 109,
        # pool pressure while a 3-deep ring is in flight: armed MemoryErrors
        # first CAP the ring (extension attempts absorb hits, no preempt),
        # then — once the ring drains to a synchronous round — force a real
        # preempt-to-host. 8 hits guarantee the preempt lands regardless of
        # where the ring absorbs the early ones (ring depth ≤ 3 absorptions
        # per drain cycle). The preempted stream must resume bit-identical
        # to the depth-0 baseline with zero page/slot leaks.
        "engine": {**_TINY, "decode_lookahead": 3},
        "baseline_engine": {"decode_lookahead": 0},
        "load": _LOAD,
        "faults": [{"point": "scheduler.page_alloc",
                    "spec": "8*raise(MemoryError)"}],
        "invariants": ["exactly_one_terminal", "expected_errors",
                       "streams_match_baseline", "engine_accounting"],
        "expect_stats": {"preemptions": [1, None]},
    },
    {
        "name": "spec-preempt",
        "kind": "engine",
        # the seed is one whose greedy continuations repeat inside their 16
        # tokens, so that a proposer has drafts (most seeds' do not, and
        # expect_stats then fails the scenario as vacuous)
        "seed": 116,
        # batched speculative decoding (k=3 draft spans through the ragged
        # dispatch) under a 3-deep lookahead ring, on a tiny repetitive
        # alphabet that arms the ngram proposers. The armed MemoryErrors
        # land mid-run on page-chain growth — 8 of them, as in ring-preempt:
        # ring and draft extensions absorb a hit without preempting, the
        # capacity sweep of the next synchronous round preempts a
        # speculating stream to host — and every plain-round readback drain
        # is delayed while ring chunks are in flight. Resume must continue
        # bit-identical to the k=0 UNFAULTED synchronous baseline
        # (speculation + ring + preemption change speed, never text), with
        # exactly one terminal per stream and zero slot/page-ref/orphan
        # leaks; the fingerprint is seed-stable.
        "engine": {**_TINY, "scheduler_spec_k": 3, "decode_lookahead": 3},
        "baseline_engine": {"scheduler_spec_k": 0, "decode_lookahead": 0},
        "load": {**_LOAD, "max_tokens": 16, "vocab": [3, 8]},
        "faults": [
            {"point": "scheduler.page_alloc",
             "spec": {"kind": "raise", "exc": "MemoryError",
                      "mode": "once", "n": 8, "after": 6}},
            {"point": "scheduler.readback", "spec": "delay(0.02)"},
        ],
        "invariants": ["exactly_one_terminal", "expected_errors",
                       "streams_match_baseline", "engine_accounting"],
        "expect_stats": {"preemptions": [1, None],
                         "speculative.rounds": [1, None]},
    },
    {
        "name": "resume-crash",
        "kind": "engine",
        "seed": 105,
        "engine": _TINY,
        "load": _LOAD,
        # first force a preemption, then crash the resume: the engine breaks
        # mid-recovery and every stream (parked ones included) must still
        # get exactly one terminal event
        "faults": [{"point": "scheduler.page_alloc",
                    "spec": "1*raise(MemoryError)"},
                   {"point": "scheduler.resume", "spec": "1*raise"}],
        "invariants": ["exactly_one_terminal"],
        "expect_stats": {"preemptions": [1, None]},
        "deterministic_tokens": False,
    },
    # ---- end-to-end cancellation & deadlines --------------------------
    {
        # cancel 8 of 16 mid-decode streams (each victim's cancel fires from
        # its own emit callback after 4 tokens — scheduler-thread
        # deterministic): survivors bit-identical to the uncancelled
        # baseline, exactly one terminal per stream (victims: 'cancelled'),
        # zero slot/page-ref/orphan leaks, and real decode budget reclaimed
        "name": "cancel-storm",
        "kind": "cancel_storm",
        "seed": 110,
        "engine": {**_TINY, "max_batch": 16, "prefix_cache_pages": 80},
        "load": {"requests": 16, "prompt_len": [4, 10], "max_tokens": 24},
        "cancel": [1, 3, 5, 7, 9, 11, 13, 15],
        "cancel_after_tokens": 4,
        "invariants": ["exactly_one_terminal", "expected_errors",
                       "streams_match_baseline", "engine_accounting",
                       "cancelled_terminals"],
    },
    {
        # both slots pinned by long streams behind an armed readback delay;
        # laggards with 150 ms deadlines pile up in the queue and must LAPSE
        # there — 'deadline' terminal, zero tokens, timeline shows
        # enqueued → deadline_exceeded with no 'admitted' in between —
        # while the runners finish bit-identically to the unfaulted baseline
        "name": "deadline-under-load",
        "kind": "deadline",
        "seed": 111,
        "engine": _TINY,
        "load": {"requests": 2, "prompt_len": [4, 10], "max_tokens": 24},
        "laggards": 4,
        "deadline_ms": 150,
        "faults": [{"point": "scheduler.readback", "spec": "delay(0.15)"}],
        "invariants": ["exactly_one_terminal", "expected_errors",
                       "streams_match_baseline", "engine_accounting",
                       "cancelled_terminals"],
    },
    # ---- runtime / replica pool ---------------------------------------
    {
        "name": "replica-failover",
        "kind": "pool",
        "seed": 201,
        "replicas": 2,
        "engine": _TINY,
        "load": {**_LOAD, "max_tokens": 12},
        # one replica dies at its 2nd readback; its in-flight requests fail
        # over mid-stream and the continuation (greedy) must reproduce the
        # single-engine baseline token-for-token
        "faults": [{"point": "scheduler.readback",
                    "spec": {"kind": "raise", "mode": "once", "after": 1}}],
        "invariants": ["exactly_one_terminal", "expected_errors",
                       "streams_match_baseline", "pool_clean"],
        "expect_stats": {"failovers": [1, None], "healthy": [1, 1]},
    },
    {
        "name": "pool-submit-reject",
        "kind": "pool",
        "seed": 202,
        "replicas": 2,
        "engine": _TINY,
        "load": _LOAD,
        "faults": [{"point": "replicas.submit", "spec": "1*raise"}],
        # the rejected request never enters the pool (caller sees the raise,
        # no tracking record leaks); the rest stream normally
        "invariants": ["exactly_one_terminal", "streams_match_baseline",
                       "pool_clean"],
        "expect_error": [0],
        "expect_submit_errors": 1,
    },
    {
        "name": "failover-denied",
        "kind": "pool",
        "seed": 203,
        "replicas": 2,
        "engine": _TINY,
        "load": _LOAD,
        # every readback dies AND the failover path itself faults: requests
        # must surface clean errors (no hang, no double terminal)
        "faults": [{"point": "scheduler.readback", "spec": "raise"},
                   {"point": "replicas.failover", "spec": "raise"}],
        "invariants": ["exactly_one_terminal", "pool_clean"],
        "expect_error": [0, 1, 2, 3],
        "expect_stats": {"failovers_failed": [1, None]},
        "deterministic_tokens": False,
    },
    # ---- prefill/decode disaggregation (runtime/pd.py) ----------------
    {
        # a prefill-role replica breaks mid-handoff (the armed
        # scheduler.handoff raise fires at the KV export, right before the
        # page copy): every stream it carried error-terminates into the
        # pool's failover, RE-prefills prompt+emitted on the surviving
        # prefill replica, and hands off to the decode replica for real —
        # each stream bit-identical to the unified single-engine baseline,
        # exactly one terminal, zero slot/page/tracking leaks on every
        # live replica (the corpse is exempt; its pool died whole)
        "name": "pd-handoff-crash",
        "kind": "pd_pool",
        "seed": 210,
        "prefill_replicas": 2,
        "decode_replicas": 1,
        "engine": _TINY,
        "load": {**_LOAD, "max_tokens": 12},
        "faults": [{"point": "scheduler.handoff", "spec": "1*raise"}],
        "invariants": ["exactly_one_terminal", "expected_errors",
                       "streams_match_baseline", "pool_clean",
                       "pool_engine_accounting"],
        "expect_stats": {"failovers": [1, None], "healthy": [2, 2],
                         "pd.handoffs": [1, None]},
    },
    # ---- replica lifecycle (runtime/lifecycle.py) ---------------------
    {
        # the self-healing acceptance cycle, crash-loop leg: a mid-stream
        # break fails streams over to the survivor (bit-identical); the
        # supervisor's rebuilds keep failing (armed replicas.rebuild), so
        # strikes walk through exponential backoff to BENCHED; disarm +
        # operator restart rebuilds for real, a probation canary promotes,
        # and the pool returns to healthy == replicas with zero
        # slot/page/tracking leaks — no process restart anywhere
        "name": "replica-crash-loop",
        "kind": "replica_crash_loop",
        "seed": 207,
        "replicas": 2,
        "max_strikes": 2,
        "engine": _TINY,
        "load": {**_LOAD, "max_tokens": 12},
        "faults": [{"point": "scheduler.readback",
                    "spec": {"kind": "raise", "mode": "once", "after": 1}},
                   {"point": "replicas.rebuild", "spec": "raise"}],
        "invariants": ["exactly_one_terminal", "expected_errors",
                       "streams_match_baseline", "pool_clean",
                       "pool_engine_accounting"],
    },
    {
        # graceful-drain leg: drain a replica WHILE its streams run. New
        # admissions route around it at once; past the tiny deadline the
        # engine closes and stragglers fail over mid-stream — every stream
        # bit-identical to the undrained baseline, the drain episode
        # visible in the flight recorder (drain_begin → drain_end), and a
        # restart + canary returns the pool to full capacity
        "name": "drain-under-load",
        "kind": "replica_drain",
        "seed": 208,
        "replicas": 2,
        "deadline_s": 0.05,
        "drain_after_s": 0.2,
        "engine": _TINY,
        "load": {**_LOAD, "max_tokens": 16},
        # the per-readback delay stretches every stream so the drain
        # reliably lands mid-flight; greedy tokens are latency-invariant
        "faults": [{"point": "scheduler.readback", "spec": "delay(0.05)"}],
        "invariants": ["exactly_one_terminal", "expected_errors",
                       "streams_match_baseline", "pool_clean",
                       "pool_engine_accounting"],
    },
    # ---- modkit -------------------------------------------------------
    {
        "name": "http-retry-storm",
        "kind": "http_retry",
        "seed": 301,
        # first attempt dies in transport; the retry layer (budget-guarded)
        # must recover and the upstream must see exactly one request
        "faults": [{"point": "http_client.request",
                    "spec": "1*raise(ClientError)"}],
        "expect_injected": 1,
    },
    {
        "name": "db-commit-fault",
        "kind": "db_commit",
        "seed": 302,
        "faults": [{"point": "db_engine.commit", "spec": "1*raise"}],
    },
    # ---- gateway + modules over the live REST surface -----------------
    {
        "name": "oagw-breaker-recovery",
        "kind": "server_breaker",
        "seed": 401,
        "fault_spec": "2*raise(ClientError)",
    },
    {
        "name": "gateway-request-fault",
        "kind": "server_gateway",
        "seed": 402,
    },
    {
        "name": "serverless-retry-deadletter",
        "kind": "serverless",
        "seed": 403,
    },
    {
        "name": "worker-job-crash",
        "kind": "worker",
        "seed": 404,
    },
    {
        "name": "grpc-evict-tick",
        "kind": "grpc_evict",
        "seed": 405,
    },
    # ---- cross-host federation (runtime/federation.py) -----------------
    {
        # two REAL worker subprocesses over loopback gRPC: an armed
        # federation.route raise rejects one request as a typed 503 before
        # any host is dialed; a repeated-prefix request lands on the host
        # already holding the prefix (gossiped digest chains); SIGKILLing
        # the serving host mid-stream fails over to the survivor with the
        # delivered text bit-identical to an in-process baseline and
        # exactly one terminal; the corpse leaves the registry within one
        # lease window (lost host = lost capacity)
        "name": "worker-host-crash",
        "kind": "worker_host_crash",
        "seed": 406,
        "lease_ttl_s": 2.0,
        "load": {"max_tokens": 16},
        "faults": [{"point": "federation.route", "spec": "1*raise"}],
    },
    {
        # fabric-fleetscope: two REAL loopback worker hosts behind one
        # gateway; a readback delay armed over REST onto worker-0 ONLY
        # (PUT body {"host": ...} forwarded over the observability wire)
        # burns that host's itl objective in ITS process; the heartbeat
        # payload walks the gateway's FleetDoctor to degraded/shedding,
        # GET /v1/monitoring/fleet marks the host, new requests provably
        # steer to the healthy survivor (placement reason "health"),
        # streams stay bit-identical to the unfaulted run, and disarming
        # walks the host back to healthy within the recovery hysteresis
        "name": "fleet-doctor-shed",
        "kind": "fleet_doctor_shed",
        "seed": 407,
        "lease_ttl_s": 4.0,
        # delay(0.4) per decode_chunk-2 readback ≈ 200ms/token mean itl —
        # far over the 60ms objective; ambient CPU mean itl sits well under
        "delay_spec": "delay(0.4)",
        "itl_threshold_ms": 60.0,
        "load": {"max_tokens": 8},
    },
    # ---- tenant isolation (weighted-fair queue + selective shedding) ---
    {
        # one tenant floods 32 requests while a light tenant sends 4: the
        # weighted-fair queue admits every light request while most of the
        # heavy backlog still waits (FIFO would starve it behind all 32),
        # the light tenant's queue wait stays bounded, weight-normalized
        # token shares converge by the light tenant's completion, every
        # stream is bit-identical to its tenant's solo run, zero leaks
        "name": "noisy-neighbor",
        "kind": "noisy_neighbor",
        "seed": 601,
        "engine": _TINY,
        "heavy_requests": 32,
        "light_requests": 4,
        "load": {"prompt_len": [4, 10], "max_tokens": 8},
        "invariants": ["exactly_one_terminal", "streams_match_baseline",
                       "engine_accounting"],
    },
    {
        # a readback delay (armed over REST) burns the itl objective while
        # the heavy tenant floods a REAL two-tenant stack: the doctor
        # attributes the burn per tenant and the gateway sheds ONLY the
        # over-fair-share tenant (429 tenant_shed + Retry-After) while the
        # light tenant keeps serving baseline-identical text; /readyz
        # stays 200 (global shedding is the last resort) and the abuser
        # recovers once the burn drains
        "name": "selective-shed",
        "kind": "selective_shed",
        "seed": 602,
        "delay_spec": "delay(0.4)",
        "itl_threshold_ms": 30.0,
        "heavy_requests": 16,
    },
    # ---- fabric-doctor (SLO engine + watchdogs + degradation machine) --
    {
        # delay on every decode readback (armed over the guarded REST
        # control plane against a REAL gateway+llm stack) blows the itl
        # burn rate: /readyz flips 200→503→200 through the full healthy →
        # degraded → shedding → recovering → healthy cycle, shedding 429s
        # NEW requests pre-enqueue (Retry-After), and streams already in
        # flight finish bit-identically to the unfaulted baseline
        "name": "slo-burn-shed-recover",
        "kind": "slo_burn",
        "seed": 501,
        "delay_spec": "delay(0.5)",   # ≈62 ms/token ≫ the 30 ms objective
        "itl_threshold_ms": 30.0,
    },
    {
        # same seed/engine/load as admit-delay so the cached unfaulted
        # baseline is shared; a 0.35 s delay per readback makes every round
        # glacial without changing a token — all three stall watchdogs
        # (scheduler_round / stream_stall / queue_age) must trip, stalled
        # streams must be marked in the flight recorder's live table, and
        # the state machine must walk back to healthy after the drain
        "name": "stream-stall-watchdog",
        "kind": "stall",
        "seed": 103,
        "engine": _TINY,
        "load": _LOAD,
        "faults": [{"point": "scheduler.readback", "spec": "delay(0.35)"}],
        "invariants": ["exactly_one_terminal", "streams_match_baseline",
                       "engine_accounting", "state_sequence",
                       "watchdogs_tripped"],
        "expect_watchdogs": ["scheduler_round", "stream_stall", "queue_age"],
        "expect_state_sequence": ["healthy", "degraded", "healthy"],
    },
]


def scenario_by_name(name: str) -> dict[str, Any]:
    for spec in BUILTIN_SCENARIOS:
        if spec["name"] == name:
            return spec
    raise KeyError(f"unknown scenario {name!r}; builtin: "
                   f"{[s['name'] for s in BUILTIN_SCENARIOS]}")


def load_scenario_file(path: str | Path) -> list[dict[str, Any]]:
    """Load scenarios from a YAML (or JSON — valid YAML) file with a
    top-level ``scenarios:`` list."""
    import yaml

    doc = yaml.safe_load(Path(path).read_text())
    scenarios = doc.get("scenarios") if isinstance(doc, dict) else doc
    if not isinstance(scenarios, list):
        raise ValueError(f"{path}: expected a top-level 'scenarios:' list")
    return scenarios


def covered_points(specs: list[dict[str, Any]] | None = None) -> set[str]:
    """Failpoint names exercised by the given (default: builtin) scenarios.
    tests/test_faultlab.py asserts this covers the whole catalog."""
    specs = BUILTIN_SCENARIOS if specs is None else specs
    out: set[str] = set()
    for spec in specs:
        for fault in spec.get("faults", []):
            out.add(fault["point"])
        if spec.get("kind") == "server_breaker":
            out.add("oagw.upstream")
        if spec.get("kind") == "server_gateway":
            out.add("gateway.request")
        if spec.get("kind") == "serverless":
            out.update({"serverless.invoke", "serverless.tick"})
        if spec.get("kind") == "worker":
            out.add("llm_gateway.worker_stream")
        if spec.get("kind") == "grpc_evict":
            out.add("grpc_hub.evict")
        if spec.get("kind") == "slo_burn":
            out.add("scheduler.readback")  # armed over REST, not via faults
        if spec.get("kind") == "fleet_doctor_shed":
            # armed over REST with {"host": ...}, fired in the worker process
            out.add("scheduler.readback")
    return out
