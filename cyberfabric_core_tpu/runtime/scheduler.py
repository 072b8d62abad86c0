"""Continuous batching scheduler — slot-based admission over a persistent KV pool.

BASELINE config #2 ("64 concurrent /v1/chat/completions streams") is served by this
scheduler: requests are admitted into free slots of a device-resident KV pool
mid-flight, decode runs lockstep chunks across ALL active slots, finished slots
free immediately for the next waiting request: a long generation never blocks
a short one.

Device programs (``runtime/programs.py``; jitted, the page pools donated):
- mixed_step:         every decode row's next token and one prefilling slot's
  prompt chunk in one pass over the weights; a prompt is only ever computed
  as such chunks, admitted into a slot in PREFILL phase with no device work
- paged_decode_chunk: k fused steps over all slots (inactive slots compute
  garbage that is masked host-side — the static shape is the price of zero
  recompiles)

The decode loop is PIPELINED: host work and device work overlap instead of
alternating.

- N-deep lookahead (the epoch ring): up to ``decode_lookahead`` chunks are
  kept in flight beyond the one being drained, each chained off the previous
  chunk's device-resident outputs (last_tokens / keys / pools / page table /
  lengths / finished mask) — the host emit loop runs while the device works
  N chunks ahead. The chunks in flight are tokens the running rows are
  owed, so what can wait for them does: ADMISSION AND RESUME WAIT until the
  ring is empty (``_admit``; the ring stops deepening the moment a slot is
  free and a request is pending or suspended, ``_can_extend_ring``, so that
  is at most ``decode_lookahead`` further drains, each emitting its tokens
  to every running row), then the prompt's chunk runs as the lane of a
  ``mixed_step`` and the ring is rebuilt off that dispatch. What cannot
  wait and can be replayed still DISCARDS: a preemption or a host-detected
  stop bumps ``_epoch`` and the stale suffix of the ring is dropped
  (``_discard_ring``), as is a ring no running row is left to drain; the
  fallback synchronous round recomputes from committed state, so emitted
  streams are byte-identical at any depth. (Discarded chunks are harmless:
  their KV writes land past every committed length and are either
  rewritten identically or masked by attention-length bounds; pages they
  touched of freed slots are fully rescattered by the next owner.) A model
  with recurrent state cannot replay a chunk (it has advanced the state),
  so there a stale ring only ever drains: the one place the ring's rules
  ask what kind of model is served.
- The emit behind the next launch: a drain that leaves NOTHING undrained
  (the ring's last chunk ahead of an arrival, a prompt's chunk that is not
  its last, every round without lookahead) commits as any other and HOLDS
  its emit; the loop admits, plans, uploads and launches what comes next,
  and flushes the held token events right behind that launch, under the
  device's work (``_close_round``, ``_flush_held_emit``). The same
  speculation as the ring's, for the one launch that used to wait. A
  ``mixed_step`` launched ahead of a held emit is never discarded, and
  whatever reads or ends a stream on the host flushes first.
- A prompt's next chunk behind the one in flight: a mixed round is a launch
  half and a drain half (``_dispatch_mixed``, ``_commit_mixed``), and where
  the host knows what the next ``mixed_step`` computes before this one's
  tokens are read (a plain step advances every running row by one; the
  lane is the prompt's ids) the next step is launched off this one's
  device outputs BEFORE this one is drained, as a decode chunk chains off
  the ring's tail (``_chains_mixed``). Steps that carry a prompt's chunks
  follow one another on the device with no host work between them; the
  next round starts at the chained step's drain half.
- Device-side termination: stop-token matching (per-slot padded stop-id
  rows), the max-tokens bound and the window bound are evaluated INSIDE the
  decode program against a device-resident ``finished`` mask — a finished
  row freezes on-device (no further length/key/KV advance), so an in-flight
  ring SURVIVES finishes instead of being discarded; host readback exists
  only to emit tokens. Requests whose stop set exceeds
  ``device_stop_width`` fall back to host-side stop detection (their stop
  finishes bump the epoch, the pre-device-termination behavior).
- Async double-buffered readback: every dispatched chunk starts a
  non-blocking device→host transfer immediately
  (``copy_to_host_async``), and the round's single sanctioned sync point
  drains the OLDEST chunk — by then its transfer has typically landed, so
  the blocking wait collapses (``readback_wait_ms_p50`` in stats(): the
  records' ``sync_wait_ms``).
- Prefill admission budget: ``prefill_budget_tokens`` caps prompt tokens
  admitted per round (Sarathi-style interleave) so an arrival burst no longer
  stalls every in-flight decode behind an unbounded prefill drain. When the
  prefill queue DRAINS inside a mixed round, the ring spans the transition:
  decode chunks chain directly off the mixed dispatch's outputs (the flip
  state — active mask, first tokens, lengths — is computed on device), so
  mixed→pure-decode needs no synchronous fallback round.
- A slot's rows, by who writes them: what the programs only read (the page
  table, temp/top_p/top_k, stop ids, limits, the active mask) the HOST owns
  as numpy arrays and uploads whole, when changed, with the next dispatch;
  what the device advances (last token, key, length, finished) only the
  step programs write (a flipping row inside ``mixed_step``). No program
  is dispatched for a row at an arrival, a flip or a finish.
- Tenant isolation: the pending queue is PER-TENANT FIFO deques drained by
  token-weighted fair scheduling (``TenantFairQueue`` — a VTC-style virtual
  counter per tenant, charged with the prefill + decode tokens actually
  consumed; the backlogged tenant with the smallest weighted counter wins
  admission). Per-tenant caps are enforced at round boundaries: a tenant at
  ``tenant_max_slots`` or holding its ``tenant_max_pages`` hard quota is
  skipped by admission (its requests stay queued, nobody waits behind
  them); ``tenant_soft_pages`` overshoot under contention marks the
  tenant's youngest slot for a preempt-to-host yield (the sweep is pure
  bookkeeping — the device work runs in the capacity pass, where
  preemption already lives); ``tenant_max_pending`` overflow raises its own
  429. Fairness reorders ADMISSION only — per-request token streams are
  byte-identical to the tenant-blind scheduler.
- End-to-end cancellation & deadlines: ``cancel(request_id, reason)`` is
  thread-safe and applied at the next round boundary in EVERY phase
  (pending-queue removal pre-admit, mid-chunked-prefill abort, mid-decode
  row deactivation, suspended drop), and a per-round expiry sweep lapses
  requests whose ``deadline`` passed (``deadline_exceeded``; a queued
  request whose remaining budget cannot cover its estimated prefill is
  never admitted). A mid-decode cancel freezes the row (off the active
  mask, page-table row zeroed so later dispatches park its KV writes
  on scratch) WITHOUT bumping the epoch — the lookahead ring drains through
  the cancel instead of discarding, so surviving streams lose nothing.

The one sanctioned host<-device sync of the decode loop is the oldest-chunk
drain (fabric-lint AS04 enforces this — non-blocking transfer starts are
allowed anywhere in the hot loop, blocking reads only at the single
``sync-point:`` marker per round method).

The reference's analogue is request-level tokio concurrency + per-route in-flight
semaphores (SURVEY §2.6); there is no model-execution scheduler to mirror, so this
is TPU-first design: static shapes, bucketed prefill, donation, one dispatch per
chunk.
"""

from __future__ import annotations

import logging
import math
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import decoder_module
from ..models.configs import ModelConfig, get_config
from ..modkit.concurrency import locked_snapshot
from ..modkit.failpoints import failpoint, record_recovery
from ..modkit.flight_recorder import record_event
from ..modkit.metrics import bump_counter
from ..modkit.telemetry import (get_global_tracer, reset_log_context,
                                set_log_context, startup, traceparent_ids)
from ..ops.sampling import host_key, host_split
from .engine import (EngineConfig, SamplingParams, SchedulerSaturated,
                     StepEvent, TenantQuotaExceeded, TenantSaturated)
from .programs import (_CTL, _LANE_COLS, LANE_ROWS, ProgramKey,
                       step_programs)
from .speculative import NgramProposer

logger = logging.getLogger("scheduler")

#: /metrics of a model that generates by blocks: a running row's part in one
#: forward, those of them that were commit forwards, the blocks and tokens
#: the host took from them
_BLOCK_SERIES = ("llm_block_row_forwards_total",
                 "llm_block_commit_row_forwards_total",
                 "llm_blocks_committed_total",
                 "llm_block_tokens_emitted_total")


#: /metrics of a model whose forwards count their expert layers, by the names
#: of its module's ``STEP_COUNTERS``: the assignments routed (tokens x experts a
#: token, over layers and forwards), those that fell on experts held here
#: (experts held over experts routed of them where routing is uniform), the
#: held experts that received a token, the expert layers that ran over the
#: compacted list of a share's assignments (``models/llama.py: moe_experts``)
#: and the expert layers run, and the held assignments of decode steps alone
#: (nemotron_h: over the decode-only touched, the rows a touched expert
#: has), and the rows ONE grouped matmul of an expert layer multiplied (its
#: work items x the row tile ``ops/grouped_matmul.py: row_tile`` picked: over
#: the experts touched, the rows the MXU is fed for an expert). Beside them
#: the held experts offered, and
#: touched and offered once more over the forwards of decode chunks alone: a
#: mixed step's prompt chunk touches nearly every expert, a decode step's
#: rows do not
_MOE_SERIES_OF = {"assignments": "llm_moe_assignments_total",
                  "local": "llm_moe_assignments_local_total",
                  "touched": "llm_moe_experts_touched_total",
                  "compact": "llm_moe_layer_forwards_compact_total",
                  "forwards": "llm_moe_layer_forwards_total",
                  "decode_local": "llm_moe_decode_assignments_local_total",
                  "item_rows": "llm_moe_item_rows_total",
                  # a model whose queries attend over a chosen set: the keys
                  # its index passes scored (a query's visible keys), the
                  # keys attended, the queries and those of them that saw
                  # more than ``index_topk`` keys, over layers and forwards
                  "keys_scored": "llm_dsa_keys_scored_total",
                  "keys_selected": "llm_dsa_keys_selected_total",
                  "queries": "llm_dsa_queries_total",
                  "queries_binding": "llm_dsa_queries_binding_total"}
_MOE_DRAIN_SERIES = ("llm_moe_experts_offered_total",
                     "llm_moe_decode_experts_touched_total",
                     "llm_moe_decode_experts_offered_total")
#: and of decode chunks' forwards alone, as the decode-only experts are kept
#: apart: the keys scored and attended, the queries and those the selection
#: bound, and the index passes (steps x layers), so that a reader can price
#: ONE call of a decode step
_DSA_DECODE_SERIES = {
    "keys_scored": "llm_dsa_decode_keys_scored_total",
    "keys_selected": "llm_dsa_decode_keys_selected_total",
    "queries": "llm_dsa_decode_queries_total",
    "queries_binding": "llm_dsa_decode_queries_binding_total"}
_DSA_DECODE_CALLS = "llm_dsa_decode_calls_total"


def _moe_series(counters: tuple) -> tuple:
    """The /metrics series of a model whose module has these
    ``STEP_COUNTERS`` (none for none)."""
    if not counters:
        return ()
    return (tuple(_MOE_SERIES_OF[n] for n in counters) + _MOE_DRAIN_SERIES
            + ((*_DSA_DECODE_SERIES.values(), _DSA_DECODE_CALLS)
               if "keys_scored" in counters else ()))


#: /metrics of a model whose stack runs several times a token
#: (``ModelConfig.loop_steps``; ``models/ouro.py``): the forwards drained and
#: the passes of the stack they ran (their ratio is the depth a token paid
#: for: ``loop_steps`` while every pass runs), and what the exit gate says of
#: the decode rows that ran, the pass at which it WOULD have let each out
#: summed (``STEP_COUNTERS``: thousandths of a pass on the drained matrix)
#: over those rows
_LOOP_SERIES = ("llm_loop_forwards_total", "llm_loop_passes_total",
                "llm_loop_exit_pass_sum_total", "llm_loop_exit_rows_total")


def _null_ctx():
    import contextlib

    return contextlib.nullcontext()


@dataclass
class _SlotState:
    request_id: str
    emit: Callable[[StepEvent], None]  # called from the scheduler thread
    sampling: SamplingParams
    stops: frozenset[int]
    emitted: int = 0
    request_index: int = 0  # external correlation id
    chain: Optional[list[int]] = None  # page ids held by this slot
    #: the slot's pages of the WINDOW page group (a model with window
    #: layers; runtime/paged.py), by the chain's logical index, 0 where the
    #: row has given a page back; None where the model has one group
    wchain: Optional[list[int]] = None
    #: W3C traceparent the gateway propagated through submit; trace_sampled is
    #: parsed ONCE at submission — the decode hot loop's span guard is a
    #: single bool check (the disarmed-failpoint pattern), so an unsampled
    #: trace costs ~nothing per chunk
    trace: Optional[str] = None
    trace_sampled: bool = False
    #: mixed-batch chunked prefill: a slot is admitted in
    #: "prefill" phase with NO device work done yet — its prompt is consumed
    #: chunk-by-chunk inside decode rounds (the ragged dispatch) and the slot
    #: flips to "decode" when the last chunk lands. ``prefill_key`` holds the
    #: request's untouched PRNG key until the final chunk samples the first
    #: token (so intervening decode rounds can't advance its stream).
    phase: str = "decode"
    prompt_ids: Optional[list[int]] = None
    prefill_pos: int = 0
    cached_len: int = 0
    prefill_key: Any = None
    prefill_chunks: int = 0
    #: a model with recurrent state: ``(tokens, snapshot row)`` taken where a
    #: mixed call ended on a snapshot boundary of this prompt; handed to the
    #: pool at commit, given back if the prompt leaves its slot before
    state_snapshots: list = field(default_factory=list)
    prefill_t0: float = 0.0
    prefill_wall: float = 0.0
    #: absolute monotonic deadline (None = unbounded): the per-round expiry
    #: sweep lapses the request with ``deadline_exceeded`` once passed —
    #: a dead SSE consumer or a blown client budget stops burning decode
    #: rounds instead of running to max_tokens
    deadline: Optional[float] = None
    #: owning tenant (SecurityContext.tenant_id threaded through the
    #: gateway/worker): decode tokens are charged to its virtual counter,
    #: per-tenant caps count this slot, and the cap sweep can yield it
    tenant: str = "default"
    #: batched speculative decoding (scheduler_spec_k > 0): the
    #: per-stream prompt-lookup proposer, fed every emitted token from
    #: _emit_token. Armed at decode activation only for ELIGIBLE requests —
    #: temperature 0 (verification is argmax equality: lossless) whose token
    #: limit fires before the window bound ever could, so window-bound
    #: streams keep the exact k=0 chunk-boundary "length" semantics by never
    #: speculating. None = this stream never proposes (also the
    #: spec_min_accept adaptive gate's sticky off state).
    proposer: Any = None
    #: rolling acceptance evidence for the spec_min_accept gate
    spec_proposed: int = 0
    spec_accepted: int = 0


@dataclass
class _Pending:
    request_id: str
    prompt_ids: list[int]
    sampling: SamplingParams
    emit: Callable[[StepEvent], None]
    enqueued_at: float = field(default_factory=time.monotonic)
    #: per-request PRNG key, assigned at TAKE time in FIFO order
    key: Any = None
    trace: Optional[str] = None  # W3C traceparent from the gateway span
    #: absolute monotonic deadline (None = unbounded); a pending entry whose
    #: deadline passes — or whose remaining budget cannot even cover the
    #: estimated prefill — lapses in the queue and NEVER occupies a slot
    deadline: Optional[float] = None
    #: owning tenant: FIFO within this tenant's queue, weighted-fair across
    #: tenants (TenantFairQueue)
    tenant: str = "default"


@dataclass
class _Suspended:
    """A preempted request: its KV pages live on HOST until pool space frees.
    Resume restores the pages and continues decoding — no recompute, the
    client stream just pauses (checkpoint/resume for in-flight requests)."""

    state: _SlotState
    host_kv: tuple  # (k, v) numpy [L, n_pages, page, Hkv, D]
    length: int  # decode: valid kv length; prefill phase: prefill_pos
    #: meaningless for a prefill-phase suspend (no sample yet); a model that
    #: generates by blocks parks its open block here, [block_length] tokens
    last_token: Any
    slot_key: Any  # per-slot RNG key (None for prefill phase: key untouched)
    #: True when the preemption was a tenant soft-quota YIELD (not pool
    #: pressure): resume defers this record while another tenant still has
    #: pending work — restoring it immediately would hand the freed slot
    #: straight back to the over-quota tenant (suspended requests outrank
    #: admissions) and preempt/restore-thrash without ever serving the
    #: starved tenant
    soft_yielded: bool = False
    suspended_at: float = field(default_factory=time.monotonic)
    #: wall-clock twin of suspended_at: the llm.preempt span emitted at
    #: resume is backdated to this (OTLP timestamps are unix-epoch ns)
    suspended_wall: float = field(default_factory=time.time)
    #: PD disaggregation: True when this record is a cross-engine KV handoff
    #: (prefill-role engine → decode-role engine) rather than a local
    #: preemption. The decode branch of _resume_suspended admits it through
    #: the same restore path but records a ``handoff_import`` event instead
    #: of ``resumed`` and keeps it out of the preemption/recovery stats.
    handoff: bool = False


@dataclass
class _InflightChunk:
    """A dispatched-but-unread decode chunk (one entry of the lookahead
    ring).

    ``epoch`` is the scheduler state epoch at dispatch; a preemption (or a
    host-side stop the device could not see) bumps the engine epoch,
    invalidating this chunk and every ring entry after it — their tokens
    are discarded and a synchronous round recomputes from committed state.
    An admission or a resume never finds this chunk in flight: they wait
    for the ring to empty. Device-predicted finishes (stop match inside the
    device stop width, max-tokens, window) do NOT bump: the finished row is
    frozen on-device, so the ring stays valid. The device outputs here are
    FUTURES: nothing blocks until the oldest-chunk drain (the D2H transfer
    is started non-blocking at dispatch)."""

    chunk_dev: Any        # [N, k] int32 tokens (-1 for frozen-row steps)
    last: Any             # [N] last tokens after the chunk (frozen rows keep)
    keys: Any             # [N, 2] per-slot key streams after the chunk
    lengths_dev: Any      # [N] lengths after the chunk (inactive rows pinned 0)
    finished_dev: Any     # [N] bool device-side finished mask after the chunk
    active_dev: Any       # [N] bool active mask this chunk was dispatched with
    #                       (chained dispatches reuse it; NEVER committed —
    #                       host finish deactivations must not be undone)
    epoch: int


@dataclass
class _HeldEmit:
    """The emit of a round whose drain left NOTHING UNDRAINED, held back so
    that it runs behind the next launch instead of in front of it (the device
    would wait out every token event of it). The round is committed: the
    device handles, the host's length mirror, the counters and what the next
    plan reads (``prefill_pos``, a flip) are as after any drain. What waits
    is what a stream sees: token events, flight-recorder events, tenant
    charges, finishes (a slot freed by one becomes free at the flush) and
    the round's record. At most one is held, and never past the next drain:
    ``_flush_held_emit``."""

    #: the round's emit over what it drained (its tokens, ``old_lengths``,
    #: depth and plan are the closure's); returns a block model's
    #: (blocks, tokens) for the record
    emit: Callable[[], Optional[tuple[int, int]]]
    #: ``_record_round``'s arguments
    record: dict[str, Any]
    #: the pass's phases, taken where it ended (``_PhaseClock.take``): the
    #: flushed emit's time belongs to the pass it runs in
    clock: tuple[dict[str, list[float]], float, float]


@dataclass
class _MixedStep:
    """A launched-but-undrained ``mixed_step``: the LAUNCH half of a mixed
    round (``_dispatch_mixed``), kept until its drain half
    (``_commit_mixed``) has read its tokens. Between the two the host may
    queue what comes next behind it, off its device outputs (``rec``): the
    decode chunks of ``_mixed_ring_span``, or the next prompt chunk's step
    (``chained`` on THAT step). It is never in the ring and never discarded:
    its drain commits it (``_close_round``, rule 1)."""

    toks_dev: Any         # [N] (or [N, spec_w + 1]) tokens, counters appended
    rec: _InflightChunk   # the step's device outputs: what is chained off it
    plan: list            # (slot, state, chunk): the prompt chunks it carries
    finals: list          # (slot, state): the prompts whose last chunk it is
    spec_plan: list       # (slot, state, drafts): an all-rows step's spans
    positions: int        # what the dispatch computes (the round's record)
    t0: float             # the launch half's start, monotonic and wall
    wall0: float
    chained: bool         # launched off a step that was still undrained
    spanned: int = 0      # decode chunks chained off it
    #: what the ragged kernel walks for the lanes' spans
    #: (``_count_ragged_walk``), for the round's record and the chunk's span
    ragged: dict = field(default_factory=dict)

    def carried(self) -> dict[int, int]:
        """slot -> the tokens of its prompt this step carries: what its
        commit will add to ``prefill_pos``."""
        return {slot: chunk for slot, _, chunk in self.plan}


#: what the scheduler thread can be doing: the loop is TILED by these, every
#: instant from one pass's start to the next in exactly one of them
#: (docs/ARCHITECTURE.md, "The scheduler's phases", says what each holds)
PHASES = ("wait", "service", "admit", "capacity", "plan", "upload", "launch",
          "drain", "commit", "emit")
_SPAN_NAMES = {(p, s): f"sched.{p}.starved" if s else f"sched.{p}"
               for p in PHASES for s in (False, True)}


def tree_bytes(tree: Any) -> int:
    """Bytes of a tree's arrays (the weights as they are held)."""
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


class _PhaseClock:
    """The one place the scheduler thread's time is taken. ``to(phase)`` is a
    SWITCH, not a nest: it closes the phase the thread was in and opens the
    next, so the phases tile the thread's time by construction, in three
    forms at once:

    - sums for the next round record: per phase ``[wall, cpu, starved]``
      seconds since the previous record (``take``), wall from
      ``time.monotonic()``, cpu from ``time.thread_time()`` (wall less cpu is
      time in which the thread did not run: a wait for the GIL, or the host
      descheduling it);
    - a ``jax.profiler.TraceAnnotation`` named ``sched.<phase>``, so that a
      trace taken through ``/v1/monitoring/profiler/start`` has the same
      phases on the clock of its device planes (no profiler running: an
      annotation is a branch);
    - the device's wait for the host. ``starved`` is up while nothing this
      scheduler launched is undrained: raised at the return of a drain that
      leaves the ring empty (and at a discard that empties it, the one place
      it can read high: by the device time of the chunks dropped), dropped at
      the return of the next launch. A switch adds the time elapsed under
      the flag to the leaving phase's ``starved`` and to
      ``llm_device_starved_seconds_total{model,phase}``, and names the span
      ``sched.<phase>.starved``. A lower bound of the device's idle time: a
      chunk that ended before its drain was called is seen late, and what
      the device takes to start a launched program is not seen.

    Used by one thread at a time (the scheduler's; a test that steps
    ``_loop_pass`` by hand is that thread)."""

    def __init__(self, model: str) -> None:
        self._model = model
        self.phase = "wait"
        self.starved = True         # nothing launched yet
        self._acc: dict[str, list[float]] = {}
        self._span: Any = None
        self._thread = threading.get_ident()
        self._t = self._pass_t0 = time.monotonic()
        self._cpu = time.thread_time()

    def _stamp(self) -> float:
        """Book the time since the last stamp to the current phase."""
        now, cpu, thread = (time.monotonic(), time.thread_time(),
                            threading.get_ident())
        wall = now - self._t
        acc = self._acc.get(self.phase)
        if acc is None:
            acc = self._acc[self.phase] = [0.0, 0.0, 0.0]
        acc[0] += wall
        if thread == self._thread:  # thread_time is per thread
            acc[1] += cpu - self._cpu
        if self.starved:
            acc[2] += wall
            bump_counter("llm_device_starved_seconds_total", n=wall,
                         model=self._model, phase=self.phase)
        self._t, self._cpu, self._thread = now, cpu, thread
        return now

    def to(self, phase: str, starved: Optional[bool] = None) -> float:
        """Switch to ``phase`` (re-entering the current one is a switch too:
        it is how ``starved`` changes inside a phase). Returns the instant,
        ``time.monotonic()``."""
        now = self._stamp()
        self.phase = phase
        if starved is not None:
            self.starved = starved
        if self._span is not None:
            self._span.__exit__(None, None, None)
        self._span = jax.profiler.TraceAnnotation(
            _SPAN_NAMES[phase, self.starved])
        self._span.__enter__()
        return now

    def take(self) -> tuple[dict[str, list[float]], float, float,
                            Optional[dict[str, float]]]:
        """At a round record: ``phases`` (phase -> [wall_ms, cpu_ms,
        starved_ms], in the order they first ran) and ``pass_ms`` since the
        previous record, whose sum over the phases it is; the instant; and
        what this thread traced, lowered and compiled since the previous
        record (program -> seconds, from the compile ledger's listeners:
        None unless the pass compiled)."""
        now = self._stamp()
        phases = {p: [round(1e3 * wall, 4), round(1e3 * min(cpu, wall), 4),
                      round(1e3 * starved, 4)]
                  for p, (wall, cpu, starved) in self._acc.items()}
        pass_ms = round(1e3 * (now - self._pass_t0), 4)
        self._acc, self._pass_t0 = {}, now
        return phases, pass_ms, now, startup.take_compiled()

    def close(self) -> None:
        """The loop's thread ends: close its open span."""
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None


class TenantFairQueue:
    """Per-tenant FIFO pending queues drained by token-weighted fair
    scheduling (a VTC-style virtual counter per tenant).

    Every tenant owns one FIFO deque; :meth:`pop_fair` serves the backlogged
    tenant with the smallest *virtual counter* — a cumulative count of the
    prefill + decode tokens the tenant actually consumed, divided by its
    configured weight (:meth:`charge`). A tenant that has consumed little
    relative to its entitlement therefore wins admission, which is exactly
    what bounds a light tenant's queue wait under a heavy tenant's flood;
    order *within* a tenant stays strictly FIFO, so single-tenant
    deployments see the exact pre-tenancy admission order.

    New-backlog lift: when a tenant goes from idle to backlogged, its
    counter is lifted to the minimum counter among currently backlogged
    tenants — an idle tenant cannot bank credit and then monopolize the
    engine with a burst (the standard VTC refresh rule).

    ``fair=False`` degrades to one global FIFO (the tenant-blind baseline).

    Threading: ``put``/``remove_if``/``drain_all`` may run on any thread
    (one lock acquire); ``pop_fair`` and ``charge`` run only on the
    scheduler thread. All methods are non-blocking bookkeeping — dict/deque
    work, no sleeps, no device syncs (fabric-lint WD01)."""

    def __init__(self, fair: bool = True) -> None:
        from collections import deque

        self.fair = fair
        self._lock = threading.Lock()
        self._queues: dict[str, "deque[_Pending]"] = {}
        self._count = 0
        #: virtual counters (charged tokens / weight), never reset — the
        #: RELATIVE ordering is what matters, and floats hold ~2^53 tokens
        self._vtc: dict[str, float] = {}
        #: raw cumulative charged tokens per tenant (stats / doctor
        #: attribution — the "actual tokens consumed" figure)
        self._charged: dict[str, int] = {}

    def _key(self, tenant: str) -> str:
        return tenant if self.fair else "default"

    def put(self, req: "_Pending") -> None:
        with self._lock:
            key = self._key(req.tenant)
            q = self._queues.get(key)
            if q is None:
                from collections import deque

                q = self._queues[key] = deque()
            if not q:
                # idle → backlogged: lift the counter to the backlogged
                # minimum so banked idleness cannot become a monopoly
                backlogged = [self._vtc.get(t, 0.0)
                              for t, other in self._queues.items()
                              if other and t != key]
                floor = min(backlogged) if backlogged else None
                if floor is not None:
                    self._vtc[key] = max(self._vtc.get(key, 0.0), floor)
            q.append(req)
            self._count += 1

    def put_front(self, req: "_Pending") -> None:
        """Return a just-popped request to the HEAD of its tenant's queue
        (the defensive no-free-slot requeue paths) — FIFO order within the
        tenant is preserved, unlike a tail re-put."""
        with self._lock:
            key = self._key(req.tenant)
            from collections import deque

            self._queues.setdefault(key, deque()).appendleft(req)
            self._count += 1

    def pop_fair(self, blocked: Optional[set] = None) -> Optional["_Pending"]:
        """The next request by weighted-fair order: smallest virtual counter
        among backlogged tenants not in ``blocked`` (tenants at a slot/page
        cap); ties break on head arrival time, then tenant id, so the order
        is deterministic. Scheduler thread only."""
        with self._lock:
            best_key = None
            best = (0.0, 0.0, "")
            for key, q in self._queues.items():
                if not q or (blocked and key in blocked):
                    continue
                cand = (self._vtc.get(key, 0.0), q[0].enqueued_at, key)
                if best_key is None or cand < best:
                    best_key, best = key, cand
            if best_key is None:
                return None
            self._count -= 1
            return self._queues[best_key].popleft()

    def charge(self, tenant: str, tokens: int, weight: float) -> None:
        """Charge ``tokens`` consumed tokens to ``tenant`` at ``weight``
        (scheduler thread; one uncontended lock acquire + dict math —
        WD01-shaped, and the fairness-guard A/B holds it under the 1%
        bar)."""
        if tokens <= 0:
            return
        key = self._key(tenant)
        with self._lock:
            self._vtc[key] = (self._vtc.get(key, 0.0)
                              + tokens / max(weight, 1e-9))
            self._charged[key] = self._charged.get(key, 0) + tokens

    # ------------------------------------------------------------ reads
    def qsize(self) -> int:
        return self._count

    def empty(self) -> bool:
        return self._count == 0

    def tenant_depth(self, tenant: str) -> int:
        with self._lock:
            q = self._queues.get(self._key(tenant))
            return len(q) if q else 0

    def depths(self) -> dict[str, int]:
        with self._lock:
            return {t: len(q) for t, q in self._queues.items() if q}

    def snapshot(self) -> list["_Pending"]:
        """Advisory copy of every pending request (cancel/expiry scans)."""
        with self._lock:
            return [req for q in self._queues.values() for req in q]

    def oldest_age(self) -> Optional[float]:
        """Age of the oldest pending request across all tenants (the
        doctor's queue-age watchdog input)."""
        with self._lock:
            heads = [q[0].enqueued_at for q in self._queues.values() if q]
        if not heads:
            return None
        return time.monotonic() - min(heads)

    def remove_if(self, pred) -> list["_Pending"]:
        """Remove-and-return every pending request matching ``pred``; FIFO
        order of survivors is untouched (no drain-and-requeue)."""
        removed: list["_Pending"] = []
        with self._lock:
            for key, q in self._queues.items():
                if not q or not any(pred(r) for r in q):
                    continue
                kept = [r for r in q if not pred(r)]
                removed.extend(r for r in q if pred(r))
                q.clear()
                q.extend(kept)
            self._count -= len(removed)
        return removed

    def drain_all(self) -> list["_Pending"]:
        """Pop everything (teardown); callers emit terminals outside any
        engine lock."""
        with self._lock:
            out = [req for q in self._queues.values() for req in q]
            for q in self._queues.values():
                q.clear()
            self._count = 0
        return out

    def vtc_snapshot(self) -> dict[str, float]:
        with self._lock:
            return dict(self._vtc)

    def charged_snapshot(self) -> dict[str, int]:
        with self._lock:
            return dict(self._charged)


class ContinuousBatchingEngine:
    """Runs a dedicated scheduler thread driving the device; submission is
    thread-safe. ``emit`` callbacks fire on the scheduler thread — bridge to
    asyncio with call_soon_threadsafe."""

    def __init__(
        self,
        config: EngineConfig,
        model_config: Optional[ModelConfig] = None,
        params: Optional[Any] = None,
        seed: int = 0,
        device: Optional[Any] = None,
    ) -> None:
        self.config = config
        self.model_config = model_config or get_config(config.model)
        self.dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.dtype(config.dtype)
        # device pinning (DP replica pools): params are COMMITTED to the device
        # and the scheduler thread sets it as its default, so every program this
        # engine compiles — and every host->device transfer it makes — lands
        # there, not on jax.devices()[0]
        self.device = device
        # tensor parallelism: tp > 1 lifts the WHOLE engine onto a
        # NamedSharding mesh over the first tp visible devices — params
        # Megatron-sharded, the paged KV pool split on the kv-head axis,
        # host-control rows explicitly replicated (the SH01 discipline), and
        # every dispatch family compiled under GSPMD. tp=1 keeps the
        # single-device engine byte-identical to pre-tp builds (mesh is
        # None and no code path below changes).
        self.tp = max(1, int(config.tp))
        # prefill/decode disaggregation role (runtime/pd.py): validated
        # before any allocation so a mis-roled config dies typed at BUILD
        # time. Prefill engines run only chunked prefill (mixed-batch
        # machinery, no decode rows survive past the first token) and push
        # each stream's KV + resume state to _handoff_sink; decode engines
        # admit those records in a handoff phase that skips prefill.
        #: the decoder's model module (models/llama.py, models/falcon_h1.py):
        #: the paged programs call its entry points by one set of names
        self._model = decoder_module(self.model_config)
        #: recurrent state beside the K/V pages (falcon_h1; granite_hybrid,
        #: in the layers of that kind): the serving programs carry the state
        #: slab as a third donated operand, and the modes that cannot carry
        #: it are refused here, at build
        self._has_state = self.model_config.has_state
        if self._has_state:
            self._refuse_without_state_support(config)
        #: a decode step yields a block of tokens, not a token (sdar_moe):
        #: the width of the open block every running row carries where the
        #: llama family carries its last token; 0 = one token a step. Read
        #: from the ModelConfig, as ``_has_state`` is: no option picks it
        self._block = (self.model_config.block_length
                       if self.model_config.is_block else 0)
        if self._block:
            self._refuse_without_block_support(config)
        if self.model_config.is_latent:
            self._refuse_without_latent_support(config)
        elif self.model_config.window_layers:
            self._refuse_without_window_group_support(config)
        #: the stack runs several times a token (ouro): the pool is
        #: ``loop_steps`` times the model's depth, and the forwards hand
        #: over what the exit gate says
        self._looped = self.model_config.loop_steps > 1
        if self._looped:
            self._refuse_without_loop_support(config)
        #: counters a model's forwards hand over beside the hidden state
        #: (``STEP_COUNTERS`` of its module: an expert model's routed
        #: assignments, those on experts held here, held experts touched, a
        #: share's expert layers run over the compacted list and all of
        #: them; a looped model's exit gate); they ride the drained token
        #: matrix as its last rows
        self._step_counters: tuple = getattr(self._model, "STEP_COUNTERS", ())
        self.pd_role = str(config.pd_role or "")
        if self.pd_role not in ("", "prefill", "decode"):
            raise ValueError(
                f"pd_role must be '', 'prefill' or 'decode', got "
                f"{config.pd_role!r}")
        #: set by PDServingPool on prefill-role engines: called on the
        #: scheduler thread with the _Suspended handoff record right after
        #: the first token samples. Never set on unified/decode engines.
        self._handoff_sink: Optional[Callable[["_Suspended"], None]] = None
        self.mesh = None
        self._replicated = None
        self._pool_sharding = None
        self.feasibility: Optional[dict] = None
        if self.tp > 1 and device is not None:
            raise ValueError(
                "tp > 1 cannot combine with a pinned device (dp replica "
                "pools own one device per engine; shard OR replicate, "
                "not both)")
        page = config.prefix_page_size
        self.n_slots = config.max_batch
        self.pmax = -(-config.max_seq_len // page)
        # every slot must be able to hold a full-window chain: size the
        # pool so capacity extension can always succeed via eviction
        num_pages = max(config.prefix_cache_pages,
                        self.n_slots * self.pmax + 1)
        if num_pages > config.prefix_cache_pages:
            logger.info("prefix_cache_pages %d below slot minimum; using %d",
                        config.prefix_cache_pages, num_pages)
        if self.tp > 1 or config.hbm_bytes_per_device > 0:
            # feasibility gate BEFORE any allocation: an over-HBM plan dies
            # here as a typed error (parallel/feasibility.py derives the
            # per-device bytes from the same shardings served below), never
            # as a device OOM mid-build or at request time
            from ..parallel.feasibility import gate_engine_plan

            self.feasibility = gate_engine_plan(
                self.model_config, self.tp,
                quantization=config.quantization, dtype=self.dtype,
                max_batch=config.max_batch, max_seq_len=config.max_seq_len,
                page_size=page, num_pages=num_pages,
                state_rows=(config.max_batch + self._state_snapshot_rows()
                            if self._has_state else None),
                hbm_bytes=config.hbm_bytes_per_device or None)
            self.feasibility.pop("leaves", None)
            self.feasibility.pop("read_plan", None)
        if self.tp > 1:
            from ..parallel.mesh import MeshConfig, build_mesh
            from ..parallel.sharding import (llama_page_pool_sharding,
                                             replicated)

            devices = jax.devices()
            if len(devices) < self.tp:
                raise ValueError(
                    f"tp={self.tp} needs {self.tp} devices, have "
                    f"{len(devices)} (forced-host meshes: set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={self.tp})")
            self.mesh = build_mesh(MeshConfig(dp=1, tp=self.tp),
                                   devices[: self.tp])
            self._replicated = replicated(self.mesh)
            self._pool_sharding = llama_page_pool_sharding(
                self.model_config, self.mesh)
            #: mesh handed to the paged attention kernels (shard_map over
            #: the tp head axis — required wherever the kernel compiles as
            #: real Mosaic, since GSPMD cannot auto-partition it; bitwise-
            #: equivalent on interpret backends). Only meaningful when the
            #: kv heads actually shard; a replicated pool (tp > Hkv) keeps
            #: the plain GSPMD path.
            self._attn_mesh = self.mesh if "tp" in (
                self._pool_sharding.spec or ()) else None
        else:
            self._attn_mesh = None
        self._device_ctx = (lambda: jax.default_device(self.device)) \
            if device is not None else _null_ctx
        import contextlib

        _init_ctx = contextlib.ExitStack()  # rest of __init__ allocates on-device
        if device is not None:
            _init_ctx.enter_context(jax.default_device(device))
        # the start-up timeline's stages of an engine's build (children of the
        # worker's ``engine.build`` where it opened one): weights, then the
        # slot rows and the pool, then the programs
        with startup.stage(
                "engine.weights",
                source="synthetic" if params is None else "checkpoint",
                quantization=config.quantization) as made:
            from .quant import quant_bits as _qb

            quant_bits = _qb(config.quantization)
            if params is None:
                if quant_bits is not None:
                    from .quant import init_params_quantized

                    params = init_params_quantized(
                        self.model_config, jax.random.PRNGKey(seed), self.dtype,
                        bits=quant_bits)
                else:
                    params = self._model.init_params(
                        self.model_config, jax.random.PRNGKey(seed), self.dtype)
            else:
                if quant_bits is not None and not isinstance(
                        params.get("embed"), dict):
                    # a provided unquantized tree gets quantized, never
                    # silently served bf16
                    from .quant import quantize_llama_params

                    params = quantize_llama_params(params, bits=quant_bits)
                if device is not None:
                    params = jax.device_put(params, device)
            if self.mesh is not None:
                # Megatron-style tp shardings (wq/wk/wv/gate/up column-parallel,
                # wo/down row-parallel, lm_head vocab-sharded) — the SAME spec
                # tree the feasibility gate budgeted and the AOT compiler lowers
                from ..parallel.sharding import shard_llama_params

                params = shard_llama_params(params, self.model_config, self.mesh)
            made.attrs["bytes"] = tree_bytes(params)
        self.params = params
        self._rng = host_key(seed)

        with startup.stage("engine.pool") as pooled:
            # host-side slot state (mirrors of the device-resident rows)
            self.slots: list[Optional[_SlotState]] = [None] * self.n_slots
            self.lengths = np.zeros(self.n_slots, np.int32)
            self.active = np.zeros(self.n_slots, bool)

            # what the device ADVANCES, a row a slot: its last token (a model
            # that generates by blocks: its open block), key stream, length and
            # finished mark. Only the step programs write them (a flipping row
            # inside mixed_step), and restore_row at a resume
            self._last_tokens = self._dev(
                jnp.full((self.n_slots, self._block),
                         self.model_config.mask_token_id, jnp.int32)
                if self._block else jnp.zeros((self.n_slots,), jnp.int32))
            self._lengths_dev = self._dev(jnp.zeros((self.n_slots,), jnp.int32))
            self._finished_dev = self._dev(jnp.zeros((self.n_slots,), bool))
            self._slot_keys = self._dev(jax.random.split(
                jax.random.PRNGKey(seed ^ 0x5EED), self.n_slots))

            # what the host OWNS and the programs only read: the page table and
            # every slot's sampling and termination rows in one block (_CTL),
            # and the active mask. Host arrays written in place at admission,
            # chain growth, finish, cancel, preemption and resume; _sync_rows
            # uploads what changed since its last upload, whole, ahead of a
            # dispatch (replicated over a mesh: _dev). The stop ids (-1 padded
            # to device_stop_width) and the limit let the programs freeze a
            # finished row on the device; _dev_term marks slots whose FULL stop
            # set fits the row (others fall back to host stop detection)
            self._stop_width = max(1, config.device_stop_width)
            self._rows = np.zeros(
                (self.n_slots, self._tw + _CTL + self._stop_width), np.int32)
            self._rows[:, self._tw + _CTL:] = -1
            self._tables = self._rows[:, : self._tw]      # views, as is _warp
            self.page_table = self._rows[:, : self.pmax]
            self.window_table = self._rows[:, self.pmax: self._tw]
            self._warp = self._rows[:, self._tw + 3: self._tw + _CTL].view(
                np.float32)  # temperature, top_p
            self._warp[:, 1] = 1.0
            self._rows_up, self._active_up = self._rows.copy(), self.active.copy()
            self._rows_dev = self._dev(self._rows_up)
            self._active_dev = self._dev(self._active_up)
            self._dev_term = np.ones(self.n_slots, bool)

            # slot KV lives in ONE paged pool shared with the prefix cache —
            # decode attention reads through per-slot page tables
            # (ops/paged_attention.py), prefix pages are shared zero-copy, and
            # idle slots cost one scratch-page read instead of a max_seq scan.
            from .paged import PrefixKVPool

            self.pool = PrefixKVPool(
                self.model_config, num_pages=num_pages,
                page_size=page, dtype=self.dtype,
                sharding=self._pool_sharding,
                state_slots=self.n_slots if self._has_state else 0,
                state_snapshots=self._state_snapshot_rows(),
                window_pages=self._window_pages())
            pooled.attrs.update(
                pages=num_pages,
                bytes=self.pool.pool_bytes() + self.pool.state_bytes())
            if self._two_groups:    # ``bytes`` is both groups'; these the
                pooled.attrs.update(                        # window group's
                    window_pages=self.pool.window_pages,
                    window_bytes=self.pool.window_pool_bytes())

        from collections import deque as _deque

        #: tenant-aware pending queue: per-tenant FIFO deques drained by
        #: token-weighted fair scheduling (VTC). tenant_fair=False degrades
        #: to one global FIFO — the tenant-blind A/B baseline.
        self._pending = TenantFairQueue(fair=config.tenant_fair)
        self._tenant_weights: dict[str, float] = dict(
            config.tenant_weights or {})
        #: True when ANY per-tenant cap is configured AND the queue is
        #: tenant-fair — the round-boundary cap sweep short-circuits on
        #: this one bool otherwise. The tenant-blind queue collapses every
        #: tenant onto one key, so caps could not be attributed: enforcing
        #: them would either skip nobody (blocked-set keys never match) or
        #: read a tenant's own backlog as contention — disarm loudly
        #: instead of enforcing wrongly.
        caps_configured = bool(
            config.tenant_max_slots or config.tenant_soft_pages
            or config.tenant_max_pages or config.tenant_max_pending)
        self._tenant_caps_armed = caps_configured and config.tenant_fair
        if caps_configured and not config.tenant_fair:
            logger.warning(
                "per-tenant caps configured with tenant_fair=False; caps "
                "are DISARMED (the tenant-blind queue cannot attribute "
                "work to tenants)")
        #: slots the cap sweep marked for a soft-quota yield; consumed by
        #: the next capacity pass (where preemption device work already
        #: lives) — the sweep itself stays pure bookkeeping
        self._soft_yield: set[int] = set()
        #: per-tenant rejection counters by reason (pending/quota) + yields
        self.tenant_rejections: dict[str, dict[str, int]] = {}
        self.tenant_soft_yields: dict[str, int] = {}
        #: admission throughput observations (ts, requests_admitted) — the
        #: saturation 429's Retry-After derives from the observed drain
        #: rate instead of a constant
        self._admit_events: "_deque[tuple[float, int]]" = _deque(maxlen=256)
        #: serializes submit()'s bound check-and-put (many gateway threads)
        self._submit_lock = threading.Lock()
        #: end-to-end cancellation: request ids a client/gateway asked to
        #: cancel (id → reason), registered from ANY thread under
        #: ``_cancel_lock`` and APPLIED by the scheduler thread at the next
        #: round boundary (_service_cancellations) — cancel() itself never
        #: touches device state, so it is safe on gateway event-loop threads
        self._cancel_lock = threading.Lock()
        self._cancel_requests: dict[str, str] = {}
        #: fast-path flag for the per-round expiry sweep: stays False until
        #: the first deadline-carrying submit, so deployments that never set
        #: deadlines pay one bool check per round
        self._has_deadlines = False
        from collections import deque as _rate_deque

        #: recent prefill throughput observations (tokens/s) — the
        #: admission-time estimate behind "never admit a request whose
        #: remaining deadline budget cannot even cover its prefill" uses the
        #: BEST recent rate (contention and cold compiles only ever slow a
        #: prefill down, so the max is the least-contaminated measurement —
        #: the bench guards' best-run rule). One cold-compile sample can
        #: therefore never poison the gate into rejecting all traffic.
        self._prefill_rates: "_rate_deque[float]" = _rate_deque(maxlen=32)
        self._suspended: "_deque[_Suspended]" = _deque()
        #: a state snapshot can be taken where a mixed call ends on a multiple
        #: of this many tokens: the prefill budget, in whole pages (0: none)
        budget = config.prefill_budget_tokens
        self._state_unit = (math.lcm(budget, config.prefix_page_size)
                            if self._has_state and budget > 0
                            and self._state_snapshot_rows() > 0 else 0)
        #: slots currently in "prefill" phase, FIFO by admission — the chunk
        #: planner fills the per-round token budget in this order
        self._prefill_slots: "_deque[int]" = _deque()
        #: O(1) slot allocation: maintained at admit/finish/preempt/resume —
        #: invariant: set(_free_slots) == {i | not active[i]}
        self._free_slots: "_deque[int]" = _deque(range(self.n_slots))
        self.preemptions = 0
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._thread_lock = threading.Lock()
        self._broken: Optional[str] = None
        #: set by close(): the engine was deliberately retired (drain /
        #: rolling restart). Distinct from _broken — a closed engine is
        #: clean but spent; submit/start reject, and a lifecycle manager
        #: builds a FRESH engine (reusing .params) instead of restarting it
        self._closed = False
        #: state epoch: bumped by what changes the rows under a ring in
        #: flight (preemption, a host-fallback stop, a handoff export) —
        #: ring entries dispatched at an older epoch are stale. Admission
        #: and resume wait for an empty ring instead (_admit)
        self._epoch = 0
        #: the lookahead ring: dispatched-but-undrained chunks, oldest first.
        #: Ring size beyond the drained chunk is capped at _lookahead_depth.
        self._ring: "_deque[_InflightChunk]" = _deque()
        #: the emit of the round whose drain emptied the ring, until the
        #: next launch is queued in front of it (_close_round)
        self._held: Optional[_HeldEmit] = None
        #: the ``mixed_step`` a round launched behind the one it drained (a
        #: prompt's next chunk, known before the drain), until the next
        #: round drains it (_decode_round_mixed, _chains_mixed)
        self._mixed: Optional[_MixedStep] = None
        self._lookahead_depth = config.resolve_lookahead_depth()
        #: batched speculative decoding: k draft tokens per speculating slot
        #: per round, verified as a q_len=k+1 ragged span in the mixed-batch
        #: dispatch.
        #: 0 disables everything: no spec program is built and every round
        #: takes the exact pre-speculation code path (the bit-identity
        #: default the k=0 goldens pin).
        self.spec_k = max(0, int(config.scheduler_spec_k))
        self._spec_w = self.spec_k + 1
        #: acceptance observability (stats()["speculative"]): rounds that
        #: carried at least one draft span, the subset that also carried
        #: prefill chunks, draft tokens proposed vs accepted on device, the
        #: tokens emitted through spec rounds, the accept-length histogram
        #: (rounds × per-slot spans binned by accepted count), and streams
        #: the spec_min_accept gate switched off
        self.spec_stats = {"rounds": 0, "mixed_rounds": 0, "proposed": 0,
                           "accepted": 0, "emitted": 0, "slots_disabled": 0}
        self._spec_accept_hist: dict[int, int] = {}
        with startup.stage("engine.programs"):
            self._build_programs()

        # metrics (BASELINE observability: batch occupancy, tokens/sec, and
        # the per-round pipeline breakdown the overlap claim rests on)
        from collections import deque

        self.tokens_emitted = 0
        self.requests_completed = 0
        self.rejected_saturated = 0
        #: cancellation accounting: terminal counts by reason (e.g.
        #: client_disconnect / deadline) and the decode budget reclaimed —
        #: max_tokens the fabric did NOT have to generate for dead clients
        self.cancellations: dict[str, int] = {}
        self.reclaimed_tokens = 0
        self.resume_latency_samples: "deque[float]" = deque(maxlen=512)
        self.decode_rounds = 0
        self.lookahead_rounds = 0
        self.mixed_rounds = 0
        self.prefill_chunks = 0
        self.chunked_prefill_tokens = 0
        #: over mixed rounds: positions the dispatches computed, and the
        #: tokens among them (chunk tokens + decode rows) — stats() gives
        #: their ratio, the useful share of a mixed step
        self.mixed_positions = 0
        self.mixed_useful_tokens = 0
        self.occupancy_samples: "deque[int]" = deque(maxlen=1000)
        self.round_timings: "deque[dict]" = deque(maxlen=512)
        self.queue_wait_samples: "deque[float]" = deque(maxlen=2048)
        self._lookahead_stats = {"dispatched": 0, "used": 0, "discarded": 0}
        # the ring's /metrics series exist from engine build, so a window
        # without a discard reads 0 and not nothing
        for series in ("llm_decode_chunks_dispatched_total",
                       "llm_decode_chunks_discarded_total",
                       "llm_admission_ring_waits_total",
                       "llm_drains_ring_empty_total",
                       "llm_emits_deferred_total",
                       "llm_mixed_steps_total",
                       "llm_mixed_steps_chained_total",
                       "llm_control_rows_uploads_total",
                       "llm_loose_row_programs_total",
                       "llm_attn_pages_walked_total",
                       "llm_attn_page_groups_total",
                       "llm_attn_pages_offered_total",
                       "llm_attn_window_pages_walked_total",
                       "llm_attn_window_pages_offered_total",
                       "llm_attn_window_page_groups_total",
                       "llm_window_pages_freed_total",
                       "llm_ragged_pages_walked_total",
                       "llm_ragged_trips_total") + (
                           _BLOCK_SERIES if self._block else ()
                       ) + (_LOOP_SERIES if self._looped
                            else _moe_series(self._step_counters)):
            bump_counter(series, n=0.0)
        #: window pages rows had given back at the last round record
        self._window_freed_recorded = 0
        #: achieved ring depth at each drain (how many chunks stayed in
        #: flight while the host emitted) → stats() depth histogram
        self._depth_hist: dict[int, int] = {}
        #: the scheduler thread's time by phase (the round records' timing
        #: fields, the ``sched.*`` spans of a profiler trace, the device's
        #: wait for the host): the only clock the loop reads
        self._clock = _PhaseClock(config.model)
        #: round heartbeat (monotonic): the doctor's scheduler-round
        #: watchdog reads this to notice a wedged decode loop
        self.last_round_at = time.monotonic()
        _init_ctx.close()

    # ------------------------------------------------------------------ programs
    def _refuse_without_state_support(self, config: EngineConfig) -> None:
        """A model with recurrent state is served on one device, unified;
        each mode below lacks one named thing."""
        name = self.model_config.name
        if config.scheduler_spec_k > 0:
            raise ValueError(
                f"{name}: scheduler_spec_k > 0 needs a state rollback: a "
                "rejected draft has already advanced the recurrent state, "
                "and nothing restores it")
        if config.pd_role:
            raise ValueError(
                f"{name}: pd_role={config.pd_role!r} hands rows over as "
                "pages, and the export carries no recurrent state")
        if max(1, int(config.tp)) > 1:
            raise ValueError(
                f"{name}: tp > 1 has no sharding for the state slab")

    def _refuse_without_block_support(self, config: EngineConfig) -> None:
        """A model that generates by blocks is served on one device, unified,
        without speculation; each mode below lacks one named thing."""
        name = self.model_config.name
        if config.scheduler_spec_k > 0:
            raise ValueError(
                f"{name}: scheduler_spec_k > 0 verifies a draft left to "
                "right against next-token logits, and a block model's "
                "logits at a position are for the token AT it")
        if config.pd_role:
            raise ValueError(
                f"{name}: pd_role={config.pd_role!r} hands a row over with "
                "one last token, and the export carries no open block")
        if max(1, int(config.tp)) > 1:
            raise ValueError(
                f"{name}: tp > 1 has no partitioning of the grouped expert "
                "matmul (no tp or ep axis)")
        if config.prefix_page_size % self._block:
            raise ValueError(
                f"{name}: a page of {config.prefix_page_size} tokens has to "
                f"be whole blocks of {self._block}: a shared prefix ends on "
                "a block boundary")

    def _refuse_without_latent_support(self, config: EngineConfig) -> None:
        """A model whose cache is a latent page is served on one device,
        unified, without speculation; each mode below lacks one named
        thing."""
        name = self.model_config.name
        if config.scheduler_spec_k > 0:
            raise ValueError(
                f"{name}: scheduler_spec_k > 0 verifies a draft span through "
                "the K/V ragged kernel's all-rows call, and the latent "
                "kernels have no such program")
        if config.pd_role:
            raise ValueError(
                f"{name}: pd_role={config.pd_role!r} hands rows over as K "
                "and V pages with a kv-head axis; a latent page has neither")
        if max(1, int(config.tp)) > 1:
            raise ValueError(
                f"{name}: tp > 1 has no sharding for a latent page (no "
                "kv-head axis) nor an ep axis for the experts")

    def _refuse_without_window_group_support(self,
                                             config: EngineConfig) -> None:
        """A model whose K/V pages come in two page groups is served on one
        device, unified, without speculation; each mode below lacks one
        named thing (a latent model's two groups are refused above, by what
        a latent page lacks)."""
        name = self.model_config.name
        if config.scheduler_spec_k > 0:
            raise ValueError(
                f"{name}: scheduler_spec_k > 0 verifies a draft span through "
                "llama's all-rows forward, which reads one page group, one "
                "window and one count of query heads")
        if config.pd_role:
            raise ValueError(
                f"{name}: pd_role={config.pd_role!r} hands rows over as the "
                "pages of ONE page group; a decode replica could not "
                "continue a row without its window pages")
        if max(1, int(config.tp)) > 1:
            raise ValueError(
                f"{name}: tp > 1 has no sharding for the window page group "
                "nor an ep axis for the experts")

    def _refuse_without_loop_support(self, config: EngineConfig) -> None:
        """A model whose stack runs several times a token is served on one
        device, unified, without speculation, every pass run; each mode
        below lacks one named thing."""
        cfg = self.model_config
        if cfg.early_exit_threshold < 1.0:
            raise ValueError(
                f"{cfg.name}: early_exit_threshold "
                f"{cfg.early_exit_threshold} lets rows of one batch leave at "
                "different passes, and a step costs every row the same "
                "here: only the published 1 (every pass runs) is served")
        if config.scheduler_spec_k > 0:
            raise ValueError(
                f"{cfg.name}: scheduler_spec_k > 0 verifies a draft span "
                "through llama's all-rows forward, which runs the stack "
                "once and norms no branch")
        if config.pd_role:
            raise ValueError(
                f"{cfg.name}: pd_role={config.pd_role!r} has no decode-role "
                "admission tested for a pool of loop_steps x num_layers "
                "cache layers")
        if max(1, int(config.tp)) > 1:
            raise ValueError(
                f"{cfg.name}: tp > 1 has no sharding for the sandwich "
                "norms' and the exit gate's leaves")

    @property
    def _two_groups(self) -> bool:
        """The model caches in two page groups (runtime/paged.py)."""
        return self.model_config.window_layers > 0

    @property
    def _tw(self) -> int:
        """Slots of a row's page table: a run of ``pmax`` a page group, the
        full group's, then the window group's where the model has one."""
        return self.pmax * (2 if self._two_groups else 1)

    def _window_pages(self) -> int:
        """Pages of the window page group (0: the model has one group), from
        shapes alone: what every slot holds at rest and through a decode
        ring, and two prompt chunks in flight (a chained ``mixed_step``)
        beside them, and scratch. The group has no tree and keeps no prefix,
        so more pages would buy nothing; a row it cannot serve is preempted
        like one the full group cannot (``_grow_tables``)."""
        cfg = self.model_config
        if not cfg.window_layers:
            return 0
        page = self.config.prefix_page_size
        ring = max(1, self.config.decode_chunk) * max(
            1, self.config.decode_lookahead + 1)
        at_rest = cfg.window_pages(page, ring)
        chunk = cfg.window_pages(page, self.config.prefill_budget_tokens)
        return self.n_slots * (at_rest + 1) + 2 * chunk + 1

    def _state_snapshot_rows(self) -> int:
        if not self._has_state:
            return 0
        n = self.config.state_snapshots
        return self.config.max_batch if n < 0 else n

    def _build_programs(self) -> None:
        """The step programs, by what they read: what is read of
        ``self.config`` here, directly or derived, is what shapes a program."""
        key = ProgramKey(
            self.model_config, max(1, self.config.decode_chunk),
            self.config.max_seq_len, self.n_slots, self._tw,
            n_cache=len(self.pool.cache_operands()),
            has_state=self._has_state, step_counters=self._step_counters,
            block=self._block, attn_mesh=self._attn_mesh, spec_k=self.spec_k)
        (self._restore_row_fn, self._paged_decode_fn, self._mixed_step_fn,
         self._spec_step_fn) = step_programs(key)
        self._k_steps = key.decode_chunk

    def _bucket_for(self, length: int) -> int:
        return self.config.bucket_for(length)

    @property
    def _step_tokens(self) -> int:
        """The most a row's length advances in one forward."""
        return self._block or 1

    @property
    def _chunk_tokens(self) -> int:
        """The most a row's length advances in one decode chunk: what the
        page chains are grown by. A block model commits at most every other
        forward (a denoise forward that unmasks everything, then the commit
        forward)."""
        if self._block:
            return self._block * -(-self._k_steps // 2)
        return self._k_steps

    def _prefill_target(self, state: "_SlotState") -> int:
        """The prompt tokens a row's lane computes: all of them, or (a model
        that generates by blocks) its whole blocks; the leftover opens the
        row's first block."""
        n = len(state.prompt_ids)
        return n - n % self._block if self._block else n

    def _token_limit(self, prompt_len: int, max_tokens: int) -> int:
        """The kept length at which a row has its ``max_tokens``: the llama
        family emits its first token at the prompt's end, before any decode
        step; a block model emits what it commits."""
        return prompt_len + max_tokens - (0 if self._block else 1)

    # ------------------------------------------------------------------ public api
    def start(self) -> None:
        with self._thread_lock:
            if self._broken:
                raise RuntimeError(f"scheduler is broken: {self._broken}")
            if self._closed:
                raise RuntimeError("scheduler is closed; build a fresh engine")
            if self._thread is None or not self._thread.is_alive():
                self._stop.clear()
                # the thread works for the stage that starts it (the
                # worker's ``engine.thread``, inside its model's build)
                self._thread = threading.Thread(
                    target=self._run_loop, args=(startup.current(),),
                    name="cb-scheduler", daemon=True)
                self._thread.start()

    def shutdown(self, timeout: float = 10.0) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout)

    def close(self, timeout: float = 10.0) -> None:
        """Retire the engine: stop the scheduler thread, then error-terminate
        everything still in flight (the replica pool's failover wrapper turns
        those errors into resubmissions elsewhere — the drain-deadline
        "preempt and fail over" path). Callers wanting a CLEAN drain stop
        routing new work first and wait for idle, so there is nothing left to
        fail. Unlike a loop crash, close() never sets ``_broken`` — the
        engine is rebuildable (a lifecycle manager constructs a fresh
        ContinuousBatchingEngine reusing ``.params``, O(scheduler start) not
        O(weight load)), just never restartable in place. Idempotent."""
        if self._closed:
            return
        self._closed = True
        self.shutdown(timeout)
        # after the join the scheduler thread is gone (or wedged in a device
        # call — in which case its future emits are deduped by the pool's
        # done-tracking wrapper); state is ours to clean up
        self._fail_all_inflight("replica closed")

    def submit(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams,
        emit: Callable[[StepEvent], None],
        request_id: Optional[str] = None,
        trace: Optional[str] = None,
        deadline: Optional[float] = None,
        tenant: Optional[str] = None,
    ) -> str:
        """Enqueue a request; ``emit`` receives StepEvents from the scheduler
        thread (request_index is unused here — events are per-request already).
        ``trace`` is the caller's W3C traceparent: lifecycle spans
        (llm.prefill / llm.decode_chunk / llm.preempt) join that trace.
        ``deadline`` is an absolute ``time.monotonic()`` instant: once passed
        the request lapses with a ``deadline`` terminal wherever it is —
        still queued (never admitted), mid-chunked-prefill, mid-decode, or
        suspended — via the per-round expiry sweep.
        ``tenant`` is the caller's SecurityContext.tenant_id (None → the
        default class): it keys the weighted-fair pending queue, the
        per-tenant caps, and the per-tenant accounting."""
        rid = request_id or f"req-{uuid.uuid4().hex[:16]}"
        tenant = tenant or "default"
        self._bucket_for(len(prompt_ids))  # validate early, in caller context
        if self._tenant_caps_armed and self.config.tenant_max_pages > 0:
            # hard page quota, checked against the request's WORST-CASE need
            # (full prompt + max_tokens): a request that can never fit the
            # tenant's quota must be rejected now, not admitted into a
            # preempt/resume livelock against its own cap
            need = self.pool.pages_for(
                min(len(prompt_ids) + sampling.max_tokens,
                    self.config.max_seq_len))
            if need > self.config.tenant_max_pages:
                self._bump_tenant_rejection(tenant, "quota")
                raise TenantQuotaExceeded(
                    f"request needs {need} KV pages > tenant hard quota "
                    f"{self.config.tenant_max_pages} (prompt "
                    f"{len(prompt_ids)} + max_tokens {sampling.max_tokens})",
                    tenant=tenant)
        if not self.active_slots and not self._suspended \
                and not self._prefill_slots and self._pending.qsize() == 0:
            # idle→busy: restart the round-stall clock. last_round_at is
            # otherwise only refreshed by COMPLETED rounds, so after an
            # idle gap the doctor's scheduler_round watchdog would read
            # the whole gap as stall age and trip on the first request —
            # degrading a healthy server during warmup. Age must measure
            # time-with-work-but-no-round, not time-since-last-round.
            # Advisory snapshot + GIL-atomic float store, deliberately
            # outside _submit_lock (matching the scheduler thread's own
            # unguarded per-round write): a racing refresh lands on ~now
            # either way.
            self.last_round_at = time.monotonic()
        with self._submit_lock:
            # dead-engine rejection lives UNDER the submit lock, paired with
            # _fail_all_inflight's locked queue drain: either this put lands
            # before the teardown drain (the request gets its error
            # terminal) or the flag is already visible here and we reject —
            # a request can never be stranded in a queue no loop will drain
            if self._closed:
                raise RuntimeError(
                    "scheduler is closed; build a fresh engine")
            if self._broken:
                raise RuntimeError(f"scheduler is broken: {self._broken}")
            # check-and-put under one lock: concurrent gateway threads must
            # not overshoot the bound between qsize() and put() (the
            # scheduler-side requeue paths bypass the bound by design —
            # those requests were already admitted once)
            if self._tenant_caps_armed and self.config.tenant_max_pending \
                    and self._pending.tenant_depth(tenant) >= \
                    self.config.tenant_max_pending:
                # the TENANT's own queue is full: its retry storm saturates
                # itself — the global queue (and every other tenant) keeps
                # admitting. Retry-After scales with the tenant's backlog.
                self.rejected_saturated += 1
                self._bump_tenant_rejection(tenant, "pending")
                raise TenantSaturated(
                    f"tenant {tenant!r} pending queue full "
                    f"({self.config.tenant_max_pending} requests); "
                    "retry later",
                    retry_after_s=self._saturation_retry_after(
                        self._pending.tenant_depth(tenant)),
                    tenant=tenant)
            if self.config.max_pending and \
                    self._pending.qsize() >= self.config.max_pending:
                # backpressure at admission: reject NOW (callers map this to
                # 429 + Retry-After) instead of growing the queue unbounded.
                # Retry-After derives from the observed drain rate — a
                # nearly-draining queue says "1s", a wedged one says "30s".
                self.rejected_saturated += 1
                raise SchedulerSaturated(
                    f"pending queue full ({self.config.max_pending} "
                    "requests); retry later",
                    retry_after_s=self._saturation_retry_after(
                        self._pending.qsize()))
            # recorded BEFORE the put: once the request is visible to the
            # scheduler thread it can be admitted (and even finished)
            # immediately — a late 'enqueued' would arrive out of order and
            # reopen a ghost record
            extra = {}
            if deadline is not None:
                self._has_deadlines = True
                extra["deadline_ms"] = round(
                    (deadline - time.monotonic()) * 1000.0, 1)
            record_event(rid, "enqueued", prompt_tokens=len(prompt_ids),
                         trace_id=traceparent_ids(trace)[0], tenant=tenant,
                         **extra)
            self._pending.put(_Pending(rid, list(prompt_ids), sampling, emit,
                                       trace=trace, deadline=deadline,
                                       tenant=tenant))
        self._wake.set()
        self.start()
        return rid

    def submit_handoff(self, rec: _Suspended) -> None:
        """PD disaggregation: enqueue a handed-off stream (prefill already
        done elsewhere, KV on host, first token emitted) for decode-side
        admission. The record enters the suspended deque — the handoff
        phase IS the resume path: _resume_suspended restores the pages,
        restores the slot rows from the record's length/last-token/key, and
        decode continues with zero prefill work on this engine. Suspended
        outranks admission, so a handoff is never stuck behind this
        engine's own queue. Runs on the SOURCE engine's scheduler thread
        (via the pool's handoff sink): non-blocking bookkeeping only — a
        deque append is GIL-atomic against this engine's popleft, and the
        _submit_lock pairs the dead-engine check with _fail_all_inflight's
        drain exactly like submit()."""
        if self.pd_role == "prefill":
            raise RuntimeError(
                "handoff target must be a decode-role or unified engine")
        state = rec.state
        # (re-)arm speculation under THIS engine's spec config — the
        # prefill role runs with spec disabled, so the proposer arrives
        # None; seed it with the full history (prompt + the one emitted
        # token) so proposals match a unified engine's exactly
        state.proposer = None
        self._arm_spec(state, state.prompt_ids)
        if state.proposer is not None:
            state.proposer.extend([rec.last_token])
        if not self.active_slots and not self._suspended \
                and not self._prefill_slots and self._pending.qsize() == 0:
            # idle→busy heartbeat refresh, same contract as submit()
            self.last_round_at = time.monotonic()
        with self._submit_lock:
            if self._closed:
                raise RuntimeError(
                    "scheduler is closed; build a fresh engine")
            if self._broken:
                raise RuntimeError(f"scheduler is broken: {self._broken}")
            if state.deadline is not None:
                self._has_deadlines = True
            self._suspended.append(rec)
        self._wake.set()
        self.start()

    @property
    def active_slots(self) -> int:
        return int(self.active.sum())

    def servable(self) -> bool:
        """Cheap per-request admission probe (two attribute reads — no
        stats() dict build): False once the loop crashed or close() retired
        the engine, at which point a supervisor should rebuild it."""
        return self._broken is None and not self._closed

    # --------------------------------------------------------- cancellation
    def cancel(self, request_id: str, reason: str = "cancelled") -> bool:
        """Request cancellation of ``request_id`` — safe from ANY thread and
        non-blocking (a dict write + a wake; no device work, no sleeps): the
        gateway calls this on its event loop when an SSE consumer vanishes.
        The scheduler thread applies it at the next round boundary
        (:meth:`_service_cancellations`): a still-queued request leaves the
        pending queue, a prefilling/decoding slot is deactivated and its
        pages released, a suspended request is dropped — each with exactly
        one ``cancelled`` terminal. Idempotent; cancelling a request that
        already finished is a no-op. Returns an ADVISORY bool: whether the
        id was visible somewhere in this engine at call time."""
        found = self._cancel_known(request_id)
        with self._cancel_lock:
            self._cancel_requests[request_id] = reason
        self._wake.set()
        return found

    def _cancel_known(self, request_id: str) -> bool:
        """Advisory presence probe (slot scan + suspended-deque snapshot +
        one queue-mutex peek; the authoritative lookup happens on the
        scheduler thread). Runs on gateway threads — the suspended deque
        must be copied under the advisory contract, not bare ``list()``:
        the scheduler thread preempts/resumes concurrently, and a resized
        deque raises mid-copy (fabric-lint RC04)."""
        for state in self.slots:
            if state is not None and state.request_id == request_id:
                return True
        for rec in locked_snapshot(self._suspended):
            if rec.state.request_id == request_id:
                return True
        return any(req.request_id == request_id
                   for req in self._pending.snapshot())

    def _service_cancellations(self) -> None:
        """Apply registered cancels and lapse blown deadlines — runs on the
        scheduler thread at every round boundary, so a cancelled mid-decode
        stream frees its slot, KV pages, and prefix pins within ONE round.

        Ring interaction (the deep-lookahead composition): a mid-decode
        cancel does NOT bump the epoch, so in-flight speculative chunks keep
        draining for the surviving rows — no full discard. That is safe
        because (a) chunks already in flight write the cancelled row's KV
        only into its own PRIVATE chain pages (decode positions sit past the
        tree-committed prompt pages), and any later owner of a released page
        rewrites every position before reading it, in dispatch order behind
        the stale writes; (b) chunks dispatched AFTER the cancel see the
        zeroed page-table row (flushed at dispatch) and park the row's
        writes on scratch page 0 — the same freeze the device-resident
        finished mask gives device-predicted stops; (c) the host mirrors
        (``active``/``slots``) are cleared here, so the emit loop masks the
        row's tokens out of every later drain."""
        with self._cancel_lock:
            if self._cancel_requests:
                cancels = self._cancel_requests
                self._cancel_requests = {}
            else:
                cancels = {}
        if not cancels and not self._has_deadlines:
            return
        now = time.monotonic()
        self._cancel_filter_pending(cancels, now)
        self._cancel_suspended(cancels, now)
        for slot in range(self.n_slots):
            state = self.slots[slot]
            if state is None:
                continue
            reason = cancels.pop(state.request_id, None)
            kind = "cancelled"
            if reason is None and state.deadline is not None \
                    and now >= state.deadline:
                reason, kind = "deadline", "deadline_exceeded"
            if reason is None:
                continue
            # what ends a stream on the host flushes first: tokens drained
            # before the cancel precede its terminal, and if they ended the
            # stream the cancel raced that terminal
            if self._settle() and self.slots[slot] is not state:
                continue
            self._cancel_slot(slot, state, reason, kind)
        # ids that matched nothing raced a terminal (finished/preempt-shed in
        # the same round): the request already got its one terminal — the
        # cancel is consumed without effect, never a second emission

    def _cancel_filter_pending(self, cancels: dict[str, str],
                               now: float) -> None:
        """Lapse/cancel still-queued requests without ever taking a slot.
        The advisory scan keeps the common no-victim round O(pending) cheap;
        the drain-and-requeue runs under ``_submit_lock`` (the same
        discipline as _fail_all_inflight) and the terminals emit outside
        it."""
        snapshot = self._pending.snapshot()
        if not any(req.request_id in cancels
                   or (req.deadline is not None and now >= req.deadline)
                   for req in snapshot):
            return
        with self._submit_lock:
            removed = self._pending.remove_if(
                lambda req: req.request_id in cancels
                or (req.deadline is not None and now >= req.deadline))
        victims: list[tuple[_Pending, str, str]] = []
        for req in removed:
            reason = cancels.pop(req.request_id, None)
            if reason is not None:
                victims.append((req, reason, "cancelled"))
            else:
                victims.append((req, "deadline", "deadline_exceeded"))
        for req, reason, kind in victims:
            self._cancel_finalize(req.request_id, req.emit, reason, kind,
                                  phase="queued", emitted=0,
                                  reclaimed=req.sampling.max_tokens,
                                  trace=req.trace,
                                  trace_sampled=traceparent_ids(req.trace)[1],
                                  tenant=req.tenant)

    def _cancel_suspended(self, cancels: dict[str, str], now: float) -> None:
        """Drop cancelled/lapsed preempted requests — their KV lives on host
        (no pool pages held while suspended), so the saved copy just
        drops."""
        if not self._suspended:
            return
        kept: list[_Suspended] = []
        victims: list[tuple[_Suspended, str, str]] = []
        # _suspended mutations take _submit_lock uniformly now that
        # submit_handoff appends from OTHER engines' scheduler threads
        # (emits stay outside the lock — see _fail_all_inflight)
        with self._submit_lock:
            while self._suspended:
                rec = self._suspended.popleft()
                reason = cancels.pop(rec.state.request_id, None)
                kind = "cancelled"
                if reason is None and rec.state.deadline is not None \
                        and now >= rec.state.deadline:
                    reason, kind = "deadline", "deadline_exceeded"
                if reason is None:
                    kept.append(rec)
                else:
                    victims.append((rec, reason, kind))
            self._suspended.extend(kept)
        for rec, reason, kind in victims:
            self._cancel_finalize(
                rec.state.request_id, rec.state.emit, reason, kind,
                phase="suspended", emitted=rec.state.emitted,
                reclaimed=rec.state.sampling.max_tokens - rec.state.emitted,
                trace=rec.state.trace,
                trace_sampled=rec.state.trace_sampled,
                tenant=rec.state.tenant)

    def _cancel_slot(self, slot: int, state: _SlotState, reason: str,
                     kind: str) -> None:
        """Deactivate one occupied slot (prefill OR decode phase) and
        release everything it holds: the slot itself, its page chain (the
        chain's refs are the only pins a mid-flight request holds — the
        radix probe pin was released at admission), and its rows on the
        host (off the active mask, the page-table row zeroed, so chunks
        dispatched after this park the row's KV writes on scratch).
        Deliberately NO epoch bump — see _service_cancellations: the
        lookahead ring drains through a cancel instead of discarding."""
        phase = state.phase
        if phase == "prefill":
            self._prefill_slots.remove(slot)
        self.active[slot] = False
        self.slots[slot] = None
        self._release_free_slot(slot)
        if state.chain is not None:
            self._release_chains(state)
            self._drop_pending_snapshots(state)
            self._tables[slot, :] = 0
        self._cancel_finalize(
            state.request_id, state.emit, reason, kind, phase=phase,
            emitted=state.emitted, slot=slot,
            reclaimed=state.sampling.max_tokens - state.emitted,
            trace=state.trace, trace_sampled=state.trace_sampled,
            tenant=state.tenant)

    def _cancel_finalize(self, request_id: str,
                         emit: Callable[[StepEvent], None], reason: str,
                         kind: str, *, phase: str, emitted: int,
                         reclaimed: int, slot: Optional[int] = None,
                         trace: Optional[str] = None,
                         trace_sampled: bool = False,
                         tenant: str = "default") -> None:
        """One terminal per cancellation: accounting, the flight-recorder
        terminal (``cancelled`` / ``deadline_exceeded``), metrics, an
        ``llm.cancel`` span for sampled traces, and the client StepEvent —
        all through never-raises helpers (the emit callback may belong to a
        connection that no longer exists)."""
        self.cancellations[reason] = self.cancellations.get(reason, 0) + 1
        self.reclaimed_tokens += max(0, int(reclaimed))
        attrs = {"reason": reason, "phase": phase, "tokens": emitted,
                 "tenant": tenant}
        if slot is not None:
            attrs["slot"] = slot
        record_event(request_id, kind, **attrs)
        bump_counter("llm_cancellations_total", reason=reason)
        if reclaimed > 0:
            bump_counter("llm_cancel_reclaimed_tokens_total",
                         n=int(reclaimed))
        if trace_sampled:
            # the request's OTLP trace ends with WHY it ended — the span
            # distinguishes a disconnect-abort from a deadline lapse
            get_global_tracer().emit_span(
                "llm.cancel", traceparent=trace,
                start_unix_ns=int(time.time() * 1e9), duration_ms=0.0,
                request_id=request_id, reason=reason, kind=kind,
                phase=phase, tokens=emitted, tenant=tenant)
        finished = "deadline" if kind == "deadline_exceeded" else "cancelled"
        try:
            emit(StepEvent(0, -1, finished))
        except Exception:  # noqa: BLE001 — the client is gone by definition
            pass

    def _note_prefill_rate(self, tokens: int, dur_s: float) -> None:
        """Observed prefill throughput under CURRENT load — feeds the
        admission-time "can this request even prefill before its deadline"
        estimate. Durations include budget pacing across rounds, which is
        exactly the wait a new admission would experience."""
        if tokens <= 0 or dur_s <= 0:
            return
        self._prefill_rates.append(tokens / dur_s)

    def _estimate_prefill_s(self, tokens: int) -> float:
        """Optimistic-by-construction, permissive-when-cold: the BEST recent
        rate (slow samples are contamination — compiles, contention — never
        capability), and 0 with no observations yet (admit and let the
        per-round sweep judge it). Under-estimating only costs one wasted
        prefill; over-estimating would reject servable traffic, and a
        poisoned estimate could otherwise lock out every deadline-carrying
        request forever (rejected requests never prefill, so the rate would
        never correct)."""
        rate = max(locked_snapshot(self._prefill_rates), default=0.0)
        if rate <= 0:
            return 0.0
        return tokens / rate

    # ---------------------------------------------------- tenant isolation
    def _weight(self, tenant: str) -> float:
        return float(self._tenant_weights.get(
            tenant, self.config.tenant_default_weight))

    def _charge_tenant(self, tenant: str, tokens: int) -> None:
        """Charge actually-consumed tokens (prefill or decode) to the
        tenant's virtual counter — the fair queue's only scheduling input.
        Scheduler thread only; plain dict math (WD01-shaped)."""
        self._pending.charge(tenant, tokens, self._weight(tenant))

    def _bump_tenant_rejection(self, tenant: str, reason: str) -> None:
        """Never-raises rejection accounting (submit runs on gateway
        threads; a metrics error must not turn a 429 into a 500)."""
        try:
            per = self.tenant_rejections.setdefault(tenant, {})
            per[reason] = per.get(reason, 0) + 1
            bump_counter("llm_tenant_rejections_total", tenant=tenant,
                         reason=reason)
        except Exception:  # noqa: BLE001
            pass

    #: drain-rate observations older than this are stale — an overnight
    #: idle gap must not read as "the queue drains one request per hour"
    _DRAIN_RATE_WINDOW_S = 60.0

    def _drain_rate_per_s(self) -> float:
        """Observed admission throughput (requests/s) over the recent
        window — how fast the pending queue actually drains. Only events
        inside the window count, and the FIRST surviving event anchors the
        span without contributing its count (its admissions happened over
        an interval that ENDED at its timestamp — counting them would
        overestimate the rate when samples are few)."""
        events = locked_snapshot(self._admit_events)
        cutoff = time.monotonic() - self._DRAIN_RATE_WINDOW_S
        events = [e for e in events if e[0] >= cutoff]
        if len(events) < 2:
            return 0.0
        span = events[-1][0] - events[0][0]
        if span <= 0:
            return 0.0
        return sum(n for _, n in events[1:]) / span

    def _saturation_retry_after(self, depth: int) -> float:
        """Retry-After for a saturated queue, derived from the observed
        drain rate: roughly "when will a slot in line open up", clamped to
        [1, 30] seconds (an idle/unknown rate reads as 1s — optimistic,
        like the pre-derivation constant)."""
        rate = self._drain_rate_per_s()
        if rate <= 0:
            return 1.0
        return float(min(30.0, max(1.0, depth / rate)))

    def _tenant_slot_counts(self) -> dict[str, int]:
        """Occupied slots (decode + chunked prefill) per tenant."""
        counts: dict[str, int] = {}
        for state in self.slots:
            if state is not None:
                counts[state.tenant] = counts.get(state.tenant, 0) + 1
        return counts

    def _tenant_page_counts(self) -> dict[str, int]:
        """KV pages held per tenant (slot chains only — suspended requests
        hold host memory, not pool pages)."""
        counts: dict[str, int] = {}
        for state in self.slots:
            if state is not None and state.chain is not None:
                counts[state.tenant] = (counts.get(state.tenant, 0)
                                        + len(state.chain))
        return counts

    def _blocked_tenants(self) -> set:
        """Tenants admission must skip this pass: at their slot cap, or
        already holding their hard page quota. Their requests stay queued;
        weighted-fair pop serves everyone else around them."""
        blocked: set = set()
        if not self._tenant_caps_armed:
            return blocked
        max_slots = self.config.tenant_max_slots
        max_pages = self.config.tenant_max_pages
        slots = self._tenant_slot_counts() if max_slots else {}
        pages = self._tenant_page_counts() if max_pages else {}
        for tenant, n in slots.items():
            if n >= max_slots:
                blocked.add(tenant)
        for tenant, n in pages.items():
            if max_pages and n >= max_pages:
                blocked.add(tenant)
        return blocked

    def _service_tenant_caps(self) -> None:
        """Round-boundary soft-quota sweep (the PR-9 cancellation pattern:
        non-blocking bookkeeping only, no device work, never raises —
        fabric-lint WD01). A tenant holding more than ``tenant_soft_pages``
        KV pages *under contention* — another tenant backlogged in the
        pending queue, or requests suspended waiting for pool space — has
        its YOUNGEST slot marked for a yield; the next capacity pass (where
        preemption's device work already lives) preempts it to host through
        the existing `_preempt_slot` path. One victim per sweep, so a
        momentary overshoot never thrashes a tenant's whole fleet."""
        if not self._tenant_caps_armed:
            return
        soft = self.config.tenant_soft_pages
        if soft <= 0 or self._soft_yield:
            return  # previous mark not yet consumed
        pages = self._tenant_page_counts()
        over = {t: n for t, n in pages.items() if n > soft}
        if not over:
            return
        # contention test: someone ELSE is waiting for capacity
        depths = self._pending.depths()
        contention = bool(self._suspended) or any(
            d > 0 for t, d in depths.items() if t not in over)
        if not contention:
            return
        victim_tenant = max(over, key=over.get)  # worst offender first
        # youngest slot = the least sunk prefill/decode cost to re-pay
        best_slot, best_len = None, None
        for slot in range(self.n_slots):
            state = self.slots[slot]
            # decode-phase slots only: the consuming capacity pass walks
            # ACTIVE slots (mid-chunked-prefill yields ride the existing
            # pool-pressure path instead)
            if state is None or state.tenant != victim_tenant \
                    or not self.active[slot]:
                continue
            length = int(self.lengths[slot])
            if best_len is None or length < best_len:
                best_slot, best_len = slot, length
        if best_slot is None:
            return
        self._soft_yield.add(best_slot)
        self.tenant_soft_yields[victim_tenant] = \
            self.tenant_soft_yields.get(victim_tenant, 0) + 1
        bump_counter("llm_tenant_soft_yields_total", tenant=victim_tenant)
        record_event(self.slots[best_slot].request_id, "soft_yield_marked",
                     slot=best_slot, tenant=victim_tenant,
                     pages=over[victim_tenant], soft_cap=soft)

    def tenant_snapshot(self) -> dict[str, dict[str, Any]]:
        """Per-tenant live figures — the /v1/monitoring/tenants row source
        and the doctor's attribution feed. Cheap advisory reads (one slot
        scan + queue-lock snapshots); safe from any thread."""
        slots = self._tenant_slot_counts()
        pages = self._tenant_page_counts()
        depths = self._pending.depths()
        vtc = self._pending.vtc_snapshot()
        charged = self._pending.charged_snapshot()
        # gateway threads insert new tenant/reason keys on rejection while
        # this (possibly a lifecycle/doctor thread) iterates — the advisory
        # snapshot contract: degrade, never raise (a raising stats()
        # quarantines a healthy replica). Inner per-tenant dicts grow new
        # reason keys concurrently too, so they get their own snapshots.
        rejections = {t: locked_snapshot(per)
                      for t, per in
                      locked_snapshot(self.tenant_rejections).items()}
        yields = locked_snapshot(self.tenant_soft_yields)
        tenants = (set(slots) | set(pages) | set(depths) | set(charged)
                   | set(rejections))
        out: dict[str, dict[str, Any]] = {}
        for tenant in tenants:
            out[tenant] = {
                "weight": self._weight(tenant),
                "active_slots": slots.get(tenant, 0),
                "pages": pages.get(tenant, 0),
                "pending": depths.get(tenant, 0),
                "virtual_counter": round(vtc.get(tenant, 0.0), 3),
                "charged_tokens": charged.get(tenant, 0),
                "soft_yields": yields.get(tenant, 0),
                "rejections": rejections.get(tenant, {}),
            }
            if self._has_state:
                # state rows held beside pages held: one a slot
                out[tenant]["state_rows"] = slots.get(tenant, 0)
            if self._two_groups:
                # "pages" counts the full group; these the window group
                out[tenant]["window_pages"] = sum(
                    sum(1 for p in st.wchain if p)
                    for st in locked_snapshot(self.slots)
                    if st is not None and st.tenant == tenant
                    and st.wchain is not None)
            if self.model_config.is_latent:
                # what a page holds is the configuration's: a latent row
                out[tenant]["page_layout"] = "latent"
                out[tenant]["cache_bytes_per_token"] = \
                    self.model_config.cache_bytes_per_token(
                        jnp.dtype(self.dtype).itemsize)
        return out

    def state_rows_in_use(self) -> int:
        """Rows of the state slab that hold something: one a live slot, plus
        the snapshot rows a page or a prompt in prefill holds (0 for a model
        without recurrent state)."""
        if not self._has_state:
            return 0
        live = sum(1 for s in locked_snapshot(self.slots) if s is not None)
        return live + self.pool.state_stats()["state_snapshot_rows_in_use"]

    def moe_layers_built(self) -> int:
        """Layers of the expert stack the parameters were BUILT with (its
        leading dimension; 0 for a dense model): ``cfg.moe_layers`` unless a
        tree stacks its experts over layers that hold none."""
        for stack in self.params.values():
            if isinstance(stack, dict) and "moe_up" in stack:
                leaf = stack["moe_up"]
                return int((leaf["q"] if isinstance(leaf, dict)
                            else leaf).shape[0])
        return 0

    def attn_heads_built(self) -> tuple[int, int]:
        """Query heads of a layer that attends over everything and of one
        behind a window, as the parameters were BUILT: the rows of each
        kind's output projection over the head size where the tree stacks
        the two kinds apart (``full`` and ``window``), the model's one count
        twice where it does not."""
        cfg = self.model_config

        def heads(stack: str) -> int:
            leaf = self.params[stack]["wo"]
            rows = (leaf["q"] if isinstance(leaf, dict) else leaf).shape[-2]
            return int(rows) // cfg.head_dim

        if "window" in self.params and "full" in self.params:
            return heads("full"), heads("window")
        return cfg.num_heads, cfg.num_heads

    # -------------------------------------------------------- health surface
    def mesh_info(self) -> dict[str, Any]:
        """The serving-mesh block (stats()["mesh"], /v1/monitoring/replicas,
        llm_mesh_* gauges): topology, tp degree, how the paged pool shards,
        and the feasibility plan's per-device byte budget. Cheap attribute
        reads — safe for gauges and lifecycle probes (no stats() build)."""
        try:
            platform = jax.devices()[0].platform
        except Exception:  # noqa: BLE001 — a wedged backend must not break stats
            platform = "unknown"
        kv_sharded = bool(
            self._pool_sharding is not None
            and "tp" in (self._pool_sharding.spec or ()))
        info: dict[str, Any] = {
            "tp": self.tp,
            "devices": self.tp if self.mesh is not None else 1,
            "topology": f"{platform}:{self.tp}",
            "kv_heads_sharded": kv_sharded,
        }
        if self.pool is not None:
            pool_bytes = self.pool.pool_bytes()
            info["sharded_page_bytes_per_device"] = (
                pool_bytes // self.tp if kv_sharded else pool_bytes)
        if self.feasibility is not None:
            info["plan"] = {
                k: self.feasibility.get(k)
                for k in ("param_bytes_per_device", "kv_bytes_per_device",
                          "total_bytes_per_device", "hbm_bytes",
                          "hbm_utilization", "fits", "enforced",
                          "quantization")}
        return info

    def pending_depth(self) -> int:
        """Live pending-queue depth (llm_queue_depth{model=} gauge)."""
        return self._pending.qsize()

    def pending_oldest_age_s(self) -> Optional[float]:
        """Age of the oldest pending request (across every tenant queue),
        or None when empty — the doctor's queue-age watchdog input.
        Advisory read, one lock acquire."""
        return self._pending.oldest_age()

    def heartbeat(self) -> dict[str, Any]:
        """Round-liveness snapshot for the doctor's watchdogs: how long ago
        the last decode round completed, the recent p95 round time, and
        whether there is work the loop OUGHT to be making progress on."""
        # advisory snapshot of a deque the scheduler thread appends to
        durations = sorted(
            t["dispatch_ms"] + t["sync_wait_ms"] + t["host_emit_ms"]
            for t in locked_snapshot(self.round_timings))
        p95 = durations[int(0.95 * (len(durations) - 1))] if durations else 0.0
        return {
            "last_round_age_s": round(time.monotonic() - self.last_round_at, 3),
            "round_p95_ms": round(p95, 3),
            "rounds": self.decode_rounds,
            "active": self.active_slots,
            "prefilling": len(self._prefill_slots),
            "pending": self._pending.qsize(),
            "suspended": len(self._suspended),
            "oldest_pending_age_s": self.pending_oldest_age_s(),
            "broken": self._broken,
        }

    @staticmethod
    def _p50(samples: list) -> float:
        if not samples:
            return 0.0
        s = sorted(samples)
        return float(s[len(s) // 2])

    @staticmethod
    def _pq(samples: list, q: float) -> float:
        """Nearest-rank percentile (q in [0,1]) over a small sample list."""
        if not samples:
            return 0.0
        s = sorted(samples)
        return float(s[min(len(s) - 1, int(q * len(s)))])

    @staticmethod
    def _dispatch_by_kind(timings: list) -> dict[str, list[float]]:
        """Group round dispatch times by round kind (pure decode / mixed /
        prefill-only). Entries recorded before the kind field existed count
        as decode — the dominant kind in any steady-state window."""
        out: dict[str, list[float]] = {}
        for t in timings:
            out.setdefault(t.get("kind", "decode"), []).append(
                t["dispatch_ms"])
        return out

    def stats(self) -> dict[str, Any]:
        # snapshot collections the scheduler thread resizes (advisory
        # metrics — locked_snapshot degrades to empty, never raises)
        occ_samples = locked_snapshot(self.occupancy_samples)
        occ = sum(occ_samples) / max(1, len(occ_samples))
        timings = locked_snapshot(self.round_timings)
        waits = locked_snapshot(self.queue_wait_samples)
        resumes = locked_snapshot(self.resume_latency_samples)
        la = dict(self._lookahead_stats)  # fixed key set: updates, no resize
        depth_hist = locked_snapshot(self._depth_hist)
        per_kind = self._dispatch_by_kind(timings)
        sync_wait_p50 = round(self._p50(
            [t["sync_wait_ms"] for t in timings]), 3)
        pipeline = {
            "rounds": self.decode_rounds,
            "lookahead_rounds": self.lookahead_rounds,
            "overlap_ratio": round(
                self.lookahead_rounds / max(1, self.decode_rounds), 3),
            "admit_ms_p50": round(self._p50(
                [t["admit_ms"] for t in timings]), 3),
            "dispatch_ms_p50": round(self._p50(
                [t["dispatch_ms"] for t in timings]), 3),
            "sync_wait_ms_p50": sync_wait_p50,
            "host_emit_ms_p50": round(self._p50(
                [t["host_emit_ms"] for t in timings]), 3),
            "lookahead": la,
            # deep lookahead (the epoch ring): configured depth, achieved
            # depth histogram at drain time, what fraction of speculative
            # dispatches were thrown away, and how long the sanctioned drain
            # actually blocked (≈0 when the async D2H transfer won the race;
            # the records' sync_wait_ms under its older name)
            "depth": self._lookahead_depth,
            "depth_hist": {str(d): n
                           for d, n in sorted(depth_hist.items())},
            "discard_ratio": round(
                la["discarded"] / max(1, la["dispatched"]), 3),
            "readback_wait_ms_p50": sync_wait_p50,
            # mixed-batch chunked prefill (ragged kernel piggybacking)
            "mixed_rounds": self.mixed_rounds,
            "prefill_chunks": self.prefill_chunks,
            "chunked_prefill_tokens": self.chunked_prefill_tokens,
            # what the mixed dispatches computed, and the share of it that
            # was a token (a chunk's, or a decode row's): padding is the rest
            "mixed_positions": self.mixed_positions,
            "mixed_useful_share": round(
                self.mixed_useful_tokens / max(1, self.mixed_positions), 4),
            # per-round-kind dispatch-time breakdown: pure-decode rounds vs
            # mixed (decode + prefill chunks) vs prefill-only — the
            # attribution the PD-disaggregation claim rests on (a unified
            # pool's decode tail hides inside "mixed"/"prefill" here;
            # a decode-role engine must show only "decode"). Exported as
            # llm_round_dispatch_ms{kind,quantile}.
            "dispatch_ms_by_kind": {
                kind: {
                    "p50": round(self._pq(per_kind.get(kind, ()), 0.50), 3),
                    "p99": round(self._pq(per_kind.get(kind, ()), 0.99), 3),
                    "count": len(per_kind.get(kind, ())),
                }
                for kind in ("decode", "mixed", "prefill")
            },
        }
        accept_hist = locked_snapshot(self._spec_accept_hist)
        spec = dict(self.spec_stats)
        speculative = {
            "k": self.spec_k,
            **spec,
            "accept_rate": round(
                spec["accepted"] / max(1, spec["proposed"]), 3),
            "accept_hist": {str(a): n
                            for a, n in sorted(accept_hist.items())},
        }
        return {
            "broken": self._broken,
            "closed": self._closed,
            # tensor-parallel serving: mesh topology, tp degree, pool
            # sharding and the feasibility plan's per-device byte budget
            "mesh": self.mesh_info(),
            # batched speculative decoding: rounds that carried draft spans,
            # draft tokens proposed vs device-accepted, tokens emitted via
            # spec rounds, and the acceptance-length histogram
            "speculative": speculative,
            "prefix_cache": self.pool.stats() if self.pool is not None else None,
            "state_rows_in_use": self.state_rows_in_use(),
            "slots": self.n_slots,
            "active": self.active_slots,
            "prefilling": len(self._prefill_slots),
            "pending": self._pending.qsize(),
            "suspended": len(self._suspended),
            "preemptions": self.preemptions,
            "tokens_emitted": self.tokens_emitted,
            "requests_completed": self.requests_completed,
            "mean_occupancy": round(occ, 2),
            "pipeline": pipeline,
            "queue_wait_ms": {
                "p50": round(self._p50(waits), 3),
                "max": round(max(waits), 3) if waits else 0.0,
                "count": len(waits),
            },
            # queue saturation is now ATTRIBUTABLE: per-tenant pending
            # depth plus the observed drain rate the 429 Retry-After
            # derives from
            "queue": {
                "pending": self._pending.qsize(),
                "per_tenant": self._pending.depths(),
                "drain_rate_per_s": round(self._drain_rate_per_s(), 3),
                "retry_after_s": round(self._saturation_retry_after(
                    self._pending.qsize()), 1),
            },
            # tenant isolation: weights, live occupancy, virtual counters,
            # charged tokens, caps activity — the fairness ledger
            "tenants": self.tenant_snapshot(),
            "rejected_saturated": self.rejected_saturated,
            # end-to-end cancellation: terminals by reason + the decode
            # budget (max_tokens never generated) reclaimed for live users
            # (reason keys are inserted by the scheduler thread mid-copy)
            "cancellations": locked_snapshot(self.cancellations),
            "reclaimed_tokens": self.reclaimed_tokens,
            # preempt→resume recovery latency (the stream-pause a client
            # actually experiences); also exported device-wide as the
            # fault_recovery_seconds{point=scheduler.resume} histogram
            "resume_recovery_ms": {
                "p50": round(self._p50(resumes) * 1000.0, 3),
                "count": len(resumes),
            },
        }

    # ------------------------------------------------------------------ loop
    def _run_loop(self, stage: Any = None) -> None:
        startup.adopt(stage)
        logger.info("continuous scheduler up: %d slots, chunk %d, "
                    "lookahead depth %d",
                    self.n_slots, self._k_steps, self._lookahead_depth)
        with self._device_ctx():
            self._loop_body()

    def _loop_body(self) -> None:
        try:
            while True:
                try:
                    if self._stop.is_set():
                        # stopped between a drain and the launch its emit
                        # would have followed, or with a step launched ahead
                        # of its drain: the tokens are the streams', whoever
                        # ends them next
                        self._settle()
                        return
                    if not self._loop_pass():
                        self._clock.to("wait")
                        self._wake.wait(timeout=0.1)
                        self._wake.clear()
                except Exception as e:  # noqa: BLE001 — device errors must not hang clients
                    logger.exception("scheduler loop failed; failing in-flight requests")
                    self._broken = str(e)[:500]
                    self._fail_all_inflight("scheduler loop failed")
                    return
        finally:
            self._clock.close()

    def _loop_pass(self) -> bool:
        """One pass of the loop: the round boundary's bookkeeping, admission,
        one round. False when there was nothing to do (the loop then waits
        to be woken).

        A round is a launch half and a drain half, and a pass need not hold
        both halves of the same one. What may be in flight, or held, as a
        pass begins, one of:

        - the RING of decode chunks: admission waits for it (``_admit``),
          the round drains its oldest chunk and tops it up;
        - an UNDRAINED ``mixed_step`` (``self._mixed``: a prompt's next
          chunk, launched by the previous round behind the step it
          drained): the round starts at its drain half, having first
          queued what comes next behind it (``_decode_round_mixed``);
          a cancel or a preemption drains and commits it first
          (``_settle``), a resume waits a round for it;
        - the previous round's emit HELD (its drain left the device with
          nothing queued: ``_close_round``): the round this pass launches
          flushes it right behind its launch; a pass that launches nothing
          (no arrival after all, every row preempted, the loop about to
          wait) flushes it before it returns, so no token waits longer than
          one launch;
        - or nothing."""
        held = self._held
        # cancels/deadlines apply at the round boundary: BEFORE admission (a
        # lapsed pending entry must never take the slot this pass is about
        # to hand out)
        self._clock.to("service")
        self._service_cancellations()
        # tenant soft-quota sweep: pure bookkeeping (marks a yield victim;
        # the capacity pass performs the actual preempt)
        self._service_tenant_caps()
        if self._ring and not self.active.any():
            # no row runs (all finished or cancelled): nothing will drain
            # these chunks, and nothing is left to replay them for
            self._discard_ring()
        # holds everything back while the ring has chunks in flight
        self._clock.to("admit")
        admitted = self._admit()
        # prefilling slots are work too: mixed-batch rounds must run even
        # before any slot reaches decode phase
        ran = bool(self.active.any() or self._prefill_slots
                   or self._mixed is not None)
        if ran:
            self._decode_round()
        if held is not None and self._held is held:
            self._flush_held_emit()      # nothing was launched in front of it
            return True
        return ran or admitted > 0

    def _fail_all_inflight(self, why: str) -> None:
        """Error-terminate every in-flight, prefilling, suspended, and queued
        request — the shared teardown of the loop-crash path (``_broken`` set
        by the caller) and :meth:`close` (``_broken`` stays None: a closed
        engine is SPENT, not poisoned — lifecycle managers rebuild a fresh
        engine off its ``.params``). Single-threaded by construction: runs on
        the scheduler thread (crash) or after the thread joined (close).
        Tokens of a held emit were drained before the fault: they go out
        first, then the terminals. What is undrained is dropped, the ring's
        chunks and a ``mixed_step`` launched ahead of its drain alike: no
        stream is left to owe its tokens to, and after a fault inside an
        emit a later step's tokens would leave a gap in a stream that a
        pool's failover replays (the loop's clean stop has drained the step
        before it returns: ``_loop_body``)."""
        try:
            self._flush_held_emit()
        except Exception:  # noqa: BLE001 — the teardown must reach every stream
            logger.exception("the held emit failed during teardown")
        self._ring.clear()
        self._mixed = None
        with self._cancel_lock:
            # every in-flight/queued request gets its error terminal below;
            # a pending cancel for one of them must not re-fire later
            self._cancel_requests.clear()
        for slot in range(self.n_slots):
            state = self.slots[slot]
            if state is not None:
                # record BEFORE emit: the replica pool's failover
                # wrapper resubmits synchronously inside emit — the
                # terminal must close THIS attempt's record, not the
                # fresh one the resubmission just opened
                record_event(state.request_id, "error",
                             detail=why)
                try:
                    state.emit(StepEvent(0, -1, "error"))
                except Exception:
                    pass
                self.slots[slot] = None
        self.active[:] = False
        self._prefill_slots.clear()
        # preempted AND handed-off requests fail too. The POP runs under the
        # submit lock, paired with submit_handoff()'s locked append: a
        # racing handoff either lands before this drain (error terminal
        # below) or sees _closed/_broken under the same lock and raises —
        # a handed-off stream can never be stranded on a dead deque. Emits
        # run after the lock for the same ABBA reason as the queued drain.
        stranded_recs: list[_Suspended] = []
        with self._submit_lock:
            while self._suspended:
                stranded_recs.append(self._suspended.popleft())
        for rec in stranded_recs:
            record_event(rec.state.request_id, "error",
                         detail=f"{why} while suspended")
            try:
                rec.state.emit(StepEvent(0, -1, "error"))
            except Exception:
                pass
        # drain queued requests too — the POP runs under the submit lock, so
        # a racing submit() either lands its put before the pop (and gets
        # its error terminal below) or sees _closed/_broken under the same
        # lock and rejects: a client can never be stranded on a queue no
        # loop will serve. The EMITS run after the lock is released — a
        # pool's failover emit submits into ANOTHER engine's _submit_lock
        # (and sleeps its jittered backoff), so emitting under ours would
        # deadlock two same-round teardowns against each other (ABBA) and
        # block fast rejects behind the whole drain.
        self._soft_yield.clear()
        stranded: list[_Pending] = []
        with self._submit_lock:
            stranded.extend(self._pending.drain_all())
        for req in stranded:
            record_event(req.request_id, "error",
                         detail=f"{why} while queued")
            req.emit(StepEvent(0, -1, "error"))

    # ------------------------------------------------------------ slot accounting
    def _take_free_slot(self) -> Optional[int]:
        """O(1) slot allocation off the free-slot deque (the old O(n_slots)
        linear scan ran once per admission attempt)."""
        if not self._free_slots:
            return None
        return self._free_slots.popleft()

    def _release_free_slot(self, slot: int) -> None:
        # a pending soft-yield mark dies with the occupancy: the slot's next
        # owner (possibly another tenant) must not inherit the preempt
        self._soft_yield.discard(slot)
        self._free_slots.append(slot)

    def _reclaim_failed_admission(self, slot: int) -> bool:
        """After an admission exception: return the slot to the free deque
        ONLY if the slot holds no request. Releasing a slot that was marked
        live would hand it to a second request (stream hijack + leaked page
        chain). Returns True when the request was NOT admitted (caller
        should emit its error event)."""
        if self.active[slot] or self.slots[slot] is not None:
            return False  # the slot is serving
        if slot not in self._free_slots:  # first-token finish already freed it
            self._release_free_slot(slot)
        return True

    # ------------------------------------------------------------ device rows
    def _dev(self, x: Any) -> Any:
        """Host→device upload with an EXPLICIT destination: replicated over
        the serving mesh (tp > 1) or the plain default device. Every
        host-control upload in this engine routes through here — the
        host-owned rows (_sync_rows), a mixed step's lane, a resumed row — so
        a sharded-intent array can never be silently full-replicated by an
        implicit transfer, and control rows are guaranteed identical on
        every mesh device (the fabric-lint SH01 discipline)."""
        if self.mesh is not None:
            return jax.device_put(x, self._replicated)
        return jnp.asarray(x)

    def _set_slot_rows(self, slot: int, s: SamplingParams, stops: frozenset,
                       limit: int, gen_start: int = 0) -> None:
        """A slot's sampling and termination rows, written on the HOST
        (admission, resume); ``_sync_rows`` uploads them with the next
        dispatch. The first ``device_stop_width`` stop ids (-1 padded; sets
        that overflow fall back to host stop detection via _dev_term) and
        the length at which the row hits its max-tokens bound."""
        ctl = self._rows[slot, self._tw:]
        ctl[:3] = s.top_k, max(0, limit), gen_start
        self._warp[slot] = s.temperature, s.top_p
        ids = sorted(stops)[: self._stop_width]
        ctl[_CTL:] = -1
        ctl[_CTL: _CTL + len(ids)] = ids
        self._dev_term[slot] = len(stops) <= self._stop_width

    def _sync_rows(self, active: bool = True) -> None:
        """Ahead of a dispatch: upload, whole, the host-owned rows that
        changed since their last upload (the page table and the control
        rows are one block of a few KB; a copy goes up, so the host array
        stays free to change). A chained chunk and a mixed step take
        ``active`` elsewhere (their predecessor's, the lane's) and pass
        False. A finish, a cancel or a preemption writes nothing to the
        device: it clears ``self.active``, and a row that is not active
        neither runs nor stays finished nor keeps a length."""
        changed = False
        if not np.array_equal(self._rows, self._rows_up):
            changed = not np.array_equal(self._rows[:, self._tw:],
                                         self._rows_up[:, self._tw:])
            self._rows_up = self._rows.copy()
            self._rows_dev = self._dev(self._rows_up)
        if active and not np.array_equal(self.active, self._active_up):
            changed = True
            self._active_up = self.active.copy()
            self._active_dev = self._dev(self._active_up)
        if changed:
            bump_counter("llm_control_rows_uploads_total")

    # ------------------------------------------------------------ admission
    def _resume_suspended(self) -> int:
        """Restore preempted requests (FIFO) while slots AND pool space allow.
        Suspended requests outrank new admissions — their prefill is already
        paid and a client is mid-stream."""
        resumed = 0
        deferred: list[_Suspended] = []
        while self._suspended:
            if not self._free_slots:
                break
            rec = self._suspended[0]
            if rec.soft_yielded and self._defer_soft_yield(rec.state.tenant):
                # a soft-quota YIELD stays parked while other tenants have
                # pending work AND its tenant is still over the live cap —
                # resuming it then would hand the slot its preemption just
                # freed straight back to the over-quota tenant (suspended
                # outranks admission) and thrash preempt/restore without
                # the starved tenant ever admitting. The live re-judge
                # mirrors the mark's own: once the tenant's other usage
                # drops to the cap the stream resumes even under
                # contention (a yielded stream's stall is bounded by its
                # tenant's overshoot, never by another tenant's backlog).
                with self._submit_lock:
                    deferred.append(self._suspended.popleft())
                continue
            # armed raise here error-terminates the engine mid-recovery (the
            # faultlab resume-crash scenario asserts every client still gets
            # exactly one terminal event)
            failpoint("scheduler.resume")
            try:
                # PD handoff records land through the import half of the
                # export/import pair (same restore machinery: fresh private
                # pages, cast + re-sharded under THIS pool's sharding)
                if rec.handoff:
                    chain = self.pool.import_pages(rec.host_kv)
                elif self._has_state:
                    # the row comes back with the pages, into the slot the
                    # record will take (the take below pops this one)
                    chain = self.pool.restore_chain_from_host(
                        rec.host_kv, state_row=self._free_slots[0])
                else:
                    chain = self.pool.restore_chain_from_host(rec.host_kv)
                wchain = None
                try:
                    if self._two_groups:    # the window pages it held
                        wchain = self.pool.restore_window_from_host(
                            rec.host_kv[-1])
                        self.pool.extend_window(
                            wchain, rec.length + self._chunk_tokens)
                    self.pool.extend_chain(chain, rec.length + self._chunk_tokens)
                except MemoryError:
                    # give back the restored pages — a half-resume must not leak
                    self.pool.release_slot(chain)
                    if wchain:
                        self.pool.release_window(wchain)
                    raise
            except MemoryError:
                # Terminal-shed when the request can NEVER fit: either its
                # page need exceeds the whole pool, or the pool is idle and
                # still can't hold it.  Checking feasibility (not just
                # idleness) matters under sustained load — _admit keeps the
                # slots busy, so `active` may never empty, and an infeasible
                # suspended request would otherwise hang its client stream
                # and everyone FIFO-behind it while thrashing restore/release
                # of its host KV pages every cycle (round-2 advisory).
                pages_needed = self.pool.pages_for(rec.length + self._chunk_tokens)
                if (pages_needed > self.pool.capacity_pages
                        or not self.active.any()):
                    with self._submit_lock:
                        self._suspended.popleft()
                    reason = (
                        f"needs {pages_needed} pages > pool capacity "
                        f"{self.pool.capacity_pages}"
                        if pages_needed > self.pool.capacity_pages
                        else "cannot fit the idle pool")
                    logger.warning(
                        "request %s (len=%d) %s; finishing with 'length'",
                        rec.state.request_id, rec.length, reason)
                    rec.state.emit(StepEvent(0, -1, "length"))
                    record_event(rec.state.request_id, "finished",
                                 reason="length", shed=True,
                                 tokens=rec.state.emitted)
                    self.requests_completed += 1
                    continue
                break  # still no room; stay suspended
            with self._submit_lock:
                self._suspended.popleft()
            slot = self._take_free_slot()
            assert slot is not None  # guarded by the _free_slots check above
            state = rec.state
            state.chain, state.wchain = chain, wchain
            self.slots[slot] = state
            s = state.sampling
            if state.phase == "prefill":
                # a mid-chunked-prefill preempt: the slot re-enters the
                # prefill queue and keeps chunking from prefill_pos; its key
                # stream is still untouched (no sample happened yet)
                self.active[slot] = False
                self.lengths[slot] = 0
                self._set_slot_rows(
                    slot, s, state.stops,
                    self._token_limit(len(state.prompt_ids), s.max_tokens),
                    len(state.prompt_ids))
                self._prefill_slots.append(slot)
            else:
                self.active[slot] = True
                self.lengths[slot] = rec.length
                # limit re-derived from the resume point: L - emitted + max
                # equals the original prompt_len + max_tokens - 1 bound
                self._set_slot_rows(
                    slot, s, state.stops,
                    self._token_limit(len(state.prompt_ids), s.max_tokens)
                    if self._block
                    else rec.length - state.emitted + s.max_tokens,
                    len(state.prompt_ids or ()))
                # the row's device-advanced state comes back mid-stream:
                # ONE program, its values one upload (slot, length, key,
                # last token or open block)
                row = np.concatenate([
                    [slot, rec.length],
                    np.asarray(rec.slot_key, np.uint32).view(np.int32),
                    np.atleast_1d(rec.last_token)]).astype(np.int32)
                (self._last_tokens, self._slot_keys, self._lengths_dev,
                 self._finished_dev) = self._restore_row_fn(
                     self._last_tokens, self._slot_keys, self._lengths_dev,
                     self._finished_dev, self._dev(row))
                bump_counter("llm_loose_row_programs_total")
            self._tables[slot, :] = 0
            self.page_table[slot, : len(chain)] = chain
            if wchain is not None:
                self.window_table[slot, : len(wchain)] = wchain
            resumed += 1
            pause_s = time.monotonic() - rec.suspended_at
            if rec.handoff:
                # cross-engine PD handoff, not a recovery: it gets its own
                # flight-recorder verb (one request id, export on the
                # prefill engine + import here) and stays out of the
                # preemption/recovery latency stats — those measure pool
                # pressure, and a handoff pause is routing, not pressure.
                record_event(state.request_id, "handoff_import", slot=slot,
                             length=rec.length, pages=len(chain),
                             pause_ms=round(pause_s * 1000.0, 3))
            else:
                self.resume_latency_samples.append(pause_s)
                record_recovery("scheduler.resume", pause_s)
                record_event(state.request_id, "resumed", slot=slot,
                             phase=state.phase,
                             pause_ms=round(pause_s * 1000.0, 3))
            if state.trace_sampled and not rec.handoff:
                # the pause a client stream actually experienced, as a span
                # in the request's trace (backdated to the preemption)
                get_global_tracer().emit_span(
                    "llm.preempt", traceparent=state.trace,
                    start_unix_ns=int(rec.suspended_wall * 1e9),
                    duration_ms=pause_s * 1000.0,
                    request_id=state.request_id, slot=slot)
            token = set_log_context(state.request_id,
                                    traceparent_ids(state.trace)[0])
            try:
                logger.info("%s %s into slot %d (len=%d, paused %.3fs)",
                            "imported" if rec.handoff else "resumed",
                            state.request_id, slot, rec.length, pause_s)
            finally:
                reset_log_context(token)
        with self._submit_lock:
            for rec in reversed(deferred):  # restore FIFO head order
                self._suspended.appendleft(rec)
        return resumed

    def _other_tenant_pending(self, tenant: str) -> bool:
        """True while any OTHER tenant has pending (not-yet-admitted) work —
        the contention condition that keeps a soft-quota yield parked.
        Compares in the queue's own key space so the tenant-blind mode
        (one shared key) never reads its own backlog as contention."""
        key = self._pending._key(tenant)
        return any(t != key and depth > 0
                   for t, depth in self._pending.depths().items())

    def _defer_soft_yield(self, tenant: str) -> bool:
        """Should a soft-quota yield stay parked this pass? Only while the
        contention persists AND the tenant's CURRENT page usage still
        exceeds the soft cap — the same live re-judge the yield mark gets
        at consumption, so a tenant whose other streams finished resumes
        immediately instead of being starved by an unrelated backlog."""
        if not self._other_tenant_pending(tenant):
            return False
        soft = self.config.tenant_soft_pages
        return soft > 0 and \
            self._tenant_page_counts().get(tenant, 0) > soft

    def _admit(self) -> int:
        """Resume suspended streams, then admit pending requests into free
        slots in PREFILL phase. Admission does no device work: the round
        loop paces the prompts' chunks under ``prefill_budget_tokens``.

        Both WAIT FOR AN EMPTY RING, whatever the model: the chunks in
        flight are tokens the running rows are owed, and the device runs
        them before anything dispatched now, so dropping them gains the
        arrival nothing and costs every running row a chunk computed twice
        (with recurrent state it cannot even be replayed). The wait is
        bounded: ``_can_extend_ring`` stops deepening the ring while
        ``_admission_waiting``, so it is empty within ``decode_lookahead``
        further drains. The rows a slot's admission writes (on the host:
        ``_set_slot_rows``; its ``mixed_step`` uploads them and starts the
        device rows itself) are therefore never under a chunk in flight, and
        nothing here bumps ``_epoch``.

        The rule is unchanged by a HELD emit (``_close_round``): the round
        whose drain emptied the ring is drained and committed, only what
        its streams see of it waits, so the ring IS empty and the arrival
        is admitted, planned and launched ahead of those token events. What
        the held emit will free is not there yet: a slot (and the pages) of
        a row it finishes becomes free at the flush, one launch later, and
        is never handed out early.

        A ``mixed_step`` launched ahead of its drain (``self._mixed``: a
        prompt's next chunk, queued behind the step the last round drained)
        is not in the ring and holds an arrival back no more than a held
        emit does: the arrival is admitted now, as it was when that chunk's
        step was launched a pass later, and joins the lane behind the
        prompts already there (what admission writes is the host's, and a
        free slot is no row of the step in flight; a state row seeded from a
        snapshot is queued behind it). What that step's commit will bring
        is not there yet: the pages of a prompt whose final chunk it carries
        reach the prefix tree at its drain. A RESUME waits for that drain,
        one round: it writes the committed device carry, which the step in
        flight is ahead of (``_chains_mixed`` chains nothing meanwhile)."""
        if self._ring:
            if self._admission_waiting():
                bump_counter("llm_admission_ring_waits_total")
            return 0
        if self._mixed is not None and self._suspended:
            return 0
        failpoint("scheduler.admit")
        admitted = self._resume_suspended()
        taken: list[_Pending] = []
        popped = 0
        # tenants at their slot/page caps are skipped by the fair pop —
        # their requests stay queued, everyone else admits around them.
        # Slot counts update as this pass takes requests, so one pass can
        # never overshoot a tenant's cap with a burst.
        blocked = self._blocked_tenants()
        max_slots = self.config.tenant_max_slots
        tenant_taken = self._tenant_slot_counts() if max_slots else {}
        while len(taken) < len(self._free_slots):
            req = self._pending.pop_fair(blocked)
            if req is None:
                break
            popped += 1
            if req.deadline is not None:
                now = time.monotonic()
                # the estimate gate applies only while the engine is BUSY
                # (its point is shedding doomed work under pile-up): an
                # idle engine always admits — a wrong estimate then costs
                # one prefill, and the fresh observation keeps the rate
                # honest (a rejected request never prefills, so an
                # always-rejecting gate could never self-correct)
                busy = self.active.any() or bool(self._prefill_slots)
                if now >= req.deadline or (busy and (req.deadline - now) <
                        self._estimate_prefill_s(len(req.prompt_ids))):
                    # lapsed — or the remaining budget cannot even cover the
                    # estimated prefill: admitting would burn a slot and
                    # prefill compute to produce a guaranteed lapse. The
                    # request never occupies a slot.
                    self._cancel_finalize(
                        req.request_id, req.emit, "deadline",
                        "deadline_exceeded", phase="queued", emitted=0,
                        reclaimed=req.sampling.max_tokens,
                        trace=req.trace,
                        trace_sampled=traceparent_ids(req.trace)[1],
                        tenant=req.tenant)
                    continue
            taken.append(req)
            if max_slots:
                tenant_taken[req.tenant] = tenant_taken.get(req.tenant, 0) + 1
                if tenant_taken[req.tenant] >= max_slots:
                    blocked.add(req.tenant)
            wait_ms = (time.monotonic() - req.enqueued_at) * 1000.0
            self.queue_wait_samples.append(wait_ms)
            record_event(req.request_id, "admitted", tenant=req.tenant,
                         queue_wait_ms=round(wait_ms, 3))
        if popped:
            # drain-rate observation (requests that LEFT the queue this
            # pass, lapses included): the saturation Retry-After reads this
            self._admit_events.append((time.monotonic(), popped))
        if taken:
            admitted += self._place(taken)
        return admitted

    def _assign_keys(self, reqs: list[_Pending]) -> None:
        """Assign per-request key streams in FIFO order: a seeded request
        gets its own stream, the rest split the engine's in admission order.
        Key data made on the host (``ops/sampling.py``), the same words
        ``jax.random`` would give: no program is dispatched."""
        for req in reqs:
            if req.key is None:
                if req.sampling.seed is not None:
                    req.key = host_key(req.sampling.seed)
                else:
                    self._rng, req.key = host_split(self._rng)

    def _place(self, reqs: list[_Pending]) -> int:
        """Every taken request — cold or prefix-hit — claims a slot in
        PREFILL phase with zero device work; the round loop then piggybacks
        its prompt chunks into decode rounds. A prefix hit seeds the slot's
        chain with the cached pages, so only the uncached suffix is ever
        chunk-prefilled."""
        placed = 0
        self._assign_keys(reqs)
        for i, req in enumerate(reqs):
            slot = self._take_free_slot()
            if slot is None:  # unreachable: takes are bounded by free slots
                for dropped in reversed(reqs[i:]):
                    logger.error("no free slot for %s; requeueing",
                                 dropped.request_id)
                    self._pending.put_front(dropped)
                break
            try:
                self._admit_prefill_slot(slot, req)
                placed += 1
            except Exception:  # noqa: BLE001
                log_tok = set_log_context(req.request_id,
                                          traceparent_ids(req.trace)[0])
                try:
                    logger.exception("mixed admission failed for %s",
                                     req.request_id)
                finally:
                    reset_log_context(log_tok)
                if self._reclaim_failed_admission(slot):
                    record_event(req.request_id, "error",
                                 detail="mixed admission failed")
                    try:
                        req.emit(StepEvent(0, -1, "error"))
                    except Exception:  # noqa: BLE001 — emit may be the fault
                        pass
                else:
                    placed += 1
        return placed

    def _admit_prefill_slot(self, slot: int, req: _Pending) -> None:
        """Claim a slot for chunked prefill. The chain starts as the prefix
        cache's matched pages (slot-ref'd so tree eviction orphans rather
        than frees them — the existing ref/orphan machinery); private pages
        are allocated chunk-by-chunk as prefill progresses."""
        # armed raise exercises the failed-admission reclaim path: _place
        # catches, reclaims the slot, and error-terminates only this request
        failpoint("scheduler.prefill")
        cached_pages, cached_len = self.pool.match_prefix(req.prompt_ids)
        chain = list(cached_pages)
        if chain:
            # refs (not the radix pin) protect the pages from here on
            self.pool.ref_pages(chain)
            # recurrent state: the snapshot at the hit's end is the row's
            self.pool.seed_state_row(slot, chain)
        # LOAD-BEARING for chain == [] too: a fully-cached prompt matches
        # (and pins) tree nodes but match_prefix trims its page list to
        # empty — this release is the only unpin for those nodes
        self.pool.release(req.prompt_ids)
        s = req.sampling
        try:
            state = _SlotState(
                request_id=req.request_id,
                emit=req.emit,
                sampling=s,
                stops=frozenset(s.stop_token_ids)
                | frozenset(self.config.eos_token_ids),
                chain=chain,
                wchain=[] if self._two_groups else None,
                trace=req.trace,
                trace_sampled=traceparent_ids(req.trace)[1],
                phase="prefill",
                prompt_ids=list(req.prompt_ids),
                prefill_pos=cached_len,
                cached_len=cached_len,
                prefill_key=req.key,
                prefill_t0=time.monotonic(),
                prefill_wall=time.time(),
                deadline=req.deadline,
                tenant=req.tenant,
            )
            self.slots[slot] = state
            self.lengths[slot] = 0
            self._tables[slot, :] = 0
            self.page_table[slot, : len(chain)] = chain
            self._set_slot_rows(
                slot, s, state.stops,
                self._token_limit(len(req.prompt_ids), s.max_tokens),
                len(req.prompt_ids))
        except Exception:
            self.pool.release_slot(chain)
            self._tables[slot, :] = 0
            self.slots[slot] = None
            raise
        self._prefill_slots.append(slot)

    def _arm_spec(self, state: _SlotState, prompt_ids: list[int]) -> None:
        """Arm per-stream speculation at decode activation (the final
        chunk's flip, and a PD handoff). Eligibility: greedy only —
        verification is argmax equality, so acceptance is lossless — and the
        request's token limit must fire before the window bound ever could
        (limit + decode_chunk ≤ max_seq): a window-bound stream's "length"
        finish lands on a k=0 chunk boundary, which speculation's variable
        advance would move, so those streams simply never speculate. The
        proposer is seeded with the prompt; _emit_token feeds it every
        emitted token from the first one on."""
        if not self.spec_k:
            return
        s = state.sampling
        if s.temperature != 0.0:
            return
        if len(prompt_ids) + s.max_tokens - 1 + self._k_steps \
                > self.config.max_seq_len:
            return
        proposer = NgramProposer(self.config.spec_max_ngram,
                                 self.config.spec_min_ngram, self.spec_k)
        proposer.extend(list(prompt_ids))
        state.proposer = proposer

    def _emit_token(self, slot: int, tok: int, force_length: bool = False) -> None:
        state = self.slots[slot]
        assert state is not None
        if state.proposer is not None:
            # proposer feeding: every emitted token extends this stream's
            # ngram index, so the next round's proposals come from the live
            # emitted history (prompt-lookup decoding)
            state.proposer.extend([tok])
        state.emitted += 1
        # decode charge: one actually-emitted token against the tenant's
        # virtual counter (plain dict math — AS04/WD01 clean)
        self._charge_tenant(state.tenant, 1)
        if tok in state.stops:
            fin: Optional[str] = "stop"
        elif state.emitted >= state.sampling.max_tokens:
            fin = "length"
        elif force_length:
            fin = "length"
        else:
            fin = None
        state.emit(StepEvent(0, tok, fin))
        self.tokens_emitted += 1
        if fin is not None:
            record_event(state.request_id, "finished", reason=fin,
                         tokens=state.emitted)
            self.active[slot] = False
            self.slots[slot] = None
            self.requests_completed += 1
            self._release_free_slot(slot)
            if fin == "stop" and not self._dev_term[slot]:
                # host-fallback stop (set overflowed device_stop_width): the
                # device kept the row running, so every in-flight ring chunk
                # diverged from host truth — stale, discard via the epoch.
                # Device-predicted finishes (stop within width, max-tokens,
                # window) deliberately do NOT bump: the decode program froze
                # the row, so the ring stays valid and overlap survives the
                # finish — the whole point of device-side termination.
                self._epoch += 1
            if state.chain is not None:
                self._release_chains(state)
                self._tables[slot, :] = 0

    # ------------------------------------------------------------ decode round
    def _ensure_chunk_capacity(self, horizon: Optional[int] = None) -> None:
        """Before a chunk, every active slot's chain must cover its
        length + horizon tokens (a chunk may cross a page boundary mid-flight;
        page allocation is host-side, so it happens here, never inside jit).
        With an N-deep lookahead ring the horizon is (N+1)·k so every
        speculative chunk's positions are covered too. Slots the pool cannot
        serve are preempted to host and resumed by _admit when space frees; a
        request even an idle pool can't hold is terminal-shed there (bounded —
        no infinite retry)."""
        horizon = horizon if horizon is not None else self._chunk_tokens
        for slot in range(self.n_slots):
            state = self.slots[slot]
            if state is None or not self.active[slot]:
                continue
            if slot in self._soft_yield:
                # tenant soft-quota yield marked by the round-boundary cap
                # sweep: the actual preempt (device readback + host save)
                # runs HERE, where preemption already lives — re-judged
                # against the live cap so a stale mark cannot evict a
                # tenant that already shrank below its quota
                self._soft_yield.discard(slot)
                soft = self.config.tenant_soft_pages
                if soft > 0 and self._tenant_page_counts().get(
                        state.tenant, 0) > soft:
                    self._preempt_slot(slot, state, soft_yielded=True)
                    continue
            try:
                # an armed MemoryError here forces the preempt-to-host path
                # without real pool pressure (deterministic faultlab preempt
                # scenarios; streams must stay bit-identical across it)
                self._chain_pressure_check()
                self._grow_chain(slot, state, horizon)
            except MemoryError:
                self._preempt_slot(slot, state)

    def _chain_pressure_check(self) -> None:
        """The ``scheduler.page_alloc`` failpoint, shared by every page-chain
        growth path — the capacity sweep (per active slot), ring extension,
        and mixed ring spanning. An armed MemoryError forces the
        preempt-to-host / ring-cap paths with no real pool pressure; one
        literal call site keeps FP01's name↔site mapping 1:1."""
        failpoint("scheduler.page_alloc")

    def _extend_chain_to(self, slot: int, state: _SlotState,
                         target: int) -> None:
        """Speculative-path chain growth (ring extension / mixed spanning):
        grow one slot's chain to cover ``target`` tokens and patch its
        page-table rows; no-op when already covered. Raises MemoryError on
        real pool pressure or an armed scheduler.page_alloc — callers cap
        the ring/span instead of preempting (the next synchronous round's
        capacity sweep preempts properly)."""
        if self.pool.pages_for(target) <= self._pages_held(state):
            return
        self._chain_pressure_check()
        self._grow_tables(slot, state, target)

    @staticmethod
    def _pages_held(state: _SlotState) -> int:
        """Pages of a slot's table that BOTH its page groups cover: what the
        capacity guards compare a need with (``_grow_tables`` keeps the two
        chains as long as each other)."""
        held = len(state.chain)
        return held if state.wchain is None else min(held, len(state.wchain))

    def _grow_tables(self, slot: int, state: _SlotState, tokens: int) -> None:
        """Extend a slot's chain, and its window chain where the model has
        a window page group, to cover ``tokens`` and patch its page-table
        rows. MemoryError is either group's, and leaves BOTH chains as they
        were: the window group's pages are taken first (its allocator gives
        all or nothing and has no tree to evict from) and handed back where
        the full group then has none, so a row never runs with one group's
        table short of its tokens."""
        chain, wchain = state.chain, state.wchain
        before = len(chain)
        if wchain is not None:
            wbefore = len(wchain)
            self.pool.extend_window(wchain, tokens)
        try:
            self.pool.extend_chain(chain, tokens)
        except MemoryError:
            if wchain is not None:
                self.pool.release_window(wchain, keep=wbefore)
            raise
        self.page_table[slot, before: len(chain)] = chain[before:]
        if wchain is not None:
            self.window_table[slot, wbefore: len(wchain)] = wchain[wbefore:]

    def _release_chains(self, state: _SlotState) -> None:
        """A slot leaves: both its chains' pages go back."""
        self.pool.release_slot(state.chain)
        if state.wchain is not None:
            self.pool.release_window(state.wchain)

    def _trim_windows(self) -> None:
        """After a commit (a decode chunk's, a mixed step's): every row of a
        model with a window page group gives back the window pages that lie
        left of the window of its COMMITTED length (``prefill_pos`` of a
        prompt still in chunks). Every step in flight or launched later has
        its queries at or past that length, and runs before any later step
        that writes the page for its next owner (runtime/paged.py)."""
        if not self._two_groups:
            return
        for slot, state in enumerate(self.slots):
            if state is None or state.wchain is None:
                continue
            length = (state.prefill_pos if state.phase == "prefill"
                      else int(self.lengths[slot]))
            if self.pool.trim_window(state.wchain, length):
                self.window_table[slot, : len(state.wchain)] = state.wchain

    def _grow_chain(self, slot: int, state: _SlotState, horizon: int) -> None:
        """Extend one slot's chain to cover length + horizon. Raises
        MemoryError only when even the MANDATORY chunk (length + k) cannot be
        covered — the caller preempts then."""
        assert state.chain is not None
        L = int(self.lengths[slot])
        needed = min(L + horizon, self.config.max_seq_len)
        if self.pool.pages_for(needed) <= self._pages_held(state):
            return
        try:
            self._grow_tables(slot, state, needed)
            return
        except MemoryError:
            # the deep-lookahead horizon is OPPORTUNISTIC — a slot that can
            # still cover its mandatory chunk must not be preempted for it
            # (preempting on the optimistic ask would livelock: resume only
            # restores length+k, the next round asks the ring horizon again,
            # and the request round-trips its KV forever without a token)
            mandatory = min(L + self._chunk_tokens, self.config.max_seq_len)
            if self.pool.pages_for(mandatory) <= self._pages_held(state):
                return  # enough for the chunk; lookahead will just skip
        self._grow_tables(slot, state, mandatory)  # MemoryError → preempt

    def _preempt_slot(self, slot: int, state: _SlotState,
                      soft_yielded: bool = False) -> None:
        """Preempt-to-host, don't shed: save the chain's KV, free the pages,
        and park the request — _admit resumes it when space frees (no
        recompute; the stream pauses, never errors). Works mid-chunked-
        prefill too: the saved pages cover prefill_pos tokens and chunking
        continues from there on resume. ``soft_yielded`` marks a tenant
        soft-quota yield: resume defers it while other tenants have pending
        work (see _resume_suspended). What is in flight or held is settled
        first (``_settle``: a step launched ahead of its drain is drained
        and committed, a held emit flushed): the record parks the stream as
        its client has seen it (``emitted``, the last token) at the lengths
        the device has reached, and a row those tokens finished has nothing
        to park."""
        if self._settle() and self.slots[slot] is not state:
            return
        chain = state.chain
        is_prefill = state.phase == "prefill"
        length = state.prefill_pos if is_prefill else int(self.lengths[slot])
        token = set_log_context(state.request_id,
                                traceparent_ids(state.trace)[0])
        try:
            logger.warning("pool exhausted; preempting %s to host "
                           "(%s len=%d, %d pages)", state.request_id,
                           state.phase, length, len(chain))
        finally:
            reset_log_context(token)
        record_event(state.request_id, "preempted", slot=slot,
                     phase=state.phase, length=length)
        host_kv = (self.pool.save_chain_to_host(chain, state_row=slot)
                   if self._has_state else self.pool.save_chain_to_host(chain))
        if state.wchain is not None:    # the window pages it still holds
            host_kv += (self.pool.save_window_to_host(state.wchain),)
        self._drop_pending_snapshots(state)
        with self._submit_lock:
            self._suspended.append(_Suspended(
                state=state, host_kv=host_kv,
                length=length,
                last_token=0 if is_prefill
                else np.asarray(self._last_tokens)[slot].copy() if self._block
                else int(np.asarray(self._last_tokens)[slot]),
                slot_key=None if is_prefill
                else np.asarray(self._slot_keys)[slot],
                soft_yielded=soft_yielded))
        self.preemptions += 1
        if is_prefill:
            self._prefill_slots.remove(slot)
        self.active[slot] = False
        self.slots[slot] = None
        self._release_free_slot(slot)
        self._epoch += 1
        self._release_chains(state)
        self._tables[slot, :] = 0

    def _drop_pending_snapshots(self, state: _SlotState) -> None:
        """A prompt that leaves its slot before its commit (preempted,
        cancelled) gives back the snapshot rows it took on the way."""
        if state.state_snapshots:
            self.pool.drop_snapshot_rows(
                [row for _, row in state.state_snapshots])
            state.state_snapshots = []

    def _dispatch_chunk(self, after: Optional[_InflightChunk]) -> _InflightChunk:
        """One fused-chunk dispatch (async — the return holds futures).
        ``after`` chains the dispatch onto a still-unread ring entry's device
        outputs — that is the N-deep lookahead. The chunk's device→host
        transfer is STARTED here, non-blocking (copy_to_host_async is a
        transfer enqueue, not a sync — AS04-clean by design): by the time the
        drain's sanctioned sync point reads the oldest chunk, its bytes have
        usually already landed host-side."""
        self._clock.to("upload")
        self._sync_rows(active=after is None)
        self._clock.to("launch")
        if after is None:
            last, keys, lengths, fin, active = (
                self._last_tokens, self._slot_keys, self._lengths_dev,
                self._finished_dev, self._active_dev)
        else:
            last, keys, lengths, fin, active = (
                after.last, after.keys, after.lengths_dev,
                after.finished_dev, after.active_dev)
        chunk_dev, *outs = self._paged_decode_fn(
            self.params, *self.pool.cache_operands(), self._rows_dev, last,
            lengths, active, fin, keys)
        self._clock.to("launch", starved=False)  # the device has work
        last_o, keys_o, lens_o, fin_o = self.pool.adopt(outs)
        try:
            chunk_dev.copy_to_host_async()  # non-blocking D2H start
        except AttributeError:  # non-jax.Array backends (tests/stubs)
            pass
        bump_counter("llm_decode_chunks_dispatched_total")
        return _InflightChunk(chunk_dev, last_o, keys_o, lens_o, fin_o,
                              active, self._epoch)

    def _admission_waiting(self) -> bool:
        """A slot is free and a request is pending or suspended: ``_admit``
        takes it as soon as the ring is empty."""
        return bool(self._free_slots) and (
            bool(self._suspended) or not self._pending.empty())

    def _can_extend_ring(self) -> bool:
        """Chain one more speculative chunk off the ring tail only when the
        speculation is likely to survive: no admission/resume is waiting for
        the ring to empty (deepening it would starve the arrival), no prompt
        chunks are pending (a mixed round would be next),
        and every active chain pre-extends to cover the deeper horizon
        WITHOUT preempting (a failed extension just caps the ring depth; the
        next synchronous round preempts properly). Predictable finishes
        (max-tokens, window) no longer cap the ring — the decode program's
        device-resident finished mask freezes those rows in place — and
        stop-token finishes are device-matched too when the stop set fits
        ``device_stop_width``; only host-fallback stops still discard, via
        the epoch check at drain time."""
        if self._stop.is_set() or not self._ring:
            return False
        if self._ring[-1].epoch != self._epoch:
            return False
        if self._prefill_slots:
            # pending prompt chunks: the next round is a mixed round, not the
            # speculated pure-decode chunk — deterministic fallback to sync
            return False
        if self._admission_waiting():
            return False  # _admit holds it back until this ring is empty
        if self.spec_k and self._spec_round_safe() and self._spec_candidates():
            # live draft proposals: stop deepening the ring so it drains and
            # the next dispatch speculates instead — a k-token verify span
            # beats a chained plain chunk on the same traffic
            return False
        k = self._chunk_tokens
        horizon = (len(self._ring) + 1) * k
        max_seq = self.config.max_seq_len
        for slot in range(self.n_slots):
            state = self.slots[slot]
            if state is None or not self.active[slot]:
                continue
            L = int(self.lengths[slot])
            try:
                self._extend_chain_to(slot, state, min(L + horizon, max_seq))
            except MemoryError:
                return False  # cap the ring; a sync round preempts later
        return True

    def _discard_ring(self) -> None:
        """Drop every still-undrained ring entry (the stale suffix of the
        pipeline — chunks already drained were committed and emitted).
        Callers: what cannot wait for the ring and can be replayed — the
        epoch checks of a decode round (a preemption or a host-fallback stop
        bumped ``_epoch``) and of every emit (``_emit_round``: at once or
        flushed behind a launch, a decode round's or a mixed one's), and
        ``_loop_pass`` when no running row is left to drain it. Admission
        and resume never come here: they wait (``_admit``). Only DECODE
        chunks are ever in the ring: a ``mixed_step`` launched ahead of a
        held emit is not, so nothing here can drop a prompt's chunk
        (``_close_round``, rule 1).
        Committed state (last_tokens / keys / lengths / finished) was never
        advanced past the last drained chunk, so nothing needs restoring; a
        discarded chunk's only lasting effect is KV written past every
        committed length — rewritten identically by the synchronous fallback
        for surviving slots, masked by attention-length bounds, or fully
        rescattered by the next owner of a freed slot's pages."""
        if self._has_state and self.active.any():
            # a chunk in flight has ADVANCED each running row's recurrent
            # state, and a replay from the committed lengths would advance it
            # again (K/V past a committed length is rewritten; state is
            # not). So a stale ring is never dropped while a row runs: the
            # rounds that follow drain it (it is not extended:
            # _can_extend_ring checks the epoch), rows the host finished are
            # masked out of the emit, and _admit takes nothing until it is
            # empty. This is the one rule of the ring that asks whether the
            # model has state.
            return
        self._lookahead_stats["discarded"] += len(self._ring)
        bump_counter("llm_decode_chunks_discarded_total", n=len(self._ring))
        self._ring.clear()
        # nothing launched is undrained any more (the device may still be
        # running what was dropped: _PhaseClock says what that costs)
        self._clock.to(self._clock.phase, starved=True)

    def _commit_chunk(self, rec: _InflightChunk,
                      commits: Optional[np.ndarray] = None) -> np.ndarray:
        """Adopt a drained chunk's device outputs as committed state; advance
        the host length mirror. Returns the pre-chunk lengths for the emit
        loop. The active mask is NOT committed (it is an input the chunk never
        modifies — committing it would resurrect rows the host finished while
        the chunk was in flight). Active slots advance by k; inactive slots
        pin to 0 so their garbage positions never run past the rope table /
        page chain bounds. ``commits`` (a block model): the blocks each row
        committed in the chunk; a row advances by those, not by k."""
        self._last_tokens = rec.last
        self._slot_keys = rec.keys
        self._lengths_dev = rec.lengths_dev
        self._finished_dev = rec.finished_dev
        old_lengths = self.lengths.copy()
        advance = self._k_steps if commits is None else commits * self._block
        self.lengths = np.where(self.active, self.lengths + advance,
                                0).astype(np.int32)
        self._trim_windows()
        return old_lengths

    def _close_round(self, emit: Callable[[], Optional[tuple[int, int]]],
                     hold: bool = True, **record: Any) -> None:
        """The end of a round, from its commit on: ``emit`` hands the
        drained tokens to their streams, then ``_record_round(**record)``
        closes the pass. ONE emit routine, run at one of two points, by what
        the drain left behind:

        - something still in flight (a steady decode round, a mixed step
          with chunks chained off it, or with the next prompt chunk's step
          launched off it before this drain: ``self._mixed``): at once. The
          device works under it.
        - NOTHING UNDRAINED (the ring's last chunk ahead of an arrival, a
          prompt's last known chunk ahead of one, a step whose successor
          could not be known before its drain, an engine with no lookahead):
          the device would wait out every token event, so the emit is HELD
          and the loop goes on to what it does next anyway: ``_admit`` off
          the empty ring, plan, upload and launch of the arrival's or the
          next chunk's ``mixed_step`` (``_mixed_ring_span`` behind it) or of
          the resync decode chunk, which flushes it right behind that launch
          and before its own drain (``_flush_held_emit``). This is the
          speculation the ring already makes, a chunk launched before the
          host has emitted the one in front of it, applied to the one launch
          that used to wait. What carries it is the same: the device's
          finished mask freezes a row that ends by length, window or a
          device-matched stop, and a host-fallback stop found in the flushed
          emit bumps ``_epoch``.

        Two rules, because the step launched ahead may carry a prompt's
        chunk. (1) A ``mixed_step`` launched ahead, of a held emit or (one
        case more: ``_chains_mixed``) of the DRAIN of the step before it, is
        NEVER DISCARDED (a chunk's K/V and, with recurrent state, its state
        advance are not replayable): it is not in the ring, its drain
        commits it whatever an emit found in the meantime, and rows such an
        emit finished are masked out of its own emit (its decode rows are
        read at its commit), as ``_discard_ring`` already does for every
        model with state. A host-fallback stop found in a step's emit cannot
        un-run the step chained behind it: the row is masked out there.
        Decode chunks launched ahead follow the ring's rule.
        (2) Whatever reads or ends a stream on the host SETTLES FIRST
        (``_settle``: drain and commit the step in flight, flush the held
        emit): ``_service_cancellations`` for a slot it ends,
        ``_preempt_slot``, the loop's stop; a pass that launches nothing
        flushes, as does a speculating engine before ``_spec_candidates``
        is asked (``_decode_round``; it chains no step); an emit that
        itself hands a row off to another engine is not held at all
        (``hold`` False) and no step is chained off its step; and
        ``_fail_all_inflight`` flushes what was drained and drops what was
        not. A slot freed by a finish in a held emit is free at the flush,
        one launch later: ``_admit`` never sees it early, and its rule
        (wait for an empty ring) is unchanged, since the held round IS
        drained and committed."""
        in_flight = bool(self._ring) or self._mixed is not None
        if not in_flight:
            bump_counter("llm_drains_ring_empty_total")
        if in_flight or not hold:
            self._emit_round(emit, record)
            return
        # the record's pass ends here; the emit's time is the next pass's
        self._held = _HeldEmit(emit, record, self._clock.take())
        self.last_round_at = self._held.clock[2]

    def _settle(self) -> bool:
        """Rule 2 of ``_close_round``, for whatever is about to read or end
        a stream on the host: DRAIN AND COMMIT the ``mixed_step`` in flight
        (launched behind the one the last round drained), then flush the
        emit that is held (that step's own, now). Decode chunks in the ring
        are not drained here: they follow the ring's rules. Returns whether
        anything was emitted. The clock comes back to the caller's phase."""
        step, self._mixed = self._mixed, None
        if step is None:
            return self._flush_held_emit()
        back = self._clock.phase
        self._commit_mixed(step)        # nothing is in flight: its emit is held
        self._flush_held_emit()
        self._clock.to(back)
        return True

    def _flush_held_emit(self, behind_launch: bool = False) -> bool:
        """Run the held emit, if there is one, and close its round's record.
        ``behind_launch``: the caller has just queued the next program, which
        is what the emit was held for (counted); every other caller flushes
        FIRST, before it reads or ends a stream. Returns whether anything
        was held. The clock comes back to the caller's phase."""
        held, self._held = self._held, None
        if held is None:
            return False
        if behind_launch:
            bump_counter("llm_emits_deferred_total")
        back = self._clock.phase
        self._emit_round(held.emit, held.record, held.clock)
        self._clock.to(back)
        return True

    def _emit_round(self, emit: Callable[[], Optional[tuple[int, int]]],
                    record: dict[str, Any],
                    clock: Optional[tuple] = None) -> None:
        self._clock.to("emit")
        block_out = emit()
        # a host-fallback stop in there changed the world: the chunks in
        # flight are stale (device-predicted finishes leave the epoch alone,
        # so the ring survives them; that is the deep-lookahead win)
        if self._ring and self._ring[0].epoch != self._epoch:
            self._discard_ring()
        self._record_round(block_out=block_out, clock=clock, **record)

    def _record_round(self, lookahead: bool,
                      ts: Optional[float] = None,
                      mixed: bool = False,
                      chunk_tokens: int = 0,
                      depth: int = 0,
                      spec_tokens: int = 0,
                      kind: str = "decode",
                      positions: Optional[int] = None,
                      block_out: Optional[tuple[int, int]] = None,
                      counted: Optional[dict] = None,
                      chained: bool = False,
                      clock: Optional[tuple] = None) -> None:
        """One timing-schema owner for every round kind. ``ts`` is the
        round's wall-clock start; /v1/monitoring/rounds exports these entries
        as Chrome trace events, which need absolute timestamps.
        ``positions`` is what the dispatch computed: ``B + R*Qc`` for a lane
        step, ``B x Qmax`` for an all-rows (speculative) one, and ``B`` a
        step for a decode round (the default). ``depth``: what the device
        had queued behind the drained dispatch while the host emitted it
        (decode chunks in the ring, a mixed step's chained successor: 0 is
        a drain that left nothing undrained); ``chained``: a mixed step
        that was itself launched off a still-undrained step.

        The record closes the PASS: ``phases`` is the scheduler thread's
        time since the previous record by phase, ``[wall_ms, cpu_ms,
        starved_ms]`` each (``_PhaseClock``), and ``pass_ms`` their walls'
        sum, so consecutive records add up to the thread's time; passes that
        ran no round are in the next record's ``wait`` / ``service`` /
        ``admit``. The four stage fields are sums over the phases they
        cover. ``clock``: the phases of a round whose emit was held, taken
        where its pass ended (the emit's time is in the record of the pass
        that flushed it, behind that pass's ``launch``)."""
        if clock is None:
            clock = self._clock.take()
            self.last_round_at = clock[2]
        phases, pass_ms, _, compiled = clock

        def wall_ms(*of: str) -> float:
            return round(sum(phases[p][0] for p in of if p in phases), 3)

        if positions is None:
            positions = self.n_slots * self._k_steps * self._step_tokens
        self.decode_rounds += 1
        if lookahead:
            self.lookahead_rounds += 1
        if mixed:
            self.mixed_rounds += 1
            self.mixed_positions += positions
            self.mixed_useful_tokens += chunk_tokens + self.active_slots
        self.round_timings.append({
            "ts": round(ts if ts is not None else time.time(), 6),
            "admit_ms": wall_ms("admit"),
            "dispatch_ms": wall_ms("capacity", "plan", "upload", "launch"),
            "sync_wait_ms": wall_ms("drain"),
            "host_emit_ms": wall_ms("commit", "emit"),
            "phases": phases,
            "pass_ms": pass_ms,
            "lookahead": lookahead,
            "mixed": mixed,
            # round kind for the dispatch-time attribution: "decode" (pure
            # decode rows), "mixed" (decode + prefill chunks in one ragged
            # dispatch), "prefill" (only prefill chunks — the prefill-role
            # engine's steady state, and the unified pool's storm rounds)
            "kind": kind,
            "chunk_tokens": chunk_tokens,
            "positions": positions,
            "depth": depth,
            "chained": chained,
            "spec_tokens": spec_tokens,
            "active": self.active_slots,
            # a block model: the forwards of the round's dispatch, and the
            # blocks and tokens the host took from it
            **({"forwards": 1 if kind != "decode" or mixed
                else self._k_steps, "blocks_committed": block_out[0],
                "tokens_emitted": block_out[1]} if block_out else {}),
            # what the dispatch's forwards counted (``_take_step_counters``).
            # A chip's share of the experts: ``local_assignments``, the
            # routed assignments that fell on experts held here; a looped
            # model: ``loop_steps``, ``passes`` run and ``exit_pass_mean``
            **(counted or {}),
            # the pass compiled (a first use of a program, or a recompile on
            # the request path): the seconds and the programs' names
            **({"compile_ms": round(1e3 * sum(compiled.values()), 3),
                "compiled": sorted(compiled)} if compiled else {}),
        })

    def _count_ragged_walk(self, hist: np.ndarray, q_lens: np.ndarray,
                           width: int) -> dict:
        """/metrics of the ragged kernel's walk over a mixed step's lanes
        (spans of ``q_lens`` queries behind ``hist`` tokens, ``width`` wide):
        the pages its programs copy (of one pool: a K/V kernel copies a
        page's K and V together) and the trips (key blocks: one score dot a
        kv head each) they attend over them in, summed over the layers, each
        kind of layer by its own window and query heads. Pages a trip near
        ``ragged_trip_pages`` say the key blocks run full. Counted at the
        dispatch from the lane's operands by the kernels' own
        ``ragged_walk`` (``ops/page_walk.py``: the latent kernel's and the
        K/V kernel's walk is one; the q-block and the trip are each
        kernel's own rule)."""
        cfg = self.model_config
        # a model with a window page group: its full layers, then its window
        # layers; one window for every layer otherwise. The query heads pick
        # the K/V kernel's q-block (a shard's, under a mesh)
        kinds = [(cfg.kv_layers, cfg.sliding_window, cfg.num_heads)]
        if self._two_groups:
            kinds = [(cfg.kv_layers, None, cfg.num_heads),
                     (cfg.window_layers, cfg.sliding_window,
                      cfg.window_heads)]
        lanes = (hist, q_lens, width, self.config.prefix_page_size, self.pmax)
        if cfg.is_latent:
            from ..ops.mla_attention import ragged_walk

            def walked(window, heads):
                return ragged_walk(*lanes, window)
        else:
            from ..ops.paged_attention import ragged_walk

            def walked(window, heads):
                return ragged_walk(*lanes, window, heads // self.tp,
                                   cfg.block_length)
        pages = trips = 0
        for layers, window, heads in kinds:
            n_pages, n_trips = walked(window, heads)
            pages += layers * n_pages
            trips += layers * n_trips
        bump_counter("llm_ragged_pages_walked_total", n=pages)
        bump_counter("llm_ragged_trips_total", n=trips)
        return {"ragged_pages": pages, "ragged_trips": trips}

    def _count_attn_pages(self, kept: np.ndarray, grew: np.ndarray) -> dict:
        """/metrics of the decode kernel's walk over a drained dispatch: the
        pages it walked (those that hold tokens a row's query reads) and the
        groups it took them in (``decode_page_group``: a trip each of the
        kernel's walk, which runs one program a row), beside the page
        table's slots, summed over forwards and layers. ``kept`` [B]:
        each row's length going in; ``grew`` [B, forwards]: whether that
        forward added a step's tokens to it (a frozen row stops growing). A
        row that does not run sits at length 0 on the device and is counted
        as the one page and the one group a program costs. Counted from the
        host's mirror by the kernel's own :func:`page_span`, so a step pays
        nothing for it. Returns what the round's record and its
        ``llm.decode_chunk`` spans say of a model with a window page group
        (nothing of another): the ``full_pages`` and ``window_pages`` walked,
        and the ``window_pages_freed`` since the last record."""
        from ..models.llama import decode_page_group
        from ..ops.paged_attention import page_span

        step = self._step_tokens
        lengths = (np.where(self.active, kept, 0)[:, None]
                   + step * (np.cumsum(grew, axis=1) - grew + 1))
        slots = self.page_table.shape[1]
        cfg, page = self.model_config, self.config.prefix_page_size
        # a model with a window page group: the layers that attend over
        # everything here, its window layers under a pair of their own
        window = None if self._two_groups else cfg.sliding_window
        first, last = page_span(lengths, page, slots, window)
        layers = cfg.kv_layers                   # the layers that attend
        itemsize = jnp.dtype(self.dtype).itemsize
        # the shard the kernel sees: the kv heads split over ``tp`` only
        # where the pool does (a replicated pool: the whole of them)
        tp = self.tp if self._attn_mesh is not None else 1
        group = decode_page_group(cfg, page, slots, itemsize, window, tp)
        walked = int((last - first + 1).sum()) * layers
        bump_counter("llm_attn_pages_walked_total", n=walked)
        bump_counter("llm_attn_page_groups_total",
                     n=int(((last - first) // group + 1).sum()) * layers)
        bump_counter("llm_attn_pages_offered_total",
                     n=lengths.size * slots * layers)
        if not self._two_groups:
            return {}
        first, last = page_span(lengths, page, slots, cfg.sliding_window)
        window_walked = int((last - first + 1).sum()) * cfg.window_layers
        # the window layers' trips: one a row where the trip is what the
        # window spans
        window_groups = int(((last - first) // decode_page_group(
            cfg, page, slots, itemsize, cfg.sliding_window, tp) + 1).sum())
        bump_counter("llm_attn_window_pages_walked_total", n=window_walked)
        bump_counter("llm_attn_window_page_groups_total",
                     n=window_groups * cfg.window_layers)
        bump_counter("llm_attn_window_pages_offered_total",
                     n=lengths.size * slots * cfg.window_layers)
        freed = self.pool.window_pages_freed - self._window_freed_recorded
        self._window_freed_recorded += freed
        return {"full_pages": walked, "window_pages": window_walked,
                "window_pages_freed": freed}

    def _take_block_counters(self, drained: np.ndarray
                             ) -> tuple[np.ndarray, np.ndarray]:
        """A block model's drained matrix carries one more row, the
        program's counters, and one more column, the forwards each row ran.
        Bump the counters; hand back (tokens, forwards a row)."""
        row_forwards, commits = (int(v) for v in drained[-1, :2])
        bump_counter("llm_block_row_forwards_total", n=row_forwards)
        bump_counter("llm_block_commit_row_forwards_total", n=commits)
        return drained[:-1, :-1], drained[:-1, -1]

    def _take_step_counters(self, drained: np.ndarray, forwards: int,
                            decode: bool = False
                            ) -> tuple[np.ndarray, dict[str, Any]]:
        """Where the model's forwards hand counters over
        (``_step_counters``), the drained matrix carries them as its last
        rows (``_append_counts``): the one way out of the programs beside
        the tokens. Bump their series and hand back (the matrix without
        them, what the round's record says of them). An expert model: with
        the experts HELD that the ``forwards`` offered, and for a ``decode``
        chunk's drain the decode-only pair too; the record gets the
        assignments that fell on held experts where the model counts them.
        A looped model: ``_count_loop``."""
        names = self._step_counters
        if not names:
            return drained, {}
        rows = len(names) if drained.ndim == 1 else \
            -(-len(names) // drained.shape[1])
        counts = dict(zip(names,
                          (int(v) for v in drained[-rows:].reshape(-1))))
        drained = drained[:-rows]
        if self._looped:
            return drained, self._count_loop(counts, forwards)
        for name, n_counted in counts.items():
            bump_counter(_MOE_SERIES_OF[name], n=n_counted)
        offered = (forwards * self.model_config.num_moe_layers
                   * self.model_config.experts_local)
        bump_counter("llm_moe_experts_offered_total", n=offered)
        if decode:
            bump_counter("llm_moe_decode_experts_touched_total",
                         n=counts["touched"])
            bump_counter("llm_moe_decode_experts_offered_total", n=offered)
        said = ({"local_assignments": counts["local"]}
                if "local" in counts else {})
        if "keys_scored" in counts:
            if decode:
                for name, series in _DSA_DECODE_SERIES.items():
                    bump_counter(series, n=counts[name])
                bump_counter(_DSA_DECODE_CALLS,
                             n=forwards * self.model_config.num_layers)
            said.update(keys_scored=counts["keys_scored"],
                        keys_selected=counts["keys_selected"])
        return drained, said

    def _count_loop(self, counts: dict[str, int],
                    forwards: int) -> dict[str, Any]:
        """/metrics of a looped model's drained dispatch (``_LOOP_SERIES``)
        and what its round's record and ``llm.decode_chunk`` spans say of
        it: ``loop_steps``, the ``passes`` the forwards ran, and
        ``exit_pass_mean``, the pass at which the gate would have let the
        decode rows out (absent where no decode row ran)."""
        steps = self.model_config.loop_steps
        exit_sum, rows = counts["exit_pass_milli"] / 1000.0, counts["exit_rows"]
        bump_counter("llm_loop_forwards_total", n=forwards)
        bump_counter("llm_loop_passes_total", n=forwards * steps)
        bump_counter("llm_loop_exit_pass_sum_total", n=exit_sum)
        bump_counter("llm_loop_exit_rows_total", n=rows)
        return {"loop_steps": steps, "passes": forwards * steps,
                **({"exit_pass_mean": round(exit_sum / rows, 3)}
                   if rows else {})}

    def _emit_block(self, slot: int, toks: np.ndarray, start: int) -> int:
        """Emit the block a row committed at ``start .. start + W - 1``:
        the positions past the prompt (a first block opens with the
        prompt's leftover), up to the stop id or token limit that ends the
        answer INSIDE the block; the last token ends it with ``length``
        where no further block fits the window. Returns tokens emitted."""
        state = self.slots[slot]
        W, emitted = self._block, 0
        no_room = start + 2 * W > self.config.max_seq_len
        for j in range(W):
            if start + j < len(state.prompt_ids):
                continue
            if not self.active[slot]:
                break
            self._emit_token(slot, int(toks[j]),
                             force_length=no_room and j == W - 1)
            emitted += 1
        return emitted

    def _emit_block_chunk(self, chunk: np.ndarray, ran: np.ndarray,
                          old_lengths: np.ndarray, depth: int = 0,
                          rows: Optional[list[int]] = None
                          ) -> tuple[int, int]:
        """``_emit_chunk`` of a block model: ``chunk`` [B, forwards*W],
        forward ``f``'s tokens at ``[f*W, (f+1)*W)`` or -1 where the row
        committed nothing in it; ``ran`` the forwards each row ran. One
        flight-recorder event a row a chunk, before its tokens (a finish
        closes the record). Returns (blocks, tokens) emitted."""
        W = self._block
        committed = chunk[:, ::W] >= 0                    # [B, forwards]
        rows = [s for s in (range(self.n_slots) if rows is None else rows)
                if self.active[s] and self.slots[s] is not None]
        for slot in rows:
            record_event(self.slots[slot].request_id, "decode_chunk",
                         slot=slot, tokens=int(committed[slot].sum()) * W,
                         depth=depth, blocks=int(committed[slot].sum()),
                         row_forwards=int(ran[slot]))
        at = old_lengths.copy()
        blocks = tokens = 0
        for f in range(committed.shape[1]):
            for slot in rows:
                if not self.active[slot] or not committed[slot, f]:
                    continue
                tokens += self._emit_block(
                    slot, chunk[slot, f * W:(f + 1) * W], int(at[slot]))
                at[slot] += W
                blocks += 1
        bump_counter("llm_blocks_committed_total", n=blocks)
        bump_counter("llm_block_tokens_emitted_total", n=tokens)
        return blocks, tokens

    def _emit_chunk(self, chunk: np.ndarray, old_lengths: np.ndarray,
                    rows: list[int], depth: int = 0) -> None:
        """``rows``: the slots that ran in the chunk (active at its commit).
        A held emit runs after the next pass's ``_admit``, and a row resumed
        there is active but has no token in THIS chunk."""
        k = self._k_steps
        # one flight-recorder event per active slot per CHUNK (k fused
        # tokens), never per token — the per-round cost is a handful of
        # lock-once appends against a whole device dispatch. ``depth`` stamps
        # how many lookahead chunks were still in flight at this drain.
        for slot in rows:
            state = self.slots[slot]
            if state is not None and self.active[slot]:
                record_event(state.request_id, "decode_chunk", slot=slot,
                             tokens=k, depth=depth)
        for j in range(k):
            last_of_chunk = j == k - 1
            for slot in rows:
                if not self.active[slot]:
                    continue
                # finish-with-length at chunk end when the NEXT chunk can't fit
                next_chunk_overflows = (
                    int(old_lengths[slot]) + 2 * k > self.config.max_seq_len)
                self._emit_token(
                    slot, int(chunk[slot, j]),
                    force_length=last_of_chunk and next_chunk_overflows)

    # ------------------------------------------------------------ mixed round
    def _plan_prefill_chunks(self, max_rows: Optional[int] = None,
                             ahead: Optional[dict[int, int]] = None
                             ) -> list[tuple[int, _SlotState, int]]:
        """Assign this round's prompt chunks: fill ``prefill_budget_tokens``
        across prefilling slots FIFO (admission order), at most ``max_rows``
        of them — ``mixed_step``'s lane holds one slot's chunk a step, so
        slots in prefill take consecutive steps, each under the budget; the
        all-rows speculative step takes as many as the budget covers. The
        head slot always gets at least one token, so a tiny budget cannot
        stall prefill; a budget of 0 means one unbounded chunk (whole
        remaining prompt). ``ahead``: the tokens of a slot's prompt that an
        undrained step already carries (slot -> chunk): the plan starts
        where that step's commit will leave ``prefill_pos``."""
        budget = self.config.prefill_budget_tokens
        ahead = ahead or {}
        left = budget if budget > 0 else float("inf")
        plan: list[tuple[int, _SlotState, int]] = []
        for slot in list(self._prefill_slots):
            if left <= 0 or (max_rows is not None and len(plan) >= max_rows):
                break
            state = self.slots[slot]
            if state is None or state.phase != "prefill":
                continue  # defensive: the deque tracks prefill-phase slots
            pos = state.prefill_pos + ahead.get(slot, 0)
            remaining = self._prefill_target(state) - pos
            chunk = int(min(remaining, left)) if left != float("inf") \
                else remaining
            if self._block:
                # whole blocks; a prompt with none left to compute (shorter
                # than a block, or cached up to its leftover) still takes
                # its step, with an empty lane, to flip
                chunk -= chunk % self._block
                if chunk <= 0 and remaining > 0:
                    chunk = self._block
                if remaining == 0:
                    plan.append((slot, state, 0))
                    left -= 1
                    continue
            if self._state_unit:
                # end on the next snapshot boundary rather than past it, so
                # that every row passes through the boundaries it crosses
                chunk = min(chunk, self._state_unit
                            - pos % self._state_unit)
            if chunk <= 0:
                continue
            plan.append((slot, state, chunk))
            left -= chunk
        return plan

    def _grow_chain_prefill(self, slot: int, state: _SlotState,
                            end: int) -> None:
        """Extend a prefilling slot's chain to cover its next chunk's pages,
        up to the prompt position ``end`` where the chunk stops (a chunk may
        cross page boundaries). Raises MemoryError when the pool cannot
        serve it even after eviction — the caller preempts-to-host and
        chunking resumes where it left off; for a step planned ahead of a
        drain the caller launches nothing, and the next round asks again on
        committed state (``_dispatch_mixed``)."""
        # armed MemoryError forces the preempt-mid-chunked-prefill path with
        # no real pool pressure (faultlab mixed-prefill-preempt scenario)
        failpoint("scheduler.prefill_chunk")
        if self.pool.pages_for(end) <= self._pages_held(state):
            return
        self._grow_tables(slot, state, end)

    def _finish_prefill(self, slot: int, state: _SlotState) -> float:
        """Flip a fully-prefilled slot to decode, at its mixed step's
        COMMIT (the next plan, capacity pass and admission read all of it,
        so it never waits for a held emit): commit the prompt's full pages
        to the radix tree (later requests reuse them zero-copy) and adopt
        the flip the mixed dispatch computed on the device (its outputs are
        already the committed rows: nothing is patched). The epoch stays:
        chunks that span the flip were chained off the mixed dispatch,
        which computed it on-device (active_out/final_lens), and with no
        span there is no ring to stale. What the stream sees of it is
        ``_emit_first_token``'s. Returns the prefill's duration, ms."""
        # the kept length at the flip: the whole prompt, or (a block model)
        # its whole blocks
        T = self._prefill_target(state)
        try:
            self.pool.commit_chain(state.prompt_ids, state.chain,
                                   snapshots=state.state_snapshots)
        except Exception:  # noqa: BLE001 — the cache insert is best-effort
            logger.exception("prefix-tree commit failed for %s",
                             state.request_id)
        state.state_snapshots = []
        state.phase = "decode"
        self._arm_spec(state, state.prompt_ids)
        self._prefill_slots.remove(slot)
        self.lengths[slot] = T
        self.active[slot] = True
        dur_ms = (time.monotonic() - state.prefill_t0) * 1000.0
        # the chunked path's duration spans the budget-paced rounds — the
        # realistic "time to get through prefill under current load"
        self._note_prefill_rate(T - state.cached_len, dur_ms / 1000.0)
        return dur_ms

    def _emit_first_token(self, slot: int, state: _SlotState,
                          tok: Optional[int], dur_ms: float) -> None:
        """The emit half of a flip: the terminal ``prefill`` event and span,
        then the first token, sampled inside the flip's dispatch (a block
        model gets none here: ``tok`` None)."""
        # the terminal "prefill" event (ttft anchors here); the per-chunk
        # progress lives in prefill_chunk events
        record_event(state.request_id, "prefill", slot=slot, mixed=True,
                     cached_len=state.cached_len,
                     prompt_tokens=len(state.prompt_ids),
                     chunks=state.prefill_chunks, dur_ms=round(dur_ms, 3))
        if state.trace:
            get_global_tracer().emit_span(
                "llm.prefill", traceparent=state.trace,
                start_unix_ns=int(state.prefill_wall * 1e9),
                duration_ms=dur_ms, request_id=state.request_id, slot=slot,
                prompt_tokens=len(state.prompt_ids),
                cached_len=state.cached_len, mixed=True,
                chunks=state.prefill_chunks, tenant=state.tenant)
        if tok is None:
            return
        no_room = (self._prefill_target(state) + self._k_steps
                   > self.config.max_seq_len)
        self._emit_token(slot, tok, force_length=no_room)
        # PD disaggregation: a prefill-role engine's job ends at the first
        # token. If the emit above finished the stream (stop/length on
        # token one), slots[slot] is already None and there is nothing to
        # hand off — the guard keys on slot survival, not on phase.
        if self.pd_role == "prefill" and self._handoff_sink is not None \
                and self.slots[slot] is state:
            self._export_handoff(slot, state, tok)

    def _export_handoff(self, slot: int, state: _SlotState, tok: int) -> None:
        """Export a just-prefilled stream off this engine (prefill role):
        copy its committed chain to host, free the slot, and push the
        resume record at the pool's handoff sink, which enqueues it on a
        decode-role engine. Runs on the scheduler thread right after the
        first token emitted. Failure atomicity: a raise here (the armed
        ``scheduler.handoff`` failpoint, or a real export fault) propagates
        to the loop → the engine breaks → _fail_all_inflight error-
        terminates the stream → the replica pool's failover re-prefills
        prompt+emitted on a survivor, so the client stream stays
        bit-identical (greedy) and nothing leaks (the broken engine's pool
        dies whole)."""
        # armed raise = faultlab pd-handoff-crash: prefill replica dies
        # mid-handoff, the stream must fail over and re-prefill elsewhere
        failpoint("scheduler.handoff")
        T = int(self.lengths[slot])
        chain = state.chain
        n_pages = len(chain)
        # export releases this engine's hold on the chain: tree-shared
        # prefix pages stay cached in the prefill radix (the warm-prefix
        # short-circuit for later requests), private pages free. The radix
        # pins from this request's match_prefix were already consumed by
        # admission, so no prompt_ids release is needed here.
        host_kv = self.pool.export_pages(chain)
        state.chain = None
        # the post-first-sample key stream (committed at the mixed-round
        # drain) — the decode engine continues sampling from exactly here,
        # which is what makes seeded streams bit-identical across the split
        slot_key = np.asarray(self._slot_keys)[slot]
        rec = _Suspended(state=state, host_kv=host_kv, length=T,
                         last_token=tok, slot_key=slot_key, handoff=True)
        # free the slot with the preempt teardown idiom — the chain is
        # already released above, so no pool.release_slot here
        self.active[slot] = False
        self.slots[slot] = None
        self._release_free_slot(slot)
        self._epoch += 1
        self._tables[slot, :] = 0
        record_event(state.request_id, "handoff_export", slot=slot,
                     length=T, pages=n_pages, tokens_emitted=state.emitted)
        self._handoff_sink(rec)

    # ------------------------------------------------------------ speculation
    def _spec_candidates(self) -> bool:
        """Cheap pre-check: some active decode row is armed for speculation
        and its proposer has a draft RIGHT NOW (a few dict probes per slot).
        Gates both the spec-round entry and ring deepening — the ring stops
        growing while speculation is ready, so it drains in a round or two
        and the next dispatch carries draft spans instead."""
        if not self.spec_k:
            return False
        for slot in range(self.n_slots):
            state = self.slots[slot]
            if (state is not None and self.active[slot]
                    and state.proposer is not None
                    and state.proposer.propose()):
                return True
        return False

    def _spec_round_safe(self) -> bool:
        """A pure-decode round may become a speculative round only while
        EVERY active row is LIMIT-bound — its max-tokens limit fires before
        the window bound ever could (limit + decode_chunk ≤ max_seq). A
        limit-bound stream finishes at exactly max_tokens regardless of how
        rounds chunk its advance, so variable spec-round advances cannot
        move its terminal; a window-bound stream's "length" finish lands on
        a chunk-lattice point, which a 1-token spec-round advance would
        shift — k>0 must never move that finish off its k=0 boundary (the
        byte-identity contract), so those batches just keep taking plain
        chunks to the brim."""
        max_seq = self.config.max_seq_len
        for slot in range(self.n_slots):
            if not self.active[slot]:
                continue
            state = self.slots[slot]
            if state is None:
                continue
            limit = (int(self.lengths[slot]) - state.emitted
                     + state.sampling.max_tokens)
            if limit + self._k_steps > max_seq:
                return False
        return True

    def _spec_gate_closed(self, state: _SlotState) -> bool:
        """spec_min_accept: after a probation window of 4k proposed drafts,
        a stream whose rolling acceptance rate sits below the floor stops
        proposing for good (sticky — the proposer and its index memory are
        dropped). Deterministic per stream and acceptance-checked, so the
        gate can only change speed, never tokens."""
        floor = self.config.spec_min_accept
        if floor <= 0.0:
            return False
        if state.spec_proposed < 4 * self.spec_k:
            return False
        if state.spec_accepted < floor * state.spec_proposed:
            state.proposer = None
            self.spec_stats["slots_disabled"] += 1
            record_event(state.request_id, "spec_disabled",
                         proposed=state.spec_proposed,
                         accepted=state.spec_accepted)
            return True
        return False

    def _plan_spec(self, budget_left) -> list[tuple[int, "_SlotState",
                                                    list[int]]]:
        """Plan this round's draft spans: one proposer probe per armed
        active row, trimmed to the shared ragged token budget (speculation
        and prefill chunks draw from the same prefill_budget_tokens pool),
        the row's remaining token allowance (a draft past max_tokens can
        never commit), the window guard, and page-chain coverage — a failed
        chain extension just skips that row's speculation this round, never
        a preempt (the capacity sweep already guaranteed the mandatory
        chunk)."""
        plan: list[tuple[int, _SlotState, list[int]]] = []
        if not self.spec_k:
            return plan
        max_seq = self.config.max_seq_len
        for slot in range(self.n_slots):
            if budget_left <= 0:
                break
            state = self.slots[slot]
            if state is None or not self.active[slot] \
                    or state.proposer is None or self._spec_gate_closed(state):
                continue
            L = int(self.lengths[slot])
            if L + self._spec_w + self._k_steps > max_seq:
                continue
            remaining = state.sampling.max_tokens - state.emitted
            cap = int(min(self.spec_k, remaining - 1, budget_left))
            if cap <= 0:
                continue
            drafts = state.proposer.propose()
            if not drafts:
                continue
            drafts = drafts[:cap]
            try:
                self._extend_chain_to(slot, state,
                                      min(L + 1 + len(drafts), max_seq))
            except MemoryError:
                continue
            plan.append((slot, state, drafts))
            budget_left -= len(drafts)
        return plan

    def _mixed_ring_span(self, rec: _InflightChunk,
                         finals: list[tuple[int, "_SlotState"]]) -> int:
        """Let the lookahead ring SPAN the mixed→pure-decode transition: when
        this mixed dispatch consumes the last pending prompt chunks, the flip
        state (active mask, first tokens, post-flip lengths, finished mask)
        already exists ON DEVICE in the dispatch's outputs — so decode chunks
        chain straight off it, with no synchronous fallback round. Chains are
        pre-extended opportunistically; any MemoryError just caps the span
        (the next synchronous round preempts properly). Returns the number of
        chunks chained. Speculative dispatches never span (see the call
        site), so the record's device lengths always match the host mirror
        +1 here and the horizons below stay exact. A step launched ahead of
        its predecessor's drain comes here a round later, that predecessor
        committed; if a host-fallback stop in the predecessor's emit bumped
        the epoch since, the step's active mask still runs the stopped row
        and nothing is chained off it."""
        depth = self._lookahead_depth
        if (depth <= 0 or len(finals) != len(self._prefill_slots)
                or self._suspended or not self._pending.empty()
                or self._stop.is_set() or rec.epoch != self._epoch):
            return 0
        k = self._chunk_tokens
        chained = 0
        tail = rec
        for h in range(depth):
            self._clock.to("capacity")
            # the mixed step's own advance + h+1 chained chunks
            if not self._extend_chains_behind(
                    finals, self._step_tokens + (h + 1) * k):
                return chained  # cap the span; sync rounds preempt
            self._ring.append(self._dispatch_chunk(after=tail))
            tail = self._ring[-1]
            self._lookahead_stats["dispatched"] += 1
            chained += 1
        return chained

    def _decode_round_mixed(self, spec_only: bool = False) -> bool:
        """One mixed-batch round: decode rows advance ONE token while the
        head prefilling slot's chunk (≤ prefill_budget_tokens; slots take
        consecutive rounds, FIFO) runs in the SAME dispatch as the lane of
        ``mixed_step`` — Sarathi-style piggybacking with no phase
        separation, so an arrival burst never stalls in-flight streams
        behind a prefill drain, and the dispatch computes the tokens it has
        (``n_slots + width`` positions).

        A round is a LAUNCH HALF (``_dispatch_mixed``: capacity, plan,
        upload, launch, the state snapshot, the transfer's start) and a
        DRAIN HALF (``_commit_mixed``: the one sync, the commit, the emit),
        and between the two it queues what comes next behind its step:

        - the step drains the prefill queue: decode chunks chain off its
          outputs (``_mixed_ring_span``), so the mixed→pure-decode
          transition keeps the pipeline full;
        - a prompt chunk is left (this prompt's next, another admitted
          slot's) and the host knows NOW what its step computes
          (``_chains_mixed``): that step is launched off this one's device
          carry before this one is drained, as a decode chunk chains off the
          ring's tail, and stays in ``self._mixed`` for the next round,
          which begins at this point (its launch half is done), so steps
          follow one another on the device with no host work between them;
        - neither: the drain leaves nothing in flight and the emit is held
          for the next pass's launch (``_close_round``).

        No ring is in flight at the start: prefill work is admitted and
        resumed only off an empty ring (``_admit``), none is built while a
        prompt has chunks left, and the ``spec_only`` entry is taken off a
        drained ring.

        Where the emit runs (``_close_round``): with chunks or a step
        chained off this step, at once, after its commit; with nothing
        chained it is HELD, and the next pass's launch goes out first. In
        turn this round flushes the emit a previous drain held, right
        behind its own launches and before its own drain. A step launched
        ahead of an emit or of a drain is never discarded (its chunk's K/V
        and state advance cannot be replayed): rows an emit finished in the
        meantime are masked out of its own emit, whose ``decode_rows`` are
        read at its commit. The commit carries what the next plan reads:
        ``prefill_pos`` and a final chunk's flip (``_finish_prefill``); a
        step chained ahead of it is planned against what that commit WILL
        leave (``_dispatch_mixed``).

        Speculative rounds (scheduler_spec_k > 0): eligible greedy rows with
        a live ngram proposal become q_len=1+d draft spans in the SAME
        dispatch (the _spec_step_fn variant), sharing the round's ragged
        token budget with prefill chunks — chunks first (a cold prompt beats
        an optimistic draft), leftovers to drafts. Accept/reject, per-row
        advance (1..k+1 tokens) and rollback all run on device; the emit
        loop just walks each row's -1-terminated token list through
        the ordinary _emit_token path, so stop/limit/charging/cancel
        semantics are untouched. ``spec_only=True`` is the pure-decode entry
        (no prefill slots): returns False without dispatching when no draft
        survives planning, and the caller falls back to the plain chunk
        round."""
        assert not self._ring, "a mixed round met chunks in flight"
        step, self._mixed = self._mixed, None
        if step is None:
            step = self._dispatch_mixed(spec_only=spec_only)
            if step is None:
                return False
        # ring spanning: chain lookahead chunks off the step BEFORE its
        # drain, so the device keeps working while the host emits + flips.
        # Speculative dispatches deliberately do NOT span: their proposals
        # almost always recur next round (repetitive text is why they fired),
        # and a chained plain chunk would spend k weight passes on k tokens
        # where the next verify span spends ONE on up to k+1 — the ring
        # instead rebuilds the moment proposals dry up (_can_extend_ring).
        if not step.spec_plan:
            step.spanned = self._mixed_ring_span(step.rec, step.finals)
        # or the next prompt chunk's step, where the host knows it already
        if not step.spanned and self._chains_mixed(step):
            self._mixed = self._dispatch_mixed(after=step)
        # the step and what was chained off it are queued: the emit held
        # back at the last drain runs under them (``_close_round``, rule 1:
        # whatever it finds, THIS step is drained and committed below). A
        # host-fallback stop in it drops only the chunks chained behind,
        # and the step itself is still undrained
        self._flush_held_emit(behind_launch=True)
        self._commit_mixed(step)
        return True

    def _chains_mixed(self, step: _MixedStep) -> bool:
        """Whether the next ``mixed_step`` may be launched off ``step``
        while ``step`` is undrained: everything the next step takes from the
        host has to be known before ``step``'s tokens are. From what the
        round can see, no option: a plain step advances every running row by
        exactly one and its lane (a prompt's ids, where the chunk starts,
        whether it is final) is host data, so it chains; a speculating
        engine does not (a draft span's advance is 1..k+1, and the next
        proposals come from text not yet emitted), nor a block model (a row
        advances by what the forward committed), nor a step whose emit hands
        its flipped rows to another engine (they leave this one). And
        nothing is queued ahead of what has to act on committed state first:
        a resume (it writes the committed carry), a preemption marked for
        the next capacity pass, the loop's stop. Whether a next chunk
        exists, and whether its pages can be had, is the plan's to find
        (``_dispatch_mixed``)."""
        return not (self.spec_k or self._block
                    or (step.finals and self._exports_flips())
                    or self._suspended or self._soft_yield
                    or self._stop.is_set())

    def _exports_flips(self) -> bool:
        """A prefill-role engine hands a flipped row off inside the emit of
        the step that flipped it (``_export_handoff``)."""
        return self.pd_role == "prefill" and self._handoff_sink is not None

    def _dispatch_mixed(self, after: Optional[_MixedStep] = None,
                        spec_only: bool = False) -> Optional[_MixedStep]:
        """The launch half of a mixed round: capacity, plan, the lane's
        upload and the launch of one ``mixed_step`` (async: the return holds
        futures), then the snapshot of a row whose chunk ended on a boundary
        and the start of the tokens' transfer. None when there is nothing to
        launch (every planned slot preempted or flipped, no draft).

        ``after`` chains the step onto a still-undrained step's device
        outputs, as ``_dispatch_chunk(after=)`` chains a decode chunk: the
        carry (last tokens, keys, lengths, finished) is ``after``'s, and the
        plan is made against the state ``after``'s commit WILL leave: each
        of its chunks landed (``prefill_pos + chunk``), its final chunks'
        rows flipped (active, at the prompt's length), every running row one
        token longer. A row that ends inside ``after`` by length, window or
        a device-matched stop is frozen by its finished mask, as for a
        chained decode chunk. Chained, nothing is preempted here (that reads
        committed state): a chain the pool cannot grow means no step, None,
        and the round goes on as one with nothing to chain."""
        t0 = self._clock.to("capacity")
        wall0 = time.time()
        n = self.n_slots
        ahead = after.carried() if after else {}
        plan: list[tuple[int, _SlotState, int]] = []
        if after is None:
            # capacity: decode rows keep a full chunk of headroom (the
            # invariant every round preserves); prefill rows cover their
            # chunk's pages. MemoryError on either path preempts-to-host.
            self._ensure_chunk_capacity(self._chunk_tokens)
            active = self.active
            carry = (self._last_tokens, self._lengths_dev,
                     self._finished_dev, self._slot_keys)
        else:
            active = self.active.copy()
            active[[slot for slot, _ in after.finals]] = True
            carry = (after.rec.last, after.rec.lengths_dev,
                     after.rec.finished_dev, after.rec.keys)
        if not spec_only:
            # one slot's chunk a step (the lane), FIFO; an engine that
            # speculates plans every slot the budget covers, for the all-rows
            # step a round with draft spans takes
            self._clock.to("plan")
            planned = self._plan_prefill_chunks(
                max_rows=None if self.spec_k else LANE_ROWS, ahead=ahead)
            self._clock.to("capacity")
            if after is not None and not (
                    planned and self._extend_chains_behind(
                        after.finals,
                        self._step_tokens + self._chunk_tokens)):
                return None
            for slot, state, chunk in planned:
                try:
                    self._grow_chain_prefill(
                        slot, state,
                        state.prefill_pos + ahead.get(slot, 0) + chunk)
                    plan.append((slot, state, chunk))
                except MemoryError:
                    if after is not None:
                        return None
                    self._preempt_slot(slot, state)
        self._clock.to("plan")
        # speculation shares the ragged token budget: prefill chunks draw
        # first (a cold prompt's TTFT beats an optimistic draft, and chunk
        # pacing stays bit-identical to k=0), drafts take what is left —
        # floored at one span's worth, so a budget-filling admission burst
        # can't starve in-flight streams of their speculation entirely
        # (budget 0 = unbounded, as for chunks)
        budget = self.config.prefill_budget_tokens
        spec_left = (max(budget - sum(c for _, _, c in plan), self.spec_k)
                     if budget > 0 else float("inf"))
        spec_plan = self._plan_spec(spec_left) if self.spec_k else []
        if not plan and not spec_plan:
            # every planned slot got preempted (or flipped), and nothing
            # speculates: the next loop pass runs a plain decode round /
            # resumes from host (spec_only: the caller falls through to the
            # plain round immediately)
            return None
        if not spec_plan:
            # no draft survived: the lane step, whose chunk is the head's (a
            # later slot's grown chain serves its own step)
            plan = plan[:LANE_ROWS]
        # static dispatch width: the prefill bucket covering the largest
        # chunk — and the spec span width when rows speculate — rounded to
        # the kernel's q_block (bounded compile variants)
        q_need = self._bucket_for(max(max(c for _, _, c in plan), 1)) \
            if plan else 1
        if spec_plan:
            q_need = max(q_need, self._spec_w)
        q_max = -(-q_need // 8) * 8
        # the span operands: one row a lane (mixed_step), or one a slot with
        # the decode rows as spans of 1 among them (spec_mixed_step)
        n_spans = n if spec_plan else len(plan)
        q_ids = np.zeros((n_spans, q_max), np.int32)
        q_lens = np.zeros(n_spans, np.int32)
        hist = np.zeros(n_spans, np.int32)
        spec_lens = np.zeros(n, np.int32)
        if spec_plan:
            q_lens[active] = 1  # decode rows
        sample = active.copy()
        final_mask = np.zeros(n, bool)
        final_lens = np.zeros(n, np.int32)
        #: the dispatch's by-slot columns (_unpack_lane); a flipping row's
        #: key and (a block model) opened block go in as its chunk is planned
        by_slot = np.zeros((n, _LANE_COLS + self._block), np.int32)
        finals: list[tuple[int, _SlotState]] = []
        for lane, (slot, state, chunk) in enumerate(plan):
            pos = state.prefill_pos + ahead.get(slot, 0)
            r = slot if spec_plan else lane
            q_ids[r, :chunk] = state.prompt_ids[pos: pos + chunk]
            q_lens[r] = chunk
            hist[r] = pos
            if pos + chunk == self._prefill_target(state):
                # final chunk: this dispatch samples the first token — the
                # request's untouched key stream rides the lane to its row
                finals.append((slot, state))
                sample[slot] = True
                final_mask[slot] = True
                final_lens[slot] = pos + chunk
                by_slot[slot, 4:_LANE_COLS] = state.prefill_key.view(np.int32)
                if self._block:
                    # no first token: the prompt's leftover opens the row's
                    # first block, which the row's first forward will run
                    left = state.prompt_ids[pos + chunk:]
                    opened = by_slot[slot, _LANE_COLS:]
                    opened[:] = self.model_config.mask_token_id
                    opened[: len(left)] = left
        for slot, state, drafts in spec_plan:
            # draft span: position 0 (the last committed token) is filled on
            # device from last_tokens; the drafts follow
            d = len(drafts)
            q_ids[slot, 1:1 + d] = drafts
            q_lens[slot] = 1 + d
            spec_lens[slot] = d
        # TWO uploads a mixed step: the host-owned rows where they changed,
        # and everything this dispatch carries as one flat block
        # (_unpack_lane; the spans' last column: the lane's slot, or a
        # slot's draft length in the all-rows step)
        by_slot[:, :4] = np.column_stack(
            [active, sample, final_mask, final_lens])
        lane_host = np.concatenate([
            by_slot.ravel(),
            np.column_stack([q_ids, q_lens, hist, spec_lens if spec_plan
                             else [slot for slot, _, _ in plan]]).ravel()
        ]).astype(np.int32)
        positions = n * q_max if spec_plan \
            else n * self._step_tokens + q_ids.size
        self._clock.to("upload")
        self._sync_rows(active=False)
        lane = self._dev(lane_host)
        self._clock.to("launch")
        last, lengths, fin, keys = carry
        toks_dev, *outs = (self._spec_step_fn if spec_plan
                           else self._mixed_step_fn)(
            self.params, *self.pool.cache_operands(), self._rows_dev, lane,
            last, lengths, fin, keys)
        self._clock.to("launch", starved=False)  # the device has work
        last_o, keys_o, lens_o, fin_o, active_o = self.pool.adopt(outs)
        # the flip's active mask is the device's own from here on
        self._active_dev, self._active_up = active_o, active | final_mask
        if self._state_unit:
            # a snapshot of each row whose chunk ended on a boundary, as THIS
            # call left it: before anything chained behind advances the row
            for slot, state, chunk in plan:
                end = state.prefill_pos + ahead.get(slot, 0) + chunk
                if end % self._state_unit == 0:
                    row = self.pool.take_snapshot(slot)
                    if row is not None:
                        state.state_snapshots.append((end, row))
        try:
            toks_dev.copy_to_host_async()  # non-blocking D2H start
        except AttributeError:
            pass
        bump_counter("llm_mixed_steps_total")
        if after is not None:
            bump_counter("llm_mixed_steps_chained_total")
        return _MixedStep(
            toks_dev, _InflightChunk(toks_dev, last_o, keys_o, lens_o, fin_o,
                                     active_o, self._epoch),
            plan, finals, spec_plan, positions, t0, wall0,
            chained=after is not None,
            ragged=self._count_ragged_walk(hist, q_lens, q_max))

    def _extend_chains_behind(self, finals: list[tuple[int, _SlotState]],
                              horizon: int) -> bool:
        """The capacity pass of a dispatch chained off an undrained mixed
        step, whose ``finals`` it flips: every running row (from its
        committed length) and every flipped row (from its prompt's length)
        covers ``horizon`` more tokens, that step's own advance included.
        Grown without preempting, as the ring's extensions are
        (``_extend_chain_to``): False where the pool cannot serve one, and
        the capacity pass of the next round made on committed state
        preempts as ever."""
        max_seq = self.config.max_seq_len
        flipping = {slot for slot, _ in finals}
        for slot in range(self.n_slots):
            state = self.slots[slot]
            if state is None:
                continue
            if self.active[slot]:
                length = int(self.lengths[slot])
            elif slot in flipping:
                length = self._prefill_target(state)
            else:
                continue
            try:
                self._extend_chain_to(slot, state,
                                      min(length + horizon, max_seq))
            except MemoryError:
                return False
        return True

    def _commit_mixed(self, step: _MixedStep) -> None:
        """The drain half of a mixed round: the one sync (the step's
        tokens), the commit (the device carry, the host's length mirror,
        each chunk's progress and a final chunk's flip), and the emit
        through ``_close_round``: at once under what was chained off the
        step (decode chunks in the ring, the next chunk's step in
        ``self._mixed``), held where nothing was."""
        plan, finals, spec_plan = step.plan, step.finals, step.spec_plan
        wall0, n = step.wall0, self.n_slots
        # what the device has behind this step while the host emits it
        depth = step.spanned + (self._mixed is not None)
        self._clock.to("drain", starved=False)
        toks = np.asarray(step.toks_dev, np.int32)  # sync-point: mixed-round drain (AS04)
        # where nothing was chained off this dispatch the device waits from
        # here to the next launch
        round_ms = (self._clock.to("commit", starved=not depth)
                    - step.t0) * 1000.0
        self._last_tokens = step.rec.last
        self._slot_keys = step.rec.keys
        self._lengths_dev = step.rec.lengths_dev
        self._finished_dev = step.rec.finished_dev
        # spec dispatches return [n, spec_w + 1]: -1-sentinel emit columns
        # plus the accept-count column (one drain carries both); plain mixed
        # returns [n] — normalize to 2-D so one emit loop serves both
        ran = None
        toks, counted = self._take_step_counters(toks, forwards=1)
        if self._block:
            toks2d, ran = self._take_block_counters(toks)
            accepts = None
        elif toks.ndim == 2:
            toks2d, accepts = toks[:, :-1], toks[:, -1]
        else:
            toks2d, accepts = toks[:, None], None
        # the rows that ran AND still run: one an emit finished since the
        # launch (a held emit flushed behind it, the emit of the step this
        # one was chained off) has a token here that nobody is owed
        decode_rows = [s for s in range(n) if self.active[s]]
        old_lengths = self.lengths.copy()
        if not spec_plan:    # a draft span rides the ragged kernel
            counted = {**counted, **self._count_attn_pages(
                old_lengths, np.zeros((n, 1), bool))}
        if self._block:
            self.lengths = np.where(
                self.active & (toks2d[:, 0] >= 0),
                self.lengths + self._block, self.lengths).astype(np.int32)
        elif spec_plan:
            # variable per-slot advance: the host mirror adopts each row's
            # actual emit count (1..k+1), matching the device's new_lens
            adv = (toks2d >= 0).sum(axis=1).astype(np.int32)
            self.lengths = np.where(self.active, self.lengths + adv,
                                    self.lengths).astype(np.int32)
        else:
            self.lengths = np.where(self.active, self.lengths + 1,
                                    self.lengths).astype(np.int32)
        spec_slots = {slot: (state, drafts)
                      for slot, state, drafts in spec_plan}
        row_tokens = {slot: int((toks2d[slot] >= 0).sum())
                      for slot in decode_rows} if spec_plan else None
        row_attrs = {slot: {"spec_proposed": len(drafts),
                            "spec_accepted": int(accepts[slot])}
                     for slot, (state, drafts) in spec_slots.items()} \
            if spec_plan else None
        if self._block:
            row_tokens = {slot: int(toks2d[slot, 0] >= 0) * self._block
                          for slot in decode_rows}
            row_attrs = {slot: {"blocks": int(toks2d[slot, 0] >= 0),
                                "row_forwards": int(ran[slot])}
                         for slot in decode_rows}
        self._emit_decode_spans(wall0, round_ms, lookahead=False,
                                rows=decode_rows, tokens=1, depth=depth,
                                row_tokens=row_tokens, row_attrs=row_attrs,
                                round_attrs=counted if self._looped else None)
        # acceptance accounting BEFORE the emit loop (a mid-row finish
        # clears the slot state): totals, the accept-length histogram, the
        # per-stream evidence the spec_min_accept gate reads, and the
        # monitoring counters
        if spec_plan:
            self.spec_stats["rounds"] += 1
            if plan:
                self.spec_stats["mixed_rounds"] += 1
            round_proposed = round_accepted = 0
            for slot, (state, drafts) in spec_slots.items():
                a = int(accepts[slot])
                d = len(drafts)
                round_proposed += d
                round_accepted += a
                self.spec_stats["proposed"] += d
                self.spec_stats["accepted"] += a
                self.spec_stats["emitted"] += int((toks2d[slot] >= 0).sum())
                self._spec_accept_hist[a] = \
                    self._spec_accept_hist.get(a, 0) + 1
                state.spec_proposed += d
                state.spec_accepted += a
            bump_counter("llm_spec_tokens_proposed_total", n=round_proposed)
            bump_counter("llm_spec_tokens_accepted_total", n=round_accepted)
        # what the next plan, capacity pass and admission read is the
        # commit's: each chunk's progress, and a final chunk's flip (after
        # the decode rows' lengths above: the flipped row starts at T)
        done = []
        for slot, state, chunk in plan:
            state.prefill_pos += chunk
            state.prefill_chunks += 1
            self.prefill_chunks += 1
            self.chunked_prefill_tokens += chunk
            done.append((slot, state, chunk, state.prefill_pos))
        first = [(slot, state, None if self._block else int(toks2d[slot, 0]),
                  self._finish_prefill(slot, state))
                 for slot, state in finals]
        self._trim_windows()

        def emit() -> Optional[tuple[int, int]]:
            for slot, state, chunk, pos in done:
                # chunked prefill charges as it lands — a tenant mid-prompt
                # is already paying its fair-queue bill, not only at
                # completion
                self._charge_tenant(state.tenant, chunk)
                # one event per piggybacked chunk (mirrors decode_chunk):
                # the request timeline shows interleaved prefill progress
                record_event(state.request_id, "prefill_chunk", slot=slot,
                             tokens=chunk, pos=pos,
                             of=len(state.prompt_ids))
                if state.trace_sampled:
                    get_global_tracer().emit_span(
                        "llm.prefill_chunk", traceparent=state.trace,
                        start_unix_ns=int(wall0 * 1e9),
                        duration_ms=round_ms,
                        request_id=state.request_id, slot=slot,
                        tokens=chunk, **step.ragged)
            for slot, state, tok, dur_ms in first:
                self._emit_first_token(slot, state, tok, dur_ms)
            if self._block:
                return self._emit_block_chunk(toks2d, ran, old_lengths,
                                              depth=depth,
                                              rows=decode_rows)
            for slot in decode_rows:
                state = self.slots[slot]
                if state is None or not self.active[slot]:
                    continue
                n_row = int((toks2d[slot] >= 0).sum())
                extra = row_attrs.get(slot, {}) if row_attrs else {}
                record_event(state.request_id, "decode_chunk", slot=slot,
                             tokens=n_row, depth=depth, **extra)
                for j in range(n_row):
                    if not self.active[slot]:
                        break  # a host-authoritative finish truncates the row
                    # keep the invariant: after each token the slot must
                    # still fit a full decode chunk, else finish with
                    # 'length' now
                    no_room = (int(old_lengths[slot]) + j + 1 + self._k_steps
                               > self.config.max_seq_len)
                    self._emit_token(slot, int(toks2d[slot, j]),
                                     force_length=no_room)
            return None

        # at once under what was chained off this step, or held for the
        # next launch (another arrival's chunk, a resync). A prefill-role
        # engine hands a flipped row off inside this emit
        # (``_export_handoff``), and what ends a stream here is not held
        exports = bool(first) and self._exports_flips()
        self._close_round(emit, hold=not exports, lookahead=False, ts=wall0,
                          mixed=bool(plan),
                          chunk_tokens=sum(c for _, _, c in plan),
                          depth=depth,
                          spec_tokens=sum(len(dr) for _, _, dr in spec_plan),
                          kind=("mixed" if decode_rows else "prefill")
                          if plan else "decode", positions=step.positions,
                          counted={**(counted or {}), **step.ragged},
                          chained=step.chained)

    def _decode_round(self) -> None:
        self.occupancy_samples.append(self.active_slots)
        if self.spec_k:
            # a speculative dispatch is taken off a drained ring and its
            # proposals come from EMITTED text (``_emit_token`` feeds the
            # proposer): a held emit goes out before ``_spec_candidates``
            # or ``_plan_spec`` is asked
            self._flush_held_emit()
        if self._prefill_slots or self._mixed is not None:
            self._decode_round_mixed()
            return
        if self.spec_k and not self._ring and self._spec_round_safe() \
                and self._spec_candidates():
            # speculative round: draft spans through the ragged dispatch
            # (commits 1..k+1 tokens per speculating row for ONE weight
            # pass). Runs only off a drained ring — in-flight plain chunks
            # are valid and drain first; _can_extend_ring stops deepening
            # the ring while proposals are live, so this engages within a
            # round or two. Falls through to the plain chunk round when no
            # draft survives planning (budget/pages/limits).
            if self._decode_round_mixed(spec_only=True):
                return
        t0 = self._clock.to("capacity")
        wall0 = time.time()
        depth = self._lookahead_depth
        # an epoch bump since dispatch (preempt/host-fallback stop/handoff
        # export) stales every undrained entry — drop the suffix, resync below
        if self._ring and self._ring[0].epoch != self._epoch:
            self._discard_ring()
        used_lookahead = bool(self._ring)
        if used_lookahead:
            self._lookahead_stats["used"] += 1
        else:
            self._ensure_chunk_capacity(self._chunk_tokens * (depth + 1))
            if not self.active.any():
                return  # everyone got preempted
            self._ring.append(self._dispatch_chunk(after=None))
            # the device has a chunk queued: the emit held back at the last
            # drain runs under it. A host-fallback stop in it stales that
            # chunk like any other in flight (a K/V model drops it and the
            # next pass replays; with state it stays and drains), and if it
            # finished the last running row the chunk is nobody's: the next
            # pass drops it undrained, as it does a ring no row is left for
            if self._flush_held_emit(behind_launch=True) and not (
                    self._ring and self.active.any()):
                return
        # top up the ring: chain chunks off the tail until depth is reached
        # (each extension re-validates epoch + page-chain coverage, which
        # is capacity work; the dispatch switches to upload and launch)
        while len(self._ring) <= depth:
            self._clock.to("capacity")
            if not self._can_extend_ring():
                break
            self._ring.append(self._dispatch_chunk(after=self._ring[-1]))
            self._lookahead_stats["dispatched"] += 1
        self._clock.to("drain")
        inflight = self._ring.popleft()
        ring_depth = len(self._ring)  # chunks still in flight while we emit
        # armed raise here models a device fault at the chunk readback: the
        # loop-body handler breaks the engine and error-terminates every
        # stream (the replica pool's failover trigger)
        failpoint("scheduler.readback")
        chunk = np.asarray(inflight.chunk_dev, np.int32)  # sync-point: the ONE sanctioned decode-loop drain (AS04)
        # the ring's last chunk: the device waits from here to the next launch
        round_ms = (self._clock.to("commit", starved=not self._ring)
                    - t0) * 1000.0
        self._depth_hist[ring_depth] = self._depth_hist.get(ring_depth, 0) + 1
        chunk, counted = self._take_step_counters(chunk, self._k_steps,
                                                  decode=True)
        # the rows that ran in this chunk: a held emit is flushed after the
        # next pass's ``_admit``, whose resumed rows have no token in it
        rows = np.flatnonzero(self.active).tolist()
        if self._block:
            chunk, ran = self._take_block_counters(chunk)
            committed = chunk[:, ::self._block] >= 0
            commits = committed.sum(axis=1)
            old_lengths = self._commit_chunk(inflight, commits)
            counted = {**counted,
                       **self._count_attn_pages(old_lengths, committed)}
            round_attrs = counted or None   # on every span of the round
            self._emit_decode_spans(
                wall0, round_ms, used_lookahead, depth=ring_depth,
                row_tokens={s: int(c) * self._block
                            for s, c in enumerate(commits)},
                row_attrs={s: {"blocks": int(c), "row_forwards": int(ran[s])}
                           for s, c in enumerate(commits)},
                round_attrs=round_attrs)

            def emit() -> tuple[int, int]:
                return self._emit_block_chunk(chunk, ran, old_lengths,
                                              depth=ring_depth, rows=rows)
        else:
            old_lengths = self._commit_chunk(inflight)
            counted = {**counted,
                       **self._count_attn_pages(old_lengths, chunk >= 0)}
            self._emit_decode_spans(
                wall0, round_ms, used_lookahead, depth=ring_depth,
                round_attrs=counted or None)    # on every span of the round

            def emit() -> None:
                self._emit_chunk(chunk, old_lengths, rows, depth=ring_depth)
        # at once under the chunks in flight, or held for the next launch
        self._close_round(emit, lookahead=used_lookahead, ts=wall0,
                          depth=ring_depth, counted=counted)

    def _emit_decode_spans(self, wall0: float, dur_ms: float,
                           lookahead: bool, rows: Optional[list[int]] = None,
                           tokens: Optional[int] = None,
                           depth: int = 0,
                           row_tokens: Optional[dict] = None,
                           row_attrs: Optional[dict] = None,
                           round_attrs: Optional[dict] = None) -> None:
        """llm.decode_chunk spans for SAMPLED in-flight requests — called
        before the emit loop (a mid-chunk finish clears the slot state). The
        guard is one bool attribute per slot: an unsampled or traceless
        request pays nothing here (the disarmed-failpoint pattern). Mixed
        rounds pass ``rows`` (their decode rows only) and ``tokens=1``. ``depth`` is
        the ring depth still in flight at this round's drain. Speculative
        rounds pass ``row_tokens`` (per-slot variable advance) and
        ``row_attrs`` (spec_proposed/spec_accepted stamps — the depth-style
        acceptance evidence on each span); ``round_attrs`` stamps what the
        round counted as a whole (``local_assignments``) on every span."""
        k = tokens if tokens is not None else self._k_steps
        start_ns = int(wall0 * 1e9)
        for slot in (rows if rows is not None else range(self.n_slots)):
            state = self.slots[slot]
            if state is None or not state.trace_sampled or not self.active[slot]:
                continue
            extra = {**(round_attrs or {}),
                     **(row_attrs.get(slot, {}) if row_attrs else {})}
            get_global_tracer().emit_span(
                "llm.decode_chunk", traceparent=state.trace,
                start_unix_ns=start_ns, duration_ms=dur_ms,
                request_id=state.request_id, slot=slot,
                tokens=row_tokens.get(slot, k) if row_tokens else k,
                lookahead=lookahead, depth=depth, **extra)
