"""The engine's configuration and request types.

``EngineConfig`` is what a deployment states about one served model (window,
slots, pool, quantization, the scheduler's policies); ``SamplingParams`` and
``StepEvent`` are what a request carries in and what the scheduler emits back;
the three ``submit()`` refusals are the typed backpressure the gateway maps to
429s. The engine itself is ``runtime/scheduler.py: ContinuousBatchingEngine``
over ``runtime/programs.py``. ``build_decode_chunk_fn`` is the dense-cache
decode body that ``runtime/export.py`` lowers for the native host.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax

from ..models import ModelConfig
from ..models import llama
from ..ops.sampling import sample_token


class SchedulerSaturated(RuntimeError):
    """``submit()`` rejected: the pending queue is at ``max_pending``.

    Serving layers map this to HTTP 429 + ``Retry-After`` — backpressure at
    admission instead of unbounded host memory growth under an arrival storm.
    """

    def __init__(self, detail: str, retry_after_s: float = 1.0) -> None:
        super().__init__(detail)
        self.retry_after_s = retry_after_s


class TenantSaturated(SchedulerSaturated):
    """``submit()`` rejected: the CALLER'S tenant is at its own pending-depth
    bound (``tenant_max_pending``) while the global queue may still have
    room. Serving layers map this to its own 429 + ``Retry-After`` problem
    (``llm.tenant_saturated``) so a single tenant's retry storm reads as that
    tenant's saturation, never as global backpressure punishing everyone."""

    def __init__(self, detail: str, retry_after_s: float = 1.0,
                 tenant: str = "default") -> None:
        super().__init__(detail, retry_after_s)
        self.tenant = tenant


class TenantQuotaExceeded(RuntimeError):
    """``submit()`` rejected: the request cannot be served within its
    tenant's hard KV-page quota (``tenant_max_pages``) — either the request
    alone needs more pages than the whole quota, or the tenant already holds
    the quota. Serving layers map this to ``llm.tenant_quota_exceeded``."""

    def __init__(self, detail: str, tenant: str = "default",
                 retry_after_s: float = 1.0) -> None:
        super().__init__(detail)
        self.tenant = tenant
        self.retry_after_s = retry_after_s


@dataclass
class SamplingParams:
    """Per-request decode parameters (llm-gateway request schema surface)."""

    max_tokens: int = 128
    temperature: float = 0.0  # 0 → greedy
    top_p: float = 1.0
    top_k: int = 0
    stop_token_ids: tuple[int, ...] = ()
    seed: Optional[int] = None


@dataclass
class EngineConfig:
    model: str = "tiny-llama"
    max_seq_len: int = 256
    max_batch: int = 4
    dtype: str = "bfloat16"
    prefill_buckets: tuple[int, ...] = ()  # default: powers of 2 up to max_seq_len
    #: model-level end-of-sequence ids (from the tokenizer/checkpoint config);
    #: per-request stop_token_ids extend these. No implicit guessing.
    eos_token_ids: tuple[int, ...] = ()
    #: decode steps fused into ONE device program via lax.scan. Each host→device
    #: dispatch costs ~1-70ms depending on transport; fusing k steps amortizes it
    #: k-fold. Tokens past a row's EOS within a chunk are discarded host-side.
    decode_chunk: int = 8
    #: continuous scheduler: the page pool's size in pages. Anything under
    #: the slot minimum (every slot a full window, plus the scratch page),
    #: the default included, is raised to that minimum.
    prefix_cache_pages: int = 0
    prefix_page_size: int = 64
    #: weight-only quantization: "none" | "int8" | "int4" (each rung ~halves
    #: HBM + decode traffic; int4 is per-channel — the bandwidth experiment,
    #: int8 the accuracy default — see runtime/quant.py)
    quantization: str = "none"
    #: the longest and shortest n-gram a stream's NgramProposer matches
    #: against its own history (runtime/speculative.py)
    spec_max_ngram: int = 3
    spec_min_ngram: int = 1
    #: batched speculative decoding in the CONTINUOUS scheduler:
    #: up to this many ngram-proposed draft tokens per speculating slot per
    #: round, verified as ONE q_len=k+1 ragged span inside the mixed-batch
    #: dispatch with accept/reject, accepted-length and rollback computed on
    #: device (a rejected suffix's KV is rewritten before any later read —
    #: runtime/scheduler.py "speculative rounds"). Greedy-only per slot
    #: (temperature 0) and lossless: greedy streams are byte-identical to
    #: ``scheduler_spec_k=0`` — speculation changes speed, never text.
    #: 0 = off (the default: streams bit-identical to the pre-speculation
    #: scheduler). Drafts come from each stream's own emitted-token history
    #: (prompt-lookup / NgramProposer).
    scheduler_spec_k: int = 0
    #: adaptive per-stream speculation gate (continuous scheduler): after a
    #: probation window of 4*scheduler_spec_k proposed drafts, a stream whose
    #: rolling acceptance rate sits below this floor stops proposing for the
    #: rest of its life — its verify width was pure waste on that text.
    #: 0.0 = never disable. Deterministic per stream and acceptance-checked,
    #: so the gate can only ever change speed, never token values.
    spec_min_accept: float = 0.0
    #: continuous scheduler: lookahead DEPTH — up to this many decode chunks
    #: are kept in flight beyond the one being drained (an epoch ring), each
    #: chained off device-resident state, so the host's emit overlaps N device
    #: chunks. The HOST's number: no program reads it (programs.ProgramKey).
    #: A finish freezes its row inside the decode program and the ring
    #: survives it; admissions and resumes WAIT for an empty ring (PR 30); a
    #: preemption or a host-detected stop discards the stale suffix: streams
    #: are byte-identical at any depth (0 = synchronous; legacy bools parse).
    decode_lookahead: int = 2
    #: device-side stop-token matching width: per-slot stop ids live in a
    #: [n_slots, device_stop_width] device array (-1 padded). A request whose
    #: stop set exceeds this falls back to host-side stop detection for that
    #: slot (its stop finishes discard the in-flight ring, exactly the
    #: pre-device-termination behavior); max-tokens/window bounds are always
    #: device-resident regardless.
    device_stop_width: int = 8
    #: continuous scheduler: per-round prefill budget in prompt tokens
    #: (Sarathi-style mixed-batch rounds). Pending prompts are split into
    #: chunks of at most this many tokens that piggyback INTO decode rounds
    #: through the ragged paged-attention kernel (one dispatch serves decode
    #: rows at q_len=1 and the prefilling slot's chunk), so an arrival burst
    #: never stalls the decode streams behind a whole prompt. 0 = a prompt
    #: is one chunk.
    prefill_budget_tokens: int = 512
    #: continuous scheduler: bound on the pending (not-yet-admitted) queue.
    #: ``submit`` raises :class:`SchedulerSaturated` at the bound — the
    #: gateway maps it to 429 + Retry-After — instead of queueing without
    #: limit (unbounded host memory + unbounded queue latency under a
    #: storm). 0 = unbounded (pre-faultlab behavior).
    max_pending: int = 2048
    #: tenant isolation (continuous scheduler): when True the pending queue
    #: is a set of PER-TENANT FIFO queues drained by token-weighted fair
    #: scheduling — each tenant carries a virtual token counter (VTC)
    #: charged with the prefill + decode tokens it actually consumed, and
    #: admission always serves the backlogged tenant with the smallest
    #: weighted counter (FIFO preserved *within* a tenant). False restores
    #: the tenant-blind global FIFO. Fairness reorders ADMISSION only —
    #: tokens within a stream are byte-identical either way.
    tenant_fair: bool = True
    #: weight of any tenant not named in ``tenant_weights`` (the default
    #: class). A tenant with weight 2 is entitled to twice the token share
    #: of a weight-1 tenant while both are backlogged.
    tenant_default_weight: float = 1.0
    #: per-tenant weight overrides, ``{tenant_id: weight}``
    tenant_weights: Optional[dict] = None
    #: per-tenant cap on concurrently OCCUPIED slots (decode + chunked
    #: prefill); a tenant at its cap is skipped by admission until one of
    #: its slots frees — its requests stay queued, nobody else waits behind
    #: them. 0 = uncapped.
    tenant_max_slots: int = 0
    #: per-tenant SOFT cap on held KV pages: exceeding it only matters under
    #: contention (another tenant backlogged / requests suspended), where
    #: the round-boundary cap sweep YIELDS the over-cap tenant's youngest
    #: slot via the existing preempt-to-host path. 0 = uncapped.
    tenant_soft_pages: int = 0
    #: per-tenant HARD cap on held KV pages: a submit whose worst-case page
    #: need can never fit the quota is rejected outright
    #: (:class:`TenantQuotaExceeded` → 429), and admission skips a tenant
    #: already holding its quota. 0 = uncapped.
    tenant_max_pages: int = 0
    #: per-tenant bound on PENDING (not-yet-admitted) requests: overflow
    #: raises :class:`TenantSaturated` (its own 429 + Retry-After) so one
    #: tenant's retry storm saturates that tenant, not the global queue.
    #: 0 = unbounded (the global ``max_pending`` still applies).
    tenant_max_pending: int = 0
    #: tensor parallelism (continuous scheduler): shard the engine over the
    #: first ``tp`` visible devices as a NamedSharding mesh — Megatron-style
    #: weight shardings (parallel/sharding.py), the paged KV pool split on
    #: the kv-head axis, host-control rows (tokens/lengths/stops/page-table/
    #: sampling) explicitly replicated, and XLA GSPMD inserting the
    #: collectives inside the existing dispatch families. 1 (default) keeps
    #: the single-device engine byte-identical to pre-tp builds; tp=N on the
    #: forced-host CPU mesh produces bit-identical streams to tp=1 (pinned
    #: by tests/test_tp_engine.py). The 70B-class path is tp=8 (+int8) per
    #: FEASIBILITY_70B.json.
    tp: int = 1
    #: per-device HBM byte budget for the feasibility gate: engine
    #: construction derives the per-device plan (params + KV pool +
    #: activations via parallel/feasibility.py — the same shard math the AOT
    #: compiler lowers) and raises InfeasiblePlanError when the budget
    #: cannot hold it, so an over-HBM config (bf16@tp=8 on v5e) dies with a
    #: typed, explainable error at BUILD time instead of a device OOM at
    #: request time. 0 = plan without enforcing (CPU hosts / forced-host
    #: meshes have no HBM to protect; the plan still lands in
    #: stats()["mesh"]).
    hbm_bytes_per_device: int = 0
    #: prefill/decode disaggregation role. "" (default) = unified engine
    #: serving both phases. "prefill" = this engine runs ONLY chunked
    #: prefill (mixed-batch machinery with no decode rows) and exports each
    #: request's committed KV pages + resume state to a handoff sink after
    #: the first token; requires the paged pool + mixed batching. "decode" =
    #: this engine admits handed-off streams in a handoff phase that skips
    #: prefill entirely (deep ring + speculation intact); requires the paged
    #: pool. Set by PDServingPool (runtime/pd.py) via
    #: engine_options.pd_prefill_replicas / pd_decode_replicas.
    pd_role: str = ""
    #: a model with recurrent state (falcon_h1) keeps snapshots of it for
    #: prefix reuse in this many further rows of the state slab, each owned
    #: by the prefix-tree page at whose end it was taken (runtime/paged.py).
    #: -1 = as many as slots; 0 = none (every prompt prefills from its start).
    #: Ignored by models without state.
    state_snapshots: int = -1

    def resolve_lookahead_depth(self) -> int:
        """Lookahead ring depth as an int ≥ 0. Legacy bool configs parse as
        on/off: True → the class default depth, False → 0 (synchronous) —
        ONE rule for every entry path (direct EngineConfig, registry
        engine_options via the worker), so the same legacy value can never
        select different pipeline depths depending on which layer parsed
        it."""
        if isinstance(self.decode_lookahead, bool):
            return EngineConfig.decode_lookahead if self.decode_lookahead \
                else 0
        return max(0, int(self.decode_lookahead))

    def buckets(self) -> tuple[int, ...]:
        if self.prefill_buckets:
            return self.prefill_buckets
        out, b = [], 16
        while b < self.max_seq_len:
            out.append(b)
            b *= 2
        out.append(self.max_seq_len)
        return tuple(out)

    def bucket_for(self, length: int) -> int:
        """Smallest prefill bucket covering ``length``; rejects prompts that
        leave no decode room (a clamped first write would corrupt the cache)."""
        if length >= self.max_seq_len:
            raise ValueError(
                f"prompt length {length} leaves no decode room (max_seq_len "
                f"{self.max_seq_len}; prompts must be strictly shorter)"
            )
        for b in self.buckets():
            if length <= b:
                return b
        raise AssertionError("unreachable: buckets() always covers max_seq_len")


def build_decode_chunk_fn(model_config: ModelConfig, k_steps: int,
                          rope_tables) -> Callable:
    """The fused dense decode body: k (forward T=1 → lm_head → sample) steps
    under one lax.scan. The export path jits it (with its own donation
    spec)."""

    def decode_chunk(params, k_cache, v_cache, last_tokens, lengths, rng,
                     temperature, top_p, top_k):
        def step(carry, _):
            cache, toks, lens, rng = carry
            hidden, cache = llama.forward(
                params, model_config, toks[:, None], lens[:, None], cache, lens,
                rope_tables)
            logits = llama.lm_head_logits(params, model_config, hidden[:, 0, :])
            rng, sub = jax.random.split(rng)
            nxt = sample_token(logits, sub, temperature, top_p, top_k)
            return (cache, nxt, lens + 1, rng), nxt

        (cache, last, _, rng), toks = jax.lax.scan(
            step, ((k_cache, v_cache), last_tokens, lengths, rng),
            None, length=k_steps)
        return toks.T, cache[0], cache[1], last, rng  # toks: [B, k]

    return decode_chunk


@dataclass
class StepEvent:
    """One emitted token for one active request slot."""

    request_index: int
    token_id: int
    #: terminal reason when this is the final event: stop | length (clean
    #: finishes), error (engine fault — the replica pool fails it over),
    #: cancelled (client/gateway let go), deadline (the request's
    #: deadline lapsed — scheduler-side expiry sweep)
    finished: Optional[str] = None
