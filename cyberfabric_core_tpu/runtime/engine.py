"""InferenceEngine — jitted prefill/decode over a persistent device-resident KV cache.

The decode loop is the true hot loop (SURVEY §7 "hard parts"): one device step per
output token across the whole batch. Design:

- prefill and decode are separate jitted computations; the KV cache is **donated**
  on every call so XLA updates it in place (no per-token cache copy in HBM);
- prefill pads to bucket lengths (powers of two) so a handful of compiled programs
  serve all prompt lengths — no dynamic shapes, no recompiles in steady state;
- the LM head runs on the gathered last-token hidden state only;
- sampling happens on-device ([B] temperature/top-p/top-k runtime scalars) with a
  sort-free greedy fast path; decode fuses `decode_chunk` steps into one program
  via lax.scan, so the host pays one dispatch + one [B, k] readback per k tokens.

Reference anchors: this implements the llm-gateway "local worker" the specs left
abstract (DESIGN.md:317-346); TP sharding for multi-chip lives in parallel/ and is
applied by sharding the same param tree.
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import ModelConfig, get_config
from ..models import llama
from ..ops.rope import rope_frequencies
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
    donate_cache: bool = True
    #: model-level end-of-sequence ids (from the tokenizer/checkpoint config);
    #: per-request stop_token_ids extend these. No implicit guessing.
    eos_token_ids: tuple[int, ...] = ()
    #: decode steps fused into ONE device program via lax.scan. Each host→device
    #: dispatch costs ~1-70ms depending on transport; fusing k steps amortizes it
    #: k-fold. Tokens past a row's EOS within a chunk are discarded host-side.
    decode_chunk: int = 8
    #: Pallas flash kernel for prefill attention. None = auto (on for TPU).
    use_flash: Optional[bool] = None
    #: continuous scheduler: the page pool's size in pages. Anything under
    #: the slot minimum (every slot a full window, plus the scratch page),
    #: the default included, is raised to that minimum.
    prefix_cache_pages: int = 0
    prefix_page_size: int = 64
    #: weight-only quantization: "none" | "int8" | "int4" (each rung ~halves
    #: HBM + decode traffic; int4 is per-channel — the bandwidth experiment,
    #: int8 the accuracy default — see runtime/quant.py)
    quantization: str = "none"
    #: speculative decoding: "off" | "ngram" (prompt-lookup drafting + one
    #: fused [1, k+1] verify forward; greedy bs=1 only, lossless) | "draft"
    #: (a small draft MODEL proposes k tokens; fused verify with Leviathan
    #: acceptance sampling — distribution-preserving at any temperature,
    #: bit-lossless at temperature 0 — see runtime/speculative.py).
    #: Non-eligible requests fall back silently.
    speculative: str = "off"
    spec_k: int = 8
    spec_max_ngram: int = 3
    spec_min_ngram: int = 1
    #: draft mode: config name of the proposer model (must share the target's
    #: vocab/tokenizer) + optional checkpoint dir for its weights
    draft_model: str = ""
    draft_checkpoint: str = ""
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
    #: (prompt-lookup / NgramProposer). The legacy ``speculative``/``spec_k``
    #: fields keep driving only the lockstep InferenceEngine path.
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
    #: the tenant-blind global FIFO (the A/B baseline for
    #: ``bench.py --fairness-guard``). Fairness reorders ADMISSION only —
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

    def resolve_use_flash(self) -> bool:
        if self.use_flash is not None:
            return self.use_flash
        from ..ops.platform import default_interpret

        # flash defaults on whenever kernels compile for real (live TPU, or
        # AOT lowering against a TPU topology under compiled_kernels())
        return not default_interpret()

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
    under one lax.scan. The lockstep engine and the export path jit this same
    function (with their own donation specs)."""

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
class GenerationResult:
    token_ids: list[int]
    finish_reason: str  # stop | length
    prompt_tokens: int
    completion_tokens: int
    ttft_ms: float = 0.0
    total_ms: float = 0.0


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


class InferenceEngine:
    """Batch-synchronous engine: prefill a batch, then lockstep decode.

    The continuous-batching scheduler (runtime/scheduler.py) drives the same jitted
    computations with slot-level admission; this class is the direct path used by
    single-shot generation and the benchmarks.
    """

    def __init__(
        self,
        config: EngineConfig,
        model_config: Optional[ModelConfig] = None,
        params: Optional[Any] = None,
        seed: int = 0,
    ) -> None:
        self.config = config
        self.model_config = model_config or get_config(config.model)
        if self.model_config.architecture != "llama":
            raise ValueError(
                f"InferenceEngine drives llama-family decoders through a "
                f"dense cache, got {self.model_config.architecture!r} "
                f"({self.model_config.name}): a model with recurrent state "
                "is served by the continuous scheduler's paged path only")
        self.dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.dtype(config.dtype)
        from .quant import quant_bits as _qb

        quant_bits = _qb(config.quantization)
        if params is None:
            if quant_bits is not None:
                from .quant import init_params_quantized

                params = init_params_quantized(
                    self.model_config, jax.random.PRNGKey(seed), self.dtype,
                    bits=quant_bits)
            else:
                params = llama.init_params(
                    self.model_config, jax.random.PRNGKey(seed), self.dtype)
        elif quant_bits is not None and not isinstance(
                params.get("embed"), dict):  # already-quantized trees pass through
            from .quant import quantize_llama_params

            params = quantize_llama_params(params, bits=quant_bits)
        self.params = params
        self.rope_tables = rope_frequencies(
            self.model_config.head_dim,
            max(self.model_config.max_position, config.max_seq_len),
            self.model_config.rope_theta,
        )
        self._rng = jax.random.PRNGKey(seed)
        self._compiled_prefill: dict[tuple[int, int], Callable] = {}
        self._decode_fn = self._build_decode(max(1, config.decode_chunk))
        self._decode_tail_fn: Optional[Callable] = None  # k=1, built on demand
        self._verify_fn: Optional[Callable] = None  # spec decode, on demand
        self._verify_accept_fn: Optional[Callable] = None  # draft mode
        self._draft = None  # DraftModel, built on first draft-mode request
        #: cumulative speculative-decoding counters (observability surface);
        #: accept_hist[a] counts verify rounds that accepted exactly a drafts
        #: (the acceptance-length distribution the perf claim rests on)
        self.spec_stats = {"verify_calls": 0, "drafted": 0, "accepted": 0,
                           "spec_tokens": 0, "fallback_steps": 0,
                           "accept_hist": {}}

    def _record_spec_round(self, a: int, spec_k: int, committed: int) -> None:
        """One verify round's evidence — shared by the ngram and draft paths
        so the acceptance stats can never drift between them."""
        s = self.spec_stats
        s["verify_calls"] += 1
        s["drafted"] += spec_k
        s["accepted"] += a
        s["spec_tokens"] += committed
        s["accept_hist"][a] = s["accept_hist"].get(a, 0) + 1

    # ------------------------------------------------------------------ jit builders
    def _build_prefill(self) -> Callable:
        """Prefill + FIRST-token sampling in one program, with the KV cache
        CREATED inside the program: TTFT costs exactly one dispatch round trip
        (no separate zeros-allocation dispatch per request)."""
        cfg = self.model_config
        max_seq = self.config.max_seq_len
        dtype = self.dtype
        use_flash = self.config.resolve_use_flash()

        def prefill(params, input_ids, lengths, rng, temperature, top_p, top_k, rope):
            B, T = input_ids.shape
            cache = llama.init_cache(cfg, B, max_seq, dtype)
            positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
            start = jnp.zeros((B,), jnp.int32)
            hidden, cache = llama.forward(params, cfg, input_ids, positions, cache, start, rope,
                                          use_flash=use_flash)
            last_h = llama.gather_last_hidden(hidden, lengths)
            logits = llama.lm_head_logits(params, cfg, last_h)  # [B, V] f32
            rng, sub = jax.random.split(rng)
            first = sample_token(logits, sub, temperature, top_p, top_k)
            return first, cache, rng

        return jax.jit(prefill)

    def _build_decode(self, k_steps: int) -> Callable:
        """Jit the shared fused decode body (one dispatch, one [B, k] readback)."""
        fn = build_decode_chunk_fn(self.model_config, k_steps, self.rope_tables)
        return jax.jit(fn, donate_argnums=(1, 2) if self.config.donate_cache else ())

    def _prefill_for(self, batch: int, bucket: int) -> Callable:
        key = (batch, bucket)
        fn = self._compiled_prefill.get(key)
        if fn is None:
            fn = self._build_prefill()
            self._compiled_prefill[key] = fn
        return fn

    def _bucket_for(self, length: int) -> int:
        return self.config.bucket_for(length)

    # ------------------------------------------------------------------ profiling
    def decode_cost_analysis(self, batch: Optional[int] = None) -> dict:
        """XLA cost analysis of one fused decode chunk (SURVEY §5 device-side
        profiling): flops + bytes per chunk, and per-token derived numbers —
        the roofline inputs for tokens/sec work. The AOT-compiled program is
        cached per batch size (lower().compile() bypasses the jit cache)."""
        from ..modkit.telemetry import xla_cost_summary

        B = batch or self.config.max_batch
        if not hasattr(self, "_cost_compiled"):
            self._cost_compiled: dict[int, Any] = {}
        compiled = self._cost_compiled.get(B)
        if compiled is None:
            cfg = self.model_config
            # abstract avals only — lowering must not allocate a second KV
            # cache on a device already holding the live one
            sds = jax.ShapeDtypeStruct
            cache_aval = sds((cfg.kv_layers, B, self.config.max_seq_len,
                              cfg.num_kv_heads, cfg.head_dim), self.dtype)
            params_avals = jax.tree.map(
                lambda a: sds(jnp.shape(a), jnp.asarray(a).dtype), self.params)
            args = (params_avals, cache_aval, cache_aval,
                    sds((B,), jnp.int32), sds((B,), jnp.int32),
                    sds((2,), jnp.uint32), sds((B,), jnp.float32),
                    sds((B,), jnp.float32), sds((B,), jnp.int32))
            compiled = self._decode_fn.lower(*args).compile()
            self._cost_compiled[B] = compiled
        out = xla_cost_summary(compiled)
        k = max(1, self.config.decode_chunk)
        if "flops" in out:
            out["flops_per_token"] = out["flops"] / (B * k)
        if "bytes_accessed" in out:
            out["bytes_per_token"] = out["bytes_accessed"] / (B * k)
        out["batch"] = B
        out["decode_chunk"] = k
        return out

    # ------------------------------------------------------------------ generation
    def generate(
        self,
        prompts: list[list[int]],
        sampling: SamplingParams | list[SamplingParams],
        *,
        on_token: Optional[Callable[[StepEvent], None]] = None,
    ) -> list[GenerationResult]:
        """Lockstep batched generation. Emits StepEvents as tokens are produced."""
        events = self.generate_stream(prompts, sampling)
        results: dict[int, GenerationResult] = {}
        collected: dict[int, list[int]] = {i: [] for i in range(len(prompts))}
        meta: dict[int, dict] = {}
        for ev in events:
            if ev.token_id >= 0:  # token-less finish events carry -1
                collected[ev.request_index].append(ev.token_id)
            if on_token:
                on_token(ev)
            if ev.finished:
                meta[ev.request_index] = {"finish": ev.finished}
        # generate_stream attaches timing on self._last_timing
        timing = self._last_timing
        for i, prompt in enumerate(prompts):
            toks = collected[i]
            fin = meta.get(i, {}).get("finish", "length")
            if fin == "stop" and toks:
                toks = toks[:-1]  # drop the stop token from visible output
            results[i] = GenerationResult(
                token_ids=toks,
                finish_reason=fin,
                prompt_tokens=len(prompt),
                completion_tokens=len(toks),
                ttft_ms=timing["ttft_ms"],
                total_ms=timing["total_ms"],
            )
        return [results[i] for i in range(len(prompts))]

    def _ensure_draft(self, spec_k: int):
        """Build the draft model once per engine: weights from
        ``draft_checkpoint`` when given (the real deployment shape — e.g. a
        1B drafting for an 8B), else seeded synthetic (mechanics-only: a
        random draft accepts ~never but stays lossless)."""
        if self._draft is None:
            from pathlib import Path

            from ..models.configs import get_config
            from .speculative import DraftModel

            dcfg = get_config(self.config.draft_model)
            if dcfg.vocab_size != self.model_config.vocab_size:
                raise ValueError(
                    f"draft model {self.config.draft_model!r} vocab "
                    f"{dcfg.vocab_size} != target vocab "
                    f"{self.model_config.vocab_size} — speculation needs a "
                    "shared tokenizer")
            ckpt = self.config.draft_checkpoint
            if ckpt:
                if not Path(ckpt).exists():
                    # never fall back silently: a typo'd path would yield a
                    # random draft with ~zero acceptance — output stays
                    # lossless, so the severe throughput regression would
                    # surface nowhere (round-4 advisory, medium)
                    raise ValueError(
                        f"draft_checkpoint {ckpt!r} does not exist; unset it "
                        "to run with synthetic draft weights (test mode)")
                from .weights import load_llama_params

                dparams = load_llama_params(ckpt, dcfg, dtype=self.dtype)
            else:
                dparams = llama.init_params(dcfg, jax.random.PRNGKey(7),
                                            self.dtype)
            self._draft = DraftModel(dcfg, dparams,
                                     max_seq=self.config.max_seq_len,
                                     dtype=self.dtype, k=spec_k)
        return self._draft

    def generate_stream(
        self,
        prompts: list[list[int]],
        sampling: SamplingParams | list[SamplingParams],
    ) -> Iterator[StepEvent]:
        """Yields StepEvents, `decode_chunk` tokens per device round trip."""
        B = len(prompts)
        if B == 0:
            self._last_timing = {"ttft_ms": 0.0, "total_ms": 0.0}
            return
        if B > self.config.max_batch:
            raise ValueError(f"batch {B} exceeds max_batch {self.config.max_batch}")
        per_req = sampling if isinstance(sampling, list) else [sampling] * B
        # per-request seed (REQUEST schema): when the whole batch shares one
        # explicit seed, sampling is reproducible across calls. (Mixed seeds in
        # one lockstep batch are best-effort — the continuous scheduler docs
        # the same; per-row device keys are a later refinement.)
        seeds = {s.seed for s in per_req}
        if len(seeds) == 1 and (seed_val := next(iter(seeds))) is not None:
            self._rng = jax.random.PRNGKey(seed_val)
        t_start = time.monotonic()

        lengths_list = [len(p) for p in prompts]
        max_len = max(lengths_list)
        bucket = self._bucket_for(max_len)
        ids = np.zeros((B, bucket), np.int32)
        for i, p in enumerate(prompts):
            ids[i, : len(p)] = p
        lengths = jnp.asarray(lengths_list, jnp.int32)

        temperature = jnp.asarray([s.temperature for s in per_req], jnp.float32)
        top_p = jnp.asarray([s.top_p for s in per_req], jnp.float32)
        top_k = jnp.asarray([s.top_k for s in per_req], jnp.int32)

        prefill = self._prefill_for(B, bucket)
        first_dev, cache, self._rng = prefill(
            self.params, jnp.asarray(ids), lengths, self._rng,
            temperature, top_p, top_k, self.rope_tables,
        )
        first = np.asarray(first_dev, np.int32)
        ttft_ms = (time.monotonic() - t_start) * 1000.0

        stops = [set(s.stop_token_ids) | set(self.config.eos_token_ids) for s in per_req]
        max_new = [s.max_tokens for s in per_req]
        done = [False] * B
        emitted = [0] * B

        def classify(i: int, tok: int) -> Optional[str]:
            if tok in stops[i]:
                return "stop"
            if emitted[i] >= max_new[i]:
                return "length"
            return None

        cur = first
        lengths_np = np.asarray(lengths_list, np.int32)
        step_lengths = jnp.asarray(lengths_np)
        last_tokens = first_dev  # stays on device; no H2D round trip

        # emit first tokens
        for i in range(B):
            emitted[i] += 1
            fin = classify(i, int(cur[i]))
            done[i] = fin is not None
            yield StepEvent(i, int(cur[i]), fin)

        k_steps = max(1, self.config.decode_chunk)
        steps = 0
        max_steps = max(max_new) if max_new else 0

        def run_chunk(fn, k):
            nonlocal cache, last_tokens, lengths_np, step_lengths, steps
            chunk_dev, kc, vc, last, self._rng = fn(
                self.params, cache[0], cache[1], last_tokens, step_lengths,
                self._rng, temperature, top_p, top_k,
            )
            cache = (kc, vc)
            last_tokens = last
            lengths_np = lengths_np + k
            step_lengths = step_lengths + k
            steps += k
            return np.asarray(chunk_dev, np.int32)  # sync: one [B, k] readback

        def emit_chunk(chunk, k, next_fits):
            # rows that can't continue finish with "length" on their final
            # emitted token (single event per token)
            last_dispatchable = not next_fits or steps >= max_steps
            for j in range(k):
                for i in range(B):
                    if done[i]:
                        continue
                    emitted[i] += 1
                    tok = int(chunk[i, j])
                    fin = classify(i, tok)
                    if fin is None and last_dispatchable and j == k - 1:
                        fin = "length"
                    done[i] = fin is not None
                    yield StepEvent(i, tok, fin)

        def spec_loop():
            """Prompt-lookup speculative decode (greedy bs=1, lossless —
            runtime/speculative.py). Each iteration commits 1..spec_k+1
            tokens for one device round trip."""
            nonlocal cache
            from .speculative import NgramProposer, accept_length, build_verify_fn

            spec_k = max(1, self.config.spec_k)
            if self._verify_fn is None:
                self._verify_fn = build_verify_fn(
                    self.model_config, spec_k, self.rope_tables)
            proposer = NgramProposer(self.config.spec_max_ngram,
                                     self.config.spec_min_ngram, spec_k)
            last_tok = int(cur[0])
            proposer.extend(list(prompts[0]) + [last_tok])
            L = int(lengths_np[0])
            max_seq = self.config.max_seq_len

            while not done[0] and emitted[0] < max_new[0] and L < max_seq:
                drafts = (proposer.propose()
                          if L + spec_k + 1 <= max_seq else None)
                if drafts is None:
                    # no recurring n-gram (or window tail): plain single step
                    if self._decode_tail_fn is None:
                        self._decode_tail_fn = self._build_decode(1)
                    self.spec_stats["fallback_steps"] += 1
                    chunk_dev, kc, vc, _, self._rng = self._decode_tail_fn(
                        self.params, cache[0], cache[1],
                        jnp.asarray([last_tok], jnp.int32),
                        jnp.asarray([L], jnp.int32),
                        self._rng, temperature, top_p, top_k)
                    cache = (kc, vc)
                    toks = [int(np.asarray(chunk_dev)[0, 0])]
                    L += 1
                else:
                    # pad to the static draft width; a padded token only gets
                    # accepted when it IS the greedy argmax, so padding never
                    # changes output
                    drafts = (drafts + [drafts[-1]] * spec_k)[:spec_k]
                    tokens = jnp.asarray([[last_tok] + drafts], jnp.int32)
                    outs_dev, kc, vc = self._verify_fn(
                        self.params, cache[0], cache[1], tokens,
                        jnp.asarray([L], jnp.int32))
                    cache = (kc, vc)
                    outs = np.asarray(outs_dev, np.int32)[0].tolist()
                    a = accept_length(drafts, outs)
                    toks = drafts[:a] + [outs[a]]
                    self._record_spec_round(a, spec_k, len(toks))
                    L += a + 1
                proposer.extend(toks)
                for j, tok in enumerate(toks):
                    if done[0]:
                        break  # tokens past a finish are discarded
                    emitted[0] += 1
                    last_tok = tok
                    fin = classify(0, tok)
                    if fin is None and j == len(toks) - 1 and L >= max_seq:
                        fin = "length"  # window exhausted on this token
                    done[0] = fin is not None
                    yield StepEvent(0, tok, fin)
            lengths_np[0] = L  # keep the shared epilogue's view consistent

        def draft_spec_loop():
            """Draft-MODEL speculation (bs=1, any temperature): the small
            draft proposes k sampled tokens, the target runs ONE fused
            verify + acceptance-sampling pass (runtime/speculative.py) —
            distribution-preserving always, bit-lossless at temperature 0.
            Each round commits 1..k+1 target tokens for one big forward."""
            nonlocal cache
            spec_k = max(1, self.config.spec_k)
            draft = self._ensure_draft(spec_k)
            if self._verify_accept_fn is None:
                from .speculative import build_verify_accept_fn

                self._verify_accept_fn = build_verify_accept_fn(
                    self.model_config, spec_k, self.rope_tables)
            self._rng, dk = jax.random.split(self._rng)
            draft.reset(list(prompts[0]), dk)
            last_tok = int(cur[0])
            L = int(lengths_np[0])
            max_seq = self.config.max_seq_len

            while not done[0] and emitted[0] < max_new[0] and L < max_seq:
                window_ok = (L + spec_k + 1 <= max_seq
                             and draft.len + spec_k + 1 <= draft.max_seq)
                if not window_ok:
                    if self._decode_tail_fn is None:
                        self._decode_tail_fn = self._build_decode(1)
                    self.spec_stats["fallback_steps"] += 1
                    chunk_dev, kc, vc, _, self._rng = self._decode_tail_fn(
                        self.params, cache[0], cache[1],
                        jnp.asarray([last_tok], jnp.int32),
                        jnp.asarray([L], jnp.int32),
                        self._rng, temperature, top_p, top_k)
                    cache = (kc, vc)
                    toks = [int(np.asarray(chunk_dev)[0, 0])]
                    L += 1
                else:
                    drafts, dists = draft.propose(last_tok, temperature,
                                                  top_p, top_k)
                    tokens = jnp.asarray([[last_tok] + drafts], jnp.int32)
                    a_dev, nxt_dev, self._rng, kc, vc = self._verify_accept_fn(
                        self.params, cache[0], cache[1], tokens,
                        jnp.asarray([L], jnp.int32), jnp.stack(dists),
                        self._rng, temperature[:1], top_p[:1], top_k[:1])
                    cache = (kc, vc)
                    a = int(a_dev)
                    nxt = int(nxt_dev)
                    toks = drafts[:a] + [nxt]
                    # draft cache bookkeeping: drafting already wrote KV for
                    # (last_tok, d1..d_{k-1}). The bonus/resampled token stays
                    # PENDING (same convention as the target — its KV lands
                    # when next round consumes it); on full acceptance d_k
                    # still needs consuming first.
                    if a < spec_k:
                        draft.len += a + 1
                    else:
                        draft.len += spec_k
                        draft.consume([drafts[-1]], temperature, top_p, top_k)
                    self._record_spec_round(a, spec_k, len(toks))
                    L += a + 1
                for j, tok in enumerate(toks):
                    if done[0]:
                        break
                    emitted[0] += 1
                    last_tok = tok
                    fin = classify(0, tok)
                    if fin is None and j == len(toks) - 1 and L >= max_seq:
                        fin = "length"
                    done[0] = fin is not None
                    yield StepEvent(0, tok, fin)
            lengths_np[0] = L

        if (self.config.speculative == "draft" and B == 1
                and self.config.draft_model and not all(done)):
            yield from draft_spec_loop()
        elif (self.config.speculative == "ngram" and B == 1
                and all(s.temperature == 0.0 for s in per_req)
                and not all(done)):
            yield from spec_loop()
        else:
            while not all(done) and steps < max_steps:
                # a chunk writes k cache slots from the current length; it must
                # fit entirely (chunks are static-shaped — no partial dispatch)
                if int(lengths_np.max()) + k_steps > self.config.max_seq_len:
                    break
                chunk = run_chunk(self._decode_fn, k_steps)
                next_fits = int(lengths_np.max()) + k_steps <= self.config.max_seq_len
                # once full chunks stop fitting, the k=1 tail decoder continues
                tail_will_run = (not next_fits
                                 and int(lengths_np.max()) < self.config.max_seq_len)
                yield from emit_chunk(chunk, k_steps, next_fits or tail_will_run)

            # tail: single-step decode fills the last < decode_chunk slots of
            # the window so near-capacity prompts still decode to the brim
            while not all(done) and steps < max_steps \
                    and int(lengths_np.max()) < self.config.max_seq_len:
                if self._decode_tail_fn is None:
                    self._decode_tail_fn = self._build_decode(1)
                chunk = run_chunk(self._decode_tail_fn, 1)
                next_fits = int(lengths_np.max()) < self.config.max_seq_len
                yield from emit_chunk(chunk, 1, next_fits)

        # epilogue: any still-active row gets a token-less finish event so every
        # stream terminates with a reason
        for i in range(B):
            if not done[i]:
                done[i] = True
                yield StepEvent(i, -1, "length")

        self._last_timing = {
            "ttft_ms": ttft_ms,
            "total_ms": (time.monotonic() - t_start) * 1000.0,
        }

    # ------------------------------------------------------------------ warmup
    def warmup(self, lengths: tuple[int, ...] = ()) -> None:
        """Pre-compile prefill buckets + decode so first requests aren't 20-40s."""
        for bucket in lengths or (self.config.buckets()[0],):
            prompt = [1] * min(bucket, 8)
            self.generate([prompt], SamplingParams(max_tokens=2))
