"""Tensor-parallel continuous batching on the virtual 8-device CPU mesh.

The acceptance contract of the tp tentpole: ``EngineConfig.tp`` lifts the
WHOLE continuous scheduler onto a NamedSharding mesh — Megatron-sharded
params, the paged KV pool split on the kv-head axis, replicated host-control
rows — and the streams it emits are BIT-IDENTICAL to the single-device
engine across every dispatch family: chunked mixed-batch prefill,
the deep lookahead ring, spec-k ragged verify spans, seeded sampling, and
mid-stream cancellation. Sharding is an implementation detail, never a
semantics change (the test_parallel.py invariant, now end-to-end through
the serving engine).

The feasibility gate rides along: an over-HBM plan (FEASIBILITY_70B's
bf16@tp=8 shape) dies at engine construction as a typed
InfeasiblePlanError, never as a device OOM at request time.
"""

import threading
import time

import jax
import pytest

from cyberfabric_core_tpu.parallel.feasibility import InfeasiblePlanError
from cyberfabric_core_tpu.runtime.engine import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine


def _config(tp: int, **over) -> EngineConfig:
    base = dict(model="tiny-llama", max_seq_len=128, max_batch=4,
                decode_chunk=4, prefix_cache_pages=64, prefix_page_size=8,
                decode_lookahead=2, scheduler_spec_k=2, tp=tp)
    base.update(over)
    return EngineConfig(**base)


def _drive(engine: ContinuousBatchingEngine, requests: list[tuple],
           cancel_at: dict = None, timeout: float = 240.0):
    """Submit ``requests`` [(prompt, sampling), ...] and collect every
    stream as [(token_id, finished), ...]. ``cancel_at[i] = n`` cancels
    request i from its own emit callback once n tokens arrived — the
    deterministic mid-stream cancel the PR-9 storm scenario uses."""
    cancel_at = cancel_at or {}
    streams: dict[int, list] = {i: [] for i in range(len(requests))}
    rids: dict[int, str] = {}
    done = threading.Event()
    left = [len(requests)]

    def mk(i):
        tokens_seen = [0]

        def emit(ev):
            streams[i].append((ev.token_id, ev.finished))
            if ev.token_id >= 0:
                tokens_seen[0] += 1
                if tokens_seen[0] == cancel_at.get(i):
                    engine.cancel(rids[i], "test_cancel")
            if ev.finished:
                left[0] -= 1
                if left[0] == 0:
                    done.set()
        return emit

    for i, (prompt, sampling) in enumerate(requests):
        rids[i] = engine.submit(list(prompt), sampling, mk(i))
    assert done.wait(timeout), "streams did not finish"
    return streams


def _scenarios(engine: ContinuousBatchingEngine):
    """The composition suite, run sequentially through ONE engine so the
    prefix-cache state evolves identically across tp arms: a greedy
    mixed-batch storm with a shared prefix (radix hit on the repeat), a
    seeded stochastic stream, a window-bound stream, and a mid-stream
    cancel with greedy survivors."""
    out = {}
    # tiled motifs: the ngram proposer needs recurring n-grams, so greedy
    # limit-bound streams actually PROPOSE spec spans from the first rounds
    shared = [5, 6, 7] * 3
    # 1) greedy storm: duplicate prompts exercise coalescing/prefix reuse,
    #    greedy limit-bound streams arm spec-k spans, the tail prompt spans
    #    page boundaries (12 tokens over page_size=8)
    out["storm"] = _drive(engine, [
        (shared, SamplingParams(max_tokens=24)),
        (shared, SamplingParams(max_tokens=20)),
        ([20, 21, 22, 23] * 3, SamplingParams(max_tokens=16)),
    ])
    # 2) seeded stochastic + greedy companion (per-slot key streams under
    #    the mesh must reproduce the exact single-device sequence)
    out["seeded"] = _drive(engine, [
        ([3, 4, 5, 6, 7], SamplingParams(max_tokens=16, temperature=0.8,
                                         top_p=0.9, seed=1234)),
        ([9, 8, 7, 6, 5, 4], SamplingParams(max_tokens=12)),
    ])
    # 3) window-bound: max_tokens unreachable before max_seq — the force-
    #    length chunk-lattice finish must land on the same boundary
    out["window"] = _drive(engine, [
        ([2] * 100, SamplingParams(max_tokens=500)),
    ])
    # 4) mid-stream cancel: victim killed from its own emit callback after
    #    3 tokens; the greedy survivors must lose nothing
    out["cancel"] = _drive(engine, [
        ([40, 41, 42, 43, 44], SamplingParams(max_tokens=48)),
        ([50, 51, 52, 53], SamplingParams(max_tokens=20)),
        ([60, 61, 62, 63, 64, 65], SamplingParams(max_tokens=20)),
    ], cancel_at={0: 3})
    return out


@pytest.fixture(scope="module")
def tp_runs():
    """One run of the composition suite per tp degree. tp=2 shards the
    pool's kv-head axis for real (tiny-llama has 2 kv heads); tp=8 is the
    acceptance topology (pool replicated, params still tp-sharded)."""
    runs = {}
    for tp in (1, 2, 8):
        engine = ContinuousBatchingEngine(_config(tp), seed=0)
        engine.start()
        runs[tp] = (engine, _scenarios(engine))
        stats = engine.stats()
        engine.shutdown()
        runs[tp] = (stats, runs[tp][1],
                    getattr(engine.pool.k_pool, "sharding", None))
    return runs


def _assert_identical(a, b, scenario, cancelled=()):
    for i in a[scenario]:
        sa, sb = a[scenario][i], b[scenario][i]
        if i in cancelled:
            # the cancel lands at a round boundary, so the cut point may
            # shift with host timing — token VALUES and the terminal must
            # agree (the survivors' full bitwise identity is the claim)
            ra = [t for t, _ in sa if t >= 0]
            rb = [t for t, _ in sb if t >= 0]
            n = min(len(ra), len(rb))
            assert ra[:n] == rb[:n], f"{scenario}[{i}] diverged pre-cancel"
            assert sa[-1][1] == sb[-1][1] == "cancelled"
        else:
            assert sa == sb, f"{scenario}[{i}] diverged"


@pytest.mark.parametrize("tp", [2, 8])
def test_tp_streams_bit_identical(tp_runs, tp):
    """The acceptance criterion: every scenario's streams at tp=N equal the
    tp=1 run bit-for-bit (greedy, seeded, window-bound), and the cancel
    scenario's survivors too."""
    _, base, _ = tp_runs[1]
    _, mesh_run, _ = tp_runs[tp]
    _assert_identical(base, mesh_run, "storm")
    _assert_identical(base, mesh_run, "seeded")
    _assert_identical(base, mesh_run, "window")
    _assert_identical(base, mesh_run, "cancel", cancelled={0})


def test_tp_compositions_actually_engaged(tp_runs):
    """The identity claim is vacuous unless the tp run exercised the real
    machinery: mixed-batch rounds, the lookahead ring, spec-k spans and a
    cancel terminal must all have fired on the mesh engine."""
    stats, _, _ = tp_runs[8]
    pipe = stats["pipeline"]
    assert pipe["mixed_rounds"] > 0, "no ragged mixed-batch dispatch ran"
    assert pipe["lookahead_rounds"] > 0, "the deep ring never engaged"
    assert stats["speculative"]["proposed"] > 0, "no spec span was planned"
    assert stats["cancellations"].get("test_cancel") == 1
    assert stats["tokens_emitted"] > 0


def test_tp_mesh_surface(tp_runs):
    """stats()['mesh'] reports the topology, tp degree, pool sharding and
    the feasibility plan; the pool's NamedSharding survives a full serve
    cycle (admission, chunked prefill, ring, spec, cancel, release)."""
    stats1, _, _ = tp_runs[1]
    assert stats1["mesh"]["tp"] == 1 and stats1["mesh"]["devices"] == 1
    stats2, _, pool_sharding = tp_runs[2]
    mesh2 = stats2["mesh"]
    assert mesh2["tp"] == 2 and mesh2["devices"] == 2
    assert mesh2["kv_heads_sharded"] is True  # tiny-llama: 2 kv heads / 2
    assert mesh2["plan"]["fits"] is True and mesh2["plan"]["enforced"] is False
    # the load-bearing propagation pin: every pool update path (scatter,
    # decode writes, restore) must preserve the head sharding, or serving
    # silently degrades to full replication after the first round
    assert pool_sharding is not None and "tp" in tuple(pool_sharding.spec)
    stats8, _, _ = tp_runs[8]
    assert stats8["mesh"]["kv_heads_sharded"] is False  # 2 heads % 8 != 0
    assert stats8["mesh"]["sharded_page_bytes_per_device"] > 0


def test_tp_rejects_pinned_device():
    """tp>1 cannot combine with dp-pool device pinning — one engine, one
    parallelism axis."""
    with pytest.raises(ValueError, match="pinned device"):
        ContinuousBatchingEngine(_config(2), device=jax.devices()[0])


def test_feasibility_gate_rejects_over_budget_plan():
    """The FEASIBILITY_70B bf16@tp=8 verdict enforced at BUILD time: engine
    construction with a known HBM budget raises the typed error (with the
    machine-derived plan attached) before any allocation — never a device
    OOM at request time."""
    from cyberfabric_core_tpu.models.configs import get_config

    cfg = _config(8, model="llama-3-70b",
                  hbm_bytes_per_device=16 * 1024**3)
    t0 = time.monotonic()
    with pytest.raises(InfeasiblePlanError) as exc:
        ContinuousBatchingEngine(cfg, model_config=get_config("llama-3-70b"))
    # the gate fires on eval_shape math, long before a 70B tree could ever
    # materialize (seconds, not a 140GB allocation attempt)
    assert time.monotonic() - t0 < 30.0
    plan = exc.value.plan
    assert plan["fits"] is False and plan["enforced"] is True
    assert plan["total_bytes_per_device"] > 16 * 1024**3
    assert "tp=8" in str(exc.value)


def test_worker_infeasible_plan_is_clean_problem():
    """The worker half of the gate satellite: a registry model whose
    engine_options carry the over-budget plan surfaces as the typed
    llm.infeasible_plan 507 problem at first request — a clean response,
    never a device OOM (and never a generic 500)."""
    import asyncio

    from cyberfabric_core_tpu.modkit.errors import ProblemError
    from cyberfabric_core_tpu.modules.llm_gateway.worker import LocalTpuWorker
    from cyberfabric_core_tpu.modules.sdk import ModelInfo

    model = ModelInfo(
        canonical_id="local::tp-70b-bf16", provider_slug="local",
        provider_model_id="tp-70b-bf16",
        engine_options={"model_config": "llama-3-70b", "max_seq_len": 2048,
                        "max_batch": 8, "tp": 8,
                        "hbm_bytes_per_device": 16 * 1024**3})

    async def go():
        worker = LocalTpuWorker({})
        agen = worker.completion_stream(model, "hello", {"max_tokens": 4})
        try:
            await agen.__anext__()
        except ProblemError as e:
            return e.problem, worker
        finally:
            await agen.aclose()
        raise AssertionError("infeasible plan served a token")

    problem, worker = asyncio.run(go())
    assert problem.code == "infeasible_plan"
    assert problem.status == 507
    assert "tp=8" in (problem.detail or "")
    # the entry never landed: a retry re-gates instead of reusing a corpse
    assert not worker._entries


def test_worker_rejects_tp_with_dp_pool():
    """dp_replicas pins one device per replica; combining it with a tp mesh
    must fail loudly at build, not crash in the engine's pinning check."""
    import asyncio

    from cyberfabric_core_tpu.modules.llm_gateway.worker import LocalTpuWorker
    from cyberfabric_core_tpu.modules.sdk import ModelInfo

    model = ModelInfo(
        canonical_id="local::tp-dp", provider_slug="local",
        provider_model_id="tp-dp",
        engine_options={"model_config": "tiny-llama", "max_seq_len": 128,
                        "max_batch": 2, "tp": 2, "dp_replicas": 2})

    async def go():
        worker = LocalTpuWorker({})
        agen = worker.completion_stream(model, "hello", {"max_tokens": 4})
        try:
            await agen.__anext__()
        finally:
            await agen.aclose()

    with pytest.raises(ValueError, match="cannot combine"):
        asyncio.run(go())


def test_aot_serving_set_tp_keying():
    """The AOT serving set gains (topology, tp, spec_k, stop_width)-keyed
    variants: with a tp mesh, every program name carries the -tpN suffix,
    the param tree carries the Megatron shardings, the pool shards on the
    kv-head axis and every control row is explicitly replicated (the SH01
    discipline mirrored into the lowering args). Pure tracing — no
    compile, so this runs in tier-1 while the minutes-scale Mosaic compile
    stays in the slow AOT gate."""
    import numpy as np
    from jax.sharding import Mesh

    from cyberfabric_core_tpu.runtime.aot_tpu import serving_programs

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("ep", "tp"))
    progs = serving_programs("tiny-llama", prefill_bucket=32, decode_chunk=4,
                             max_batch=2, max_seq_len=64, page_size=16,
                             spec_k=2, mesh=mesh)
    assert set(progs) == {"prefill-flash-b1x32-tp2", "paged-decode-k4x2-tp2",
                          "spec-verify-w3x2-tp2"}
    _, decode_args = progs["paged-decode-k4x2-tp2"]
    params_abs, k_pool_abs = decode_args[0], decode_args[1]
    # pool: kv-head axis on tp (tiny-llama: 2 kv heads / 2)
    assert "tp" in tuple(k_pool_abs.sharding.spec)
    # weights: wq column-parallel on tp
    assert "tp" in tuple(params_abs["layers"]["wq"].sharding.spec)
    # every remaining arg (control rows, keys) pins an explicit sharding
    for arg in decode_args[2:]:
        for leaf in jax.tree.leaves(arg):
            assert getattr(leaf, "sharding", None) is not None
    # tp=0 path unchanged: same names as the committed AOT goldens
    plain = serving_programs("tiny-llama", prefill_bucket=32, decode_chunk=4,
                             max_batch=2, max_seq_len=64, page_size=16)
    assert set(plain) == {"prefill-flash-b1x32", "paged-decode-k4x2"}


def test_feasibility_gate_passes_int8_rung():
    """…while the int8 rung of the SAME shape passes the same budget (the
    FEASIBILITY_70B.json verdict pair) — proven via the gate helper, no
    engine build needed."""
    from cyberfabric_core_tpu.parallel.feasibility import gate_engine_plan

    plan = gate_engine_plan("llama-3-70b", 8, quantization="int8",
                            hbm_bytes=16 * 1024**3)
    assert plan["fits"] is True and plan["enforced"] is True
