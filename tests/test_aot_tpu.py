"""What must hold before the chip: the kernels of the main path compile for a
real v5e at a served model's widths, the compile cache can be placed from
outside, and chip_smoke.py refuses to pass without a TPU — all cheap, and
collected first, so tier-1 guards them. The whole-program AOT proofs below
them (the serving set through runtime/aot_tpu.py, the scheduler's own
programs at mistral-7b) take minutes and are marked ``slow``.

Needs only the libtpu wheel (topology description), not a TPU device — so a
tiling/lowering bug in ops/flash_attention.py or ops/paged_attention.py fails
CI instead of waiting for a chip run. SURVEY §7 stage 3.
"""

import os
import re
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = Path(__file__).resolve().parents[1]
slow = pytest.mark.slow


def _topo_or_skip(name="v5e:2x2"):
    from cyberfabric_core_tpu.runtime.aot_tpu import tpu_topology

    try:
        return tpu_topology(name)
    except Exception as e:  # noqa: BLE001 — no libtpu in this environment
        pytest.skip(f"TPU topology unavailable: {e}")


# ---- kernels of the main path at mistral-7b's shapes (Hq 32, Hkv 8, D 128,
# page 64, bf16, the worker's default max_batch 8 / max_seq_len 2048)

@pytest.fixture()
def one_chip():
    """A described v5e chip to compile for, with the persistent compile cache
    off: an entry written by a topology compile cannot be read back without
    a chip, and the next compile would warn about it."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    topo = _topo_or_skip()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        sharding = SingleDeviceSharding(topo.devices[0])
        yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                        sharding=sharding)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


_HQ, _HKV, _D, _PAGE, _B, _PMAX = 32, 8, 128, 64, 8, 32
_N_PAGES = _B * _PMAX * 5 // 4 + 1   # the worker's default pool
_POOL = (2, _N_PAGES, _PAGE, _HKV * _D)   # two layers of it, as stored
_WINDOW = 4096


def _compiles_with_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [1, 4])
def test_flash_kernel_compiles_at_mistral_7b_shapes(one_chip, batch):
    """One prompt, and a prefill of several rows: per-row lengths
    as a blocked SMEM operand lowered at batch 1 only (first chip run)."""
    from cyberfabric_core_tpu.ops.flash_attention import flash_self_attention

    T = 512
    _compiles_with_mosaic(
        lambda q, k, v, n: flash_self_attention(
            q, k, v, n, interpret=False, sliding_window=_WINDOW),
        one_chip((batch, T, _HQ, _D), jnp.bfloat16),
        one_chip((batch, T, _HKV, _D), jnp.bfloat16),
        one_chip((batch, T, _HKV, _D), jnp.bfloat16),
        one_chip((batch,), jnp.int32))


@pytest.mark.parametrize("heads,kv_heads,batch,pmax,window", [
    (_HQ, _HKV, _B, _PMAX, _WINDOW),
    (28, 4, 16, 64, None),      # qwen2-7b: 7 queries a kv head, 4k context
    (128, 4, 16, 32, None),     # sdar: 4 x 8 query rows folded on a kv head
    (48, 8, 64, 128, None),     # laguna's full layers: 6 queries a kv head
    (72, 8, 64, 128, 512),      # its window layers: 9, a window that binds
    (16, 16, 8, 13, None),      # ouro: one query row a kv head, 8-page trips
    (32, 2, 64, 64, None),      # nemotron: 16 query rows on each of 2 kv heads
], ids=["mistral-7b", "qwen2-7b", "sdar-folded", "laguna-full",
        "laguna-window", "ouro", "nemotron"])
def test_paged_decode_kernel_compiles_at_served_shapes(
        one_chip, heads, kv_heads, batch, pmax, window):
    """A program a row that walks its pages itself (the trip these shapes
    are served with): the pools left where they live, the table and the
    lengths in scalar prefetch, a kv head's query rows one padded slab."""
    from cyberfabric_core_tpu.ops.paged_attention import (
        paged_decode_attention)

    pool = one_chip((2, batch * pmax * 5 // 4 + 1, _PAGE, kv_heads * _D),
                    jnp.bfloat16)
    _compiles_with_mosaic(
        lambda q, k, v, pt, n, layer: paged_decode_attention(
            q, k, v, pt, n, layer,
            interpret=False, sliding_window=window, two_d_dots=True),
        one_chip((batch, heads, _D), jnp.bfloat16), pool, pool,
        one_chip((batch, pmax), jnp.int32), one_chip((batch,), jnp.int32),
        one_chip((), jnp.int32))


@pytest.mark.parametrize("q_width,heads,kv_heads,window,block,name", [
    (8, _HQ, _HKV, _WINDOW, 1, None), (64, _HQ, _HKV, _WINDOW, 1, None),
    (512, _HQ, _HKV, _WINDOW, 1, None),
    # laguna's two call sites in one program: a lane of one chunk, 48 query
    # heads over everything and 72 behind a window that binds, each under
    # its own name in a device trace
    (512, 48, _HKV, None, 1, "gqa_full_ragged_attention"),
    (512, 72, _HKV, 512, 1, "gqa_window_ragged_attention"),
    # the other groupings the walk is served at: solar's 8 queries a kv
    # head, nemotron's 16 (512 rows a score dot), ouro's 1 (at a width that
    # is not whole q-blocks: the wrapper pads it), sdar's block mask
    (512, 64, 8, None, 1, None), (512, 32, 2, None, 1, None),
    (264, 16, 16, None, 1, None), (128, 32, 4, None, 4, None),
], ids=["8", "64", "512", "laguna-full", "laguna-window", "solar",
        "nemotron", "ouro-264", "sdar-block-mask"])
def test_ragged_kernel_compiles_at_mistral_7b_shapes(
        one_chip, q_width, heads, kv_heads, window, block, name):
    """Mixed q_len rows share one call; its width is the round's largest
    prefill chunk bucket (8 = a speculative span, 512 = the chunk budget).
    A program a (lane, q-block) that walks its pages itself: the rings, the
    DMAs out of both pools and the loop over kv heads lower."""
    from cyberfabric_core_tpu.ops.paged_attention import ragged_paged_attention

    pool = one_chip((2, _N_PAGES, _PAGE, kv_heads * _D), jnp.bfloat16)
    rows = 1 if name else _B
    text = jax.jit(
        lambda q, k, v, pt, h, n, layer: ragged_paged_attention(
            q, k, v, pt, h, n, layer, interpret=False,
            sliding_window=window, two_d_dots=True, block=block,
            name=name)).lower(
        one_chip((rows, q_width, heads, _D), jnp.bfloat16), pool, pool,
        one_chip((rows, _PMAX), jnp.int32), one_chip((rows,), jnp.int32),
        one_chip((rows,), jnp.int32), one_chip((), jnp.int32)
    ).compile().as_text()
    assert "tpu_custom_call" in text and (name or "") in text


@pytest.mark.parametrize("rows,experts,width,inner", [
    (512, 128, 2048, 768),      # sdar decode forward: tile 64
    (128, 12, 7168, 2048),      # kimi decode step: tile 64, 4 column tiles
    (1408, 128, 1024, 2688),    # nemotron decode step: tile 64
    (5760, 72, 4096, 768),      # granite 512-token mixed step: tile 128
], ids=["sdar-decode", "kimi-decode", "nemotron-decode", "granite-mixed"])
def test_grouped_matmul_compiles_at_the_cells_calls(one_chip, rows, experts,
                                                    width, inner):
    """The expert kernel at the row tile its rule picks for the cells' calls
    (``row_tile``: 64 where the mean expert has at most 64 rows), int8 blocks
    out of a stack of two layers, into the rows' width and back; the Mosaic
    call keeps the name the benchmark's trace reader looks for."""
    from cyberfabric_core_tpu.ops.grouped_matmul import grouped_matmul

    for k, n in ((width, inner), (inner, width)):
        text = jax.jit(
            lambda x, w, s, sizes, layer: grouped_matmul(
                x, w, s, sizes, layer, interpret=False)).lower(
            one_chip((rows, k), jnp.bfloat16),
            one_chip((2, experts, k, n), jnp.int8),
            one_chip((2, experts, n), jnp.float32),
            one_chip((experts,), jnp.int32),
            one_chip((), jnp.int32)).compile().as_text()
        assert "tpu_custom_call" in text and "grouped_matmul" in text


# ---- the compile cache and chip_smoke.py without a chip

@pytest.mark.parametrize("case", ["from_env", "fixed_path", "cpu"])
def test_compile_cache_dir_is_placed_from_outside(case, monkeypatch,
                                                  tmp_path):
    """JAX_COMPILATION_CACHE_DIR set → no directory is set in code; unset →
    the one fixed path inside the checkout on a TPU, nothing on the CPU."""
    from jax.experimental.compilation_cache import compilation_cache

    from cyberfabric_core_tpu.ops import platform

    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        if case == "from_env":
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert platform.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir is None
        elif case == "fixed_path":
            monkeypatch.setattr(platform, "on_tpu", lambda: True)
            fixed = str(REPO / ".jax_cache")
            assert platform.enable_compile_cache() == fixed
            assert jax.config.jax_compilation_cache_dir == fixed
        else:
            assert platform.enable_compile_cache() is None
            assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


def test_chip_smoke_fails_without_a_tpu():
    """On the CPU the script exits non-zero and never prints the line the
    driver reads as success."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout
    assert "JAX found no TPU" in proc.stderr


# ---- whole programs (minutes each)

@pytest.fixture()
def lane_wide_tiny(monkeypatch):
    """tiny-llama with kv rows of one whole lane tile (2 kv heads of 64): a
    kernel that copies its pages itself takes a page as the pool holds it,
    and Mosaic refuses a DMA of 32 lanes (no served model is that narrow)."""
    import dataclasses

    from cyberfabric_core_tpu.models.configs import MODEL_CONFIGS

    name = "tiny-llama-d64"
    monkeypatch.setitem(MODEL_CONFIGS, name, dataclasses.replace(
        MODEL_CONFIGS["tiny-llama"], name=name, head_dim=64))
    return name


@slow
@pytest.mark.parametrize("quant", ["none", "int8", "int4"])
def test_serving_set_compiles_for_v5e(quant, lane_wide_tiny):
    """Flash prefill + fused paged-decode chunk lower for the TPU target in
    every quantization rung, with real Mosaic kernels in the module."""
    _topo_or_skip()
    from cyberfabric_core_tpu.runtime.aot_tpu import aot_compile

    report = aot_compile(
        lane_wide_tiny, quantization=quant, topology="v5e:2x2",
        prefill_bucket=64, decode_chunk=4, max_batch=2, max_seq_len=128)
    names = {p["name"] for p in report["programs"]}
    assert names == {"prefill-flash-b1x64", "paged-decode-k4x2"}
    for prog in report["programs"]:
        assert "memory" in prog, prog
        # the whole point: Pallas lowered through Mosaic, not interpret mode
        assert prog["has_mosaic_kernel"], prog["name"]
        assert "tpu_custom_call" in prog["custom_calls"], prog["name"]


@slow
def test_tp_sharded_prefill_compiles_for_v5e():
    """Megatron-style TP shardings + GSPMD collectives lower for the TPU
    mesh (tp=4 over the v5e:2x2 topology). Compiles ONLY the tp program
    (include_serving=False) — the serving set has its own test."""
    _topo_or_skip()
    from cyberfabric_core_tpu.models import llama
    from cyberfabric_core_tpu.models.configs import get_config
    from cyberfabric_core_tpu.runtime.aot_tpu import aot_compile

    report = aot_compile(
        "tiny-llama", quantization="none", topology="v5e:2x2",
        prefill_bucket=64, decode_chunk=4, max_batch=2, max_seq_len=128,
        tp=4, include_serving=False)
    (tp_prog,) = report["programs"]
    assert tp_prog["name"] == "prefill-tp4"
    assert "memory" in tp_prog
    # per-device argument bytes must be well under the replicated param
    # total (embed, lm_head and all matmul weights are tp-sharded)
    cfg = get_config("tiny-llama")
    params = jax.eval_shape(
        lambda k: llama.init_params(cfg, k, jnp.bfloat16),
        jax.random.PRNGKey(0))
    replicated_bytes = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize for l in jax.tree.leaves(params))
    assert tp_prog["memory"]["argument_bytes"] < replicated_bytes


def test_tp_exceeding_topology_is_a_clear_error():
    _topo_or_skip()
    from cyberfabric_core_tpu.runtime.aot_tpu import aot_compile

    with pytest.raises(ValueError, match="tp=8 exceeds the 4 devices"):
        aot_compile("tiny-llama", topology="v5e:2x2", tp=8,
                    include_serving=False)


def test_serialize_without_out_dir_is_a_clear_error():
    from cyberfabric_core_tpu.runtime.aot_tpu import aot_compile

    with pytest.raises(ValueError, match="serialize"):
        aot_compile("tiny-llama", serialize=True)


@slow
def test_serialized_executable_roundtrip(tmp_path, lane_wide_tiny):
    """serialize=True writes deserializable TPU executables with digests —
    what a TPU host loads to skip compilation entirely."""
    _topo_or_skip()
    import hashlib
    import json

    from cyberfabric_core_tpu.runtime.aot_tpu import aot_compile

    from cyberfabric_core_tpu.runtime.aot_tpu import read_serialized

    report = aot_compile(
        lane_wide_tiny, quantization="int8", topology="v5e:2x2",
        prefill_bucket=32, decode_chunk=2, max_batch=2, max_seq_len=64,
        out_dir=tmp_path, serialize=True)
    manifest = json.loads((tmp_path / "aot_manifest.json").read_text())
    assert manifest == report
    for prog in report["programs"]:
        path = tmp_path / prog["executable"]["path"]
        blob = path.read_bytes()
        assert len(blob) == prog["executable"]["bytes"] > 0
        assert hashlib.sha256(blob).hexdigest() == prog["executable"]["sha256"]
        # container parses back: payload + the arg trees deserialize_and_load
        # needs on the TPU host (full load requires live TPU devices)
        parsed = read_serialized(path)
        assert parsed["name"] == prog["name"]
        assert len(parsed["payload"]) > 1000
        assert parsed["in_tree"] is not None and parsed["out_tree"] is not None


def test_compiled_kernels_context_forces_mosaic():
    """The override that makes AOT possible: inside compiled_kernels() the
    default interpret decision flips to compiled even on a CPU backend."""
    from cyberfabric_core_tpu.ops.platform import (compiled_kernels,
                                                   default_interpret)

    on_cpu = jax.devices()[0].platform != "tpu"
    assert default_interpret() is on_cpu
    with compiled_kernels():
        assert default_interpret() is False
    assert default_interpret() is on_cpu


def _decode_operands(sds, eng, block: int = 0) -> tuple:
    """``paged_decode_chunk`` after the cache operands: the host-owned rows
    (page table and control rows, one block), the last tokens (or the open
    blocks), lengths, active, finished, keys."""
    from cyberfabric_core_tpu.runtime.programs import _CTL

    n = eng.n_slots
    return (sds((n, eng._tw + _CTL + eng.config.device_stop_width),
                jnp.int32),
            sds((n, block) if block else (n,), jnp.int32),
            sds((n,), jnp.int32), sds((n,), bool), sds((n,), bool),
            sds((n, 2), jnp.uint32))


def _mixed_operands(sds, eng, width: int, block: int = 0) -> tuple:
    """``mixed_step`` after the cache operands: the rows, the dispatch's
    flat lane block at ``width``, then what the device advances."""
    from cyberfabric_core_tpu.runtime.programs import LANE_ROWS, lane_words

    rows, last, lens, _, fin, keys = _decode_operands(sds, eng, block)
    return (rows, sds((lane_words(eng.n_slots, block, LANE_ROWS, width),),
                      jnp.int32), last, lens, fin, keys)


def _assert_whole_array_untouched(text: str, array, name: str) -> None:
    """Every instruction of the optimised HLO that yields an array of
    ``array``'s shape (a page pool, the state slab) is a parameter, the
    loop's tuple plumbing, a kernel or an in-place update: none is a copy, a
    ``dynamic-slice`` or a ``reshape``, which would move the whole of it."""
    import re

    shape = "%s[%s]" % ({"bfloat16": "bf16", "float32": "f32"}[
        str(array.dtype)], ",".join(map(str, array.shape)))
    seen = 0
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = " + re.escape(shape)
                     + r"\S* (\w[\w\-]*)\(", line)
        if m:
            seen += 1
            assert m.group(1) in (
                "parameter", "get-tuple-element", "custom-call",
                "dynamic-update-slice", "scatter", "fusion", "while",
                "bitcast", "tuple"), (name, line[:200])
    assert seen, f"{name}: no instruction of shape {shape} (a wrong pattern?)"


@slow
@pytest.mark.parametrize("tp", [1, 4])
def test_scheduler_programs_compile_for_v5e_at_mistral_7b(tp):
    """The scheduler's OWN jitted programs (not the mirror in aot_tpu.py) —
    ``_paged_decode_fn`` and ``_mixed_step_fn`` as ``_build_programs`` makes
    them, with their donation — lowered from shapes for mistral-7b int8 at
    the worker's default shape and page pool, on one described chip and as
    tp=4 over the 2x2. Each holds a Mosaic call and fits the 15.75 GiB the
    compiler budgets. About 25 s a program; a compile, not a chip run."""
    from jax.sharding import NamedSharding, PartitionSpec as P, \
        SingleDeviceSharding

    from cyberfabric_core_tpu.models import decoder_module, get_config
    from cyberfabric_core_tpu.ops.platform import compiled_kernels
    from cyberfabric_core_tpu.parallel.feasibility import V5E_HBM_BYTES
    from cyberfabric_core_tpu.parallel.mesh import MeshConfig, build_mesh
    from cyberfabric_core_tpu.parallel.sharding import (
        abstract_params, llama_page_pool_sharding, sharded_abstract_params)
    from cyberfabric_core_tpu.runtime.engine import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    topo = _topo_or_skip()
    n, max_seq = _B, _PMAX * _PAGE
    cfg = get_config("mistral-7b")
    # an engine with everything _build_programs reads and nothing allocated
    eng = object.__new__(ContinuousBatchingEngine)
    eng.config = EngineConfig(
        model="mistral-7b", max_seq_len=max_seq, max_batch=n, decode_chunk=8,
        quantization="int8", prefix_cache_pages=_N_PAGES,
        prefix_page_size=_PAGE, tp=tp)
    eng.model_config, eng.dtype = cfg, jnp.bfloat16
    eng._model, eng._has_state, eng._block = decoder_module(cfg), False, 0
    eng._step_counters, eng.n_slots, eng.pmax = (), n, _PMAX
    eng.spec_k, eng._spec_w = 0, 1
    if tp > 1:
        eng.mesh = eng._attn_mesh = build_mesh(MeshConfig(dp=1, tp=tp),
                                               topo.devices[:tp])
        repl = NamedSharding(eng.mesh, P())
        pool_sharding = llama_page_pool_sharding(cfg, eng.mesh)
        params = sharded_abstract_params(cfg, eng.mesh, jnp.bfloat16, "int8")
    else:
        eng.mesh = eng._attn_mesh = None
        repl = pool_sharding = SingleDeviceSharding(topo.devices[0])
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl),
            abstract_params(cfg, jnp.bfloat16, "int8"))
    eng.pool = types.SimpleNamespace(cache_operands=lambda: (None, None))
    with compiled_kernels():
        eng._build_programs()

    def sds(shape, dtype, sharding=repl):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool = sds((cfg.num_layers, _N_PAGES, _PAGE,
                cfg.num_kv_heads * cfg.head_dim), jnp.bfloat16, pool_sharding)
    programs = {
        "paged_decode_chunk": (eng._paged_decode_fn, (
            params, pool, pool, *_decode_operands(sds, eng))),
        **{f"mixed_step@{w}": (eng._mixed_step_fn, (
            params, pool, pool, *_mixed_operands(sds, eng, w)))
           for w in (64, 256)},
    }
    for name, (fn, args) in programs.items():
        with compiled_kernels():
            compiled = fn.lower(*args).compile()
        if tp == 1:
            _assert_whole_array_untouched(compiled.as_text(), pool, name)
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"{name} tp={tp}: argument {mem.argument_size_in_bytes / 1e9:.2f}"
              f" output {mem.output_size_in_bytes / 1e9:.2f} aliased "
              f"{mem.alias_size_in_bytes / 1e9:.2f} temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB per device")
        assert "tpu_custom_call" in compiled.as_text(), name
        assert mem.alias_size_in_bytes >= 2 * np.prod(
            pool.sharding.shard_shape(pool.shape)) * 2, "pools not donated"
        assert live < V5E_HBM_BYTES, (name, live)


@slow
def test_scheduler_programs_compile_for_v5e_at_falcon_h1():
    """The scheduler's own ``paged_decode_chunk`` and ``mixed_step`` for
    falcon-h1-34b-16l int8 at the benchmark cell's shape (16 slots of 2048,
    641 pages, 16 snapshot rows), on one described chip: each holds the
    ``ssm_state_update`` Mosaic call or the chunked form, donates the state
    slab with the pools, fits the 15.75 GiB the compiler budgets, and copies
    nothing the size of the slab. A compile, not a chip run."""
    from jax.sharding import SingleDeviceSharding

    from cyberfabric_core_tpu.models import decoder_module, get_config
    from cyberfabric_core_tpu.ops.platform import compiled_kernels
    from cyberfabric_core_tpu.parallel.feasibility import V5E_HBM_BYTES
    from cyberfabric_core_tpu.parallel.sharding import abstract_params
    from cyberfabric_core_tpu.runtime.engine import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    topo = _topo_or_skip()
    n, max_seq, pages, rows = 16, 2048, 641, 32
    cfg = get_config("falcon-h1-34b-16l")
    eng = object.__new__(ContinuousBatchingEngine)
    eng.config = EngineConfig(
        model=cfg.name, max_seq_len=max_seq, max_batch=n, decode_chunk=8,
        quantization="int8", prefix_cache_pages=pages, prefix_page_size=_PAGE)
    eng.model_config, eng.dtype = cfg, jnp.bfloat16
    eng._model, eng._has_state, eng._block = decoder_module(cfg), True, 0
    eng._step_counters, eng.n_slots, eng.pmax = (), n, max_seq // _PAGE
    eng.spec_k, eng._spec_w = 0, 1
    eng.mesh = eng._attn_mesh = None
    here = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=here)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          abstract_params(cfg, jnp.bfloat16, "int8"))
    eng.pool = types.SimpleNamespace(cache_operands=lambda: (None,) * 3)
    with compiled_kernels():
        eng._build_programs()

    f32 = jnp.float32
    pool = sds((cfg.num_layers, pages, _PAGE, cfg.num_kv_heads * cfg.head_dim),
               jnp.bfloat16)
    state = {"ssm": sds((cfg.num_layers, rows, cfg.ssm_heads,
                         cfg.ssm_head_dim, cfg.ssm_state), f32),
             "conv": sds((cfg.num_layers, rows, cfg.ssm_conv - 1,
                          cfg.ssm_conv_dim), f32)}
    slab_bytes = int(np.prod(state["ssm"].shape)) * 4

    def mixed(width):
        return (eng._mixed_step_fn, (
            params, pool, pool, state, *_mixed_operands(sds, eng, width)))

    programs = {
        "paged_decode_chunk": (eng._paged_decode_fn, (
            params, pool, pool, state, *_decode_operands(sds, eng))),
        "mixed_step@64": mixed(64), "mixed_step@256": mixed(256),
    }
    for name, (fn, args) in programs.items():
        with compiled_kernels():
            compiled = fn.lower(*args).compile()
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"{name}: argument {mem.argument_size_in_bytes / 1e9:.2f} output "
              f"{mem.output_size_in_bytes / 1e9:.2f} aliased "
              f"{mem.alias_size_in_bytes / 1e9:.2f} temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB")
        text = compiled.as_text()
        if os.environ.get("AOT_DUMP_DIR"):
            Path(os.environ["AOT_DUMP_DIR"], f"{name}.hlo.txt").write_text(text)
        assert "tpu_custom_call" in text, name
        if name == "paged_decode_chunk":
            assert "ssm_state_update" in text
        assert mem.alias_size_in_bytes >= slab_bytes + 2 * int(
            np.prod(pool.shape)) * 2, "pools and state slab not donated"
        assert live < V5E_HBM_BYTES, (name, live)
        # nothing the size of the slab or of a pool is copied, sliced or
        # reshaped
        _assert_whole_array_untouched(text, state["ssm"], name)
        _assert_whole_array_untouched(text, pool, name)


@pytest.mark.slow
def test_scheduler_programs_compile_for_v5e_at_sdar_moe():
    """The scheduler's own ``paged_decode_chunk`` and ``mixed_step`` for
    sdar-30b-a3b-16l int8 at the benchmark cell's shape (16 slots of 2048,
    641 pages, 10 forwards a chunk, blocks of 4), on one described chip: each
    holds the ``grouped_matmul`` Mosaic call and both paged kernels, fits the
    15.75 GiB the compiler budgets, and copies no layer of the expert stacks
    (an ``s8[128, 2048, 768]`` array is what a sliced stack in front of a
    kernel would be). A compile, not a chip run."""
    from jax.sharding import SingleDeviceSharding

    from cyberfabric_core_tpu.models import decoder_module, get_config
    from cyberfabric_core_tpu.ops.platform import compiled_kernels
    from cyberfabric_core_tpu.parallel.feasibility import V5E_HBM_BYTES
    from cyberfabric_core_tpu.parallel.sharding import abstract_params
    from cyberfabric_core_tpu.runtime.engine import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    topo = _topo_or_skip()
    n, max_seq, pages = 16, 2048, 641
    cfg = get_config("sdar-30b-a3b-16l")
    eng = object.__new__(ContinuousBatchingEngine)
    eng.config = EngineConfig(
        model=cfg.name, max_seq_len=max_seq, max_batch=n, decode_chunk=10,
        quantization="int8", prefix_cache_pages=pages, prefix_page_size=_PAGE)
    eng.model_config, eng.dtype = cfg, jnp.bfloat16
    eng._model, eng._has_state = decoder_module(cfg), False
    eng._block = cfg.block_length
    eng._step_counters = eng._model.STEP_COUNTERS
    eng.n_slots, eng.pmax = n, max_seq // _PAGE
    eng.spec_k, eng._spec_w = 0, 1
    eng.mesh = eng._attn_mesh = None
    eng.pool = types.SimpleNamespace(cache_operands=lambda: (None, None))
    here = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=here)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          abstract_params(cfg, jnp.bfloat16, "int8"))
    assert params["layers"]["router"].dtype == jnp.float32
    with compiled_kernels():
        eng._build_programs()

    pool = sds((cfg.num_layers, pages, _PAGE, cfg.num_kv_heads * cfg.head_dim),
               jnp.bfloat16)
    W = cfg.block_length

    def mixed(width):
        return (eng._mixed_step_fn, (
            params, pool, pool, *_mixed_operands(sds, eng, width, W)))

    programs = {
        "paged_decode_chunk": (eng._paged_decode_fn, (
            params, pool, pool, *_decode_operands(sds, eng, W))),
        "mixed_step@64": mixed(64), "mixed_step@512": mixed(512),
    }
    import re

    for name, (fn, args) in programs.items():
        with compiled_kernels():
            compiled = fn.lower(*args).compile()
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"{name}: argument {mem.argument_size_in_bytes / 1e9:.2f} output "
              f"{mem.output_size_in_bytes / 1e9:.2f} aliased "
              f"{mem.alias_size_in_bytes / 1e9:.2f} temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB")
        text = compiled.as_text()
        if os.environ.get("AOT_DUMP_DIR"):
            Path(os.environ["AOT_DUMP_DIR"], f"{name}.hlo.txt").write_text(text)
        for kernel in ("grouped_matmul", "paged_decode_attention"):
            assert kernel in text, (name, kernel)
        assert "tpu_custom_call" in text, name
        assert mem.alias_size_in_bytes >= 2 * int(np.prod(pool.shape)) * 2, name
        sliced = re.search(r"s8\[(1,)?128,(2048,768|768,2048)\]", text)
        assert not sliced, f"{name}: a layer of an expert stack, {sliced[0]}"
        assert live <= V5E_HBM_BYTES, (name, live)


#: the compiled step programs' temporaries on PR 52's tree, the ragged latent
#: kernel a grid step a page (``memory_analysis().temp_size_in_bytes`` as the
#: slow tests print it, rounded up to the next 10 MB), and what PR 53's walk
#: may add at most: a ring of 3 key blocks of 256 keys x 640 lanes bf16 and
#: a score block of 80 x 32 rows x 256 keys f32
_LATENT_TEMP = {("kimi", "paged_decode_chunk"): 0.50e9,
                ("kimi", "mixed_step@64"): 0.03e9,
                ("kimi", "mixed_step@512"): 0.30e9,
                ("motif", "mixed_step@512"): 0.78e9}
_RAGGED_SCRATCH = 3 * 256 * 640 * 2 + 80 * 32 * 256 * 4


@pytest.mark.parametrize("heads,pmax,window", [
    (64, 48, None),         # kimi-k2.5: 64 slots of 3072
    (80, 128, None),        # motif-3-beta's full layers: 64 slots of 8192
    (80, 128, 128),         # ... and its window layers: a trip of 3 pages
], ids=["kimi-k2.5", "motif-full", "motif-window"])
def test_latent_kernels_compile_at_served_shapes(one_chip, heads, pmax,
                                                 window):
    """``mla_decode_attention`` (one program a slot, the pool left where it
    lives, a ring of key blocks the kernel copies a row's pages into itself:
    DMAs and semaphores the interpreter only imitates) and
    ``mla_ragged_attention`` (since PR 53 the same walk, one program a block
    of 32 queries, a trip of 4 pages) at a chunk of 512, on a latent page of
    512 + 64 lanes in 640, 64 slots, at 64 and at 80 heads, with a window
    and without."""
    from cyberfabric_core_tpu.ops.mla_attention import (mla_decode_attention,
                                                        mla_ragged_attention)

    batch, width, rank = 64, 640, 512
    pool = one_chip((2, batch * pmax + 1, _PAGE, width), jnp.bfloat16)
    _compiles_with_mosaic(
        lambda q, p, pt, n, layer: mla_decode_attention(
            q, p, pt, n, layer, rank=rank, scale=0.1447, interpret=False,
            sliding_window=window),
        one_chip((batch, heads, width), jnp.bfloat16), pool,
        one_chip((batch, pmax), jnp.int32), one_chip((batch,), jnp.int32),
        one_chip((), jnp.int32))
    lane = one_chip((1,), jnp.int32)
    _compiles_with_mosaic(
        lambda q, p, pt, h, n, layer: mla_ragged_attention(
            q, p, pt, h, n, layer, rank=rank, scale=0.1447, interpret=False,
            sliding_window=window),
        one_chip((1, heads, 512, width), jnp.bfloat16), pool,
        one_chip((1, pmax), jnp.int32), lane, lane, one_chip((), jnp.int32))


@pytest.mark.slow
def test_scheduler_programs_compile_for_v5e_at_kimi_k2():
    """The scheduler's own ``paged_decode_chunk`` and ``mixed_step`` for
    kimi-k2.5-share32-15l int8 at the benchmark cell's shape (64 slots of
    3072, 3073 latent pages, 8 steps a chunk), on one described chip: each
    holds both latent kernels' and the ``grouped_matmul`` Mosaic calls, takes
    ONE pool operand and aliases it, fits the 15.75 GiB the compiler budgets,
    and copies no layer of the expert stacks. A compile, not a chip run."""
    from jax.sharding import SingleDeviceSharding

    from cyberfabric_core_tpu.models import decoder_module, get_config
    from cyberfabric_core_tpu.ops.platform import compiled_kernels
    from cyberfabric_core_tpu.parallel.feasibility import V5E_HBM_BYTES
    from cyberfabric_core_tpu.parallel.sharding import abstract_params
    from cyberfabric_core_tpu.runtime.engine import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    topo = _topo_or_skip()
    n, max_seq, pages = 64, 3072, 3073
    cfg = get_config("kimi-k2.5-share32-15l")
    eng = object.__new__(ContinuousBatchingEngine)
    eng.config = EngineConfig(
        model=cfg.name, max_seq_len=max_seq, max_batch=n, decode_chunk=8,
        quantization="int8", prefix_cache_pages=pages, prefix_page_size=_PAGE)
    eng.model_config, eng.dtype = cfg, jnp.bfloat16
    eng._model, eng._has_state, eng._block = decoder_module(cfg), False, 0
    eng._step_counters = eng._model.STEP_COUNTERS
    eng.n_slots, eng.pmax = n, max_seq // _PAGE
    eng.spec_k, eng._spec_w = 0, 1
    eng.mesh = eng._attn_mesh = None
    here = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=here)

    pool = sds((cfg.num_layers, pages, _PAGE, cfg.latent_lanes), jnp.bfloat16)

    class _Pool:        # what _build_programs asks the page pool
        @staticmethod
        def cache_operands():
            return (pool,)

    eng.pool = _Pool()
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          abstract_params(cfg, jnp.bfloat16, "int8"))
    assert params["layers"]["router"].dtype == jnp.float32
    assert params["layers"]["moe_gate"]["q"].shape == (14, 12, 7168, 2048)
    with compiled_kernels():
        eng._build_programs()

    def mixed(width):
        return (eng._mixed_step_fn, (
            params, pool, *_mixed_operands(sds, eng, width)))

    programs = {
        "paged_decode_chunk": (eng._paged_decode_fn, (
            params, pool, *_decode_operands(sds, eng))),
        "mixed_step@64": mixed(64), "mixed_step@512": mixed(512),
    }
    import re

    for name, (fn, args) in programs.items():
        with compiled_kernels():
            compiled = fn.lower(*args).compile()
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"{name}: argument {mem.argument_size_in_bytes / 1e9:.2f} output "
              f"{mem.output_size_in_bytes / 1e9:.2f} aliased "
              f"{mem.alias_size_in_bytes / 1e9:.2f} temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB")
        text = compiled.as_text()
        if os.environ.get("AOT_DUMP_DIR"):
            Path(os.environ["AOT_DUMP_DIR"], f"{name}.hlo.txt").write_text(text)
        kernels = ["grouped_matmul", "mla_decode_attention"]
        if name != "paged_decode_chunk":
            kernels.append("mla_ragged_attention")
        for kernel in kernels:
            assert kernel in text, (name, kernel)
        assert "paged_decode_attention" not in text.replace(
            "mla_decode_attention", ""), name
        assert mem.alias_size_in_bytes >= int(np.prod(pool.shape)) * 2, name
        sliced = re.search(r"s8\[(1,)?12,(7168,2048|2048,7168)\]", text)
        assert not sliced, f"{name}: a layer of an expert stack, {sliced[0]}"
        assert live <= V5E_HBM_BYTES, (name, live)
        # PR 53: the ragged kernel's ring (3 x 256 x 640 bf16) and its score
        # block (2048 x 256 f32) are VMEM scratch, so the temporaries are
        # what they were with a grid step a page (0.49 / 0.02 / 0.29 GB as
        # printed, PR 52's tree): no more than those two on top
        assert mem.temp_size_in_bytes <= _LATENT_TEMP["kimi", name] \
            + _RAGGED_SCRATCH, (name, mem.temp_size_in_bytes)


@pytest.mark.slow
def test_scheduler_programs_compile_for_v5e_at_granite_hybrid():
    """The scheduler's own ``paged_decode_chunk`` and ``mixed_step`` for
    granite-4.0-h-small-10l int8 at the served shapes of
    ``benchmark/configs/granite-4.0-h-small-int8.json`` (64 slots of 4096,
    5121 pages in ONE pool layer, 96 state rows in NINE slab layers, 8 steps
    a chunk), on one described chip: each holds the ``ssm_state_update``, the
    ``grouped_matmul`` and the paged attention Mosaic calls, donates the pools
    and the slab, fits the 15.75 GiB the compiler budgets, copies nothing the
    size of the slab or of a pool and no layer of a weight stack. A compile,
    not a chip run."""
    import json
    import re

    from jax.sharding import SingleDeviceSharding

    from cyberfabric_core_tpu.models import decoder_module, get_config
    from cyberfabric_core_tpu.ops.platform import compiled_kernels
    from cyberfabric_core_tpu.parallel.feasibility import V5E_HBM_BYTES
    from cyberfabric_core_tpu.parallel.sharding import abstract_params
    from cyberfabric_core_tpu.runtime.engine import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    topo = _topo_or_skip()
    serving = json.loads(Path(
        __file__).resolve().parents[1].joinpath(
            "benchmark/configs/granite-4.0-h-small-int8.json").read_text()
    )["serving"]
    n, max_seq = serving["max_batch"], serving["max_seq_len"]
    pages = serving["pool_pages"] + 1      # the worker's default margin in
    rows = n + serving["state_snapshots"]
    cfg = get_config(serving["model_config"])
    assert (cfg.kv_layers, cfg.state_layers, pages, rows) == (1, 9, 5121, 96)
    eng = object.__new__(ContinuousBatchingEngine)
    eng.config = EngineConfig(
        model=cfg.name, max_seq_len=max_seq, max_batch=n,
        decode_chunk=serving["decode_chunk"], quantization="int8",
        prefix_cache_pages=pages, prefix_page_size=_PAGE)
    eng.model_config, eng.dtype = cfg, jnp.bfloat16
    eng._model, eng._has_state, eng._block = decoder_module(cfg), True, 0
    eng._step_counters = eng._model.STEP_COUNTERS
    eng.n_slots, eng.pmax = n, max_seq // _PAGE
    eng.spec_k, eng._spec_w = 0, 1
    eng.mesh = eng._attn_mesh = None
    here = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=here)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          abstract_params(cfg, jnp.bfloat16, "int8"))
    assert params["layers"]["router"].dtype == jnp.float32
    assert params["layers"]["moe_gate"]["q"].shape == (10, 72, 4096, 768)
    assert params["mamba"]["ssm_in"]["q"].shape == (9, 4096, 16768)
    eng.pool = types.SimpleNamespace(cache_operands=lambda: (None,) * 3)
    with compiled_kernels():
        eng._build_programs()

    f32 = jnp.float32
    pool = sds((cfg.kv_layers, pages, _PAGE, cfg.num_kv_heads * cfg.head_dim),
               jnp.bfloat16)
    state = {"ssm": sds((cfg.state_layers, rows, cfg.ssm_heads,
                         cfg.ssm_head_dim, cfg.ssm_state), f32),
             "conv": sds((cfg.state_layers, rows, cfg.ssm_conv - 1,
                          cfg.ssm_conv_dim), f32)}
    slab_bytes = int(np.prod(state["ssm"].shape)) * 4

    def mixed(width):
        return (eng._mixed_step_fn, (
            params, pool, pool, state, *_mixed_operands(sds, eng, width)))

    programs = {
        "paged_decode_chunk": (eng._paged_decode_fn, (
            params, pool, pool, state, *_decode_operands(sds, eng))),
        "mixed_step@64": mixed(64), "mixed_step@512": mixed(512),
    }
    for name, (fn, args) in programs.items():
        with compiled_kernels():
            compiled = fn.lower(*args).compile()
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"{name}: argument {mem.argument_size_in_bytes / 1e9:.2f} output "
              f"{mem.output_size_in_bytes / 1e9:.2f} aliased "
              f"{mem.alias_size_in_bytes / 1e9:.2f} temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB")
        text = compiled.as_text()
        if os.environ.get("AOT_DUMP_DIR"):
            Path(os.environ["AOT_DUMP_DIR"], f"{name}.hlo.txt").write_text(text)
        for kernel in ("ssm_state_update", "grouped_matmul",
                       "paged_decode_attention"):
            assert kernel in text, (name, kernel)
        assert mem.alias_size_in_bytes >= slab_bytes + 2 * int(
            np.prod(pool.shape)) * 2, "pools and state slab not donated"
        assert live < V5E_HBM_BYTES, (name, live)
        _assert_whole_array_untouched(text, state["ssm"], name)
        _assert_whole_array_untouched(text, pool, name)
        # a run picks its layer out of the whole stack as a scan picks its
        # ``xs``: no layer of a stack is copied
        copied = re.search(
            r"s8\[(1,)?(72,(4096,768|768,4096)|4096,16768|8192,4096)\]\S* "
            r"copy\(", text)
        assert not copied, f"{name}: a layer of a weight stack, {copied[0]}"


@pytest.mark.slow
def test_scheduler_programs_compile_for_v5e_at_nemotron_h():
    """The scheduler's own ``paged_decode_chunk`` and ``mixed_step`` for
    nemotron-3-super-share4-22l int8 at the served shapes of
    ``benchmark/configs/nemotron-3-super-int8.json`` (64 slots of 4096, 5121
    pages in TWO pool layers, 96 state rows in TEN slab layers, 128 held
    experts in TEN expert-stack layers, 8 steps a chunk), on one described
    chip: each holds the ``ssm_state_update``, the ``grouped_matmul`` and the
    paged attention Mosaic calls, donates the pools and the slab, fits the
    15.75 GiB the compiler budgets, copies nothing the size of the slab or
    of a pool and no layer of a weight stack. The compiled size is printed
    beside granite's (``-k 'granite or nemotron' -s``). A compile, not a
    chip run."""
    import json
    import re
    import time

    from jax.sharding import SingleDeviceSharding

    from cyberfabric_core_tpu.models import decoder_module, get_config
    from cyberfabric_core_tpu.ops.platform import compiled_kernels
    from cyberfabric_core_tpu.parallel.feasibility import V5E_HBM_BYTES
    from cyberfabric_core_tpu.parallel.sharding import abstract_params
    from cyberfabric_core_tpu.runtime.engine import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    topo = _topo_or_skip()
    serving = json.loads(Path(
        __file__).resolve().parents[1].joinpath(
            "benchmark/configs/nemotron-3-super-int8.json").read_text()
    )["serving"]
    n, max_seq = serving["max_batch"], serving["max_seq_len"]
    pages = serving["pool_pages"] + 1      # the worker's default margin in
    rows = n + serving["state_snapshots"]
    cfg = get_config(serving["model_config"])
    assert (cfg.kv_layers, cfg.state_layers, cfg.moe_layers, pages) == (
        2, 10, 10, 5121)
    eng = object.__new__(ContinuousBatchingEngine)
    eng.config = EngineConfig(
        model=cfg.name, max_seq_len=max_seq, max_batch=n,
        decode_chunk=serving["decode_chunk"], quantization="int8",
        prefix_cache_pages=pages, prefix_page_size=_PAGE)
    eng.model_config, eng.dtype = cfg, jnp.bfloat16
    eng._model, eng._has_state, eng._block = decoder_module(cfg), True, 0
    eng._step_counters = eng._model.STEP_COUNTERS
    eng.n_slots, eng.pmax = n, max_seq // _PAGE
    eng.spec_k, eng._spec_w = 0, 1
    eng.mesh = eng._attn_mesh = None
    here = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=here)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          abstract_params(cfg, jnp.bfloat16, "int8"))
    assert params["moe"]["router"].dtype == jnp.float32
    assert params["moe"]["moe_up"]["q"].shape == (10, 128, 1024, 2688)
    assert params["moe"]["moe_down"]["q"].shape == (10, 128, 2688, 1024)
    assert "moe_gate" not in params["moe"]
    assert params["mamba"]["ssm_in"]["q"].shape == (10, 4096, 18560)
    assert params["lm_head"]["q"].shape == (4096, 32768)
    eng.pool = types.SimpleNamespace(cache_operands=lambda: (None,) * 3)
    with compiled_kernels():
        eng._build_programs()

    f32 = jnp.float32
    pool = sds((cfg.kv_layers, pages, _PAGE, cfg.num_kv_heads * cfg.head_dim),
               jnp.bfloat16)
    state = {"ssm": sds((cfg.state_layers, rows, cfg.ssm_heads,
                         cfg.ssm_head_dim, cfg.ssm_state), f32),
             "conv": sds((cfg.state_layers, rows, cfg.ssm_conv - 1,
                          cfg.ssm_conv_dim), f32)}
    slab_bytes = int(np.prod(state["ssm"].shape)) * 4

    def mixed(width):
        return (eng._mixed_step_fn, (
            params, pool, pool, state, *_mixed_operands(sds, eng, width)))

    programs = {
        "paged_decode_chunk": (eng._paged_decode_fn, (
            params, pool, pool, state, *_decode_operands(sds, eng))),
        "mixed_step@64": mixed(64), "mixed_step@512": mixed(512),
    }
    for name, (fn, args) in programs.items():
        started = time.monotonic()
        with compiled_kernels():
            compiled = fn.lower(*args).compile()
        took = time.monotonic() - started
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        text = compiled.as_text()
        print(f"{name}: argument {mem.argument_size_in_bytes / 1e9:.2f} output "
              f"{mem.output_size_in_bytes / 1e9:.2f} aliased "
              f"{mem.alias_size_in_bytes / 1e9:.2f} temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB; compiled in "
              f"{took:.0f} s, code "
              f"{mem.generated_code_size_in_bytes / 1e6:.1f} MB, optimised "
              f"HLO {len(text) / 1e6:.1f} MB")
        if os.environ.get("AOT_DUMP_DIR"):
            Path(os.environ["AOT_DUMP_DIR"], f"{name}.hlo.txt").write_text(text)
        for kernel in ("ssm_state_update", "grouped_matmul",
                       "paged_decode_attention"):
            assert kernel in text, (name, kernel)
        assert mem.alias_size_in_bytes >= slab_bytes + 2 * int(
            np.prod(pool.shape)) * 2, "pools and state slab not donated"
        assert live < V5E_HBM_BYTES, (name, live)
        _assert_whole_array_untouched(text, state["ssm"], name)
        _assert_whole_array_untouched(text, pool, name)
        # a unit picks its layers out of the whole stacks as a scan picks its
        # ``xs``: no layer of a stack is copied
        copied = re.search(
            r"s8\[(1,)?(128,(1024,2688|2688,1024)|4096,18560|8192,4096|"
            r"4096,5376|5376,4096)\]\S* copy\(", text)
        assert not copied, f"{name}: a layer of a weight stack, {copied[0]}"


def test_kda_state_kernel_compiles_at_served_shapes(one_chip):
    """``ops/kda.kda_state_update`` at solar-open2's shapes (64 rows of 64
    heads of [128, 128] f32 in layer 5 of a 9-layer slab of 80 rows) lowers
    through Mosaic for a described v5e, 16 heads a program, the slab donated
    and aliased: nothing slab-sized besides the argument."""
    from cyberfabric_core_tpu.ops.kda import kda_state_update
    from cyberfabric_core_tpu.ops.ssd import _head_block

    assert _head_block(64, 1, 4 * 128 * 128) == 16
    f32, B, H, D = jnp.float32, 64, 64, 128
    slab = one_chip((9, 80, H, D, D), f32)
    compiled = jax.jit(
        lambda s, layer, q, k, v, g, beta, mask: kda_state_update(
            s, layer, q, k, v, g, beta, mask, kernel=True),
        donate_argnums=(0,)).lower(
            slab, one_chip((), jnp.int32), *(one_chip((B, H, D), f32),) * 4,
            one_chip((B, H), f32), one_chip((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kda_state_update" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 9 * 80 * H * D * D * 4
    assert mem.temp_size_in_bytes < 64 * 1024 * 1024


@pytest.mark.parametrize("layers,rows,batch,heads,p,n,groups,block", [
    pytest.param(9, 96, 64, 128, 64, 128, 1, 32, id="granite"),
    pytest.param(10, 96, 64, 128, 64, 128, 8, 32, id="nemotron"),
    pytest.param(16, 32, 16, 32, 128, 256, 2, 8, id="falcon-h1"),
])
def test_ssm_state_kernel_compiles_at_served_shapes(
        one_chip, layers, rows, batch, heads, p, n, groups, block):
    """``ops/ssd.ssm_state_update`` at the three cells' shapes (the batch's
    rows of the whole slab, a middle layer) lowers through Mosaic for a
    described v5e with its read-out on the MXU (PR 46: bfloat16 pieces
    against a block of ones; interpret mode cannot say whether Mosaic takes
    the casts, the dots and a block of several groups' B and C), the slab
    donated and aliased: nothing slab-sized besides the argument."""
    from cyberfabric_core_tpu.ops.ssd import _head_block, ssm_state_update

    assert _head_block(heads, groups, 4 * p * n) == block
    f32, bf16 = jnp.float32, jnp.bfloat16
    compiled = jax.jit(
        lambda s, layer, x, dt, a, b, c, mask: ssm_state_update(
            s, layer, x, dt, a, b, c, mask, kernel=True),
        donate_argnums=(0,)).lower(
            one_chip((layers, rows, heads, p, n), f32),
            one_chip((), jnp.int32), one_chip((batch, heads, p), bf16),
            one_chip((batch, heads), f32), one_chip((heads,), f32),
            *(one_chip((batch, groups, n), bf16),) * 2,
            one_chip((batch,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm_state_update" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= layers * rows * heads * p * n * 4
    assert mem.temp_size_in_bytes < 64 * 1024 * 1024


@pytest.mark.slow
def test_scheduler_programs_compile_for_v5e_at_solar_open2():
    """The scheduler's own ``paged_decode_chunk`` and ``mixed_step`` for
    solar-open2-share8-12l int8 at the served shapes of
    ``benchmark/configs/solar-open2-int8.json`` (64 slots of 3072, 3073 pages
    in THREE pool layers, 80 state rows in NINE slab layers, 40 held experts
    in TWELVE expert-stack layers, 8 steps a chunk), on one described chip:
    each holds the ``kda_state_update``, the ``grouped_matmul`` and the paged
    attention Mosaic calls, donates the pools and the slab, fits the 15.75
    GiB the compiler budgets and copies nothing the size of the slab or of a
    pool. A compile, not a chip run (``-s`` prints the sizes)."""
    import json
    import time

    from jax.sharding import SingleDeviceSharding

    from cyberfabric_core_tpu.models import decoder_module, get_config
    from cyberfabric_core_tpu.ops.platform import compiled_kernels
    from cyberfabric_core_tpu.parallel.feasibility import V5E_HBM_BYTES
    from cyberfabric_core_tpu.parallel.sharding import abstract_params
    from cyberfabric_core_tpu.runtime.engine import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    topo = _topo_or_skip()
    serving = json.loads(REPO.joinpath(
        "benchmark/configs/solar-open2-int8.json").read_text())["serving"]
    n, max_seq = serving["max_batch"], serving["max_seq_len"]
    pages = serving["pool_pages"] + 1      # the scratch page
    rows = n + serving["state_snapshots"]
    cfg = get_config(serving["model_config"])
    assert (cfg.kv_layers, cfg.state_layers, cfg.moe_layers, pages) == (
        3, 9, 12, 3073)
    eng = object.__new__(ContinuousBatchingEngine)
    eng.config = EngineConfig(
        model=cfg.name, max_seq_len=max_seq, max_batch=n,
        decode_chunk=serving["decode_chunk"], quantization="int8",
        prefix_cache_pages=pages, prefix_page_size=_PAGE)
    eng.model_config, eng.dtype = cfg, jnp.bfloat16
    eng._model, eng._has_state, eng._block = decoder_module(cfg), True, 0
    eng._step_counters = eng._model.STEP_COUNTERS
    eng.n_slots, eng.pmax = n, max_seq // _PAGE
    eng.spec_k, eng._spec_w = 0, 1
    eng.mesh = eng._attn_mesh = None
    here = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=here)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          abstract_params(cfg, jnp.bfloat16, "int8"))
    assert params["layers"]["router"].dtype == jnp.float32
    assert params["layers"]["moe_gate"]["q"].shape == (12, 40, 4096, 1280)
    assert params["kda"]["wq"]["q"].shape == (9, 4096, 8192)
    assert params["kda"]["conv_w"].shape == (9, 4, 24576)
    assert params["attention"]["w_gate"]["q"].shape == (3, 4096, 8192)
    assert params["lm_head"]["q"].shape == (4096, 24576)
    eng.pool = types.SimpleNamespace(cache_operands=lambda: (None,) * 3)
    with compiled_kernels():
        eng._build_programs()

    f32 = jnp.float32
    pool = sds((cfg.kv_layers, pages, _PAGE, cfg.num_kv_heads * cfg.head_dim),
               jnp.bfloat16)
    state = {"ssm": sds((cfg.state_layers, rows, cfg.ssm_heads,
                         cfg.ssm_head_dim, cfg.ssm_state), f32),
             "conv": sds((cfg.state_layers, rows,
                          (cfg.ssm_conv - 1) * cfg.ssm_conv_dim), f32)}
    slab_bytes = int(np.prod(state["ssm"].shape)) * 4

    def mixed(width):
        return (eng._mixed_step_fn, (
            params, pool, pool, state, *_mixed_operands(sds, eng, width)))

    programs = {
        "paged_decode_chunk": (eng._paged_decode_fn, (
            params, pool, pool, state, *_decode_operands(sds, eng))),
        "mixed_step@64": mixed(64), "mixed_step@512": mixed(512),
    }
    for name, (fn, args) in programs.items():
        started = time.monotonic()
        with compiled_kernels():
            compiled = fn.lower(*args).compile()
        took = time.monotonic() - started
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        text = compiled.as_text()
        print(f"{name}: argument {mem.argument_size_in_bytes / 1e9:.2f} output "
              f"{mem.output_size_in_bytes / 1e9:.2f} aliased "
              f"{mem.alias_size_in_bytes / 1e9:.2f} temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB; compiled in "
              f"{took:.0f} s, code "
              f"{mem.generated_code_size_in_bytes / 1e6:.1f} MB, optimised "
              f"HLO {len(text) / 1e6:.1f} MB")
        if os.environ.get("AOT_DUMP_DIR"):
            Path(os.environ["AOT_DUMP_DIR"], f"{name}.hlo.txt").write_text(text)
        for kernel in ("kda_state_update", "grouped_matmul",
                       "paged_decode_attention"):
            assert kernel in text, (name, kernel)
        assert "ssm_state_update" not in text, name
        assert mem.alias_size_in_bytes >= slab_bytes + 2 * int(
            np.prod(pool.shape)) * 2, "pools and state slab not donated"
        assert live < V5E_HBM_BYTES, (name, live)
        _assert_whole_array_untouched(text, state["ssm"], name)
        _assert_whole_array_untouched(text, state["conv"], name)
        _assert_whole_array_untouched(text, pool, name)


@pytest.mark.slow
@pytest.mark.parametrize("name", ["paged_decode_chunk", "mixed_step@64",
                                  "mixed_step@512"])
def test_scheduler_programs_compile_for_v5e_at_motif(name):
    """The scheduler's own ``paged_decode_chunk`` and ``mixed_step`` (an item
    a program: each compiles for minutes) for
    motif-3-beta-share32-27l int8 at the served shapes of
    ``benchmark/configs/motif-3-beta-int8.json`` (64 slots of 8192, 8193
    pages in the SIX layers of the full page group, 343 in the TWENTY-ONE of
    the window group, a page table of 2 x 128 slots a row, 12 held experts in
    25 expert-stack layers, 8 steps a chunk), on one described chip: each
    holds the latent kernels of both call sites by their own names and the
    ``grouped_matmul`` Mosaic call, donates both pools and copies neither,
    fits the 15.75 GiB the compiler budgets. A compile, not a chip run
    (``-s`` prints the sizes)."""
    import json
    import time

    from jax.sharding import SingleDeviceSharding

    from cyberfabric_core_tpu.models import decoder_module, get_config
    from cyberfabric_core_tpu.ops.platform import compiled_kernels
    from cyberfabric_core_tpu.parallel.feasibility import V5E_HBM_BYTES
    from cyberfabric_core_tpu.parallel.sharding import abstract_params
    from cyberfabric_core_tpu.runtime.engine import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    topo = _topo_or_skip()
    serving = json.loads(REPO.joinpath(
        "benchmark/configs/motif-3-beta-int8.json").read_text())["serving"]
    n, max_seq = serving["max_batch"], serving["max_seq_len"]
    pages = serving["pool_pages"] + 1
    cfg = get_config(serving["model_config"])
    eng = object.__new__(ContinuousBatchingEngine)
    eng.config = EngineConfig(
        model=cfg.name, max_seq_len=max_seq, max_batch=n,
        decode_chunk=serving["decode_chunk"], quantization="int8",
        prefix_cache_pages=pages, prefix_page_size=_PAGE,
        prefill_budget_tokens=serving["prefill_budget_tokens"])
    eng.model_config, eng.dtype = cfg, jnp.bfloat16
    eng._model, eng._has_state, eng._block = decoder_module(cfg), False, 0
    eng._step_counters = eng._model.STEP_COUNTERS
    eng.n_slots, eng.pmax = n, max_seq // _PAGE
    window_pages = eng._window_pages()      # from shapes: no option
    assert (cfg.kv_layers, cfg.window_layers, cfg.moe_layers, pages,
            window_pages) == (6, 21, 25, 8193,
                              serving["window_pool_pages"] + 1)
    eng.spec_k, eng._spec_w = 0, 1
    eng.mesh = eng._attn_mesh = None
    assert eng._tw == 2 * eng.pmax == 256
    here = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=here)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          abstract_params(cfg, jnp.bfloat16, "int8"))
    assert params["layers"]["router"].dtype == jnp.float32
    assert params["layers"]["moe_gate"]["q"].shape == (25, 12, 4096, 1280)
    assert params["layers"]["wkv_b"]["q"].shape == (25, 512, 16 * 256)
    assert params["layers"]["mhc_phi"].shape == (25, 2, 16384, 24)
    assert params["dense"]["gate"]["q"].shape == (2, 4096, 12288)
    assert params["lm_head"]["q"].shape == (4096, 27520)
    full = sds((cfg.kv_layers, pages, _PAGE, cfg.latent_lanes), jnp.bfloat16)
    window = sds((cfg.window_layers, window_pages, _PAGE, cfg.latent_lanes),
                 jnp.bfloat16)
    eng.pool = types.SimpleNamespace(cache_operands=lambda: (full, window))
    with compiled_kernels():
        eng._build_programs()

    def mixed(width):
        return (eng._mixed_step_fn, (
            params, full, window, *_mixed_operands(sds, eng, width)))

    fn, args = {
        "paged_decode_chunk": lambda: (eng._paged_decode_fn, (
            params, full, window, *_decode_operands(sds, eng))),
        "mixed_step@64": lambda: mixed(64),
        "mixed_step@512": lambda: mixed(512),
    }[name]()
    started = time.monotonic()
    with compiled_kernels():
        compiled = fn.lower(*args).compile()
    took = time.monotonic() - started
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"{name}: argument {mem.argument_size_in_bytes / 1e9:.2f} output "
          f"{mem.output_size_in_bytes / 1e9:.2f} aliased "
          f"{mem.alias_size_in_bytes / 1e9:.2f} temp "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, compiled in {took:.0f} s")
    text = compiled.as_text()
    if os.environ.get("AOT_DUMP_DIR"):
        Path(os.environ["AOT_DUMP_DIR"], f"{name}.hlo.txt").write_text(text)
    kernels = ["grouped_matmul", "gdla_full_decode_attention",
               "gdla_window_decode_attention"]
    if name != "paged_decode_chunk":
        kernels += ["gdla_full_ragged_attention",
                    "gdla_window_ragged_attention"]
    for kernel in kernels:
        assert kernel in text, (name, kernel)
    for pool in (full, window):
        _assert_whole_array_untouched(text, pool, name)
    assert mem.alias_size_in_bytes >= sum(
        int(np.prod(p.shape)) * 2 for p in (full, window)), name
    assert live <= V5E_HBM_BYTES, (name, live)
    # as kimi's: the ring and the score block of both call sites' ragged
    # kernels are VMEM scratch
    if ("motif", name) in _LATENT_TEMP:
        assert mem.temp_size_in_bytes <= _LATENT_TEMP["motif", name] \
            + 2 * _RAGGED_SCRATCH, (name, mem.temp_size_in_bytes)
    # the residual streams are carried lane-dense, a token's four streams
    # side by side in whole (16, 128) tiles; no array puts them on the
    # sublanes, where the chip stores tiles of four rows
    S, H = cfg.mhc_expansion_rate, cfg.hidden_size
    carry = re.search(rf"bf16\[(\d+),{S * H}\]\{{1,0:T\(8,128\)\(2,1\)", text)
    assert carry and f"[{carry.group(1)},{S},{H}]" not in text, name


def _ouro_programs(model: str, conf_file: str, sharding):
    """The scheduler's own two programs for an ouro configuration file's
    served shapes, and their abstract operands: (cfg, pool, {name: (fn,
    args)})."""
    import json

    from cyberfabric_core_tpu.models import decoder_module, get_config
    from cyberfabric_core_tpu.ops.platform import compiled_kernels
    from cyberfabric_core_tpu.parallel.sharding import abstract_params
    from cyberfabric_core_tpu.runtime.engine import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    serving = json.loads(REPO.joinpath(conf_file).read_text())["serving"]
    assert serving["model_config"] == model
    n, max_seq, page = (serving["max_batch"], serving["max_seq_len"],
                        serving["page"])
    pages = serving["pool_pages"] + 1
    cfg = get_config(model)
    eng = object.__new__(ContinuousBatchingEngine)
    eng.config = EngineConfig(
        model=model, max_seq_len=max_seq, max_batch=n,
        decode_chunk=serving["decode_chunk"], quantization="int8",
        prefix_cache_pages=pages, prefix_page_size=page,
        prefill_budget_tokens=serving["prefill_budget_tokens"])
    eng.model_config, eng.dtype = cfg, jnp.bfloat16
    eng._model, eng._has_state, eng._block = decoder_module(cfg), False, 0
    eng._step_counters = eng._model.STEP_COUNTERS
    eng.n_slots, eng.pmax = n, -(-max_seq // page)
    eng.spec_k, eng._spec_w = 0, 1
    eng.mesh = eng._attn_mesh = None
    assert pages == n * eng.pmax + 1        # the slot minimum, no margin

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          abstract_params(cfg, jnp.bfloat16, "int8"))
    assert params["layers"]["wq"]["q"].shape[0] == cfg.num_layers
    assert params["exit_gate"]["w"].dtype == jnp.float32
    pool = sds((cfg.kv_layers, pages, page, cfg.num_kv_heads * cfg.head_dim),
               jnp.bfloat16)
    eng.pool = types.SimpleNamespace(cache_operands=lambda: (pool, pool))
    with compiled_kernels():
        eng._build_programs()
    return cfg, pool, {
        "paged_decode_chunk": (eng._paged_decode_fn, (
            params, pool, pool, *_decode_operands(sds, eng))),
        **{f"mixed_step@{w}": (eng._mixed_step_fn, (
            params, pool, pool, *_mixed_operands(sds, eng, w)))
           for w in serving["mixed_widths"][-2:]}}


def test_the_loop_is_a_loop_in_the_lowered_programs():
    """The tiny twin of the slow compile below, on this backend: in
    ``tiny-ouro``'s two step programs as lowered from shapes the passes are
    a loop whose body is the loop over layers (a ``while`` inside a
    ``while``, inside the chunk's), not ``loop_steps`` unrolled copies: each
    layer matrix is multiplied at ONE site, and the pool is donated."""
    from cyberfabric_core_tpu.ops.paged_attention import (
        kv_block_sizes, ragged_trip_pages)

    cfg, pool, programs = _ouro_programs(
        "tiny-ouro", "benchmark/tests/rehearsal/configs/tiny-ouro.json", None)
    assert (cfg.loop_steps, cfg.num_layers, pool.shape[0]) == (3, 3, 9)
    for name, (fn, args) in programs.items():
        text = fn.lower(*args).as_text()
        whiles = text.count("stablehlo.while")
        # the passes, the layers inside them, the chunk's steps around both
        assert whiles >= (3 if name == "paged_decode_chunk" else 2), name
        # a layer's seven matrices, the head, the gate and the interpreted
        # kernels' own products, once each whatever R is: three unrolled
        # passes would hold 21 layer products alone. (The ragged kernel's
        # walk holds a score and a value dot for every block size of a
        # trip, for a q-block's first trip and for its later ones.)
        walk = 0 if name == "paged_decode_chunk" else 4 * len(
            kv_block_sizes(ragged_trip_pages(pool.shape[2], None, 32)))
        dots = len(re.findall(r"stablehlo\.dot_general", text))
        assert dots < 3 * 7 + walk, (name, dots, walk)
        assert text.count("tf.aliasing_output") >= 2, name


@pytest.mark.slow
def test_scheduler_programs_compile_for_v5e_at_ouro():
    """The scheduler's own ``paged_decode_chunk`` and ``mixed_step`` for
    ouro-2.6b int8 at the served shapes of
    ``benchmark/configs/ouro-2.6b-int8.json`` (8 slots of 832, 105 pages in
    192 cache layers = 10.57 GB, 48 layers of weights run 4 times), on one
    described chip: each holds the paged kernels, donates both pools and
    copies neither through the two nested loops, and fits the 15.75 GiB the
    compiler budgets. A compile, not a chip run (``-s`` prints the sizes)."""
    import time

    from jax.sharding import SingleDeviceSharding

    from cyberfabric_core_tpu.ops.platform import compiled_kernels
    from cyberfabric_core_tpu.parallel.feasibility import V5E_HBM_BYTES

    topo = _topo_or_skip()
    cfg, pool, programs = _ouro_programs(
        "ouro-2.6b", "benchmark/configs/ouro-2.6b-int8.json",
        SingleDeviceSharding(topo.devices[0]))
    assert pool.shape == (192, 105, 64, 2048)
    for name, (fn, args) in programs.items():
        started = time.monotonic()
        with compiled_kernels():
            compiled = fn.lower(*args).compile()
        took = time.monotonic() - started
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"{name}: argument {mem.argument_size_in_bytes / 1e9:.2f} "
              f"output {mem.output_size_in_bytes / 1e9:.2f} aliased "
              f"{mem.alias_size_in_bytes / 1e9:.2f} temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB, compiled in "
              f"{took:.0f} s")
        text = compiled.as_text()
        if os.environ.get("AOT_DUMP_DIR"):
            Path(os.environ["AOT_DUMP_DIR"],
                 f"ouro-{name}.hlo.txt").write_text(text)
        assert "paged_decode_attention" in text, name
        _assert_whole_array_untouched(text, pool, name)
        assert mem.alias_size_in_bytes >= 2 * int(np.prod(pool.shape)) * 2
        assert live <= V5E_HBM_BYTES, (name, live)


def _laguna_programs(conf_file: str, sharding, pools_of=None):
    """The scheduler's own two programs for a laguna configuration file's
    served shapes, and their abstract operands: (cfg, params, the four
    pools, {name: (fn, args)}). ``pools_of(cfg, eng, sds, pages, page)``:
    another architecture's cache operands (absent: laguna's two page groups
    of K and V)."""
    import json

    from cyberfabric_core_tpu.models import decoder_module, get_config
    from cyberfabric_core_tpu.ops.platform import compiled_kernels
    from cyberfabric_core_tpu.parallel.sharding import abstract_params
    from cyberfabric_core_tpu.runtime.engine import EngineConfig
    from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

    serving = json.loads(REPO.joinpath(conf_file).read_text())["serving"]
    n, max_seq, page = (serving["max_batch"], serving["max_seq_len"],
                        serving["page"])
    pages = serving["pool_pages"] + 1
    cfg = get_config(serving["model_config"])
    eng = object.__new__(ContinuousBatchingEngine)
    eng.config = EngineConfig(
        model=cfg.name, max_seq_len=max_seq, max_batch=n,
        decode_chunk=serving["decode_chunk"], quantization="int8",
        prefix_cache_pages=pages, prefix_page_size=page,
        prefill_budget_tokens=serving["prefill_budget_tokens"])
    eng.model_config, eng.dtype = cfg, jnp.bfloat16
    eng._model, eng._has_state, eng._block = decoder_module(cfg), False, 0
    eng._step_counters = eng._model.STEP_COUNTERS
    eng.n_slots, eng.pmax = n, max_seq // page
    eng.spec_k, eng._spec_w = 0, 1
    eng.mesh = eng._attn_mesh = None

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          abstract_params(cfg, jnp.bfloat16, "int8"))
    if pools_of is None:
        assert eng._tw == 2 * eng.pmax
        lanes = cfg.num_kv_heads * cfg.head_dim
        full = sds((cfg.kv_layers, pages, page, lanes), jnp.bfloat16)
        window = sds((cfg.window_layers, eng._window_pages(), page, lanes),
                     jnp.bfloat16)
        pools = (full, full, window, window)
    else:
        pools = pools_of(cfg, eng, sds, pages, page)
    eng.pool = types.SimpleNamespace(cache_operands=lambda: pools)
    if sharding is None:            # the CPU's: interpreted kernels
        eng._build_programs()
    else:
        with compiled_kernels():
            eng._build_programs()
    programs = {"paged_decode_chunk": (eng._paged_decode_fn, (
        params, *pools, *_decode_operands(sds, eng)))}
    for width in serving["mixed_widths"][-1:]:
        programs[f"mixed_step@{width}"] = (eng._mixed_step_fn, (
            params, *pools, *_mixed_operands(sds, eng, width)))
    return cfg, params, pools, programs


def test_laguna_programs_hold_three_bodies_and_two_kernel_instances():
    """The tiny twin of the served compile below, lowered and not compiled
    (seconds): ``tiny-laguna-share4``'s two programs take FOUR donated pools,
    hold each K/V kernel under both call sites' names, and cut the six
    layers into a dense full layer, a run of three window layers and the
    tail (a full expert layer, a window layer): scans, not six bodies."""
    cfg, params, pools, programs = _laguna_programs(
        "benchmark/tests/rehearsal/configs/tiny-laguna.json", None)
    assert (cfg.kv_layers, cfg.window_layers) == (2, 4)
    assert params["full"]["wq"]["q"].shape == (2, 64, 6 * 32)
    assert params["window"]["wq"]["q"].shape == (4, 64, 9 * 32)
    assert params["window"]["w_gate"]["q"].shape == (4, 64, 9)
    assert params["layers"]["router"].dtype == jnp.float32
    for name, (fn, args) in programs.items():
        lowered = fn.lower(*args)
        text = lowered.as_text(debug_info=True)     # the scopes' names
        scopes = ["gqa_full_decode_attention", "gqa_window_decode_attention",
                  "laguna_full_layer", "laguna_window_layer"]
        if name != "paged_decode_chunk":
            scopes += ["gqa_full_ragged_attention",
                       "gqa_window_ragged_attention"]
        for scope in scopes:
            assert scope in text, (name, scope)
        text = lowered.as_text()
        assert text.count("tf.aliasing_output") == 4, name
        # the five expert layers' grouped matmuls sit in the three expert
        # bodies (gate, up, down each), not once a layer (15)
        assert text.count("call @grouped_matmul") == 9, name


@pytest.mark.slow
def test_scheduler_programs_compile_for_v5e_at_laguna():
    """The scheduler's own ``paged_decode_chunk`` and ``mixed_step`` at 512
    for laguna-s-2.1-share8-12l int8 at the served shapes of
    ``benchmark/configs/laguna-s-2.1-int8.json`` (64 slots of 8192, 8193
    pages in the THREE layers of the full K/V page group, 739 in the NINE of
    the window group, a page table of 2 x 128 slots a row, 32 held experts
    in 11 expert layers, 8 steps a chunk), on one described chip: each holds
    the K/V kernels of both call sites by their own names and the
    ``grouped_matmul`` Mosaic call, donates all four pools and copies none,
    fits the 15.75 GiB the compiler budgets. A compile, not a chip run
    (``-s`` prints the sizes). As read at PR 55 (by hand): 12.55 GB of
    arguments, ``paged_decode_chunk`` 0.41 GB of temporaries (38 s),
    ``mixed_step@512`` 0.39 GB (55 s; 0.4 with the grid form): the lane's
    q and o in the ragged kernel's slab layout (9.4 MB each at the window
    layers' 72 heads) are among them; what the walk keeps beside them is
    VMEM the call asks for (two rings of 3 key blocks of 10 or 16 pages,
    7.9-12.6 MB; the accumulators of 64 queries x 72 heads, 7.1 MB; one kv
    head's score block, 1.5-2.4 MB; under its 64 MB limit) and shows in no
    HBM number."""
    import json
    import time

    from jax.sharding import SingleDeviceSharding

    from cyberfabric_core_tpu.ops.platform import compiled_kernels
    from cyberfabric_core_tpu.parallel.feasibility import V5E_HBM_BYTES

    topo = _topo_or_skip()
    conf_file = "benchmark/configs/laguna-s-2.1-int8.json"
    serving = json.loads(REPO.joinpath(conf_file).read_text())["serving"]
    cfg, params, pools, programs = _laguna_programs(
        conf_file, SingleDeviceSharding(topo.devices[0]))
    assert (cfg.kv_layers, cfg.window_layers, cfg.moe_layers) == (3, 9, 11)
    assert pools[0].shape == (3, 8193, 64, 1024)
    assert pools[2].shape == (9, serving["window_pool_pages"] + 1, 64, 1024)
    assert params["full"]["wq"]["q"].shape == (3, 3072, 48 * 128)
    assert params["window"]["wo"]["q"].shape == (9, 72 * 128, 3072)
    assert params["layers"]["moe_gate"]["q"].shape == (11, 32, 3072, 1024)
    assert params["lm_head"]["q"].shape == (3072, 12544)
    for name, (fn, args) in programs.items():
        started = time.monotonic()
        with compiled_kernels():
            compiled = fn.lower(*args).compile()
        took = time.monotonic() - started
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"{name}: argument {mem.argument_size_in_bytes / 1e9:.2f} "
              f"output {mem.output_size_in_bytes / 1e9:.2f} aliased "
              f"{mem.alias_size_in_bytes / 1e9:.2f} temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB, compiled in "
              f"{took:.0f} s")
        text = compiled.as_text()
        if os.environ.get("AOT_DUMP_DIR"):
            Path(os.environ["AOT_DUMP_DIR"],
                 f"laguna-{name}.hlo.txt").write_text(text)
        kernels = ["grouped_matmul", "gqa_full_decode_attention",
                   "gqa_window_decode_attention"]
        if name != "paged_decode_chunk":
            kernels += ["gqa_full_ragged_attention",
                        "gqa_window_ragged_attention"]
        for kernel in kernels:
            assert kernel in text, (name, kernel)
        for pool in (pools[0], pools[2]):
            _assert_whole_array_untouched(text, pool, name)
        assert mem.alias_size_in_bytes >= sum(
            int(np.prod(p.shape)) * 2 for p in pools), name
        assert live <= V5E_HBM_BYTES, (name, live)


# ---- attention over a chosen set (models/glm_dsa.py, ops/dsa.py)

def test_dsa_kernels_compile_at_served_shapes(one_chip):
    """The two index kernels of ``ops/dsa.py`` (one program a decode row over
    its index pages of 128 lanes; one a block of 32 queries, 1 024 query rows
    against key blocks of 1 024), the selection (``dsa_select``: blocks of
    32 and of 64 rows of 16 384 scores) and both latent kernels under the one
    operand they gain (``keep``: [32, 16384] of decode rows as int32 tiles a
    trip, [1, 512, 16384] of a chunk as int8 tiles a q-block and trip), at
    glm-5's served shapes: 32 slots of 16 384 tokens, 32 index heads, 64
    heads on a latent page of 640 lanes."""
    from cyberfabric_core_tpu.ops import dsa
    from cyberfabric_core_tpu.ops.mla_attention import (mla_decode_attention,
                                                        mla_ragged_attention)

    batch, pmax, rank = 32, 256, 512
    index = one_chip((2, batch * pmax + 1, _PAGE, 128), jnp.bfloat16)
    latent = one_chip((2, batch * pmax + 1, _PAGE, 640), jnp.bfloat16)
    table, lens = one_chip((batch, pmax), jnp.int32), one_chip((batch,),
                                                               jnp.int32)
    layer, lane = one_chip((), jnp.int32), one_chip((1,), jnp.int32)
    _compiles_with_mosaic(
        lambda q, w, p, pt, n, ly: dsa.index_scores(q, w, p, pt, n, ly),
        one_chip((batch, 32, 128), jnp.bfloat16),
        one_chip((batch, 32), jnp.float32), index, table, lens, layer)
    _compiles_with_mosaic(
        lambda q, w, p, pt, h, n, ly: dsa.index_scores_ragged(
            q, w, p, pt, h, n, ly),
        one_chip((1, 512, 32, 128), jnp.bfloat16),
        one_chip((1, 512, 32), jnp.float32), index,
        one_chip((1, pmax), jnp.int32), lane, lane, layer)
    for queries in (batch, 512):    # decode rows; a chunk's queries
        _compiles_with_mosaic(
            lambda s, span: dsa.select(s, 2048, span),
            one_chip((queries, pmax * _PAGE), jnp.float32), layer)
    _compiles_with_mosaic(
        lambda q, p, pt, n, ly, keep: mla_decode_attention(
            q, p, pt, n, ly, rank=rank, scale=0.0625, interpret=False,
            keep=keep, name="dsa_sparse_decode_attention"),
        one_chip((batch, 64, 640), jnp.bfloat16), latent, table, lens, layer,
        one_chip((batch, pmax * _PAGE), jnp.int8))
    _compiles_with_mosaic(
        lambda q, p, pt, h, n, ly, keep: mla_ragged_attention(
            q, p, pt, h, n, ly, rank=rank, scale=0.0625, interpret=False,
            keep=keep, name="dsa_ragged_attention"),
        one_chip((1, 64, 512, 640), jnp.bfloat16), latent,
        one_chip((1, pmax), jnp.int32), lane, lane, layer,
        one_chip((1, 512, pmax * _PAGE), jnp.int8))


def _glm_dsa_programs(conf_file: str, sharding):
    """The scheduler's own two programs for a glm_moe_dsa configuration
    file's served shapes (:func:`_laguna_programs` over the latent chain's
    two arrays): (cfg, params, the two pools, {name: (fn, args)})."""
    def pools(cfg, eng, sds, pages, page):
        return tuple(sds((cfg.kv_layers, pages, page, lanes), jnp.bfloat16)
                     for lanes in (cfg.latent_lanes, cfg.index_lanes))

    return _laguna_programs(conf_file, sharding, pools)


def _sorts_in(text: str) -> list[tuple[str, str]]:
    """(result shapes, op name) of every ``sort`` and ``TopK`` instruction
    of an optimised HLO text."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) (sort|custom-call)\(", line)
        if m and (m.group(2) == "sort" or 'custom_call_target="TopK"' in line):
            name = re.search(r'op_name="([^"]*)"', line)
            found.append((m.group(1), name.group(1) if name else ""))
    return found


def test_glm_dsa_programs_take_two_pools_and_hold_the_named_parts():
    """The tiny twin of the served compile below, on the CPU (interpreted
    kernels): ``tiny-glm-dsa-share4``'s two programs take TWO donated pools
    (the latent rows and the index keys), hold the index pass, the selection
    and the latent kernels under the chosen keys by the names a device trace
    shows, and keep the indexer's head weights float32. Nothing reads
    ``aux["chosen"]``, so no op of the position list is even lowered (the
    trace drops a scan's carry that feeds only itself), and COMPILED they
    hold no sort inside a layer's attention (the selection is a bisection,
    ``dsa_select``): what is left to sort is the experts' routing and the
    sampler's row of logits."""
    cfg, params, pools, programs = _glm_dsa_programs(
        "benchmark/tests/rehearsal/configs/tiny-glm-dsa.json", None)
    assert (cfg.kv_layers, cfg.index_topk) == (5, 12)
    assert pools[0].shape[:3] == pools[1].shape[:3]
    assert params["layers"]["index_wq"]["q"].dtype == jnp.int8
    assert params["dense"]["index_w"].dtype == jnp.float32
    for name, (fn, args) in programs.items():
        lowered = fn.lower(*args)
        text = lowered.as_text(debug_info=True)     # the scopes' names
        scopes = ["glm_dsa_layer", "dsa_index_scores", "dsa_topk",
                  "dsa_select", "dsa_sparse_decode_attention"]
        if name != "paged_decode_chunk":
            scopes += ["dsa_index_scores_ragged", "dsa_ragged_attention"]
        for scope in scopes:
            assert scope in text, (name, scope)
        assert lowered.as_text().count("tf.aliasing_output") == 2, name
        assert "dsa_chosen_positions" not in text, name
        sorts = _sorts_in(lowered.compile().as_text())
        assert sorts and not [s for s in sorts if "glm_dsa_layer" in s[1]], (
            name, sorts)


@pytest.mark.slow
def test_scheduler_programs_compile_for_v5e_at_glm_dsa():
    """The scheduler's own ``paged_decode_chunk`` and ``mixed_step`` at 512
    for glm-5-share16-7l int8 at the served shapes of
    ``benchmark/configs/glm-5-int8.json`` (32 slots of 16 384, 8193 pages in
    both arrays of the latent chain, 16 held experts in 6 expert layers, 8
    steps a chunk), on one described chip: each holds the index kernels, the
    selection's (``dsa_select``: no sort of a row of scores), the latent
    kernels under their chosen-set names and the ``grouped_matmul`` Mosaic
    call, donates both pools and copies neither, fits the 15.75 GiB
    the compiler budgets. A compile, not a chip run (4-6 minutes)."""
    import time

    from jax.sharding import SingleDeviceSharding

    from cyberfabric_core_tpu.ops.platform import compiled_kernels
    from cyberfabric_core_tpu.parallel.feasibility import V5E_HBM_BYTES

    topo = _topo_or_skip()
    cfg, params, pools, programs = _glm_dsa_programs(
        "benchmark/configs/glm-5-int8.json",
        SingleDeviceSharding(topo.devices[0]))
    assert pools[0].shape == (7, 8193, 64, 640)
    assert pools[1].shape == (7, 8193, 64, 128)
    assert params["layers"]["moe_gate"]["q"].shape == (6, 16, 6144, 2048)
    assert params["layers"]["index_wq"]["q"].shape == (6, 2048, 32 * 128)
    assert params["lm_head"]["q"].shape == (6144, 19360)
    for name, (fn, args) in programs.items():
        started = time.monotonic()
        with compiled_kernels():
            compiled = fn.lower(*args).compile()
        took = time.monotonic() - started
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"{name}: argument {mem.argument_size_in_bytes / 1e9:.2f} "
              f"output {mem.output_size_in_bytes / 1e9:.2f} aliased "
              f"{mem.alias_size_in_bytes / 1e9:.2f} temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB, compiled in "
              f"{took:.0f} s")
        text = compiled.as_text()
        if os.environ.get("AOT_DUMP_DIR"):
            Path(os.environ["AOT_DUMP_DIR"],
                 f"glm-dsa-{name}.hlo.txt").write_text(text)
        kernels = ["grouped_matmul", "dsa_index_scores", "dsa_select",
                   "dsa_sparse_decode_attention"]
        if name != "paged_decode_chunk":
            kernels += ["dsa_index_scores_ragged", "dsa_ragged_attention"]
        for kernel in kernels:
            assert kernel in text, (name, kernel)
        # nothing reads aux["chosen"]: no sort in a layer's attention
        assert not [s for s in _sorts_in(text) if "glm_dsa_layer" in s[1]]
        for pool in pools:
            _assert_whole_array_untouched(text, pool, name)
        assert mem.alias_size_in_bytes >= sum(
            int(np.prod(p.shape)) * 2 for p in pools), name
        assert live <= V5E_HBM_BYTES, (name, live)
