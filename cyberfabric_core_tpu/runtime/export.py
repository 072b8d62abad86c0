"""AOT StableHLO export — the model-registry "emits StableHLO for each
registered architecture" requirement (BASELINE.json north star; SURVEY §7:
the C++ host consumes AOT-exported programs, so the serving computations must
exist as portable artifacts, not only as live jit caches).

Exports are pure lowering (jit(...).lower(avals) → StableHLO MLIR) — no device
compile, no weight materialization: parameter shapes come from
``jax.eval_shape`` over the architecture's init, so a 70B export costs MBs of
text, not HBM. Each artifact is deterministic for (architecture, shapes,
dtype, quantization), recorded in a manifest with sha256 so registries can
dedupe and the host can cache compiled executables keyed by digest.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..models import ModelConfig, get_config
from ..models import llama
from ..ops.rope import rope_frequencies


@dataclass
class ExportedProgram:
    name: str                 # e.g. "prefill-b1x128" | "decode-k8"
    path: str                 # artifact file (MLIR text)
    sha256: str
    size_bytes: int
    arg_shapes: list[str]


def _param_avals(cfg: ModelConfig, dtype, quantization: str):
    """Abstract parameter tree for the architecture (no allocation)."""
    base = jax.eval_shape(
        lambda k: llama.init_params(cfg, k, dtype), jax.random.PRNGKey(0))
    from .quant import quant_bits

    bits = quant_bits(quantization)
    if bits is not None:
        from .quant import quantize_llama_params

        # shape-level quantization (init_params_quantized materializes +
        # blocks per leaf — the abstract path must stay allocation-free)
        return jax.eval_shape(lambda p: quantize_llama_params(p, bits), base)
    return base


def _stablehlo_text(jitted, *avals) -> str:
    lowered = jitted.lower(*avals)
    return str(lowered.compiler_ir(dialect="stablehlo"))


def _atomic_write(path: Path, text: str) -> None:
    """Write-then-rename: concurrent exports / readers must never see a torn
    file whose bytes no longer match the manifest digest."""
    import os
    import tempfile

    fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=f".{path.name}.",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            f.write(text)
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


def _write_artifact(out_dir: Path, stem: str, text: str,
                    arg_shapes: list[str]) -> ExportedProgram:
    out_dir.mkdir(parents=True, exist_ok=True)
    digest = hashlib.sha256(text.encode()).hexdigest()
    path = out_dir / f"{stem}.mlir"
    _atomic_write(path, text)
    # Manifest-relative path: a bundle must stay consumable after being
    # moved/renamed (or written with a relative out_dir and consumed from a
    # different cwd) — consumers resolve it against the manifest's directory.
    return ExportedProgram(name=stem, path=path.name, sha256=digest,
                           size_bytes=len(text), arg_shapes=arg_shapes)


def export_llama_programs(
    model: str,
    out_dir: Path,
    *,
    batch: int = 1,
    prefill_bucket: int = 128,
    decode_chunk: int = 8,
    max_seq_len: int = 1024,
    dtype=jnp.bfloat16,
    quantization: str = "none",
    conformance: bool = False,
) -> dict[str, Any]:
    """Export the two serving programs (prefill+first-token, fused decode
    chunk) for a decoder architecture. Returns the manifest dict.

    ``conformance=True`` additionally materializes (small!) params and writes
    ``conformance.npz`` — recorded inputs/outputs a fresh-process consumer
    replays to prove the artifacts execute (runtime/consume.py)."""
    from .engine import build_decode_chunk_fn

    cfg = get_config(model)
    if cfg.architecture != "llama":
        raise ValueError(f"export_llama_programs drives decoder models, got "
                         f"{cfg.architecture}")
    if conformance and jnp.dtype(dtype).name not in (
            "float32", "float64", "int32", "int64"):
        # fail BEFORE artifacts are written / params materialized — a late
        # error would leave a partial export (artifacts, no manifest)
        raise ValueError(
            f"conformance=True needs an npz-native dtype (float32), got "
            f"{jnp.dtype(dtype).name}")
    # the forward's cache insert is a scatter whose OOB writes are DROPPED
    # (unlike dynamic_update_slice, which clamps) — a bucket wider than the
    # cache would silently attend over zero KV, so reject it loudly here
    if prefill_bucket > max_seq_len:
        raise ValueError(
            f"prefill_bucket {prefill_bucket} must be <= max_seq_len "
            f"{max_seq_len}: the cache insert at offset cache_start must fit "
            f"the cache entirely (decode room is enforced per-prompt by "
            f"EngineConfig.bucket_for)")
    rope = rope_frequencies(cfg.head_dim, max(cfg.max_position, max_seq_len),
                            cfg.rope_theta)
    params = _param_avals(cfg, dtype, quantization)
    sds = jax.ShapeDtypeStruct
    B = batch

    def prefill(p, input_ids, lengths, rng, temperature, top_p, top_k):
        T = input_ids.shape[1]
        cache = llama.init_cache(cfg, B, max_seq_len, dtype)
        positions = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
        start = jnp.zeros((B,), jnp.int32)
        hidden, cache = llama.forward(p, cfg, input_ids, positions, cache,
                                      start, rope)
        last_h = llama.gather_last_hidden(hidden, lengths)
        logits = llama.lm_head_logits(p, cfg, last_h)
        from ..ops.sampling import sample_token

        rng, sub = jax.random.split(rng)
        first = sample_token(logits, sub, temperature, top_p, top_k)
        return first, cache, rng

    prefill_avals = (
        params, sds((B, prefill_bucket), jnp.int32), sds((B,), jnp.int32),
        sds((2,), jnp.uint32), sds((B,), jnp.float32), sds((B,), jnp.float32),
        sds((B,), jnp.int32))
    decode_fn = build_decode_chunk_fn(cfg, decode_chunk, rope)
    cache_aval = sds((cfg.kv_layers, B, max_seq_len, cfg.num_kv_heads,
                      cfg.head_dim), dtype)
    decode_avals = (
        params, cache_aval, cache_aval, sds((B,), jnp.int32),
        sds((B,), jnp.int32), sds((2,), jnp.uint32), sds((B,), jnp.float32),
        sds((B,), jnp.float32), sds((B,), jnp.int32))

    programs = [
        _write_artifact(
            out_dir, f"prefill-b{B}x{prefill_bucket}",
            _stablehlo_text(jax.jit(prefill), *prefill_avals),
            [str(a) for a in prefill_avals[1:]]),
        _write_artifact(
            out_dir, f"decode-k{decode_chunk}",
            _stablehlo_text(
                jax.jit(decode_fn, donate_argnums=(1, 2)), *decode_avals),
            [str(a) for a in decode_avals[1:]]),
    ]

    if conformance:
        # Conformance bundle: recorded inputs + live-jit outputs so a fresh
        # process (runtime/consume.py — or a native PJRT host) can prove the
        # ARTIFACT executes to the same results. Materializes params, so only
        # sensible for small configs; the npz stores the flattened calling
        # convention (leaf order == the lowered program's arg order).
        import numpy as np

        from .quant import quant_bits as _qb

        _bits = _qb(quantization)
        if _bits is not None:
            from .quant import init_params_quantized

            live_params = init_params_quantized(cfg, jax.random.PRNGKey(0),
                                                dtype, bits=_bits)
        else:
            live_params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype)
        rng = jax.random.PRNGKey(7)
        ids = jax.random.randint(jax.random.PRNGKey(1), (B, prefill_bucket),
                                 3, cfg.vocab_size, jnp.int32)
        lengths = jnp.full((B,), prefill_bucket, jnp.int32)
        temp = jnp.zeros((B,), jnp.float32)      # greedy: deterministic
        top_p = jnp.ones((B,), jnp.float32)
        top_k = jnp.zeros((B,), jnp.int32)
        pre_in = (live_params, ids, lengths, rng, temp, top_p, top_k)
        pre_out = jax.jit(prefill)(*pre_in)
        first, cache, rng2 = pre_out
        dec_in = (live_params, cache[0], cache[1],
                  first, lengths, rng2, temp, top_p, top_k)
        dec_out = jax.jit(decode_fn)(*dec_in)  # no donation: inputs reused

        bundle: dict[str, Any] = {}
        for prog_name, args_tree, outs_tree in (
                (programs[0].name, pre_in, pre_out),
                (programs[1].name, dec_in, dec_out)):
            # int4 leaves (W4 export) widen to int8 for npz storage; their
            # indices ride the bundle so the consumer narrows them back to
            # the artifact's s4 calling convention
            int4_in = [i for i, x in enumerate(jax.tree_util.tree_leaves(args_tree))
                       if getattr(x, "dtype", None) == jnp.int4]
            in_leaves = [np.asarray(x.astype(jnp.int8))
                         if getattr(x, "dtype", None) == jnp.int4
                         else np.asarray(x)
                         for x in jax.tree_util.tree_leaves(args_tree)]
            out_leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(outs_tree)]
            for leaves in (in_leaves, out_leaves):
                for a in leaves:
                    if a.dtype.name not in ("float32", "float64", "int8",
                                            "int32", "int64", "uint32",
                                            "uint64", "bool"):
                        raise ValueError(
                            f"conformance bundle needs npz-native dtypes; got "
                            f"{a.dtype} — export with dtype=float32")
            bundle[f"{prog_name}.n_in"] = np.int64(len(in_leaves))
            bundle[f"{prog_name}.n_out"] = np.int64(len(out_leaves))
            bundle[f"{prog_name}.int4_in"] = np.asarray(int4_in, np.int64)
            for i, a in enumerate(in_leaves):
                bundle[f"{prog_name}.in{i}"] = a
            for i, a in enumerate(out_leaves):
                bundle[f"{prog_name}.out{i}"] = a
        np.savez(out_dir / "conformance.npz", **bundle)
    manifest = {
        "model": model,
        "architecture": cfg.architecture,
        "dialect": "stablehlo",
        "dtype": jnp.dtype(dtype).name,
        "quantization": quantization,
        "batch": B,
        "prefill_bucket": prefill_bucket,
        "decode_chunk": decode_chunk,
        "max_seq_len": max_seq_len,
        "exported_at": time.time(),
        # program paths are manifest-relative; export_dir records where this
        # bundle was originally written (informational — consumers resolve
        # against wherever they actually find the manifest)
        "export_dir": str(out_dir.resolve()),
        "programs": [vars(p) for p in programs],
    }
    _atomic_write(out_dir / "manifest.json", json.dumps(manifest, indent=1))
    return manifest


def export_bert_program(
    model: str,
    out_dir: Path,
    *,
    batch: int = 8,
    seq_len: int = 256,
    dtype=jnp.bfloat16,
) -> dict[str, Any]:
    """Export the encoder forward (embeddings path, BASELINE config #3)."""
    from ..models import bert

    cfg = get_config(model)
    if cfg.architecture != "bert":
        raise ValueError(f"export_bert_program drives encoder models, got "
                         f"{cfg.architecture}")
    params = jax.eval_shape(
        lambda k: bert.init_params(cfg, k, dtype), jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct

    def encode(p, input_ids, attention_mask):
        return bert.embed_pooled(p, cfg, input_ids, attention_mask)

    avals = (params, sds((batch, seq_len), jnp.int32),
             sds((batch, seq_len), jnp.int32))
    program = _write_artifact(
        out_dir, f"encode-b{batch}x{seq_len}",
        _stablehlo_text(jax.jit(encode), *avals),
        [str(a) for a in avals[1:]])
    manifest = {
        "model": model,
        "architecture": cfg.architecture,
        "dialect": "stablehlo",
        "dtype": jnp.dtype(dtype).name,
        "batch": batch,
        "seq_len": seq_len,
        "exported_at": time.time(),
        "export_dir": str(out_dir.resolve()),
        "programs": [vars(program)],
    }
    _atomic_write(out_dir / "manifest.json", json.dumps(manifest, indent=1))
    return manifest


def export_for_model(model_config_name: str, architecture: str,
                     out_root: Path, *,
                     engine_options: Optional[dict] = None) -> dict[str, Any]:
    """Registry-facing entry: export the serving programs for a managed model
    using its engine options (quantization, chunk, seq len)."""
    opts = engine_options or {}
    out_dir = out_root / model_config_name
    if architecture == "bert":
        return export_bert_program(
            model_config_name, out_dir,
            batch=int(opts.get("embed_batch", 8)),
            seq_len=int(opts.get("embed_seq_len", 256)))
    return export_llama_programs(
        model_config_name, out_dir,
        batch=int(opts.get("export_batch", 1)),
        prefill_bucket=int(opts.get("export_prefill_bucket", 128)),
        decode_chunk=int(opts.get("decode_chunk", 8)),
        max_seq_len=int(opts.get("max_seq_len", 1024)),
        quantization=str(opts.get("quantization", "none")),
    )
