"""AOT TPU compilation against a topology description — no chip required.

Round-3 verdict item 2: the Pallas kernels had "only ever run in
interpret/CPU mode; TPU tiling/lowering failures would be invisible today."
This module compiles the REAL serving program set — the exact program bodies
`runtime/scheduler.py:_build_programs` jits (bucketed flash prefill, fused
paged-decode chunk with the ragged paged-attention kernel, int8/int4
variants) — for a TPU topology (libtpu PJRT topology, e.g. ``v5e:2x2``) on a
CPU-only host. Pallas kernels lower through Mosaic for real
(`ops/platform.compiled_kernels`), XLA runs its full TPU pipeline, and the
serialized executables mean hardware day is execution-only.

SURVEY §7 stage 3 / BASELINE.json north star (llama-3-8b serving on v5e).
CLI:

    python -m cyberfabric_core_tpu.runtime.aot_tpu --model llama-3-8b \
        --quant int8 --topology v5e:2x2 --out aot_artifacts/

Reference anchor: the reference's AOT story is per-architecture artifact
emission keyed by digest (model-registry PRD.md:200-224); here the target is
a serialized TPU executable rather than source IR — one step further down
the same pipeline as runtime/export.py's StableHLO artifacts.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import time
from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import llama
from ..models.configs import ModelConfig, get_config
from ..ops.platform import compiled_kernels
from ..ops.sampling import sample_token, sample_token_per_slot, split_keys_per_slot

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def tpu_topology(name: str = "v5e:2x2"):
    """PJRT TPU topology description (no device needed). Known names include
    v5e:1x1 … v5e:4x4 etc.; requires the libtpu wheel, present in this image."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name=name)


def _replicated(topo_devices, n: int = 1):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(topo_devices[:n]).reshape(n), ("tp",))
    return mesh, NamedSharding(mesh, P())


def _with_sharding(tree, sharding):
    """ShapeDtypeStruct tree pinned to a sharding (replicated by default) —
    lowering needs a device placement to know its compile target."""
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        tree)


def _abstract_params(cfg: ModelConfig, dtype, quantization: str):
    from .quant import quant_bits, quantize_llama_params

    bits = quant_bits(quantization)

    def build(key):
        p = llama.init_params(cfg, key, dtype)
        return quantize_llama_params(p, bits) if bits else p

    return jax.eval_shape(build, jax.random.PRNGKey(0))


def serving_programs(
    model: str,
    *,
    dtype=jnp.bfloat16,
    quantization: str = "none",
    prefill_bucket: int = 512,
    decode_chunk: int = 16,
    max_batch: int = 8,
    page_size: int = 64,
    max_seq_len: int = 2048,
    device_stop_width: int = 8,
    spec_k: int = 0,
    use_flash: bool = True,
    prefix_cache_pages: int = 0,
    mesh: Any = None,
) -> dict[str, tuple[Any, tuple]]:
    """name → (fn, abstract_args): the scheduler's program set, abstracted.

    Bodies intentionally mirror runtime/scheduler.py:_build_programs — same
    flash prefill + sample fusion, same scan-fused paged decode chunk — so a
    lowering failure here is a lowering failure of the real serving path.
    ``spec_k > 0`` adds the batched-speculation ragged verify step
    (parameterized like ``--device-stop-width``: it must match the serving
    EngineConfig's ``scheduler_spec_k`` or the AOT cache misses).

    ``mesh`` switches the set to the TENSOR-PARALLEL serving variants: the
    abstract param tree carries the Megatron NamedShardings
    (parallel/sharding.sharded_abstract_params — the exact tree the engine
    uploads), the paged pool shards on the kv-head axis, and every host-
    control row pins to the replicated sharding, so GSPMD lowers the same
    collectives serving runs. Program names gain a ``-tp{N}`` suffix — the
    AOT cache key is (topology, tp, spec_k, device_stop_width, shapes).
    """
    cfg = get_config(model)
    if prefill_bucket > max_seq_len:
        raise ValueError("prefill_bucket must fit max_seq_len")
    rope = llama.rope_frequencies(cfg.head_dim, cfg.max_position, cfg.rope_theta)
    suffix = ""
    pool_sharding = repl_sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.sharding import (llama_page_pool_sharding,
                                         sharded_abstract_params)

        tp_degree = dict(mesh.shape).get("tp", 1)
        suffix = f"-tp{tp_degree}"
        params_abs = sharded_abstract_params(cfg, mesh, dtype, quantization)
        pool_sharding = llama_page_pool_sharding(cfg, mesh)
        repl_sharding = NamedSharding(mesh, P())
    else:
        params_abs = _abstract_params(cfg, dtype, quantization)
    _plain_sds = jax.ShapeDtypeStruct

    def sds(shape, dt):
        # control rows: EXPLICITLY replicated under a tp mesh (the engine's
        # SH01 discipline, mirrored into the lowering args)
        if repl_sharding is not None:
            return _plain_sds(shape, dt, sharding=repl_sharding)
        return _plain_sds(shape, dt)

    # program-shape knob, part of the AOT cache key: flash only where mesh
    # is None (tp meshes take the jnp attention path — the flash kernel
    # cannot auto-partition under GSPMD, tp_sharded_program's documented
    # discipline), so the compiled set keys on the pair (AK01)
    flash = use_flash and mesh is None

    def prefill(params, ids, lengths, rng, temp, top_p, top_k, rope_t):
        last_h, kv = llama.prefill_collect(params, cfg, ids, lengths, rope_t,
                                           use_flash=flash)
        logits = llama.lm_head_logits(params, cfg, last_h)
        rng, sub = jax.random.split(rng)
        return sample_token(logits, sub, temp, top_p, top_k), kv, rng

    key_abs = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    prefill_args = (
        params_abs,
        sds((1, prefill_bucket), jnp.int32),
        sds((1,), jnp.int32),
        key_abs,
        sds((1,), jnp.float32),
        sds((1,), jnp.float32),
        sds((1,), jnp.int32),
        jax.eval_shape(lambda: rope),
    )

    # pool depth mirrors the engine: max(config.prefix_cache_pages, the
    # per-slot minimum) — a bigger committed pool is a different program
    # shape, so it keys the cache too (AK01)
    pmax = -(-max_seq_len // page_size)
    n_pages = max(prefix_cache_pages, max_batch * pmax + 1)
    pool_shape = (cfg.num_layers, n_pages, page_size,
                  cfg.num_kv_heads * cfg.head_dim)
    pool_sds = _plain_sds(pool_shape, dtype, sharding=pool_sharding) \
        if pool_sharding is not None else _plain_sds(pool_shape, dtype)

    # device-side termination mirror (runtime/scheduler.py): per-slot stop-id
    # rows (-1 padded to device_stop_width — must match the serving
    # EngineConfig or the AOT cache misses), max-tokens length limits, and a
    # finished mask that freezes rows so the deep-lookahead ring survives
    # finishes
    stop_width = device_stop_width

    def paged_decode_chunk(params, k_pool, v_pool, page_table, last_tokens,
                           lengths, active, finished, stop_ids, limit_lens,
                           keys, temp, top_p, top_k):
        def step(carry, j):
            pools, toks, lens, fin, keys = carry
            run = active & jnp.logical_not(fin)
            hidden, pools = llama.forward_paged_decode(
                params, cfg, toks[:, None], pools, page_table, lens, rope,
                write_mask=run, mesh=mesh)
            logits = llama.lm_head_logits(params, cfg, hidden[:, 0, :])
            keys2, subs = split_keys_per_slot(keys)
            nxt = sample_token_per_slot(logits, subs, temp, top_p, top_k)
            new_lens = lens + 1
            is_stop = jnp.any(nxt[:, None] == stop_ids, axis=1)
            hit = (new_lens >= limit_lens) | (
                (j == decode_chunk - 1) & (new_lens + decode_chunk
                                           > max_seq_len))
            emit = jnp.where(run, nxt, -1)
            return (pools, jnp.where(run, nxt, toks),
                    jnp.where(run, new_lens, lens),
                    fin | (run & (is_stop | hit)),
                    jnp.where(run[:, None], keys2, keys)), emit

        (pools, last, lens, fin, keys), toks = jax.lax.scan(
            step, ((k_pool, v_pool), last_tokens, lengths, finished, keys),
            jnp.arange(decode_chunk, dtype=jnp.int32))
        lens = jnp.where(active, lens, 0)
        return toks.T, pools[0], pools[1], last, keys, lens, fin

    keys_abs = jax.eval_shape(
        lambda: jax.random.split(jax.random.PRNGKey(0), max_batch))
    decode_args = (
        params_abs, pool_sds, pool_sds,
        sds((max_batch, pmax), jnp.int32),
        sds((max_batch,), jnp.int32),
        sds((max_batch,), jnp.int32),
        sds((max_batch,), jnp.bool_),
        sds((max_batch,), jnp.bool_),
        sds((max_batch, stop_width), jnp.int32),
        sds((max_batch,), jnp.int32),
        keys_abs,
        sds((max_batch,), jnp.float32),
        sds((max_batch,), jnp.float32),
        sds((max_batch,), jnp.int32),
    )
    programs = {
        f"prefill-flash-b1x{prefill_bucket}{suffix}": (prefill, prefill_args),
        f"paged-decode-k{decode_chunk}x{max_batch}{suffix}":
            (paged_decode_chunk, decode_args),
    }

    if spec_k > 0:
        # batched speculative decoding: the scheduler's ragged verify step
        # (runtime/scheduler.py spec_mixed_step) — speculating rows run a
        # q_len=1+d draft span through the ragged paged kernel; accept,
        # per-position stop/limit truncation and the length advance happen
        # in-program. The body mirrors the serving jit exactly so a Mosaic
        # lowering failure of the spec path is visible pre-hardware.
        from .speculative import greedy_accept_counts

        spec_w = spec_k + 1
        q_max = -(-spec_w // 8) * 8

        def spec_verify_step(params, k_pool, v_pool, page_table, q_ids,
                             q_lens, prefill_hist, last_tokens, lengths,
                             active, finished, sample_mask, final_mask,
                             final_lens, spec_lens, stop_ids, limit_lens,
                             keys, temp, top_p, top_k):
            run = active & jnp.logical_not(finished)
            q_ids = q_ids.at[:, 0].set(
                jnp.where(active, last_tokens, q_ids[:, 0]))
            hist = jnp.where(active, lengths, prefill_hist)
            hidden, pools = llama.forward_paged_mixed(
                params, cfg, q_ids, (k_pool, v_pool), page_table, hist,
                q_lens, rope, write_mask=run | jnp.logical_not(active),
                mesh=mesh)
            last_h = llama.gather_last_hidden(hidden, q_lens)
            logits = llama.lm_head_logits(params, cfg, last_h)
            keys2, subs = split_keys_per_slot(keys)
            nxt = sample_token_per_slot(logits, subs, temp, top_p, top_k)
            N = q_ids.shape[0]
            H = hidden.shape[-1]
            span_h = jax.lax.dynamic_slice_in_dim(hidden, 0, spec_w, axis=1)
            span_logits = llama.lm_head_logits(
                params, cfg, span_h.reshape(N * spec_w, H))
            outs = jnp.argmax(span_logits, axis=-1).astype(
                jnp.int32).reshape(N, spec_w)
            spec = (spec_lens > 0) & run
            a = greedy_accept_counts(outs, q_ids[:, 1:spec_w], spec_lens)
            committed = outs.at[:, 0].set(jnp.where(spec, outs[:, 0], nxt))
            n_commit = jnp.where(spec, a + 1, 1)
            idx = jnp.arange(spec_w, dtype=jnp.int32)[None, :]
            in_commit = idx < n_commit[:, None]
            is_stop = jnp.any(
                committed[:, :, None] == stop_ids[:, None, :], axis=2)
            eff_len = jnp.where(
                run, lengths, jnp.where(final_mask, final_lens - 1, lengths))
            len_after = eff_len[:, None] + idx + 1
            hit = (len_after >= limit_lens[:, None]) | (
                len_after + decode_chunk > max_seq_len)
            fin_at = (is_stop | hit) & in_commit
            alive = jnp.cumprod(
                1 - jnp.pad(fin_at.astype(jnp.int32),
                            ((0, 0), (1, 0)))[:, :spec_w], axis=1) > 0
            emit = in_commit & alive
            n_emit = jnp.sum(emit.astype(jnp.int32), axis=1)
            sample = sample_mask & jnp.logical_not(finished)
            toks = jnp.where(emit & sample[:, None], committed, -1)
            new_last = jnp.where(
                sample,
                jnp.take_along_axis(
                    committed, jnp.maximum(n_emit - 1, 0)[:, None],
                    axis=1)[:, 0],
                last_tokens)
            keys_out = jnp.where(sample[:, None], keys2, keys)
            new_lens = jnp.where(
                run, lengths + n_emit,
                jnp.where(final_mask, final_lens,
                          jnp.where(active, lengths, 0)))
            fin_out = finished | (sample & jnp.any(fin_at & emit, axis=1))
            active_out = active | final_mask
            # accept counts ride the emit matrix's last column — one drain
            # carries tokens AND acceptance (the serving AS04 discipline)
            a_out = jnp.where(spec, a, -1)
            toks_out = jnp.concatenate([toks, a_out[:, None]], axis=1)
            return (toks_out, pools[0], pools[1], new_last, keys_out,
                    new_lens, fin_out, active_out)

        spec_args = (
            params_abs, pool_sds, pool_sds,
            sds((max_batch, pmax), jnp.int32),
            sds((max_batch, q_max), jnp.int32),
            sds((max_batch,), jnp.int32),
            sds((max_batch,), jnp.int32),
            sds((max_batch,), jnp.int32),
            sds((max_batch,), jnp.int32),
            sds((max_batch,), jnp.bool_),
            sds((max_batch,), jnp.bool_),
            sds((max_batch,), jnp.bool_),
            sds((max_batch,), jnp.bool_),
            sds((max_batch,), jnp.int32),
            sds((max_batch,), jnp.int32),
            sds((max_batch, stop_width), jnp.int32),
            sds((max_batch,), jnp.int32),
            keys_abs,
            sds((max_batch,), jnp.float32),
            sds((max_batch,), jnp.float32),
            sds((max_batch,), jnp.int32),
        )
        programs[f"spec-verify-w{spec_w}x{max_batch}{suffix}"] = \
            (spec_verify_step, spec_args)

    if repl_sharding is not None:
        # leaves eval_shape produced without a placement (rng keys, rope
        # tables) pin to the replicated sharding — every arg of a tp
        # program names its destination explicitly
        programs = {
            name: (fn, jax.tree.map(
                lambda l: _plain_sds(l.shape, l.dtype,
                                     sharding=repl_sharding)
                if getattr(l, "sharding", None) is None else l, args))
            for name, (fn, args) in programs.items()}
    return programs


def tp_sharded_program(model: str, mesh, *, dtype=jnp.bfloat16,
                       quantization: str = "none",
                       prefill_bucket: int = 512, use_flash: bool = False):
    """TP-sharded prefill over the topology mesh — proves the Megatron-style
    shardings + GSPMD collectives lower for the TPU target too (XLA enforces
    the per-device HBM budget at AOT compile, so this doubles as the hard
    oracle behind parallel/feasibility.py's static plan).

    ``use_flash`` defaults False: Mosaic kernels don't auto-partition under
    GSPMD (they'd need a shard_map wrapper), and the TP serving path runs
    the jnp attention — this program mirrors it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.sharding import sharded_abstract_params

    cfg = get_config(model)
    rope = llama.rope_frequencies(cfg.head_dim, cfg.max_position, cfg.rope_theta)
    sds = jax.ShapeDtypeStruct
    # the SAME sharded abstract tree the feasibility planner budgets with
    params_abs = sharded_abstract_params(cfg, mesh, dtype, quantization)
    repl = NamedSharding(mesh, P())

    def prefill_logits(params, ids, lengths, rope_t):
        last_h, _ = llama.prefill_collect(params, cfg, ids, lengths, rope_t,
                                          use_flash=use_flash)
        return llama.lm_head_logits(params, cfg, last_h)

    args = (
        params_abs,
        sds((1, prefill_bucket), jnp.int32, sharding=repl),
        sds((1,), jnp.int32, sharding=repl),
        jax.tree.map(lambda l: sds(l.shape, l.dtype, sharding=repl),
                     jax.eval_shape(lambda: rope)),
    )
    return prefill_logits, args


def aot_compile(
    model: str,
    *,
    quantization: str = "none",
    topology: str = "v5e:2x2",
    dtype: str = "bfloat16",
    prefill_bucket: int = 512,
    decode_chunk: int = 16,
    max_batch: int = 8,
    max_seq_len: int = 2048,
    device_stop_width: int = 8,
    spec_k: int = 0,
    use_flash: bool = True,
    prefix_cache_pages: int = 0,
    tp: int = 0,
    include_serving: bool = True,
    out_dir: Optional[str | Path] = None,
    serialize: bool = False,
) -> dict:
    """Compile the serving set for ``topology``; returns the evidence report.

    ``serialize=True`` additionally writes serialized TPU executables (+ a
    manifest with sha256) so a TPU host can skip compilation entirely."""
    if serialize and out_dir is None:
        raise ValueError("serialize=True requires out_dir (--out): the whole "
                         "point is executables on disk for hardware day")
    topo = tpu_topology(topology)
    if tp and tp > len(topo.devices):
        raise ValueError(f"tp={tp} exceeds the {len(topo.devices)} devices "
                         f"of topology {topology!r}")
    dt = _DTYPES[dtype]
    mesh1, repl = _replicated(topo.devices, 1)
    report: dict[str, Any] = {
        "model": model, "quantization": quantization, "topology": topology,
        "dtype": dtype, "prefill_bucket": prefill_bucket,
        "decode_chunk": decode_chunk, "max_batch": max_batch,
        "max_seq_len": max_seq_len, "spec_k": spec_k, "tp": tp,
        "device_stop_width": device_stop_width, "use_flash": use_flash,
        "prefix_cache_pages": prefix_cache_pages, "programs": [],
    }
    out = Path(out_dir) if out_dir else None
    if out:
        out.mkdir(parents=True, exist_ok=True)

    jobs = []
    if include_serving:
        progs = serving_programs(
            model, dtype=dt, quantization=quantization,
            prefill_bucket=prefill_bucket, decode_chunk=decode_chunk,
            max_batch=max_batch, max_seq_len=max_seq_len,
            device_stop_width=device_stop_width, spec_k=spec_k,
            use_flash=use_flash, prefix_cache_pages=prefix_cache_pages)
        jobs = [(name, fn, jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=repl)
            if getattr(l, "sharding", None) is None else l, args))
            for name, (fn, args) in progs.items()]
    if tp:
        from jax.sharding import Mesh

        # ep axis of size 1 so MoE expert shardings resolve on pure-TP meshes
        tp_mesh = Mesh(np.asarray(topo.devices[:tp]).reshape(1, tp),
                       ("ep", "tp"))
        fn, args = tp_sharded_program(model, tp_mesh, dtype=dt,
                                      quantization=quantization,
                                      prefill_bucket=prefill_bucket)
        jobs.append((f"prefill-tp{tp}", fn, args))
        if include_serving:
            # the tp SERVING set: the same paged-decode / spec-verify
            # bodies, lowered with Megatron-sharded params, the kv-head-
            # sharded pool and replicated control rows — the (topology, tp,
            # spec_k, stop_width)-keyed variants the mesh engine runs, so a
            # GSPMD/Mosaic lowering failure of the sharded path is visible
            # pre-hardware exactly like the single-device one
            tp_progs = serving_programs(
                model, dtype=dt, quantization=quantization,
                prefill_bucket=prefill_bucket, decode_chunk=decode_chunk,
                max_batch=max_batch, max_seq_len=max_seq_len,
                device_stop_width=device_stop_width, spec_k=spec_k,
                use_flash=use_flash,
                prefix_cache_pages=prefix_cache_pages, mesh=tp_mesh)
            jobs.extend((name, fn, args)
                        for name, (fn, args) in tp_progs.items())

    for name, fn, args in jobs:
        t0 = time.monotonic()
        with compiled_kernels():
            lowered = jax.jit(fn).lower(*args)
            compiled = lowered.compile()
        dt_s = time.monotonic() - t0
        entry: dict[str, Any] = {"name": name,
                                 "compile_seconds": round(dt_s, 2)}
        try:
            mem = compiled.memory_analysis()
            entry["memory"] = {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "code_bytes": int(mem.generated_code_size_in_bytes),
            }
        except Exception as e:  # noqa: BLE001 — analysis is best-effort
            entry["memory_error"] = str(e)[:200]
        import re

        hlo = lowered.as_text()
        entry["custom_calls"] = sorted(
            set(re.findall(r"stablehlo\.custom_call @(\w+)", hlo)))
        entry["has_mosaic_kernel"] = "tpu_custom_call" in hlo
        if serialize:
            import pickle

            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(compiled)
            # self-contained artifact: deserialize_and_load needs the arg
            # trees, so they ship inside the file, not in the caller's memory
            blob = pickle.dumps({"format": 1, "name": name,
                                 "payload": payload, "in_tree": in_tree,
                                 "out_tree": out_tree})
            path = out / f"{name}.jaxexec"
            path.write_bytes(blob)
            entry["executable"] = {
                "path": path.name, "bytes": len(blob),
                "sha256": hashlib.sha256(blob).hexdigest(),
            }
        report["programs"].append(entry)
    if out:
        (out / "aot_manifest.json").write_text(json.dumps(report, indent=1))
    return report


def read_serialized(path: str | Path) -> dict:
    """Parse a .jaxexec artifact container (payload + arg trees). Structure
    check only — loading onto devices is ``load_serialized``."""
    import pickle

    blob = pickle.loads(Path(path).read_bytes())
    if blob.get("format") != 1 or not blob.get("payload"):
        raise ValueError(f"{path}: not a v1 .jaxexec artifact")
    return blob


def load_serialized(path: str | Path, backend: str = "tpu"):
    """Hardware-day path: deserialize a .jaxexec straight into a loaded
    executable on the live TPU backend — no tracing, no XLA compile."""
    from jax.experimental import serialize_executable

    blob = read_serialized(path)
    return serialize_executable.deserialize_and_load(
        blob["payload"], blob["in_tree"], blob["out_tree"], backend=backend)


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="llama-3-8b")
    ap.add_argument("--quant", default="int8")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--prefill-bucket", type=int, default=512)
    ap.add_argument("--decode-chunk", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq-len", type=int, default=2048)
    ap.add_argument("--device-stop-width", type=int, default=8)
    ap.add_argument("--spec-k", type=int, default=0,
                    help="scheduler_spec_k of the serving config: adds the "
                         "batched-speculation ragged verify step to the "
                         "compiled set (0 = off, matching the default)")
    ap.add_argument("--use-flash", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="part of the AOT key: flash vs jnp attention are "
                         "different compiled programs")
    ap.add_argument("--prefix-cache-pages", type=int, default=0,
                    help="prefix_cache_pages of the serving config: pool "
                         "depth above the per-slot minimum changes the "
                         "compiled program shape, so it keys the cache")
    ap.add_argument("--tp", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--serialize", action="store_true")
    args = ap.parse_args(argv)
    report = aot_compile(
        args.model, quantization=args.quant, topology=args.topology,
        dtype=args.dtype, prefill_bucket=args.prefill_bucket,
        decode_chunk=args.decode_chunk, max_batch=args.max_batch,
        max_seq_len=args.max_seq_len,
        device_stop_width=args.device_stop_width, spec_k=args.spec_k,
        use_flash=args.use_flash,
        prefix_cache_pages=args.prefix_cache_pages, tp=args.tp,
        out_dir=args.out,
        serialize=args.serialize)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
