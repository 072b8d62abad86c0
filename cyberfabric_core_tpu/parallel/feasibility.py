"""Machine-checked TP feasibility plans (round-3 verdict item 3).

BASELINE #5 — llama-3-70b served TP-sharded on a v5e pod slice — previously
had "no shape-level proof": nothing pinned the tp=8 sharding plan or the
per-device HBM byte budget, so an infeasible sharding would only surface on
hardware day. This module derives the plan from the SAME sources serving
uses — `jax.eval_shape` over `models/llama.init_params` (+ the quantized
tree) and `parallel/sharding.llama_param_shardings` — computes per-device
bytes via `NamedSharding.shard_shape` on an AbstractMesh (no devices
needed), adds the KV pool and an activation estimate, and emits the
per-shard safetensors read plan (which rows/cols of each HF tensor each tp
rank needs).

Reference anchor: model-registry PRD.md:200-224 (managed models declare
architecture/size_bytes/format — the registry must know whether a model FITS
before admitting it to a node).

CLI: python -m cyberfabric_core_tpu.parallel.feasibility --model llama-3-70b \
         --tp 8 --quant int8
"""

from __future__ import annotations

import argparse
import json
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec as P

from ..models import llama
from ..models.configs import ModelConfig, get_config
from ..runtime.weights import _LLAMA_MAP
from .sharding import llama_param_shardings

#: HBM a program may use on one v5e chip: ``memory_stats()["bytes_limit"]`` as
#: the device reports it (chip run of PR 21), 15.75 GiB of the part's nominal
#: 16 — the figure the TPU compiler budgets too ("Used 17.06G of 15.75G hbm"
#: when it refused phi-3-mini bf16 at the worker's default shape). Pass
#: ``hbm_bytes`` for another generation or for a live reading.
V5E_HBM_BYTES = 16_909_336_064


class InfeasiblePlanError(ValueError):
    """A serving configuration whose per-device byte budget exceeds HBM —
    raised by the engine-construction gate (:func:`gate_engine_plan`) so an
    over-budget config (FEASIBILITY_70B's bf16@tp=8 shape) is rejected with
    a typed, explainable error at BUILD time, never as a device OOM at
    request time. Carries the full machine-derived ``plan`` report."""

    def __init__(self, message: str, plan: dict[str, Any]):
        super().__init__(message)
        self.plan = plan


def gate_engine_plan(
    model: "str | ModelConfig",
    tp: int,
    *,
    quantization: str = "none",
    dtype=jnp.bfloat16,
    max_batch: int = 8,
    max_seq_len: int = 8192,
    page_size: int = 64,
    num_pages: Optional[int] = None,
    hbm_bytes: Optional[int] = None,
    state_rows: Optional[int] = None,
) -> dict[str, Any]:
    """Engine-construction gate: derive the per-device byte plan for the
    EXACT serving geometry (the engine passes its real page-pool size via
    ``num_pages``) and raise :class:`InfeasiblePlanError` when a known HBM
    budget cannot hold it. ``hbm_bytes=None`` plans without enforcing (CPU
    hosts and forced-host meshes have no HBM to blow) — the report still
    lands in ``stats()["mesh"]`` so the budget is visible either way."""
    cfg = model if isinstance(model, ModelConfig) else get_config(model)
    plan = tp_plan(cfg, max(1, tp), quantization=quantization, dtype=dtype,
                   max_batch=max_batch, max_seq_len=max_seq_len,
                   page_size=page_size, num_pages=num_pages,
                   state_rows=state_rows,
                   hbm_bytes=hbm_bytes or V5E_HBM_BYTES,
                   # the engine's pool REPLICATES when tp cannot divide the
                   # kv heads — budget what serving actually allocates
                   kv_replicated=tp > 1 and cfg.num_kv_heads % tp != 0)
    plan["enforced"] = hbm_bytes is not None
    if hbm_bytes is not None and not plan["fits"]:
        raise InfeasiblePlanError(
            f"{plan['model']} @ tp={plan['tp']} quant={quantization} needs "
            f"{plan['total_bytes_per_device']} bytes/device "
            f"(params {plan['param_bytes_per_device']} + KV "
            f"{plan['kv_bytes_per_device']} + state "
            f"{plan['state_bytes_per_device']} + activations "
            f"{plan['activation_bytes_estimate']}) > HBM budget {hbm_bytes} "
            f"({plan['hbm_utilization']:.2f}x the budget); "
            "raise tp, quantize, or shrink max_batch/max_seq_len",
            plan={k: v for k, v in plan.items()
                  if k not in ("leaves", "read_plan")})
    return plan


def _walk(tree: dict, prefix: str = ""):
    for k, v in tree.items():
        path = f"{prefix}.{k}" if prefix else k
        if isinstance(v, dict) and not any(
                qk in v for qk in ("q", "qe")):
            yield from _walk(v, path)
        else:
            yield path, v


def tp_plan(
    model: str | ModelConfig,
    tp: int,
    *,
    ep: int = 1,
    quantization: str = "none",
    dtype=jnp.bfloat16,
    max_batch: int = 8,
    max_seq_len: int = 8192,
    page_size: int = 64,
    prefill_bucket: int = 2048,
    hbm_bytes: int = V5E_HBM_BYTES,
    num_pages: Optional[int] = None,
    kv_replicated: bool = False,
    state_rows: Optional[int] = None,
) -> dict[str, Any]:
    """Per-device byte budget + per-shard read plan for ``model`` at tp=N.

    Returns a report whose ``fits`` verdict is machine-derived: every
    per-leaf shard shape comes from NamedSharding.shard_shape over the same
    spec tree serving applies, never hand-multiplied fractions.
    """
    from .sharding import sharded_abstract_params

    # a ModelConfig passes through directly — the load rehearsal plans over
    # scaled geometries that aren't registry entries
    cfg = model if isinstance(model, ModelConfig) else get_config(model)
    model = cfg.name
    if cfg.num_kv_heads % tp and tp % cfg.num_kv_heads:
        raise ValueError(
            f"{model}: num_kv_heads={cfg.num_kv_heads} and tp={tp} divide "
            "neither way — the KV cache cannot shard")
    if ep > 1 and cfg.num_experts % ep:
        raise ValueError(f"{model}: num_experts={cfg.num_experts} not "
                         f"divisible by ep={ep}")
    # the ep axis always exists (size 1 for dense models / pure-TP plans) so
    # MoE expert shardings resolve on any plan
    mesh = AbstractMesh((ep, tp), ("ep", "tp"))
    # the SAME sharded abstract tree the AOT compiler lowers — planner and
    # compiler cannot drift (tests/test_feasibility.py pins them together)
    sharded = sharded_abstract_params(cfg, mesh, dtype, quantization)
    spec_tree = llama_param_shardings(cfg, mesh)
    specs = dict(_walk(spec_tree))

    leaves = []
    param_bytes_device = 0
    param_bytes_total = 0
    for path, leaf in _walk(sharded):
        sub = leaf if isinstance(leaf, dict) and any(
            k in leaf for k in ("q", "qe")) else {"": leaf}
        for qk, arr in sub.items():
            shard = arr.sharding.shard_shape(arr.shape)
            per_dev = int(np.prod(shard)) * arr.dtype.itemsize
            total = int(np.prod(arr.shape)) * arr.dtype.itemsize
            leaves.append({
                "leaf": f"{path}.{qk}" if qk else path,
                "shape": list(arr.shape), "dtype": str(arr.dtype),
                "spec": str(arr.sharding.spec), "shard_shape": list(shard),
                "bytes_per_device": per_dev,
            })
            param_bytes_device += per_dev
            param_bytes_total += total

    # KV pool [L, n_pages, page, Hkv*D], kv heads sharded on tp (or page
    # replicated when tp > kv heads — q_per_kv grouping still shards queries).
    # ``num_pages`` pins the ENGINE's actual pool size (prefix-cache headroom
    # included) so the gate budgets the bytes serving will really allocate.
    pages = num_pages if num_pages is not None \
        else max_batch * (-(-max_seq_len // page_size)) + 1
    # ``kv_replicated`` budgets the ENGINE's fallback (tp does not divide
    # the kv heads → llama_page_pool_sharding replicates, every device pays
    # full heads); the default models the canonical Megatron layouts —
    # heads/tp when tp divides, duplicated-KV groups (1 head/device) when
    # the mesh outgrows the head count
    kv_heads_dev = cfg.num_kv_heads if kv_replicated \
        else max(1, cfg.num_kv_heads // tp)
    kv_dtype = jnp.dtype(dtype)
    kv_bytes_device = (2 * cfg.kv_layers * pages * page_size * kv_heads_dev
                       * cfg.head_dim * kv_dtype.itemsize)

    # activation high-water estimate for the prefill bucket (B=1): hidden
    # stream + per-layer q/k/v + attention scores at flash block granularity.
    # Deliberately coarse-over: the AOT gate (runtime/aot_tpu.py memory
    # analysis) is the exact oracle; this keeps the planner device-free.
    act_bytes = int(prefill_bucket * cfg.hidden_size * 2 * 8)

    # recurrent state beside the pool (falcon_h1): one f32 row a slot plus the
    # snapshot rows (``state_rows``: the engine's count; absent: the slots),
    # replicated — every device pays all of it
    state_bytes_device = cfg.state_bytes_per_row() * (
        state_rows if state_rows is not None else max_batch)

    total_device = (param_bytes_device + kv_bytes_device + state_bytes_device
                    + act_bytes)
    read_plan = _read_plan(cfg, tp, ep, specs, sharded)
    return {
        "model": model, "tp": tp, "ep": ep, "quantization": quantization,
        "dtype": str(jnp.dtype(dtype)), "max_batch": max_batch,
        "max_seq_len": max_seq_len, "page_size": page_size,
        "param_bytes_total": param_bytes_total,
        "param_bytes_per_device": param_bytes_device,
        "kv_bytes_per_device": kv_bytes_device,
        "state_bytes_per_device": state_bytes_device,
        "activation_bytes_estimate": act_bytes,
        "total_bytes_per_device": total_device,
        "hbm_bytes": hbm_bytes,
        "hbm_utilization": round(total_device / hbm_bytes, 4),
        "fits": total_device < hbm_bytes,
        "leaves": leaves,
        "read_plan": read_plan,
    }


def _read_plan(cfg: ModelConfig, tp: int, ep: int, specs: dict[str, Any],
               sharded_tree: dict) -> list[dict]:
    """Per-shard safetensors read plan: for each HF tensor, the axis each tp
    rank slices, the per-rank extent along it (what a sharded loader passes
    to safetensors get_slice() so rank r never reads other ranks' bytes),
    and — for MoE leaves under expert parallelism — which experts each ep
    rank reads at all."""
    shapes = dict(_walk(sharded_tree))

    def leaf_shape(leaf: str) -> tuple[int, ...]:
        node = shapes[leaf]
        if isinstance(node, dict):  # quantized: 'q'/'qe' keeps the geometry
            node = node.get("q") or node.get("qe")
        return tuple(node.shape)

    plan = []
    for leaf, (tmpl, transpose) in _LLAMA_MAP.items():
        if leaf == "lm_head" and cfg.tie_embeddings:
            continue
        if leaf in ("layers.bq", "layers.bk", "layers.bv") \
                and not cfg.attention_bias:
            continue
        if leaf.startswith("layers.moe") or leaf == "layers.router":
            if cfg.num_experts == 0:
                continue
        elif leaf in ("layers.gate", "layers.up", "layers.down") \
                and cfg.num_experts > 0:
            continue
        spec = tuple(specs[leaf].spec)
        entry: dict[str, Any] = {"tensor": tmpl}
        if "{e}" in tmpl:
            # each ep rank reads only its num_experts/ep expert files
            entry["experts_per_rank"] = cfg.num_experts // ep
            entry["ep_ranks"] = ep
        our_axes = [i for i, s in enumerate(spec) if s == "tp"]
        if not our_axes:
            entry["sharded"] = False
            plan.append(entry)
            continue
        (axis,) = our_axes
        n_data_axes = len(spec)
        # our tensor axes → HF axes: stacked L (and E) dims vanish; transpose
        # swaps the remaining matrix axes
        mat_rank = 2 if leaf not in ("layers.bq", "layers.bk", "layers.bv",
                                     "final_norm") else 1
        mat_axis = axis - (n_data_axes - mat_rank)
        hf_axis = (mat_rank - 1 - mat_axis) if transpose else mat_axis
        # HF tensor dims = trailing matrix dims of our leaf, transposed back
        mat_dims = leaf_shape(leaf)[-mat_rank:]
        hf_dims = tuple(reversed(mat_dims)) if transpose else mat_dims
        entry.update({
            "sharded": True,
            "hf_slice_axis": int(hf_axis),
            "hf_shape": list(hf_dims),
            "per_rank_extent": int(hf_dims[hf_axis]) // tp,
            "ranks": tp,
        })
        plan.append(entry)
    return plan


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="llama-3-70b")
    ap.add_argument("--tp", type=int, default=8)
    ap.add_argument("--ep", type=int, default=1)
    ap.add_argument("--quant", default="int8")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq-len", type=int, default=8192)
    ap.add_argument("--full", action="store_true",
                    help="include per-leaf table in the output")
    args = ap.parse_args(argv)
    report = tp_plan(args.model, args.tp, ep=args.ep, quantization=args.quant,
                     max_batch=args.max_batch, max_seq_len=args.max_seq_len)
    if not args.full:
        report = {k: v for k, v in report.items() if k not in ("leaves",)}
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
