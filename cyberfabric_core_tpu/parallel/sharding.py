"""GSPMD sharding rules for the llama family.

Megatron-style tensor parallelism expressed as NamedShardings; XLA GSPMD inserts the
collectives (one all-reduce after the attention output projection, one after the MLP
down projection — riding ICI on a TPU mesh):

- wq/wk/wv: column-parallel (head dim sharded on ``tp``)
- wo:       row-parallel (input dim sharded on ``tp``)
- gate/up:  column-parallel; down: row-parallel
- lm_head:  vocab-sharded; embed + norms replicated
- KV cache: kv-head axis on ``tp``, batch axis on ``dp``

Stacked-layer leading dim (L) is never sharded. num_kv_heads must divide by tp for
the cache sharding (8 kv heads → tp≤8 for Llama-3/Mistral; the 70B across v5e-8 is
exactly tp=8).
"""

from __future__ import annotations

from typing import Any

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.configs import ModelConfig


def llama_param_shardings(cfg: ModelConfig, mesh: Mesh,
                          layer_axis: Any = None) -> dict[str, Any]:
    """Tree of NamedShardings matching models/llama.init_params structure.

    ``layer_axis``: mesh axis name (e.g. "pp") to shard the stacked layer dim
    over — each device holds 1/pp of the depth and the scan streams the next
    layer's weights over ICI (memory scaling for deep models)."""

    def ns(*spec):
        if layer_axis is not None and len(spec) >= 2:
            # leaves under "layers" carry the leading stacked-L dim
            spec = (layer_axis,) + spec[1:]
        return NamedSharding(mesh, P(*spec))

    def ns_global(*spec):
        return NamedSharding(mesh, P(*spec))

    tree = {
        "embed": ns_global(None, None),   # replicated: gather is tiny, avoid a
                                          # vocab all-gather on every step
        "final_norm": ns_global(None),
        "layers": {
            "attn_norm": ns(None, None),
            "wq": ns(None, None, "tp"),
            "wk": ns(None, None, "tp"),
            "wv": ns(None, None, "tp"),
            "wo": ns(None, "tp", None),
            "mlp_norm": ns(None, None),
            "gate": ns(None, None, "tp"),
            "up": ns(None, None, "tp"),
            "down": ns(None, "tp", None),
        },
    }
    if cfg.attention_bias:
        # bias vectors follow their projection's OUTPUT sharding
        tree["layers"].update({
            "bq": ns(None, "tp"), "bk": ns(None, "tp"), "bv": ns(None, "tp"),
        })
    if not cfg.tie_embeddings:
        tree["lm_head"] = ns_global(None, "tp")  # vocab-sharded head
    if cfg.num_experts > 0:
        # expert parallelism: the expert dim shards over ep; each device computes
        # its local experts, the weighted combine is one all-reduce over ep
        tree["layers"].update({
            "router": ns(None, None, None),
            "moe_gate": ns(None, "ep", None, "tp"),
            "moe_up": ns(None, "ep", None, "tp"),
            "moe_down": ns(None, "ep", "tp", None),
        })
        for dense_key in ("gate", "up", "down"):
            tree["layers"].pop(dense_key, None)
    if cfg.has_state:
        # falcon_h1's mixer serves on one device (the state slab has no tp
        # sharding; the scheduler refuses tp > 1): every leaf replicated
        tree["layers"].update({
            "ssm_in": ns(None, None, None), "ssm_out": ns(None, None, None),
            "conv_w": ns(None, None, None), "conv_b": ns(None, None),
            "A_log": ns(None, None), "D": ns(None, None),
            "dt_bias": ns(None, None), "ssm_norm": ns(None, None),
        })
    return tree


def llama_cache_sharding(mesh: Mesh) -> NamedSharding:
    """KV cache [L, B, S, Hkv, D]: batch on dp, kv heads on tp."""
    return NamedSharding(mesh, P(None, "dp", None, "tp", None))


def _kv_head_axis(cfg: ModelConfig, mesh: Mesh):
    """Mesh axis for the KV-head dim, or None when it cannot divide (tp >
    num_kv_heads replicates the cache; query heads still shard via the
    column-parallel projections — q_per_kv grouping keeps them busy)."""
    tp = mesh.shape.get("tp", 1) if hasattr(mesh, "shape") else 1
    return "tp" if tp > 1 and cfg.num_kv_heads % tp == 0 else None


def llama_page_pool_sharding(cfg: ModelConfig, mesh: Mesh) -> NamedSharding:
    """Paged KV pool [L, num_pages, page, Hkv*D] (runtime/paged.py; the two
    minor dimensions stored merged, head-major): the merged axis shards on
    ``tp``, which hands a device its contiguous Hkv/tp heads — every device
    holds its heads' slice of EVERY page, so page allocation, the radix
    prefix tree, page-table rows and save/restore-to-host all stay
    head-count-agnostic host bookkeeping. Falls back to replication when tp
    does not divide the kv heads."""
    return NamedSharding(mesh, P(None, None, None, _kv_head_axis(cfg, mesh)))


def replicated(mesh: Mesh) -> NamedSharding:
    """The explicit destination for host-control rows under a serving mesh
    (tokens / lengths / stops / page table / sampling params): every device
    holds the full copy, so control flow never gathers. Passing this —
    rather than a bare ``jax.device_put(x)`` — is the discipline fabric-lint
    SH01 enforces in mesh-mode runtime code."""
    return NamedSharding(mesh, P())


def shard_llama_params(params: Any, cfg: ModelConfig, mesh: Mesh,
                       layer_axis: Any = None) -> Any:
    """device_put a CONCRETE llama param tree (plain or quantized) onto its
    Megatron-style NamedShardings. Quantized sub-leaves ('q'/'s'/'qe'/'se',
    runtime/quant.py layouts) derive their spec from the parent weight's via
    spec_for_quant_leaf — the same walk sharded_abstract_params uses, so the
    uploaded tree matches what the AOT compiler and the feasibility planner
    budgeted."""
    import jax

    spec_tree = llama_param_shardings(cfg, mesh, layer_axis=layer_axis)

    def walk(node, spec_node):
        if isinstance(node, dict) and any(k in node for k in ("q", "qe")):
            return {k: jax.device_put(v, NamedSharding(
                mesh, spec_for_quant_leaf(spec_node.spec, k)))
                for k, v in node.items()}
        if isinstance(node, dict):
            return {k: walk(v, spec_node[k]) for k, v in node.items()}
        return jax.device_put(node, spec_node)

    return walk(params, spec_tree)


def input_shardings(mesh: Mesh) -> dict[str, NamedSharding]:
    """Activations entering jit: token ids/positions [B, T] on dp, lengths [B]."""
    return {
        "ids": NamedSharding(mesh, P("dp", None)),
        "lengths": NamedSharding(mesh, P("dp")),
        "replicated": NamedSharding(mesh, P()),
    }


def apply_shardings(params: Any, shardings: Any):
    """device_put a param tree onto its shardings (host-side staging path)."""
    import jax

    return jax.tree.map(
        lambda a, s: jax.device_put(a, s), params, shardings,
        is_leaf=lambda x: not isinstance(x, dict),
    )


def spec_for_quant_leaf(spec: P, leaf_key: str) -> P:
    """Sharding spec for a quantized sub-leaf (runtime/quant.py layouts),
    derived from the parent weight's spec: 'q' keeps the full spec, 's'
    ([..., out], per-out-channel scales) drops the contraction axis (-2),
    'qe' keeps, 'se' ([V], per-row embed scales) keeps only the row axis."""
    if leaf_key in ("q", "qe"):
        return spec
    entries = tuple(spec)
    if leaf_key == "s":
        return P(*(entries[:-2] + entries[-1:])) if len(entries) >= 2 else spec
    if leaf_key == "se":
        return P(entries[0]) if entries else spec
    raise ValueError(f"unknown quant leaf {leaf_key!r}")


def abstract_params(cfg: ModelConfig, dtype, quantization: str = "none"):
    """ShapeDtypeStruct tree of the (optionally quantized) param tree —
    eval_shape over the SAME builders serving uses, zero allocation."""
    import jax

    from ..models import decoder_module
    from ..runtime.quant import quant_bits, quantize_llama_params

    bits = quant_bits(quantization)
    model = decoder_module(cfg)

    def build(key):
        p = model.init_params(cfg, key, dtype)
        return quantize_llama_params(p, bits) if bits else p

    return jax.eval_shape(build, jax.random.PRNGKey(0))


def sharded_abstract_params(cfg: ModelConfig, mesh, dtype,
                            quantization: str = "none",
                            layer_axis: Any = None):
    """Abstract param tree with every leaf pinned to its NamedSharding —
    quantized sub-leaves ('q'/'s'/'qe'/'se') derive their spec from the
    parent weight's via spec_for_quant_leaf. The ONE source both the AOT
    compiler (runtime/aot_tpu.py) and the feasibility planner
    (parallel/feasibility.py) consume, so they cannot drift."""
    import jax

    spec_tree = llama_param_shardings(cfg, mesh, layer_axis=layer_axis)
    abstract = abstract_params(cfg, dtype, quantization)
    sds = jax.ShapeDtypeStruct

    def walk(abs_node, spec_node):
        if isinstance(abs_node, dict) and any(
                k in abs_node for k in ("q", "qe")):
            return {k: sds(v.shape, v.dtype, sharding=NamedSharding(
                mesh, spec_for_quant_leaf(spec_node.spec, k)))
                for k, v in abs_node.items()}
        if isinstance(abs_node, dict):
            return {k: walk(v, spec_node[k]) for k, v in abs_node.items()}
        return sds(abs_node.shape, abs_node.dtype, sharding=spec_node)

    return walk(abstract, spec_tree)
