"""The llama decoder family (Llama-3, Mistral, Phi-3): functional JAX forward.

TPU-first design choices:
- **Stacked layer parameters + lax.scan** over layers: one compiled layer body
  regardless of depth (compile time O(1) in num_layers, and XLA pipelines the scan).
- **Dense KV cache [L, B, S, Hkv, D]** carried through the layer scan and updated
  with a token-sized scatter (while-loop carries alias in place, so decode writes
  T new tokens, never the cache); static S keeps every shape compile-time constant.
- **bf16 weights/activations, f32 softmax/norm statistics**, einsum contractions
  with preferred_element_type=f32 so the MXU accumulates in f32.
- Forward returns hidden states; the LM head is applied separately so prefill can
  gather the single last-token hidden state before touching the [H, 128k] head
  matmul (vocab matmul on all T prefill positions would be pure waste).

Weight names follow our own tree; runtime/weights.py maps HF safetensors names onto
it (reference requirement: model-registry PRD.md:200-224 — managed models,
safetensors format).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..ops.attention import attention_with_cache
from ..ops.norms import rms_norm
from ..ops.platform import default_interpret as _default_interpret
from ..ops.rope import apply_rope, rope_frequencies
from .configs import ModelConfig

Params = dict[str, Any]
KVCache = tuple[jnp.ndarray, jnp.ndarray]  # (k, v): [L, B, S, Hkv, D]


def _wmat(w, dtype):
    """Weight leaf → (matrix, out-channel scale or None). Quantized leaves are
    {"q": int8, "s": f32} (runtime/quant.py); the convert sits inside the dot
    operand so XLA fuses it and streams int8 from HBM."""
    if isinstance(w, dict):
        return w["q"].astype(dtype), w["s"]
    return w, None


def _scaled(y: jnp.ndarray, scale) -> jnp.ndarray:
    return y if scale is None else y * scale


def poly_norm(z: jnp.ndarray, coef: jnp.ndarray, bias: jnp.ndarray,
              cfg: ModelConfig) -> jnp.ndarray:
    """PolyNorm (PolyCom, arXiv:2411.03884) over the rows of ``z`` [..., I],
    f32: ``s (a1 z/rms(z) + a2 z²/rms(z²) + a3 z³/rms(z³)) + clamp(b)``, each
    rms over the WHOLE row (an MLP's, or one expert's), ``coef`` [3] and
    ``bias`` [] the unit's own, ``s`` and the clamp the configuration's."""
    def normed(p):
        return p * jax.lax.rsqrt(
            jnp.mean(jnp.square(p), axis=-1, keepdims=True) + cfg.rms_norm_eps)

    with jax.named_scope("poly_norm"):
        z = z.astype(jnp.float32)
        z2 = z * z
        y = (coef[0] * normed(z) + coef[1] * normed(z2)
             + coef[2] * normed(z2 * z))
        clamp = cfg.polynorm_bias_clamp
        return cfg.polynorm_output_scale * y + jnp.clip(bias, -clamp, clamp)


def _act(x: jnp.ndarray, cfg: ModelConfig, poly=None) -> jnp.ndarray:
    """MLP activation: SiLU (llama family), tanh-approx GeLU (gemma), the
    squared ReLU (nemotron_h's two-matrix MLP) or PolyNorm (motif; ``poly``:
    the unit's ``(coef, bias)``), the one that reduces over a row. Unknown
    values are rejected at config time (ModelConfig.__post_init__)."""
    if cfg.hidden_act == "poly_norm":
        return poly_norm(x, *poly, cfg)
    if cfg.hidden_act in ("gelu", "gelu_pytorch_tanh"):
        return jax.nn.gelu(x, approximate=True)
    if cfg.hidden_act == "relu2":
        return jnp.square(jax.nn.relu(x))
    return jax.nn.silu(x)


def _embed_scale(h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """gemma multiplies embeddings by sqrt(hidden_size) (in the activation
    dtype, matching the reference checkpoints' bf16 rounding)."""
    if cfg.embedding_multiplier != 1.0:
        return h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
    return h


def embed_lookup(embed, ids: jnp.ndarray, dtype) -> jnp.ndarray:
    if isinstance(embed, dict):  # {"qe","se"}: int8 rows with per-row scales
        rows = embed["qe"][ids].astype(jnp.float32) * embed["se"][ids][..., None]
        return rows.astype(dtype)
    return embed[ids]


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init parameters at model shape (bench/synthetic-weight path)."""
    H, I, V, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_layers
    Dq, Dkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    k = iter(jax.random.split(key, 20))

    def w(rng, *shape):
        # sample directly in the target dtype: a 70B-scale f32 intermediate would
        # double peak HBM during init for no benefit at synthetic-weight quality
        scale = jnp.asarray(1.0 / (shape[-2] if len(shape) > 1 else shape[-1]) ** 0.5, dtype)
        return jax.random.normal(rng, shape, dtype) * scale

    layers: dict[str, jnp.ndarray] = {
        "attn_norm": jnp.ones((L, H), dtype),
        "wq": w(next(k), L, H, Dq),
        "wk": w(next(k), L, H, Dkv),
        "wv": w(next(k), L, H, Dkv),
        "wo": w(next(k), L, Dq, H),
        "mlp_norm": jnp.ones((L, H), dtype),
    }
    if cfg.attention_bias:  # Qwen2-family: bias on q/k/v projections only
        layers.update({
            "bq": w(next(k), L, Dq), "bk": w(next(k), L, Dkv),
            "bv": w(next(k), L, Dkv),
        })
    if cfg.qk_norm:
        layers.update({"q_norm": jnp.ones((L, cfg.head_dim), dtype),
                       "k_norm": jnp.ones((L, cfg.head_dim), dtype)})
    if cfg.num_experts > 0:
        E = cfg.num_experts
        router = w(next(k), L, H, E)
        layers.update({
            "router": router.astype(jnp.float32) if cfg.router_float32
            else router,
            "moe_gate": w(next(k), L, E, H, I),
            "moe_up": w(next(k), L, E, H, I),
            "moe_down": w(next(k), L, E, I, H),
        })
    else:
        layers.update({
            "gate": w(next(k), L, H, I),
            "up": w(next(k), L, H, I),
            "down": w(next(k), L, I, H),
        })
    params: Params = {
        "embed": w(next(k), V, H),
        "final_norm": jnp.ones((H,), dtype),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = w(next(k), H, V)
    return params


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16) -> KVCache:
    shape = (cfg.kv_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _moe_mlp_dense(x: jnp.ndarray, lp: dict, cfg: ModelConfig) -> jnp.ndarray:
    """Reference MoE formulation: every expert computes, top-k combine mask.
    E× the FLOPs of the routed path — kept as the semantics oracle the grouped
    kernel is parity-tested against (tests/test_moe.py)."""
    E, K = cfg.num_experts, cfg.experts_per_token
    router_logits = jnp.einsum("bth,he->bte", x, lp["router"],
                               preferred_element_type=jnp.float32)
    # top-k gate: softmax over the selected experts only (Mixtral semantics)
    top_vals, _ = jax.lax.top_k(router_logits, K)  # [B, T, K]
    threshold = top_vals[..., K - 1:K]
    mask = router_logits >= threshold
    masked_logits = jnp.where(mask, router_logits, -1e30)
    weights = jax.nn.softmax(masked_logits, axis=-1)  # [B, T, E], zeros off-topk

    g_m, g_s = _wmat(lp["moe_gate"], x.dtype)
    u_m, u_s = _wmat(lp["moe_up"], x.dtype)
    d_m, d_s = _wmat(lp["moe_down"], x.dtype)
    gate = _scaled(jnp.einsum("bth,ehi->btei", x, g_m,
                   preferred_element_type=jnp.float32), g_s)
    up = _scaled(jnp.einsum("bth,ehi->btei", x, u_m,
                 preferred_element_type=jnp.float32), u_s)
    act = (_act(gate, cfg) * up).astype(x.dtype)
    expert_out = _scaled(jnp.einsum("btei,eih->bteh", act, d_m,
                         preferred_element_type=jnp.float32), d_s)
    return jnp.einsum("bteh,bte->bth", expert_out, weights.astype(jnp.float32))


#: the expert matrices of a layers tree: handed to the expert layer as the
#: tree stacks them, ``[L, E, ...]``, with the layer's index, never a layer of
#: them (ops/grouped_matmul.py says why). **An expert's form is which of
#: them the tree holds**: all three is the gated MLP ``(act(x W_gate) ⊙ x
#: W_up) W_down``; without ``moe_gate`` it is the two-matrix MLP ``act(x
#: W_up) W_down`` (nemotron_h, ``act`` the squared ReLU). ``moe_up`` is
#: ``[L, E, rows' width, I]`` and ``moe_down`` ``[L, E, I, rows' width]``,
#: the rows' width the hidden size or a latent narrower than it
MOE_LEAVES = ("moe_gate", "moe_up", "moe_down")


def split_moe(layers: dict) -> tuple[dict, dict | None]:
    """(what a ``lax.scan`` over the layers slices, the stacked expert
    matrices it must not slice); the second is None for a dense model."""
    if "moe_up" not in layers:
        return layers, None
    return ({k: v for k, v in layers.items() if k not in MOE_LEAVES},
            {k: layers[k] for k in MOE_LEAVES if k in layers})


def moe_route(flat: jnp.ndarray, router: jnp.ndarray, k: int, *,
              sigmoid: bool = False, bias: jnp.ndarray | None = None,
              scale: float = 1.0):
    """Top-k routing of ``flat`` [N, H]: (experts [N, k] int32, gates [N, k]
    f32). The gate is the softmax over the chosen experts' scores, which is
    also the softmax over every expert renormalised over the chosen
    (``norm_topk_prob``): the two are one number, times ``scale`` where the
    model gives one (laguna's ``moe_routed_scaling_factor``). A float32 router is
    multiplied in float32 at full precision, since a score decides WHICH
    experts run, not only how much.

    ``sigmoid`` (the DeepSeek-V3 router): the score is ``sigmoid(x W_g)``,
    the experts chosen are the ``k`` largest of score + ``bias`` [E] (the
    selection bias only chooses), and the gate is the score over the chosen
    scores' sum, times ``scale`` (``routed_scaling_factor``)."""
    if router.dtype == jnp.float32:
        logits = jnp.einsum("nh,he->ne", flat.astype(jnp.float32), router,
                            precision=jax.lax.Precision.HIGHEST)
    else:
        logits = jnp.einsum("nh,he->ne", flat, router,
                            preferred_element_type=jnp.float32)
    if not sigmoid:
        top_vals, top_idx = jax.lax.top_k(logits, k)
        gates = jax.nn.softmax(top_vals, axis=-1)
        return top_idx.astype(jnp.int32), (
            gates if scale == 1.0 else gates * scale)
    score = jax.nn.sigmoid(logits)
    _, top_idx = jax.lax.top_k(
        score if bias is None else score + bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(score, top_idx, axis=1)
    gates = chosen / jnp.sum(chosen, axis=1, keepdims=True) * scale
    return top_idx.astype(jnp.int32), gates


def moe_capacity(n_assign: int, cfg: ModelConfig) -> int:
    """Rows of the sorted assignments that :func:`moe_experts` computes where
    a chip holds a SHARE of the routed experts: whole ``ROW_TILE``s, four
    times what uniform routing sends the held experts (``n_assign x
    experts_local / num_experts``), at most all ``n_assign``. A function of
    shapes alone; a chip that holds every expert computes every row."""
    from ..ops.grouped_matmul import ROW_TILE

    if not cfg.experts_held:
        return n_assign
    tiles = -(-4 * n_assign * cfg.experts_local // (cfg.num_experts * ROW_TILE))
    return min(tiles * ROW_TILE, n_assign)


def moe_experts(flat: jnp.ndarray, top_idx: jnp.ndarray, gates: jnp.ndarray,
                moe: dict, cfg: ModelConfig, layer, poly=None) -> jnp.ndarray:
    """The chosen experts' MLPs over the rows ``flat`` [N, W], summed by
    gate: [N, W] f32. ``moe`` holds the STACKED expert matrices, in the form
    ``MOE_LEAVES`` describes (gated three-matrix, or two-matrix where the
    tree has no ``moe_gate``), and ``layer`` picks the layer inside the
    grouped matmul; ``W`` is whatever width the matrices take (the hidden
    size, or nemotron_h's latent). Dropless: the ``N*K`` assignments are
    sorted by expert, each expert multiplies the rows that chose it, however
    many, and the results go back to token order. ``poly``: the layer's
    PolyNorm ``(coef, bias)`` where that is the activation; a row of the
    sorted list is one token at one expert, so the activation's reduction
    runs over that expert's whole width.

    **A chip's share** (``cfg.experts_held``): ``top_idx`` ranges over all
    ``cfg.num_experts`` but ``moe`` holds experts ``cfg.expert_offset ..``
    only. The assignments to experts held elsewhere sort to the end, past
    every group, and add nothing: the result is the part of the layer that
    the held experts give. The gather, the matmuls' buffers, the gate
    multiply and the scatter-add run over the first ``moe_capacity`` rows of
    the sorted list, the COMPACTED list of the assignments held here, where
    those are all of them (``lax.cond`` on the held count, on the device);
    a step that holds more takes every row. No assignment is dropped either
    way, and the held rows are the same rows in the same tiles."""
    from ..ops.grouped_matmul import grouped_matmul

    E = cfg.experts_local
    N, K = top_idx.shape
    interpret = _default_interpret()
    expert_of = top_idx.reshape(N * K)
    if cfg.experts_held:
        expert_of = expert_of - cfg.expert_offset
        expert_of = jnp.where((expert_of >= 0) & (expert_of < E), expert_of, E)
    order = jnp.argsort(expert_of)            # stable: token order in a group
    sizes = jnp.bincount(expert_of, length=E).astype(jnp.int32)

    def gmm(x, w):
        m, s = (w["q"], w["s"]) if isinstance(w, dict) else (w, None)
        return grouped_matmul(x, m, s, sizes, layer, interpret=interpret)

    def over(order):
        """The layer over these rows of the sorted list (all that are held
        lie inside them)."""
        rows = flat[order // K]                                # [rows, W]
        if "moe_gate" in moe:
            gate = gmm(rows, moe["moe_gate"])
            up = gmm(rows, moe["moe_up"])
            act = _act(gate, cfg, poly) * up
        else:
            act = _act(gmm(rows, moe["moe_up"]), cfg, poly)
        act = act.astype(flat.dtype)
        out = gmm(act, moe["moe_down"]) * gates.reshape(N * K)[order][:, None]
        if cfg.experts_held:   # rows past the groups were never written
            held = jnp.arange(order.shape[0], dtype=jnp.int32) < jnp.sum(sizes)
            out = jnp.where(held[:, None], out, 0.0)
        return jnp.zeros((N, flat.shape[1]), jnp.float32
                         ).at[order // K].add(out)

    capacity = moe_capacity(N * K, cfg)
    if capacity == N * K:
        return over(order)
    return jax.lax.cond(jnp.sum(sizes) <= capacity,
                        lambda: over(order[:capacity]), lambda: over(order))


def _held_sizes(top_idx: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The assignments ``[N, K]`` that fell on each expert this chip holds
    ``[experts_local]``."""
    E = cfg.experts_local
    held = top_idx.reshape(-1) - cfg.expert_offset
    return jnp.bincount(jnp.where((held >= 0) & (held < E), held, E),
                        length=E + 1)[:-1]


def moe_item_rows(top_idx: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The rows ONE grouped matmul of :func:`moe_experts` multiplies for the
    experts chosen ``[N, K]``: its real work items times the row tile it
    picked for the rows it ran over (``ops/grouped_matmul.py: item_rows``;
    the compacted list where a share's assignments fitted it). Over the
    experts touched it says how many rows the MXU is fed for an expert,
    beside the rows that are real (int32 scalar)."""
    from ..ops.grouped_matmul import item_rows

    sizes, n = _held_sizes(top_idx, cfg), top_idx.size
    capacity = moe_capacity(n, cfg)
    if capacity == n:
        return item_rows(sizes, n)
    return jnp.where(jnp.sum(sizes) <= capacity, item_rows(sizes, capacity),
                     item_rows(sizes, n))


def moe_share_counts(top_idx: jnp.ndarray, cfg: ModelConfig):
    """What one expert layer counts of the experts chosen ``[N, K]``: the
    assignments routed, those that fell on experts this chip holds, and the
    held experts with at least one (three int32 scalars)."""
    sizes = _held_sizes(top_idx, cfg)
    return (jnp.asarray(top_idx.size, jnp.int32),
            jnp.sum(sizes).astype(jnp.int32),
            jnp.sum(sizes > 0).astype(jnp.int32))


def _moe_mlp(x: jnp.ndarray, lp: dict, cfg: ModelConfig,
             moe: dict | None = None, layer=None) -> jnp.ndarray:
    """Routed MoE MLP over ``x`` [B, T, H] -> f32: :func:`moe_route` then
    :func:`moe_experts`, for every MoE configuration. ``moe``/``layer``: the
    stacked expert matrices and the layer (what the forwards pass); without
    them ``lp`` holds one layer's, which is then a stack of one."""
    B, T, H = x.shape
    flat = x.reshape(B * T, H)
    if moe is None:
        moe = jax.tree.map(lambda a: a[None], {k: lp[k] for k in MOE_LEAVES})
        layer = 0
    top_idx, gates = moe_route(flat, lp["router"], cfg.experts_per_token)
    return moe_experts(flat, top_idx, gates, moe, cfg, layer).reshape(B, T, H)


def _qkv_proj(lp: dict, x: jnp.ndarray, cfg: ModelConfig,
              positions: jnp.ndarray, cos_t, sin_t, heads: int | None = None):
    """Shared q/k/v projection + reshape + rope for one layer (any T).
    ``cfg.key_multiplier`` (falcon_h1) scales k in f32, before the cast; at
    1.0 nothing is traced for it. ``cfg.rotary`` False (granite_hybrid): no
    rotation, q and k go on as projected. ``heads``: the query heads of
    this layer's kind where a model has two (laguna); ``cfg.num_heads``
    otherwise."""
    B, T = x.shape[0], x.shape[1]
    Hq, Hkv, D = heads or cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    wq_m, wq_s = _wmat(lp["wq"], x.dtype)
    wk_m, wk_s = _wmat(lp["wk"], x.dtype)
    wv_m, wv_s = _wmat(lp["wv"], x.dtype)
    q = _scaled(jnp.einsum("bth,hd->btd", x, wq_m,
                preferred_element_type=jnp.float32), wq_s)
    kproj = _scaled(jnp.einsum("bth,hd->btd", x, wk_m,
                    preferred_element_type=jnp.float32), wk_s)
    vproj = _scaled(jnp.einsum("bth,hd->btd", x, wv_m,
                    preferred_element_type=jnp.float32), wv_s)
    if cfg.attention_bias:  # Qwen2-family q/k/v bias (biases stay unquantized)
        q = q + lp["bq"]
        kproj = kproj + lp["bk"]
        vproj = vproj + lp["bv"]
    if cfg.key_multiplier != 1.0:
        kproj = kproj * cfg.key_multiplier
    q = q.astype(x.dtype)
    kproj = kproj.astype(x.dtype)
    vproj = vproj.astype(x.dtype)
    q = q.reshape(B, T, Hq, D)
    kproj = kproj.reshape(B, T, Hkv, D)
    vproj = vproj.reshape(B, T, Hkv, D)
    if cfg.qk_norm:     # per head, before the rotation (the Qwen3 block)
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        kproj = rms_norm(kproj, lp["k_norm"], cfg.rms_norm_eps)
    if cfg.rotary:
        q = apply_rope(q, positions, cos_t, sin_t)
        kproj = apply_rope(kproj, positions, cos_t, sin_t)
    return q, kproj, vproj


def _attn_proj(lp: dict, attn_flat: jnp.ndarray, dtype,
               multiplier: float = 1.0) -> jnp.ndarray:
    """The attention branch: the output projection (weights read as
    ``dtype``), f32, before anything is added to it (a block that norms the
    branch first reads it here)."""
    wo_m, wo_s = _wmat(lp["wo"], dtype)
    out = _scaled(jnp.einsum("btd,dh->bth", attn_flat, wo_m,
                  preferred_element_type=jnp.float32), wo_s)
    if multiplier != 1.0:
        out = out * multiplier
    return out


def _attn_out(lp: dict, h: jnp.ndarray, attn_flat: jnp.ndarray,
              multiplier: float = 1.0) -> jnp.ndarray:
    """Output projection + residual; ``multiplier`` (falcon_h1's
    attention_out_multiplier) scales the projection in f32."""
    return h + _attn_proj(lp, attn_flat, h.dtype, multiplier).astype(h.dtype)


def _mlp_residual(lp: dict, h: jnp.ndarray, cfg: ModelConfig,
                  moe: dict | None = None, layer=None) -> jnp.ndarray:
    """Post-attention norm + (MoE or dense) MLP + residual. ``moe`` and
    ``layer``: see :func:`_moe_mlp`."""
    x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps, cfg.norm_weight_offset)
    if cfg.num_experts > 0:
        return h + _moe_mlp(x, lp, cfg, moe, layer).astype(h.dtype)
    return h + _dense_mlp(lp, x, cfg).astype(h.dtype)


def _dense_mlp(lp: dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The MLP branch over normed rows ``x`` [B, T, H]: the gated MLP of
    three matrices, f32, before anything is added to it."""
    g_m, g_s = _wmat(lp["gate"], x.dtype)
    u_m, u_s = _wmat(lp["up"], x.dtype)
    d_m, d_s = _wmat(lp["down"], x.dtype)
    gate = _scaled(jnp.einsum("bth,hi->bti", x, g_m,
                   preferred_element_type=jnp.float32), g_s)
    up = _scaled(jnp.einsum("bth,hi->bti", x, u_m,
                 preferred_element_type=jnp.float32), u_s)
    gate_mult, down_mult = cfg.mlp_multipliers      # falcon_h1; (1, 1) else
    if gate_mult != 1.0:
        gate = gate * gate_mult
    act = (_act(gate, cfg) * up).astype(x.dtype)
    down = _scaled(jnp.einsum("bti,ih->bth", act, d_m,
                   preferred_element_type=jnp.float32), d_s)
    if down_mult != 1.0:
        down = down * down_mult
    return down


def forward(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [B, T] int32
    positions: jnp.ndarray,    # [B, T] int32 absolute positions
    cache: KVCache,
    cache_start: jnp.ndarray,  # [B] int32 — write offset (current valid length)
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    use_flash: bool = False,
) -> tuple[jnp.ndarray, KVCache]:
    """One forward pass (prefill T>1 or decode T=1). Returns (hidden [B,T,H], cache).

    ``use_flash`` routes attention through the Pallas flash kernel — ONLY valid
    for fresh-cache prefill (cache_start all zero): the kernel attends within
    the new tokens, not over cache history.
    """
    cos_t, sin_t = rope_tables
    B, T = input_ids.shape
    Hq, D = cfg.num_heads, cfg.head_dim

    h = _embed_scale(embed_lookup(params["embed"], input_ids,
                     params["final_norm"].dtype), cfg)  # [B, T, H] gather
    kv_len_after = cache_start + T  # valid cache length after this step's insert

    # The cache rides the scan CARRY (not ys): XLA aliases while-loop carries
    # in place, so each layer writes only its [B, T] new tokens via scatter —
    # the ys formulation re-materialized the full layer cache every step,
    # which at decode (T=1) cost a cache-sized HBM write per token.
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]            # [B, 1]
    t_idx = cache_start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]

    def layer_body(carry, xs):
        h, k_cache, v_cache = carry
        lp, layer = xs
        x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps, cfg.norm_weight_offset)
        q, kproj, vproj = _qkv_proj(lp, x, cfg, positions, cos_t, sin_t)

        k_cache = k_cache.at[layer, b_idx, t_idx].set(
            kproj.astype(k_cache.dtype))
        v_cache = v_cache.at[layer, b_idx, t_idx].set(
            vproj.astype(v_cache.dtype))

        if use_flash:
            from ..ops.flash_attention import flash_self_attention

            attn = flash_self_attention(
                q, kproj, vproj, kv_len_after,
                interpret=_default_interpret(),
                sliding_window=cfg.sliding_window,
            )
        else:
            attn = attention_with_cache(
                q, k_cache[layer], v_cache[layer], positions, kv_len_after,
                sliding_window=cfg.sliding_window,
            )
        h = _attn_out(lp, h, attn.reshape(B, T, Hq * D))
        h = _mlp_residual(lp, h, cfg, moe, layer)
        return (h, k_cache, v_cache), None

    k_cache, v_cache = cache
    scanned, moe = split_moe(params["layers"])
    (h, k_cache, v_cache), _ = jax.lax.scan(
        layer_body, (h, k_cache, v_cache),
        (scanned, jnp.arange(cfg.num_layers, dtype=jnp.int32)),
    )
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps, cfg.norm_weight_offset)
    return h, (k_cache, v_cache)


#: (k, v): [L, N, page, Hkv*D] — the two minor dimensions stored MERGED,
#: head-major, which is the block the paged kernels read (on a tiled TPU
#: layout merging them later is a copy of the pool, not a view)
PagedPools = tuple[jnp.ndarray, jnp.ndarray]


def _merged_pools(pools: PagedPools) -> tuple[PagedPools, tuple | None]:
    """What ``forward_paged_*`` run on, and the shape to hand back. A 5-D
    ``[L, N, page, Hkv, D]`` pool (benchmark/adapters/llama.py builds them) is
    merged once here, outside the layer scan, and un-merged on return by
    :func:`_restore_pools`: one copy a call, the same scatter and kernels.
    ROADMAP D12 deletes this entry once that caller builds merged pools."""
    if pools[0].ndim == 4:
        return pools, None
    shape = pools[0].shape
    return tuple(p.reshape(*shape[:3], -1) for p in pools), shape


def _restore_pools(pools: PagedPools, shape: tuple | None) -> PagedPools:
    return pools if shape is None else tuple(p.reshape(shape) for p in pools)


def _shard_mapped_attn(mesh, kernel_fn, q_spec, tail_specs):
    """Wrap a paged-attention kernel call in shard_map over the mesh's tp
    axis (the stacked pools' merged head axis sharded: head-major order
    makes a device's slice its contiguous Hkv/tp heads; ``tail_specs`` cover
    the replicated control operands — page table, lengths/hist/q_lens — and
    the layer index follows them). Mosaic kernels cannot be
    automatically partitioned by GSPMD — each device runs the kernel over
    ITS head slice, which is exactly the head-axis sharding the Ragged
    Paged Attention paper names. Head-major GQA grouping survives the
    split because consecutive q heads map to consecutive kv heads
    (requires num_kv_heads % tp == 0 — the engine gates on it).
    check_vma=False: pallas_call defeats the replication checker. The ONE
    wrapping implementation both paged forwards share, so the specs cannot
    drift."""
    from jax.sharding import PartitionSpec as P

    kv_spec = P(None, None, None, "tp")
    return jax.shard_map(
        kernel_fn, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec) + tuple(tail_specs) + (P(),),
        out_specs=q_spec, check_vma=False)


class DecodeGroup(NamedTuple):
    """The decode rows of a mixed step: every slot's one token, beside the
    lane's chunk (``forward_paged_mixed``). Row ``b`` is slot ``b``. A model
    that generates by blocks has ``tokens`` [B, W], each slot's open block,
    at positions ``lengths .. lengths + W - 1``."""
    tokens: jnp.ndarray    # [B] int32 each slot's last token
    lengths: jnp.ndarray   # [B] int32 valid length BEFORE this token
    run: jnp.ndarray       # [B] bool; False rows write to scratch, keep state


def _decode_attend(cfg: ModelConfig, interpret: bool, mesh):
    """``attend(q [B, Hq, D], k_pool, v_pool, work, layer)`` over the stacked
    pools, ``work`` the step's :func:`decode_work`. The kernel takes the
    pools whole and copies a layer's pages itself: a ``k_pool[layer]`` in
    front of it would materialise 1/L of the pool."""
    from ..ops.paged_attention import paged_decode_attention

    def attend(qq, kk, vv, work, ly):
        return paged_decode_attention(
            qq, kk, vv, *work, ly, interpret=interpret,
            sliding_window=cfg.sliding_window,
            scale=cfg.attention_multiplier or None)

    if mesh is None:
        return attend
    from jax.sharding import PartitionSpec as P

    return _shard_mapped_attn(
        mesh, attend, P(None, "tp", None), ((P(None, None), P(None)),))


def decode_page_group(cfg: ModelConfig, page_size: int, n_pages: int,
                      itemsize: int, window: int | None, tp: int = 1) -> int:
    """Pages a trip of ``cfg``'s decode kernel takes, over a table of
    ``n_pages`` slots a row whose pool holds ``itemsize`` bytes a number,
    in the layers behind ``window`` (None: those that attend over
    everything), a shard of ``tp``'s: the kernel's own rule, by the shapes
    it sees. What the scheduler counts a row's groups of pages by."""
    if cfg.is_latent:
        from ..ops.mla_attention import trip_pages

        return trip_pages(page_size, window)
    from ..ops.paged_attention import decode_trip_pages

    return decode_trip_pages(
        page_size, cfg.num_kv_heads // tp * cfg.head_dim, itemsize, n_pages,
        window)


def decode_work(page_table, lengths):
    """What the decode kernel walks in one step: the page table and
    ``lengths`` [B], which counts the tokens the step itself writes. The
    kernel walks a row's span itself, so this is the same for every layer
    and every window."""
    return page_table, lengths


def _ragged_attend(cfg: ModelConfig, interpret: bool, mesh):
    """``attend(q [R, Qc, Hq, D], k_pool, v_pool, page_table [R, Pmax], hist,
    q_lens, layer)``: the ragged kernel, as :func:`_decode_attend`; under
    the block mask where the model generates by blocks."""
    from ..ops.paged_attention import ragged_paged_attention

    def attend(qq, kk, vv, pt, hh, ql, ly):
        return ragged_paged_attention(
            qq, kk, vv, pt, hh, ql, ly, interpret=interpret,
            sliding_window=cfg.sliding_window, block=cfg.block_length,
            scale=cfg.attention_multiplier or None)

    if mesh is None:
        return attend
    from jax.sharding import PartitionSpec as P

    return _shard_mapped_attn(
        mesh, attend, P(None, None, "tp", None),
        (P(None, None), P(None), P(None)))


def _decode_targets(page_table, lengths, write_mask, page_size: int,
                    width: int | None = None):
    """Where each slot's one new token is written: (page id, offset), [B]
    each; rows with ``write_mask`` False target scratch page 0. ``width``:
    a block of that many tokens a slot, at ``lengths ..``; [B, width]."""
    if width is None:
        pid = jnp.take_along_axis(
            page_table, (lengths // page_size)[:, None], axis=1)[:, 0]
        off = lengths % page_size
    else:
        pos = lengths[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
        pid = jnp.take_along_axis(page_table, pos // page_size, axis=1)
        off = pos % page_size
        if write_mask is not None:
            write_mask = write_mask[:, None]
    if write_mask is not None:
        pid = jnp.where(write_mask, pid, 0)
        off = jnp.where(write_mask, off, 0)
    return pid, off


def forward_paged_decode(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [B, 1] int32 — one token per slot
    pools: PagedPools,
    page_table: jnp.ndarray,   # [B, Pmax] int32 physical page ids per slot
    lengths: jnp.ndarray,      # [B] int32 current valid length (BEFORE this token)
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,  # [B] bool; False rows → scratch
    mesh=None,
) -> tuple[jnp.ndarray, PagedPools]:
    """One decode step over the paged KV pool. Returns (hidden [B,1,H], pools).

    Each slot's new k/v token lands at (page_table[b, len//page], len%page);
    attention runs through the ragged paged kernel, so HBM reads scale with the
    tokens present, not n_slots × max_seq. Pages may be shared across slots
    (prefix cache) — they are only ever read here; writes target each slot's
    private tail page (admission guarantees the tail page is unshared).
    ``write_mask`` (device-side termination): rows marked False — frozen by
    the decode program's finished mask — redirect their k/v scatter to
    scratch page 0 instead of re-writing position ``lengths`` of their chain.
    ``mesh`` (tensor-parallel serving, kv-head-sharded pools): the attention
    kernel runs under shard_map over the tp axis — required wherever the
    kernel compiles as a real Mosaic call (GSPMD cannot auto-partition it);
    on interpret backends it is an equivalent, bit-identical partitioning.
    """
    if interpret is None:
        interpret = _default_interpret()
    cos_t, sin_t = rope_tables
    B = input_ids.shape[0]
    Hq, D = cfg.num_heads, cfg.head_dim
    pools, caller_shape = _merged_pools(pools)
    page_size = pools[0].shape[2]
    positions = lengths[:, None]
    pid, off = _decode_targets(page_table, lengths, write_mask, page_size)
    attend = _decode_attend(cfg, interpret, mesh)
    work = decode_work(page_table, lengths + 1)

    h = _embed_scale(embed_lookup(params["embed"], input_ids, params["final_norm"].dtype), cfg)

    # pools ride the scan carry (in-place via while-loop aliasing) — the ys
    # form would re-materialize the WHOLE pool per layer per step, and the
    # pool is n_pages-sized, far larger than one request's cache
    def layer_body(carry, xs):
        h, k_pool, v_pool = carry
        lp, layer = xs
        x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps, cfg.norm_weight_offset)
        q, kproj, vproj = _qkv_proj(lp, x, cfg, positions, cos_t, sin_t)

        # scatter the new token into each slot's tail page (inactive slots all
        # target scratch page 0 — duplicate writes there are harmless): B rows
        # of Hkv*D, merged like the pool
        k_pool = k_pool.at[layer, pid, off].set(
            kproj.reshape(B, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[layer, pid, off].set(
            vproj.reshape(B, -1).astype(v_pool.dtype))
        attn = attend(q[:, 0], k_pool, v_pool, work, layer)
        h = _attn_out(lp, h, attn.reshape(B, 1, Hq * D))
        h = _mlp_residual(lp, h, cfg, moe, layer)
        return (h, k_pool, v_pool), None

    k_pool, v_pool = pools
    scanned, moe = split_moe(params["layers"])
    (h, k_pool, v_pool), _ = jax.lax.scan(
        layer_body, (h, k_pool, v_pool),
        (scanned, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps, cfg.norm_weight_offset)
    return h, _restore_pools((k_pool, v_pool), caller_shape)


class MixedLayout(NamedTuple):
    """The token layout of one mixed step: the decode group's ``n_dec``
    tokens (0 where there is no group) ahead of the lane's ``R x Qc``, as one
    row of ``n_dec + R*Qc`` tokens, so that every matmul of a layer runs once
    over the tokens the step has and reads its weight once."""
    n_dec: int
    lanes: tuple[int, int]     # (R, Qc)
    ids: jnp.ndarray           # [1, N] int32
    positions: jnp.ndarray     # [1, N] int32
    pid: jnp.ndarray           # [N] page each token's k/v is written to
    off: jnp.ndarray           # [N] its offset in that page
    lane_table: jnp.ndarray    # [R, Pmax] the lanes' rows of the page table
    lane_valid: jnp.ndarray    # [R] bool: the lane has tokens and may write
    work: Any                  # the decode group's :func:`decode_work`
    #                            (None: no group)


def mixed_layout(input_ids, page_table, hist, q_lens, write_mask, rows,
                 decode: DecodeGroup | None, pool) -> MixedLayout:
    """Lay a mixed step's tokens out (see :func:`forward_paged_mixed`);
    ``pool`` is the cache the attention kernels read, ``page_table`` its
    page group's."""
    page_size = pool.shape[2]
    R, Qc = input_ids.shape
    lane_table = page_table if rows is None else page_table[rows]
    offs = jnp.arange(Qc, dtype=jnp.int32)[None, :]            # [1, Qc]
    valid = offs < q_lens[:, None]                             # [R, Qc]
    if write_mask is not None:
        valid = valid & write_mask[:, None]
    positions = jnp.where(valid, hist[:, None] + offs, 0)
    # per-token write targets; padding targets scratch page 0 (harmless)
    pid = jnp.where(
        valid,
        jnp.take_along_axis(lane_table, positions // page_size, axis=1), 0)
    off = jnp.where(valid, positions % page_size, 0)
    ids, positions = input_ids.reshape(-1), positions.reshape(-1)
    pid, off = pid.reshape(-1), off.reshape(-1)
    n_dec, work = 0, None
    if decode is not None:
        n_dec = decode.tokens.size
        width = decode.tokens.shape[1] if decode.tokens.ndim == 2 else None
        work = decode_work(page_table, decode.lengths + (width or 1))
        d_pid, d_off = _decode_targets(page_table, decode.lengths, decode.run,
                                       page_size, width)
        d_pos = decode.lengths if width is None else (
            decode.lengths[:, None] + jnp.arange(width, dtype=jnp.int32))
        ids = jnp.concatenate([decode.tokens.reshape(-1), ids])
        positions = jnp.concatenate([d_pos.reshape(-1), positions])
        pid = jnp.concatenate([d_pid.reshape(-1), pid])
        off = jnp.concatenate([d_off.reshape(-1), off])
    return MixedLayout(n_dec, (R, Qc), ids[None], positions[None], pid, off,
                       lane_table, valid[:, 0], work)


def mixed_attention(lay: MixedLayout, q, k_pool, v_pool, hist, q_lens, layer,
                    lane_attend, decode_attend) -> jnp.ndarray:
    """The one place a mixed step's token row is split: ``q`` [1, N, Hq, D]
    → attention output [1, N, Hq*D]. The lane goes through the ragged kernel
    on its own rows of the page table, the decode group through the decode
    kernel over the layout's table and lengths, each after the step's k/v is
    in the pool."""
    R, Qc = lay.lanes
    nd = lay.n_dec
    lane = lane_attend(q[0, nd:].reshape(R, Qc, *q.shape[2:]), k_pool, v_pool,
                       lay.lane_table, hist, q_lens, layer)
    lane = lane.reshape(1, R * Qc, -1)
    if not nd:
        return lane
    dec = decode_attend(q[0, :nd], k_pool, v_pool, lay.work, layer)
    return jnp.concatenate([dec.reshape(1, nd, -1), lane], axis=1)


def mixed_hidden_out(lay: MixedLayout, h: jnp.ndarray, q_lens, rows):
    """What a mixed step hands back of ``h`` [1, N, H]: the lanes' hidden
    ``[R, Qc, H]`` where there is no decode group; with one, the ``[B, H]``
    rows the head needs — a slot's decode row, or its lane's last position
    where a lane with tokens names it."""
    R, Qc = lay.lanes
    nd = lay.n_dec
    lane = h[0, nd:].reshape(R, Qc, -1)
    if not nd:
        return lane
    dec = h[0, :nd]
    if rows is None:
        rows = jnp.arange(R, dtype=jnp.int32)
    last = gather_last_hidden(lane, q_lens)
    return dec.at[rows].set(
        jnp.where((q_lens > 0)[:, None], last, dec[rows]))


def forward_paged_mixed(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [R, Qc] int32 — per-lane query span, padded
    pools: PagedPools,
    page_table: jnp.ndarray,   # [B, Pmax] int32 physical page ids per slot
    hist: jnp.ndarray,         # [R] int32 kv tokens BEFORE each lane's span
    q_lens: jnp.ndarray,       # [R] int32 span length (0 = idle lane)
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,  # [R] bool; False lanes → scratch
    mesh=None,
    *,
    rows: jnp.ndarray | None = None,        # [R] int32 each lane's slot
    decode: DecodeGroup | None = None,
) -> tuple[jnp.ndarray, PagedPools]:
    """One ragged mixed-batch step over the paged KV pool, computed over the
    tokens it has. ``mesh``: see :func:`forward_paged_decode`.

    **Lanes.** Lane ``r`` carries a span of ``q_lens[r]`` tokens of slot
    ``rows[r]`` (distinct slots); ``rows=None`` is the all-rows call, lane
    ``r`` = slot ``r`` (``R = B``): what a caller with ragged spans on many
    rows wants (speculative spans, a reference comparison). The span's tokens
    land at absolute positions hist[r] .. hist[r]+q_len-1 of the slot's page
    chain (a chunk may cross page boundaries — per-token page resolution);
    attention runs the ragged paged kernel on the lanes' rows of the page
    table, causal relative to each lane's own history. Padding positions
    scatter to scratch page 0 and produce garbage hidden states that nothing
    downstream reads; so does a lane whose ``write_mask`` is False.

    **Decode group.** With ``decode``, every slot's one token
    (:class:`DecodeGroup`) rides the same pass, attended by the decode kernel
    exactly as :func:`forward_paged_decode` does. Inside a layer the group's
    ``B`` tokens and the lanes' ``R*Qc`` are one row of ``B + R*Qc``, so each
    weight is read once and the work is the tokens', not ``B x Qc``. A slot
    should not both run in the group and have a lane with tokens.

    Returns (hidden, pools): without a decode group the lanes' hidden
    ``[R, Qc, H]``; with one, ``[B, H]`` — each slot's decode row, or the
    last position of the lane that names it (what the head reads).
    """
    if interpret is None:
        interpret = _default_interpret()
    cos_t, sin_t = rope_tables
    pools, caller_shape = _merged_pools(pools)
    lay = mixed_layout(input_ids, page_table, hist, q_lens, write_mask, rows,
                       decode, pools[0])
    lane_attend = _ragged_attend(cfg, interpret, mesh)
    decode_attend = _decode_attend(cfg, interpret, mesh)

    h = _embed_scale(embed_lookup(params["embed"], lay.ids,
                                  params["final_norm"].dtype), cfg)

    def layer_body(carry, xs):
        h, k_pool, v_pool = carry
        lp, layer = xs
        x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps, cfg.norm_weight_offset)
        q, kproj, vproj = _qkv_proj(lp, x, cfg, lay.positions, cos_t, sin_t)

        # scatter the step's k/v BEFORE attending: within-span causality then
        # reads the chunk's earlier tokens back through the page chain
        n = lay.pid.shape[0]
        k_pool = k_pool.at[layer, lay.pid, lay.off].set(
            kproj.reshape(n, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[layer, lay.pid, lay.off].set(
            vproj.reshape(n, -1).astype(v_pool.dtype))
        attn = mixed_attention(lay, q, k_pool, v_pool, hist, q_lens, layer,
                               lane_attend, decode_attend)
        h = _attn_out(lp, h, attn)
        h = _mlp_residual(lp, h, cfg, moe, layer)
        return (h, k_pool, v_pool), None

    k_pool, v_pool = pools
    scanned, moe = split_moe(params["layers"])
    (h, k_pool, v_pool), _ = jax.lax.scan(
        layer_body, (h, k_pool, v_pool),
        (scanned, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    h = mixed_hidden_out(lay, h, q_lens, rows)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps, cfg.norm_weight_offset)
    return h, _restore_pools((k_pool, v_pool), caller_shape)


def prefill_collect(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,   # [B, T]
    lengths: jnp.ndarray,     # [B]
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    use_flash: bool = False,
) -> tuple[jnp.ndarray, KVCache]:
    """Prefill that RETURNS the new per-layer k/v instead of writing a cache.

    The continuous-batching scheduler prefills one request at a time and scatters
    the returned [L, B, T, Hkv, D] into its slot of the persistent pool with a
    single donated dynamic_update_slice — prefill compute stays O(one request),
    not O(pool size). Semantics identical to `forward` on a fresh cache of S=T.
    """
    B, T = input_ids.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    # dtype from final_norm, not embed: quantized trees carry a dict embed
    cache = init_cache(cfg, B, T, params["final_norm"].dtype)
    hidden, kv = forward(
        params, cfg, input_ids, positions, cache,
        jnp.zeros((B,), jnp.int32), rope_tables, use_flash=use_flash,
    )
    last_h = gather_last_hidden(hidden, lengths)
    return last_h, kv


def _softcap(logits: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """gemma-2 final-logit soft capping: cap * tanh(logits / cap)."""
    if cfg.final_logit_softcap > 0.0:
        cap = cfg.final_logit_softcap
        return cap * jnp.tanh(logits / cap)
    return logits


def lm_head_logits(params: Params, cfg: ModelConfig, hidden: jnp.ndarray) -> jnp.ndarray:
    """hidden [B, H] (or [B, T, H]) → logits in f32."""
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    if isinstance(head, dict):
        if "qe" in head:  # tied quantized embed: rows [V, H] with per-row scales
            logits = jnp.einsum("...h,vh->...v", hidden, head["qe"].astype(hidden.dtype),
                                preferred_element_type=jnp.float32) * head["se"]
        else:
            logits = jnp.einsum("...h,hv->...v", hidden, head["q"].astype(hidden.dtype),
                                preferred_element_type=jnp.float32) * head["s"]
    else:
        if cfg.tie_embeddings:
            head = head.T
        logits = jnp.einsum("...h,hv->...v", hidden, head,
                            preferred_element_type=jnp.float32)
    if cfg.lm_head_multiplier != 1.0:   # falcon_h1
        logits = logits * cfg.lm_head_multiplier
    if cfg.logits_scaling != 1.0:       # granite_hybrid: a divisor
        logits = logits / cfg.logits_scaling
    # single exit: every head variant gets the gemma-2 softcap
    return _softcap(logits, cfg)


def gather_last_hidden(hidden: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """hidden [B, T, H], lengths [B] → [B, H] at index lengths-1 per row."""
    idx = jnp.maximum(lengths - 1, 0)
    return jnp.take_along_axis(hidden, idx[:, None, None], axis=1)[:, 0, :]
