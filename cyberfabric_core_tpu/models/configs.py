"""Architecture configs for the BASELINE model set.

BASELINE.json configs name Llama-3-8B/70B, Mistral-7B, Phi-3-mini, bge-base-en;
model-registry PRD:200-224 requires architecture/size/format metadata for managed
local models. All decoder models here are the llama family (RMSNorm + RoPE + GQA +
SwiGLU); family differences are config-driven, not code-forked — one TPU-optimized
forward serves them all.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional


#: the kinds of ``ModelConfig.layer_types`` whose layers hold recurrent state
#: (a row of the state slab and a conv tail) and write no page
STATE_KINDS = ("mamba", "kda")

#: the architectures whose model module calls its attention kernels by the
#: layer's kind over TWO page groups (``sliding_window_period``): motif on
#: latent pages, laguna on K/V pages. The other modules read one window a
#: model, so a per-layer window is refused for them at config time
TWO_GROUP_ARCHITECTURES = ("motif", "laguna")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    #: "llama" (decoder family) | "falcon_h1" (decoder whose every block runs
    #: a Mamba-2 mixer beside GQA attention) | "sdar_moe" (decoder of routed
    #: experts that generates by diffusion over blocks) | "kimi_k2" (decoder
    #: of latent attention over a latent page, a leading dense layer, then
    #: sigmoid-routed experts beside a shared expert) | "granite_hybrid" (a
    #: stack whose layers differ in kind, ``layer_types``: Mamba-2 mixer
    #: layers and attention layers without rotary, routed experts beside a
    #: shared MLP after each) | "nemotron_h" (a stack whose every layer is ONE
    #: sub-layer, ``layer_types``: a Mamba-2 mixer, attention without rotary,
    #: or routed experts that work in a latent beside a shared expert) |
    #: "solar_open2" (a stack of linear-attention layers under a gated delta
    #: rule, ``kda`` of ``layer_types``, and gated attention layers without
    #: rotary, sigmoid-routed experts beside a shared expert after each) |
    #: "motif" (a decoder of grouped differential attention over a latent
    #: page, three layers in four behind a window whose pages live in a page
    #: group of their own, four residual streams mixed by hyper-connections,
    #: PolyNorm MLPs, kimi_k2's sigmoid router over a share of the experts) |
    #: "ouro" (a llama stack run ``loop_steps`` times a token with the SAME
    #: weights, every branch normed on both sides, the final norm after every
    #: pass and an exit gate that reads it; a pass caches its own K and V) |
    #: "glm_moe_dsa" (kimi_k2's block with a learned indexer in front of the
    #: attention: ``index_heads`` small heads score every cached key of a
    #: row against an index key cached beside the latent row, and a query
    #: attends over the ``index_topk`` keys that score highest) |
    #: "bert" (encoder)
    architecture: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_position: int = 8192
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    sliding_window: Optional[int] = None  # Mistral-style SWA
    attention_bias: bool = False
    # gemma-family knobs
    #: "silu" (llama) | "gelu" (gemma GeGLU): the gate's activation in a gated
    #: MLP of three matrices; "relu2" (nemotron_h): ``relu(x W1)² W2``, an
    #: MLP of two matrices with no gate; "poly_norm" (motif): a gated MLP whose
    #: gate goes through ``s (a1 z/rms(z) + a2 z²/rms(z²) + a3 z³/rms(z³)) +
    #: clamp(b)``, rms over the MLP's whole row, coefficients a layer's own
    hidden_act: str = "silu"
    norm_weight_offset: float = 0.0   # gemma RMSNorm computes (offset + w) * x̂
    embedding_multiplier: float = 1.0  # gemma scales embeddings by sqrt(H)
    final_logit_softcap: float = 0.0  # gemma-2: logits = cap * tanh(logits/cap)
    # mixture-of-experts (0 = dense MLP)
    num_experts: int = 0
    experts_per_token: int = 2
    #: RMSNorm over each q and k head before RoPE (the Qwen3 block's
    #: q_norm/k_norm; one weight vector of head_dim a layer each)
    qk_norm: bool = False
    # generation by diffusion over blocks (sdar_moe; 1 = one token a step,
    # left to right). The sequence is cut into blocks of block_length at
    # absolute positions; attention is causal between blocks and full inside
    # one; an open block holds mask_token_id where nothing is decided yet and
    # is denoised in forwards that unmask, by ``remasking``, at least
    # block_length / denoising_steps positions each. The model card's
    # defaults, not config.json keys.
    block_length: int = 1
    denoising_steps: int = 1
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9
    mask_token_id: int = -1
    # kimi_k2 (the DeepSeek-V3 block). Latent attention (kv_lora_rank 0 =
    # GQA attention): q is projected down to q_lora_rank, normed, and up to
    # num_heads x (qk_nope_head_dim + qk_rope_head_dim); k and v come from
    # ONE compressed row of kv_lora_rank a token plus one rotary key of
    # qk_rope_head_dim that all heads share, and the cache holds only those
    # (``latent_width`` numbers a token a layer). Names as published.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: leading layers with a dense MLP of intermediate_size; the layers
    #: after them hold the experts, of moe_intermediate_size each
    first_k_dense: int = 0
    moe_intermediate_size: int = 0
    #: experts every token runs, beside the routed ones (one MLP of
    #: shared_experts x moe_intermediate_size)
    shared_experts: int = 0
    #: the sigmoid router's gate is score over the chosen scores' sum, times
    #: this (the scoring itself is the architecture's constant, as
    #: router_float32 is: kimi_k2 scores by sigmoid, the others by softmax)
    routed_scaling_factor: float = 1.0
    # one chip's share of a layer that a deployment divides over chips: the
    # router scores all num_experts, this chip computes experts
    # expert_offset .. expert_offset + experts_held - 1 (0 = all of them)
    # and holds vocab_held rows of the embedding and the head (0 = all)
    experts_held: int = 0
    expert_offset: int = 0
    vocab_held: int = 0
    # YaRN rotary scaling (rope_factor 1 = none): the published rope_scaling
    # keys factor, original_max_position_embeddings, beta_fast, beta_slow,
    # mscale, mscale_all_dim
    rope_factor: float = 1.0
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # glm_moe_dsa: attention over a chosen set (0 = over every cached key).
    # index_n_heads, index_head_dim, index_topk as published: a query's
    # index_heads heads of index_head_dim score each key's ONE index key
    # (cached beside the latent row, ``index_lanes`` numbers a token a
    # layer), and the query attends over the index_topk keys that score
    # highest among those it may see (all of them while it sees no more)
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # falcon_h1: the state-space mixer beside attention (0 heads = no mixer).
    # Names follow the published config: mamba_d_ssm, mamba_n_heads,
    # mamba_d_head, mamba_d_state, mamba_n_groups, mamba_d_conv,
    # mamba_chunk_size.
    ssm_inner: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # falcon_h1's fixed multipliers (muP), each applied where the published
    # forward applies it; ssm_multipliers are for (z, x, B, C, dt) of the
    # mixer's input projection, mlp_multipliers for (gate, down)
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: tuple = (1.0, 1.0)
    # granite_hybrid (model_type granitemoehybrid) and nemotron_h, names as
    # published. The kind of every layer, "mamba", "attention", "moe" or
    # "kda" (empty: every layer is what the architecture's one block is): a
    # mamba layer holds recurrent state and writes no page, an attention
    # layer holds pages and no state, a moe layer (experts ALONE, no mixer in
    # front of them: nemotron_h) neither, and a kda layer (solar_open2:
    # linear attention under a gated delta rule) holds state and no page as
    # a mamba layer does: ``ssm_heads`` heads whose state is ``[ssm_head_dim
    # keys, ssm_state values]`` (``linear_attn_config``'s num_heads and
    # head_dim, twice), a conv tail of ``ssm_conv - 1`` inputs over q, k AND
    # v, the WY form in chunks of ``ssm_chunk``. So the page pool has
    # ``kv_layers`` layers, the state slab ``state_layers`` and the expert
    # stack ``moe_layers``. Whether a mamba or attention layer ALSO holds an expert
    # layer is what the architecture says (granite_hybrid: every one does),
    # not what ``layer_types`` implies
    layer_types: tuple = ()
    #: every branch (mixer or attention, then the expert layer) is added to
    #: the residual stream times this
    residual_multiplier: float = 1.0
    #: the softmax scale as a given number (0: ``head_dim^-1/2``)
    attention_multiplier: float = 0.0
    #: the logits are divided by this
    logits_scaling: float = 1.0
    #: False: no rotary embedding ("position_embedding_type": "nope")
    rotary: bool = True
    #: width of the shared MLP every token runs beside the routed experts
    shared_intermediate_size: int = 0
    #: nemotron_h: the routed experts work on rows of this width, between ONE
    #: dense down-projection of the hidden and ONE up-projection (0: on the
    #: hidden itself); the router and the shared expert see the full hidden
    moe_latent_size: int = 0
    # solar_open2, names as published. use_gqa_gate: an attention layer's
    # output is multiplied by ``sigmoid(x W_gate)`` before ``W_o``
    use_gqa_gate: bool = False
    #: ``β = 2 sigmoid(.)`` (True) or ``sigmoid(.)``: with the factor 2 the
    #: eigenvalue of ``I − β k kᵀ`` lies in (−1, 1)
    kda_allow_neg_eigval: bool = False
    # motif (model_type Motif), each beside its published name.
    #: sliding_window_period (with sliding_window_pattern interleave): layer
    #: ``i`` attends over everything where ``(i + 1) % period == 0`` and
    #: over its last ``sliding_window`` tokens otherwise (0: one window, or
    #: none, for every layer). The two kinds cache in two PAGE GROUPS
    #: (``runtime/paged.py``): a pool and a page table each
    sliding_window_period: int = 0
    #: which layer of a period is the full one: layer ``i`` attends over
    #: everything where ``i % period == full_layer_phase % period`` (-1, the
    #: LAST of each period: motif's reading of its own key; 0, the first:
    #: laguna's published ``layer_types``)
    full_layer_phase: int = -1
    # laguna (model_type laguna), each beside its published name.
    #: num_attention_heads_per_layer: query heads of a WINDOW layer (0: as
    #: ``num_heads``, which the full layers have); both kinds read the same
    #: ``num_kv_heads`` of ``head_dim``
    window_num_heads: int = 0
    #: rope_parameters by layer type. ``rope_theta`` and the YaRN keys above
    #: are the FULL layers'; a window layer's tables are plain
    #: ``window_rope_theta`` over the whole head (0: every layer shares the
    #: one pair of tables). ``partial_rotary_factor``: the full layers rotate
    #: the leading this share of a head and pass the rest through. The
    #: published ``attention_factor`` is what ``ops/rope.py`` derives from
    #: ``rope_factor`` (0.1 ln(factor) + 1) and is not stated again
    window_rope_theta: float = 0.0
    partial_rotary_factor: float = 1.0
    #: gating "per-head": an attention layer's output is multiplied, a head
    #: a token, by ``sigmoid(x W_g)`` [heads] before ``W_o``
    head_gate: bool = False
    #: num_noise_heads: the LAST this many of ``num_heads`` are the noise
    #: heads of differential attention; noise head ``g`` and signal heads
    #: ``4g..`` read latent kv group ``g`` of ``num_kv_heads``
    num_noise_heads: int = 0
    #: mhc_expansion_rate: residual streams a token carries (1: one residual)
    mhc_expansion_rate: int = 1
    #: mhc_sinkhorn_iters: alternations of row and column normalisation
    mhc_sinkhorn_iters: int = 20
    #: polynorm_output_scale, polynorm_bias_clamp
    polynorm_output_scale: float = 1.0
    polynorm_bias_clamp: float = 0.0
    #: hidden_clamp: every sub-layer's output is clamped to +- this (0: not)
    hidden_clamp: float = 0.0
    # ouro (model_type ouro), each beside its published name.
    #: total_ut_steps: passes of the WHOLE stack a token runs, one set of
    #: weights; pass ``t`` of layer ``l`` attends over what pass ``t`` of
    #: layer ``l`` cached, so it is cache layer ``t * num_layers + l``
    loop_steps: int = 1
    #: early_exit_threshold: a token leaves at the first pass whose cumulated
    #: exit probability reaches this; at the published 1 that is the last
    #: pass, always (the one value the engines serve)
    early_exit_threshold: float = 1.0
    #: every branch (attention, MLP) is normed again BEFORE it is added to
    #: the residual: four norms a layer
    sandwich_norm: bool = False
    # bert-family extras
    layer_norm_eps: float = 1e-12
    type_vocab_size: int = 2
    pooling: str = "cls"  # bge uses CLS pooling + L2 norm

    def __post_init__(self) -> None:
        if self.hidden_act not in ("silu", "gelu", "gelu_pytorch_tanh",
                                   "relu2", "poly_norm"):
            # fail at config time, not as silently-wrong activations at runtime
            raise ValueError(
                f"unknown hidden_act {self.hidden_act!r} (supported: silu, "
                "gelu, gelu_pytorch_tanh, relu2, poly_norm)")
        if self.sliding_window_period and not (
                self.sliding_window
                and self.architecture in TWO_GROUP_ARCHITECTURES):
            raise ValueError(
                f"{self.name}: a per-layer window (sliding_window_period "
                f"{self.sliding_window_period}) needs a sliding_window and a "
                "model module that calls its kernels by the layer's kind "
                f"over two page groups ({', '.join(TWO_GROUP_ARCHITECTURES)}"
                "); the other modules read one window a model")
        if (self.window_num_heads or self.window_rope_theta) \
                and not self.sliding_window_period:
            raise ValueError(
                f"{self.name}: window_num_heads and window_rope_theta are "
                "the window LAYERS' and need a sliding_window_period")
        if self.window_heads % max(self.num_kv_heads, 1):
            raise ValueError(
                f"{self.name}: {self.window_heads} query heads of a window "
                f"layer are not whole groups over {self.num_kv_heads} kv "
                "heads")
        if self.index_topk and not (self.is_latent and self.index_heads
                                    and self.index_head_dim
                                    and not self.sliding_window):
            raise ValueError(
                f"{self.name}: index_topk {self.index_topk} selects among a "
                "latent page's rows: it needs kv_lora_rank, index_heads and "
                "index_head_dim, and goes with no sliding window")
        if self.remasking not in ("low_confidence_static",
                                  "low_confidence_dynamic"):
            raise ValueError(f"unknown remasking {self.remasking!r}")
        if self.expert_offset + self.experts_held > max(self.num_experts, 0):
            raise ValueError(
                f"{self.name}: experts {self.expert_offset}.."
                f"{self.expert_offset + self.experts_held - 1} are not among "
                f"the router's {self.num_experts}")
        if self.layer_types and (
                len(self.layer_types) != self.num_layers
                or set(self.layer_types) - {"mamba", "attention", "moe",
                                            "kda"}):
            raise ValueError(
                f"{self.name}: layer_types names {len(self.layer_types)} "
                f"layers of kinds {sorted(set(self.layer_types))} for "
                f"num_layers {self.num_layers} (kinds: mamba, attention, "
                "moe, kda)")
        if self.loop_steps < 1 or (self.loop_steps > 1 and (
                self.layer_types or self.sliding_window_period)):
            raise ValueError(
                f"{self.name}: loop_steps {self.loop_steps} runs ONE stack "
                "of layers of one kind several times; it is at least 1 and "
                "goes with neither layer_types nor a per-layer window")
        if self.block_length > 1 and (
                self.block_length % self.denoising_steps
                or not 0 <= self.mask_token_id < self.vocab_size):
            raise ValueError(
                f"{self.name}: block_length {self.block_length} needs "
                "denoising_steps that divide it and a mask_token_id inside "
                "the vocabulary")

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def window_heads(self) -> int:
        """Query heads of a window layer (``num_heads``: a full layer's)."""
        return self.window_num_heads or self.num_heads

    @property
    def router_float32(self) -> bool:
        """The router's weights stay float32 whatever the activations' dtype
        (a score decides WHICH experts run, not only how much)."""
        return self.architecture in ("sdar_moe", "kimi_k2", "granite_hybrid",
                                     "nemotron_h", "solar_open2", "motif",
                                     "laguna", "glm_moe_dsa")

    @property
    def is_latent(self) -> bool:
        """The cache is one latent row a token a layer, not K and V."""
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Numbers a token a layer in a latent page: the compressed row and
        the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        """Lanes a token takes in a latent page: ``latent_width`` rounded up
        to whole lane tiles of 128, the rest zero. A TPU array's minor
        dimension is tiled by 128 whatever its shape says, so 576 numbers
        take 640 lanes of HBM either way; stated in the shape, the pool keeps
        the row-major layout its kernels and its scatter read (left to the
        device, a minor dimension with 11% padding makes it choose the PAGE
        axis as the minor one for a pool of 3073 pages, and every step then
        copies the pool twice)."""
        return -(-self.latent_width // 128) * 128

    @property
    def is_sparse(self) -> bool:
        """A query attends over a chosen set of the keys it may see: the
        cache holds an index key a token a layer beside the latent row."""
        return self.index_topk > 0

    @property
    def index_lanes(self) -> int:
        """Lanes a token's index key takes in the index pool
        (``index_head_dim`` in whole lane tiles; 0: no indexer)."""
        return -(-self.index_head_dim // 128) * 128 if self.is_sparse else 0

    @property
    def experts_local(self) -> int:
        """Routed experts this chip computes, of ``num_experts`` routed over."""
        return self.experts_held or self.num_experts

    @property
    def vocab_rows(self) -> int:
        """Rows of the embedding and the head this chip holds: what ids and
        logits range over."""
        return self.vocab_held or self.vocab_size

    @property
    def moe_layers(self) -> int:
        """Layers that hold routed experts: the expert stack's leading
        dimension. Where ``layer_types`` names ``moe`` layers, those; else
        every layer past the leading dense ones."""
        if "moe" in self.layer_types:
            return self.layer_types.count("moe")
        return self.num_layers - self.first_k_dense if self.num_experts else 0

    #: the name kimi_k2's code reads it by
    num_moe_layers = moe_layers

    @property
    def expert_row_width(self) -> int:
        """Width of the rows the routed experts multiply."""
        return self.moe_latent_size or self.hidden_size

    @property
    def kv_layers(self) -> int:
        """CACHE layers a row keeps for its whole length: the page pool's
        leading dimension. A layer that attends caches once for every pass
        of the stack (``loop_steps``; 1 but for a looped model), so this
        passes ``num_layers`` where a stack runs several times."""
        return self.attention_layers * self.loop_steps

    @property
    def attention_layers(self) -> int:
        """Layers of the model whose pages a row keeps for its whole length,
        each counted ONCE: what holds attention weights of that kind."""
        if self.sliding_window_period:
            return self.full_layers_before(self.num_layers)
        if not self.layer_types:
            return self.num_layers
        return self.layer_types.count("attention")

    @property
    def window_layers(self) -> int:
        """Layers whose pages a row gives back once they lie left of its
        window: the WINDOW page group's leading dimension (0: no such
        group; one window for every layer keeps its pages)."""
        return self.num_layers - self.kv_layers \
            if self.sliding_window_period else 0

    def layer_is_full(self, layer: int) -> bool:
        """Layer ``layer`` attends over a row's whole length."""
        period = self.sliding_window_period
        return (not period
                or layer % period == self.full_layer_phase % period)

    def full_layers_before(self, layer):
        """The full layers among layers ``0 .. layer - 1`` of a stack with a
        per-layer window: a full layer's index in the full page group, and
        ``layer`` less this a window layer's in the window group. Whole
        numbers in, a whole number out; a traced ``layer`` (inside a scan
        over layers) gives a traced count."""
        period = self.sliding_window_period
        return (layer + period - 1 - self.full_layer_phase % period) // period

    def window_pages(self, page_size: int, queries: int = 1) -> int:
        """The most pages the window of ``queries`` consecutive positions
        spans."""
        return (self.sliding_window + queries - 3) // page_size + 2

    @property
    def state_layers(self) -> int:
        """Layers that hold recurrent state, of either kind that does: the
        state slab's leading dimension."""
        if not self.layer_types:
            return self.num_layers if self.has_state else 0
        return sum(self.layer_types.count(k) for k in STATE_KINDS)

    def cut_to(self, layers: int, name: str | None = None) -> "ModelConfig":
        """The first ``layers`` layers of this configuration (a pipeline
        stage; a judged depth), ``layer_types`` cut with them."""
        return dataclasses.replace(
            self, name=name or self.name, num_layers=layers,
            layer_types=self.layer_types[:layers])

    def cache_bytes_per_token(self, itemsize: int = 2) -> int:
        """Bytes a token holds in the page pool over its ``kv_layers`` cache
        layers: a layer that caches, times the passes that run it."""
        return self.kv_layers * self._page_row_numbers * itemsize

    def window_bytes_per_token(self, itemsize: int = 2) -> int:
        """Bytes a token holds in the WINDOW page group while it lies inside
        its row's window (0: the model has one page group)."""
        return self.window_layers * self._page_row_numbers * itemsize

    @property
    def _page_row_numbers(self) -> int:
        """Numbers a token stores in one cache layer: a latent row in whole
        lane tiles (and the index key beside it, where a query attends over
        a chosen set), or K and V of every kv head."""
        return (self.latent_lanes + self.index_lanes if self.is_latent
                else 2 * self.num_kv_heads * self.head_dim)

    @property
    def is_block(self) -> bool:
        """A decode step yields a block of tokens, not a token."""
        return self.block_length > 1

    @property
    def has_state(self) -> bool:
        """A recurrent state a row beside its K/V pages."""
        return self.ssm_heads > 0

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the mixer's depthwise conv runs over: x, B and C of a
        Mamba-2 mixer; q, k and v of a kda layer."""
        if "kda" in self.layer_types:
            return self.ssm_heads * (2 * self.ssm_head_dim + self.ssm_state)
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_proj_dim(self) -> int:
        """Width of the mixer's input projection: z, then x B C, then dt."""
        return self.ssm_inner + self.ssm_conv_dim + self.ssm_heads

    def state_bytes_per_row(self) -> int:
        """f32 recurrent state and conv tail of one row, over the layers
        that hold state."""
        per_layer = (self.ssm_heads * self.ssm_head_dim * self.ssm_state
                     + (self.ssm_conv - 1) * self.ssm_conv_dim)
        return 4 * self.state_layers * per_layer

    @property
    def expert_width(self) -> int:
        """Width of one routed expert's MLP."""
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def shared_width(self) -> int:
        """Width of the MLP every token runs beside the routed experts."""
        return (self.shared_intermediate_size
                or self.shared_experts * self.moe_intermediate_size)

    def kda_matrices(self) -> dict[str, tuple[int, int]]:
        """(rows, columns) of a kda layer's matrices by the names the
        parameter tree gives them: q, k, v, o, the decay's and the output
        gate's low-rank pairs (rank = the head size: ``kda_use_full_proj``
        false), β."""
        h, r = self.hidden_size, self.ssm_head_dim
        dk, dv = self.ssm_heads * self.ssm_head_dim, \
            self.ssm_heads * self.ssm_state
        return {"wq": (h, dk), "wk": (h, dk), "wv": (h, dv), "wo": (dv, h),
                "f_a": (h, r), "f_b": (r, dk), "g_a": (h, r), "g_b": (r, dv),
                "w_beta": (h, self.ssm_heads)}

    def latent_matrices(self) -> dict[str, tuple[int, int]]:
        """A latent attention layer's matrices, (contraction, outputs) each:
        the two query projections, the compressed row and rotary key, the up
        projection of K and V, the output; and the indexer's three where a
        query attends over a chosen set (``index_w`` float32, never
        quantised, as a router is)."""
        h, hq = self.hidden_size, self.num_heads
        out = {"wq_a": (h, self.q_lora_rank),
               "wq_b": (self.q_lora_rank, hq * self.head_dim),
               "wkv_a": (h, self.latent_width),
               "wkv_b": (self.kv_lora_rank,
                         hq * (self.qk_nope_head_dim + self.v_head_dim)),
               "wo": (hq * self.v_head_dim, h)}
        if self.is_sparse:
            out.update({
                "index_wq": (self.q_lora_rank,
                             self.index_heads * self.index_head_dim),
                "index_wk": (h, self.index_head_dim),
                "index_w": (h, self.index_heads)})
        return out

    def param_count(self) -> int:
        """Approximate parameter count (for HBM budgeting). A layer counts
        ONCE however many passes run it (``loop_steps``)."""
        h, i, v, l = self.hidden_size, self.intermediate_size, self.vocab_size, self.num_layers
        if self.is_sparse:
            # latent attention's five matrices and two inner norms, the
            # indexer (its three matrices, the index key's norm and bias),
            # a layer's two norms; a dense MLP, or the experts with their
            # router and its selection bias beside the shared expert
            layer = (sum(k * n for k, n in self.latent_matrices().values())
                     + self.q_lora_rank + self.kv_lora_rank
                     + 2 * self.index_head_dim + 2 * h)
            expert = 3 * h * self.moe_intermediate_size
            return (l * layer + self.first_k_dense * 3 * h * i
                    + self.moe_layers * (
                        self.num_experts * (expert + h + 1)
                        + self.shared_experts * expert)
                    + 2 * v * h + h)

        def attention(heads: int) -> int:
            """One attention layer of ``heads`` query heads: q, k, v, o, the
            elementwise output gate or the gate a head."""
            dq, dkv = heads * self.head_dim, self.num_kv_heads * self.head_dim
            return (2 * h * dq + 2 * h * dkv
                    + (h * dq if self.use_gqa_gate else 0)
                    + (h * heads if self.head_gate else 0))

        attn = attention(self.num_heads)
        emb = v * h * (1 if self.tie_embeddings else 2)
        if self.window_layers and not self.is_latent:
            # two kinds of attention layer (laguna); the leading dense MLPs,
            # then a router, the routed experts and a shared one a layer
            expert = 3 * h * self.expert_width
            return (self.attention_layers * attn
                    + self.window_layers * attention(self.window_heads)
                    + self.first_k_dense * 3 * h * i
                    + self.moe_layers * (self.num_experts * expert + h
                                         * self.num_experts
                                         + 3 * h * self.shared_width)
                    + l * 2 * h + emb + h)
        mixer = 0
        if "kda" in self.layer_types:
            # the matrices; conv taps, A_log, dt_bias, the head norm's weight
            mixer = (sum(k * n for k, n in self.kda_matrices().values())
                     + self.ssm_conv * self.ssm_conv_dim + self.ssm_heads
                     + self.ssm_heads * self.ssm_head_dim + self.ssm_state)
        elif self.has_state:  # in/out projections, conv + bias, A, D, dt, norm
            mixer = (h * self.ssm_proj_dim + self.ssm_inner * h
                     + (self.ssm_conv + 1) * self.ssm_conv_dim
                     + 3 * self.ssm_heads + self.ssm_inner)
        if "moe" in self.layer_types:
            # one sub-layer and one norm a layer; an expert of two matrices
            # on the latent, the router and its selection bias, the latent's
            # two projections, the shared expert of two matrices
            w = self.expert_row_width
            experts = (self.num_experts * (2 * w * i + h + 1)
                       + (2 * h * w if self.moe_latent_size else 0)
                       + 2 * h * self.shared_intermediate_size)
            return (self.attention_layers * attn + self.state_layers * mixer
                    + self.moe_layers * experts + l * h + emb + h)
        if "kda" in self.layer_types:
            # a gated expert of three matrices, the router and its selection
            # bias, the shared expert, after every mixer
            mlp = (self.num_experts * (3 * h * self.expert_width + h + 1)
                   + 3 * h * self.shared_width)
        else:
            mlp = 3 * h * i * max(self.num_experts, 1) + h * self.num_experts \
                + 3 * h * self.shared_intermediate_size
        # the norms: two a layer, four under sandwich norms; the exit gate
        norms = (4 if self.sandwich_norm else 2) * h
        gate = h + 1 if self.loop_steps > 1 else 0
        return (self.attention_layers * attn + self.state_layers * mixer
                + l * (mlp + norms) + emb + h + gate)

    def weight_bytes(self, itemsize: int = 1) -> dict[str, int]:
        """Bytes of the matrices THIS CHIP holds, by the kind of layer that
        holds them (``experts``: the held routed experts alone; ``vocab``:
        the held rows of embedding and head), at ``itemsize`` a weight with
        one f32 scale an output channel where ``itemsize`` is 1. For a
        ``layer_types`` stack (nemotron_h: one sub-layer a layer, an expert
        of two matrices; solar_open2: an expert layer of gated three-matrix
        experts after every mixer) and for a stack of two kinds of attention
        layer on K/V pages (laguna: ``window_attention`` beside
        ``attention``, ``dense_mlp`` the leading dense layers')."""
        h, w = self.hidden_size, self.expert_row_width
        i, shared = self.expert_width, self.shared_width
        scale = 4 if itemsize == 1 else 0
        gated = self.hidden_act != "relu2"      # a third matrix, the gate

        def mat(k: int, n: int, count: int = 1) -> int:
            return count * (k * n * itemsize + n * scale)

        dq, dkv = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        dw = self.window_heads * self.head_dim

        def gate(heads: int) -> int:
            return mat(h, heads) if self.head_gate else 0

        if self.is_sparse:
            # the latent stack with an indexer: a layer's attention and
            # indexer (``index_w`` float32), the dense MLPs, the shared
            # expert and the float32 router and bias, the held experts
            latent = self.latent_matrices()
            index_w = latent.pop("index_w")
            return {
                "attention": self.num_layers * sum(
                    mat(k, n) for name, (k, n) in latent.items()
                    if not name.startswith("index_")),
                "indexer": self.num_layers * (
                    sum(mat(k, n) for name, (k, n) in latent.items()
                        if name.startswith("index_"))
                    + 4 * index_w[0] * index_w[1]),
                "dense_mlp": self.first_k_dense * (
                    2 * mat(h, self.intermediate_size)
                    + mat(self.intermediate_size, h)),
                "moe_dense": self.moe_layers * (
                    2 * mat(h, shared) + mat(shared, h)
                    + 4 * (h + 1) * self.num_experts),
                "experts": self.moe_layers * self.experts_local * (
                    2 * mat(w, i) + mat(i, w)),
                "vocab": 2 * self.vocab_rows * (h * itemsize + scale),
            }
        return {
            "mamba": self.layer_types.count("mamba") * (
                mat(h, self.ssm_proj_dim) + mat(self.ssm_inner, h)),
            "kda": self.layer_types.count("kda") * sum(
                mat(k, n) for k, n in self.kda_matrices().values()),
            "attention": self.attention_layers * (
                mat(h, dq, 2 if self.use_gqa_gate else 1) + 2 * mat(h, dkv)
                + mat(dq, h) + gate(self.num_heads)),
            # the layers behind a window, where they hold K and V pages of
            # their own kind of attention (laguna: other query heads)
            "window_attention": 0 if self.is_latent else self.window_layers * (
                2 * mat(h, dw) + 2 * mat(h, dkv) + gate(self.window_heads)),
            "dense_mlp": (self.first_k_dense if self.num_experts
                          and not self.layer_types else 0) * (
                2 * mat(h, self.intermediate_size)
                + mat(self.intermediate_size, h)),
            "moe_dense": self.moe_layers * (
                mat(h, shared, 2 if gated else 1) + mat(shared, h)
                + (mat(h, w) + mat(w, h) if self.moe_latent_size else 0)
                + 4 * (h + 1) * self.num_experts),
            "experts": self.moe_layers * self.experts_local * (
                mat(w, i, 2 if gated else 1) + mat(i, w)),
            "vocab": (self.vocab_rows * (h * itemsize + scale)
                      * (1 if self.tie_embeddings else 2)),
        }


def _nemotron_kinds(pattern: str) -> tuple:
    """``hybrid_override_pattern`` as ``layer_types``."""
    kind = {"M": "mamba", "*": "attention", "E": "moe"}
    return tuple(kind[c] for c in pattern)


MODEL_CONFIGS: dict[str, ModelConfig] = {
    # testing config: tiny shapes, CPU-fast, same code paths
    "tiny-llama": ModelConfig(
        name="tiny-llama", architecture="llama", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        max_position=256, rope_theta=10000.0,
    ),
    "llama-3-8b": ModelConfig(
        name="llama-3-8b", architecture="llama", vocab_size=128256, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        head_dim=128, max_position=8192, rope_theta=500000.0,
    ),
    "llama-3-70b": ModelConfig(
        name="llama-3-70b", architecture="llama", vocab_size=128256, hidden_size=8192,
        intermediate_size=28672, num_layers=80, num_heads=64, num_kv_heads=8,
        head_dim=128, max_position=8192, rope_theta=500000.0,
    ),
    "mistral-7b": ModelConfig(
        name="mistral-7b", architecture="llama", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        head_dim=128, max_position=32768, rope_theta=10000.0, sliding_window=4096,
    ),
    "phi-3-mini": ModelConfig(
        name="phi-3-mini", architecture="llama", vocab_size=32064, hidden_size=3072,
        intermediate_size=8192, num_layers=32, num_heads=32, num_kv_heads=32,
        head_dim=96, max_position=4096, rope_theta=10000.0,
    ),
    "tiny-llama-8l": ModelConfig(
        # 8-layer big sibling of tiny-llama: the TARGET of the cross-model
        # speculation benchmark (2-layer draft vs 8-layer target, round-4
        # verdict item 3) — same vocab so the pair shares a tokenizer
        name="tiny-llama-8l", architecture="llama", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=8, num_heads=4,
        num_kv_heads=2, head_dim=16, max_position=256, rope_theta=10000.0,
    ),
    "tiny-moe": ModelConfig(
        name="tiny-moe", architecture="llama", vocab_size=512, hidden_size=64,
        intermediate_size=96, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        max_position=256, rope_theta=10000.0, num_experts=4, experts_per_token=2,
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b", architecture="llama", vocab_size=32000,
        hidden_size=4096, intermediate_size=14336, num_layers=32, num_heads=32,
        num_kv_heads=8, head_dim=128, max_position=8192, rope_theta=1000000.0,
        num_experts=8, experts_per_token=2,
    ),
    "qwen2-7b": ModelConfig(
        name="qwen2-7b", architecture="llama", vocab_size=152064,
        hidden_size=3584, intermediate_size=18944, num_layers=28,
        num_heads=28, num_kv_heads=4, head_dim=128, max_position=32768,
        rope_theta=1e6, rms_norm_eps=1e-6, attention_bias=True,
    ),
    "tiny-qwen2": ModelConfig(
        name="tiny-qwen2", architecture="llama", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, max_position=256, rope_theta=10000.0,
        attention_bias=True, tie_embeddings=True,
    ),
    "gemma-7b": ModelConfig(
        name="gemma-7b", architecture="llama", vocab_size=256000,
        hidden_size=3072, intermediate_size=24576, num_layers=28,
        num_heads=16, num_kv_heads=16, head_dim=256, max_position=8192,
        rope_theta=10000.0, rms_norm_eps=1e-6, tie_embeddings=True,
        hidden_act="gelu", norm_weight_offset=1.0,
        embedding_multiplier=3072.0 ** 0.5,
    ),
    "tiny-gemma": ModelConfig(
        name="tiny-gemma", architecture="llama", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, max_position=256, rope_theta=10000.0,
        tie_embeddings=True, hidden_act="gelu", norm_weight_offset=1.0,
        embedding_multiplier=8.0, final_logit_softcap=30.0,
    ),
    # golden-parity configs: exact mirrors of the committed HF fixtures under
    # tests/golden/fixtures/ (tests/golden/generate_fixtures.py) — kept in the
    # registry so the worker's checkpoint-path flow serves them end-to-end
    "tiny-llama-golden": ModelConfig(
        name="tiny-llama-golden", architecture="llama", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, max_position=256, rope_theta=10000.0,
        rms_norm_eps=1e-5,
    ),
    "tiny-llama-outlier": ModelConfig(
        # tiny-llama-golden geometry with OUTLIER-INJECTED fixture weights
        # (tests/golden/generate_fixtures.py): the non-Gaussian heavy-tail
        # regime the quantization accuracy bounds are proven on
        name="tiny-llama-outlier", architecture="llama", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, max_position=256, rope_theta=10000.0,
        rms_norm_eps=1e-5,
    ),
    "tiny-qwen2-golden": ModelConfig(
        name="tiny-qwen2-golden", architecture="llama", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, max_position=256, rope_theta=1e6,
        rms_norm_eps=1e-6, tie_embeddings=True, attention_bias=True,
    ),
    "tiny-gemma-golden": ModelConfig(
        name="tiny-gemma-golden", architecture="llama", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, max_position=256, rope_theta=10000.0,
        rms_norm_eps=1e-6, tie_embeddings=True, hidden_act="gelu_pytorch_tanh",
        norm_weight_offset=1.0, embedding_multiplier=8.0,
    ),
    "tiny-mixtral-golden": ModelConfig(
        name="tiny-mixtral-golden", architecture="llama", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, max_position=256, rope_theta=1e6,
        rms_norm_eps=1e-5, num_experts=4, experts_per_token=2,
    ),
    # Falcon-H1-34B-Instruct, config.json as published: 72 identical blocks,
    # each a Mamba-2 mixer (32 heads of 128, state 256, 2 groups of B and C)
    # beside GQA 20/4 attention, then a SwiGLU MLP; every multiplier as given
    "falcon-h1-34b": ModelConfig(
        name="falcon-h1-34b", architecture="falcon_h1", vocab_size=261120,
        hidden_size=5120, intermediate_size=21504, num_layers=72,
        num_heads=20, num_kv_heads=4, head_dim=128, max_position=262144,
        rope_theta=1e11, rms_norm_eps=1e-5,
        embedding_multiplier=5.656854249492381,
        ssm_inner=4096, ssm_heads=32, ssm_head_dim=128, ssm_state=256,
        ssm_groups=2, ssm_conv=4, ssm_chunk=128,
        attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804, lm_head_multiplier=0.0078125,
        ssm_in_multiplier=0.25, ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    ),
    # CPU-test preset of the same block, every ratio kept: more than one
    # group, a state size that is not the head size, attention 2 queries a
    # kv head, a chunk (8) shorter than the test prompts
    "tiny-falcon-h1": ModelConfig(
        name="tiny-falcon-h1", architecture="falcon_h1", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, max_position=256, rope_theta=10000.0,
        embedding_multiplier=2.0,
        ssm_inner=64, ssm_heads=4, ssm_head_dim=16, ssm_state=32,
        ssm_groups=2, ssm_conv=4, ssm_chunk=8,
        attention_in_multiplier=1.0, attention_out_multiplier=0.5,
        key_multiplier=0.25, lm_head_multiplier=0.5,
        ssm_in_multiplier=0.5, ssm_out_multiplier=0.35,
        ssm_multipliers=(0.7, 0.5, 0.35, 1.0, 0.7),
        mlp_multipliers=(0.7, 0.3),
    ),
    # SDAR-30B-A3B-Chat, config.json as published (model_type sdar_moe): 48
    # blocks of GQA 32/4 attention with q/k head norms and 128 routed experts
    # top-8 (moe_intermediate_size 768; every layer sparse, so the dense
    # intermediate_size 6144 of the file names no matrix); generation by
    # diffusion over blocks of 4 with the model card's defaults
    "sdar-30b-a3b": ModelConfig(
        name="sdar-30b-a3b", architecture="sdar_moe", vocab_size=151936,
        hidden_size=2048, intermediate_size=768, num_layers=48,
        num_heads=32, num_kv_heads=4, head_dim=128, max_position=32768,
        rope_theta=1e6, rms_norm_eps=1e-6, num_experts=128,
        experts_per_token=8, qk_norm=True, block_length=4,
        denoising_steps=4, mask_token_id=151669,
    ),
    # CPU-test preset of the same block: 8 experts top-2, 2 queries a kv
    # head, blocks of 4 in 4 steps, the mask id inside a vocabulary of 512
    "tiny-sdar": ModelConfig(
        name="tiny-sdar", architecture="sdar_moe", vocab_size=512,
        hidden_size=64, intermediate_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, max_position=256, rope_theta=10000.0,
        rms_norm_eps=1e-6, num_experts=8, experts_per_token=2, qk_norm=True,
        block_length=4, denoising_steps=4, mask_token_id=511,
    ),
    # Kimi-K2.5's language model, config.json as published (model_type
    # kimi_k2, the DeepSeek-V3 block): 61 layers of 64-head latent attention
    # (head_dim here is the query/key head, 128 + 64), one leading dense
    # layer of 18432, then 384 routed experts of 2048 top-8 under a sigmoid
    # router with a selection bias beside one shared expert; YaRN x64 over
    # 4096. Text in, text out: the vision tower is not part of it
    "kimi-k2.5": ModelConfig(
        name="kimi-k2.5", architecture="kimi_k2", vocab_size=163840,
        hidden_size=7168, intermediate_size=18432, num_layers=61,
        num_heads=64, num_kv_heads=64, head_dim=192, max_position=262144,
        rope_theta=50000.0, rms_norm_eps=1e-5, num_experts=384,
        experts_per_token=8, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        first_k_dense=1, moe_intermediate_size=2048, shared_experts=1,
        routed_scaling_factor=2.827,
        rope_factor=64.0, rope_original_max=4096, rope_beta_fast=32.0,
        rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0,
    ),
    # CPU-test preset of the same block: a dense layer then 2 expert layers,
    # 16 experts top-4 of which a chip may hold 4, latent 32 + 16, YaRN x4
    "tiny-kimi": ModelConfig(
        name="tiny-kimi", architecture="kimi_k2", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=3, num_heads=4,
        num_kv_heads=4, head_dim=48, max_position=1024, rope_theta=10000.0,
        rms_norm_eps=1e-5, num_experts=16, experts_per_token=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, first_k_dense=1,
        moe_intermediate_size=32, shared_experts=1,
        routed_scaling_factor=2.5,
        rope_factor=4.0, rope_original_max=64, rope_beta_fast=32.0,
        rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0,
    ),
    # GLM-5, config.json as published (model_type glm_moe_dsa, zai-org,
    # 744 B): kimi_k2's block (64 heads of latent attention, 192 + 64 / 256,
    # q_lora 2048, kv_lora 512, plain rotary at 1e6) with a learned indexer
    # in front of the attention (32 heads of 128 pick the 2048 keys a query
    # attends), three leading dense layers of 12288, then 256 sigmoid-routed
    # experts of 2048 top-8 (scale 2.5, a selection bias) beside one shared
    # expert; untied head. The multi-token-prediction layer
    # (num_nextn_predict_layers 1) is a draft module and not part of the
    # served model
    "glm-5": ModelConfig(
        name="glm-5", architecture="glm_moe_dsa", vocab_size=154880,
        hidden_size=6144, intermediate_size=12288, num_layers=78,
        num_heads=64, num_kv_heads=64, head_dim=256, max_position=202752,
        rope_theta=1e6, rms_norm_eps=1e-5, num_experts=256,
        experts_per_token=8, q_lora_rank=2048, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        first_k_dense=3, moe_intermediate_size=2048, shared_experts=1,
        routed_scaling_factor=2.5, index_heads=32, index_head_dim=128,
        index_topk=2048,
    ),
    # CPU-test preset of the same block: two dense layers then three expert
    # layers, 8 experts top-3, latent 32 + 8, 4 index heads of 16 that pick
    # 12 keys: with pages of 4 the selection binds after three pages
    "tiny-glm-dsa": ModelConfig(
        name="tiny-glm-dsa", architecture="glm_moe_dsa", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=5, num_heads=4,
        num_kv_heads=4, head_dim=32, max_position=1024, rope_theta=10000.0,
        rms_norm_eps=1e-5, num_experts=8, experts_per_token=3,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=32, first_k_dense=2,
        moe_intermediate_size=32, shared_experts=1,
        routed_scaling_factor=2.5, index_heads=4, index_head_dim=16,
        index_topk=12,
    ),
    # granite-4.0-h-small, config.json as published (model_type
    # granitemoehybrid, 32B-A9B): 40 layers of which those at 5, 15, 25 and
    # 35 are GQA 32/8 attention WITHOUT rotary and the rest Mamba-2 mixers
    # (128 heads of 64, state 128, one group, conv 4, chunk 256); after each,
    # 72 routed experts of 768 top-10 (softmax over the chosen logits) beside
    # a shared MLP of 1536; embedding x 12, every branch x 0.22 into the
    # residual stream, the softmax scale 1/128 as given, tied head, logits / 16
    "granite-4.0-h-small": ModelConfig(
        name="granite-4.0-h-small", architecture="granite_hybrid",
        vocab_size=100352, hidden_size=4096, intermediate_size=768,
        num_layers=40, num_heads=32, num_kv_heads=8, head_dim=128,
        max_position=131072, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_embeddings=True, num_experts=72, experts_per_token=10,
        shared_intermediate_size=1536, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.0078125,
        logits_scaling=16.0, rotary=False,
        layer_types=tuple("attention" if i % 10 == 5 else "mamba"
                          for i in range(40)),
        ssm_inner=8192, ssm_heads=128, ssm_head_dim=64, ssm_state=128,
        ssm_groups=1, ssm_conv=4, ssm_chunk=256,
    ),
    # CPU-test preset of the same stack: two periods of ``m m a m``, 8
    # experts top-3 beside a shared MLP, heads >> 8 in one group, every
    # multiplier other than 1, a chunk (8) shorter than the test prompts
    "tiny-granite-hybrid": ModelConfig(
        name="tiny-granite-hybrid", architecture="granite_hybrid",
        vocab_size=512, hidden_size=64, intermediate_size=32, num_layers=8,
        num_heads=4, num_kv_heads=2, head_dim=16, max_position=256,
        rope_theta=10000.0, rms_norm_eps=1e-5, tie_embeddings=True,
        num_experts=8, experts_per_token=3, shared_intermediate_size=48,
        embedding_multiplier=3.0, residual_multiplier=0.5,
        attention_multiplier=0.125, logits_scaling=2.0, rotary=False,
        layer_types=("mamba", "mamba", "attention", "mamba") * 2,
        ssm_inner=128, ssm_heads=16, ssm_head_dim=8, ssm_state=16,
        ssm_groups=1, ssm_conv=4, ssm_chunk=8,
    ),
    # NVIDIA-Nemotron-3-Super-120B-A12B, config.json as published (model_type
    # nemotron_h): 88 layers, each ONE sub-layer by hybrid_override_pattern
    # (M a Mamba-2 mixer of 128 heads of 64, state 128, 8 groups; * GQA 32/2
    # attention without rotary; E 512 sigmoid-routed experts top-22, scale 5,
    # of 2688 in a latent of 1024, relu2 and no gate, beside a shared expert
    # of 5376 on the full hidden), untied head. The multi-token-prediction
    # module (num_nextn_predict_layers 1) is a draft module and not part of
    # the served model
    "nemotron-3-super-120b-a12b": ModelConfig(
        name="nemotron-3-super-120b-a12b", architecture="nemotron_h",
        vocab_size=131072, hidden_size=4096, intermediate_size=2688,
        num_layers=88, num_heads=32, num_kv_heads=2, head_dim=128,
        max_position=262144, rope_theta=10000.0, rms_norm_eps=1e-5,
        hidden_act="relu2", num_experts=512, experts_per_token=22,
        routed_scaling_factor=5.0, moe_latent_size=1024,
        shared_intermediate_size=5376, rotary=False,
        layer_types=_nemotron_kinds(
            "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
        ssm_inner=8192, ssm_heads=128, ssm_head_dim=64, ssm_state=128,
        ssm_groups=8, ssm_conv=4, ssm_chunk=128,
    ),
    # CPU-test preset of the same stack: all three kinds and the M E pair
    # repeated, 16 experts top-3 (scale 2.5) of 24 in a latent of 32 < 64,
    # 2 groups of 8 heads, 4 queries a kv head, an untied head, a chunk (8)
    # shorter than the test prompts
    "tiny-nemotron-h": ModelConfig(
        name="tiny-nemotron-h", architecture="nemotron_h", vocab_size=512,
        hidden_size=64, intermediate_size=24, num_layers=16, num_heads=4,
        num_kv_heads=1, head_dim=16, max_position=256, rope_theta=10000.0,
        rms_norm_eps=1e-5, hidden_act="relu2", num_experts=16,
        experts_per_token=3, routed_scaling_factor=2.5, moe_latent_size=32,
        shared_intermediate_size=48, rotary=False,
        layer_types=_nemotron_kinds("MEME*EME" * 2),
        ssm_inner=128, ssm_heads=16, ssm_head_dim=8, ssm_state=16,
        ssm_groups=2, ssm_conv=4, ssm_chunk=8,
    ),
    # Solar-Open2-250B, config.json as published (model_type solar_open2,
    # 250B-A15B): 48 layers of which gqa_layers 0, 4, ..., 44 are GQA 64/8
    # attention WITHOUT rotary (use_rope false) with a sigmoid output gate
    # (use_gqa_gate) and the rest KDA, linear attention under a gated delta
    # rule with a decay for every key channel (linear_attn_config: 64 heads
    # of 128 keys and 128 values, a short conv of 4 over q, k and v;
    # kda_allow_neg_eigval; kda_use_full_proj false: the decay's and the
    # gate's projections are low rank). After EVERY mixer
    # (first_k_dense_replace 0) 320 sigmoid-routed experts of 1280 top-8,
    # gates normalised over the chosen, scale 1, beside one shared expert;
    # untied head. intermediate_size 10240 is a dense layer's width and no
    # layer is dense; ssm_chunk is the WY form's (not a published key)
    "solar-open2-250b": ModelConfig(
        name="solar-open2-250b", architecture="solar_open2",
        vocab_size=196608, hidden_size=4096, intermediate_size=10240,
        num_layers=48, num_heads=64, num_kv_heads=8, head_dim=128,
        max_position=1048576, rope_theta=10000.0, rms_norm_eps=1e-5,
        rotary=False, use_gqa_gate=True, kda_allow_neg_eigval=True,
        num_experts=320, experts_per_token=8, moe_intermediate_size=1280,
        shared_experts=1, routed_scaling_factor=1.0,
        layer_types=("attention", "kda", "kda", "kda") * 12,
        ssm_heads=64, ssm_head_dim=128, ssm_state=128, ssm_conv=4,
        ssm_chunk=64,
    ),
    # CPU-test preset of the same stack: two periods of ``a k k k``, 16
    # experts top-4 of which a chip may hold 4, 2 queries a kv head, 4 linear
    # heads of 16 keys and 16 values, a chunk (8) shorter than the test
    # prompts
    "tiny-solar-open2": ModelConfig(
        name="tiny-solar-open2", architecture="solar_open2", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=8, num_heads=4,
        num_kv_heads=2, head_dim=16, max_position=256, rope_theta=10000.0,
        rms_norm_eps=1e-5, rotary=False, use_gqa_gate=True,
        kda_allow_neg_eigval=True, num_experts=16, experts_per_token=4,
        moe_intermediate_size=32, shared_experts=1,
        routed_scaling_factor=1.0,
        layer_types=("attention", "kda", "kda", "kda") * 2,
        ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_conv=4, ssm_chunk=8,
    ),
    # Motif-3-Beta, config.json as published (model_type Motif, 314B-A13.2B):
    # 53 layers of grouped differential attention over a latent page (GDLA:
    # 80 query heads of 128 + 64, the last 16 of them noise heads, over 16
    # latent kv groups; q_lora 1024, kv_lora 512; a sigmoid output gate), the
    # layers with (i + 1) % 4 == 0 full and the rest behind a window of 128;
    # rotary 10000 with no YaRN scaling applied (apply_yarn_scaling false);
    # four residual streams under Sinkhorn-normalised hyper-connections; two
    # leading dense layers of 12288, then 384 sigmoid-routed experts of 1280
    # top-8 (route_norm, route_scale 2, no selection bias) beside one shared
    # expert, every MLP gated through PolyNorm; untied head. The
    # multi-token-prediction module (num_nextn_predict_layers 1) is a draft
    # module and not part of the served model
    "motif-3-beta": ModelConfig(
        name="motif-3-beta", architecture="motif", vocab_size=220160,
        hidden_size=4096, intermediate_size=12288, num_layers=53,
        num_heads=80, num_kv_heads=16, head_dim=192, max_position=262144,
        rope_theta=10000.0, rms_norm_eps=1e-5, hidden_act="poly_norm",
        sliding_window=128, sliding_window_period=4, num_noise_heads=16,
        num_experts=384, experts_per_token=8, q_lora_rank=1024,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, first_k_dense=2, moe_intermediate_size=1280,
        shared_experts=1, routed_scaling_factor=2.0,
        mhc_expansion_rate=4, mhc_sinkhorn_iters=20,
        polynorm_output_scale=0.5, polynorm_bias_clamp=0.5,
        hidden_clamp=1e6,
    ),
    # CPU-test preset of the same stack: two dense layers, then a window
    # layer and a full one, one whole period and three window layers (every
    # part of ``motif.layer_plan``); 10 heads = 8 + 2 over 2 groups, latent
    # 32 + 16, a window of 24 (a page and a half of 16), 4 streams, 16
    # experts top-4
    "tiny-motif": ModelConfig(
        name="tiny-motif", architecture="motif", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=11, num_heads=10,
        num_kv_heads=2, head_dim=48, max_position=1024, rope_theta=10000.0,
        rms_norm_eps=1e-5, hidden_act="poly_norm", sliding_window=24,
        sliding_window_period=4, num_noise_heads=2, num_experts=16,
        experts_per_token=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        first_k_dense=2, moe_intermediate_size=32, shared_experts=1,
        routed_scaling_factor=2.0, mhc_expansion_rate=4,
        mhc_sinkhorn_iters=20, polynorm_output_scale=0.5,
        polynorm_bias_clamp=0.5, hidden_clamp=1e6,
    ),
    "ouro-2.6b": ModelConfig(
        # https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json
        # (model_type ouro): total_ut_steps 4, early_exit_threshold 1
        name="ouro-2.6b", architecture="ouro", vocab_size=49152,
        hidden_size=2048, intermediate_size=5632, num_layers=48,
        num_heads=16, num_kv_heads=16, head_dim=128, max_position=65536,
        rope_theta=1e6, rms_norm_eps=1e-6, loop_steps=4,
        early_exit_threshold=1.0, sandwich_norm=True,
    ),
    "tiny-ouro": ModelConfig(
        # passes != layers, so a swapped index shows
        name="tiny-ouro", architecture="ouro", vocab_size=512,
        hidden_size=128, intermediate_size=256, num_layers=3, num_heads=4,
        num_kv_heads=4, head_dim=32, max_position=512, rope_theta=10000.0,
        rms_norm_eps=1e-6, loop_steps=3, early_exit_threshold=1.0,
        sandwich_norm=True,
    ),
    # Laguna-S-2.1, config.json as published (model_type laguna, poolside,
    # about 118 B): 48 layers of GQA attention over 8 kv heads of 128, layer i
    # full where i % 4 == 0 (layer_types) with 48 query heads, YaRN x128 over
    # 8192 at theta 500000 on the FIRST 64 numbers of a head
    # (partial_rotary_factor 0.5), tables times the published
    # attention_factor, which is YaRN's 0.1 ln 128 + 1; the others behind a
    # window of 512 with 72 query heads and plain theta 10000 over the whole
    # head; a sigmoid gate a head (gating per-head); layer 0 a
    # dense SwiGLU of 12288 (mlp_only_layers [0]), then 256 softmax-routed
    # experts of 1024 top-10, gates normalised over the chosen
    # (norm_topk_prob) and scaled by 2.5, beside a shared expert of 1024;
    # untied head
    "laguna-s-2.1": ModelConfig(
        name="laguna-s-2.1", architecture="laguna", vocab_size=100352,
        hidden_size=3072, intermediate_size=12288, num_layers=48,
        num_heads=48, window_num_heads=72, num_kv_heads=8, head_dim=128,
        max_position=1048576, rms_norm_eps=1e-6, sliding_window=512,
        sliding_window_period=4, full_layer_phase=0, head_gate=True,
        rope_theta=500000.0, rope_factor=128.0, rope_original_max=8192,
        rope_beta_fast=32.0, rope_beta_slow=1.0,
        partial_rotary_factor=0.5,
        window_rope_theta=10000.0, num_experts=256, experts_per_token=10,
        first_k_dense=1, moe_intermediate_size=1024,
        shared_intermediate_size=1024, routed_scaling_factor=2.5,
    ),
    # CPU-test preset of the same stack: a dense full layer, three window
    # layers, a full expert layer and a window layer behind it (every body
    # ``motif.layer_plan`` cuts the served stack into); 6 and 9 query heads
    # on 3 kv heads of 32, a window of 8 over pages of 4 (shorter than the
    # test rows), half a head rotated under YaRN x4 in the full layers, 8
    # experts top-3
    "tiny-laguna": ModelConfig(
        name="tiny-laguna", architecture="laguna", vocab_size=512,
        hidden_size=64, intermediate_size=128, num_layers=6, num_heads=6,
        window_num_heads=9, num_kv_heads=3, head_dim=32, max_position=1024,
        rms_norm_eps=1e-6, sliding_window=8, sliding_window_period=4,
        full_layer_phase=0, head_gate=True, rope_theta=10000.0,
        rope_factor=4.0, rope_original_max=64, rope_beta_fast=32.0,
        rope_beta_slow=1.0, partial_rotary_factor=0.5, window_rope_theta=100.0, num_experts=8,
        experts_per_token=3, first_k_dense=1, moe_intermediate_size=32,
        shared_intermediate_size=32, routed_scaling_factor=2.5,
    ),
    "bge-base-en": ModelConfig(
        name="bge-base-en", architecture="bert", vocab_size=30522, hidden_size=768,
        intermediate_size=3072, num_layers=12, num_heads=12, num_kv_heads=12,
        head_dim=64, max_position=512, rope_theta=0.0,
    ),
    "tiny-bert": ModelConfig(
        name="tiny-bert", architecture="bert", vocab_size=384, hidden_size=32,
        intermediate_size=64, num_layers=2, num_heads=2, num_kv_heads=2, head_dim=16,
        max_position=128, rope_theta=0.0,
    ),
}

# one chip's stage of the 72-block deployment: 16 blocks of falcon-h1-34b and
# nothing else changed (tiny-llama-8l is the precedent for a named depth)
MODEL_CONFIGS["falcon-h1-34b-16l"] = dataclasses.replace(
    MODEL_CONFIGS["falcon-h1-34b"], name="falcon-h1-34b-16l", num_layers=16)


# one chip's stage of the 48-block deployment: 16 blocks, every expert
MODEL_CONFIGS["sdar-30b-a3b-16l"] = dataclasses.replace(
    MODEL_CONFIGS["sdar-30b-a3b"], name="sdar-30b-a3b-16l", num_layers=16)


# share 0 of the first pipeline stage of a 128-chip deployment of kimi-k2.5
# (4 stages; each layer divided over 32 chips): the dense layer and 14 expert
# layers, experts 0-11 of each layer's 384, rows 0-20479 of the vocabulary
# (an 8-way split); attention is data-parallel, so every head is here
MODEL_CONFIGS["kimi-k2.5-share32-15l"] = dataclasses.replace(
    MODEL_CONFIGS["kimi-k2.5"], name="kimi-k2.5-share32-15l", num_layers=15,
    experts_held=12, expert_offset=0, vocab_held=20480)

# a share of the tiny preset: experts 4-7 of 16, half the vocabulary
MODEL_CONFIGS["tiny-kimi-share4"] = dataclasses.replace(
    MODEL_CONFIGS["tiny-kimi"], name="tiny-kimi-share4", experts_held=4,
    expert_offset=4, vocab_held=256)


# the first of 4 pipeline stages of granite-4.0-h-small, one period of its
# layer pattern each: layers 0-9, nine mamba layers and the attention layer
# at 5, every expert, every head and the whole vocabulary (the tied head
# rides on the first stage as it does in falcon's and sdar's cuts)
MODEL_CONFIGS["granite-4.0-h-small-10l"] = MODEL_CONFIGS[
    "granite-4.0-h-small"].cut_to(10, "granite-4.0-h-small-10l")

# one period of the tiny preset (``m m a m``: three runs where the preset has
# five), for the CPU tests that build an engine: compile time is the runs'
MODEL_CONFIGS["tiny-granite-hybrid-4l"] = MODEL_CONFIGS[
    "tiny-granite-hybrid"].cut_to(4, "tiny-granite-hybrid-4l")


# chip 0 of the first of 4 pipeline stages of nemotron-3-super (16 chips, a
# four-chip host a stage): layers 0-21, two periods of the pattern (10 M, 10
# E, 2 *); of each E layer's 512 experts the 128 this chip holds, rows
# 0-32767 of the vocabulary (the first stage carries embedding and head);
# mixers, attention, shared expert, router and latent projections whole
MODEL_CONFIGS["nemotron-3-super-share4-22l"] = dataclasses.replace(
    MODEL_CONFIGS["nemotron-3-super-120b-a12b"].cut_to(
        22, "nemotron-3-super-share4-22l"),
    experts_held=128, expert_offset=0, vocab_held=32768, max_position=4096)

# a share of the tiny preset: experts 4-7 of 16, half the vocabulary; and its
# first period alone (``MEME*EME``), for the CPU tests that build an engine
MODEL_CONFIGS["tiny-nemotron-h-share4"] = dataclasses.replace(
    MODEL_CONFIGS["tiny-nemotron-h"], name="tiny-nemotron-h-share4",
    experts_held=4, expert_offset=4, vocab_held=256)
MODEL_CONFIGS["tiny-nemotron-h-share4-8l"] = MODEL_CONFIGS[
    "tiny-nemotron-h-share4"].cut_to(8, "tiny-nemotron-h-share4-8l")


# chip 0 of the first of 4 pipeline stages of solar-open2-250b (32 chips, 8
# sharing each layer): layers 0-11, three periods of ``a k k k`` (3 attention
# layers, 9 kda layers, 12 expert layers); of each layer's 320 experts the 40
# this chip holds, rows 0-24575 of the vocabulary (the first stage carries
# embedding and head); mixers, attention, shared expert and router whole
MODEL_CONFIGS["solar-open2-share8-12l"] = dataclasses.replace(
    MODEL_CONFIGS["solar-open2-250b"].cut_to(12, "solar-open2-share8-12l"),
    experts_held=40, expert_offset=0, vocab_held=24576, max_position=3072)

# a share of the tiny preset: experts 4-7 of 16, half the vocabulary; and its
# first period alone (``a k k k``), for the CPU tests that build an engine
MODEL_CONFIGS["tiny-solar-open2-share4"] = dataclasses.replace(
    MODEL_CONFIGS["tiny-solar-open2"], name="tiny-solar-open2-share4",
    experts_held=4, expert_offset=4, vocab_held=256)
MODEL_CONFIGS["tiny-solar-open2-share4-4l"] = MODEL_CONFIGS[
    "tiny-solar-open2-share4"].cut_to(4, "tiny-solar-open2-share4-4l")


# share 0 of the first of 2 pipeline stages of a 64-chip deployment of
# motif-3-beta (32 chips share each layer): layers 0-26 (the 2 dense layers,
# 25 expert layers; 6 full layers and 21 window layers), experts 0-11 of each
# layer's 384, rows 0-27519 of the vocabulary (an 8-way split); attention is
# data-parallel, so every head is here
MODEL_CONFIGS["motif-3-beta-share32-27l"] = dataclasses.replace(
    MODEL_CONFIGS["motif-3-beta"], name="motif-3-beta-share32-27l",
    num_layers=27, experts_held=12, expert_offset=0, vocab_held=27520,
    max_position=8192)

# a share of the tiny preset: experts 4-7 of 16, half the vocabulary; and its
# first four layers (dense, dense, a window layer, a full one), for the CPU
# tests that build an engine
MODEL_CONFIGS["tiny-motif-share4"] = dataclasses.replace(
    MODEL_CONFIGS["tiny-motif"], name="tiny-motif-share4", experts_held=4,
    expert_offset=4, vocab_held=256)
MODEL_CONFIGS["tiny-motif-share4-4l"] = MODEL_CONFIGS[
    "tiny-motif-share4"].cut_to(4, "tiny-motif-share4-4l")


# share 0 of the first of 4 pipeline stages of a 32-chip deployment of
# laguna-s-2.1 (8 chips share each layer): layers 0-11, three whole periods
# (layer 0 dense and full, full expert layers 4 and 8, nine window expert
# layers), experts 0-31 of each layer's 256, rows 0-12543 of the vocabulary
# (an 8-way split); attention is data-parallel, so both head counts are here
MODEL_CONFIGS["laguna-s-2.1-share8-12l"] = dataclasses.replace(
    MODEL_CONFIGS["laguna-s-2.1"], name="laguna-s-2.1-share8-12l",
    num_layers=12, experts_held=32, expert_offset=0, vocab_held=12544,
    max_position=8192)

# a share of the tiny preset: experts 4-7 of 8, half the vocabulary
MODEL_CONFIGS["tiny-laguna-share4"] = dataclasses.replace(
    MODEL_CONFIGS["tiny-laguna"], name="tiny-laguna-share4", experts_held=4,
    expert_offset=4, vocab_held=256)


# share 0 of the first pipeline stage of a deployment of glm-5 whose layers
# are each shared by 16 chips: ONE of the three leading dense layers and the
# 6 expert layers after them, experts 0-15 of each layer's 256, rows 0-19359
# of the vocabulary (an 8-way split); attention and the indexer are
# data-parallel, so every head and the whole indexer are here
MODEL_CONFIGS["glm-5-share16-7l"] = dataclasses.replace(
    MODEL_CONFIGS["glm-5"], name="glm-5-share16-7l", num_layers=7,
    first_k_dense=1, experts_held=16, expert_offset=0, vocab_held=19360,
    max_position=16384)

# a share of the tiny preset: experts 4-7 of 8, half the vocabulary
MODEL_CONFIGS["tiny-glm-dsa-share4"] = dataclasses.replace(
    MODEL_CONFIGS["tiny-glm-dsa"], name="tiny-glm-dsa-share4",
    experts_held=4, expert_offset=4, vocab_held=256)


def get_config(name: str) -> ModelConfig:
    try:
        return MODEL_CONFIGS[name]
    except KeyError:
        raise KeyError(f"unknown model config {name!r}; known: {sorted(MODEL_CONFIGS)}")
