"""Shared by the solar_open2 tests: the published-config view of a
``ModelConfig`` (the keys ``benchmark/solar_open2_reference.py``,
``solar_open2_weights.py`` and ``solar_open2_counts.py`` read). The paged
cache for driving the forwards without the scheduler is granite_hybrid's
(``granite_hybrid_helpers.PagedRun(..., module=solar_open2)``)."""


def published(c) -> dict:
    """``ModelConfig`` → the Hugging Face key names of ``config.json``, with
    the chip's share where the benchmark's files put it: ``n_routed_experts``
    and ``vocab_size`` what is held, ``serving`` the router's width and the
    first held expert."""
    return dict(
        hidden_size=c.hidden_size, intermediate_size=c.intermediate_size,
        moe_intermediate_size=c.moe_intermediate_size,
        vocab_size=c.vocab_rows, num_hidden_layers=c.num_layers,
        gqa_layers=[i for i, k in enumerate(c.layer_types)
                    if k == "attention"],
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
        head_dim=c.head_dim, n_routed_experts=c.experts_local,
        n_shared_experts=c.shared_experts,
        num_experts_per_tok=c.experts_per_token,
        routed_scaling_factor=c.routed_scaling_factor,
        rms_norm_eps=c.rms_norm_eps, use_gqa_gate=c.use_gqa_gate,
        kda_allow_neg_eigval=c.kda_allow_neg_eigval,
        linear_attn_config=dict(short_conv_kernel_size=c.ssm_conv,
                                head_dim=c.ssm_head_dim,
                                num_heads=c.ssm_heads, num_kv_heads=None),
        serving=dict(experts_routed=c.num_experts,
                     expert_offset=c.expert_offset))
