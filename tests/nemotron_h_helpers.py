"""Shared by the nemotron_h tests: the published-config view of a
``ModelConfig`` (the keys ``benchmark/nemotron_h_reference.py``,
``nemotron_h_weights.py`` and ``nemotron_h_counts.py`` read). The paged cache
for driving the forwards without the scheduler is granite_hybrid's
(``granite_hybrid_helpers.PagedRun(..., module=nemotron_h)``)."""

LETTER = {"mamba": "M", "attention": "*", "moe": "E"}


def published(c) -> dict:
    """``ModelConfig`` → the Hugging Face key names of ``config.json``, with
    the chip's share where the benchmark's files put it: ``n_routed_experts``
    and ``vocab_size`` what is held, ``serving`` the router's width and the
    first held expert."""
    return dict(
        hidden_size=c.hidden_size, moe_intermediate_size=c.intermediate_size,
        moe_latent_size=c.moe_latent_size,
        moe_shared_expert_intermediate_size=c.shared_intermediate_size,
        vocab_size=c.vocab_rows, num_hidden_layers=c.num_layers,
        hybrid_override_pattern="".join(LETTER[k] for k in c.layer_types),
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
        head_dim=c.head_dim, n_routed_experts=c.experts_local,
        num_experts_per_tok=c.experts_per_token,
        routed_scaling_factor=c.routed_scaling_factor,
        norm_eps=c.rms_norm_eps, mamba_num_heads=c.ssm_heads,
        mamba_head_dim=c.ssm_head_dim, ssm_state_size=c.ssm_state,
        n_groups=c.ssm_groups, conv_kernel=c.ssm_conv, chunk_size=c.ssm_chunk,
        serving=dict(experts_routed=c.num_experts,
                     expert_offset=c.expert_offset))
