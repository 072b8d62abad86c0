"""Shared by the falcon_h1 tests: the published-config view of a
``ModelConfig`` (the keys ``benchmark/falcon_h1_reference.py`` and
``falcon_h1_weights.py`` read), and a paged cache for driving the model's
forward passes without the scheduler."""

import jax.numpy as jnp
import numpy as np

from cyberfabric_core_tpu.models import falcon_h1
from cyberfabric_core_tpu.ops.rope import rope_frequencies


def published(c) -> dict:
    """``ModelConfig`` → the Hugging Face key names of ``config.json``."""
    return dict(
        hidden_size=c.hidden_size, intermediate_size=c.intermediate_size,
        vocab_size=c.vocab_size, num_hidden_layers=c.num_layers,
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
        head_dim=c.head_dim, rms_norm_eps=c.rms_norm_eps,
        rope_theta=c.rope_theta, mamba_d_ssm=c.ssm_inner,
        mamba_n_heads=c.ssm_heads, mamba_d_head=c.ssm_head_dim,
        mamba_d_state=c.ssm_state, mamba_n_groups=c.ssm_groups,
        mamba_d_conv=c.ssm_conv, mamba_chunk_size=c.ssm_chunk,
        embedding_multiplier=c.embedding_multiplier,
        attention_in_multiplier=c.attention_in_multiplier,
        attention_out_multiplier=c.attention_out_multiplier,
        key_multiplier=c.key_multiplier,
        lm_head_multiplier=c.lm_head_multiplier,
        ssm_in_multiplier=c.ssm_in_multiplier,
        ssm_out_multiplier=c.ssm_out_multiplier,
        ssm_multipliers=list(c.ssm_multipliers),
        mlp_multipliers=list(c.mlp_multipliers))


class PagedRun:
    """Prefill in chunks through ``forward_paged_mixed``, then decode through
    ``forward_paged_decode``, each row on its own pages; collects the logits
    at every position from the last prompt token on."""

    def __init__(self, cfg, params, rows, page=16, pmax=8, chunk=16):
        self.cfg, self.params, self.rows, self.chunk = cfg, params, rows, chunk
        self.rope = rope_frequencies(cfg.head_dim, page * pmax, cfg.rope_theta)
        shape = (cfg.num_layers, rows * pmax + 1, page,
                 cfg.num_kv_heads * cfg.head_dim)
        self.pools = (jnp.zeros(shape, jnp.bfloat16),
                      jnp.zeros(shape, jnp.bfloat16))
        self.table = jnp.asarray(
            1 + np.arange(rows * pmax).reshape(rows, pmax), jnp.int32)
        self.state = falcon_h1.init_state(cfg, rows + 1)

    def mixed_step(self, ids, hist, q_lens, write_mask=None):
        hidden, self.pools, self.state = falcon_h1.forward_paged_mixed(
            self.params, self.cfg, jnp.asarray(ids), self.pools, self.table,
            jnp.asarray(hist), jnp.asarray(q_lens), self.rope,
            write_mask=write_mask, state=self.state)
        last = falcon_h1.gather_last_hidden(hidden, jnp.asarray(q_lens))
        return np.asarray(falcon_h1.lm_head_logits(self.params, self.cfg, last),
                          np.float32)

    def decode(self, ids, lens, write_mask=None):
        hidden, self.pools, self.state = falcon_h1.forward_paged_decode(
            self.params, self.cfg, jnp.asarray(ids), self.pools, self.table,
            jnp.asarray(lens), self.rope, write_mask=write_mask,
            state=self.state)
        return np.asarray(falcon_h1.lm_head_logits(
            self.params, self.cfg, hidden[:, 0]), np.float32)

    def run(self, seqs, lens, steps):
        """{(row, position): logits}: chunked prefill of ``seqs[r][:lens[r]]``
        then ``steps`` forced decode tokens a row."""
        done = np.zeros(self.rows, np.int32)
        lens = np.asarray(lens)
        got = {}
        while (done < lens).any():
            q = np.clip(lens - done, 0, self.chunk).astype(np.int32)
            ids = np.zeros((self.rows, self.chunk), np.int32)
            for r in range(self.rows):
                ids[r, : q[r]] = seqs[r][done[r]: done[r] + q[r]]
            logits = self.mixed_step(ids, done, q)
            for r in range(self.rows):
                done[r] += q[r]
                if q[r] and done[r] >= lens[r]:
                    got[(r, int(done[r]) - 1)] = logits[r]
        for _ in range(steps):
            ids = np.asarray([[seqs[r][done[r]]] for r in range(self.rows)],
                             np.int32)
            logits = self.decode(ids, done)
            for r in range(self.rows):
                got[(r, int(done[r]))] = logits[r]
                done[r] += 1
        return got
