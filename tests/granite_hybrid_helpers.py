"""Shared by the granite_hybrid tests: the published-config view of a
``ModelConfig`` (the keys ``benchmark/granite_hybrid_reference.py``,
``granite_hybrid_weights.py`` and ``granite_hybrid_counts.py`` read), and a
paged cache for driving the model's forward passes without the scheduler."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from cyberfabric_core_tpu.models import granite_hybrid
from cyberfabric_core_tpu.ops.rope import rope_tables


def published(c) -> dict:
    """``ModelConfig`` → the Hugging Face key names of ``config.json``."""
    return dict(
        hidden_size=c.hidden_size, intermediate_size=c.intermediate_size,
        shared_intermediate_size=c.shared_intermediate_size,
        vocab_size=c.vocab_size, num_hidden_layers=c.num_layers,
        layer_types=list(c.layer_types),
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
        num_local_experts=c.num_experts,
        num_experts_per_tok=c.experts_per_token,
        rms_norm_eps=c.rms_norm_eps, mamba_expand=c.ssm_inner // c.hidden_size,
        mamba_n_heads=c.ssm_heads, mamba_d_head=c.ssm_head_dim,
        mamba_d_state=c.ssm_state, mamba_n_groups=c.ssm_groups,
        mamba_d_conv=c.ssm_conv, mamba_chunk_size=c.ssm_chunk,
        embedding_multiplier=c.embedding_multiplier,
        residual_multiplier=c.residual_multiplier,
        attention_multiplier=c.attention_multiplier,
        logits_scaling=c.logits_scaling)


@functools.partial(jax.jit, static_argnames=("cfg", "module"))
def _mixed(params, cfg, ids, pools, table, hist, q_lens, rope, write_mask,
           rows, decode, state, module=granite_hybrid):
    return module.forward_paged_mixed(
        params, cfg, ids, pools, table, hist, q_lens, rope,
        write_mask=write_mask, rows=rows, decode=decode, state=state)


@functools.partial(jax.jit, static_argnames=("cfg", "module"))
def _decode(params, cfg, ids, pools, table, lens, rope, write_mask, state,
            module=granite_hybrid):
    return module.forward_paged_decode(
        params, cfg, ids, pools, table, lens, rope, write_mask=write_mask,
        state=state)


class PagedRun:
    """Prefill in chunks through ``forward_paged_mixed``, then decode through
    ``forward_paged_decode``, each row on its own pages; collects the logits
    at every position from the last prompt token on, and the experts every
    token of a row chose (``self.experts[r]``: [expert layers, tokens, K]).
    ``module``: the model module whose forwards run (the granite_hybrid
    signatures; ``models/nemotron_h.py`` has them too)."""

    def __init__(self, cfg, params, rows, page=16, pmax=8, chunk=16,
                 module=granite_hybrid):
        self.cfg, self.params, self.rows, self.chunk = cfg, params, rows, chunk
        self.module = module
        self.rope = rope_tables(cfg, page * pmax)
        shape = (cfg.kv_layers, rows * pmax + 1, page,
                 cfg.num_kv_heads * cfg.head_dim)
        self.pools = (jnp.zeros(shape, jnp.bfloat16),
                      jnp.zeros(shape, jnp.bfloat16))
        self.table = jnp.asarray(
            1 + np.arange(rows * pmax).reshape(rows, pmax), jnp.int32)
        self.state = module.init_state(cfg, rows + 1)
        self.experts = [np.zeros((cfg.moe_layers, 0, cfg.experts_per_token),
                                 np.int32) for _ in range(rows)]
        self.aux = None

    def _logits(self, hidden):
        return np.asarray(self.module.lm_head_logits(
            self.params, self.cfg, hidden), np.float32)

    def mixed_step(self, ids, hist, q_lens, write_mask=None, rows=None,
                   decode=None):
        """Jitted (one compile a shape: an eager call would compile every
        run's scan anew each time)."""
        hidden, self.pools, self.state, self.aux = _mixed(
            self.params, self.cfg, jnp.asarray(ids), self.pools, self.table,
            jnp.asarray(hist), jnp.asarray(q_lens), self.rope, write_mask,
            rows, decode, self.state, module=self.module)
        if decode is not None:
            return self._logits(hidden)
        chosen = np.asarray(self.aux["experts"])
        width = np.asarray(ids).shape[1]
        for r, q in enumerate(np.asarray(q_lens)):
            self.experts[r] = np.concatenate(
                [self.experts[r], chosen[:, r * width: r * width + q]], 1)
        return self._logits(self.module.gather_last_hidden(
            hidden, jnp.asarray(q_lens)))

    def decode(self, ids, lens, write_mask=None):
        hidden, self.pools, self.state, self.aux = _decode(
            self.params, self.cfg, jnp.asarray(ids), self.pools, self.table,
            jnp.asarray(lens), self.rope, write_mask, self.state,
            module=self.module)
        chosen = np.asarray(self.aux["experts"])
        for r in range(self.rows):
            self.experts[r] = np.concatenate(
                [self.experts[r], chosen[:, r: r + 1]], 1)
        return self._logits(hidden[:, 0])

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
