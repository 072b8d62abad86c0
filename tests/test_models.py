"""Model + engine tests on tiny shapes (CPU backend, same code paths as TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.models import get_config
from cyberfabric_core_tpu.models import bert, llama
from cyberfabric_core_tpu.ops.rope import rope_frequencies
from cyberfabric_core_tpu.ops.sampling import sample_token
from conftest import run_request
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

CFG = get_config("tiny-llama")


@pytest.fixture(scope="module")
def tiny_params():
    return llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)


@pytest.fixture(scope="module")
def rope():
    return rope_frequencies(CFG.head_dim, CFG.max_position, CFG.rope_theta)


def test_forward_shapes(tiny_params, rope):
    B, T = 2, 8
    cache = llama.init_cache(CFG, B, 32, jnp.float32)
    ids = jnp.zeros((B, T), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T)).astype(jnp.int32)
    h, (k, v) = llama.forward(tiny_params, CFG, ids, pos, cache,
                              jnp.zeros((B,), jnp.int32), rope)
    assert h.shape == (B, T, CFG.hidden_size)
    assert k.shape == (CFG.num_layers, B, 32, CFG.num_kv_heads, CFG.head_dim)
    logits = llama.lm_head_logits(tiny_params, CFG, h[:, -1, :])
    assert logits.shape == (B, CFG.vocab_size) and logits.dtype == jnp.float32


def test_incremental_decode_matches_full_prefill(tiny_params, rope):
    """The KV-cache decode path must produce the same logits as a full forward —
    the core correctness invariant of the cache machinery."""
    T = 10
    key = jax.random.PRNGKey(1)
    ids = jax.random.randint(key, (1, T), 0, CFG.vocab_size)

    # full prefill of all T tokens
    cache_full = llama.init_cache(CFG, 1, 32, jnp.float32)
    pos = jnp.arange(T, dtype=jnp.int32)[None, :]
    h_full, _ = llama.forward(tiny_params, CFG, ids, pos, cache_full,
                              jnp.zeros((1,), jnp.int32), rope)
    logits_full = llama.lm_head_logits(tiny_params, CFG, h_full[0, -1])

    # prefill T-1 then decode the final token incrementally
    cache = llama.init_cache(CFG, 1, 32, jnp.float32)
    h_pre, cache = llama.forward(tiny_params, CFG, ids[:, : T - 1], pos[:, : T - 1],
                                 cache, jnp.zeros((1,), jnp.int32), rope)
    h_dec, cache = llama.forward(tiny_params, CFG, ids[:, T - 1:], pos[:, T - 1:],
                                 cache, jnp.asarray([T - 1], jnp.int32), rope)
    logits_inc = llama.lm_head_logits(tiny_params, CFG, h_dec[0, -1])

    np.testing.assert_allclose(np.asarray(logits_full), np.asarray(logits_inc),
                               rtol=2e-4, atol=2e-4)


def test_ragged_batch_isolation(tiny_params, rope):
    """Rows in a padded batch must not contaminate each other."""
    p1 = [5, 6, 7]
    cache1 = llama.init_cache(CFG, 1, 32, jnp.float32)
    pos1 = jnp.arange(3, dtype=jnp.int32)[None, :]
    h1, _ = llama.forward(tiny_params, CFG, jnp.asarray([p1]), pos1, cache1,
                          jnp.zeros((1,), jnp.int32), rope)
    solo = llama.lm_head_logits(tiny_params, CFG, h1[0, 2])

    # same prompt padded inside a 2-row batch with a longer neighbor
    ids = jnp.asarray([[5, 6, 7, 0, 0, 0], [9, 8, 7, 6, 5, 4]], jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(6)[None, :], (2, 6)).astype(jnp.int32)
    cache = llama.init_cache(CFG, 2, 32, jnp.float32)
    h, _ = llama.forward(tiny_params, CFG, ids, pos, cache,
                         jnp.zeros((2,), jnp.int32), rope)
    batched = llama.lm_head_logits(tiny_params, CFG, llama.gather_last_hidden(
        h, jnp.asarray([3, 6], jnp.int32))[0])
    np.testing.assert_allclose(np.asarray(solo), np.asarray(batched), rtol=2e-4, atol=2e-4)


def test_sliding_window_masks_distant_tokens(tiny_params, rope):
    """Mistral-style SWA: with window w, tokens further than w back are invisible."""
    import dataclasses

    cfg_swa = dataclasses.replace(CFG, sliding_window=4)
    T = 12
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, T), 3, CFG.vocab_size)
    pos = jnp.arange(T, dtype=jnp.int32)[None, :]

    def last_logits(cfg, token_prefix):
        cache = llama.init_cache(cfg, 1, 32, jnp.float32)
        h, _ = llama.forward(tiny_params, cfg, token_prefix, pos, cache,
                             jnp.zeros((1,), jnp.int32), rope)
        return llama.lm_head_logits(tiny_params, cfg, h[0, -1])

    base = last_logits(cfg_swa, ids)
    # perturb a token OUTSIDE the window of the last position (pos 2 << 11-4)
    ids_perturbed = ids.at[0, 2].set((ids[0, 2] + 1) % CFG.vocab_size)
    swa = last_logits(cfg_swa, ids_perturbed)
    np.testing.assert_allclose(np.asarray(base), np.asarray(swa), rtol=1e-5, atol=1e-5)
    # sanity: without the window the same perturbation DOES change the logits
    full = last_logits(CFG, ids_perturbed)
    assert not np.allclose(np.asarray(base), np.asarray(full), rtol=1e-3, atol=1e-3)


def test_sampling_greedy_and_temperature():
    logits = jnp.asarray([[0.0, 5.0, 1.0, 2.0]] * 3, jnp.float32)
    toks = sample_token(logits, jax.random.PRNGKey(0),
                        jnp.zeros((3,)), jnp.ones((3,)), jnp.zeros((3,), jnp.int32))
    assert list(np.asarray(toks)) == [1, 1, 1]
    # top_k=1 sampling == greedy regardless of temperature
    toks = sample_token(logits, jax.random.PRNGKey(1),
                        jnp.ones((3,)) * 2.0, jnp.ones((3,)), jnp.ones((3,), jnp.int32))
    assert list(np.asarray(toks)) == [1, 1, 1]
    # top_p tiny keeps only the argmax
    toks = sample_token(logits, jax.random.PRNGKey(2),
                        jnp.ones((3,)), jnp.asarray([1e-6] * 3), jnp.zeros((3,), jnp.int32))
    assert list(np.asarray(toks)) == [1, 1, 1]


@pytest.fixture(scope="module")
def engine():
    eng = ContinuousBatchingEngine(EngineConfig(
        model="tiny-llama", max_seq_len=64, max_batch=3, decode_chunk=4),
        seed=0)
    yield eng
    eng.shutdown()


def _engine_run(model, prompt=(5, 6, 7), max_tokens=6, **over):
    """A short greedy answer of a fresh engine of ``model``, and its params."""
    eng = ContinuousBatchingEngine(EngineConfig(
        model=model, max_seq_len=64, max_batch=2, decode_chunk=4, **over),
        seed=0)
    try:
        return run_request(eng, list(prompt),
                           SamplingParams(max_tokens=max_tokens)), eng.params
    finally:
        eng.shutdown()


def test_engine_generate_deterministic(engine):
    tokens, finish = run_request(engine, [1, 5, 9],
                                 SamplingParams(max_tokens=8))
    assert 1 <= len(tokens) <= 8
    assert finish in ("stop", "length")
    # deterministic under greedy
    assert run_request(engine, [1, 5, 9],
                       SamplingParams(max_tokens=8)) == (tokens, finish)


def test_engine_batch_matches_single(engine):
    """Sharing the batch must not change greedy results vs solo runs."""
    from concurrent.futures import ThreadPoolExecutor

    def answer(prompt):
        return run_request(engine, prompt, SamplingParams(max_tokens=6))

    prompts = ([1, 5], [1, 7, 9, 11], [1])
    solo = [answer(p) for p in prompts]
    with ThreadPoolExecutor(len(prompts)) as together:
        assert list(together.map(answer, prompts)) == solo


def test_engine_stop_tokens(engine):
    base, _ = run_request(engine, [1, 5, 9], SamplingParams(max_tokens=8))
    assert len(base) >= 2
    stop_at = base[1]
    tokens, finish = run_request(engine, [1, 5, 9], SamplingParams(
        max_tokens=8, stop_token_ids=(stop_at,)))
    assert finish == "stop"
    # the stop token is the last one emitted; the worker keeps it from the text
    assert tokens == base[:2]


def test_bert_embeddings():
    cfg = get_config("tiny-bert")
    params = bert.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    ids = jnp.asarray([[5, 6, 7, 0], [5, 6, 7, 9]], jnp.int32)
    mask = jnp.asarray([[1, 1, 1, 0], [1, 1, 1, 1]], jnp.int32)
    emb = bert.embed_pooled(params, cfg, ids, mask)
    assert emb.shape == (2, cfg.hidden_size)
    norms = np.linalg.norm(np.asarray(emb), axis=-1)
    np.testing.assert_allclose(norms, 1.0, rtol=1e-5)
    # padding must not affect the CLS embedding of the padded row... it can, via
    # attention normalization? No: masked positions contribute zero weight.
    ids2 = jnp.asarray([[5, 6, 7, 3]], jnp.int32)
    mask2 = jnp.asarray([[1, 1, 1, 0]], jnp.int32)
    emb2 = bert.embed_pooled(params, cfg, ids2, mask2)
    np.testing.assert_allclose(np.asarray(emb[0]), np.asarray(emb2[0]), rtol=1e-5, atol=1e-5)


def test_seeded_sampling_reproducible(engine):
    """SamplingParams.seed: same seed -> same sampled tokens across calls."""
    p = SamplingParams(max_tokens=8, temperature=0.9, top_p=0.95, seed=1234)
    a, _ = run_request(engine, [1, 5, 9], p)
    # interleave an unrelated request to perturb engine rng state
    run_request(engine, [2, 2], SamplingParams(max_tokens=3, temperature=0.7))
    b, _ = run_request(engine, [1, 5, 9], p)
    assert a == b
    # different seed diverges (overwhelmingly likely at temp 0.9)
    c, _ = run_request(engine, [1, 5, 9], SamplingParams(
        max_tokens=8, temperature=0.9, top_p=0.95, seed=999))
    assert c != a


def test_qwen2_attention_bias_family():
    """Qwen2-family support: attention_bias=True threads real q/k/v bias
    terms through the projection (zeroing them changes logits), incremental
    decode stays consistent with prefill, and tied embeddings drive the head.
    Reference model card geometry: qwen2-7b in models/configs.py."""
    import jax

    from cyberfabric_core_tpu.models import get_config, llama
    from cyberfabric_core_tpu.ops.rope import rope_frequencies

    cfg = get_config("tiny-qwen2")
    assert cfg.attention_bias and cfg.tie_embeddings
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    assert {"bq", "bk", "bv"} <= set(params["layers"])
    rope = rope_frequencies(cfg.head_dim, 64, cfg.rope_theta)

    ids = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32)[None, :], (1, 4))
    start = jnp.zeros((1,), jnp.int32)

    cache = llama.init_cache(cfg, 1, 16)
    h_full, _ = llama.forward(params, cfg, ids, pos, cache, start, rope)
    logits_full = llama.lm_head_logits(params, cfg, h_full[:, -1, :])

    # bias is live: zeroing it must change the output
    zeroed = dict(params, layers={**params["layers"],
                                  "bq": params["layers"]["bq"] * 0,
                                  "bk": params["layers"]["bk"] * 0,
                                  "bv": params["layers"]["bv"] * 0})
    h_nob, _ = llama.forward(zeroed, cfg, ids, pos, llama.init_cache(cfg, 1, 16),
                             start, rope)
    assert not np.allclose(np.asarray(h_full), np.asarray(h_nob), atol=1e-4)

    # incremental decode over the cache matches full prefill
    cache = llama.init_cache(cfg, 1, 16)
    h3, cache = llama.forward(params, cfg, ids[:, :3], pos[:, :3], cache,
                              jnp.zeros((1,), jnp.int32), rope)
    h4, cache = llama.forward(params, cfg, ids[:, 3:], pos[:, 3:], cache,
                              jnp.asarray([3], jnp.int32), rope)
    logits_inc = llama.lm_head_logits(params, cfg, h4[:, -1, :])
    np.testing.assert_allclose(np.asarray(logits_full), np.asarray(logits_inc),
                               rtol=2e-2, atol=2e-2)


def test_qwen2_engine_and_quant():
    """tiny-qwen2 runs through the engine incl. int8 (biases unquantized)."""
    (tokens, _), _ = _engine_run("tiny-qwen2")
    assert len(tokens) == 6

    (tokens_q, _), params_q = _engine_run("tiny-qwen2", quantization="int8")
    assert not isinstance(params_q["layers"]["bq"], dict)  # bias not quantized
    assert len(tokens_q) == 6


def test_gemma_family_knobs():
    """Gemma-family: GeGLU activation, (1+w) RMSNorm, sqrt(H) embedding
    scaling, and gemma-2 logit softcapping are all live (each knob changes
    the output), and the family runs end to end through the engine.
    Geometry reference: gemma-7b in models/configs.py."""
    import dataclasses

    import jax

    from cyberfabric_core_tpu.models import get_config, llama

    cfg = get_config("tiny-gemma")
    assert cfg.hidden_act == "gelu" and cfg.norm_weight_offset == 1.0
    params = llama.init_params(cfg, jax.random.PRNGKey(5))
    assert "lm_head" not in params  # tied embeddings

    from cyberfabric_core_tpu.ops.rope import rope_frequencies
    rope = rope_frequencies(cfg.head_dim, 64, cfg.rope_theta)
    ids = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32)[None, :], (1, 4))
    start = jnp.zeros((1,), jnp.int32)

    def logits_for(c):
        h, _ = llama.forward(params, c, ids, pos, llama.init_cache(c, 1, 16),
                             start, rope)
        return np.asarray(llama.lm_head_logits(params, c, h[:, -1, :]))

    base = logits_for(cfg)
    # every knob is live: flipping each one changes the logits
    for change in ({"hidden_act": "silu"}, {"norm_weight_offset": 0.0},
                   {"embedding_multiplier": 1.0}, {"final_logit_softcap": 0.0}):
        assert not np.allclose(base, logits_for(
            dataclasses.replace(cfg, **change)), atol=1e-5), change
    # softcap bounds the logits
    assert np.abs(base).max() <= cfg.final_logit_softcap + 1e-3

    (tokens, _), _ = _engine_run("tiny-gemma")
    assert len(tokens) == 6
