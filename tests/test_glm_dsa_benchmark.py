"""The cheap cases of ``benchmark/tests/test_glm_dsa.py`` (no judge, no
server), re-exported so that tier-1 holds this configuration's entries in
``BENCHMARK.json``, its file's published keys, its counts module and what
its metric files read; and, so that no file of this configuration's is much
over a minute of one worker, the two model cases that need no judged
scenario: the forward against ``models/kimi_k2.py``'s on the same tree, and
the presets' counts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.tests.test_glm_dsa import (  # noqa: F401
    test_every_new_metric_file_reads_its_own_call_site_and_counter,
    test_the_configuration_carries_the_published_keys_unchanged,
    test_the_counts_answer_the_roles_and_agree_with_a_count_by_hand,
    test_the_real_files_names_resolve_and_only_add,
    test_the_rehearsal_files_names_resolve)
from cyberfabric_core_tpu.models import (decoder_module, get_config, glm_dsa,
                                         kimi_k2)
from cyberfabric_core_tpu.ops import rope

PAGE = 4


def test_with_index_topk_over_the_row_the_forward_is_kimi_k2s():
    """(d) EQUALS KIMI. On the same tree, with ``index_topk`` at the row's
    length or more, a chunked prefill and decode steps give
    ``models/kimi_k2.py``'s hidden states (the attention is the same set);
    with the selection binding they do not; and in ONE batch a row under
    ``index_topk`` is attended whole beside a row over it."""
    cfg = dataclasses.replace(get_config("tiny-glm-dsa"), num_layers=2,
                              first_k_dense=1)
    params = glm_dsa.init_params(cfg, jax.random.PRNGKey(3))
    B, pmax, T = 2, 16, 40
    table = jnp.asarray(1 + np.arange(B * pmax, dtype=np.int32).reshape(
        B, pmax))
    tables = rope.rope_tables(cfg, pmax * PAGE)
    rng = np.random.default_rng(1)
    ids = rng.integers(3, 512, (B, T + 2)).astype(np.int32)
    lens = np.array([T, 9], np.int32)        # row 1 stays under index_topk

    def run(module, model_cfg, pools):
        mixed = jax.jit(lambda ids, pools, hist, q: module.forward_paged_mixed(
            params, model_cfg, ids, pools, table, hist, q, tables))
        decode = jax.jit(lambda ids, pools, lengths:
                         module.forward_paged_decode(
                             params, model_cfg, ids, pools, table, lengths,
                             tables))
        hist = np.zeros(B, np.int32)
        outs = []
        while (hist < lens).any():
            q = np.minimum(lens - hist, 16).clip(0)
            chunk = np.zeros((B, 16), np.int32)
            for r in range(B):
                chunk[r, : q[r]] = ids[r, hist[r]: hist[r] + q[r]]
            h, pools, _ = mixed(jnp.asarray(chunk), pools, jnp.asarray(hist),
                                jnp.asarray(q))
            outs.append(np.asarray(module.gather_last_hidden(
                h, jnp.asarray(q)), np.float32)[q > 0])
            hist = hist + q
        for step in range(2):
            tok = np.stack([ids[r, lens[r] + step] for r in range(B)])
            h, pools, _ = decode(jnp.asarray(tok[:, None]), pools,
                                 jnp.asarray(lens + step))
            outs.append(np.asarray(h[:, 0], np.float32))
        return outs

    def pools(model_cfg):
        shape = (model_cfg.num_layers, B * pmax + 1, PAGE)
        both = (jnp.zeros((*shape, model_cfg.latent_lanes), jnp.bfloat16),
                jnp.zeros((*shape, 128), jnp.bfloat16))
        return both if model_cfg.is_sparse else both[:1]

    as_kimi = dataclasses.replace(cfg, architecture="kimi_k2", index_heads=0,
                                  index_head_dim=0, index_topk=0)
    want = run(kimi_k2, as_kimi, pools(as_kimi))
    got = run(glm_dsa, cfg, pools(cfg))
    # row 1 (9 tokens, then 10 and 11: index_topk 12 is at or above its
    # length in every call) is attended whole, beside a row that is not:
    # kimi_k2's numbers
    assert len(got) == len(want) == 5
    for a, b in zip(got[:1] + got[-2:], want[:1] + want[-2:]):
        np.testing.assert_allclose(a[1], b[1], rtol=0.02, atol=0.02)
    # row 0's first chunk sees 16 keys from its 13th query on, its decode
    # steps 12 of 41: another result
    assert np.abs(got[-1][0] - want[-1][0]).max() > 0.05


def test_presets_params_and_the_one_device_refusal():
    """The published preset counts about 744 B parameters (the indexer
    counted, the prediction layer not); the served share holds 5.58 GB of
    int8 weights and caches 10.75 KB a token; the drawn and the quantised
    tiny trees hold exactly ``param_count`` numbers; a mesh is refused with
    the architecture's own name."""
    from cyberfabric_core_tpu.runtime.quant import init_params_quantized

    big = get_config("glm-5")
    assert 742e9 < big.param_count() < 746e9
    assert (big.index_heads, big.index_head_dim, big.index_topk) == (
        32, 128, 2048)
    no_indexer = dataclasses.replace(big, index_heads=0, index_head_dim=0,
                                     index_topk=0, architecture="kimi_k2")
    assert not no_indexer.is_sparse and big.is_sparse
    served = get_config("glm-5-share16-7l")
    assert (served.num_layers, served.first_k_dense, served.experts_held,
            served.vocab_rows) == (7, 1, 16, 19360)
    by_kind = served.weight_bytes(1)
    assert 5.55e9 < sum(by_kind.values()) < 5.62e9
    assert 65e6 < by_kind["indexer"] < 75e6
    assert served.cache_bytes_per_token() == 7 * (640 + 128) * 2
    tiny = get_config("tiny-glm-dsa")
    assert decoder_module(tiny) is glm_dsa
    # shapes and dtypes alone: nothing is drawn
    drawn = jax.eval_shape(lambda k: glm_dsa.init_params(tiny, k),
                           jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(drawn)) == tiny.param_count()
    quant = jax.eval_shape(
        lambda k: init_params_quantized(get_config("tiny-glm-dsa-share4"), k),
        jax.random.PRNGKey(0))
    assert quant["layers"]["index_wq"]["q"].dtype == jnp.int8
    assert quant["dense"]["index_w"].dtype == jnp.float32
    assert quant["layers"]["index_k_norm"].dtype == jnp.bfloat16
    assert glm_dsa.STEP_COUNTERS[:6] == kimi_k2.STEP_COUNTERS
    with pytest.raises(ValueError, match="glm_moe_dsa serves on one device"):
        glm_dsa._one_device(object(), None)
    with pytest.raises(ValueError, match="index_topk"):
        dataclasses.replace(tiny, kv_lora_rank=0)
