"""Operations and bytes of the GLM-5 block as one chip's share runs it, from
shapes and from what the program's counters MEASURED, by role (``opcounts.py``
counts the llama family's; a configuration names this module under
``counts``). The harness's parent process imports this module: no JAX.

Each function takes the configuration file and its serving block and returns
``{"flops", "bytes", "what"}`` for ONE execution of the thing named. Of the
configuration's keys ``n_routed_experts`` and ``vocab_size`` are the chip's
share (experts held, vocabulary rows held). What shapes alone do not say is
read from ``serving``, where a reader has put the measured value
(``kimi_k2_readers.roofline_measured``):

- ``keys_scored_per_call``: keys ONE index pass of a decode step scored, the
  whole batch (``llm_dsa_decode_keys_scored_total`` over
  ``llm_dsa_decode_calls_total``: a row's length, summed over the rows that
  ran);
- ``keys_selected_per_call``: keys the attention behind it attended
  (``llm_dsa_decode_keys_selected_total`` over the same calls: ``min(length,
  index_topk)`` a row);
- ``experts_touched_share`` and ``assignments_local_share``: as
  ``kimi_k2_counts.py`` reads them, over decode steps alone.

Without them the functions return nothing to count: no expectation from
shapes (PERF.md, PR 31). **The count is the work, not the implementation**:
an index key counts its 128 numbers read once, a chosen latent row its 576
read ONCE, whatever a gather in front of the kernel writes and reads again;
a masked walk of the whole span would read low on it the same way.
"""

from __future__ import annotations

from typing import Optional

from benchmark import kimi_k2_counts

latent_row = kimi_k2_counts.latent_row
expert_params = kimi_k2_counts.expert_params
routed_experts = kimi_k2_counts.routed_experts


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """A token's latent row and its index key, over the layers."""
    return cfg["num_hidden_layers"] * (
        latent_row(cfg) + cfg["index_head_dim"]) * itemsize


def indexer_params(cfg: dict) -> tuple[int, int, int]:
    """(int8 weights, f32 scales, float32 bytes) of one layer's indexer: the
    query and key projections int8, the heads' weights float32."""
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    return (cfg["q_lora_rank"] * heads * dim + cfg["hidden_size"] * dim,
            heads * dim + dim, 4 * cfg["hidden_size"] * heads)


def dsa_index_scores(cfg: dict, serving: dict) -> Optional[dict]:
    """One index pass of a decode step, the whole batch: every key scored
    reads its index key ONCE (it is scored by all the index heads); 2 FLOPs
    a number a head."""
    keys = serving.get("keys_scored_per_call")
    if keys is None:
        return None
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    return {"flops": float(keys) * heads * dim * 2.0,
            "bytes": float(keys) * dim * 2.0,
            "what": f"{float(keys):.0f} keys x {dim} bf16 numbers read once; "
                    f"{heads} heads x {dim} x 2 FLOPs a key"}


def dsa_sparse_decode_attention(cfg: dict, serving: dict) -> Optional[dict]:
    """One decode step's attention over the chosen rows, one layer, the
    whole batch: every chosen latent row read ONCE (the key and the value of
    all the heads); per head and key 2 FLOPs a number of the key (rank +
    rope) and of the value (rank)."""
    keys = serving.get("keys_selected_per_call")
    if keys is None:
        return None
    heads, row = cfg["num_attention_heads"], latent_row(cfg)
    rank = cfg["kv_lora_rank"]
    return {"flops": heads * float(keys) * 2.0 * (row + rank),
            "bytes": float(keys) * row * 2.0,
            "what": f"{float(keys):.0f} chosen keys x {row} bf16 numbers "
                    f"read once; {heads} heads x 2 x ({row} + {rank}) FLOPs "
                    "a key"}


def step_weights(cfg: dict, serving: dict) -> Optional[dict]:
    """What ONE decode step of the whole batch streams, weights only:
    kimi_k2's count of the block (the latent attention at this
    configuration's head sizes comes out of the same keys) plus every
    layer's indexer."""
    base = kimi_k2_counts.step_weights(cfg, serving)
    if base is None:
        return None
    weights, scales, f32 = indexer_params(cfg)
    layers, rows = cfg["num_hidden_layers"], serving["max_batch"]
    return {"flops": base["flops"] + 2.0 * rows * layers * (weights + f32 / 4),
            "bytes": base["bytes"] + layers * (weights + 4.0 * scales + f32),
            "what": base["what"] + "; every layer's indexer"}


def decode_step(cfg: dict, serving: dict) -> Optional[dict]:
    """The whole decode step: :func:`step_weights` plus, a layer, the index
    keys scored and the latent rows attended, as measured."""
    weights = step_weights(cfg, serving)
    index = dsa_index_scores(cfg, serving)
    attn = dsa_sparse_decode_attention(cfg, serving)
    if weights is None or index is None or attn is None:
        return None
    layers = cfg["num_hidden_layers"]
    return {"flops": weights["flops"] + layers * (index["flops"]
                                                  + attn["flops"]),
            "bytes": weights["bytes"] + layers * (index["bytes"]
                                                  + attn["bytes"]),
            "what": weights["what"] + f"; + {layers} layers x ("
            + index["what"] + "; " + attn["what"] + ")"}
