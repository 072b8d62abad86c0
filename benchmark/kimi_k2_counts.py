"""Operations and bytes of the Kimi-K2 block as one chip's share runs it, from
shapes and from what the program's counters MEASURED, by role (``opcounts.py``
counts the llama family's; a configuration names this module under
``counts``). The harness's parent process imports this module: no JAX.

Each function takes the configuration file and its serving block and returns
``{"flops", "bytes", "what"}`` for ONE execution of the thing named. Of the
configuration's keys ``n_routed_experts`` and ``vocab_size`` are the chip's
share (experts held, vocabulary rows held). What shapes alone do not say is
read from ``serving``, where a reader has put the measured value
(``kimi_k2_readers.roofline_measured``):

- ``attn_pages_walked_share``: pages the latent decode kernel's grid walked
  over the page table's slots (``llm_attn_pages_walked_total`` over
  ``_offered_total``), so one call walks that share of ``max_batch x
  max_seq_len / page`` pages;
- ``experts_touched_share``: held experts with at least one token over held
  experts offered, over the forwards of decode chunks alone
  (``llm_moe_decode_experts_touched_total`` over ``_offered_total``): the
  counts here price ONE DECODE STEP, and a mixed step's prompt chunk touches
  nearly every held expert;
- ``assignments_local_share``: routed assignments that fell on held experts
  (``llm_moe_assignments_local_total`` over ``llm_moe_assignments_total``).

Without them the functions return nothing to count: there is no expectation
from shapes here on purpose (PERF.md, PR 31: a uniform expectation read a
roofline share over 100%). The counts describe the WORK (the numbers a token
caches, the matrices a step must stream), not an implementation: the latent
row counts its 576 numbers, not the 640 lanes the pool stores them in.
"""

from __future__ import annotations

from typing import Optional


def _dims(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return {"H": cfg["hidden_size"], "I": cfg["intermediate_size"],
            "Im": cfg["moe_intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "Ld": cfg["first_k_dense_replace"],
            "held": cfg["n_routed_experts"],
            "E": cfg["serving"]["experts_routed"],
            "K": cfg["num_experts_per_tok"], "Hq": heads,
            "shared": cfg["n_shared_experts"],
            "q_rank": cfg["q_lora_rank"], "rank": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"]}


def latent_row(cfg: dict) -> int:
    """Numbers a token caches a layer: the compressed row and the rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return cfg["num_hidden_layers"] * latent_row(cfg) * itemsize


def attention_params(cfg: dict) -> tuple[int, int]:
    """(int8 weights, f32 scales) of one layer's latent attention."""
    d = _dims(cfg)
    qk = d["nope"] + d["rope"]
    outs = (d["q_rank"], d["Hq"] * qk, d["rank"] + d["rope"],
            d["Hq"] * (d["nope"] + d["v"]), d["H"])
    weights = (d["H"] * d["q_rank"] + d["q_rank"] * d["Hq"] * qk
               + d["H"] * (d["rank"] + d["rope"])
               + d["rank"] * d["Hq"] * (d["nope"] + d["v"])
               + d["Hq"] * d["v"] * d["H"])
    return weights, sum(outs)


def expert_params(cfg: dict) -> tuple[int, int]:
    """(int8 weights, f32 scales) of ONE expert: gate, up and down."""
    d = _dims(cfg)
    return 3 * d["H"] * d["Im"], 2 * d["Im"] + d["H"]


def _tokens_read(cfg: dict, serving: dict) -> Optional[float]:
    """Tokens of latent cache one decode-kernel call reads (one layer, one
    step, the whole batch), from the pages its grid walked as measured: a
    row's last page counts half (it is half full on average, and an idle
    row's one program reads nothing)."""
    share = serving.get("attn_pages_walked_share")
    if share is None:
        return None
    slots = serving["max_batch"] * (serving["max_seq_len"] // serving["page"])
    pages = float(share) * slots
    return max(pages - serving["max_batch"] / 2.0, 0.0) * serving["page"]


def mla_decode_attention(cfg: dict, serving: dict) -> Optional[dict]:
    """One call of the latent decode kernel: every token's latent row read
    ONCE (it is the key and the value of all 64 heads); per head and token
    2 FLOPs a number of the key (rank + rope) and of the value (rank)."""
    tokens = _tokens_read(cfg, serving)
    if tokens is None:
        return None
    d = _dims(cfg)
    row = latent_row(cfg)
    return {"flops": d["Hq"] * tokens * 2.0 * (row + d["rank"]),
            "bytes": tokens * row * 2.0,
            "what": f"{tokens:.0f} tokens x {row} bf16 numbers read once; "
                    f"{d['Hq']} heads x 2 x ({row} + {d['rank']}) FLOPs a token"}


def _experts_touched(cfg: dict, serving: dict) -> Optional[float]:
    share = serving.get("experts_touched_share")
    return None if share is None else cfg["n_routed_experts"] * float(share)


def _local_assignments(cfg: dict, serving: dict, tokens: int) -> Optional[float]:
    share = serving.get("assignments_local_share")
    if share is None:
        return None
    return float(share) * tokens * cfg["num_experts_per_tok"]


def routed_experts(cfg: dict, serving: dict) -> Optional[dict]:
    """One expert layer's three grouped matmuls of one decode step: the int8
    matrices and f32 scales of the held experts touched, as measured, read
    once; 2 FLOPs a weight for each assignment that fell on a held expert."""
    touched = _experts_touched(cfg, serving)
    local = _local_assignments(cfg, serving, serving["max_batch"])
    if touched is None or local is None:
        return None
    weights, scales = expert_params(cfg)
    return {"flops": 2.0 * weights * local,
            "bytes": touched * (weights + 4.0 * scales),
            "what": f"{touched:.2f} of {cfg['n_routed_experts']} held experts' "
                    f"gate, up and down (int8 + f32 scales) read once; "
                    f"{local:.1f} assignments on them"}


def step_weights(cfg: dict, serving: dict) -> Optional[dict]:
    """What ONE decode step of the whole batch streams, weights only: every
    layer's attention, the dense layers' MLP, each expert layer's shared
    expert, float32 router and bias and the held experts touched as
    measured, the final norm's head over the rows held; int8 + f32 scales,
    each read once."""
    touched = _experts_touched(cfg, serving)
    local = _local_assignments(cfg, serving, serving["max_batch"])
    if touched is None or local is None:
        return None
    d = _dims(cfg)
    rows = serving["max_batch"]
    attn_w, attn_s = attention_params(cfg)
    exp_w, exp_s = expert_params(cfg)
    dense_w, dense_s = 3 * d["H"] * d["I"], 2 * d["I"] + d["H"]
    shared_w = 3 * d["H"] * d["shared"] * d["Im"]
    shared_s = 2 * d["shared"] * d["Im"] + d["H"]
    router = 4 * (d["H"] * d["E"] + d["E"])
    moe_layers = d["L"] - d["Ld"]
    weights = (d["L"] * attn_w + d["Ld"] * dense_w
               + moe_layers * (shared_w + touched * exp_w) + d["H"] * d["V"])
    scales = (d["L"] * attn_s + d["Ld"] * dense_s
              + moe_layers * (shared_s + touched * exp_s) + d["V"])
    every_token = (d["L"] * attn_w + d["Ld"] * dense_w
                   + moe_layers * (shared_w + d["H"] * d["E"])
                   + d["H"] * d["V"])
    return {"flops": 2.0 * rows * every_token
            + 2.0 * exp_w * local * moe_layers,
            "bytes": weights + 4.0 * scales + moe_layers * router,
            "what": f"{rows} rows; {d['Ld']} dense + {moe_layers} expert "
                    f"layers with {touched:.2f} of {d['held']} held experts "
                    f"touched, the head over {d['V']} rows"}


def latent_moe_step(cfg: dict, serving: dict) -> Optional[dict]:
    """The whole decode step: :func:`step_weights` plus the latent cache
    every layer's kernel reads, as walked (:func:`mla_decode_attention` a
    layer)."""
    weights = step_weights(cfg, serving)
    attn = mla_decode_attention(cfg, serving)
    if weights is None or attn is None:
        return None
    layers = cfg["num_hidden_layers"]
    return {"flops": weights["flops"] + layers * attn["flops"],
            "bytes": weights["bytes"] + layers * attn["bytes"],
            "what": weights["what"] + f"; + {layers} layers x "
            + attn["what"]}
