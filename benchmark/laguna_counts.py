"""Operations and bytes of the Laguna block as one chip's share runs it, from
shapes and from what the program's counters MEASURED, by role
(``kimi_k2_counts.py``'s contract: a configuration names this module under
``counts``; the harness's parent process imports it: no JAX).

Each function takes the configuration file and its serving block and returns
``{"flops", "bytes", "what"}`` for ONE execution of the thing named, or None
where a measured value it needs was not read. Of the configuration's keys
``num_experts`` and ``vocab_size`` are the chip's share; the kinds of the
layers are read from the published per-layer lists (``layer_types``,
``num_attention_heads_per_layer``, ``mlp_only_layers``), cut at
``num_hidden_layers``. What shapes alone do not say is read from ``serving``,
where ``kimi_k2_readers.roofline_measured`` has put it:

- ``attn_pages_walked_share``: pages the decode kernel's grid walked in the
  FULL layers over the full group's page-table slots
  (``llm_attn_pages_walked_total`` over ``llm_attn_pages_offered_total``);
- ``window_pages_walked_share``: the same of the WINDOW layers
  (``llm_attn_window_pages_walked_total`` over ``_offered_total``). One call
  of a kind walks that share of ``max_batch x max_seq_len / page`` pages;
- ``experts_touched_share``, ``assignments_local_share``: as
  ``kimi_k2_counts.py`` reads them.

A K/V page is read WHOLE (a grid program's block is a page of K and a page
of V, every kv head), so a call's bytes are its pages walked x page x 2 x
kv heads x head_dim x 2 B, the row's last, part-filled page too.
"""

from __future__ import annotations

from typing import Optional


def _dims(cfg: dict) -> dict:
    L = cfg["num_hidden_layers"]
    full = [t == "full_attention" for t in cfg["layer_types"][:L]]
    heads = cfg["num_attention_heads_per_layer"][:L]
    Ld = sum(1 for l in cfg["mlp_only_layers"] if l < L)
    return {"H": cfg["hidden_size"], "I": cfg["intermediate_size"],
            "Im": cfg["moe_intermediate_size"],
            "Is": cfg["shared_expert_intermediate_size"],
            "V": cfg["vocab_size"], "L": L, "Ld": Ld, "Lm": L - Ld,
            "Lf": sum(full), "Lw": L - sum(full),
            "Hf": next((h for h, f in zip(heads, full) if f),
                       cfg["num_attention_heads"]),
            "Hw": next((h for h, f in zip(heads, full) if not f),
                       cfg["num_attention_heads"]),
            "Hkv": cfg["num_key_value_heads"], "D": cfg["head_dim"],
            "held": cfg["num_experts"],
            "E": cfg["serving"]["experts_routed"],
            "K": cfg["num_experts_per_tok"],
            "gate": cfg.get("gating") == "per-head"}


def kv_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes a token caches in ONE layer: K and V of every kv head."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """What a token keeps for its row's whole length: the full layers'."""
    return _dims(cfg)["Lf"] * kv_row_bytes(cfg, itemsize)


def window_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """What it keeps while it lies inside the window: the window layers'."""
    return _dims(cfg)["Lw"] * kv_row_bytes(cfg, itemsize)


def attention_params(cfg: dict, heads: int) -> tuple[int, int]:
    """(int8 weights, f32 scales) of one attention layer of ``heads`` query
    heads: W_q, W_k, W_v, W_o and the gate a head."""
    d = _dims(cfg)
    dq, dkv = heads * d["D"], d["Hkv"] * d["D"]
    shapes = [(d["H"], dq), (d["H"], dkv), (d["H"], dkv), (dq, d["H"])]
    if d["gate"]:
        shapes.append((d["H"], heads))
    return sum(k * n for k, n in shapes), sum(n for _, n in shapes)


def expert_params(cfg: dict) -> tuple[int, int]:
    """(int8 weights, f32 scales) of ONE expert: gate, up and down."""
    d = _dims(cfg)
    return 3 * d["H"] * d["Im"], 2 * d["Im"] + d["H"]


def _pages_walked(serving: dict, key: str) -> Optional[float]:
    """Pages one decode-kernel call of a page group walks (one layer, one
    step, the whole batch), from the share its grid walked as measured."""
    share = serving.get(key)
    if share is None:
        return None
    return float(share) * serving["max_batch"] * (
        serving["max_seq_len"] // serving["page"])


def _decode_attention(cfg: dict, serving: dict, heads: int,
                      key: str) -> Optional[dict]:
    pages = _pages_walked(serving, key)
    if pages is None:
        return None
    tokens = pages * serving["page"]
    d = _dims(cfg)
    return {"flops": heads * tokens * 4.0 * d["D"],
            "bytes": tokens * kv_row_bytes(cfg),
            "what": f"{pages:.0f} whole pages x {serving['page']} tokens x "
                    f"{kv_row_bytes(cfg)} B of K and V read once; {heads} "
                    f"heads x 4 x {d['D']} FLOPs a key"}


def gqa_full_decode_attention(cfg: dict, serving: dict) -> Optional[dict]:
    """One call of the K/V decode kernel in a FULL layer: the pages of every
    row's whole length, scores and values of ``Hf`` query heads."""
    return _decode_attention(cfg, serving, _dims(cfg)["Hf"],
                             "attn_pages_walked_share")


def gqa_window_decode_attention(cfg: dict, serving: dict) -> Optional[dict]:
    """One call in a WINDOW layer: the pages of every row's window's span,
    ``Hw`` query heads."""
    return _decode_attention(cfg, serving, _dims(cfg)["Hw"],
                             "window_pages_walked_share")


def _experts_touched(cfg: dict, serving: dict) -> Optional[float]:
    share = serving.get("experts_touched_share")
    return None if share is None else cfg["num_experts"] * float(share)


def _local_assignments(cfg: dict, serving: dict, tokens: int) -> Optional[float]:
    share = serving.get("assignments_local_share")
    if share is None:
        return None
    return float(share) * tokens * cfg["num_experts_per_tok"]


def routed_experts(cfg: dict, serving: dict) -> Optional[dict]:
    """One expert layer's three grouped matmuls of one decode step
    (``kimi_k2_counts.routed_experts`` at this block's widths)."""
    touched = _experts_touched(cfg, serving)
    local = _local_assignments(cfg, serving, serving["max_batch"])
    if touched is None or local is None:
        return None
    weights, scales = expert_params(cfg)
    return {"flops": 2.0 * weights * local,
            "bytes": touched * (weights + 4.0 * scales),
            "what": f"{touched:.2f} of {cfg['num_experts']} held experts' "
                    f"gate, up and down (int8 + f32 scales) read once; "
                    f"{local:.1f} assignments on them"}


def step_weights(cfg: dict, serving: dict) -> Optional[dict]:
    """What ONE decode step of the whole batch streams, weights only: the
    attention of both kinds of layer, the dense layers' MLP, each expert
    layer's shared expert, float32 router and the held experts touched as
    measured, the head over the rows held; int8 + f32 scales, each read
    once."""
    touched = _experts_touched(cfg, serving)
    local = _local_assignments(cfg, serving, serving["max_batch"])
    if touched is None or local is None:
        return None
    d = _dims(cfg)
    rows = serving["max_batch"]
    full_w, full_s = attention_params(cfg, d["Hf"])
    win_w, win_s = attention_params(cfg, d["Hw"])
    exp_w, exp_s = expert_params(cfg)
    dense_w, dense_s = 3 * d["H"] * d["I"], 2 * d["I"] + d["H"]
    shared_w, shared_s = 3 * d["H"] * d["Is"], 2 * d["Is"] + d["H"]
    router = 4 * d["H"] * d["E"]
    attention_w = d["Lf"] * full_w + d["Lw"] * win_w
    weights = (attention_w + d["Ld"] * dense_w
               + d["Lm"] * (shared_w + touched * exp_w) + d["H"] * d["V"])
    scales = (d["Lf"] * full_s + d["Lw"] * win_s + d["Ld"] * dense_s
              + d["Lm"] * (shared_s + touched * exp_s) + d["V"])
    every_token = (attention_w + d["Ld"] * dense_w
                   + d["Lm"] * (shared_w + d["H"] * d["E"])
                   + d["H"] * d["V"])
    return {"flops": 2.0 * rows * every_token
            + 2.0 * exp_w * local * d["Lm"],
            "bytes": weights + 4.0 * scales + d["Lm"] * router,
            "what": f"{rows} rows; {d['Lf']} full + {d['Lw']} window "
                    f"attention layers, {d['Ld']} dense + {d['Lm']} expert "
                    f"layers with {touched:.2f} of {d['held']} held experts "
                    f"touched, the head over {d['V']} rows"}


def decode_step(cfg: dict, serving: dict) -> Optional[dict]:
    """The whole decode step: :func:`step_weights` plus the K/V pages the
    kernels read, as walked: the full layers' over their rows' whole length,
    the window layers' over their windows."""
    weights = step_weights(cfg, serving)
    full = gqa_full_decode_attention(cfg, serving)
    window = gqa_window_decode_attention(cfg, serving)
    if weights is None or full is None or window is None:
        return None
    d = _dims(cfg)
    return {"flops": weights["flops"] + d["Lf"] * full["flops"]
            + d["Lw"] * window["flops"],
            "bytes": weights["bytes"] + d["Lf"] * full["bytes"]
            + d["Lw"] * window["bytes"],
            "what": weights["what"] + f"; + {d['Lf']} full layers x "
            + full["what"] + f"; + {d['Lw']} window layers x "
            + window["what"]}
