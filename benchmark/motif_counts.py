"""Operations and bytes of the Motif block as one chip's share runs it, from
shapes and from what the program's counters MEASURED, by role
(``kimi_k2_counts.py``'s contract: a configuration names this module under
``counts``; the harness's parent process imports it: no JAX).

Each function takes the configuration file and its serving block and returns
``{"flops", "bytes", "what"}`` for ONE execution of the thing named, or None
where a measured value it needs was not read. Of the configuration's keys
``num_experts`` and ``vocab_size`` are the chip's share. What shapes alone do
not say is read from ``serving``, where ``kimi_k2_readers.roofline_measured``
has put it:

- ``attn_pages_walked_share``: pages the decode kernel's grid walked in the
  layers of ONE page group over that group's page-table slots. For the full
  group it is ``llm_attn_pages_walked_total`` over ``_offered_total`` (the
  layers that attend over everything); for the window group
  ``llm_attn_window_pages_walked_total`` over
  ``llm_attn_window_pages_offered_total``. One call walks that share of
  ``max_batch x max_seq_len / page`` pages;
- ``window_pages_walked_share``: the window group's, where a count needs both
  (the whole step);
- ``experts_touched_share``, ``assignments_local_share``: as
  ``kimi_k2_counts.py`` reads them.

The counts describe the WORK: a token's latent row counts its 576 numbers,
not the 640 lanes the pool stores; a window layer's tokens are the pages its
grid walked, ``min(length, 128)`` rounded OUT to pages, since a page is read
whole.
"""

from __future__ import annotations

from typing import Optional


def _dims(cfg: dict) -> dict:
    heads, noise = cfg["num_attention_heads"], cfg["num_noise_heads"]
    period = cfg["sliding_window_period"]
    layers = cfg["num_hidden_layers"]
    return {"H": cfg["hidden_size"], "I": cfg["intermediate_size"],
            "Im": cfg["moe_intermediate_size"], "V": cfg["vocab_size"],
            "L": layers, "Ld": cfg["n_dense_first_layers"],
            "Lf": layers // period, "Lw": layers - layers // period,
            "held": cfg["num_experts"],
            "E": cfg["serving"]["experts_routed"],
            "K": cfg["experts_top_k"], "Hq": heads, "Hs": heads - noise,
            "G": cfg["num_key_value_heads"],
            "shared": cfg["num_shared_experts"],
            "q_rank": cfg["q_lora_rank"], "rank": cfg["kv_lora_rank"],
            "nope": cfg["head_dim"] - cfg["qk_rope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
            "S": cfg["mhc_expansion_rate"]}


def latent_row(cfg: dict) -> int:
    """Numbers a token caches a layer: the compressed row and the rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """What a token keeps for its row's whole length: the full layers'."""
    return _dims(cfg)["Lf"] * latent_row(cfg) * itemsize


def attention_params(cfg: dict) -> tuple[int, int]:
    """(int8 weights, f32 scales) of one layer's attention: W_dq, W_uq,
    W_dkv, W_ukv over the kv groups, w_lam, W_gate, W_o."""
    d = _dims(cfg)
    qk = d["nope"] + d["rope"]
    shapes = ((d["H"], d["q_rank"]), (d["q_rank"], d["Hq"] * qk),
              (d["H"], d["rank"] + d["rope"]),
              (d["rank"], d["G"] * (d["nope"] + d["v"])),
              (d["H"], d["Hs"]), (d["H"], d["Hs"] * d["v"]),
              (d["Hs"] * d["v"], d["H"]))
    return sum(k * n for k, n in shapes), sum(n for _, n in shapes)


def expert_params(cfg: dict) -> tuple[int, int]:
    """(int8 weights, f32 scales) of ONE expert: gate, up and down."""
    d = _dims(cfg)
    return 3 * d["H"] * d["Im"], 2 * d["Im"] + d["H"]


def mhc_bytes(cfg: dict) -> int:
    """float32 bytes of one layer's hyper-connection maps (two sub-layers:
    phi, the norm's weight, the biases and alphas)."""
    d = _dims(cfg)
    wide, maps = d["S"] * d["H"], 2 * d["S"] + d["S"] ** 2
    return 2 * 4 * (wide * maps + wide + maps + 3)


def _tokens_read(serving: dict, key: str) -> Optional[float]:
    """Tokens of latent cache one decode-kernel call of a page group reads
    (one layer, one step, the whole batch), from the pages its grid walked
    as measured (``kimi_k2_counts._tokens_read``: a row's last page counts
    half)."""
    share = serving.get(key)
    if share is None:
        return None
    slots = serving["max_batch"] * (serving["max_seq_len"] // serving["page"])
    pages = float(share) * slots
    return max(pages - serving["max_batch"] / 2.0, 0.0) * serving["page"]


def gdla_decode_attention(cfg: dict, serving: dict,
                          key: str = "attn_pages_walked_share"
                          ) -> Optional[dict]:
    """One call of the latent decode kernel at either call site (the
    metric's file hands the page group's walked share in): every token's
    latent row read ONCE (it is the key and the value of all 80 query
    heads); per head and token 2 FLOPs a number of the key (rank + rope) and
    of the value (rank)."""
    tokens = _tokens_read(serving, key)
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
    return None if share is None else cfg["num_experts"] * float(share)


def _local_assignments(cfg: dict, serving: dict, tokens: int) -> Optional[float]:
    share = serving.get("assignments_local_share")
    if share is None:
        return None
    return float(share) * tokens * cfg["experts_top_k"]


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
    """What ONE decode step of the whole batch streams, weights only: every
    layer's attention and float32 hyper-connection maps, the dense layers'
    MLP, each expert layer's shared expert, float32 router and the held
    experts touched as measured, the head over the rows held; int8 + f32
    scales, each read once."""
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
    router = 4 * d["H"] * d["E"]
    moe_layers = d["L"] - d["Ld"]
    weights = (d["L"] * attn_w + d["Ld"] * dense_w
               + moe_layers * (shared_w + touched * exp_w) + d["H"] * d["V"])
    scales = (d["L"] * attn_s + d["Ld"] * dense_s
              + moe_layers * (shared_s + touched * exp_s) + d["V"])
    maps = d["S"] * d["H"] * (2 * d["S"] + d["S"] ** 2)
    every_token = (d["L"] * (attn_w + 2 * maps) + d["Ld"] * dense_w
                   + moe_layers * (shared_w + d["H"] * d["E"])
                   + d["H"] * d["V"])
    return {"flops": 2.0 * rows * every_token
            + 2.0 * exp_w * local * moe_layers,
            "bytes": weights + 4.0 * scales + moe_layers * router
            + d["L"] * mhc_bytes(cfg),
            "what": f"{rows} rows; {d['Ld']} dense + {moe_layers} expert "
                    f"layers with {touched:.2f} of {d['held']} held experts "
                    f"touched, f32 maps of {d['S']} streams, the head over "
                    f"{d['V']} rows"}


def gdla_moe_step(cfg: dict, serving: dict) -> Optional[dict]:
    """The whole decode step: :func:`step_weights` plus the latent cache
    the kernels read, as walked: the full layers' over their rows' whole
    length, the window layers' over their windows."""
    weights = step_weights(cfg, serving)
    full = gdla_decode_attention(cfg, serving)
    window = gdla_decode_attention(cfg, serving, "window_pages_walked_share")
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
