"""Operations and bytes of the Ouro block, from shapes and from what the
program's counters MEASURED, by role (``kimi_k2_counts.py``'s contract: a
configuration names this module under ``counts``; the harness's parent
process imports it: no JAX).

Each function takes the configuration file and its serving block and returns
``{"flops", "bytes", "what"}`` for ONE execution of the thing named, or None
where a measured value it needs was not read. What shapes alone do not say is
read from ``serving``, where ``kimi_k2_readers.roofline_measured`` has put it:

- ``attn_pages_walked_share``: pages the K/V decode kernel's grid walked over
  the slots of the page table, summed over steps and cache layers
  (``llm_attn_pages_walked_total`` over ``llm_attn_pages_offered_total``).
  One call (one cache layer, one step, the whole batch) walks that share of
  ``max_batch x ceil(max_seq_len / page)`` pages.

**A layer is counted by what it does, not by what it stores**: the 48 layers'
weights are stored once and READ ``total_ut_steps`` times a step, and a step
walks ``total_ut_steps x num_hidden_layers`` cache layers. A page is read
whole (the kernel's block is the page), so a row's last page counts all 64
tokens.
"""

from __future__ import annotations

from typing import Optional


def _dims(cfg: dict) -> dict:
    hd = cfg["head_dim"]
    return {"H": cfg["hidden_size"], "I": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "R": cfg["total_ut_steps"], "Hq": cfg["num_attention_heads"],
            "D": hd, "Dq": cfg["num_attention_heads"] * hd,
            "Dkv": cfg["num_key_value_heads"] * hd}


def layer_params(cfg: dict) -> tuple[int, int]:
    """(int8 weights, f32 scales) of ONE layer: W_q W_k W_v W_o and the
    SwiGLU's three matrices, a scale an output channel."""
    d = _dims(cfg)
    weights = (d["H"] * d["Dq"] + 2 * d["H"] * d["Dkv"] + d["Dq"] * d["H"]
               + 3 * d["H"] * d["I"])
    scales = d["Dq"] + 2 * d["Dkv"] + d["H"] + 2 * d["I"] + d["H"]
    return weights, scales


def cache_layers(cfg: dict) -> int:
    """Cache layers a token holds: a layer a pass."""
    return cfg["num_hidden_layers"] * cfg["total_ut_steps"]


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return cache_layers(cfg) * 2 * _dims(cfg)["Dkv"] * itemsize


def _pages_walked(serving: dict) -> Optional[float]:
    """Pages ONE call of the decode kernel walks, as measured."""
    share = serving.get("attn_pages_walked_share")
    if share is None:
        return None
    slots = serving["max_batch"] * -(-serving["max_seq_len"] // serving["page"])
    return float(share) * slots


def paged_decode_attention(cfg: dict, serving: dict) -> Optional[dict]:
    """One call of the K/V decode kernel (one cache layer, one step, the
    whole batch): the pages its grid walked, K and V, read once; per cached
    token and query head 2 FLOPs a number of the key and of the value."""
    pages = _pages_walked(serving)
    if pages is None:
        return None
    d = _dims(cfg)
    tokens = pages * serving["page"]
    return {"flops": tokens * d["Hq"] * 4.0 * d["D"],
            "bytes": tokens * 2.0 * d["Dkv"] * 2.0,
            "what": f"{pages:.1f} pages x {serving['page']} tokens x K and V "
                    f"x {d['Dkv']} bf16 numbers read once; {d['Hq']} heads x "
                    f"4 x {d['D']} FLOPs a token"}


def loop_step(cfg: dict, serving: dict) -> Optional[dict]:
    """One whole decode step of the batch: the layers' int8 weights and f32
    scales read ``total_ut_steps`` times, the head once, and the K/V pages
    of every cache layer as walked; 2 FLOPs a weight a row a pass, the head's
    once, and the attention's."""
    attn = paged_decode_attention(cfg, serving)
    if attn is None:
        return None
    d = _dims(cfg)
    rows = serving["max_batch"]
    weights, scales = layer_params(cfg)
    per_pass = d["L"] * (weights + 4 * scales)
    head = d["H"] * d["V"] + 4 * d["V"]
    layers = cache_layers(cfg)
    return {"flops": 2.0 * rows * (d["R"] * d["L"] * weights
                                   + d["H"] * d["V"])
            + layers * attn["flops"],
            "bytes": float(d["R"] * per_pass + head) + layers * attn["bytes"],
            "what": f"{rows} rows; {d['L']} layers' int8 weights + f32 "
                    f"scales read {d['R']} times, the head once; + "
                    f"{layers} cache layers x " + attn["what"]}
