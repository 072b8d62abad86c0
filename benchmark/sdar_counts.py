"""Operations and bytes of the SDAR-MoE block, from shapes alone, by role
(``opcounts.py`` counts the llama family's; a configuration names this module
under ``counts``). The harness's parent process imports this module: no JAX.

Each function takes the published configuration and the serving block of its
file and returns ``{"flops", "bytes", "what"}`` for ONE execution: ONE FORWARD
of every running row's open block (``serving.block_length`` positions a row),
denoise or commit alike; the ``paged_decode_chunk`` program runs
``serving.decode_chunk`` of them. There is no ``decode_step_weights`` here on
purpose: what a forward streams depends on how its tokens route, which shapes
do not say, so the llama family's ``decode_step_roofline`` finds nothing to
read for this architecture and ``block_forward_roofline`` counts the experts
as measured.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    return {"H": cfg["hidden_size"], "I": cfg["moe_intermediate_size"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "E": cfg["num_experts"], "K": cfg["num_experts_per_tok"],
            "Dq": cfg["num_attention_heads"] * cfg["head_dim"],
            "Dkv": cfg["num_key_value_heads"] * cfg["head_dim"]}


def tokens_per_forward(serving: dict) -> int:
    """Positions one forward of the full batch computes."""
    return serving["max_batch"] * serving["block_length"]


def experts_touched(cfg: dict, serving: dict) -> float:
    """Experts of one layer that receive at least one of a forward's
    assignments. ``serving["experts_touched_share"]``, where a reader has put
    the MEASURED share there (``sdar_readers.roofline_touched``), times the
    experts there are: routing decides what a forward streams, and seeded
    weights under greedy decoding route far from uniformly (a forward's
    tokens repeat). Without it, the expectation under uniform routing,
    ``E (1 - (1 - K/E)^tokens)``: 125.9 of 128 at 64 tokens top-8."""
    d = _dims(cfg)
    share = serving.get("experts_touched_share")
    if share is not None:
        return d["E"] * float(share)
    return d["E"] * (1.0 - (1.0 - d["K"] / d["E"]) ** tokens_per_forward(serving))


def _attention_params(d: dict) -> int:
    return d["H"] * d["Dq"] + 2 * d["H"] * d["Dkv"] + d["Dq"] * d["H"]


def moe_experts(cfg: dict, serving: dict) -> dict:
    """One layer's expert matmuls of one forward (the three grouped matmuls:
    gate, up, down): the int8 matrices and f32 scales of the experts touched
    read once; 2 FLOPs a weight for each of a token's ``K`` experts."""
    d = _dims(cfg)
    touched = experts_touched(cfg, serving)
    per_expert = 3 * d["H"] * d["I"]
    scales = 4 * (2 * d["I"] + d["H"])
    return {"flops": 2.0 * per_expert * d["K"] * tokens_per_forward(serving),
            "bytes": float(touched * (per_expert + scales)),
            "what": f"{touched:.1f} of {d['E']} experts' int8 gate, up and "
                    f"down matrices and f32 scales read once; "
                    f"{tokens_per_forward(serving)} tokens x {d['K']} experts"}


def forward_weights(cfg: dict, serving: dict) -> dict:
    """One forward of the whole batch: a layer's attention matrices and
    float32 router, the experts touched (:func:`experts_touched`) and the
    lm-head, each read once with its f32 scales; 2 FLOPs
    a weight a position, each position through ``K`` experts. K/V reads are
    NOT counted, so the bytes are a lower bound."""
    d = _dims(cfg)
    n = tokens_per_forward(serving)
    moe = moe_experts(cfg, serving)
    attn = _attention_params(d)
    attn_scales = 4 * (d["Dq"] + 2 * d["Dkv"] + d["H"])
    router = 4 * d["H"] * d["E"]
    layer_bytes = attn + attn_scales + router + moe["bytes"]
    layer_flops = 2.0 * n * (attn + d["H"] * d["E"]) + moe["flops"]
    return {"flops": d["L"] * layer_flops + 2.0 * n * d["H"] * d["V"],
            "bytes": float(d["L"] * layer_bytes + d["H"] * d["V"] + 4 * d["V"]),
            "what": f"one forward of {n} positions: attention, f32 router, "
                    f"head and {experts_touched(cfg, serving):.1f} of "
                    f"{d['E']} experts a layer, int8 + f32 scales, read once"}
