"""Operations and bytes of the Granite-4.0-H stack, from shapes and from what
the program's counters MEASURED, by role (``opcounts.py`` counts the llama
family's; a configuration names this module under ``counts``). The harness's
parent process imports this module: no JAX.

Each function takes the configuration file and its serving block and returns
``{"flops", "bytes", "what"}`` for ONE execution of the thing named. Layers
are counted by kind, from the first ``num_hidden_layers`` of ``layer_types``:
a mamba layer has a mixer and state, an attention layer q, k, v, o and pages,
and every layer the expert layer. What shapes alone do not say is read from
``serving``, where a reader has put the measured value
(``kimi_k2_readers.roofline_measured``):

- ``experts_touched_share``: experts with at least one token over experts
  offered, over the forwards of decode chunks alone
  (``llm_moe_decode_experts_touched_total`` over ``_offered_total``);
- ``assignments_local_share``: routed assignments that fell on experts held
  here (every expert is held: 1.0);
- ``attn_pages_walked_share``: pages the decode kernel's grid walked over the
  page table's slots, so one call walks that share of ``max_batch x
  max_seq_len / page`` pages;
- ``rows_running_share``: the round records' active rows over ``max_batch``,
  in percent (``batch_occupancy``): the rows whose state a step must move.

Without them the functions that need them return nothing to count: there is
no expectation from shapes here on purpose (PERF.md, PR 31: a uniform
expectation read a roofline share over 100%). The roles ``ssm_state_update``
and ``routed_experts`` are the ones the accepted readers of falcon-h1's and
kimi's cells ask for, answered at this model's sizes.
"""

from __future__ import annotations

from typing import Optional


def _dims(cfg: dict) -> dict:
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    head_dim = hidden // heads
    kinds = list(cfg["layer_types"][: cfg["num_hidden_layers"]])
    d_inner = cfg["mamba_expand"] * hidden
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    conv = d_inner + 2 * gn
    return {"H": hidden, "I": cfg["intermediate_size"],
            "Is": cfg["shared_intermediate_size"], "V": cfg["vocab_size"],
            "L": len(kinds), "Lm": kinds.count("mamba"),
            "La": kinds.count("attention"),
            "E": cfg["num_local_experts"], "K": cfg["num_experts_per_tok"],
            "Dq": heads * head_dim,
            "Dkv": cfg["num_key_value_heads"] * head_dim,
            "d_inner": d_inner, "Hs": cfg["mamba_n_heads"],
            "P": cfg["mamba_d_head"], "N": cfg["mamba_d_state"],
            "G": cfg["mamba_n_groups"], "Kc": cfg["mamba_d_conv"],
            "conv": conv, "proj": d_inner + conv + cfg["mamba_n_heads"]}


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one token over the attention layers."""
    d = _dims(cfg)
    return d["La"] * 2 * d["Dkv"] * itemsize


def state_bytes_per_row(cfg: dict) -> int:
    """f32 recurrent state and conv tail of one row in ONE mamba layer."""
    d = _dims(cfg)
    return 4 * (d["Hs"] * d["P"] * d["N"] + (d["Kc"] - 1) * d["conv"])


def mixer_params(cfg: dict) -> tuple[int, int, int]:
    """(int8 weights, f32 scales, f32 small leaves) of one mamba layer's
    mixer: W_in and W_out; the conv's taps and bias, A_log, D, dt_bias and
    the gated norm's weight."""
    d = _dims(cfg)
    small = (d["Kc"] + 1) * d["conv"] + 3 * d["Hs"] + d["d_inner"]
    return (d["H"] * d["proj"] + d["d_inner"] * d["H"],
            d["proj"] + d["H"], small)


def attention_params(cfg: dict) -> tuple[int, int]:
    """(int8 weights, f32 scales) of one attention layer's q, k, v, o."""
    d = _dims(cfg)
    return (2 * d["H"] * d["Dq"] + 2 * d["H"] * d["Dkv"],
            d["Dq"] + 2 * d["Dkv"] + d["H"])


def shared_params(cfg: dict) -> tuple[int, int]:
    d = _dims(cfg)
    return 3 * d["H"] * d["Is"], 2 * d["Is"] + d["H"]


def expert_params(cfg: dict) -> tuple[int, int]:
    """(int8 weights, f32 scales) of ONE expert: gate, up and down."""
    d = _dims(cfg)
    return 3 * d["H"] * d["I"], 2 * d["I"] + d["H"]


def ssm_state_update(cfg: dict, serving: dict) -> dict:
    """One call of the ``ssm_state_update`` kernel (one mamba layer, every
    row of the batch: the kernel's grid is over all rows, a row that does not
    run is read and written back as it was): each row's [Hs, P, N] f32 state
    read once and written once, its Δ·x and y ([Hs, P] f32 each), exp(Δ A)
    ([Hs] f32) and B, C ([G, N] f32 each). Per state element: one multiply by
    the decay, a multiply-add of the outer product, a multiply-add into y:
    5 FLOPs."""
    rows = serving["max_batch"]
    d = _dims(cfg)
    elements = rows * d["Hs"] * d["P"] * d["N"]
    small = rows * 4 * (2 * d["Hs"] * d["P"] + d["Hs"] + 2 * d["G"] * d["N"])
    return {"flops": 5.0 * elements, "bytes": float(2 * 4 * elements + small),
            "what": f"{rows} rows' [{d['Hs']}, {d['P']}, {d['N']}] f32 state "
                    "read and written, with x, B, C, the decay and y"}


def _experts_touched(cfg: dict, serving: dict) -> Optional[float]:
    share = serving.get("experts_touched_share")
    return None if share is None else cfg["num_local_experts"] * float(share)


def routed_experts(cfg: dict, serving: dict) -> Optional[dict]:
    """One expert layer's three grouped matmuls of one decode step: the int8
    matrices and f32 scales of the experts touched, as measured, read once;
    2 FLOPs a weight an assignment (``max_batch`` rows x K, times the share
    that fell on experts held here: all of them)."""
    touched = _experts_touched(cfg, serving)
    local = serving.get("assignments_local_share")
    if touched is None or local is None:
        return None
    assignments = float(local) * serving["max_batch"] * cfg["num_experts_per_tok"]
    weights, scales = expert_params(cfg)
    return {"flops": 2.0 * weights * assignments,
            "bytes": touched * (weights + 4.0 * scales),
            "what": f"{touched:.2f} of {cfg['num_local_experts']} experts' "
                    f"gate, up and down (int8 + f32 scales) read once; "
                    f"{assignments:.0f} assignments"}


def _pages_tokens(cfg: dict, serving: dict) -> Optional[float]:
    """Tokens of K/V one decode-kernel call reads (one attention layer, one
    step, the whole batch), from the pages its grid walked as measured: a
    row's last page counts half (it is half full on average, and an idle
    row's one program reads nothing)."""
    share = serving.get("attn_pages_walked_share")
    if share is None:
        return None
    slots = serving["max_batch"] * (serving["max_seq_len"] // serving["page"])
    pages = float(share) * slots
    return max(pages - serving["max_batch"] / 2.0, 0.0) * serving["page"]


def hybrid_moe_step(cfg: dict, serving: dict) -> Optional[dict]:
    """What ONE whole decode step must move and compute: the mamba layers'
    mixers (int8 + f32 scales + the small f32 leaves), the attention layers'
    q, k, v, o, every layer's shared MLP and float32 router and its experts
    touched AS MEASURED over decode steps, the tied head, each read once;
    the RUNNING rows' f32 state and conv tails read once and written once in
    every mamba layer; the K/V pages the attention layers' kernel walked as
    measured. 2 FLOPs a weight a running row (an expert's: an assignment),
    5 a state element."""
    touched = _experts_touched(cfg, serving)
    tokens = _pages_tokens(cfg, serving)
    running = serving.get("rows_running_share")
    if touched is None or tokens is None or running is None:
        return None
    d = _dims(cfg)
    rows = serving["max_batch"] * float(running) / 100.0
    mix_w, mix_s, mix_small = mixer_params(cfg)
    att_w, att_s = attention_params(cfg)
    sh_w, sh_s = shared_params(cfg)
    ex_w, ex_s = expert_params(cfg)
    router = d["H"] * d["E"]
    weights = (d["Lm"] * mix_w + d["La"] * att_w
               + d["L"] * (sh_w + touched * ex_w) + d["V"] * d["H"])
    f32 = (d["Lm"] * (mix_s + mix_small) + d["La"] * att_s
           + d["L"] * (sh_s + touched * ex_s + router) + d["V"])
    state = 2.0 * d["Lm"] * rows * state_bytes_per_row(cfg)
    pages = d["La"] * tokens * 2 * d["Dkv"] * 2.0
    every_token = (d["Lm"] * mix_w + d["La"] * att_w
                   + d["L"] * (sh_w + router) + d["V"] * d["H"])
    flops = (2.0 * rows * every_token
             + 2.0 * ex_w * rows * d["K"] * d["L"]
             + 5.0 * d["Lm"] * rows * d["Hs"] * d["P"] * d["N"]
             + d["La"] * 4.0 * tokens * d["Dq"])
    return {"flops": flops, "bytes": weights + 4.0 * f32 + state + pages,
            "what": f"{rows:.1f} running rows; {d['Lm']} mamba + {d['La']} "
                    f"attention layers, {touched:.2f} of {d['E']} experts "
                    f"touched a layer, the tied head over {d['V']} rows; "
                    f"state {state / 1e9:.2f} GB, pages {pages / 1e9:.3f} GB, "
                    f"weights {(weights + 4.0 * f32) / 1e9:.2f} GB"}
