"""Operations and bytes of the Nemotron-H stack as one chip's share runs it,
from shapes and from what the program's counters MEASURED, by role
(``opcounts.py`` counts the llama family's; a configuration names this module
under ``counts``). The harness's parent process imports this module: no JAX.

Each function takes the configuration file and its serving block and returns
``{"flops", "bytes", "what"}`` for ONE execution of the thing named. Layers
are counted by kind, from the first ``num_hidden_layers`` characters of
``hybrid_override_pattern``: an M layer has a mixer and state, a * layer q, k,
v, o and pages, an E layer a router, the latent's two projections, a shared
expert and the routed experts HELD (``n_routed_experts`` and ``vocab_size``
are the chip's share; ``serving.experts_routed`` the router's width). What
shapes alone do not say is read from ``serving``, where a reader has put the
measured value (``kimi_k2_readers.roofline_measured``):

- ``experts_touched_share``: held experts with at least one token over held
  experts offered, over the forwards of decode chunks alone;
- ``assignments_local_share``: routed assignments that fell on held experts;
- ``attn_pages_walked_share``: pages the decode kernel's grid walked over the
  page table's slots;
- ``rows_running_share``: the round records' active rows over ``max_batch``,
  in percent (``batch_occupancy``): the rows whose state a step must move.

Without them the functions that need them return nothing to count (PERF.md,
PR 31: a uniform expectation read a roofline share over 100%). The role
``ssm_state_update`` is the one the accepted reader of falcon-h1's and
granite's cells asks for, answered at this model's sizes (granite's state
shape, 8 groups of B and C). There is no ``routed_experts`` role on purpose:
its accepted metric divides by ``moe_experts_us``, which prices a layer at
three kernel calls, and a layer here has two (``latent_experts`` over
``latent_experts_us``).
"""

from __future__ import annotations

from typing import Optional

# tokens of K/V one decode-kernel call reads (one * layer, one step, the whole
# batch), from the pages its grid walked as measured: it reads ``serving`` alone
from .granite_hybrid_counts import _pages_tokens


def _dims(cfg: dict) -> dict:
    kinds = cfg["hybrid_override_pattern"][: cfg["num_hidden_layers"]]
    head_dim = cfg["head_dim"]
    d_inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    conv = d_inner + 2 * gn
    return {"H": cfg["hidden_size"], "I": cfg["moe_intermediate_size"],
            "W": cfg["moe_latent_size"],
            "Is": cfg["moe_shared_expert_intermediate_size"],
            "V": cfg["vocab_size"], "L": len(kinds), "Lm": kinds.count("M"),
            "La": kinds.count("*"), "Le": kinds.count("E"),
            "held": cfg["n_routed_experts"],
            "E": cfg["serving"]["experts_routed"],
            "K": cfg["num_experts_per_tok"],
            "Dq": cfg["num_attention_heads"] * head_dim,
            "Dkv": cfg["num_key_value_heads"] * head_dim,
            "d_inner": d_inner, "Hs": cfg["mamba_num_heads"],
            "P": cfg["mamba_head_dim"], "N": cfg["ssm_state_size"],
            "G": cfg["n_groups"], "Kc": cfg["conv_kernel"], "conv": conv,
            "proj": d_inner + conv + cfg["mamba_num_heads"]}


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one token over the attention layers."""
    d = _dims(cfg)
    return d["La"] * 2 * d["Dkv"] * itemsize


def state_bytes_per_row(cfg: dict) -> int:
    """f32 recurrent state and conv tail of one row in ONE mamba layer."""
    d = _dims(cfg)
    return 4 * (d["Hs"] * d["P"] * d["N"] + (d["Kc"] - 1) * d["conv"])


def mixer_params(cfg: dict) -> tuple[int, int, int]:
    """(int8 weights, f32 scales, f32 small leaves) of one M layer."""
    d = _dims(cfg)
    small = (d["Kc"] + 1) * d["conv"] + 3 * d["Hs"] + d["d_inner"]
    return (d["H"] * d["proj"] + d["d_inner"] * d["H"],
            d["proj"] + d["H"], small)


def attention_params(cfg: dict) -> tuple[int, int]:
    """(int8 weights, f32 scales) of one * layer's q, k, v, o."""
    d = _dims(cfg)
    return (2 * d["H"] * d["Dq"] + 2 * d["H"] * d["Dkv"],
            d["Dq"] + 2 * d["Dkv"] + d["H"])


def expert_layer_dense_params(cfg: dict) -> tuple[int, int, int]:
    """(int8 weights, f32 scales, f32 router and bias) of one E layer outside
    its routed experts: the shared expert's two matrices and the latent's
    two projections."""
    d = _dims(cfg)
    return (2 * d["H"] * d["Is"] + 2 * d["H"] * d["W"],
            d["Is"] + d["H"] + d["W"] + d["H"], (d["H"] + 1) * d["E"])


def expert_params(cfg: dict) -> tuple[int, int]:
    """(int8 weights, f32 scales) of ONE routed expert: two matrices on the
    latent, no gate."""
    d = _dims(cfg)
    return 2 * d["W"] * d["I"], d["I"] + d["W"]


def ssm_state_update(cfg: dict, serving: dict) -> dict:
    """One call of the ``ssm_state_update`` kernel (one M layer, every row of
    the batch: the kernel's grid is over all rows, a row that does not run is
    read and written back as it was): each row's [Hs, P, N] f32 state read
    once and written once, its Δ·x and y ([Hs, P] f32 each), exp(Δ A) ([Hs]
    f32) and B, C ([G, N] f32 each). 5 FLOPs a state element."""
    rows = serving["max_batch"]
    d = _dims(cfg)
    elements = rows * d["Hs"] * d["P"] * d["N"]
    small = rows * 4 * (2 * d["Hs"] * d["P"] + d["Hs"] + 2 * d["G"] * d["N"])
    return {"flops": 5.0 * elements, "bytes": float(2 * 4 * elements + small),
            "what": f"{rows} rows' [{d['Hs']}, {d['P']}, {d['N']}] f32 state "
                    "read and written, with x, B, C, the decay and y"}


def _experts_touched(cfg: dict, serving: dict) -> Optional[float]:
    share = serving.get("experts_touched_share")
    return None if share is None else cfg["n_routed_experts"] * float(share)


def _local_assignments(cfg: dict, serving: dict) -> Optional[float]:
    share = serving.get("assignments_local_share")
    if share is None:
        return None
    return float(share) * serving["max_batch"] * cfg["num_experts_per_tok"]


def latent_experts(cfg: dict, serving: dict) -> Optional[dict]:
    """One expert layer's TWO grouped matmuls of one decode step: the int8
    matrices and f32 scales of the held experts touched, as measured, read
    once; 2 FLOPs a weight for each assignment that fell on a held expert."""
    touched = _experts_touched(cfg, serving)
    local = _local_assignments(cfg, serving)
    if touched is None or local is None:
        return None
    weights, scales = expert_params(cfg)
    return {"flops": 2.0 * weights * local,
            "bytes": touched * (weights + 4.0 * scales),
            "what": f"{touched:.2f} of {cfg['n_routed_experts']} held "
                    f"experts' two matrices (int8 + f32 scales) read once; "
                    f"{local:.1f} assignments on them"}


def ssm_latent_moe_step(cfg: dict, serving: dict) -> Optional[dict]:
    """What ONE whole decode step must move and compute: the M layers'
    mixers (int8 + f32 scales + the small f32 leaves), the * layers' q, k, v,
    o, every E layer's shared expert, latent projections, float32 router and
    bias and its held experts touched AS MEASURED over decode steps, the
    held head, each read once; the RUNNING rows' f32 state and conv tails
    read once and written once in every M layer; the K/V pages the * layers'
    kernel walked as measured. 2 FLOPs a weight a running row (a routed
    expert's: an assignment held), 5 a state element."""
    touched = _experts_touched(cfg, serving)
    local = _local_assignments(cfg, serving)
    tokens = _pages_tokens(cfg, serving)
    running = serving.get("rows_running_share")
    if None in (touched, local, tokens, running):
        return None
    d = _dims(cfg)
    share = float(running) / 100.0
    rows = serving["max_batch"] * share
    mix_w, mix_s, mix_small = mixer_params(cfg)
    att_w, att_s = attention_params(cfg)
    el_w, el_s, router = expert_layer_dense_params(cfg)
    ex_w, ex_s = expert_params(cfg)
    weights = (d["Lm"] * mix_w + d["La"] * att_w
               + d["Le"] * (el_w + touched * ex_w) + d["V"] * d["H"])
    f32 = (d["Lm"] * (mix_s + mix_small) + d["La"] * att_s
           + d["Le"] * (el_s + touched * ex_s + router) + d["V"])
    state = 2.0 * d["Lm"] * rows * state_bytes_per_row(cfg)
    pages = d["La"] * tokens * 2 * d["Dkv"] * 2.0
    every_token = (d["Lm"] * mix_w + d["La"] * att_w
                   + d["Le"] * (el_w + d["H"] * d["E"]) + d["V"] * d["H"])
    flops = (2.0 * rows * every_token
             + 2.0 * ex_w * local * share * d["Le"]
             + 5.0 * d["Lm"] * rows * d["Hs"] * d["P"] * d["N"]
             + d["La"] * 4.0 * tokens * d["Dq"])
    return {"flops": flops, "bytes": weights + 4.0 * f32 + state + pages,
            "what": f"{rows:.1f} running rows; {d['Lm']} M + {d['La']} * + "
                    f"{d['Le']} E layers, {touched:.2f} of {d['held']} held "
                    f"experts touched a layer, the head over {d['V']} rows; "
                    f"state {state / 1e9:.2f} GB, pages {pages / 1e9:.3f} GB, "
                    f"weights {(weights + 4.0 * f32) / 1e9:.2f} GB"}
