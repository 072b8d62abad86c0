"""Operations and bytes of the Solar-Open2 stack as one chip's share runs it,
from shapes and from what the program's counters MEASURED, by role
(``opcounts.py`` counts the llama family's; a configuration names this module
under ``counts``). The harness's parent process imports this module: no JAX.

Each function takes the configuration file and its serving block and returns
``{"flops", "bytes", "what"}`` for ONE execution of the thing named. Layers
are counted by kind: layer ``l`` under ``num_hidden_layers`` is a GQA layer
where ``l`` is in ``gqa_layers`` (q, k, v, o, the output gate and pages) and
a KDA layer elsewhere (q, k, v, o, two low-rank pairs, β, the small f32
leaves and a row of state); EVERY layer holds an expert layer (a float32
router, a shared expert and the routed experts HELD: ``n_routed_experts`` and
``vocab_size`` are the chip's share, ``serving.experts_routed`` the router's
width). What shapes alone do not say is read from ``serving``, where a reader
has put the measured value (``kimi_k2_readers.roofline_measured``):

- ``experts_touched_share``: held experts with at least one token over held
  experts offered, over the forwards of decode chunks alone;
- ``assignments_local_share``: routed assignments that fell on held experts;
- ``attn_pages_walked_share``: pages the decode kernel's grid walked over the
  page table's slots;
- ``rows_running_share``: the round records' active rows over ``max_batch``,
  in percent (``batch_occupancy``): the rows whose state a step must move.

Without them the functions that need them return nothing to count (PERF.md,
PR 31: a uniform expectation read a roofline share over 100%). The role
``routed_experts`` is the one kimi's and granite's accepted metric asks for
(three grouped matmuls a layer, as ``moe_experts_us`` prices them), answered
at this model's sizes. There is no ``ssm_state_update`` role on purpose: that
metric reads the Mamba-2 kernel, which this model does not run
(``kda_state_update`` is its own).
"""

from __future__ import annotations

from typing import Optional

# tokens of K/V one decode-kernel call reads (one GQA layer, one step, the
# whole batch), from the pages its grid walked as measured: it reads
# ``serving`` alone
from .granite_hybrid_counts import _pages_tokens
# the held experts touched and the assignments held, from the measured shares
# (``n_routed_experts`` held, ``num_experts_per_tok`` a row: nemotron's keys)
from .nemotron_h_counts import _experts_touched, _local_assignments


def _dims(cfg: dict) -> dict:
    layers = cfg["num_hidden_layers"]
    gqa = sum(1 for l in cfg["gqa_layers"] if l < layers)
    linear, head_dim = cfg["linear_attn_config"], cfg["head_dim"]
    return {"H": cfg["hidden_size"], "I": cfg["moe_intermediate_size"],
            "Is": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "V": cfg["vocab_size"], "L": layers, "Lk": layers - gqa,
            "La": gqa, "held": cfg["n_routed_experts"],
            "E": cfg["serving"]["experts_routed"],
            "K": cfg["num_experts_per_tok"],
            "Dq": cfg["num_attention_heads"] * head_dim,
            "Dkv": cfg["num_key_value_heads"] * head_dim,
            "Hs": linear["num_heads"], "D": linear["head_dim"],
            "W": linear["num_heads"] * linear["head_dim"],
            "Kc": linear["short_conv_kernel_size"]}


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one token over the GQA layers."""
    d = _dims(cfg)
    return d["La"] * 2 * d["Dkv"] * itemsize


def state_bytes_per_row(cfg: dict) -> int:
    """f32 state and conv tail (over q, k AND v) of one row in ONE KDA
    layer."""
    d = _dims(cfg)
    return 4 * (d["Hs"] * d["D"] * d["D"] + (d["Kc"] - 1) * 3 * d["W"])


def kda_params(cfg: dict) -> tuple[int, int, int]:
    """(int8 weights, f32 scales, f32 small leaves) of one KDA layer: q, k,
    v, o, the decay's and the gate's low-rank pairs, β; the conv's taps,
    A_log, dt_bias and the head norm's weight."""
    d = _dims(cfg)
    weights = (4 * d["H"] * d["W"] + 2 * (d["H"] * d["D"] + d["D"] * d["W"])
               + d["H"] * d["Hs"])
    scales = 3 * d["W"] + d["H"] + 2 * (d["D"] + d["W"]) + d["Hs"]
    small = d["Kc"] * 3 * d["W"] + d["Hs"] + d["W"] + d["D"]
    return weights, scales, small


def attention_params(cfg: dict) -> tuple[int, int]:
    """(int8 weights, f32 scales) of one GQA layer's q, k, v, o and gate."""
    d = _dims(cfg)
    return (3 * d["H"] * d["Dq"] + 2 * d["H"] * d["Dkv"],
            2 * d["Dq"] + 2 * d["Dkv"] + d["H"])


def expert_layer_dense_params(cfg: dict) -> tuple[int, int, int]:
    """(int8 weights, f32 scales, f32 router and bias) of one layer's expert
    layer outside its routed experts: the shared expert's three matrices."""
    d = _dims(cfg)
    return 3 * d["H"] * d["Is"], 2 * d["Is"] + d["H"], (d["H"] + 1) * d["E"]


def expert_params(cfg: dict) -> tuple[int, int]:
    """(int8 weights, f32 scales) of ONE routed expert: gate, up and down."""
    d = _dims(cfg)
    return 3 * d["H"] * d["I"], 2 * d["I"] + d["H"]


def kda_state_update(cfg: dict, serving: dict) -> dict:
    """One call of the ``kda_state_update`` kernel (one KDA layer, every row
    of the batch: the kernel's grid is over all rows, a row that does not run
    is read and written back as it was): each row's [Hs, D, D] f32 state read
    once and written once, with q, k, βk and the decays ([Hs, D] f32 each),
    βv and o ([Hs, D] f32 each). Per state element: the decay, a
    multiply-add into ``S̃ᵀ k``, a multiply-add of the rank-one correction, a
    multiply-add into ``Sᵀ q``: 7 FLOPs."""
    rows = serving["max_batch"]
    d = _dims(cfg)
    elements = rows * d["Hs"] * d["D"] * d["D"]
    small = rows * 4 * 6 * d["Hs"] * d["D"]
    return {"flops": 7.0 * elements, "bytes": float(2 * 4 * elements + small),
            "what": f"{rows} rows' [{d['Hs']}, {d['D']}, {d['D']}] f32 state "
                    f"read and written ({2 * 4 * elements / 1e6:.1f} MB), "
                    f"with q, k, the decays, beta and o "
                    f"({small / 1e6:.1f} MB)"}


def routed_experts(cfg: dict, serving: dict) -> Optional[dict]:
    """One expert layer's THREE grouped matmuls of one decode step: the int8
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
                    f"experts' gate, up and down (int8 + f32 scales) read "
                    f"once; {local:.1f} assignments on them"}


def kda_moe_step(cfg: dict, serving: dict) -> Optional[dict]:
    """What ONE whole decode step must move and compute: the KDA layers'
    matrices (int8 + f32 scales + the small f32 leaves), the GQA layers' q,
    k, v, o and gate, every layer's shared expert, float32 router and bias
    and its held experts touched AS MEASURED over decode steps, the held
    head, each read once; the RUNNING rows' f32 state and conv tails read
    once and written once in every KDA layer; the K/V pages the GQA layers'
    kernel walked as measured. 2 FLOPs a weight a running row (a routed
    expert's: an assignment held), 7 a state element."""
    touched = _experts_touched(cfg, serving)
    local = _local_assignments(cfg, serving)
    tokens = _pages_tokens(cfg, serving)
    running = serving.get("rows_running_share")
    if None in (touched, local, tokens, running):
        return None
    d = _dims(cfg)
    share = float(running) / 100.0
    rows = serving["max_batch"] * share
    kda_w, kda_s, kda_small = kda_params(cfg)
    att_w, att_s = attention_params(cfg)
    el_w, el_s, router = expert_layer_dense_params(cfg)
    ex_w, ex_s = expert_params(cfg)
    weights = (d["Lk"] * kda_w + d["La"] * att_w
               + d["L"] * (el_w + touched * ex_w) + d["V"] * d["H"])
    f32 = (d["Lk"] * (kda_s + kda_small) + d["La"] * att_s
           + d["L"] * (el_s + touched * ex_s + router) + d["V"])
    state = 2.0 * d["Lk"] * rows * state_bytes_per_row(cfg)
    pages = d["La"] * tokens * 2 * d["Dkv"] * 2.0
    every_token = (d["Lk"] * kda_w + d["La"] * att_w
                   + d["L"] * (el_w + d["H"] * d["E"]) + d["V"] * d["H"])
    flops = (2.0 * rows * every_token
             + 2.0 * ex_w * local * share * d["L"]
             + 7.0 * d["Lk"] * rows * d["Hs"] * d["D"] * d["D"]
             + d["La"] * 4.0 * tokens * d["Dq"])
    total = weights + 4.0 * f32 + state + pages
    return {"flops": flops, "bytes": total,
            "what": f"{rows:.1f} running rows; {d['Lk']} KDA + {d['La']} GQA "
                    f"layers, an expert layer after each: {touched:.2f} of "
                    f"{d['held']} held experts touched a layer, the head "
                    f"over {d['V']} rows; state {state / 1e9:.2f} GB, pages "
                    f"{pages / 1e9:.3f} GB, weights "
                    f"{(weights + 4.0 * f32) / 1e9:.2f} GB: "
                    f"{total / 1e9:.2f} GB"}
