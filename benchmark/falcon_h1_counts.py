"""Operations and bytes of the Falcon-H1 block, from shapes alone, by role
(``opcounts.py`` counts the llama family's; a configuration names this module
under ``counts``). The harness's parent process imports this module: no JAX.

Each function takes the published configuration and the serving block of its
file and returns ``{"flops", "bytes", "what"}`` for ONE execution.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    conv = cfg["mamba_d_ssm"] + 2 * gn
    return {"H": cfg["hidden_size"], "I": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "Dq": cfg["num_attention_heads"] * cfg["head_dim"],
            "Dkv": cfg["num_key_value_heads"] * cfg["head_dim"],
            "d_ssm": cfg["mamba_d_ssm"], "Hs": cfg["mamba_n_heads"],
            "P": cfg["mamba_d_head"], "N": cfg["mamba_d_state"],
            "G": cfg["mamba_n_groups"], "K": cfg["mamba_d_conv"],
            "conv": conv, "proj": cfg["mamba_d_ssm"] + conv + cfg["mamba_n_heads"]}


def matmul_params(cfg: dict) -> int:
    """Weights every token passes through once: a block's nine matrices
    (attention's four, the mixer's two, the MLP's three) and the lm-head."""
    d = _dims(cfg)
    block = (d["H"] * d["Dq"] + 2 * d["H"] * d["Dkv"] + d["Dq"] * d["H"]
             + d["H"] * d["proj"] + d["d_ssm"] * d["H"] + 3 * d["H"] * d["I"])
    return d["L"] * block + d["H"] * d["V"]


def scale_count(cfg: dict) -> int:
    """One f32 scale an output channel of each of those matrices."""
    d = _dims(cfg)
    return d["L"] * (d["Dq"] + 2 * d["Dkv"] + d["H"] + d["proj"] + d["H"]
                     + 2 * d["I"] + d["H"]) + d["V"]


def state_bytes_per_row(cfg: dict) -> int:
    """f32 recurrent state and conv tail of one row in one block."""
    d = _dims(cfg)
    return 4 * (d["Hs"] * d["P"] * d["N"] + (d["K"] - 1) * d["conv"])


def decode_step_weights(cfg: dict, serving: dict) -> dict:
    """One decode step of the whole batch: every stored int8 weight and f32
    scale read once, and every row's recurrent state and conv tail read once
    and written once in every block (state traffic does not amortise over
    rows as weights do); 2 FLOPs a weight a row, and the state's update. K/V
    reads are NOT counted (the round record carries no row lengths), so the
    bytes are a lower bound."""
    rows = serving["max_batch"]
    d = _dims(cfg)
    params = matmul_params(cfg)
    state = 2 * d["L"] * rows * state_bytes_per_row(cfg)
    return {"flops": 2.0 * params * rows
            + d["L"] * ssm_state_update(cfg, serving)["flops"],
            "bytes": float(params + 4 * scale_count(cfg) + state),
            "what": f"int8 weights + f32 scales read once, {rows} rows' f32 "
                    "state and conv tails read once and written once a block"}


def ssm_state_update(cfg: dict, serving: dict) -> dict:
    """One call of the ``ssm_state_update`` kernel (one block, every row):
    each row's [Hs, P, N] f32 state read once and written once, its Δ·x and y
    ([Hs, P] f32 each), exp(Δ A) ([Hs] f32) and B, C ([G, N] f32 each). Per
    state element: one multiply by the decay, a multiply-add of the outer
    product, a multiply-add into y: 5 FLOPs."""
    rows = serving["max_batch"]
    d = _dims(cfg)
    elements = rows * d["Hs"] * d["P"] * d["N"]
    small = rows * 4 * (2 * d["Hs"] * d["P"] + d["Hs"] + 2 * d["G"] * d["N"])
    return {"flops": 5.0 * elements, "bytes": float(2 * 4 * elements + small),
            "what": f"{rows} rows' [{d['Hs']}, {d['P']}, {d['N']}] f32 state "
                    "read and written, with x, B, C, the decay and y"}
