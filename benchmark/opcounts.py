"""Operations and bytes a step of the model needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change what a
roofline share is measured against. Each function takes the published
configuration and the serving block of its file and returns
``{"flops", "bytes", "what"}`` for ONE execution of the thing named.

A layer-metric file names a count by its role (``decode_step_weights``). The
functions of this file count the llama family's block. A configuration of
another architecture names, under ``counts``, the module whose functions of
the same names count its own; one that names none gets no reading, never
this file's count.
"""

from __future__ import annotations

from typing import Callable, Optional

from . import names


def _dims(cfg: dict) -> dict:
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return {"H": cfg["hidden_size"], "I": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "Dq": cfg["num_attention_heads"] * hd,
            "Dkv": cfg["num_key_value_heads"] * hd}


def matmul_params(cfg: dict) -> int:
    """Weights every token passes through once: the layers' seven matrices
    and the lm-head (the embedding is a gather of one row a token)."""
    d = _dims(cfg)
    per_layer = (d["H"] * d["Dq"] + 2 * d["H"] * d["Dkv"] + d["Dq"] * d["H"]
                 + 3 * d["H"] * d["I"])
    return d["L"] * per_layer + d["H"] * d["V"]


def scale_count(cfg: dict) -> int:
    d = _dims(cfg)
    return d["L"] * (d["Dq"] + 2 * d["Dkv"] + d["H"] + 2 * d["I"] + d["H"]) + d["V"]


def decode_step_weights(cfg: dict, serving: dict) -> dict:
    """One decode step of the whole batch: every stored int8 weight and f32
    scale is read once; 2 FLOPs a weight a row. K/V reads are NOT counted (the
    round record carries no row lengths), so the bytes are a true lower bound
    and the share reported is a lower bound on the step's distance from it."""
    rows = serving["max_batch"]
    params = matmul_params(cfg)
    return {"flops": 2.0 * params * rows,
            "bytes": float(params + 4 * scale_count(cfg)),
            "what": f"int8 weights + f32 scales read once, {rows} rows"}


def count_function(conf: dict, role: str) -> Optional[Callable]:
    """The function that counts ``role`` for this configuration's
    architecture, or None where nothing does."""
    module = conf.get("counts")
    if module is None and names.adapter_of(conf) != names.DEFAULT_ADAPTER:
        return None
    found = getattr(names.load(module or __name__), role, None)
    return found if callable(found) and not role.startswith("_") else None


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """The roofline: the larger of bytes / bandwidth and FLOPs / peak."""
    by_bytes = counts["bytes"] / peaks["hbm_bytes_per_s"]
    by_flops = counts["flops"] / peaks["bf16_flops_per_s"]
    return (by_bytes, "memory") if by_bytes >= by_flops else (by_flops, "compute")
