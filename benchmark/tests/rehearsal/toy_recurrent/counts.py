"""What a step of the toy block reads, by role: the llama block's count plus
the state's own bytes and FLOPs. The harness's parent process imports this
module: no JAX here."""

from __future__ import annotations

from benchmark import opcounts

from . import STATE


def decode_step_weights(cfg: dict, serving: dict) -> dict:
    base = opcounts.decode_step_weights(cfg, serving)
    rows, vocab = serving["max_batch"], cfg["vocab_size"]
    own = (STATE * vocab + 4 * vocab        # state_out: int8 and f32 scales
           + rows * (STATE + 4)             # one state_in row a row
           + 2 * 4 * rows * STATE)          # the state read and written
    return {"flops": base["flops"] + 2.0 * STATE * vocab * rows,
            "bytes": base["bytes"] + float(own),
            "what": base["what"] + f"; state of {STATE} a row, its read-out"}
