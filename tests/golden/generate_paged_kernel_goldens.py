"""Writes tests/golden/paged_kernels.json: SHA-256 of each case of
tests/test_paged_kernel_goldens.py as the kernels of the checkout named on
the command line compute it from ONE LAYER's [N, page, Hkv, D] pool — the
kernels' signature up to commit 5f6ab0c, which is the checkout the committed
file was written from:

    JAX_PLATFORMS=cpu python tests/golden/generate_paged_kernel_goldens.py \\
        <checkout of 5f6ab0c>

Each kernel body (batched, two_d_dots) gets its own digest: at head size 128
the two sum a dot product's terms in different orders on the CPU and differ
in the last bit, which is that checkout's behaviour and not this PR's.
"""

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [sys.argv[1], str(HERE.parent)]

import jax  # noqa: E402

from cyberfabric_core_tpu.ops.paged_attention import (  # noqa: E402
    paged_decode_attention, ragged_paged_attention)
from test_paged_kernel_goldens import (  # noqa: E402
    CASES, canary, case_inputs, digest)

out = {}
for name in CASES:
    case = case_inputs(name)
    fn = paged_decode_attention if case["kernel"] == "decode" \
        else ragged_paged_attention
    out[name] = {
        body: digest(fn(case["q"], case["k_pool"], case["v_pool"],
                        case["table"], *case["rows"], interpret=True,
                        sliding_window=case["window"], two_d_dots=form))
        for body, form in (("batched", False), ("two_d_dots", True))}
(HERE / "paged_kernels.json").write_text(json.dumps(
    {"written_from": "commit 5f6ab0c", "jax": jax.__version__,
     "canary": canary(), "sha256": out},
    indent=1) + "\n")
print(f"{len(out)} cases")
