"""The paged kernels' outputs, bit for bit, against what the kernels gave
before the pool was stored merged and stacked (PR 25).

``tests/golden/paged_kernels.json`` holds a SHA-256 of each case's output
bytes as commit 5f6ab0c computed them from one layer's ``[N, page, Hkv, D]``
pool (``tests/golden/generate_paged_kernel_goldens.py`` wrote it from that
checkout, interpret mode on the CPU). Here the same pages sit in layer 1 of
a three-layer ``[L, N, page, Hkv*D]`` pool whose other layers hold other
numbers, and each kernel body must return its bytes: storing the pool in the
kernels' block shape and picking the layer in the index map moved no bit.
Since PR 57 the decode kernel walks a row's pages inside one program, a kv
head over its own query rows: at a page a trip it gives those bytes too, but
for two digests at ONE query row a kv head (one element by one bfloat16 step:
this CPU sums a matrix-vector and a matrix product in different orders),
re-pinned under ``repinned`` in the file.
"""

import functools
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

GOLDENS = Path(__file__).parent / "golden" / "paged_kernels.json"

#: (Hq, Hkv, D, dtype): mistral-7b's and qwen2-7b's grouping at their head
#: size, phi-3-mini's head size (96: lane slices off the 128 grid), and the
#: small float32 shapes of test_paged_attention.py / test_ragged_attention.py
HEADS = {
    "gqa32x8": (32, 8, 128, "bfloat16"),
    "gqa28x4": (28, 4, 128, "bfloat16"),
    "mha8-d96": (8, 8, 96, "bfloat16"),
    "gqa4x2-f32": (4, 2, 32, "float32"),
    "mqa4x1-f32": (4, 1, 16, "float32"),
}
WINDOWS = {"full": None, "window24": 24}
PAGE, PMAX, LAYERS, LAYER = 16, 4, 3, 1
DECODE_LENGTHS = [33, 7, 64, 1]
RAGGED_HIST = [37, 12, 0, 0]
RAGGED_QLENS = [1, 23, 0, 16]

CASES = [f"{kernel}.{heads}.{window}" for kernel in ("decode", "ragged")
         for heads in HEADS for window in WINDOWS]


def case_inputs(name: str) -> dict:
    """Seeded inputs of one case, in the parent's shapes: q, one layer's
    pools [N, page, Hkv, D], the page table and the row lengths."""
    kernel, heads, window = name.split(".")
    Hq, Hkv, D, dtype = HEADS[heads]
    B = len(DECODE_LENGTHS)
    N = B * PMAX + 2
    rng = np.random.default_rng(
        int(hashlib.sha256(name.encode()).hexdigest()[:8], 16))
    norm = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape, np.float32), dtype)
    table = (rng.permutation(N - 1)[: B * PMAX] + 1).reshape(B, PMAX)
    q_shape = (B, Hq, D) if kernel == "decode" else (B, 24, Hq, D)
    return {
        "kernel": kernel, "window": WINDOWS[window], "q": norm(*q_shape),
        "k_pool": norm(N, PAGE, Hkv, D), "v_pool": norm(N, PAGE, Hkv, D),
        "others": [(norm(N, PAGE, Hkv, D), norm(N, PAGE, Hkv, D))
                   for _ in range(LAYERS - 1)],
        "table": jnp.asarray(table, jnp.int32),
        "rows": ((jnp.asarray(DECODE_LENGTHS, jnp.int32),)
                 if kernel == "decode" else
                 (jnp.asarray(RAGGED_HIST, jnp.int32),
                  jnp.asarray(RAGGED_QLENS, jnp.int32))),
    }


def digest(out) -> str:
    a = np.asarray(out)
    return hashlib.sha256(a.view(np.uint8).tobytes()).hexdigest()


def canary() -> str:
    """One bf16 dot with f32 accumulation and one exp, digested: the
    arithmetic every case rests on. Where a CPU gives other bytes for it than
    the one the goldens were written on, the digests say nothing about the
    kernels, and the cases skip."""
    rng = np.random.default_rng(0)
    a, b = (jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)
            for shape in ((32, 128), (16, 128)))
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return digest(jnp.exp(s - s.max(axis=1, keepdims=True)))


@pytest.fixture(scope="module")
def goldens() -> dict:
    written = json.loads(GOLDENS.read_text())
    if canary() != written["canary"]:
        pytest.skip("this CPU rounds a dot product or an exp differently "
                    "from the one the goldens were written on")
    return written["sha256"]


def _stacked(case: dict, which: str) -> jnp.ndarray:
    """The case's layer at index LAYER of a merged stacked pool."""
    i = 0 if which == "k_pool" else 1
    layers = [o[i] for o in case["others"]]
    layers.insert(LAYER, case[which])
    pool = jnp.stack(layers)
    return pool.reshape(*pool.shape[:3], -1)


@pytest.mark.parametrize("body", ["batched", "two_d_dots"])
@pytest.mark.parametrize("name", CASES)
def test_merged_stacked_pool_is_bitwise_the_parent(name, body, goldens):
    from cyberfabric_core_tpu.ops.paged_attention import (
        paged_decode_attention, ragged_paged_attention)

    case = case_inputs(name)
    if case["kernel"] == "decode":
        # the walk inside the program (PR 57) at a page a trip sums a row's
        # pages in the grid's order, a kv head over its own query rows
        fn = functools.partial(paged_decode_attention, trip=1)
    else:
        # the walk inside the program (PR 55) at a page a trip and the
        # grid's 8 queries a program sums in the grid's order
        fn = functools.partial(ragged_paged_attention, trip=1, q_block=8)
    out = fn(case["q"], _stacked(case, "k_pool"), _stacked(case, "v_pool"),
             case["table"], *case["rows"], LAYER, interpret=True,
             sliding_window=case["window"], two_d_dots=body == "two_d_dots")
    assert digest(out) == goldens[name][body]
