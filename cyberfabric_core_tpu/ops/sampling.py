"""Token sampling: greedy / temperature / top-k / top-p, all inside jit.

Static-shape friendly: top-p uses a sorted-cumsum mask rather than dynamic
truncation, so the same compiled computation serves every request; per-request
parameters are runtime scalars, not compile-time constants.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _warp_sorted(
    logits: jnp.ndarray,       # [B, V] f32
    temperature: jnp.ndarray,  # [B] f32 (>0 rows only meaningful)
    top_p: jnp.ndarray,        # [B] f32; 1 → disabled
    top_k: jnp.ndarray,        # [B] int32; 0 → disabled
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """THE temperature/top-p/top-k warp, in sorted order: returns
    (masked_sorted_logits, sorted_idx). Single source of truth shared by
    sample_token (draws) and warped_probs (explicit distributions) — the
    speculative acceptance-sampling exactness guarantee depends on both
    using bit-identical semantics."""
    B, V = logits.shape
    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_t[:, None]
    sorted_idx = jnp.argsort(-scaled, axis=-1)               # desc, one sort
    sorted_logits = jnp.take_along_axis(scaled, sorted_idx, axis=-1)
    probs_sorted = jax.nn.softmax(sorted_logits, axis=-1)
    cumsum = jnp.cumsum(probs_sorted, axis=-1)
    # top-p: keep the smallest prefix with cumulative mass >= top_p
    # (shift so the first token crossing the threshold is kept)
    keep_p = (cumsum - probs_sorted) < top_p[:, None]
    # top-k: keep the first k sorted entries (k==0 → all)
    rank = jnp.arange(V, dtype=jnp.int32)[None, :]
    keep_k = jnp.where(top_k[:, None] > 0, rank < top_k[:, None], True)
    keep = (keep_p & keep_k).at[:, 0].set(True)  # never mask every token
    return jnp.where(keep, sorted_logits, -jnp.inf), sorted_idx


def sample_token(
    logits: jnp.ndarray,       # [B, V] f32
    key: jax.Array,
    temperature: jnp.ndarray,  # [B] f32; 0 → greedy
    top_p: jnp.ndarray,        # [B] f32; 1 → disabled
    top_k: jnp.ndarray,        # [B] int32; 0 → disabled
) -> jnp.ndarray:
    """Returns [B] int32 sampled token ids. Greedy when temperature == 0.

    All-greedy batches take a sort-free fast path via lax.cond — the full-vocab
    argsort is ~ms-scale at V=128k and would otherwise run every decode step.
    """

    def greedy_branch(operands):
        logits, *_ = operands
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sample_branch(operands):
        logits, key, temperature, top_p, top_k = operands
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        masked_sorted, sorted_idx = _warp_sorted(logits, temperature,
                                                 top_p, top_k)
        choice_in_sorted = jax.random.categorical(key, masked_sorted, axis=-1)
        sampled = jnp.take_along_axis(sorted_idx, choice_in_sorted[:, None], axis=1)[:, 0]
        return jnp.where(temperature > 0, sampled.astype(jnp.int32), greedy)

    return jax.lax.cond(
        jnp.all(temperature <= 0.0), greedy_branch, sample_branch,
        (logits, key, temperature, top_p, top_k),
    )


def sample_token_per_slot(
    logits: jnp.ndarray,       # [B, V] f32
    keys: jnp.ndarray,         # [B, 2] uint32 — one PRNG key per slot
    temperature: jnp.ndarray,  # [B] f32; 0 → greedy
    top_p: jnp.ndarray,        # [B] f32
    top_k: jnp.ndarray,        # [B] int32
) -> jnp.ndarray:
    """Per-slot-keyed sampling for continuous batching: each slot draws from its
    OWN key stream, so a request's seed reproduces its tokens regardless of
    which other requests share the batch (round-1 advisory: the shared-rng
    scheduler silently dropped per-request seeds). The all-greedy fast path is
    kept at the batch level — the vmapped sort only runs when some row samples."""

    def greedy_branch(operands):
        logits, *_ = operands
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sample_branch(operands):
        logits, keys, temperature, top_p, top_k = operands

        def one(lg, kk, tt, pp, tk):
            return sample_token(lg[None], kk, tt[None], pp[None], tk[None])[0]

        return jax.vmap(one)(logits, keys, temperature, top_p, top_k)

    return jax.lax.cond(
        jnp.all(temperature <= 0.0), greedy_branch, sample_branch,
        (logits, keys, temperature, top_p, top_k),
    )


def split_keys_per_slot(keys: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[B, 2] keys → (advanced keys [B, 2], subkeys [B, 2]), vmapped split."""
    both = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return both[:, 0], both[:, 1]


_M32 = 0xFFFFFFFF
_THREEFRY_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _threefry2x32(k0: int, k1: int, x0: int, x1: int) -> tuple[int, int]:
    """Threefry-2x32 (20 rounds) on Python ints: what ``jax.random`` runs."""
    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    x0, x1 = (x0 + ks[0]) & _M32, (x1 + ks[1]) & _M32
    for i in range(5):
        for r in _THREEFRY_ROTATIONS[i % 2]:
            x0 = (x0 + x1) & _M32
            x1 = ((x1 << r) | (x1 >> (32 - r))) & _M32
            x1 ^= x0
        x0 = (x0 + ks[(i + 1) % 3]) & _M32
        x1 = (x1 + ks[(i + 2) % 3] + i + 1) & _M32
    return x0, x1


def host_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)``'s key data made on the host, with no
    program: the seed's low 32 bits under a zero word (32-bit mode folds
    the seed to int32 first; 64-bit mode keeps the high word)."""
    high = (seed >> 32) & _M32 if jax.config.jax_enable_x64 else 0
    return np.array([high, seed & _M32], np.uint32)


def host_split(key: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``jax.random.split(key)`` on the host (the partitionable threefry
    split: output ``i`` is the block cipher of the counter ``(0, i)``), so a
    scheduler hands out key streams without dispatching a program."""
    k0, k1 = int(key[0]), int(key[1])
    return tuple(np.array(_threefry2x32(k0, k1, 0, i), np.uint32)
                 for i in range(2))


def warped_probs(
    logits: jnp.ndarray,       # [B, V] f32
    temperature: jnp.ndarray,  # [B] f32; 0 → delta on the argmax
    top_p: jnp.ndarray,        # [B] f32; 1 → disabled
    top_k: jnp.ndarray,        # [B] int32; 0 → disabled
) -> jnp.ndarray:
    """The sampling distribution as explicit probabilities [B, V] — the same
    temperature/top-p/top-k warp sample_token draws from, needed in closed
    form by speculative acceptance sampling (p_target/p_draft ratios and the
    (p_t - p_d)+ residual both require full rows, not draws). temperature=0
    renders the greedy delta distribution."""
    B, V = logits.shape
    greedy = jax.nn.one_hot(jnp.argmax(logits, axis=-1), V, dtype=jnp.float32)
    masked_sorted, sorted_idx = _warp_sorted(logits, temperature, top_p, top_k)
    probs_sorted = jax.nn.softmax(masked_sorted, axis=-1)
    # unsort back to vocab order
    inv = jnp.argsort(sorted_idx, axis=-1)
    warped = jnp.take_along_axis(probs_sorted, inv, axis=-1)
    return jnp.where((temperature > 0)[:, None], warped, greedy)


def block_unmask(
    block: jnp.ndarray,        # [B, W] int32 the open block; mask_id = open
    logits: jnp.ndarray,       # [B, W, V] f32, position i's for the token AT i
    keys: jnp.ndarray,         # [B, 2] one PRNG key per slot, for this forward
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,        # [B]
    top_k: jnp.ndarray,        # [B]
    *,
    mask_id: int,
    per_step: int,
    dynamic: bool = False,
    threshold: float = 0.9,
) -> jnp.ndarray:
    """One denoising step of generation by diffusion over blocks: at every
    masked position draw ``x0`` (greedy, or from the slot's key stream as
    :func:`sample_token_per_slot` draws) with confidence
    ``softmax(logits)[x0]``, then unmask by confidence. ``low_confidence_
    static`` (``dynamic`` False) unmasks the ``per_step`` most confident
    masked positions; ``low_confidence_dynamic`` every masked position whose
    confidence is over ``threshold`` and at least the ``per_step`` most
    confident. Ties go to the earlier position. Positions already decided
    keep their token. Returns the block [B, W]."""
    B, W, V = logits.shape
    # the mask token is never drawn (a trained model does not predict it;
    # random weights would, and the block would never close)
    flat = logits.reshape(B * W, V).at[:, mask_id].set(-jnp.inf)
    sub = jax.vmap(lambda k: jax.random.split(k, W))(keys).reshape(B * W, 2)
    x0 = sample_token_per_slot(
        flat, sub, jnp.repeat(temperature, W), jnp.repeat(top_p, W),
        jnp.repeat(top_k, W))
    picked = jnp.take_along_axis(flat, x0[:, None], axis=1)[:, 0]
    conf = jnp.exp(picked - jax.nn.logsumexp(flat, axis=-1)).reshape(B, W)
    x0 = x0.reshape(B, W)
    masked = block == mask_id
    conf = jnp.where(masked, conf, -jnp.inf)
    # rank 0 = the most confident; a stable sort keeps the earlier position
    # ahead among equals
    order = jnp.argsort(-conf, axis=1, stable=True)
    rank = jnp.argsort(order, axis=1)
    take = rank < per_step
    if dynamic:
        take = take | (conf > threshold)
    return jnp.where(take & masked, x0, block)
