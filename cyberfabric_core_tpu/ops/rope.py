"""Rotary position embeddings (RoPE), precomputed-table style.

Frequencies are computed once per model load and indexed by position inside jit —
no per-step trig on the hot path, and gather-by-position keeps decode shapes static.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature for a context stretched ``factor``
    times: ``0.1 mscale ln(factor) + 1`` (1 where nothing is stretched)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies [dim/2]: a pair that turns more than
    ``beta_fast`` times over the original context keeps ``theta^(-2i/dim)``,
    one that turns fewer than ``beta_slow`` times gets that over ``factor``
    (positions interpolated), and between the two correction dimensions the
    blend is a linear ramp."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original_max / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rope_frequencies(head_dim: int, max_position: int, theta: float,
                     inv_freq: np.ndarray | None = None,
                     scale: float = 1.0) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (cos, sin) tables of shape [max_position, head_dim//2] in f32.
    ``inv_freq`` replaces ``theta^(-2i/head_dim)`` (YaRN's blend) and
    ``scale`` multiplies both tables (YaRN's ``mscale`` ratio)."""
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    pos = np.arange(max_position, dtype=np.float64)
    angles = np.outer(pos, inv_freq)  # [P, D/2]
    return (jnp.asarray(np.cos(angles) * scale, jnp.float32),
            jnp.asarray(np.sin(angles) * scale, jnp.float32))


def rope_tables(cfg, max_position: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The (cos, sin) tables of a ``ModelConfig``: over the rotary part of a
    head (``qk_rope_head_dim`` where the head has a part that is not
    rotated), YaRN's frequencies where ``rope_factor`` > 1, the tables
    scaled by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    dim = cfg.qk_rope_head_dim or int(
        cfg.head_dim * cfg.partial_rotary_factor)
    if cfg.rope_factor <= 1.0:
        full = rope_frequencies(dim, max_position, cfg.rope_theta)
    else:
        full = rope_frequencies(
            dim, max_position, cfg.rope_theta,
            yarn_inv_freq(dim, cfg.rope_theta, cfg.rope_factor,
                          cfg.rope_original_max, cfg.rope_beta_fast,
                          cfg.rope_beta_slow),
            yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
            / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    if not cfg.window_rope_theta:
        return full
    return full, rope_frequencies(cfg.head_dim, max_position,
                                  cfg.window_rope_theta)


def attention_scale(cfg) -> float:
    """The softmax scale of a ``ModelConfig``: ``head_dim^-1/2``, times
    YaRN's ``mscale(factor, mscale_all_dim)^2`` where the context is
    stretched and ``mscale_all_dim`` is set."""
    scale = cfg.head_dim ** -0.5
    if cfg.rope_factor > 1.0 and cfg.rope_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return scale


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               cos_table: jnp.ndarray, sin_table: jnp.ndarray) -> jnp.ndarray:
    """Rotate q or k. x: [B, T, H, D]; positions: [B, T] int32.

    Uses the HF-llama "rotate_half" convention (first/second half pairing) so
    safetensors weights load without permutation. Tables of fewer than D/2
    columns rotate the head's leading part alone (``partial_rotary_factor``).
    """
    cos = cos_table[positions][:, :, None, :]  # [B, T, 1, D/2]
    sin = sin_table[positions][:, :, None, :]
    half = cos_table.shape[-1]
    if 2 * half < x.shape[-1]:
        # tables narrower than the head: the leading ``2 half`` numbers are
        # rotated (pairs inside them), the rest go on as they are
        return jnp.concatenate(
            [apply_rope(x[..., :2 * half], positions, cos_table, sin_table),
             x[..., 2 * half:]], axis=-1)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
