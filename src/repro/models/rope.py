"""Rotary position embeddings: standard RoPE, YaRN-scaled RoPE
(DeepSeek-V2) and Qwen2-VL M-RoPE.

M-RoPE splits the head dim into (temporal, height, width) sections; each
section rotates by its own position stream.  For text-only tokens all three
streams coincide, recovering standard RoPE.

YaRN (arXiv:2309.00071, as DeepSeek-V2 publishes it) keeps the
frequencies of the fast-rotating pairs, divides those of the slow pairs by
the scaling factor, and blends the pairs in between with a linear ramp over
the pair index; the attention softmax scale grows by ``mscale**2``.
"""
from __future__ import annotations

import math
from typing import Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2).astype(jnp.float32)
                            / head_dim))


def _yarn_correction_dim(rotations: float, dim: int, theta: float,
                         max_pos: int) -> float:
    """The pair index whose wavelength fits ``rotations`` turns into
    ``max_pos`` positions."""
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max_position: int, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies (dim/2,) float64: pairs below the fast
    correction index keep ``theta``'s frequency, pairs above the slow one
    take it divided by ``factor``, and a linear ramp blends the pairs in
    between."""
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    low = max(math.floor(_yarn_correction_dim(
        beta_fast, dim, theta, original_max_position)), 0)
    high = min(math.ceil(_yarn_correction_dim(
        beta_slow, dim, theta, original_max_position)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                       # 1: original frequency
    return base / factor * (1.0 - keep) + base * keep


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1 mscale ln(factor) + 1``."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _rotate_half(x: jax.Array) -> jax.Array:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float,
                 freqs=None) -> Tuple[jax.Array, jax.Array]:
    """positions: (..., seq) int32 -> cos/sin of shape (..., seq, head_dim).
    ``freqs`` (hd/2,) replaces ``theta``'s frequencies (YaRN)."""
    if freqs is None:
        freqs = rope_frequencies(head_dim, theta)       # (hd/2,)
    else:
        freqs = jnp.asarray(freqs, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., s, hd/2)
    angles = jnp.concatenate([angles, angles], axis=-1)  # (..., s, hd)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (batch, seq, heads, head_dim); cos/sin: (batch, seq, head_dim)."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return (x32 * c + _rotate_half(x32) * s).astype(dt)


def mrope_cos_sin(positions_3d: jax.Array, head_dim: int, theta: float,
                  sections: Sequence[int]) -> Tuple[jax.Array, jax.Array]:
    """Qwen2-VL multimodal RoPE.

    positions_3d: (3, batch, seq) int32 — temporal / height / width streams.
    ``sections`` gives the per-stream share of head_dim (sums to head_dim);
    internally each stream owns ``sections[i] // 2`` of the hd/2 frequency
    slots, interleaved as in the reference implementation.
    """
    assert sum(sections) == head_dim, (sections, head_dim)
    freqs = rope_frequencies(head_dim, theta)            # (hd/2,)
    # (3, b, s, hd/2)
    angles = positions_3d[..., None].astype(jnp.float32) * freqs
    half_secs = [s // 2 for s in sections]
    # pick stream i for its slice of the hd/2 frequency axis
    parts = []
    start = 0
    for i, hs in enumerate(half_secs):
        parts.append(angles[i, ..., start:start + hs])
        start += hs
    merged = jnp.concatenate(parts, axis=-1)             # (b, s, hd/2)
    merged = jnp.concatenate([merged, merged], axis=-1)  # (b, s, hd)
    return jnp.cos(merged), jnp.sin(merged)


def text_positions_3d(positions: jax.Array) -> jax.Array:
    """Lift 1-D positions (batch, seq) to degenerate 3-D M-RoPE streams."""
    return jnp.broadcast_to(positions[None], (3,) + positions.shape)
