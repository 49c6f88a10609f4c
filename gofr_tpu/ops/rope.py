"""Rotary position embeddings (RoPE), Llama-style half-split layout.

The cos/sin table is precomputed once per model (static shapes keep it out
of the per-step compile) and gathered by position ids — decode steps index
it with the current sequence offsets, so prefill and decode share one
implementation.
"""

from __future__ import annotations

import jax.numpy as jnp

from gofr_tpu.tracing import scoped


def rope_table(
    max_len: int,
    head_dim: int,
    theta: float = 10000.0,
    scaling: float = 1.0,
    dtype=jnp.float32,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin) tables of shape [max_len, head_dim//2]. ``scaling`` > 1
    is linear position-interpolation context extension."""
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    positions = jnp.arange(max_len, dtype=jnp.float32) / scaling
    angles = jnp.outer(positions, inv_freq)
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


@scoped("qkv_rope")
def apply_rope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cos_table: jnp.ndarray,
    sin_table: jnp.ndarray,
) -> jnp.ndarray:
    """Rotate ``x`` of shape [..., seq, heads, head_dim] by the angles at
    ``positions`` [..., seq]. Uses the "half-split" convention (x1 = first
    half, x2 = second half) matching Llama/HF `rotate_half`."""
    cos = cos_table[positions]  # [..., seq, half]
    sin = sin_table[positions]
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)
