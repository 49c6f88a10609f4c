"""Rotary position embeddings (RoPE): Llama-style half-split layout, and the
GPT-J interleaved-pair layout (``apply_rope_interleaved``).

The cos/sin table is precomputed once per model (static shapes keep it out
of the per-step compile) and gathered by position ids — decode steps index
it with the current sequence offsets, so prefill and decode share one
implementation.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

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


def rope_inv_freq(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    """The ``head_dim // 2`` inverse frequencies ``theta ** (-2i / head_dim)``."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


@scoped("qkv_rope")
def apply_rope_interleaved(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    inv_freq: jnp.ndarray,
    factor: jnp.ndarray | float = 1.0,
) -> jnp.ndarray:
    """Rotate ``x`` [..., seq, heads, head_dim] at ``positions`` [..., seq] in
    the GPT-J convention: the pairs are NEIGHBOURS, (x0, x1), (x2, x3), ...,
    pair ``i`` turning by ``positions * inv_freq[i] * factor``. The angles
    are computed from the positions (no table: a 200k-position model would
    carry 100 MB of it). ``factor`` scales every angle: 0 is the identity
    rotation, exactly — which is how one scanned layer body serves a layer
    with no positional embedding beside rotary ones."""
    # every pair's angle on both of its lanes
    angles = positions[..., None].astype(jnp.float32) * jnp.repeat(inv_freq, 2) * factor
    cos = jnp.cos(angles)[..., None, :]  # [..., seq, 1, head_dim]: broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    # the partner of lane 2i is 2i+1 (negated) and the other way round, as ONE
    # product with a signed permutation matrix: exact (one term a sum) and the
    # matrix unit's to do. Two lane rotations and a select (jnp.roll by +-1)
    # make the TPU compiler re-lay a prefill's whole q in float32 several
    # times a layer (docs/kernels.md, "Products that take a scanned weight")
    lane = jnp.arange(x.shape[-1])
    swap = jnp.where(lane[:, None] == (lane ^ 1)[None, :], jnp.where(lane % 2 == 0, -1.0, 1.0), 0.0)
    partner = jnp.matmul(x, swap.astype(x.dtype), precision=lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos + partner * sin).astype(x.dtype)
