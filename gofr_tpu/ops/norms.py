"""Normalization ops.

Computed in float32 regardless of input dtype (bfloat16 accumulation loses
too much precision for variance), cast back on exit — the standard TPU
recipe; XLA fuses the whole thing into neighboring matmuls.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(
    x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray | None, eps: float = 1e-12
) -> jnp.ndarray:
    """Mean-subtracted LayerNorm; ``bias=None`` is the weight-only form
    (Cohere's: no bias anywhere in the block)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = (xf - mean) / jnp.sqrt(var + eps) * weight.astype(jnp.float32)
    if bias is not None:
        normed = normed + bias.astype(jnp.float32)
    return normed.astype(dtype)
