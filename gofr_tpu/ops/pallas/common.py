"""Shared pieces of the blocked online-softmax recurrence.

Both flash (prefill) and decode kernels carry (m, l, acc) scratch across
sequential kv-block grid steps; the numerics — the NEG_INF fully-masked-row
guard and the normalizer clamp — must stay identical between them, so they
live here once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def init_softmax_scratch(ki, acc_ref, m_ref, l_ref) -> None:
    """Zero the accumulators at the first kv block of each output tile."""

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)


def softmax_block_update(s, v, acc_ref, m_ref, l_ref, v_scale=None) -> None:
    """One online-softmax step: fold masked scores ``s`` [rows, block_kv]
    (f32, masked entries == NEG_INF) and values ``v`` [block_kv, d] into the
    running (acc, m, l) scratch. Fully-masked-so-far rows keep l == 0 so the
    final divide yields zeros, not NaN.

    ``v_scale`` [1, block_kv] (f32) is the int8-KV dequant fold: per-position value
    scales ride the probabilities before the PV contraction — the same
    place the XLA path folds ``vs`` (ops.attention.decode_attention_q) —
    so a quantized ``v`` stays int8 in HBM/VMEM and converts only at the
    matmul input. The normalizer ``l`` is scale-free either way (it sums
    the unscaled probabilities)."""
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # exp against a safe 0 for all-masked rows keeps exp(NEG_INF) == 0
    # instead of exp(0) == 1.
    m_safe = jnp.where(m_next > NEG_INF / 2, m_next, 0.0)

    p = jnp.exp(s - m_safe)          # masked entries underflow to 0
    alpha = jnp.exp(m_prev - m_safe)  # rescale of previous blocks
    l_next = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

    if v_scale is None:
        p_in, v_in = p.astype(v.dtype), v
    else:
        p_in = p * v_scale
        v_in = v.astype(jnp.float32)
    pv = jax.lax.dot_general(
        p_in, v_in, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_next, l_ref.shape)


def select_head_row(block: jnp.ndarray, head) -> jnp.ndarray:
    """Row ``head`` of a [Hkv, block_kv] scale block as [1, block_kv] f32.

    The scale planes are [P, Hkv, page]: a one-head block (1, 1, page) has a
    second-minor block dim of 1 over an array dim of Hkv, which the TPU
    lowering refuses (neither a multiple of 8 nor the full dim). So the
    kernels fetch all Hkv rows of the page and pick theirs with an iota
    mask — no dynamic sublane index on a packed bf16 tile, no relayout of
    the plane in HBM."""
    x = block.astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.sum(jnp.where(row == head, x, 0.0), axis=0, keepdims=True)


def softmax_finish(ki, n_kvb, acc_ref, l_ref, write) -> None:
    """After the last kv block, normalize and hand the tile to ``write``."""

    @pl.when(ki == n_kvb - 1)
    def _():
        write(acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-20))
