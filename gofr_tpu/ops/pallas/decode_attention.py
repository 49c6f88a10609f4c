"""Single-token decode attention over the slot KV cache as a Pallas kernel.

The decode hot loop is HBM-bandwidth bound: every step streams the whole
cache [Slots, Hkv, Smax, D] past one query token per slot. This kernel walks
the grid (slot, kv_head, kv_block) reading [block_kv, D] tiles straight out
of the head-major serving layout (see gofr_tpu.ops.kvcache docstring) — no
transpose, no repeat of K/V for grouped queries — and computes the G grouped
query heads of each kv head as the rows of one [G, block_kv] MXU tile, with
the online-softmax state in VMEM scratch across kv blocks (same recurrence
as flash_attention).

Positions >= lengths[slot] are masked, so freshly-recycled slots and the
zero-padded tail of the cache never leak into live requests
(gofr_tpu.ops.kvcache semantics; continuous-batching engine contract).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gofr_tpu.ops.pallas.common import (
    NEG_INF,
    init_softmax_scratch,
    softmax_block_update,
    softmax_finish,
)


def _pick_block(total: int, desired: int) -> int:
    """Largest block <= desired that divides total (cache Smax is fixed at
    serving time, so we never pad-copy the cache)."""
    if total <= desired:
        return total
    for cand in range(desired, 0, -1):
        if total % cand == 0:
            return cand
    return total


def _decode_kernel(
    ln_ref,   # SMEM [B] per-slot live length
    q_ref,    # VMEM [1, 1, G, d]
    k_ref,    # VMEM [1, 1, block_kv, d]
    v_ref,    # VMEM [1, 1, block_kv, d]
    o_ref,    # VMEM [1, 1, G, d]
    acc_ref,  # scratch f32 [G, d]
    m_ref,    # scratch f32 [G, 128]
    l_ref,    # scratch f32 [G, 128]
    *,
    scale: float,
    block_kv: int,
    n_kvb: int,
    group: int,
):
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    init_softmax_scratch(ki, acc_ref, m_ref, l_ref)

    q = q_ref[0, 0]  # [G, d]
    k = k_ref[0, 0]  # [block_kv, d]
    v = v_ref[0, 0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [G, block_kv]

    kv_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (group, block_kv), 1)
    s = jnp.where(kv_pos < ln_ref[bi], s, NEG_INF)

    softmax_block_update(s, v, acc_ref, m_ref, l_ref)

    def write(out):
        o_ref[0, 0] = out.astype(o_ref.dtype)

    softmax_finish(ki, n_kvb, acc_ref, l_ref, write)


@functools.partial(jax.jit, static_argnames=("scale", "block_kv", "interpret"))
def decode_attention(
    q: jnp.ndarray,        # [B, Hq, D]
    k_cache: jnp.ndarray,  # [B, Hkv, Smax, D] head-major (kvcache layout)
    v_cache: jnp.ndarray,  # [B, Hkv, Smax, D]
    lengths: jnp.ndarray,  # [B]
    *,
    scale: float | None = None,
    block_kv: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Same contract as ops.attention.decode_attention → [B, Hq, D]."""
    b, hq, d = q.shape
    _, hkv, smax, _ = k_cache.shape
    if hq % hkv != 0:
        raise ValueError(f"query heads {hq} not divisible by kv heads {hkv}")
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d**0.5)
    bkv = _pick_block(smax, block_kv)
    n_kvb = smax // bkv

    # Head h groups under kv head h // G (ops.attention._group_query_heads).
    q4 = q.reshape(b, hkv, group, d)

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_kv=bkv, n_kvb=n_kvb, group=group
    )
    out = pl.pallas_call(
        kernel,
        name="attention",  # tracing.SCOPES: the kernel is named for its phase
        grid=(b, hkv, n_kvb),
        in_specs=[
            pl.BlockSpec((b,), lambda bi, hi, ki: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, group, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q4, k_cache, v_cache)
    return out.reshape(b, hq, d)
