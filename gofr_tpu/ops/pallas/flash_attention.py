"""Flash attention (blocked online-softmax) as a Pallas TPU kernel.

Prefill-shaped attention without materializing the [Sq, Skv] score matrix in
HBM: the grid walks (batch, q_head, q_block, kv_block) with the kv dimension
innermost/sequential, carrying the running max / normalizer / output
accumulator in VMEM scratch across kv blocks. Q@K^T and P@V both hit the MXU
at [block_q, block_kv] x [block_kv, d] tiles; softmax bookkeeping runs on the
VPU in f32.

The public contract is activation layout [B, S, H, D]; internally tensors
are viewed head-major [B, H, S, D] because TPU block tiling needs the last
two block dims (8k, 128k)-aligned — one transpose XLA fuses into the
producing matmul. GQA is handled in the index maps: q head h reads kv head
h // group so K/V are never repeated in HBM. Causal masking supports a
per-batch ``q_offset`` so chunked prefill at cache offset t attends as
positions t..t+Sq; ``kv_lengths`` masks padded keys. Fully-masked query rows
produce zeros, not NaN (parity with ops.attention._softmax).

Reference capability map: SURVEY.md §2.9 / §7 stage 3 — the reference
(request-level Go framework) has no kernels; this is the TPU-native hot path.
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


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _flash_kernel(
    qo_ref,  # SMEM [B] per-batch q position offset
    kl_ref,  # SMEM [B] per-batch kv length
    q_ref,   # VMEM [1, 1, block_q, d]
    k_ref,   # VMEM [1, 1, block_kv, d]
    v_ref,   # VMEM [1, 1, block_kv, d]
    o_ref,   # VMEM [1, 1, block_q, d]
    acc_ref,  # scratch f32 [block_q, d]
    m_ref,    # scratch f32 [block_q, 128]
    l_ref,    # scratch f32 [block_q, 128]
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_kv: int,
    n_kvb: int,
):
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    init_softmax_scratch(ki, acc_ref, m_ref, l_ref)

    # Causal block skip: a kv block starting past this q block's last global
    # position is fully masked — skip its matmuls entirely (~2x less MXU
    # work for square causal prefill; the DMA still streams, bounded by the
    # grid, but compute is the prefill bottleneck at these tile sizes).
    needed = True
    if causal:
        needed = ki * block_kv <= qi * block_q + block_q - 1 + qo_ref[bi]

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_kv] f32

        kv_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = kv_pos < kl_ref[bi]
        if causal:
            q_pos = (
                qi * block_q
                + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
                + qo_ref[bi]
            )
            mask = mask & (q_pos >= kv_pos)
        s = jnp.where(mask, s, NEG_INF)

        softmax_block_update(s, v, acc_ref, m_ref, l_ref)

    def write(out):
        o_ref[0, 0] = out.astype(o_ref.dtype)

    softmax_finish(ki, n_kvb, acc_ref, l_ref, write)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset: jnp.ndarray | int = 0,
    kv_lengths: jnp.ndarray | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """q [B, Sq, Hq, D]; k, v [B, Skv, Hkv, D] → [B, Sq, Hq, D].

    Same contract as ops.attention.mha_attention (minus ``bias``).
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if hq % hkv != 0:
        raise ValueError(f"query heads {hq} not divisible by kv heads {hkv}")
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d**0.5)

    if not isinstance(q_offset, jnp.ndarray) or q_offset.ndim == 0:
        q_offset = jnp.full((b,), q_offset, jnp.int32)
    q_offset = q_offset.astype(jnp.int32)
    if kv_lengths is None:
        kv_lengths = jnp.full((b,), skv, jnp.int32)
    kv_lengths = kv_lengths.astype(jnp.int32)

    # head-major views; the pads land on the (blocked) sequence dims
    qh = q.swapaxes(1, 2)  # [B, Hq, Sq, D]
    kh = k.swapaxes(1, 2)  # [B, Hkv, Skv, D]
    vh = v.swapaxes(1, 2)

    bq = min(block_q, _round_up(sq, 8))
    bkv = min(block_kv, _round_up(skv, 8))
    sq_p, skv_p = _round_up(sq, bq), _round_up(skv, bkv)
    if sq_p != sq:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if skv_p != skv:
        # padded keys sit at positions >= skv >= kv_lengths → masked out
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    n_qb, n_kvb = sq_p // bq, skv_p // bkv

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, block_q=bq, block_kv=bkv, n_kvb=n_kvb,
    )
    out = pl.pallas_call(
        kernel,
        name="attention",  # tracing.SCOPES: the kernel is named for its phase
        grid=(b, hq, n_qb, n_kvb),
        in_specs=[
            pl.BlockSpec((b,), lambda bi, hi, qi, ki: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((b,), lambda bi, hi, qi, ki: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q_offset, kv_lengths, qh, kh, vh)
    return out[:, :, :sq].swapaxes(1, 2)
