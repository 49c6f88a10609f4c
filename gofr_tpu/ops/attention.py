"""Attention ops: batched GQA prefill and single-token decode.

TPU-first shape discipline: everything is [batch, seq, heads, head_dim]
with static shapes; grouped-query attention is computed by folding query
heads into groups ([B, S, Hkv, G, D]) so the contraction runs as one big
einsum on the MXU instead of repeating K/V in HBM.

``backend="xla"`` is plain einsum + masked softmax (XLA fuses this well at
serving sizes); ``backend="pallas"`` dispatches to the flash kernels in
``gofr_tpu.ops.pallas`` (blocked online-softmax; no S×S materialization).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from gofr_tpu.tracing import scoped

NEG_INF = -1e30


# The ops whose kernel serves ``backend="auto"`` on a TPU: each entry names
# the chip measurement that put it there. Every other op stays on XLA there
# (docs/kernels.md has the timings on record for those).
KERNEL_OPS = (
    "paged_decode",  # bf16 pool: 1.04 against XLA's 1.74 ms a layer at full lengths, 2.5x / 1.7x tokens/s (PERF.md §6, PR 30)
)


def resolve_backend(backend: str, op: str | None = None) -> str:
    """Which implementation serves ``op``: an explicit ``backend`` argument
    first, then the rule. 'auto' is the kernel where it has won its
    measurement on the chip (``KERNEL_OPS``) and the traced computation
    targets a TPU (``ops.pallas.kernel_platform``, which honours
    ``platform_hint``); under the interpreter (GOFR_PALLAS_INTERPRET=1, the
    CPU tests) every op's kernel; otherwise XLA — so one model code path
    serves the CPU test mesh and real chips. An explicit 'pallas' is a
    request for the kernel by name: where it cannot lower it RAISES
    (ops.pallas.require_kernel_platform) rather than running XLA."""
    if backend == "auto":
        from gofr_tpu.ops.pallas import interpret_mode, kernel_platform

        if interpret_mode() or (op in KERNEL_OPS and kernel_platform()):
            return "pallas"
        return "xla"
    if backend == "pallas":
        from gofr_tpu.ops.pallas import require_kernel_platform

        require_kernel_platform("backend='pallas'")
        return backend
    if backend != "xla":
        raise ValueError(f"unknown attention backend {backend!r}; use 'auto', 'xla' or 'pallas'")
    return backend


def _group_query_heads(q: jnp.ndarray, num_kv_heads: int) -> jnp.ndarray:
    """[B, S, Hq, D] → [B, S, Hkv, G, D]."""
    b, s, hq, d = q.shape
    if hq % num_kv_heads != 0:
        raise ValueError(f"query heads {hq} not divisible by kv heads {num_kv_heads}")
    return q.reshape(b, s, num_kv_heads, hq // num_kv_heads, d)


@scoped("attention")
def mha_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset: jnp.ndarray | int = 0,
    kv_lengths: jnp.ndarray | None = None,
    bias: jnp.ndarray | None = None,
    scale: float | None = None,
    backend: str = "auto",
    window: jnp.ndarray | int | None = None,
) -> jnp.ndarray:
    """Full (prefill) attention.

    q: [B, Sq, Hq, D]; k, v: [B, Skv, Hkv, D] → out [B, Sq, Hq, D].

    ``q_offset`` shifts query positions (per-batch int array or scalar) so a
    chunked prefill at cache offset t attends causally as positions t..t+Sq.
    ``kv_lengths`` [B] masks padded key positions. ``bias`` is an additive
    [B, 1|Hq, Sq, Skv] mask/ALiBi-style term. ``window`` (a scalar, traced
    or not; needs ``causal``) is a sliding window: key j is visible to the
    query at position i iff ``i - window < j <= i``; None = full attention,
    the trace as it was.
    """
    backend = resolve_backend(backend)
    if window is not None and not causal:
        raise ValueError("a sliding window is defined on causal attention only")
    if backend == "pallas" and bias is None and window is None:  # kernel has no bias or window path
        if not isinstance(q_offset, jnp.ndarray):
            q_offset = jnp.asarray(q_offset, jnp.int32)
        if kv_lengths is None:
            kv_lengths = jnp.full((q.shape[0],), k.shape[1], jnp.int32)
        return _flash_mha(q, k, v, q_offset, kv_lengths, causal, scale)

    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    scale = scale if scale is not None else 1.0 / (d**0.5)

    qg = _group_query_heads(q, hkv)  # [B, Sq, Hkv, G, D]
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale

    mask = None
    if causal:
        if isinstance(q_offset, jnp.ndarray) and q_offset.ndim == 1:
            q_pos = jnp.arange(sq)[None, :] + q_offset[:, None]  # [B, Sq]
            causal_mask = q_pos[:, :, None] >= jnp.arange(skv)[None, None, :]  # [B, Sq, Skv]
            if window is not None:
                causal_mask &= q_pos[:, :, None] - window < jnp.arange(skv)[None, None, :]
            causal_mask = causal_mask[:, None, None]  # [B, 1, 1, Sq, Skv]
        else:
            q_pos = jnp.arange(sq)[:, None] + q_offset
            causal_mask = q_pos >= jnp.arange(skv)[None, :]
            if window is not None:
                causal_mask &= q_pos - window < jnp.arange(skv)[None, :]
            causal_mask = causal_mask[None, None, None]
        mask = causal_mask
    if kv_lengths is not None:
        len_mask = jnp.arange(skv)[None, :] < kv_lengths[:, None]  # [B, Skv]
        len_mask = len_mask[:, None, None, None, :]
        mask = len_mask if mask is None else (mask & len_mask)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    if bias is not None:
        # bias [B, H, Sq, Skv] → regroup to [B, Hkv, G, Sq, Skv]
        bh = bias.shape[1]
        bias5 = bias.reshape(b, hkv, bh // hkv, *bias.shape[2:]) if bh > 1 else bias[:, :, None]
        scores = scores + bias5.astype(jnp.float32)

    probs = _softmax(scores)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, hq, d)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_mha(q, k, v, q_offset, kv_lengths, causal, scale):
    """Pallas flash forward with an XLA-recompute backward: pallas_call has
    no JVP rule, so gradients re-derive the attention via the einsum path
    (flash-style recompute — no S×S tensor is saved between fwd and bwd)."""
    from gofr_tpu.ops.pallas import interpret_mode
    from gofr_tpu.ops.pallas.flash_attention import flash_attention

    return flash_attention(
        q, k, v, causal=causal, q_offset=q_offset, kv_lengths=kv_lengths,
        scale=scale, interpret=interpret_mode(),
    )


def _flash_mha_fwd(q, k, v, q_offset, kv_lengths, causal, scale):
    return _flash_mha(q, k, v, q_offset, kv_lengths, causal, scale), (q, k, v, q_offset, kv_lengths)


def _flash_mha_bwd(causal, scale, res, g):
    q, k, v, q_offset, kv_lengths = res

    def ref(q, k, v):
        return mha_attention(
            q, k, v, causal=causal, q_offset=q_offset, kv_lengths=kv_lengths,
            scale=scale, backend="xla",
        )

    _, vjp = jax.vjp(ref, q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


def _softmax(scores: jnp.ndarray) -> jnp.ndarray:
    """Softmax in f32 that returns zeros (not NaN) for fully-masked rows —
    padded query rows have every key masked."""
    m = jnp.max(scores, axis=-1, keepdims=True)
    unnorm = jnp.exp(scores - jnp.maximum(m, NEG_INF / 2))
    denom = jnp.sum(unnorm, axis=-1, keepdims=True)
    return unnorm / jnp.maximum(denom, 1e-20)


def slot_decode_kernel_ok(smax: int) -> bool:
    """Can the slot decode kernel tile a cache of length ``smax``? An
    awkward Smax (e.g. prime) would degrade the kernel's kv block to a
    sliver and serialize the grid; the block must also be a multiple of 8
    (f32 sublane tile) — only 128-aligned caches, which the engine builds
    whenever the model allows, are implicitly safe (ADVICE.md)."""
    from gofr_tpu.ops.pallas.decode_attention import _pick_block

    bkv = _pick_block(smax, 512)
    return bkv >= min(smax, 128) and bkv % 8 == 0


@scoped("attention")
def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float | None = None,
    backend: str = "auto",
    window: jnp.ndarray | int | None = None,
) -> jnp.ndarray:
    """Single-step decode: q [B, Hq, D] against a head-major cache
    [B, Hkv, Smax, D], attending to positions < lengths[b] — with a
    ``window``, to the last ``window`` of them (the query sits at
    ``lengths - 1``; XLA path only). Returns [B, Hq, D]."""
    if window is None and resolve_backend(backend, op="decode") == "pallas":
        smax = k_cache.shape[2]
        if slot_decode_kernel_ok(smax):
            from gofr_tpu.ops.pallas import interpret_mode
            from gofr_tpu.ops.pallas.decode_attention import decode_attention as pallas_decode

            return pallas_decode(
                q, k_cache, v_cache, lengths, scale=scale, interpret=interpret_mode()
            )
        if backend == "pallas":
            # Only 'auto' may degrade silently — an explicit request the
            # kernel cannot satisfy must not be ignored (ADVICE.md round 2;
            # paged_decode_attention already raises for its analog).
            raise ValueError(
                f"backend='pallas' requested but cache Smax {smax} has no kv "
                f"block >= min(Smax, 128) that divides Smax and is a multiple "
                f"of 8; use a 128-aligned cache length or backend='auto'"
            )
    b, hq, d = q.shape
    _, hkv, smax, _ = k_cache.shape
    scale = scale if scale is not None else 1.0 / (d**0.5)
    qg = q.reshape(b, hkv, hq // hkv, d)  # head h groups under kv head h // G
    scores = jnp.einsum("bkgd,bktd->bkgt", qg, k_cache).astype(jnp.float32) * scale
    mask = jnp.arange(smax)[None, :] < lengths[:, None]  # [B, Smax]
    if window is not None:
        mask &= jnp.arange(smax)[None, :] >= lengths[:, None] - window
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = _softmax(scores)
    out = jnp.einsum("bkgt,bktd->bkgd", probs.astype(v_cache.dtype), v_cache)
    return out.reshape(b, hq, d)


@scoped("attention")
def decode_attention_q(
    q: jnp.ndarray,        # [B, Hq, D]
    k_cache: jnp.ndarray,  # int8 [B, Hkv, Smax, D]
    v_cache: jnp.ndarray,  # int8 [B, Hkv, Smax, D]
    k_scale: jnp.ndarray,  # [B, Hkv, Smax]
    v_scale: jnp.ndarray,  # [B, Hkv, Smax]
    lengths: jnp.ndarray,
    *,
    scale: float | None = None,
) -> jnp.ndarray:
    """decode_attention over an int8 KV cache (ops.kvcache.QSlotKVCache).

    The int8 operands convert at the matmul input (XLA fuses the convert
    into the operand read — HBM traffic stays int8, the same mechanism as
    weight-only qdot, ops/quant.py:52). Per-position scales fold OUTSIDE
    the contractions: ``ks`` multiplies scores per key position (constant
    along the D reduction) and ``vs`` rides the probabilities (constant
    along the T reduction)."""
    b, hq, d = q.shape
    _, hkv, smax, _ = k_cache.shape
    scale = scale if scale is not None else 1.0 / (d**0.5)
    qg = q.reshape(b, hkv, hq // hkv, d)
    scores = jnp.einsum("bkgd,bktd->bkgt", qg, k_cache.astype(q.dtype)).astype(jnp.float32)
    scores = scores * k_scale[:, :, None, :].astype(jnp.float32) * scale
    mask = jnp.arange(smax)[None, :] < lengths[:, None]
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = _softmax(scores)
    pv = (probs * v_scale[:, :, None, :].astype(jnp.float32)).astype(q.dtype)
    out = jnp.einsum("bkgt,bktd->bkgd", pv, v_cache.astype(q.dtype))
    return out.reshape(b, hq, d)


# -- tensor-parallel dispatch ------------------------------------------------
#
# When an engine pins a paged.KVShardCtx (pool planes sharded over the
# mesh's tp axis along KV heads), the three paged decode entry points wrap
# their single-device bodies in shard_map: each device runs the SAME kernel
# (Pallas or XLA gather) over its own Hkv/tp heads and Hq/tp query heads,
# block tables and lengths replicated. No collective is emitted here — the
# output stays head-sharded and the model's o-projection matmul (tp-sharded
# wo) supplies the single psum that already existed for the weights.


def _kv_shard_ctx(q: jnp.ndarray, pool: jnp.ndarray):
    """The pinned shard ctx, or None when the geometry can't split (head
    counts must divide evenly — sharding never pads heads)."""
    from gofr_tpu.ops.paged import current_kv_shard

    ctx = current_kv_shard()
    if ctx is None:
        return None
    if q.shape[1] % ctx.shards or pool.shape[2] % ctx.shards:
        return None
    return ctx


def _shard_paged_call(impl, ctx, heads, pools, layer, table, lengths, pools_out: int = 0,
                      window=None):
    """Run ``impl(*heads, *pools, layer, table, lengths[, window])`` per-shard: every
    ``heads`` operand ([N, H, D]: q, a step's new K/V) splits on its head
    axis (dim 1) and every whole pool plane on its KV-head axis
    (paged.plane_partition_spec), layer/table/lengths replicated, output
    head-sharded (no reduce — see module note above). An ``impl`` that
    writes returns its first ``pools_out`` planes after the output; they
    come back sharded as they went in. A ``window`` rides replicated, last."""
    from jax.sharding import PartitionSpec as P

    from gofr_tpu.ops.paged import plane_partition_spec

    ax = ctx.axis
    head_spec = P(None, ax, None)
    pool_specs = tuple(plane_partition_spec(p.ndim, ax) for p in pools)
    scalars = (jnp.asarray(layer, jnp.int32), table, lengths)
    if window is not None:
        scalars += (jnp.asarray(window, jnp.int32),)
    return jax.shard_map(
        impl,
        mesh=ctx.mesh,
        in_specs=(head_spec,) * len(heads) + pool_specs + (P(),) * len(scalars),
        out_specs=(head_spec,) + pool_specs[:pools_out] if pools_out else head_spec,
        check_vma=False,
    )(*heads, *pools, *scalars)


def _require_kernel_page(pool: jnp.ndarray) -> None:
    """The paged kernels tile a page on the f32 sublane: a pool they cannot
    read is an error where the program is traced (warm-up), never a quiet
    XLA run."""
    page = pool.shape[3]
    if page % 8:
        raise ValueError(
            f"the paged-decode kernel needs page_size % 8 == 0 (f32 sublane "
            f"tile); this pool has pages of {page} (plane {tuple(pool.shape)}): "
            f"use a page_size that is a multiple of 8, or backend='xla'")


@scoped("attention")
def paged_decode_attention_q(
    q: jnp.ndarray,        # [N, Hq, D]
    kq_pool: jnp.ndarray,  # int8 [P, Hkv, page, D]
    vq_pool: jnp.ndarray,
    ks_pool: jnp.ndarray,  # [L, P, Hkv, page]
    vs_pool: jnp.ndarray,
    layer,                 # scalar layer index into the pool planes
    table: jnp.ndarray,    # [N, MaxP]
    lengths: jnp.ndarray,
    *,
    scale: float | None = None,
    backend: str = "auto",
) -> jnp.ndarray:
    ctx = _kv_shard_ctx(q, kq_pool)
    if ctx is not None:
        impl = partial(_paged_decode_attention_q_local, scale=scale, backend=backend)
        return _shard_paged_call(impl, ctx, (q,), (kq_pool, vq_pool, ks_pool, vs_pool),
                                 layer, table, lengths)
    return _paged_decode_attention_q_local(
        q, kq_pool, vq_pool, ks_pool, vs_pool, layer, table, lengths,
        scale=scale, backend=backend,
    )


def _paged_decode_attention_q_local(
    q: jnp.ndarray,
    kq_pool: jnp.ndarray,
    vq_pool: jnp.ndarray,
    ks_pool: jnp.ndarray,
    vs_pool: jnp.ndarray,
    layer,
    table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float | None = None,
    backend: str = "auto",
) -> jnp.ndarray:
    """paged_decode_attention over an int8 pool (ops.paged.QPagedKVCache).

    'pallas' is the FUSED kernel (ops.pallas.paged_decode.paged_decode_
    attention_q): int8 pages + scale rows stream straight out of the pool
    through the scalar-prefetched block tables and dequantize in-kernel —
    no materialized logical view, HBM traffic stays int8. 'xla' gathers
    the int8 logical views + scales per slot (one extra HBM round trip for
    the copy) and reuses the folded-scale dense decode path — correct
    everywhere. 'auto' follows resolve_backend."""
    if resolve_backend(backend, op="paged_decode_q") == "pallas":
        from gofr_tpu.ops.pallas import interpret_mode
        from gofr_tpu.ops.pallas.paged_decode import (
            paged_decode_attention_q as pallas_paged_q,
        )

        _require_kernel_page(kq_pool)
        return pallas_paged_q(
            q, kq_pool, vq_pool, ks_pool, vs_pool, layer, table, lengths,
            scale=scale, interpret=interpret_mode(),
        )
    from gofr_tpu.ops.paged import gather_kv_q

    gkq, gks = gather_kv_q(kq_pool, ks_pool, layer, table)
    gvq, gvs = gather_kv_q(vq_pool, vs_pool, layer, table)
    return decode_attention_q(q, gkq, gvq, gks, gvs, lengths, scale=scale)


@scoped("attention")
def paged_decode_attention_q4(
    q: jnp.ndarray,        # [N, Hq, D]
    kq_pool: jnp.ndarray,  # uint8 [P, Hkv, page, D//2] packed nibbles
    vq_pool: jnp.ndarray,
    ks_pool: jnp.ndarray,  # [L, P, Hkv, page]
    vs_pool: jnp.ndarray,
    layer,                 # scalar layer index into the pool planes
    table: jnp.ndarray,    # [N, MaxP]
    lengths: jnp.ndarray,
    *,
    scale: float | None = None,
    backend: str = "auto",
) -> jnp.ndarray:
    ctx = _kv_shard_ctx(q, kq_pool)
    if ctx is not None:
        impl = partial(_paged_decode_attention_q4_local, scale=scale, backend=backend)
        return _shard_paged_call(impl, ctx, (q,), (kq_pool, vq_pool, ks_pool, vs_pool),
                                 layer, table, lengths)
    return _paged_decode_attention_q4_local(
        q, kq_pool, vq_pool, ks_pool, vs_pool, layer, table, lengths,
        scale=scale, backend=backend,
    )


def _paged_decode_attention_q4_local(
    q: jnp.ndarray,
    kq_pool: jnp.ndarray,
    vq_pool: jnp.ndarray,
    ks_pool: jnp.ndarray,
    vs_pool: jnp.ndarray,
    layer,
    table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float | None = None,
    backend: str = "auto",
) -> jnp.ndarray:
    """paged_decode_attention over a PACKED int4 pool (ops.paged.
    Q4PagedKVCache; ops/quant.pack_int4 split-half nibble format).

    'pallas' is the FUSED kernel (ops.pallas.paged_decode.paged_decode_
    attention_q4): packed byte pages + scale rows stream straight out of
    the pool through the scalar-prefetched block tables; nibble unpack +
    dequant happen in-register, so the KV HBM read is half the int8
    kernel's. 'xla' gathers the packed views, unpacks after the gather
    (ops.paged.gather_kv_q4), and reuses the folded-scale dense decode
    path — correct everywhere, the parity reference for the kernel.
    'auto' follows resolve_backend (op key 'paged_decode_q4')."""
    if resolve_backend(backend, op="paged_decode_q4") == "pallas":
        from gofr_tpu.ops.pallas import interpret_mode
        from gofr_tpu.ops.pallas.paged_decode import (
            paged_decode_attention_q4 as pallas_paged_q4,
        )

        _require_kernel_page(kq_pool)
        return pallas_paged_q4(
            q, kq_pool, vq_pool, ks_pool, vs_pool, layer, table, lengths,
            scale=scale, interpret=interpret_mode(),
        )
    from gofr_tpu.ops.paged import gather_kv_q4

    gkq, gks = gather_kv_q4(kq_pool, ks_pool, layer, table)
    gvq, gvs = gather_kv_q4(vq_pool, vs_pool, layer, table)
    return decode_attention_q(q, gkq, gvq, gks, gvs, lengths, scale=scale)


@scoped("attention")
def paged_decode_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    layer,
    table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float | None = None,
    backend: str = "auto",
    window: jnp.ndarray | int | None = None,
) -> jnp.ndarray:
    ctx = _kv_shard_ctx(q, k_pool)
    if ctx is not None:
        impl = partial(_paged_decode_attention_local, scale=scale, backend=backend)
        return _shard_paged_call(impl, ctx, (q,), (k_pool, v_pool), layer, table, lengths,
                                 window=window)
    return _paged_decode_attention_local(
        q, k_pool, v_pool, layer, table, lengths, window, scale=scale, backend=backend,
    )


def _paged_decode_attention_local(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    layer,
    table: jnp.ndarray,
    lengths: jnp.ndarray,
    window: jnp.ndarray | int | None = None,
    *,
    scale: float | None = None,
    backend: str = "auto",
) -> jnp.ndarray:
    """Single-step decode against one layer of a paged KV pool (ops.paged
    layout).

    q [N, Hq, D]; k_pool/v_pool [L, P, Hkv, page, D] — the WHOLE planes,
    read at ``layer`` without slicing it out; table [N, MaxP] block table
    (OOB entries == P); lengths [N] → out [N, Hq, D].

    'pallas' streams pages straight out of the pool through scalar-prefetched
    block tables (ops.pallas.paged_decode); 'xla' materializes each slot's
    logical view with one gather (ops.paged.gather_kv) and reuses the dense
    decode path — correct everywhere, but pays an extra HBM round trip.
    ``window`` (a scalar, an operand of the kernel): the lane's query at
    ``lengths - 1`` sees the last ``window`` positions only; None = all of
    them, and the kernel's trace as it is without the argument.
    """
    if resolve_backend(backend, op="paged_decode") == "pallas":
        from gofr_tpu.ops.pallas import interpret_mode
        from gofr_tpu.ops.pallas.paged_decode import paged_decode_attention as pallas_paged

        _require_kernel_page(k_pool)
        return pallas_paged(
            q, k_pool, v_pool, layer, table, lengths,
            scale=scale, interpret=interpret_mode(), window=window,
        )
    from gofr_tpu.ops.paged import gather_kv

    k_view, v_view = gather_kv(k_pool, v_pool, layer, table)
    return decode_attention(q, k_view, v_view, lengths, scale=scale, backend="xla", window=window)


def append_rides_in_kernel(k_pool: jnp.ndarray, backend: str = "auto") -> bool:
    """Who writes a decode token's K/V into a dense paged pool: the
    ``paged_decode`` kernel itself (``paged_decode_append_attention``: one
    call a layer appends and attends) exactly where that kernel serves the
    read (``resolve_backend``) and can address the plane's rows — head_dim a
    multiple of the 128 lanes, pages of whole sublane tiles. Everywhere else
    (head_dim 64, the CPU, ``backend="xla"``; the int8 / int4 pools never
    ask) ``ops.paged.append_tokens_paged``'s scatter writes and the read
    path follows. No switch: the engine reports it (``app_tpu_kernel_backend
    {op="paged_append"}``), nothing chooses it."""
    if resolve_backend(backend, op="paged_decode") != "pallas":
        return False
    from gofr_tpu.ops.pallas.paged_decode import append_in_kernel

    return append_in_kernel(k_pool)


@scoped("attention")
def paged_decode_append_attention(
    q: jnp.ndarray,          # [N, Hq, D]
    k_new: jnp.ndarray,      # [N, Hkv, D]
    v_new: jnp.ndarray,
    k_pool: jnp.ndarray,     # [L, P, Hkv, page, D]
    v_pool: jnp.ndarray,
    layer,
    table: jnp.ndarray,      # [N, MaxP], OOB entries == P
    positions: jnp.ndarray,  # [N]
    *,
    scale: float | None = None,
    window: jnp.ndarray | int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``append_tokens_paged`` at ``positions`` then ``paged_decode_attention``
    over ``positions + 1`` (the last ``window`` of them, if given), as ONE
    kernel call that updates the planes where they lie → (attn, k_pool,
    v_pool). For pools ``append_rides_in_kernel`` admits; the write's time
    is the ``attention`` scope's."""
    from gofr_tpu.ops.pallas import interpret_mode
    from gofr_tpu.ops.pallas.paged_decode import paged_decode_append_attention as fused

    impl = partial(fused, scale=scale, interpret=interpret_mode())
    ctx = _kv_shard_ctx(q, k_pool)
    if ctx is not None:
        return _shard_paged_call(impl, ctx, (q, k_new, v_new), (k_pool, v_pool),
                                 layer, table, positions, pools_out=2, window=window)
    return impl(q, k_new, v_new, k_pool, v_pool, layer, table, positions, window)
