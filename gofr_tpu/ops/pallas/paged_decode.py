"""Decode attention over the PAGED KV cache as a Pallas kernel.

Same HBM-bound hot loop as decode_attention.py, but K/V come out of the
physical page pool [L, P, Hkv, page, D] through each slot's block table
instead of a contiguous [Smax] row. The table, the per-slot lengths and the
layer index ride in as SCALAR-PREFETCH operands
(pltpu.PrefetchScalarGridSpec), so a kernel knows (layer, physical page)
before it asks for a byte — it reads the pages a slot owns where they lie,
never a gather-materialized copy of the logical view (that copy is the XLA
fallback, ops.paged.gather_kv).

``paged_decode_attention`` (bf16 pool; what the served decode program runs
on a TPU, ops/attention.resolve_backend) — grid (1,), the pool planes left in
HBM. The kernel lists the LIVE lanes (a page in the table's first column, a
length above 0) and loops over them; an idle lane costs no copy, no product
and no grid step, and its output rows are zeros. A live lane's pages are a
loop over ceil(len / page), each page ONE copy a plane of [Hkv, page, D] — a
whole page of every KV head, which the pool's layout makes one contiguous
run — into one of two VMEM buffers, the next page's copy (the next live
lane's first, at a lane's end) in flight while this one is attended. A page
past a lane's length costs no copy and no arithmetic. Every head is attended
at once: one [Hq, D] x [D, Hkv*page] product whose blocks off a query head's
own KV head are masked away together with the positions past the length.
Unallocated table entries (== P) inside a length clamp to P-1. docs/kernels.md
has the design's A/Bs: against a BlockSpec-only grid of (slot, logical_page),
and of a grid step a lane against one loop over the live lanes.

``paged_decode_append_attention`` is the same kernel serving a whole decode
step of a layer: it also WRITES each lane's new K/V row into the planes,
which go in and come out aliased. The row's page is the lane's last, so it
is in VMEM when the row exists: the aligned sublane tile around the row is
patched there (the page is attended with it, so nothing is read back from
HBM after a write), staged and copied to the plane — O(tokens), no page
rewritten whole, no scatter of its own (ops/attention.append_rides_in_kernel
says where; PERF.md §6, PR 33).

The int8 and int4 kernels below keep the older grid, (slot, kv_head,
logical_page) with one [page, D] tile a step, dead pages included.

``paged_decode_attention_q`` is the fused int8-KV variant (ISSUE 6 /
ROADMAP O3): quantized K/V pages plus their per-position scale planes
stream straight out of the pool through the SAME scalar-prefetched block
tables and dequantize in-kernel — ``ks`` multiplies the scores, ``vs``
rides the probabilities inside the online-softmax recurrence (common.py),
exactly where the XLA path folds them (ops.attention.decode_attention_q).
No gather-materialized logical view exists anywhere: HBM traffic for the
most bandwidth-bound op in the system stays int8 end to end, where the
XLA fallback pays a full extra int8 round trip for the gather copy.

``paged_decode_attention_q4`` (ISSUE 13) extends the same discipline to
PACKED int4 pages: the pool stores two nibbles per byte ([P, Hkv, page,
D//2] uint8, ops/quant.pack_int4 split-half order), the kernel streams the
packed bytes through the identical scalar-prefetched block tables, and the
nibble unpack + dequant happen in-register — HBM reads per KV token halve
again vs int8. The scale folds are byte-for-byte the int8 kernel's: ks on
the scores after the QK matmul, vs inside the online-softmax recurrence.

None of the wrappers carries a ``jax.jit`` of its own: each is traced
inside the program that calls it, so its operations are named (and located)
by that program alone, whatever else traced the kernel first in the process —
the persistent compile cache keys on those names (tpu/device.py).
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
    select_head_row,
    softmax_block_update,
    softmax_finish,
)


def _layer_operand(layer) -> jnp.ndarray:
    """The layer index as the [1] int32 scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


# Scores of a query head against another KV head's keys carry this "position":
# beyond every length, so the length mask drops them too.
_OTHER_HEAD = 1 << 30
_LANES = 128


def _tile_rows(dtype) -> int:
    """Rows of one sublane tile of a plane in HBM: the least a copy can address
    (8 rows of 32 bits; a bf16 tile packs 16)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _paged_decode_kernel(*refs, scale: float, page: int, pool: int, append: bool,
                         windowed: bool = False):
    """One grid step for the whole call: the live lanes are a loop, and so are
    a lane's pages. An idle lane is not visited at all.

    A lane is live when its table's first entry is a page of the pool and its
    length is not 0; an idle lane (the engine hands one a row of ``pool``) costs
    a look at two scalars. The kernel lists the live lanes in lane order in
    SMEM first and loops over that list. The copies form one chain over the
    live lanes: page ``j`` of a lane lands in buffer
    ``(seq + j - first) % 2``, ``seq`` counting the pages visited before it,
    and the copy of the page after it — the NEXT LIVE LANE's first when ``j``
    is this lane's last — starts before page ``j`` is attended. The last live
    lane leaves no copy in flight. The output is one block, zero-filled once:
    an idle lane's rows stay zero.

    With ``append`` the lane's new K/V row is written from here too. Its
    page is the lane's last, so it is in VMEM when the row exists: the
    aligned tile of rows around ``off`` is patched there (the page is then
    attended WITH the row: nothing is read back from HBM after the write),
    staged, and copied to the plane. The wait for that copy is put off by
    two live lanes — the live lane after next waits before it stages its
    own — and what is left is waited for after the loop.

    ``windowed``: a last scalar-prefetch operand win [1] bounds what a lane
    sees to its last ``win`` positions, ``[length - win, length)``. Its page
    loop then STARTS at the page that holds position ``length - win`` (pages
    wholly behind the window cost no copy and no arithmetic, like pages past
    the length) and the positions of that first page that lie behind the
    window are masked. The chain of copies runs over the pages that are
    visited. A window that never binds (``win >= length``) visits what the
    unwindowed kernel visits."""
    # scalar prefetch (SMEM): ln [N] positions a lane attends (the new token's
    # included), table [N, MaxP] (entries == pool: no page), layer [1]; with
    # ``append`` wp [N] the page a lane's new row goes to
    # (>= pool: it writes nothing) and off [N] its row in that page. VMEM
    # blocks: q [N, Hq, d]; pos int32 [Hq, Hkv*page], the position in the
    # page or _OTHER_HEAD off the head's own block; knew / vnew [N, Hkv, d],
    # the step's K/V of every lane; the output [N, Hq, d]. The planes
    # [L, P, Hkv, page, d] stay where they lie; when they are written the
    # aliased outputs ARE the planes, read and written through one ref each.
    if windowed:  # the window rides last among the scalars
        n_scalars = 6 if append else 4
        win_ref, refs = refs[n_scalars - 1], refs[:n_scalars - 1] + refs[n_scalars:]
    if append:
        (ln_ref, table_ref, layer_ref, wp_ref, off_ref, q_ref, pos_ref, knew_ref, vnew_ref,
         _, _, o_ref, k_hbm, v_hbm, *scratch) = refs
    else:
        ln_ref, table_ref, layer_ref, q_ref, pos_ref, k_hbm, v_hbm, o_ref, *scratch = refs
    (k_buf, v_buf,  # [2, Hkv, page, d]: the page being attended and the one on its way
     sem,           # DMA semaphores [2 planes, 2 buffers]
     acc_ref,       # f32 [Hq, d]
     m_ref, l_ref,  # f32 [Hq, 128]
     live_ref,      # SMEM [N]: the live lanes in lane order
     *stage) = scratch
    lanes, span = table_ref.shape[0], table_ref.shape[1] * page
    hkv, _, d = k_buf.shape[1:]

    def length_of(lane):
        return jnp.minimum(ln_ref[lane], span)

    def listed(lane, count):
        live = (table_ref[lane, 0] < pool) & (ln_ref[lane] > 0)

        @pl.when(live)
        def _():
            live_ref[count] = lane

        return count + live.astype(jnp.int32)

    count = jax.lax.fori_loop(0, lanes, listed, 0)  # live_ref past count is never read: it holds nothing

    def page_copies(lane, j, buf):
        src = (layer_ref[0], jnp.minimum(table_ref[lane, j], pool - 1))
        return (pltpu.make_async_copy(k_hbm.at[src], k_buf.at[buf], sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[src], v_buf.at[buf], sem.at[1, buf]))

    def start(lane, j, buf):
        for copy in page_copies(lane, j, buf):
            copy.start()

    if windowed:
        def seen_from(lane):  # the first position a lane's window holds
            return jnp.maximum(length_of(lane) - win_ref[0], 0)

        def first_page(lane):
            return seen_from(lane) // page
    else:
        def first_page(lane):
            return 0

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(count > 0)
    def _():
        start(live_ref[0], first_page(live_ref[0]), 0)

    if append:
        k_stage, v_stage, wsem = stage  # [2, Hkv, rows, d] a plane; DMA semaphores [2 planes, 2 slots]
        rows = k_stage.shape[2]

        def writes(lane):
            return wp_ref[lane] < pool

        def tile_base(lane):  # first row of the aligned tile that holds the lane's new row
            return pl.multiple_of(off_ref[lane] // rows * rows, rows)

        def tile_copies(lane, slot):
            dst = (layer_ref[0], wp_ref[lane], slice(None), pl.ds(tile_base(lane), rows))
            return (pltpu.make_async_copy(k_stage.at[slot], k_hbm.at[dst], wsem.at[0, slot]),
                    pltpu.make_async_copy(v_stage.at[slot], v_hbm.at[dst], wsem.at[1, slot]))

        def wait_tiles(i):  # the i-th live lane's tile copies, where it started them
            @pl.when(i >= 0)
            def _():
                lane = live_ref[i]

                @pl.when(writes(lane))
                def _():
                    for copy in tile_copies(lane, i % 2):
                        copy.wait()

        def patch(lane, slot, buf):
            base = tile_base(lane)
            hit = jax.lax.broadcasted_iota(jnp.int32, (rows, d), 0) == off_ref[lane] - base
            for new_ref, page_buf, stage in ((knew_ref, k_buf, k_stage), (vnew_ref, v_buf, v_stage)):
                new = new_ref[lane].astype(jnp.float32)  # [Hkv, d]; a row is cut out of 32-bit sublanes
                for h in range(hkv):
                    tile = page_buf[buf, h, pl.ds(base, rows), :]
                    tile = jnp.where(hit, new[h:h + 1, :], tile.astype(jnp.float32)).astype(tile.dtype)
                    stage[slot, h] = tile
                    page_buf[buf, h, pl.ds(base, rows), :] = tile
            for copy in tile_copies(lane, slot):
                copy.start()

    def one_lane(i, seq):
        lane = live_ref[i]
        length = length_of(lane)
        first = first_page(lane)
        mine = pl.cdiv(length, page)  # one past the last page this lane copies
        init_softmax_scratch(0, acc_ref, m_ref, l_ref)
        if append:
            wait_tiles(i - 2)  # this lane's staging slot is free again

        def attend(j, carry):
            buf = (seq + j - first) % 2
            last = j + 1 == mine

            @pl.when(jnp.logical_not(last) | (i + 1 < count))
            def _():
                # the next live lane's first page at this lane's last
                ahead = live_ref[jnp.minimum(i + 1, count - 1)]
                start(jnp.where(last, ahead, lane), jnp.where(last, first_page(ahead), j + 1), 1 - buf)

            for copy in page_copies(lane, j, buf):
                copy.wait()

            if append:
                @pl.when(last & writes(lane))
                def _():
                    patch(lane, i % 2, buf)

            # every head at once: one [Hq, d] x [d, Hkv*page] product whose
            # off-head blocks are masked away with the dead positions (a
            # product per head pays the same MXU weight loads: A/B in
            # docs/kernels.md)
            s = jax.lax.dot_general(
                q_ref[lane], k_buf[buf].reshape(hkv * page, d),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            ) * scale  # [Hq, Hkv*page]
            where = pos_ref[...] + j * page
            seen = (where < length) & (where >= seen_from(lane)) if windowed else where < length
            s = jnp.where(seen, s, NEG_INF)
            softmax_block_update(s, v_buf[buf].reshape(hkv * page, d), acc_ref, m_ref, l_ref)
            return carry

        jax.lax.fori_loop(first, mine, attend, 0)

        def write(out):
            o_ref[lane] = out.astype(o_ref.dtype)

        softmax_finish(0, 1, acc_ref, l_ref, write)
        return seq + mine - first

    jax.lax.fori_loop(0, count, one_lane, 0)

    if append:
        wait_tiles(count - 2)
        wait_tiles(count - 1)


def _paged_decode_call(q, k_pool, v_pool, layer, table, lengths, new, *, scale, interpret,
                       window=None):
    """The kernel's one call. ``new`` is None (read only → attn) or
    ``(k_new, v_new, write_page, write_row)`` (→ attn, k_pool, v_pool, the
    planes aliased). ``window`` (a scalar, traced or not) becomes one more
    scalar-prefetch operand; None leaves the call as it is without one."""
    n, hq, d = q.shape
    _, pool, hkv, page, _ = k_pool.shape
    if hq % hkv != 0:
        raise ValueError(f"query heads {hq} not divisible by kv heads {hkv}")
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d**0.5)
    append = new is not None

    col = jnp.arange(hkv * page, dtype=jnp.int32)[None, :]
    own = jnp.arange(hq, dtype=jnp.int32)[:, None] // group == col // page
    pos = jnp.where(own, col % page, _OTHER_HEAD)

    def whole(*shape):  # one grid step: a block is fetched once and needs no second buffer
        return pl.BlockSpec(shape, lambda *_: (0,) * len(shape), pipeline_mode=pl.Buffered(1))

    plane = pl.BlockSpec(memory_space=pl.ANY)
    attn = jax.ShapeDtypeStruct((n, hq, d), q.dtype)
    in_specs = [whole(n, hq, d), whole(hq, hkv * page)]
    operands = [q, pos]
    scratch = [
        pltpu.VMEM((2, hkv, page, d), k_pool.dtype),
        pltpu.VMEM((2, hkv, page, d), v_pool.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((hq, d), jnp.float32),
        pltpu.VMEM((hq, 128), jnp.float32),
        pltpu.VMEM((hq, 128), jnp.float32),
        pltpu.SMEM((n,), jnp.int32),
    ]
    prefetch = [lengths.astype(jnp.int32), table.astype(jnp.int32), _layer_operand(layer)]
    if append:
        k_new, v_new, write_page, write_row = new
        rows = _tile_rows(k_pool.dtype)
        prefetch += [write_page.astype(jnp.int32), write_row.astype(jnp.int32)]
        in_specs += [whole(n, hkv, d), whole(n, hkv, d)]
        operands += [k_new.astype(k_pool.dtype), v_new.astype(v_pool.dtype)]
        scratch += [
            pltpu.VMEM((2, hkv, rows, d), k_pool.dtype),
            pltpu.VMEM((2, hkv, rows, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ]
    if window is not None:
        prefetch += [jnp.maximum(jnp.asarray(window, jnp.int32), 1).reshape(1)]
    planes = len(prefetch) + len(operands)  # the K plane's place among the inputs
    kernel = functools.partial(_paged_decode_kernel, scale=scale, page=page, pool=pool, append=append,
                               **({} if window is None else {"windowed": True}))
    return pl.pallas_call(
        kernel,
        name="attention",  # tracing.SCOPES: the kernel is named for its phase
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(1,),
            in_specs=in_specs + [plane, plane],
            out_specs=[whole(n, hq, d), plane, plane] if append else whole(n, hq, d),
            scratch_shapes=scratch,
        ),
        out_shape=([attn, jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                    jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)] if append else attn),
        input_output_aliases={planes: 1, planes + 1: 2} if append else {},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, *operands, k_pool, v_pool)


def paged_decode_attention(
    q: jnp.ndarray,        # [N, Hq, D]
    k_pool: jnp.ndarray,   # [L, P, Hkv, page, D]
    v_pool: jnp.ndarray,   # [L, P, Hkv, page, D]
    layer,                 # scalar layer index
    table: jnp.ndarray,    # [N, MaxP] int32, OOB entries == P
    lengths: jnp.ndarray,  # [N] live length per slot
    *,
    scale: float | None = None,
    interpret: bool = False,
    window: jnp.ndarray | int | None = None,
) -> jnp.ndarray:
    """Single-step decode against the paged pool → [N, Hq, D]; with a
    ``window``, over each lane's last ``window`` positions."""
    d = q.shape[-1]
    if d % _LANES:
        # A copy cannot cut a page whose rows are narrower than the lane
        # width out of the plane. Such a pool's layer is padded to it first:
        # the zeros add nothing to a score and their output columns are
        # dropped. That is a copy of one layer a call, not the read in
        # place (it still beat XLA at Llama-1B's shape, docs/kernels.md).
        def widen(x):
            return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, -d % _LANES)])

        def layer_of(plane):
            return widen(jax.lax.dynamic_index_in_dim(plane, layer, 0, keepdims=True))

        return paged_decode_attention(
            widen(q), layer_of(k_pool), layer_of(v_pool), 0, table, lengths,
            scale=scale if scale is not None else 1.0 / (d**0.5), interpret=interpret,
            **({} if window is None else {"window": window}))[..., :d]
    return _paged_decode_call(q, k_pool, v_pool, layer, table, lengths, None,
                              scale=scale, interpret=interpret, window=window)


def append_in_kernel(k_pool: jnp.ndarray) -> bool:
    """Whether ``paged_decode_append_attention`` can write this plane: rows
    as wide as the lanes (a narrower pool is read through a padded COPY of
    one layer, so nothing can be written through it) and pages made of whole
    sublane tiles."""
    return k_pool.shape[4] % _LANES == 0 and k_pool.shape[3] % _tile_rows(k_pool.dtype) == 0


def paged_decode_append_attention(
    q: jnp.ndarray,          # [N, Hq, D]
    k_new: jnp.ndarray,      # [N, Hkv, D] the step's K/V per lane
    v_new: jnp.ndarray,
    k_pool: jnp.ndarray,     # [L, P, Hkv, page, D]
    v_pool: jnp.ndarray,
    layer,                   # scalar layer index
    table: jnp.ndarray,      # [N, MaxP] int32, OOB entries == P
    positions: jnp.ndarray,  # [N] where each lane's new row goes; it attends positions + 1
    window: jnp.ndarray | int | None = None,  # the last ``window`` of them; None = all
    *,
    scale: float | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A decode step's append AND attention in one call → (attn [N, Hq, D],
    k_pool, v_pool): ``ops.paged.append_tokens_paged`` then
    ``paged_decode_attention`` over ``positions + 1``, the planes updated
    where they lie (aliased) and no row read back from HBM after its write.
    A lane whose page is P (idle) or whose position lies past the table's
    span writes nothing."""
    if not append_in_kernel(k_pool):
        raise ValueError(
            f"the paged-decode kernel cannot write a plane {tuple(k_pool.shape)} of "
            f"{k_pool.dtype}: it needs head_dim % {_LANES} == 0 and page_size % "
            f"{_tile_rows(k_pool.dtype)} == 0 (append_tokens_paged writes any)")
    from gofr_tpu.ops.paged import _locate_append  # the scatter's own rule for what is dropped

    positions = positions.astype(jnp.int32)
    write_page, write_row = _locate_append(table, positions, k_pool.shape[3], k_pool.shape[1])
    return tuple(_paged_decode_call(
        q, k_pool, v_pool, layer, table, positions + 1, (k_new, v_new, write_page, write_row),
        scale=scale, interpret=interpret, window=window))


def _paged_decode_q_kernel(
    ln_ref,    # SMEM [N] per-slot live length (scalar prefetch)
    table_ref, # SMEM [N, MaxP] block table (scalar prefetch)
    layer_ref, # SMEM [1] layer index (scalar prefetch; index_maps only)
    q_ref,     # VMEM [1, 1, G, d]
    k_ref,     # VMEM int8 [1, 1, 1, page, d] — the (layer, page) from index_map
    v_ref,     # VMEM int8 [1, 1, 1, page, d]
    ks_ref,    # VMEM [1, 1, Hkv, page] per-position K scales (same page pick)
    vs_ref,    # VMEM [1, 1, Hkv, page]
    o_ref,     # VMEM [1, 1, G, d]
    acc_ref,   # scratch f32 [G, d]
    m_ref,     # scratch f32 [G, 128]
    l_ref,     # scratch f32 [G, 128]
    *,
    scale: float,
    page: int,
    n_pages: int,
    group: int,
):
    bi = pl.program_id(0)
    hi = pl.program_id(1)
    pi = pl.program_id(2)
    init_softmax_scratch(pi, acc_ref, m_ref, l_ref)

    q = q_ref[0, 0]                      # [G, d]
    k = k_ref[0, 0, 0].astype(q.dtype)   # int8 → compute dtype, in VMEM
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [G, page]
    # K-scale fold: constant along the d reduction, so it multiplies the
    # finished scores per key position (decode_attention_q order: scale
    # before the mask, where a masked position's value is irrelevant).
    s = s * select_head_row(ks_ref[0, 0], hi)

    kv_pos = pi * page + jax.lax.broadcasted_iota(jnp.int32, (group, page), 1)
    s = jnp.where(kv_pos < ln_ref[bi], s, NEG_INF)

    # V-scale fold happens inside the recurrence (common.py): probabilities
    # pick up vs before the PV matmul, v converts from int8 at the input.
    softmax_block_update(s, v_ref[0, 0, 0], acc_ref, m_ref, l_ref,
                         v_scale=select_head_row(vs_ref[0, 0], hi))

    def write(out):
        o_ref[0, 0] = out.astype(o_ref.dtype)

    softmax_finish(pi, n_pages, acc_ref, l_ref, write)


def paged_decode_attention_q(
    q: jnp.ndarray,        # [N, Hq, D]
    kq_pool: jnp.ndarray,  # int8 [L, P, Hkv, page, D]
    vq_pool: jnp.ndarray,  # int8 [L, P, Hkv, page, D]
    ks_pool: jnp.ndarray,  # [L, P, Hkv, page] per-position K scales
    vs_pool: jnp.ndarray,  # [L, P, Hkv, page]
    layer,                 # scalar layer index
    table: jnp.ndarray,    # [N, MaxP] int32, OOB entries == P
    lengths: jnp.ndarray,  # [N] live length per slot
    *,
    scale: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused single-step decode against the int8 paged pool → [N, Hq, D].

    Same contract as ops.attention.paged_decode_attention_q, without the
    gather: int8 pages and their scale rows are block-streamed per
    (slot, head, logical page) and dequantized in-register."""
    n, hq, d = q.shape
    _, pool, hkv, page, _ = kq_pool.shape
    _, maxp = table.shape
    if hq % hkv != 0:
        raise ValueError(f"query heads {hq} not divisible by kv heads {hkv}")
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d**0.5)

    q4 = q.reshape(n, hkv, group, d)
    safe_table = jnp.minimum(table, pool - 1).astype(jnp.int32)

    def kv_map(bi, hi, pi, ln_ref, table_ref, layer_ref):
        return (layer_ref[0], table_ref[bi, pi], hi, 0, 0)

    def sc_map(bi, hi, pi, ln_ref, table_ref, layer_ref):
        # all Hkv scale rows of the page; the kernel picks row hi
        # (common.select_head_row says why)
        return (layer_ref[0], table_ref[bi, pi], 0, 0)

    kernel = functools.partial(
        _paged_decode_q_kernel, scale=scale, page=page, n_pages=maxp, group=group
    )
    out = pl.pallas_call(
        kernel,
        name="attention",  # tracing.SCOPES: the kernel is named for its phase
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n, hkv, maxp),
            in_specs=[
                pl.BlockSpec((1, 1, group, d), lambda bi, hi, pi, ln, tb, ly: (bi, hi, 0, 0)),
                pl.BlockSpec((1, 1, 1, page, d), kv_map),
                pl.BlockSpec((1, 1, 1, page, d), kv_map),
                pl.BlockSpec((1, 1, hkv, page), sc_map),
                pl.BlockSpec((1, 1, hkv, page), sc_map),
            ],
            out_specs=pl.BlockSpec((1, 1, group, d), lambda bi, hi, pi, ln, tb, ly: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, d), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, hkv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), safe_table, _layer_operand(layer), q4,
      kq_pool, vq_pool, ks_pool, vs_pool)
    return out.reshape(n, hq, d)


def _paged_decode_q4_kernel(
    ln_ref,    # SMEM [N] per-slot live length (scalar prefetch)
    table_ref, # SMEM [N, MaxP] block table (scalar prefetch)
    layer_ref, # SMEM [1] layer index (scalar prefetch; index_maps only)
    q_ref,     # VMEM [1, 1, G, d]
    k_ref,     # VMEM uint8 [1, 1, 1, page, d//2] packed nibbles (index_map page)
    v_ref,     # VMEM uint8 [1, 1, 1, page, d//2]
    ks_ref,    # VMEM [1, 1, Hkv, page] per-position K scales (same page pick)
    vs_ref,    # VMEM [1, 1, Hkv, page]
    o_ref,     # VMEM [1, 1, G, d]
    acc_ref,   # scratch f32 [G, d]
    m_ref,     # scratch f32 [G, 128]
    l_ref,     # scratch f32 [G, 128]
    *,
    scale: float,
    page: int,
    n_pages: int,
    group: int,
):
    bi = pl.program_id(0)
    hi = pl.program_id(1)
    pi = pl.program_id(2)
    init_softmax_scratch(pi, acc_ref, m_ref, l_ref)

    def unpack(b):
        # split-half nibble unpack (ops/quant.unpack_int4, inlined on the
        # int32 VPU): byte j holds elements j (low) and j + d/2 (high),
        # each biased +8 — so the unpacked [page, d] tile is a concatenate
        # of two contiguous nibble planes, no interleave shuffle needed
        bi32 = b.astype(jnp.int32)
        return jnp.concatenate([(bi32 & 0xF) - 8, ((bi32 >> 4) & 0xF) - 8], axis=-1)

    q = q_ref[0, 0]                              # [G, d]
    # packed → [page, d] nibbles; int32 → compute dtype goes through f32
    # (exact for [-8, 7]; the direct int32 → bf16 convert is not one the
    # v5e kernel compiler is known to take)
    k = unpack(k_ref[0, 0, 0]).astype(jnp.float32).astype(q.dtype)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [G, page]
    # K-scale fold: identical order to the int8 kernel — constant along the
    # d reduction, multiplies the finished scores per key position.
    s = s * select_head_row(ks_ref[0, 0], hi)

    kv_pos = pi * page + jax.lax.broadcasted_iota(jnp.int32, (group, page), 1)
    s = jnp.where(kv_pos < ln_ref[bi], s, NEG_INF)

    # V-scale fold inside the recurrence (common.py), with V unpacked from
    # nibbles in-register — the PV matmul input converts to f32 there.
    softmax_block_update(s, unpack(v_ref[0, 0, 0]), acc_ref, m_ref, l_ref,
                         v_scale=select_head_row(vs_ref[0, 0], hi))

    def write(out):
        o_ref[0, 0] = out.astype(o_ref.dtype)

    softmax_finish(pi, n_pages, acc_ref, l_ref, write)


def paged_decode_attention_q4(
    q: jnp.ndarray,        # [N, Hq, D]
    kq_pool: jnp.ndarray,  # uint8 [L, P, Hkv, page, D//2] packed nibbles
    vq_pool: jnp.ndarray,  # uint8 [L, P, Hkv, page, D//2]
    ks_pool: jnp.ndarray,  # [L, P, Hkv, page] per-position K scales
    vs_pool: jnp.ndarray,  # [L, P, Hkv, page]
    layer,                 # scalar layer index
    table: jnp.ndarray,    # [N, MaxP] int32, OOB entries == P
    lengths: jnp.ndarray,  # [N] live length per slot
    *,
    scale: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused single-step decode against the PACKED int4 pool → [N, Hq, D].

    Same contract as ops.attention.paged_decode_attention_q4, without the
    gather: packed nibble pages and their scale rows are block-streamed per
    (slot, head, logical page); unpack + dequant happen in-register, so HBM
    traffic for the KV read is the packed byte stream — half the int8
    kernel's, a quarter of bf16's."""
    n, hq, d = q.shape
    _, pool, hkv, page, d2 = kq_pool.shape
    _, maxp = table.shape
    if hq % hkv != 0:
        raise ValueError(f"query heads {hq} not divisible by kv heads {hkv}")
    if d2 * 2 != d:
        raise ValueError(f"packed head_dim {d2}*2 != query head_dim {d}")
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d**0.5)

    q4 = q.reshape(n, hkv, group, d)
    safe_table = jnp.minimum(table, pool - 1).astype(jnp.int32)

    def kv_map(bi, hi, pi, ln_ref, table_ref, layer_ref):
        return (layer_ref[0], table_ref[bi, pi], hi, 0, 0)

    def sc_map(bi, hi, pi, ln_ref, table_ref, layer_ref):
        # all Hkv scale rows of the page; the kernel picks row hi
        # (common.select_head_row says why)
        return (layer_ref[0], table_ref[bi, pi], 0, 0)

    kernel = functools.partial(
        _paged_decode_q4_kernel, scale=scale, page=page, n_pages=maxp, group=group
    )
    out = pl.pallas_call(
        kernel,
        name="attention",  # tracing.SCOPES: the kernel is named for its phase
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n, hkv, maxp),
            in_specs=[
                pl.BlockSpec((1, 1, group, d), lambda bi, hi, pi, ln, tb, ly: (bi, hi, 0, 0)),
                pl.BlockSpec((1, 1, 1, page, d2), kv_map),
                pl.BlockSpec((1, 1, 1, page, d2), kv_map),
                pl.BlockSpec((1, 1, hkv, page), sc_map),
                pl.BlockSpec((1, 1, hkv, page), sc_map),
            ],
            out_specs=pl.BlockSpec((1, 1, group, d), lambda bi, hi, pi, ln, tb, ly: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, d), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, hkv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), safe_table, _layer_operand(layer), q4,
      kq_pool, vq_pool, ks_pool, vs_pool)
    return out.reshape(n, hq, d)
