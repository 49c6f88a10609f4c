"""In-place KV append as a Pallas kernel (decode-bandwidth lever).

The XLA lowerings of the per-step KV append both pay O(cache) HBM traffic:
the masked-select path rewrites the ENTIRE layer buffer every decode step
(read + write of [N, Hkv, Smax, D]), and the scatter path materializes a
non-aliased copy. But the append itself only CHANGES one [Hkv, D] row per
slot. This kernel writes in place via ``input_output_aliases``: the grid
walks slots, scalar-prefetched positions pick the [block_s, D] tile
containing each slot's write row (data-dependent BlockSpec index_map), and
the kernel copies that one tile through with the new row patched in by a
masked select over the tile (a single-row store at a dynamic sublane offset
is refused on packed bf16 tiles: "cannot statically prove that index in
dimension 2 is a multiple of 8"). Per-step traffic drops from
O(N·Hkv·Smax·D) to O(N·Hkv·block_s·D) — a (Smax/block_s)× reduction on the
axis long-context decode is bound by.

Out-of-bounds convention (engine padding/bubble rows): positions >= Smax
clamp to the last tile in the index_map and the select mask is empty, so
the tile is copied through unchanged — the same dropped-write semantics as
the XLA path (ops/kvcache.append_tokens).

Slot layout only. The paged pool is written by XLA's scatter into the
buffer the layer scan carries (ops/paged.py): at the served shapes it
matched a paged twin of this kernel to 0.03% of a decode chunk on the v5e
(PERF.md §6, PR 27), for all three pool kinds and with no reserved page.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pick_block(total: int, desired: int) -> int:
    if total <= desired:
        return total
    for cand in range(desired, 0, -1):
        if total % cand == 0:
            return cand
    return total


def _patch_tile(off, new_ref, in_ref, out_ref) -> None:
    """out tile = in tile with row ``off`` (second-minor axis) replaced by
    the new row; ``off`` < 0 patches nothing. ``new_ref`` blocks are
    [1, Hkv, 1, D] — the wrapper inserts the unit row axis, because an
    in-kernel [Hkv, D] -> [Hkv, 1, D] reshape is an "unsupported shape
    cast" on bf16."""
    _, hkv, rows, d = out_ref.shape
    hit = jax.lax.broadcasted_iota(jnp.int32, (hkv, rows, d), 1) == off
    out_ref[0] = jnp.where(hit, new_ref[0].astype(out_ref.dtype), in_ref[0])


def _append_kernel(pos_ref, knew_ref, vnew_ref, k_ref, v_ref, ko_ref, vo_ref,
                   *, block_s: int, smax: int):
    pos = pos_ref[pl.program_id(0)]
    off = jnp.where(pos < smax, pos % block_s, -1)
    _patch_tile(off, knew_ref, k_ref, ko_ref)
    _patch_tile(off, vnew_ref, v_ref, vo_ref)


def append_tokens_inplace(
    k_layer: jnp.ndarray,   # [N, Hkv, Smax, D]
    v_layer: jnp.ndarray,
    positions: jnp.ndarray, # [N]
    k_new: jnp.ndarray,     # [N, Hkv, D]
    v_new: jnp.ndarray,
    *,
    block_s: int = 128,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Slot-cache append writing only the tile containing each row."""
    n, hkv, smax, d = k_layer.shape
    bs = _pick_block(smax, block_s)
    pos = positions.astype(jnp.int32)

    def cache_map(bi, pos_ref):
        return (bi, 0, jnp.minimum(pos_ref[bi] // bs, smax // bs - 1), 0)

    kernel = functools.partial(_append_kernel, block_s=bs, smax=smax)
    return pl.pallas_call(
        kernel,
        name="kv_append",  # tracing.SCOPES: the kernel is named for its phase
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((1, hkv, 1, d), lambda bi, p: (bi, 0, 0, 0)),
                pl.BlockSpec((1, hkv, 1, d), lambda bi, p: (bi, 0, 0, 0)),
                pl.BlockSpec((1, hkv, bs, d), cache_map),
                pl.BlockSpec((1, hkv, bs, d), cache_map),
            ],
            out_specs=[
                pl.BlockSpec((1, hkv, bs, d), cache_map),
                pl.BlockSpec((1, hkv, bs, d), cache_map),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k_layer.shape, k_layer.dtype),
            jax.ShapeDtypeStruct(v_layer.shape, v_layer.dtype),
        ],
        # inputs 3/4 are (k_layer, v_layer) AFTER the prefetch operand;
        # aliasing makes the untouched tiles true no-ops in HBM
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(pos, k_new[:, :, None, :], v_new[:, :, None, :], k_layer, v_layer)
