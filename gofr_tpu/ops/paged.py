"""Paged (block) KV cache: fixed-size pages + per-slot block tables.

The slot cache (gofr_tpu.ops.kvcache) reserves ``max_len`` of HBM per slot,
so slot count x sequence length multiply into the HBM budget even when most
requests are short. Here the cache is one physical POOL of pages

    k, v: [L, P, Hkv, page_size, D]

and each serving slot owns an ordered list of page ids — its *block table*.
Logical position ``p`` of slot ``s`` lives at ``(table[s, p // page_size],
p % page_size)``. HBM now scales with TOKENS IN FLIGHT, not slots x max_len:
the engine admits more concurrent requests at equal HBM and reclaims pages
the moment a request completes (SURVEY.md §7 stage 4 — no reference analog;
this is the TPU-native subsystem the build plan orders).

Layout mirrors the slot cache's head-major discipline: the last two dims of
a page block are (page_size, D) = (128k, 128k)-alignable tiles, so both the
XLA gather path and the Pallas paged-decode kernel stream [page, D] tiles
straight out of HBM per (page, kv_head).

Out-of-bounds convention: table entries for unallocated logical pages (and
batch-padding rows) point at page id P (one past the pool). Scatter writes
there are DROPPED by XLA, and gather reads CLAMP to page P-1 but are always
masked by per-slot lengths — the same trick the slot engine uses for
padding rows (engine._admit docstring).
"""

from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from gofr_tpu.ops.kvcache import quantize_row
from gofr_tpu.ops.quant import pack_int4, quantize_row_int4, unpack_int4
from gofr_tpu.tracing import scoped

# -- tensor-parallel pool sharding ------------------------------------------
#
# The pool planes shard over the mesh's tp axis along the KV-head dimension
# (axis 2 of [L, P, Hkv, page, D]; axis 2 of the [L, P, Hkv, page] scale
# planes too). Block tables stay replicated — page ids are logical, not
# per-shard — and the decode attention ops run per-shard under shard_map
# when an engine pins a KVShardCtx for its traces (engine._trace_scope).


@dataclass(frozen=True)
class KVShardCtx:
    """Trace-time pin describing how the paged pool is sharded: the mesh,
    the mesh axis the KV-head dimension is split over, and the shard count
    (= mesh.shape[axis]). Engines enter ``kv_shard_scope`` with this for
    every trace they drive so the paged decode ops wrap themselves in
    shard_map; direct ops callers (unit tests) enter it explicitly."""

    mesh: object  # jax.sharding.Mesh
    axis: str = "tp"
    shards: int = 1


_KV_SHARD: contextvars.ContextVar[KVShardCtx | None] = contextvars.ContextVar(
    "gofr_paged_kv_shard", default=None
)


def current_kv_shard() -> KVShardCtx | None:
    """The pinned pool-sharding context, or None (unsharded pool)."""
    ctx = _KV_SHARD.get()
    if ctx is not None and ctx.shards > 1:
        return ctx
    return None


@contextlib.contextmanager
def kv_shard_scope(ctx: KVShardCtx | None):
    """Pin the pool sharding for traces inside the scope (None = unsharded)."""
    tok = _KV_SHARD.set(ctx)
    try:
        yield
    finally:
        _KV_SHARD.reset(tok)


def plane_partition_spec(ndim: int, axis: str = "tp"):
    """PartitionSpec for one pool plane by rank: K/V planes are 5-D
    [L, P, Hkv, page, D], scale planes 4-D [L, P, Hkv, page] — the KV-head
    axis is dim 2 in both. Anything else (spec history planes, block
    tables) stays replicated."""
    from jax.sharding import PartitionSpec as P

    if ndim == 5:
        return P(None, None, axis, None, None)
    if ndim == 4:
        return P(None, None, axis, None)
    return P()


def pool_sharding(mesh, axis: str = "tp"):
    """NamedSharding for the 5-D K/V planes — what engines hand to the
    cache constructors. Scale planes derive their 4-D spec internally."""
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, plane_partition_spec(5, axis))


def _shard_for(sharding, ndim: int):
    """Re-rank a 5-D plane NamedSharding for an ndim-rank plane (the scale
    planes drop the trailing head_dim axis)."""
    from jax.sharding import NamedSharding, PartitionSpec

    spec = tuple(sharding.spec) + (None,) * (5 - len(tuple(sharding.spec)))
    return NamedSharding(sharding.mesh, PartitionSpec(*spec[:ndim]))


def _zeros(shape, dtype, sharding=None) -> jnp.ndarray:
    """Zero-filled plane, allocated DIRECTLY under ``sharding`` when given —
    jit with out_shardings materializes each device's shard in place, so a
    sharded pool never exists replicated, not even transiently at create."""
    if sharding is None:
        return jnp.zeros(shape, dtype)
    return jax.jit(partial(jnp.zeros, shape, dtype),
                   out_shardings=_shard_for(sharding, len(shape)))()


def _locate(pages: jnp.ndarray, pos: jnp.ndarray, page: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(physical page, in-page offset) per logical position. ``pages``
    [B, MaxP] block-table rows, ``pos`` [B, S] logical positions. The
    logical-page clamp keeps chunked tails inside the table; true OOB rows
    drop through page id P (the pool-size sentinel)."""
    pp = jnp.take_along_axis(pages, jnp.minimum(pos // page, pages.shape[1] - 1), axis=1)
    return pp, pos % page


@jax.tree_util.register_dataclass
@dataclass
class PagedKVCache:
    k: jnp.ndarray  # [L, P, Hkv, page, D]
    v: jnp.ndarray  # [L, P, Hkv, page, D]

    @classmethod
    def create(
        cls,
        layers: int,
        pages: int,
        page_size: int,
        kv_heads: int,
        head_dim: int,
        dtype=jnp.bfloat16,
        sharding=None,
    ) -> "PagedKVCache":
        shape = (layers, pages, kv_heads, page_size, head_dim)
        return cls(k=_zeros(shape, dtype, sharding), v=_zeros(shape, dtype, sharding))

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[3]


@jax.tree_util.register_dataclass
@dataclass
class QPagedKVCache:
    """int8 paged pool with per-(page, head, position) scales — the paged
    analog of kvcache.QSlotKVCache: cache reads halve and the scales fold
    outside the attention contractions. Prefix caching composes unchanged:
    a page's (int8, scale) content is still a deterministic function of
    the token prefix, so shared pages stay exact across chains."""

    k: jnp.ndarray   # int8 [L, P, Hkv, page, D]
    v: jnp.ndarray   # int8 [L, P, Hkv, page, D]
    ks: jnp.ndarray  # bf16 [L, P, Hkv, page]
    vs: jnp.ndarray  # bf16 [L, P, Hkv, page]

    @classmethod
    def create(cls, layers: int, pages: int, page_size: int, kv_heads: int,
               head_dim: int, dtype=None, sharding=None) -> "QPagedKVCache":
        del dtype
        shape = (layers, pages, kv_heads, page_size, head_dim)
        sshape = (layers, pages, kv_heads, page_size)
        return cls(
            k=_zeros(shape, jnp.int8, sharding), v=_zeros(shape, jnp.int8, sharding),
            ks=_zeros(sshape, jnp.bfloat16, sharding),
            vs=_zeros(sshape, jnp.bfloat16, sharding),
        )

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[3]


@jax.tree_util.register_dataclass
@dataclass
class Q4PagedKVCache:
    """Packed-int4 paged pool: two nibbles per byte in the head_dim axis
    (ops/quant.pack_int4 split-half order — byte j of a D-wide row holds
    elements j and j + D/2) with the same per-(page, head, position) bf16
    scale planes as the int8 layout. KV page reads quarter vs bf16 and
    halve vs int8; the scales still fold outside the attention
    contractions (in-kernel for the Pallas path, via the unpacked gather
    view for XLA). Zero-initialized bytes decode to the -8 nibble pair,
    but unwritten positions always sit behind the per-slot length mask and
    their scale planes are zero, so no read ever sees them. Prefix caching
    and handoff compose unchanged: a page's (packed, scale) content is a
    deterministic function of the token prefix."""

    k: jnp.ndarray   # uint8 [L, P, Hkv, page, D//2] packed nibbles
    v: jnp.ndarray   # uint8 [L, P, Hkv, page, D//2]
    ks: jnp.ndarray  # bf16 [L, P, Hkv, page]
    vs: jnp.ndarray  # bf16 [L, P, Hkv, page]

    @classmethod
    def create(cls, layers: int, pages: int, page_size: int, kv_heads: int,
               head_dim: int, dtype=None, sharding=None) -> "Q4PagedKVCache":
        del dtype
        if head_dim % 2:
            raise ValueError(f"int4 packing needs an even head_dim, got {head_dim}")
        shape = (layers, pages, kv_heads, page_size, head_dim // 2)
        sshape = (layers, pages, kv_heads, page_size)
        return cls(
            k=_zeros(shape, jnp.uint8, sharding), v=_zeros(shape, jnp.uint8, sharding),
            ks=_zeros(sshape, jnp.bfloat16, sharding),
            vs=_zeros(sshape, jnp.bfloat16, sharding),
        )

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[3]


def kv_plane_bytes_per_position(layers: int, kv_heads: int, head_dim: int,
                                kv_dtype: str = "bf16",
                                dense_bytes: int = 2,
                                shards: int = 1) -> int:
    """Analytic per-position pool footprint across every cache plane, by
    layout contract: dense pools carry k+v at ``dense_bytes`` per element
    (bf16 on TPU; pass 4 where the backend promotes to fp32, as CPU
    does), the int8 pool carries k+v int8 plus the two bf16 scale planes,
    and the packed-int4 pool halves the nibble planes. This is the
    cross-check for the EXACT accounting the live perf plane reads off
    the pool leaves (metrics/perf.py) and what bench archives as
    ``kv_bytes_per_decode_token`` — on the tiny CPU config the three
    layouts come out 512 / 144 / 80.

    ``shards`` > 1 reports the PER-DEVICE footprint of a tp-sharded pool
    (KV heads split over the mesh's tp axis): each device holds
    ``kv_heads // shards`` heads of every plane. Requires divisibility —
    sharding never pads heads."""
    if shards > 1:
        if kv_heads % shards:
            raise ValueError(
                f"kv_heads={kv_heads} not divisible by shards={shards}")
        kv_heads //= shards
    if kv_dtype == "int4":
        per = 2 * (head_dim // 2) + 4   # packed k+v nibbles + bf16 scales
    elif kv_dtype in ("int8", "q", "quant"):
        per = 2 * head_dim + 4          # int8 k+v + bf16 scale planes
    else:
        per = 2 * head_dim * int(dense_bytes)
    return layers * kv_heads * per


# -- in-place writes --------------------------------------------------------
#
# Every paged program carries the WHOLE pool planes [L, P, Hkv, page, ...]
# through its layer scan (models/llama._scan_paged_layers) and writes a
# layer's K/V straight into them with one scatter: a decode token (and any
# run shorter than a page) as [D] rows indexed by (layer, page, head,
# offset); a prompt or prompt chunk as whole [Hkv, page, D] blocks indexed
# by (layer, page). XLA updates a carried buffer in place, so a step moves
# O(tokens) bytes, not O(pool) — the pool is never restacked, sliced per
# layer, or rebuilt through a mask. Rows and pages whose table entry is P
# (idle lanes, padding rows, unallocated pages) write nothing
# (``mode="drop"``).


def _put_rows(plane: jnp.ndarray, layer, pp: jnp.ndarray, off: jnp.ndarray,
              new: jnp.ndarray) -> jnp.ndarray:
    """``plane[layer, pp[i], h, off[i]] = new[i, h]`` for every index ``i``
    of ``pp`` / ``off`` (any shape) and head ``h``; ``new`` is
    [*pp.shape, Hkv(, D)]. The head is an INDEX of the scatter, not part of
    its window: with a [Hkv, D] window the v5e compiler re-lays the whole
    pool out as [L, P, page, Hkv, D] inside the program and copies it in
    and out at both ends (PERF.md §6, PR 27)."""
    heads = jnp.arange(plane.shape[2])
    return plane.at[layer, pp[..., None], heads, off[..., None]].set(
        new.astype(plane.dtype), mode="drop")


def _locate_chunk(pages, s: int, offsets, page: int):
    """_locate for a chunk of ``s`` positions starting at ``offsets`` [B]
    (None = 0) → (pp, off), [B, S] each."""
    pos = jnp.arange(s)[None, :] + (offsets[:, None] if offsets is not None else 0)
    return _locate(pages, pos, page)


def _locate_append(table, positions, page: int, pool: int):
    """_locate for one position per lane → (pp, off), [N] each. A position
    past the table's span is dropped (page id P), never clamped onto the
    lane's last page."""
    pp, off = _locate(table, positions[:, None], page)
    past = positions // page >= table.shape[1]
    return jnp.where(past, pool, pp[:, 0]), off[:, 0]


def _put_pages(plane: jnp.ndarray, layer, pages: jnp.ndarray, new: jnp.ndarray,
               offsets) -> jnp.ndarray:
    """Write ``new`` [B, S, Hkv(, D)] at positions offsets..offsets+S of each
    row as WHOLE PAGES: one scatter whose index is (layer, page) and whose
    window is a [Hkv, page(, D)] block. A page-aligned whole prompt
    (``offsets`` None, S a multiple of the page) is laid out as S/page
    blocks and written; any other run reads the S/page + 1 pages it can
    touch, patches its rows in, and writes them back. Logical pages past
    the table's span, and entries == P, write nothing."""
    pool, page = plane.shape[1], plane.shape[3]
    b, s = new.shape[:2]
    tail = new.shape[2:]
    maxp = pages.shape[1]
    new = new.astype(plane.dtype)

    def blocks(x, j):  # [B, j*page, *tail] -> [B, j, Hkv, page(, D)]
        return jnp.moveaxis(x.reshape(b, j, page, *x.shape[2:]), 2, 3)

    if offsets is None and s % page == 0 and s // page <= maxp:
        return plane.at[layer, pages[:, : s // page]].set(blocks(new, s // page), mode="drop")
    if offsets is None:
        offsets = jnp.zeros((b,), jnp.int32)
    j = -(-s // page) + 1
    lp = (offsets // page)[:, None] + jnp.arange(j)  # [B, j] logical pages of the run
    pg = jnp.where(lp < maxp,
                   jnp.take_along_axis(pages, jnp.minimum(lp, maxp - 1), axis=1), pool)
    old = plane[layer, jnp.minimum(pg, pool - 1)]  # [B, j, Hkv, page(, D)]
    src = jnp.arange(j * page)[None, :] - (offsets % page)[:, None]  # row of ``new`` per slot
    hit = ((src >= 0) & (src < s)).reshape((b, j * page) + (1,) * len(tail))
    src = jnp.clip(src, 0, s - 1).reshape(hit.shape)
    rows = jnp.take_along_axis(new, src, axis=1)
    return plane.at[layer, pg].set(jnp.where(blocks(hit, j), blocks(rows, j), old), mode="drop")


def _put_run(plane: jnp.ndarray, layer, pages: jnp.ndarray, new: jnp.ndarray,
             offsets) -> jnp.ndarray:
    """A run of S consecutive positions per row, by its length (static):
    shorter than a page (speculative verify) as rows, like an append; a page
    or more (prefill, chunked prefill) as whole pages. Thousands of row
    updates in one scatter are what makes the v5e compiler re-lay the pool
    out inside the program at head_dim 64 — a second pool, and a minute of
    compile time a program (PERF.md §6, PR 27)."""
    page = plane.shape[3]
    if new.shape[1] >= page:
        return _put_pages(plane, layer, pages, new, offsets)
    pp, off = _locate_chunk(pages, new.shape[1], offsets, page)
    return _put_rows(plane, layer, pp, off, new)


def _quantize_packed_int4(new):
    """quantize_row's contract for the packed pool: ([..., Hkv, D//2] uint8
    nibble pairs, [..., Hkv] scales)."""
    q, sc = quantize_row_int4(new)
    return pack_int4(q), sc


def _put_rows_q(pool_q, pool_s, layer, pp, off, new, quantize):
    q, sc = quantize(new)  # [..., Hkv, D or D//2] stored bytes, [..., Hkv] scales
    return _put_rows(pool_q, layer, pp, off, q), _put_rows(pool_s, layer, pp, off, sc)


@scoped("kv_append")
def write_prompts_paged(
    k_pool: jnp.ndarray,   # [L, P, Hkv, page, D]
    v_pool: jnp.ndarray,
    layer,                 # scalar layer index (traced in the layer scan)
    pages: jnp.ndarray,    # [B, S_pages] physical page per logical page (P = dropped)
    k_new: jnp.ndarray,    # [B, S, Hkv, D] activation layout
    v_new: jnp.ndarray,
    offsets: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write prefilled prompts (or prompt CHUNKS) of one layer through
    per-row block tables. ``pages[b, j]`` is the physical page holding
    positions j*page .. (j+1)*page of row b; ``offsets`` [B] places the
    chunk at logical positions offsets..offsets+S (None = 0)."""
    return (_put_run(k_pool, layer, pages, k_new, offsets),
            _put_run(v_pool, layer, pages, v_new, offsets))


@scoped("kv_append")
def write_prompts_paged_q(
    pool_q: jnp.ndarray,   # int8 [L, P, Hkv, page, D] (one of k/v)
    pool_s: jnp.ndarray,   # [L, P, Hkv, page]
    layer,
    pages: jnp.ndarray,    # [B, S_pages]
    new: jnp.ndarray,      # [B, S, Hkv, D]
    offsets: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized analog of write_prompts_paged for one k/v plane pair."""
    q, sc = quantize_row(new)
    return _put_run(pool_q, layer, pages, q, offsets), _put_run(pool_s, layer, pages, sc, offsets)


@scoped("kv_append")
def write_prompts_paged_q4(
    pool_q: jnp.ndarray,   # uint8 [L, P, Hkv, page, D//2] packed (one of k/v)
    pool_s: jnp.ndarray,   # [L, P, Hkv, page]
    layer,
    pages: jnp.ndarray,    # [B, S_pages]
    new: jnp.ndarray,      # [B, S, Hkv, D]
    offsets: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """int4 analog of write_prompts_paged_q: quantize to nibbles, pack
    two-per-byte, write bytes through the block table."""
    q, sc = _quantize_packed_int4(new)
    return _put_run(pool_q, layer, pages, q, offsets), _put_run(pool_s, layer, pages, sc, offsets)


@scoped("kv_append")
def append_tokens_paged(
    k_pool: jnp.ndarray,    # [L, P, Hkv, page, D]
    v_pool: jnp.ndarray,
    layer,
    table: jnp.ndarray,     # [N, MaxP] block table for every slot
    positions: jnp.ndarray, # [N] logical write position per slot
    k_new: jnp.ndarray,     # [N, Hkv, D]
    v_new: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Append one token's K/V per slot at its current logical position of
    one layer: row ``n`` lands at ``(layer, table[n, pos // page], :,
    pos % page)``; idle lanes (table entry P) and positions past the
    table's span write nothing."""
    pp, off = _locate_append(table, positions, k_pool.shape[3], k_pool.shape[1])
    return _put_rows(k_pool, layer, pp, off, k_new), _put_rows(v_pool, layer, pp, off, v_new)


@scoped("kv_append")
def append_tokens_paged_q(
    pool_q: jnp.ndarray,    # int8 [L, P, Hkv, page, D]
    pool_s: jnp.ndarray,    # [L, P, Hkv, page]
    layer,
    table: jnp.ndarray,     # [N, MaxP]
    positions: jnp.ndarray, # [N]
    new: jnp.ndarray,       # [N, Hkv, D]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized analog of append_tokens_paged for one k/v plane pair."""
    pp, off = _locate_append(table, positions, pool_q.shape[3], pool_q.shape[1])
    return _put_rows_q(pool_q, pool_s, layer, pp, off, new, quantize_row)


@scoped("kv_append")
def append_tokens_paged_q4(
    pool_q: jnp.ndarray,    # uint8 [L, P, Hkv, page, D//2] packed
    pool_s: jnp.ndarray,    # [L, P, Hkv, page]
    layer,
    table: jnp.ndarray,     # [N, MaxP]
    positions: jnp.ndarray, # [N]
    new: jnp.ndarray,       # [N, Hkv, D]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """int4 analog of append_tokens_paged_q (packed bytes round-trip
    losslessly: the scatter stores them as they are)."""
    pp, off = _locate_append(table, positions, pool_q.shape[3], pool_q.shape[1])
    return _put_rows_q(pool_q, pool_s, layer, pp, off, new, _quantize_packed_int4)


# -- reads ---------------------------------------------------------------------
#
# The XLA read path materializes each slot's logical view with ONE gather
# whose index is (layer, page): the pages come straight out of the carried
# pool, and no [P, Hkv, page, D] copy of the layer is ever made.


def _gather_pages(plane: jnp.ndarray, layer, table: jnp.ndarray) -> jnp.ndarray:
    """[N, Hkv, MaxP*page(, D)] logical view of one layer of a plane. OOB
    table entries clamp — callers mask by lengths."""
    n, maxp = table.shape
    hkv, page = plane.shape[2], plane.shape[3]
    g = plane[layer, jnp.minimum(table, plane.shape[1] - 1)]  # [N, MaxP, Hkv, page(, D)]
    return jnp.moveaxis(g, 2, 1).reshape(n, hkv, maxp * page, *plane.shape[4:])


def _corrupt_scales(gs: jnp.ndarray) -> jnp.ndarray:
    """Chaos point ``quality.corrupt``: multiply the gathered dequant scales
    by ``factor`` (default 1.5). Evaluated at TRACE time, so an engine built
    under ``chaos.override("quality.corrupt:drop,factor=8")`` bakes the
    corruption into its compiled decode program — deterministic plausible
    wrong tokens, exactly the silent-numerics failure the quality plane
    exists to catch (and a different HLO hash, so the persistent compile
    cache can't serve a clean program). Unarmed: returns ``gs`` untouched."""
    from gofr_tpu.fleet import chaos

    pt = chaos.hook("quality.corrupt")
    if pt is not None and pt():
        factor = float(pt.params.get("factor", "1.5"))
        gs = gs * jnp.asarray(factor, gs.dtype)
    return gs


@scoped("kv_gather")
def gather_kv(
    k_pool: jnp.ndarray,   # [L, P, Hkv, page, D]
    v_pool: jnp.ndarray,
    layer,
    table: jnp.ndarray,    # [N, MaxP]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Materialize the logical [N, Hkv, MaxP*page, D] view of each slot's
    cache at one layer (XLA fallback read path; the Pallas paged-decode
    kernel reads the pool directly instead). OOB table entries clamp —
    callers must mask by lengths, which the attention ops already do."""
    return _gather_pages(k_pool, layer, table), _gather_pages(v_pool, layer, table)


@scoped("kv_gather")
def gather_kv_q(
    pool_q: jnp.ndarray,   # int8 [L, P, Hkv, page, D]
    pool_s: jnp.ndarray,   # [L, P, Hkv, page]
    layer,
    table: jnp.ndarray,    # [N, MaxP]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Logical ([N, Hkv, MaxP*page, D] int8, [N, Hkv, MaxP*page] scale)
    views of each slot's quantized cache (the XLA read path)."""
    return (_gather_pages(pool_q, layer, table),
            _corrupt_scales(_gather_pages(pool_s, layer, table)))


@scoped("kv_gather")
def gather_kv_q4(
    pool_q: jnp.ndarray,   # uint8 [L, P, Hkv, page, D//2] packed
    pool_s: jnp.ndarray,   # [L, P, Hkv, page]
    layer,
    table: jnp.ndarray,    # [N, MaxP]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Logical ([N, Hkv, MaxP*page, D] int8 in [-8, 7], [N, Hkv, MaxP*page]
    scale) views of each slot's packed cache — the XLA read path unpacks
    AFTER the gather so HBM reads stay packed; the unpacked view feeds the
    same ``decode_attention_q`` contraction the int8 layout uses."""
    return (unpack_int4(_gather_pages(pool_q, layer, table)),
            _corrupt_scales(_gather_pages(pool_s, layer, table)))


# -- hierarchical prefix cache: per-page host spill / swap-in -------------------
#
# The engine's host-DRAM cache tier (tpu/prefix.py, docs/serving.md) moves
# whole pages between the pool and host memory. Both helpers work on the
# cache PYTREE (PagedKVCache, QPagedKVCache, or Q4PagedKVCache), so one
# definition covers the bf16 layout (k/v planes), the int8 layout, and the
# packed-int4 layout (k/v bytes + ks/vs scale planes) — every plane is
# [L, P, ...page-slice dims...] and the page axis is always axis 1. Packed
# int4 pages spill/swap as opaque uint8 bytes; no repack is ever needed.


@jax.jit
def gather_page(cache, page_id):
    """Slice ONE page's content out of every plane of a paged cache pytree:
    each [L, P, ...] plane yields [L, ...]. ``page_id`` is a traced scalar,
    so one compiled program per cache type serves every spill. The engine
    reads the result back to host (``np.asarray``) at spill time — the page
    is an immutable cache leaf, so the latest ``engine.cache`` value is its
    authoritative content."""
    return jax.tree.map(lambda a: a[:, page_id], cache)


@partial(jax.jit, donate_argnums=(0,))
def swap_in_pages(cache, page_ids, payload):
    """Write host-staged page payloads back into the pool. ``page_ids`` [W]
    (padded with an out-of-bounds id — pool size — whose scatter writes XLA
    DROPS, the same convention block tables use); ``payload`` mirrors the
    cache pytree with per-plane [L, W, ...page-slice dims...] stacks.
    Returns ``(new_cache, marker)`` — the marker is a tiny output of the
    same executable, so reading it back (the unified pipeline's fold)
    blocks until the whole upload has landed without pulling the pool to
    host. The cache argument is donated, matching every other engine
    program that rewrites it (tpu/programs.py)."""
    new = jax.tree.map(
        lambda a, p: a.at[:, page_ids].set(p.astype(a.dtype)), cache, payload
    )
    return new, jnp.sum(page_ids)
