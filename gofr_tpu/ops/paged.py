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
import os
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from gofr_tpu.ops.kvcache import quantize_row
from gofr_tpu.ops.quant import pack_int4, quantize_row_int4, unpack_int4
from gofr_tpu.tracing import scoped

# The append-lowering choice (select | scatter | pallas). Engines resolve
# GOFR_PAGED_KV_WRITE ONCE at construction and pin it here for every trace
# they drive (engine._trace_scope); the env var is only read as a fallback
# for direct ops callers (unit tests, notebooks). jit caches traces
# process-globally, so A/B the lowerings across processes, not by flipping
# the env between engine builds in one process.
_WRITE_MODE: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "gofr_paged_kv_write", default=None
)


def resolve_write_mode(explicit: str | None = None) -> str:
    """The lowering to trace with: explicit arg > engine pin > env."""
    if explicit:
        return explicit
    pinned = _WRITE_MODE.get()
    if pinned is not None:
        return pinned
    return os.environ.get("GOFR_PAGED_KV_WRITE", "select")


@contextlib.contextmanager
def write_mode_scope(mode: str | None):
    """Pin the paged-append lowering for traces inside the scope — the
    engine wraps its device loop / warmup / follower loop with this so the
    choice it resolved at construction is what every trace sees."""
    tok = _WRITE_MODE.set(mode)
    try:
        yield
    finally:
        _WRITE_MODE.reset(tok)


# -- tensor-parallel pool sharding ------------------------------------------
#
# The pool planes shard over the mesh's tp axis along the KV-head dimension
# (axis 2 of [L, P, Hkv, page, D]; axis 2 of the [L, P, Hkv, page] scale
# planes too). Block tables stay replicated — page ids are logical, not
# per-shard — and the decode attention ops run per-shard under shard_map
# when an engine pins a KVShardCtx for its traces (engine._trace_scope),
# mirroring the write-mode pin above.


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


@scoped("kv_append")
def write_prompts_paged_q(
    cache_q: jnp.ndarray,  # int8 [P, Hkv, page, D] (one of k/v)
    cache_s: jnp.ndarray,  # [P, Hkv, page]
    pages: jnp.ndarray,    # [B, S_pages]
    new: jnp.ndarray,      # [B, S, Hkv, D]
    offsets: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized analog of write_prompts_paged for one k/v plane, with
    chunk offsets (logical positions offsets..offsets+S)."""
    b, s, hkv, _ = new.shape
    page = cache_q.shape[2]
    q, sc = quantize_row(new)  # [B,S,Hkv,D] int8, [B,S,Hkv]
    pos = jnp.arange(s)[None, :] + (offsets[:, None] if offsets is not None else 0)
    pp, off = _locate(pages, pos, page)  # [B,S] each
    rows = pp[:, :, None]
    heads = jnp.arange(hkv)[None, None, :]
    offs = off[:, :, None]
    cache_q = cache_q.at[rows, heads, offs].set(q)
    cache_s = cache_s.at[rows, heads, offs].set(sc.astype(cache_s.dtype))
    return cache_q, cache_s


@scoped("kv_append")
def append_tokens_paged_q(
    cache_q: jnp.ndarray,   # int8 [P, Hkv, page, D]
    cache_s: jnp.ndarray,   # [P, Hkv, page]
    table: jnp.ndarray,     # [N, MaxP]
    positions: jnp.ndarray, # [N]
    new: jnp.ndarray,       # [N, Hkv, D]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized analog of append_tokens_paged for one k/v plane, honoring
    the same write-mode lowering switch (select default — the measured
    v5e winner; scatter optional). The one-hot fold runs in f32 and casts
    back: int8 magnitudes <= 127 are exact in f32."""
    n, hkv, d = new.shape
    p_total, _, page, _ = cache_q.shape
    q, sc = quantize_row(new)  # [N,Hkv,D] int8, [N,Hkv] f32
    pp, off = _locate(table, positions[:, None], page)
    pp, off = pp[:, 0], off[:, 0]

    if resolve_write_mode() != "scatter":
        flat = pp * page + off  # OOB rows land >= p_total*page
        grid = jnp.arange(p_total * page)
        m = flat[:, None] == grid[None, :]  # [N, P*page]
        any_m = m.reshape(n, p_total, page).any(axis=0)
        mf = m.astype(jnp.float32)
        upd = jnp.einsum("np,nhd->phd", mf, q.astype(jnp.float32))
        upd = upd.reshape(p_total, page, hkv, d).transpose(0, 2, 1, 3)
        cache_q = jnp.where(any_m[:, None, :, None], upd.astype(jnp.int8), cache_q)
        upd_s = jnp.einsum("np,nh->ph", mf, sc).reshape(p_total, page, hkv)
        cache_s = jnp.where(any_m[:, None, :],
                            upd_s.transpose(0, 2, 1).astype(cache_s.dtype), cache_s)
        return cache_q, cache_s

    rows = pp[:, None]
    heads = jnp.arange(hkv)[None, :]
    cache_q = cache_q.at[rows, heads, off[:, None]].set(q)
    cache_s = cache_s.at[rows, heads, off[:, None]].set(sc.astype(cache_s.dtype))
    return cache_q, cache_s


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
def gather_kv_q(
    cache_q: jnp.ndarray,  # int8 [P, Hkv, page, D]
    cache_s: jnp.ndarray,  # [P, Hkv, page]
    table: jnp.ndarray,    # [N, MaxP]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Logical ([N, Hkv, MaxP*page, D] int8, [N, Hkv, MaxP*page] scale)
    views of each slot's quantized cache (the XLA read path)."""
    n, maxp = table.shape
    _, hkv, page, d = cache_q.shape
    safe = jnp.minimum(table, cache_q.shape[0] - 1)

    gq = cache_q[safe].transpose(0, 2, 1, 3, 4).reshape(n, hkv, maxp * page, d)
    gs = cache_s[safe].transpose(0, 2, 1, 3).reshape(n, hkv, maxp * page)
    return gq, _corrupt_scales(gs)


@scoped("kv_append")
def write_prompts_paged_q4(
    cache_q: jnp.ndarray,  # uint8 [P, Hkv, page, D//2] packed (one of k/v)
    cache_s: jnp.ndarray,  # [P, Hkv, page]
    pages: jnp.ndarray,    # [B, S_pages]
    new: jnp.ndarray,      # [B, S, Hkv, D]
    offsets: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """int4 analog of write_prompts_paged_q for one k/v plane: quantize to
    nibbles, pack two-per-byte, write bytes through the block table."""
    b, s, hkv, _ = new.shape
    page = cache_q.shape[2]
    q, sc = quantize_row_int4(new)  # [B,S,Hkv,D] int8, [B,S,Hkv]
    packed = pack_int4(q)           # [B,S,Hkv,D//2] uint8
    pos = jnp.arange(s)[None, :] + (offsets[:, None] if offsets is not None else 0)
    pp, off = _locate(pages, pos, page)  # [B,S] each
    rows = pp[:, :, None]
    heads = jnp.arange(hkv)[None, None, :]
    offs = off[:, :, None]
    cache_q = cache_q.at[rows, heads, offs].set(packed)
    cache_s = cache_s.at[rows, heads, offs].set(sc.astype(cache_s.dtype))
    return cache_q, cache_s


@scoped("kv_append")
def append_tokens_paged_q4(
    cache_q: jnp.ndarray,   # uint8 [P, Hkv, page, D//2] packed
    cache_s: jnp.ndarray,   # [P, Hkv, page]
    table: jnp.ndarray,     # [N, MaxP]
    positions: jnp.ndarray, # [N]
    new: jnp.ndarray,       # [N, Hkv, D]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """int4 analog of append_tokens_paged_q for one k/v plane, honoring the
    same write-mode lowering switch. The one-hot fold runs in f32 over the
    PACKED bytes and casts back — uint8 magnitudes <= 255 are exact in
    f32, so the byte round-trips losslessly."""
    n, hkv, d2 = new.shape[0], new.shape[1], cache_q.shape[3]
    p_total, _, page, _ = cache_q.shape
    q, sc = quantize_row_int4(new)  # [N,Hkv,D] int8, [N,Hkv] f32
    packed = pack_int4(q)           # [N,Hkv,D//2] uint8
    pp, off = _locate(table, positions[:, None], page)
    pp, off = pp[:, 0], off[:, 0]

    if resolve_write_mode() != "scatter":
        flat = pp * page + off  # OOB rows land >= p_total*page
        grid = jnp.arange(p_total * page)
        m = flat[:, None] == grid[None, :]  # [N, P*page]
        any_m = m.reshape(n, p_total, page).any(axis=0)
        mf = m.astype(jnp.float32)
        upd = jnp.einsum("np,nhd->phd", mf, packed.astype(jnp.float32))
        upd = upd.reshape(p_total, page, hkv, d2).transpose(0, 2, 1, 3)
        cache_q = jnp.where(any_m[:, None, :, None], upd.astype(jnp.uint8), cache_q)
        upd_s = jnp.einsum("np,nh->ph", mf, sc).reshape(p_total, page, hkv)
        cache_s = jnp.where(any_m[:, None, :],
                            upd_s.transpose(0, 2, 1).astype(cache_s.dtype), cache_s)
        return cache_q, cache_s

    rows = pp[:, None]
    heads = jnp.arange(hkv)[None, :]
    cache_q = cache_q.at[rows, heads, off[:, None]].set(packed)
    cache_s = cache_s.at[rows, heads, off[:, None]].set(sc.astype(cache_s.dtype))
    return cache_q, cache_s


@scoped("kv_gather")
def gather_kv_q4(
    cache_q: jnp.ndarray,  # uint8 [P, Hkv, page, D//2] packed
    cache_s: jnp.ndarray,  # [P, Hkv, page]
    table: jnp.ndarray,    # [N, MaxP]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Logical ([N, Hkv, MaxP*page, D] int8 in [-8, 7], [N, Hkv, MaxP*page]
    scale) views of each slot's packed cache — the XLA read path unpacks
    AFTER the gather so HBM reads stay packed; the unpacked view feeds the
    same ``decode_attention_q`` contraction the int8 layout uses."""
    n, maxp = table.shape
    _, hkv, page, d2 = cache_q.shape
    safe = jnp.minimum(table, cache_q.shape[0] - 1)

    gq = cache_q[safe].transpose(0, 2, 1, 3, 4).reshape(n, hkv, maxp * page, d2)
    gs = cache_s[safe].transpose(0, 2, 1, 3).reshape(n, hkv, maxp * page)
    return unpack_int4(gq), _corrupt_scales(gs)


@scoped("kv_append")
def write_prompts_paged(
    k_layer: jnp.ndarray,  # [P, Hkv, page, D]
    v_layer: jnp.ndarray,
    pages: jnp.ndarray,    # [B, S_pages] physical page per logical page (P = dropped)
    k_new: jnp.ndarray,    # [B, S, Hkv, D] activation layout
    v_new: jnp.ndarray,
    offsets: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write prefilled prompts (or prompt CHUNKS) through per-row block
    tables. ``pages[b, j]`` is the physical page holding positions
    j*page .. (j+1)*page of row b; ``offsets`` [B] places the chunk at
    logical positions offsets..offsets+S (None = 0)."""
    b, s, hkv, _ = k_new.shape
    page = k_layer.shape[2]
    pos = jnp.arange(s)[None, :] + (offsets[:, None] if offsets is not None else 0)
    pp, off = _locate(pages, pos, page)  # [B,S] each
    rows = pp[:, :, None]
    heads = jnp.arange(hkv)[None, None, :]
    offs = off[:, :, None]
    k_layer = k_layer.at[rows, heads, offs].set(k_new.astype(k_layer.dtype))
    v_layer = v_layer.at[rows, heads, offs].set(v_new.astype(v_layer.dtype))
    return k_layer, v_layer


@scoped("kv_append")
def append_tokens_paged(
    k_layer: jnp.ndarray,   # [P, Hkv, page, D]
    v_layer: jnp.ndarray,
    table: jnp.ndarray,     # [N, MaxP] block table for every slot
    positions: jnp.ndarray, # [N] logical write position per slot
    k_new: jnp.ndarray,     # [N, Hkv, D]
    v_new: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Append one token's K/V per slot at its current logical position.

    Two lowerings, chosen by ``GOFR_PAGED_KV_WRITE`` (default ``select``;
    anything else means ``scatter``): ``select`` rebuilds the pool through
    a one-hot einsum + masked select — the same trick that beat XLA's
    scatter ~1.4-2x for the slot cache on v5e (ops/kvcache.append_tokens) —
    while ``scatter`` keeps the advanced-indexing scatter (cheaper
    asymptotically for very large pools, where the one-hot matmul and
    full-pool rewrite start to dominate). The choice comes from
    ``resolve_write_mode()``: engines resolve ``GOFR_PAGED_KV_WRITE``
    once at construction and pin it for their traces (``write_mode_scope``);
    the env var is only the fallback for direct callers. jit caches traces
    process-globally, so the choice is effectively FIXED FOR THE LIFE OF
    THE PROCESS — A/B the two lowerings across separate processes, not by
    flipping the var between engine builds. OOB semantics are
    preserved either way: OOB rows' flat position falls outside the one-hot
    range, producing an all-false mask row (the scatter path relies on XLA
    dropping OOB updates)."""
    n, hkv, d = k_new.shape
    p_total, _, page, _ = k_layer.shape

    mode = resolve_write_mode()
    if mode == "pallas":
        from gofr_tpu.ops.pallas import interpret_mode, require_kernel_platform
        from gofr_tpu.ops.pallas.kv_append import append_tokens_paged_inplace

        require_kernel_platform("GOFR_PAGED_KV_WRITE=pallas")
        return append_tokens_paged_inplace(
            k_layer, v_layer, table, positions, k_new, v_new,
            interpret=interpret_mode(),
        )

    pp, off = _locate(table, positions[:, None], page)
    pp, off = pp[:, 0], off[:, 0]  # [N]

    if mode != "scatter":
        flat = pp * page + off  # [N]; OOB rows land >= p_total*page
        grid = jnp.arange(p_total * page)
        m = flat[:, None] == grid[None, :]  # [N, P*page]
        any_m = m.reshape(n, p_total, page).any(axis=0)[:, None, :, None]
        def fold(new, layer):
            upd = jnp.einsum("np,nhd->phd", m.astype(layer.dtype), new.astype(layer.dtype))
            upd = upd.reshape(p_total, page, hkv, d).transpose(0, 2, 1, 3)
            return jnp.where(any_m, upd, layer)
        return fold(k_new, k_layer), fold(v_new, v_layer)

    rows = pp[:, None]
    heads = jnp.arange(hkv)[None, :]
    k_layer = k_layer.at[rows, heads, off[:, None]].set(k_new.astype(k_layer.dtype))
    v_layer = v_layer.at[rows, heads, off[:, None]].set(v_new.astype(v_layer.dtype))
    return k_layer, v_layer


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


@scoped("kv_gather")
def gather_kv(
    k_layer: jnp.ndarray,  # [P, Hkv, page, D]
    v_layer: jnp.ndarray,
    table: jnp.ndarray,    # [N, MaxP]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Materialize the logical [N, Hkv, MaxP*page, D] view of each slot's
    cache (XLA fallback read path; the Pallas paged-decode kernel reads the
    pool directly instead). OOB table entries clamp — callers must mask by
    lengths, which the attention ops already do."""
    n, maxp = table.shape
    _, hkv, page, d = k_layer.shape

    def view(layer):
        g = layer[jnp.minimum(table, layer.shape[0] - 1)]  # [N, MaxP, Hkv, page, D]
        return g.transpose(0, 2, 1, 3, 4).reshape(n, hkv, maxp * page, d)

    return view(k_layer), view(v_layer)
