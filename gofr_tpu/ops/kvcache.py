"""Slot-based KV cache for continuous batching.

One static buffer of shape [layers, slots, kv_heads, max_len, head_dim]
per K and V. The serving engine owns slot assignment: an arriving request
claims a free slot, prefill writes its prompt at offset 0, each decode
step appends one token at ``positions[slot]``, and the slot is recycled on
completion. Static shapes mean XLA compiles exactly one decode program for
the whole serving lifetime — the continuous-batching analog of the
reference's goroutine-per-request hot path (SURVEY.md §3.2).

Layout note: layers lead so a ``lax.scan`` over layers can carry the cache
as its xs/ys. Heads sit AHEAD of sequence (head-major) so the decode/flash
Pallas kernels can block one [block_kv, head_dim] tile per (slot, kv_head)
straight out of HBM — TPU tiling requires the last two dims of a block to
be (8k, 128k)-aligned, which a seq-major layout cannot satisfy per-head.
Activations stay [B, S, H, D]; the helpers below transpose at the write,
which XLA fuses into the scatter.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from gofr_tpu.tracing import scoped


@jax.tree_util.register_dataclass
@dataclass
class SlotKVCache:
    k: jnp.ndarray  # [L, B, Hkv, Smax, D]
    v: jnp.ndarray  # [L, B, Hkv, Smax, D]

    @classmethod
    def create(
        cls,
        layers: int,
        slots: int,
        max_len: int,
        kv_heads: int,
        head_dim: int,
        dtype=jnp.bfloat16,
    ) -> "SlotKVCache":
        shape = (layers, slots, kv_heads, max_len, head_dim)
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_slots(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[3]


@jax.tree_util.register_dataclass
@dataclass
class QSlotKVCache:
    """int8 KV cache with per-(slot, head, position) symmetric scales.

    Decode attention reads dominate HBM traffic at long context; storing
    K/V as int8 halves them. Scales live per cached ROW (reduction over D),
    so dequantization folds into the attention matmuls the same way weight
    scales fold into qdot (ops/quant.py): scores pick up ``ks`` per key
    position (constant along the D contraction), and the value matmul picks
    up ``vs`` on the probabilities (constant along its T contraction) —
    the int8 buffers convert at the matmul input and HBM traffic stays
    int8. Scale overhead: 2/D of the cache bytes (bf16 scales)."""

    k: jnp.ndarray   # int8 [L, B, Hkv, Smax, D]
    v: jnp.ndarray   # int8 [L, B, Hkv, Smax, D]
    ks: jnp.ndarray  # bf16 [L, B, Hkv, Smax]
    vs: jnp.ndarray  # bf16 [L, B, Hkv, Smax]

    @classmethod
    def create(cls, layers: int, slots: int, max_len: int, kv_heads: int,
               head_dim: int, dtype=jnp.bfloat16) -> "QSlotKVCache":
        del dtype  # storage is int8 by definition; arg kept for API parity
        shape = (layers, slots, kv_heads, max_len, head_dim)
        sshape = (layers, slots, kv_heads, max_len)
        return cls(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            ks=jnp.zeros(sshape, jnp.bfloat16), vs=jnp.zeros(sshape, jnp.bfloat16),
        )

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_slots(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[3]


def quantize_row(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 over the last (head_dim) axis: returns (q int8,
    scale[...] f32 without the reduced axis)."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


@scoped("kv_append")
def write_prompts_q(
    cache_q: jnp.ndarray,   # int8 [Slots, Hkv, Smax, D] (one of k/v)
    cache_s: jnp.ndarray,   # [Slots, Hkv, Smax] scales
    slots: jnp.ndarray,
    new: jnp.ndarray,       # [B, S, Hkv, D] activation layout
    offsets: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized analog of write_prompts for ONE of the k/v planes."""
    b, s, hkv, _ = new.shape
    q, sc = quantize_row(new)  # [B,S,Hkv,D] int8, [B,S,Hkv]
    rows = slots[:, None, None]
    heads = jnp.arange(hkv)[None, :, None]
    pos = jnp.arange(s)[None, None, :]
    if offsets is not None:
        pos = pos + offsets[:, None, None]
    cache_q = cache_q.at[rows, heads, pos].set(q.swapaxes(1, 2))
    cache_s = cache_s.at[rows, heads, pos].set(sc.swapaxes(1, 2).astype(cache_s.dtype))
    return cache_q, cache_s


@scoped("kv_append")
def append_tokens_q(
    cache_q: jnp.ndarray,   # int8 [B, Hkv, Smax, D]
    cache_s: jnp.ndarray,   # [B, Hkv, Smax]
    positions: jnp.ndarray,
    new: jnp.ndarray,       # [B, Hkv, D]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized analog of append_tokens (masked-select lowering; OOB
    positions drop) for one of the k/v planes."""
    smax = cache_q.shape[2]
    q, sc = quantize_row(new)  # [B,Hkv,D] int8, [B,Hkv]
    mask = (positions[:, None] == jnp.arange(smax)[None, :])  # [B, Smax]
    cache_q = jnp.where(mask[:, None, :, None], q[:, :, None, :], cache_q)
    cache_s = jnp.where(mask[:, None, :], sc[:, :, None].astype(cache_s.dtype), cache_s)
    return cache_q, cache_s


def fake_quant_row(x: jnp.ndarray, dtype=None, scale_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Round-trip ``x`` through int8 row quantization EXACTLY as the cache
    stores and the read path dequantizes it: the scale goes through the
    cache's scale dtype (bf16) and the multiply/cast order mirrors
    ``dequantize_view``. Prefill attention in the quantized branches uses
    this for the CURRENT chunk's k/v so cold prompts attend to exactly
    what a later prefix-cache hit will read — any representation mismatch
    (e.g. an f32 scale here vs the stored bf16 scale) would let hit and
    cold runs diverge near a logit tie."""
    q, s = quantize_row(x)
    out_dtype = dtype or x.dtype
    return q.astype(out_dtype) * s.astype(scale_dtype)[..., None].astype(out_dtype)


def dequantize_view(cache_q: jnp.ndarray, cache_s: jnp.ndarray, dtype) -> jnp.ndarray:
    """[.., Smax, D] int8 × [.., Smax] scales → dense dtype view (the
    chunked-prefill gather path; attention proper keeps int8 reads)."""
    return cache_q.astype(dtype) * cache_s[..., None].astype(dtype)


@scoped("kv_append")
def write_prompts(
    k_layer: jnp.ndarray,
    v_layer: jnp.ndarray,
    slots: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    offsets: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write prefilled prompts [B, S, Hkv, D] (activation layout) into rows
    ``slots`` [B] at positions ``offsets``..``offsets``+S (0..S when
    offsets is None — whole-prompt prefill; nonzero for chunked prefill).
    ``k_layer``/``v_layer`` are per-layer views [Slots, Hkv, Smax, D]."""
    b, s, hkv, _ = k_new.shape
    rows = slots[:, None, None]
    heads = jnp.arange(hkv)[None, :, None]
    pos = jnp.arange(s)[None, None, :]
    if offsets is not None:
        pos = pos + offsets[:, None, None]
    k_layer = k_layer.at[rows, heads, pos].set(k_new.swapaxes(1, 2).astype(k_layer.dtype))
    v_layer = v_layer.at[rows, heads, pos].set(v_new.swapaxes(1, 2).astype(v_layer.dtype))
    return k_layer, v_layer


def write_prompt(
    k_layer: jnp.ndarray,
    v_layer: jnp.ndarray,
    slot: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-slot write of one prompt [S, Hkv, D] at offset 0."""
    slot = jnp.asarray(slot)[None]
    return write_prompts(k_layer, v_layer, slot, k_new[None], v_new[None])


@scoped("kv_append")
def append_tokens(
    k_layer: jnp.ndarray,
    v_layer: jnp.ndarray,
    positions: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Append one token's K/V per slot: k_new [B, Hkv, D] written at
    ``positions`` [B] in each slot's sequence dimension.

    Two lowerings, chosen by ``GOFR_KV_WRITE`` (read at TRACE time; jit
    caches traces process-globally, so A/B across processes):

    - ``select`` (default): masked full-buffer select — rewrites the whole
      layer buffer every step: O(N*Hkv*Smax*D) HBM traffic.
    - ``pallas``: in-place tile-patch kernel (ops/pallas/kv_append) —
      O(N*Hkv*block*D) traffic via input/output aliasing; requires a TPU
      (or the Pallas interpreter) and raises elsewhere.

    Which is faster on the chip is not measured (PERF.md; ROADMAP S3)."""
    import os

    if os.environ.get("GOFR_KV_WRITE", "select") == "pallas":
        from gofr_tpu.ops.pallas import interpret_mode, require_kernel_platform
        from gofr_tpu.ops.pallas.kv_append import append_tokens_inplace

        require_kernel_platform("GOFR_KV_WRITE=pallas")
        return append_tokens_inplace(
            k_layer, v_layer, positions, k_new, v_new,
            interpret=interpret_mode(),
        )
    smax = k_layer.shape[2]
    mask = (positions[:, None] == jnp.arange(smax)[None, :])[:, None, :, None]
    k_layer = jnp.where(mask, k_new.astype(k_layer.dtype)[:, :, None, :], k_layer)
    v_layer = jnp.where(mask, v_new.astype(v_layer.dtype)[:, :, None, :], v_layer)
    return k_layer, v_layer
