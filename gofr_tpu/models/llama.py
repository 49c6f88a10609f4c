"""Llama-family decoder-only LM (the framework's flagship model).

Covers Llama-2/3 shapes: RMSNorm, RoPE, grouped-query attention, SwiGLU
MLP, optional tied embeddings. Pure-functional, stacked-layer params
scanned with ``lax.scan`` (see gofr_tpu.models.base docstring).

Three jittable entry points:
- ``forward``          full causal pass, no cache (training / scoring)
- ``prefill``          writes prompt K/V into SlotKVCache slots, returns
                       last-position logits
- ``decode_step``      one token per active slot, appends K/V in place

TP sharding is expressed through logical axes (``param_axes``): heads /
kv_heads / mlp / vocab shard over "tp", giving the standard Megatron-style
column→row parallel layout per block — XLA inserts the psum on wo/w_down
(reference capability map: SURVEY.md §2.9 — this subsystem is new, the
reference has no model layer).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.models.base import fan_in_init, qkv_heads, truncated_normal
from gofr_tpu.ops import apply_rope, mha_attention, rms_norm, rope_table
from gofr_tpu.ops.attention import decode_attention, decode_attention_q, paged_decode_attention
from gofr_tpu.ops.quant import qdot
from gofr_tpu.ops.kvcache import (
    QSlotKVCache,
    SlotKVCache,
    append_tokens,
    append_tokens_q,
    dequantize_view,
    fake_quant_row,
    write_prompts,
    write_prompts_q,
)
from gofr_tpu.ops.attention import (
    append_rides_in_kernel,
    paged_decode_append_attention,
    paged_decode_attention_q,
    paged_decode_attention_q4,
)
from gofr_tpu.ops.paged import (
    PagedKVCache,
    Q4PagedKVCache,
    QPagedKVCache,
    append_tokens_paged,
    append_tokens_paged_q,
    append_tokens_paged_q4,
    gather_kv,
    gather_kv_q,
    gather_kv_q4,
    write_prompts_paged,
    write_prompts_paged_q,
    write_prompts_paged_q4,
)
from gofr_tpu.ops.quant import fake_quant_row_int4
from gofr_tpu.ops.lora import lora_logits_delta
from gofr_tpu.tracing import scoped

# Serving entry points accept a per-lane LoRA pool (``adapters`` kwarg:
# (sel, a, b, scale); ops/lora.py) — build_programs keys on this flag.
SUPPORTS_ADAPTERS = True


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int | None = None
    rope_theta: float = 500000.0
    max_seq_len: int = 8192
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    @property
    def head_size(self) -> int:
        return self.head_dim if self.head_dim is not None else self.hidden_size // self.num_heads

    # -- presets ---------------------------------------------------------------

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        return cls(**{**dict(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=500000.0,
        ), **kw})

    @classmethod
    def llama3_70b(cls, **kw) -> "LlamaConfig":
        return cls(**{**dict(
            vocab_size=128256, hidden_size=8192, intermediate_size=28672,
            num_layers=80, num_heads=64, num_kv_heads=8, rope_theta=500000.0,
        ), **kw})

    @classmethod
    def one_b(cls, **kw) -> "LlamaConfig":
        """~1B-param config that fits one v5e chip in bf16 with headroom."""
        return cls(**{**dict(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_layers=22, num_heads=32, num_kv_heads=4, rope_theta=10000.0,
        ), **kw})

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test-sized config for the CPU mesh."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
            rope_theta=10000.0, dtype=jnp.float32,
        ), **kw})


# -- params --------------------------------------------------------------------


def init(cfg: LlamaConfig, key: jax.Array) -> dict:
    e, m, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    hq, hkv, d, nl = cfg.num_heads, cfg.num_kv_heads, cfg.head_size, cfg.num_layers
    keys = jax.random.split(key, 9)
    dt = cfg.dtype

    params = {
        "embed": truncated_normal(keys[0], (v, e), 0.02, dt),
        "blocks": {
            "attn_norm": jnp.ones((nl, e), dt),
            "wq": fan_in_init(keys[1], (nl, e, hq * d), fan_in=e, dtype=dt),
            "wk": fan_in_init(keys[2], (nl, e, hkv * d), fan_in=e, dtype=dt),
            "wv": fan_in_init(keys[3], (nl, e, hkv * d), fan_in=e, dtype=dt),
            "wo": fan_in_init(keys[4], (nl, hq * d, e), fan_in=hq * d, dtype=dt),
            "mlp_norm": jnp.ones((nl, e), dt),
            "w_gate": fan_in_init(keys[5], (nl, e, m), fan_in=e, dtype=dt),
            "w_up": fan_in_init(keys[6], (nl, e, m), fan_in=e, dtype=dt),
            "w_down": fan_in_init(keys[7], (nl, m, e), fan_in=m, dtype=dt),
        },
        "final_norm": jnp.ones((e,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = truncated_normal(keys[8], (e, v), 0.02, dt)
    return params


# every linear site routes through ops.quant.qdot, so QTensor params serve
QUANTIZABLE = True
# prefill() accepts chunk offsets, so the slot-layout engine can stream
# long prompts in chunks too (the paged layout has prefill_paged for this)
SLOT_CHUNKED_PREFILL = True


def param_axes(cfg: LlamaConfig) -> dict:
    """Logical sharding axes matching ``init``'s pytree (see
    gofr_tpu.parallel.sharding)."""
    axes = {
        "embed": ("vocab", "embed"),
        "blocks": {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", None),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": (None,),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _rope(cfg: LlamaConfig):
    return rope_table(cfg.max_seq_len, cfg.head_size, theta=cfg.rope_theta)


# -- block ---------------------------------------------------------------------


# The phases every layer body below is made of carry their tracing.SCOPES name
# HERE (and inside the ops they call: apply_rope, the KV writes and gathers,
# the attention ops), not at the eight call sites: a device trace then names
# an operation by its phase whichever entry point ran it.


@scoped("embed")
def _embed(cfg: LlamaConfig, params: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    return params["embed"][tokens].astype(cfg.dtype)


@scoped("qkv_rope")
def _qkv(cfg: LlamaConfig, lp: dict, x: jnp.ndarray):
    """x [B,S,E] → q [B,S,Hq,D], k/v [B,S,Hkv,D] (post-norm, pre-rope)."""
    return qkv_heads(rms_norm(x, lp["attn_norm"], cfg.norm_eps), lp, cfg.head_size)


@scoped("o_proj")
def _o_proj(lp: dict, x: jnp.ndarray, attn: jnp.ndarray) -> jnp.ndarray:
    """Residual + output projection of the attention heads ([..., Hq, D])."""
    return x + qdot(attn.reshape(*x.shape[:-1], -1), lp["wo"])


@scoped("mlp")
def _mlp(cfg: LlamaConfig, lp: dict, x: jnp.ndarray) -> jnp.ndarray:
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    gated = jax.nn.silu(qdot(h, lp["w_gate"])) * qdot(h, lp["w_up"])
    return qdot(gated, lp["w_down"])


@scoped("lm_head")
def _lm_head(cfg: LlamaConfig, params: dict, x: jnp.ndarray, adapters=None,
             last=None, head_fn: Any = None) -> jnp.ndarray:
    """Final norm → (``last``: pick one position per row) → vocabulary
    projection in f32, plus the per-lane LoRA delta when ``adapters``."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if last is not None:
        x = x[last]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (head_fn(x, head) if head_fn is not None else qdot(x, head)).astype(jnp.float32)
    if adapters is not None:
        logits = logits + lora_logits_delta(x, adapters)
    return logits


# -- entry points --------------------------------------------------------------


@partial(jax.jit, static_argnums=(0, 4, 5))
def forward(cfg: LlamaConfig, params: dict, tokens: jnp.ndarray,
            lengths: jnp.ndarray | None = None,
            attn_fn: Any = None, head_fn: Any = None) -> jnp.ndarray:
    """Full causal forward, no cache: tokens [B,S] → logits [B,S,V] (f32).
    ``lengths`` masks padded positions out of attention.

    ``attn_fn`` swaps the attention implementation (static; same contract
    as ops.mha_attention) — e.g. a mesh-bound ring/Ulysses sequence-parallel
    attention from gofr_tpu.parallel.ring.make_seq_parallel_attn.

    ``head_fn`` swaps the lm_head projection (static; ``(x, head) ->
    logits``) — e.g. the quality plane's LoRA-delta head, which must score
    teacher-forced sequences with the exact adapter math serving used."""
    attn = attn_fn or mha_attention
    cos, sin = _rope(cfg)
    x = _embed(cfg, params, tokens)
    b, s = tokens.shape
    positions = jnp.arange(s)[None]

    def body(x, lp):
        q, k, v = _qkv(cfg, lp, x)
        q = apply_rope(q, positions, cos, sin)
        k = apply_rope(k, positions, cos, sin)
        a = attn(q, k, v, causal=True, kv_lengths=lengths)
        x = _o_proj(lp, x, a)
        x = x + _mlp(cfg, lp, x)
        return x, None

    x, _ = lax.scan(body, x, params["blocks"])
    return _lm_head(cfg, params, x, head_fn=head_fn)


@partial(jax.jit, static_argnums=(0, 4, 5))
def forward_pipelined(cfg: LlamaConfig, params: dict, tokens: jnp.ndarray,
                      lengths: jnp.ndarray, mesh: Any,
                      microbatches: int = 4) -> jnp.ndarray:
    """Pipeline-parallel full forward: blocks shard over the mesh's ``pp``
    axis (leading layers dim) and microbatches stream through the stage
    ring (gofr_tpu.parallel.pipeline). Embed/norm/head stay replicated.
    Requires num_layers % pp == 0 and batch % microbatches == 0.

    Composes with tp: heads/mlp dims of the stage weights stay tp-sharded
    inside the pipeline region (manual Megatron-style psums after wo and
    w_down), so pp×tp meshes neither replicate weights nor duplicate
    compute."""
    from gofr_tpu.parallel.pipeline import make_pipeline_forward
    from gofr_tpu.parallel.sharding import ShardingRules

    cos, sin = _rope(cfg)
    x = _embed(cfg, params, tokens)
    s = tokens.shape[1]
    positions = jnp.arange(s)[None]
    d = cfg.head_size
    tp = "tp" if "tp" in mesh.axis_names and mesh.shape["tp"] > 1 else None

    def stage(blocks_local, x, lens):
        b = x.shape[0]

        def body(x, lp):
            # local-head qkv: head counts come from the tp-sharded weights
            q, k, v = qkv_heads(rms_norm(x, lp["attn_norm"], cfg.norm_eps), lp, d)
            q = apply_rope(q, positions, cos, sin)
            k = apply_rope(k, positions, cos, sin)
            a = mha_attention(q, k, v, causal=True, kv_lengths=lens)
            o = a.reshape(b, s, -1) @ lp["wo"]
            if tp:
                o = lax.psum(o, tp)
            x = x + o
            h2 = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            mo = (jax.nn.silu(h2 @ lp["w_gate"]) * (h2 @ lp["w_up"])) @ lp["w_down"]
            if tp:
                mo = lax.psum(mo, tp)
            return x + mo, None

        x, _ = lax.scan(body, x, blocks_local)
        return x

    rules = ShardingRules().with_overrides(layers="pp")
    block_specs = {
        name: rules.spec(axes, mesh)
        for name, axes in param_axes(cfg)["blocks"].items()
    }
    pp_forward = make_pipeline_forward(
        mesh, microbatches=microbatches, param_specs=block_specs
    )
    x = pp_forward(stage, params["blocks"], x, lengths)
    return _lm_head(cfg, params, x)


@partial(jax.jit, static_argnums=0, static_argnames=("attn_fn",), donate_argnums=4)
def prefill(cfg: LlamaConfig, params: dict, tokens: jnp.ndarray, lengths: jnp.ndarray,
            cache: SlotKVCache, slots: jnp.ndarray,
            offsets: jnp.ndarray | None = None, *,
            attn_fn: Any = None,
            adapters=None) -> tuple[jnp.ndarray, SlotKVCache]:
    """Prefill prompts (or prompt CHUNKS) into cache slots.

    tokens [B,S] (padded), lengths [B] = live tokens in this call, slots
    [B] → (last-token logits [B,V] f32, updated cache). ``offsets`` [B]
    places the chunk at logical positions offsets..offsets+S (None = 0,
    whole-prompt prefill). Chunked rows attend to everything already in
    their slot through a gathered cache view; whole-prompt rows attend
    prompt-locally.

    ``attn_fn`` swaps the whole-prompt attention (same contract as
    ops.mha_attention) — e.g. a mesh-bound ring/Ulysses sequence-parallel
    attention (parallel.ring.make_seq_parallel_attn) so long-prompt
    prefill shards the sequence over an ``sp`` axis. Whole-prompt rows
    only: the chunked path's gathered-view attention stays as is.
    """
    if attn_fn is not None and offsets is not None:
        raise ValueError("attn_fn applies to whole-prompt prefill only (offsets=None)")
    cos, sin = _rope(cfg)
    x = _embed(cfg, params, tokens)
    b, s = tokens.shape
    chunked = offsets is not None
    positions = (offsets[:, None] if chunked else 0) + jnp.arange(s)[None]
    row = jnp.arange(b)
    total = (offsets + lengths) if chunked else lengths
    quant = isinstance(cache, QSlotKVCache)  # int8 KV storage (kvcache.py)

    def body(x, xs):
        if quant:
            lp, k_layer, ks_l, v_layer, vs_l = xs
        else:
            lp, k_layer, v_layer = xs
        q, k, v = _qkv(cfg, lp, x)
        q = apply_rope(q, positions, cos, sin)
        k = apply_rope(k, positions, cos, sin)
        if quant:
            k_layer, ks_l = write_prompts_q(k_layer, ks_l, slots, k, offsets)
            v_layer, vs_l = write_prompts_q(v_layer, vs_l, slots, v, offsets)
        else:
            k_layer, v_layer = write_prompts(k_layer, v_layer, slots, k, v, offsets)
        if chunked:
            if quant:
                k_view = dequantize_view(jnp.take(k_layer, slots, axis=0),
                                         jnp.take(ks_l, slots, axis=0), cfg.dtype)
                v_view = dequantize_view(jnp.take(v_layer, slots, axis=0),
                                         jnp.take(vs_l, slots, axis=0), cfg.dtype)
            else:
                k_view = jnp.take(k_layer, slots, axis=0)  # [B, Hkv, Smax, D]
                v_view = jnp.take(v_layer, slots, axis=0)
            attn = mha_attention(
                q, k_view.swapaxes(1, 2), v_view.swapaxes(1, 2),
                causal=True, q_offset=offsets, kv_lengths=total,
            )
        elif quant:
            # self-consistency with the int8 cache (see prefill_paged)
            attn = (attn_fn or mha_attention)(
                q, fake_quant_row(k), fake_quant_row(v),
                causal=True, kv_lengths=lengths)
        else:
            attn = (attn_fn or mha_attention)(q, k, v, causal=True, kv_lengths=lengths)
        x = _o_proj(lp, x, attn)
        x = x + _mlp(cfg, lp, x)
        return x, (k_layer, ks_l, v_layer, vs_l) if quant else (k_layer, v_layer)

    if quant:
        xs = (params["blocks"], cache.k, cache.ks, cache.v, cache.vs)
        x, (new_k, new_ks, new_v, new_vs) = lax.scan(body, x, xs)
        out_cache = QSlotKVCache(k=new_k, v=new_v, ks=new_ks, vs=new_vs)
    else:
        x, (new_k, new_v) = lax.scan(body, x, (params["blocks"], cache.k, cache.v))
        out_cache = SlotKVCache(k=new_k, v=new_v)
    # last live position per row → [B,E] → logits
    return _lm_head(cfg, params, x, adapters, last=(row, lengths - 1)), out_cache


@partial(jax.jit, static_argnums=0, donate_argnums=4)
def verify_step(cfg: LlamaConfig, params: dict, tokens: jnp.ndarray,
                positions: jnp.ndarray, cache: SlotKVCache,
                adapters=None) -> tuple[jnp.ndarray, SlotKVCache]:
    """Speculative-decoding verification (engine.spec_tokens): one forward
    over ``tokens`` [N, T] per slot — the current input token plus T-1
    draft tokens — written and attended at positions ``positions[n]`` ..
    ``positions[n]+T-1`` of slot n's cache. Returns logits [N, T, V] (f32,
    the target's next-token distribution AFTER each of the T tokens) and
    the updated cache.

    Draft K/V beyond the accepted prefix go stale in the cache but are
    always overwritten before they can be attended: the next round's write
    range starts at the new input position and covers every stale slot
    before its per-layer attention runs (engine._spec_chunk invariants).
    Out-of-bounds positions (inactive lanes) drop their writes — the same
    convention as prefill padding rows."""
    cos, sin = _rope(cfg)
    x = _embed(cfg, params, tokens)
    n, t = tokens.shape
    pos2d = positions[:, None] + jnp.arange(t)[None]
    total = positions + t
    rows = jnp.arange(n)
    quant = isinstance(cache, QSlotKVCache)

    def body(x, xs):
        if quant:
            lp, k_layer, ks_l, v_layer, vs_l = xs
        else:
            lp, k_layer, v_layer = xs
        q, k, v = _qkv(cfg, lp, x)
        q = apply_rope(q, pos2d, cos, sin)
        k = apply_rope(k, pos2d, cos, sin)
        if quant:
            k_layer, ks_l = write_prompts_q(k_layer, ks_l, rows, k, positions)
            v_layer, vs_l = write_prompts_q(v_layer, vs_l, rows, v, positions)
            k_view = dequantize_view(k_layer, ks_l, cfg.dtype)
            v_view = dequantize_view(v_layer, vs_l, cfg.dtype)
        else:
            k_layer, v_layer = write_prompts(k_layer, v_layer, rows, k, v, positions)
            k_view, v_view = k_layer, v_layer
        attn = mha_attention(
            q, k_view.swapaxes(1, 2), v_view.swapaxes(1, 2),
            causal=True, q_offset=positions, kv_lengths=total,
        )
        x = _o_proj(lp, x, attn)
        x = x + _mlp(cfg, lp, x)
        return x, (k_layer, ks_l, v_layer, vs_l) if quant else (k_layer, v_layer)

    if quant:
        xs = (params["blocks"], cache.k, cache.ks, cache.v, cache.vs)
        x, (new_k, new_ks, new_v, new_vs) = lax.scan(body, x, xs)
        out_cache = QSlotKVCache(k=new_k, v=new_v, ks=new_ks, vs=new_vs)
    else:
        x, (new_k, new_v) = lax.scan(body, x, (params["blocks"], cache.k, cache.v))
        out_cache = SlotKVCache(k=new_k, v=new_v)
    return _lm_head(cfg, params, x, adapters), out_cache


@partial(jax.jit, static_argnums=0, donate_argnums=4)
def decode_step(cfg: LlamaConfig, params: dict, tokens: jnp.ndarray, positions: jnp.ndarray,
                cache: SlotKVCache,
                adapters=None) -> tuple[jnp.ndarray, SlotKVCache]:
    """One decode step over every slot.

    tokens [N] (next input token per slot), positions [N] (where it goes in
    the cache = current sequence length), over the full slot batch
    N == cache.num_slots. Returns (logits [N,V] f32, updated cache).
    Inactive slots simply produce garbage logits the engine ignores —
    uniform work keeps the step a single fixed XLA program.
    """
    cos, sin = _rope(cfg)
    x = _embed(cfg, params, tokens)  # [N,E]
    n = tokens.shape[0]
    pos1 = positions[:, None]  # [N,1]
    quant = isinstance(cache, QSlotKVCache)

    def body(x, xs):
        if quant:
            lp, k_layer, ks_l, v_layer, vs_l = xs
        else:
            lp, k_layer, v_layer = xs
        q, k, v = _qkv(cfg, lp, x[:, None])  # seq dim of 1
        q = apply_rope(q, pos1, cos, sin)[:, 0]  # [N,Hq,D]
        k = apply_rope(k, pos1, cos, sin)[:, 0]
        v = v[:, 0]
        if quant:
            k_layer, ks_l = append_tokens_q(k_layer, ks_l, positions, k)
            v_layer, vs_l = append_tokens_q(v_layer, vs_l, positions, v)
            attn = decode_attention_q(q, k_layer, v_layer, ks_l, vs_l, positions + 1)
        else:
            k_layer, v_layer = append_tokens(k_layer, v_layer, positions, k, v)
            attn = decode_attention(q, k_layer, v_layer, positions + 1)
        x = _o_proj(lp, x, attn)
        x = x + _mlp(cfg, lp, x)
        return x, (k_layer, ks_l, v_layer, vs_l) if quant else (k_layer, v_layer)

    if quant:
        xs = (params["blocks"], cache.k, cache.ks, cache.v, cache.vs)
        x, (new_k, new_ks, new_v, new_vs) = lax.scan(body, x, xs)
        out_cache = QSlotKVCache(k=new_k, v=new_v, ks=new_ks, vs=new_vs)
    else:
        x, (new_k, new_v) = lax.scan(body, x, (params["blocks"], cache.k, cache.v))
        out_cache = SlotKVCache(k=new_k, v=new_v)
    return _lm_head(cfg, params, x, adapters), out_cache


def make_cache(cfg: LlamaConfig, slots: int, max_len: int | None = None) -> SlotKVCache:
    return SlotKVCache.create(
        cfg.num_layers, slots, max_len or cfg.max_seq_len, cfg.num_kv_heads,
        cfg.head_size, dtype=cfg.dtype,
    )


def make_cache_q(cfg: LlamaConfig, slots: int, max_len: int | None = None) -> QSlotKVCache:
    """int8 KV cache (kvcache.QSlotKVCache): same serving contract as
    make_cache — prefill/decode_step/verify_step branch on the cache type."""
    return QSlotKVCache.create(
        cfg.num_layers, slots, max_len or cfg.max_seq_len, cfg.num_kv_heads,
        cfg.head_size,
    )


# -- paged-cache entry points (ops.paged; SURVEY.md §7 stage 4) -----------------
#
# One idiom for the pool in all three programs: the cache pytree — whole
# planes [L, P, Hkv, page, ...] — rides in the layer scan's CARRY beside x,
# each layer addresses its rows by index, and the scan returns no
# pool-shaped ``ys``. A scan cannot alias ``ys`` onto ``xs``: handing the
# pool in as ``xs`` and taking it back as ``ys`` made XLA build a second
# pool, restack every layer into it and copy it back each step (ROADMAP
# S3). A carried buffer is updated in place.


def _scan_paged_layers(params: dict, x: jnp.ndarray, cache, layer_fn):
    """Run ``layer_fn(lp, layer, x, cache) -> (x, cache)`` over the blocks
    with the pool carried → (x, cache)."""
    def body(carry, xs):
        return layer_fn(*xs, *carry), None

    layers = jnp.arange(cache.num_layers, dtype=jnp.int32)
    (x, cache), _ = lax.scan(body, (x, cache), (params["blocks"], layers))
    return x, cache


def _write_paged(cache, layer, pages, k, v, offsets=None):
    """write_prompts_paged* by pool kind → the updated cache."""
    if isinstance(cache, PagedKVCache):
        k_pool, v_pool = write_prompts_paged(cache.k, cache.v, layer, pages, k, v, offsets)
        return PagedKVCache(k=k_pool, v=v_pool)
    wpp = write_prompts_paged_q4 if isinstance(cache, Q4PagedKVCache) else write_prompts_paged_q
    kq, ks = wpp(cache.k, cache.ks, layer, pages, k, offsets)
    vq, vs = wpp(cache.v, cache.vs, layer, pages, v, offsets)
    return type(cache)(k=kq, v=vq, ks=ks, vs=vs)


def _paged_views(cfg: LlamaConfig, cache, layer, table):
    """gather_kv* by pool kind → each slot's logical K and V views at one
    layer, [N, Hkv, MaxP*page, D] in the compute dtype."""
    if isinstance(cache, PagedKVCache):
        return gather_kv(cache.k, cache.v, layer, table)
    gkv = gather_kv_q4 if isinstance(cache, Q4PagedKVCache) else gather_kv_q
    return (dequantize_view(*gkv(cache.k, cache.ks, layer, table), cfg.dtype),
            dequantize_view(*gkv(cache.v, cache.vs, layer, table), cfg.dtype))


def _append_attend_paged(cache, layer, table, positions, q, k, v, window=None):
    """One decode token per slot, by pool kind: append its K/V at
    ``positions``, attend over ``positions + 1`` → (cache, attn). ``window``
    (a family with sliding-window layers; the dense pool only) bounds the
    attention to the last ``window`` positions; None hands the ops nothing."""
    sliding = {} if window is None else {"window": window}
    if isinstance(cache, PagedKVCache):
        if append_rides_in_kernel(cache.k):  # one kernel call writes the row and attends
            attn, k_pool, v_pool = paged_decode_append_attention(
                q, k, v, cache.k, cache.v, layer, table, positions, **sliding)
        else:
            k_pool, v_pool = append_tokens_paged(cache.k, cache.v, layer, table, positions, k, v)
            attn = paged_decode_attention(q, k_pool, v_pool, layer, table, positions + 1, **sliding)
        return PagedKVCache(k=k_pool, v=v_pool), attn
    if window is not None:
        raise ValueError("a sliding window is served on the dense paged pool only")
    q4c = isinstance(cache, Q4PagedKVCache)
    atp = append_tokens_paged_q4 if q4c else append_tokens_paged_q
    pda = paged_decode_attention_q4 if q4c else paged_decode_attention_q
    kq, ks = atp(cache.k, cache.ks, layer, table, positions, k)
    vq, vs = atp(cache.v, cache.vs, layer, table, positions, v)
    attn = pda(q, kq, vq, ks, vs, layer, table, positions + 1)
    return type(cache)(k=kq, v=vq, ks=ks, vs=vs), attn


@partial(jax.jit, static_argnums=0, donate_argnums=4)
def verify_step_paged(cfg: LlamaConfig, params: dict, tokens: jnp.ndarray,
                      positions: jnp.ndarray, cache, table: jnp.ndarray,
                      adapters=None):
    """Speculative-decoding verification against the paged pool — the
    contract and stale-draft-KV invariants of ``verify_step``, with writes
    routed through per-slot block tables (``table`` [N, MaxP]; OOB rows
    drop) and attention over the gathered logical views. Handles the
    dense, int8, and packed-int4 pools."""
    cos, sin = _rope(cfg)
    x = _embed(cfg, params, tokens)
    t = tokens.shape[1]
    pos2d = positions[:, None] + jnp.arange(t)[None]
    total = positions + t

    def layer_fn(lp, layer, x, cache):
        q, k, v = _qkv(cfg, lp, x)
        q = apply_rope(q, pos2d, cos, sin)
        k = apply_rope(k, pos2d, cos, sin)
        cache = _write_paged(cache, layer, table, k, v, positions)
        k_view, v_view = _paged_views(cfg, cache, layer, table)
        attn = mha_attention(
            q, k_view.swapaxes(1, 2), v_view.swapaxes(1, 2),
            causal=True, q_offset=positions, kv_lengths=total,
        )
        x = _o_proj(lp, x, attn)
        return x + _mlp(cfg, lp, x), cache

    x, cache = _scan_paged_layers(params, x, cache, layer_fn)
    return _lm_head(cfg, params, x, adapters), cache


def make_paged_cache(cfg: LlamaConfig, pages: int, page_size: int = 128,
                     sharding=None) -> PagedKVCache:
    return PagedKVCache.create(
        cfg.num_layers, pages, page_size, cfg.num_kv_heads, cfg.head_size,
        dtype=cfg.dtype, sharding=sharding,
    )


def make_paged_cache_q(cfg: LlamaConfig, pages: int, page_size: int = 128,
                       sharding=None) -> QPagedKVCache:
    """int8 paged pool (ops.paged.QPagedKVCache): prefill_paged /
    decode_step_paged branch on the cache type, like the slot layout."""
    return QPagedKVCache.create(
        cfg.num_layers, pages, page_size, cfg.num_kv_heads, cfg.head_size,
        sharding=sharding,
    )


def make_paged_cache_q4(cfg: LlamaConfig, pages: int, page_size: int = 128,
                        sharding=None) -> Q4PagedKVCache:
    """Packed-int4 paged pool (ops.paged.Q4PagedKVCache): same plane names
    as the int8 pool so the scan xs plumbing is shared; only the per-plane
    write/gather/attention helpers differ (cache-type branch)."""
    return Q4PagedKVCache.create(
        cfg.num_layers, pages, page_size, cfg.num_kv_heads, cfg.head_size,
        sharding=sharding,
    )


@partial(jax.jit, static_argnums=0, static_argnames=("attn_fn",), donate_argnums=4)
def prefill_paged(
    cfg: LlamaConfig, params: dict, tokens: jnp.ndarray, lengths: jnp.ndarray,
    cache: PagedKVCache, pages: jnp.ndarray, offsets: jnp.ndarray | None = None,
    *, attn_fn: Any = None, adapters=None,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """Prefill prompts (or prompt CHUNKS) through per-row block tables.

    tokens [B,S] (padded), lengths [B] = live tokens in THIS chunk,
    ``pages`` [B, MaxP] = the full block table row per request (OOB = pool
    size for padding rows / unallocated pages). ``offsets`` [B] places the
    chunk at logical positions offsets..offsets+S (None = 0, whole-prompt
    prefill). Chunked rows attend to the already-written cache through a
    gathered view; whole-prompt rows attend prompt-locally, identical to
    ``prefill``. Returns (last-chunk-token logits [B,V] f32, cache).
    """
    if attn_fn is not None and offsets is not None:
        raise ValueError("attn_fn applies to whole-prompt prefill only (offsets=None)")
    cos, sin = _rope(cfg)
    x = _embed(cfg, params, tokens)
    b, s = tokens.shape
    off = jnp.zeros((b,), jnp.int32) if offsets is None else offsets
    positions = off[:, None] + jnp.arange(s)[None]  # [B,S] logical positions
    row = jnp.arange(b)
    total = off + lengths  # [B] cache length after this chunk
    if isinstance(cache, Q4PagedKVCache):
        stored = fake_quant_row_int4
    elif isinstance(cache, QPagedKVCache):
        stored = fake_quant_row
    else:
        stored = lambda kv: kv  # noqa: E731 - the bf16 pool stores k/v as they are

    def layer_fn(lp, layer, x, cache):
        q, k, v = _qkv(cfg, lp, x)
        q = apply_rope(q, positions, cos, sin)
        k = apply_rope(k, positions, cos, sin)
        cache = _write_paged(cache, layer, pages, k, v, offsets)
        if offsets is not None:
            # attend over everything written so far (incl. this chunk)
            k_view, v_view = _paged_views(cfg, cache, layer, pages)
            attn = mha_attention(
                q, k_view.swapaxes(1, 2), v_view.swapaxes(1, 2),
                causal=True, q_offset=off, kv_lengths=total,
            )
        else:
            # attend to what the cache STORES (fake-quantized k/v on the
            # quantized pools) so a later prefix-cache hit — which reads
            # the stored pages — is bit-identical to this cold run
            # (kvcache.fake_quant_row / quant.fake_quant_row_int4)
            attn = (attn_fn or mha_attention)(
                q, stored(k), stored(v), causal=True, kv_lengths=lengths)
        x = _o_proj(lp, x, attn)
        return x + _mlp(cfg, lp, x), cache

    x, cache = _scan_paged_layers(params, x, cache, layer_fn)
    # last live position per row → [B,E] → logits
    return _lm_head(cfg, params, x, adapters, last=(row, lengths - 1)), cache


@partial(jax.jit, static_argnums=0, donate_argnums=4)
def decode_step_paged(
    cfg: LlamaConfig, params: dict, tokens: jnp.ndarray, positions: jnp.ndarray,
    cache: PagedKVCache, table: jnp.ndarray, adapters=None,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """One decode step over every slot, K/V appended through the block
    table. Contract matches ``decode_step`` with ``table`` [N, MaxP]."""
    cos, sin = _rope(cfg)
    x = _embed(cfg, params, tokens)  # [N,E]
    pos1 = positions[:, None]

    def layer_fn(lp, layer, x, cache):
        q, k, v = _qkv(cfg, lp, x[:, None])
        q = apply_rope(q, pos1, cos, sin)[:, 0]
        k = apply_rope(k, pos1, cos, sin)[:, 0]
        cache, attn = _append_attend_paged(cache, layer, table, positions, q, k, v[:, 0])
        x = _o_proj(lp, x, attn)
        return x + _mlp(cfg, lp, x), cache

    x, cache = _scan_paged_layers(params, x, cache, layer_fn)
    return _lm_head(cfg, params, x, adapters), cache
