"""Cohere2-MoE decoder LM (``model_type`` ``cohere2_moe``: Command A+).

One block a layer, its two branches reading the SAME normed input (a
parallel block, one LayerNorm a layer, no bias anywhere):

    n      = LayerNorm(x)
    attn   = GQA attention of n: three layers in four look back ``sliding_window``
             positions and rotate q, k by interleaved-pair rotary; every
             ``layer_switch``-th layer sees everything and has NO positional
             embedding
    routed = the held experts' part of  Σ_{e in top-k of sigmoid(n·Wr)} g_e · FFN_e(n)
    shared = mean over the shared experts of FFN_s(n)
    y      = x + attn + routed + shared

and a tied head over ``LayerNorm(y_L)`` scaled by ``logit_scale``.

The expert layer is told which experts it holds (``first_expert``,
``experts_held``): it routes over the router's whole width ``num_experts``
and leaves out what the absent experts would have added — one rank of an
expert-parallel deployment, without its exchange (ops/moe.moe_ffn_held).
With ``experts_held == num_experts`` it is the whole model.

Paged layout only — ``forward``, ``prefill_paged`` (with offsets: chunked
prefill), ``decode_step_paged``, ``make_paged_cache`` — built on
models/llama.py's paged helpers: one scanned layer body serves both kinds of
layer, the kind derived from the scanned layer index as two values, a window
length (one that never binds on a full layer) and a 0/1 factor on the rotary
angle (angle 0 is the identity rotation, so "no positional embedding" is
exact). No slot-layout, speculative or quantized-KV entry points: the engine
refuses those by what the module lacks.

The serving entry points also return the step's routing counts (``step_counters``
says what each is); tpu/programs.py carries them back in the readback a step
already makes.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.models.base import fan_in_init, qkv_heads, truncated_normal
from gofr_tpu.models.llama import (
    _append_attend_paged,
    _paged_views,
    _scan_paged_layers,
    _write_paged,
)
from gofr_tpu.ops import mha_attention
from gofr_tpu.ops.moe import moe_ffn_held
from gofr_tpu.ops.norms import layer_norm
from gofr_tpu.ops.paged import PagedKVCache
from gofr_tpu.ops.rope import apply_rope_interleaved, rope_inv_freq
from gofr_tpu.tracing import scope, scoped

# A window no length reaches: what a full-attention layer hands the ops.
NO_WINDOW = 1 << 30


@dataclass(frozen=True)
class Cohere2MoeConfig:
    """The defaults are CohereLabs/command-a-plus-05-2026 as published."""

    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096   # ONE expert's width, routed and shared alike
    num_layers: int = 32
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: int = 128
    num_experts: int = 128          # the router's width
    experts_held: int = 128         # how many of them this rank holds ...
    first_expert: int = 0           # ... starting at this one
    experts_per_token: int = 8
    num_shared_experts: int = 4
    layer_switch: int = 4           # layer l is full attention iff l % layer_switch == layer_switch - 1
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    max_seq_len: int = 200000
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not 0 <= self.first_expert <= self.num_experts - self.experts_held:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.experts_held} "
                f"are not among the router's {self.num_experts}")

    @property
    def head_size(self) -> int:
        return self.head_dim

    @classmethod
    def tiny(cls, **kw) -> "Cohere2MoeConfig":
        """Test-sized config for the CPU: one whole period of the layer
        pattern, a window short enough to cross."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=48, num_layers=4,
            num_heads=8, num_kv_heads=2, head_dim=16, num_experts=16, experts_held=16,
            experts_per_token=4, num_shared_experts=2, sliding_window=16,
            rope_theta=10000.0, max_seq_len=256, dtype=jnp.float32,
        ), **kw})


# -- params --------------------------------------------------------------------


def init(cfg: Cohere2MoeConfig, key: jax.Array) -> dict:
    e, m, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    hq, hkv, d, nl = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    held, sm = cfg.experts_held, cfg.num_shared_experts * cfg.intermediate_size
    keys = jax.random.split(key, 12)
    dt = cfg.dtype
    return {
        "embed": truncated_normal(keys[0], (v, e), 0.02, dt),  # the head too (tied)
        "blocks": {
            "norm": jnp.ones((nl, e), dt),
            "wq": fan_in_init(keys[1], (nl, e, hq * d), fan_in=e, dtype=dt),
            "wk": fan_in_init(keys[2], (nl, e, hkv * d), fan_in=e, dtype=dt),
            "wv": fan_in_init(keys[3], (nl, e, hkv * d), fan_in=e, dtype=dt),
            "wo": fan_in_init(keys[4], (nl, hq * d, e), fan_in=hq * d, dtype=dt),
            # the router keeps its whole width and float32 (its scores pick experts)
            "router": fan_in_init(keys[5], (nl, e, cfg.num_experts), fan_in=e, dtype=jnp.float32),
            # the shared experts side by side: one product computes all of them
            "ws_gate": fan_in_init(keys[9], (nl, e, sm), fan_in=e, dtype=dt),
            "ws_up": fan_in_init(keys[10], (nl, e, sm), fan_in=e, dtype=dt),
            "ws_down": fan_in_init(keys[11], (nl, sm, e), fan_in=m, dtype=dt),
        },
        # the held routed experts of every layer, OUTSIDE the scanned blocks:
        # the layer body reads them at the scanned index (ops/moe.moe_ffn_held)
        "experts": {
            "w_gate": fan_in_init(keys[6], (nl, held, e, m), fan_in=e, dtype=dt),
            "w_up": fan_in_init(keys[7], (nl, held, e, m), fan_in=e, dtype=dt),
            "w_down": fan_in_init(keys[8], (nl, held, m, e), fan_in=m, dtype=dt),
        },
        "final_norm": jnp.ones((e,), dt),
    }


def param_axes(cfg: Cohere2MoeConfig) -> dict:
    return {
        "embed": ("vocab", "embed"),
        "blocks": {
            "norm": ("layers", None),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "router": ("layers", "embed", None),
            "ws_gate": ("layers", "embed", "mlp"),
            "ws_up": ("layers", "embed", "mlp"),
            "ws_down": ("layers", "mlp", "embed"),
        },
        "experts": {
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        },
        "final_norm": (None,),
    }


# What a serving step's counts vector holds, in order: (counter, labels).
def step_counters(cfg: Cohere2MoeConfig) -> tuple:
    return tuple(
        ("app_tpu_moe_assignments_total", {"expert": str(cfg.first_expert + i)})
        for i in range(cfg.experts_held)) + (
        ("app_tpu_moe_assignments_absent_total", {}),
        ("app_tpu_moe_experts_hit_total", {}),
        ("app_tpu_moe_layer_steps_total", {}),
    )


def token_params(cfg: Cohere2MoeConfig) -> dict:
    """Parameters by how a token meets them, for metrics/perf.CostModel: what
    every token multiplies (attention, shared experts, router, the head's
    slice), one routed expert's worth a layer, and the routing's shape."""
    e, m, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    attention = 2 * e * cfg.num_heads * d + 2 * e * cfg.num_kv_heads * d
    always = cfg.num_layers * (attention + 3 * e * m * cfg.num_shared_experts + e * cfg.num_experts)
    return {"always": always + cfg.vocab_size * e, "expert": 3 * e * m, "layers": cfg.num_layers,
            "held": cfg.experts_held, "router_width": cfg.num_experts, "k": cfg.experts_per_token}


# -- block ---------------------------------------------------------------------


def _layer_kind(cfg: Cohere2MoeConfig, layer):
    """(window, rotary factor) of the layer at this scanned index."""
    full = layer % cfg.layer_switch == cfg.layer_switch - 1
    return (jnp.where(full, NO_WINDOW, cfg.sliding_window).astype(jnp.int32),
            jnp.where(full, 0.0, 1.0).astype(jnp.float32))


@scoped("embed")
def _embed(cfg: Cohere2MoeConfig, params: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    return params["embed"][tokens].astype(cfg.dtype)


@scoped("qkv_rope")
def _norm_qkv(cfg: Cohere2MoeConfig, lp: dict, x: jnp.ndarray, positions, factor):
    """x [B,S,E] → (n [B,S,E] float32 — the router reads it before its cast —
    and q [B,S,Hq,D], k, v [B,S,Hkv,D], q and k rotated)."""
    n32 = layer_norm(x.astype(jnp.float32), lp["norm"], None, cfg.norm_eps)
    n = n32.astype(cfg.dtype)
    inv_freq = rope_inv_freq(cfg.head_dim, cfg.rope_theta)
    q, k, v = qkv_heads(n, lp, cfg.head_dim)
    return (n32, apply_rope_interleaved(q, positions, inv_freq, factor),
            apply_rope_interleaved(k, positions, inv_freq, factor), v)


def _attend_prompt(q, k, v, **kw):
    """``mha_attention`` one KV head at a time: 128 query heads make the score
    tensor of a 4 x 1,024 prefill 2.1 GB in float32; a KV head's group is an
    eighth of it, and the heads' results do not meet before ``wo``."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    qh = q.reshape(b, s, hkv, hq // hkv, d).transpose(2, 0, 1, 3, 4)
    kh, vh = k.transpose(2, 0, 1, 3)[..., None, :], v.transpose(2, 0, 1, 3)[..., None, :]
    out = lax.map(lambda a: mha_attention(a[0], a[1], a[2], **kw), (qh, kh, vh))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, s, hq, d)


@scoped("o_proj")
def _o_proj(lp: dict, attn: jnp.ndarray) -> jnp.ndarray:
    return attn.reshape(*attn.shape[:-2], -1) @ lp["wo"]


@scoped("mlp")
def _experts(cfg: Cohere2MoeConfig, params: dict, lp: dict, layer, n: jnp.ndarray, token_mask=None):
    """n [..., E] float32 → (routed part of the held experts + the shared
    experts' mean in the model's dtype, the routing counts). The products
    read n cast to the model's dtype; the router reads it as it is."""
    shape = n.shape
    n32 = n.reshape(-1, shape[-1])
    flat = n32.astype(cfg.dtype)
    ex = params["experts"]
    routed, counts = moe_ffn_held(
        flat, lp["router"], ex["w_gate"], ex["w_up"], ex["w_down"], layer=layer, router_x=n32,
        k=cfg.experts_per_token, first_expert=cfg.first_expert,
        token_mask=None if token_mask is None else token_mask.reshape(-1))
    with scope("moe_shared"):
        h = jax.nn.silu(flat @ lp["ws_gate"]) * (flat @ lp["ws_up"])
        shared = (h @ lp["ws_down"]) * (1.0 / cfg.num_shared_experts)
    return (routed + shared).reshape(shape), counts


@scoped("lm_head")
def _lm_head(cfg: Cohere2MoeConfig, params: dict, x: jnp.ndarray, last=None) -> jnp.ndarray:
    x = layer_norm(x, params["final_norm"], None, cfg.norm_eps)
    if last is not None:
        x = x[last]
    return (x @ params["embed"].T).astype(jnp.float32) * cfg.logit_scale


def _no_counts(cfg: Cohere2MoeConfig) -> jnp.ndarray:
    return jnp.zeros((cfg.experts_held + 3,), jnp.int32)


# -- entry points --------------------------------------------------------------


@partial(jax.jit, static_argnums=0)
def forward(cfg: Cohere2MoeConfig, params: dict, tokens: jnp.ndarray,
            lengths: jnp.ndarray | None = None) -> jnp.ndarray:
    """Full causal forward, no cache: tokens [B,S] → logits [B,S,V] (f32).
    ``lengths`` masks padded positions out of attention and out of routing."""
    x = _embed(cfg, params, tokens)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    live = None if lengths is None else jnp.arange(s)[None] < lengths[:, None]

    def body(x, xs):
        lp, layer = xs
        window, factor = _layer_kind(cfg, layer)
        n, q, k, v = _norm_qkv(cfg, lp, x, positions, factor)
        a = _attend_prompt(q, k, v, causal=True, kv_lengths=lengths, window=window)
        ff, _ = _experts(cfg, params, lp, layer, n, live)
        return x + _o_proj(lp, a) + ff, None

    x, _ = lax.scan(body, x, (params["blocks"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    return _lm_head(cfg, params, x)


def make_paged_cache(cfg: Cohere2MoeConfig, pages: int, page_size: int = 128,
                     sharding=None) -> PagedKVCache:
    """One pool for both kinds of layer: a window layer keeps every position's
    pages for a sequence's whole life (releasing those behind the window is
    the allocator's, ROADMAP M2)."""
    return PagedKVCache.create(
        cfg.num_layers, pages, page_size, cfg.num_kv_heads, cfg.head_dim,
        dtype=cfg.dtype, sharding=sharding,
    )


@partial(jax.jit, static_argnums=0, donate_argnums=4)
def prefill_paged(
    cfg: Cohere2MoeConfig, params: dict, tokens: jnp.ndarray, lengths: jnp.ndarray,
    cache: PagedKVCache, pages: jnp.ndarray, offsets: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, PagedKVCache, jnp.ndarray]:
    """``llama.prefill_paged``'s contract (prompts or prompt CHUNKS through
    per-row block tables; chunked rows attend to the gathered view) →
    (last-chunk-token logits [B,V] f32, cache, routing counts)."""
    x = _embed(cfg, params, tokens)
    b, s = tokens.shape
    off = jnp.zeros((b,), jnp.int32) if offsets is None else offsets
    positions = off[:, None] + jnp.arange(s)[None]
    total = off + lengths
    live = jnp.arange(s)[None] < lengths[:, None]

    def layer_fn(lp, layer, carry, cache):
        x, counts = carry
        window, factor = _layer_kind(cfg, layer)
        n, q, k, v = _norm_qkv(cfg, lp, x, positions, factor)
        cache = _write_paged(cache, layer, pages, k, v, offsets)
        if offsets is not None:
            k_view, v_view = _paged_views(cfg, cache, layer, pages)
            a = _attend_prompt(q, k_view.swapaxes(1, 2), v_view.swapaxes(1, 2), causal=True,
                               q_offset=off, kv_lengths=total, window=window)
        else:
            a = _attend_prompt(q, k, v, causal=True, kv_lengths=lengths, window=window)
        ff, c = _experts(cfg, params, lp, layer, n, live)
        return (x + _o_proj(lp, a) + ff, counts + c), cache

    (x, counts), cache = _scan_paged_layers(params, (x, _no_counts(cfg)), cache, layer_fn)
    return _lm_head(cfg, params, x, last=(jnp.arange(b), lengths - 1)), cache, counts


@partial(jax.jit, static_argnums=0, donate_argnums=4)
def decode_step_paged(
    cfg: Cohere2MoeConfig, params: dict, tokens: jnp.ndarray, positions: jnp.ndarray,
    cache: PagedKVCache, table: jnp.ndarray,
) -> tuple[jnp.ndarray, PagedKVCache, jnp.ndarray]:
    """``llama.decode_step_paged``'s contract → (logits [N,V] f32, cache,
    routing counts). A lane with no page (the engine masks idle lanes by an
    all-OOB table row) is routed nowhere and counted nowhere."""
    x = _embed(cfg, params, tokens)
    pos1 = positions[:, None]
    live = table[:, 0] < cache.k.shape[1]

    def layer_fn(lp, layer, carry, cache):
        x, counts = carry
        window, factor = _layer_kind(cfg, layer)
        n, q, k, v = _norm_qkv(cfg, lp, x[:, None], pos1, factor)
        cache, a = _append_attend_paged(cache, layer, table, positions,
                                        q[:, 0], k[:, 0], v[:, 0], window=window)
        ff, c = _experts(cfg, params, lp, layer, n[:, 0], live)
        return (x + _o_proj(lp, a) + ff, counts + c), cache

    (x, counts), cache = _scan_paged_layers(params, (x, _no_counts(cfg)), cache, layer_fn)
    return _lm_head(cfg, params, x), cache, counts
