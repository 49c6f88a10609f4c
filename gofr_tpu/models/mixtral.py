"""Mixtral-family sparse-MoE decoder LM.

Llama attention blocks (RMSNorm / RoPE / GQA — shared via gofr_tpu.ops)
with the dense SwiGLU MLP swapped for a top-k routed mixture of experts
(gofr_tpu.ops.moe). Expert weights carry the "expert" logical axis so a
mesh with an ``ep`` axis runs expert-parallel via GSPMD all-to-alls; tp
still shards the per-expert mlp dim, so EP×TP composes.

Same three entry points as llama (forward / prefill / decode_step) and the
same SlotKVCache, so the continuous-batching engine serves it unchanged —
the reference's "swap datasource behind the container" ergonomics applied
to model families (SURVEY.md §2.4 plugin pattern).
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
from gofr_tpu.ops.attention import decode_attention
from gofr_tpu.ops.kvcache import SlotKVCache, append_tokens, write_prompts
from gofr_tpu.ops.moe import moe_ffn


@dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    num_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    head_dim: int | None = None
    rope_theta: float = 1000000.0
    max_seq_len: int = 8192
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def head_size(self) -> int:
        return self.head_dim if self.head_dim is not None else self.hidden_size // self.num_heads

    @classmethod
    def mixtral_8x7b(cls, **kw) -> "MixtralConfig":
        return cls(**{**dict(
            vocab_size=32000, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8, num_experts=8,
            experts_per_token=2,
        ), **kw})

    @classmethod
    def tiny(cls, **kw) -> "MixtralConfig":
        """Test-sized config for the CPU mesh."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_layers=2, num_heads=4, num_kv_heads=2, num_experts=4,
            experts_per_token=2, max_seq_len=128, rope_theta=10000.0,
            dtype=jnp.float32,
        ), **kw})


def init(cfg: MixtralConfig, key: jax.Array) -> dict:
    e, m, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    hq, hkv, d, nl, ne = cfg.num_heads, cfg.num_kv_heads, cfg.head_size, cfg.num_layers, cfg.num_experts
    keys = jax.random.split(key, 10)
    dt = cfg.dtype
    return {
        "embed": truncated_normal(keys[0], (v, e), 0.02, dt),
        "blocks": {
            "attn_norm": jnp.ones((nl, e), dt),
            "wq": fan_in_init(keys[1], (nl, e, hq * d), fan_in=e, dtype=dt),
            "wk": fan_in_init(keys[2], (nl, e, hkv * d), fan_in=e, dtype=dt),
            "wv": fan_in_init(keys[3], (nl, e, hkv * d), fan_in=e, dtype=dt),
            "wo": fan_in_init(keys[4], (nl, hq * d, e), fan_in=hq * d, dtype=dt),
            "mlp_norm": jnp.ones((nl, e), dt),
            "router": fan_in_init(keys[5], (nl, e, ne), fan_in=e, dtype=jnp.float32),
            "w_gate": fan_in_init(keys[6], (nl, ne, e, m), fan_in=e, dtype=dt),
            "w_up": fan_in_init(keys[7], (nl, ne, e, m), fan_in=e, dtype=dt),
            "w_down": fan_in_init(keys[8], (nl, ne, m, e), fan_in=m, dtype=dt),
        },
        "final_norm": jnp.ones((e,), dt),
        "lm_head": truncated_normal(keys[9], (e, v), 0.02, dt),
    }


def param_axes(cfg: MixtralConfig) -> dict:
    return {
        "embed": ("vocab", "embed"),
        "blocks": {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", None),
            "router": ("layers", "embed", None),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        },
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }


def _rope(cfg: MixtralConfig):
    return rope_table(cfg.max_seq_len, cfg.head_size, theta=cfg.rope_theta)


def _qkv(cfg: MixtralConfig, lp: dict, x: jnp.ndarray):
    return qkv_heads(rms_norm(x, lp["attn_norm"], cfg.norm_eps), lp, cfg.head_size)


def _moe(cfg: MixtralConfig, lp: dict, x: jnp.ndarray,
         lengths: jnp.ndarray | None = None,
         capacity: int | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x [B, S, E] → (moe output, aux loss). ``lengths`` masks padded
    positions out of routing so they never steal expert capacity."""
    b, s, e = x.shape
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    token_mask = None
    if lengths is not None:
        token_mask = (jnp.arange(s)[None, :] < lengths[:, None]).reshape(b * s)
    y, aux = moe_ffn(
        h.reshape(b * s, e),
        lp["router"],
        lp["w_gate"],
        lp["w_up"],
        lp["w_down"],
        k=cfg.experts_per_token,
        capacity_factor=cfg.capacity_factor,
        capacity=capacity,
        token_mask=token_mask,
    )
    return y.reshape(b, s, e), aux


@partial(jax.jit, static_argnums=(0, 4))
def forward_with_aux(cfg: MixtralConfig, params: dict, tokens: jnp.ndarray,
                     lengths: jnp.ndarray | None = None,
                     attn_fn: Any = None) -> tuple[jnp.ndarray, dict]:
    """Full causal forward → (logits [B,S,V] f32, {"load_balance": aux})."""
    attn = attn_fn or mha_attention
    cos, sin = _rope(cfg)
    x = params["embed"][tokens].astype(cfg.dtype)
    b, s = tokens.shape
    positions = jnp.arange(s)[None]

    def body(carry, lp):
        x, aux_sum = carry
        q, k, v = _qkv(cfg, lp, x)
        q = apply_rope(q, positions, cos, sin)
        k = apply_rope(k, positions, cos, sin)
        a = attn(q, k, v, causal=True, kv_lengths=lengths)
        x = x + a.reshape(b, s, -1) @ lp["wo"]
        y, aux = _moe(cfg, lp, x, lengths)
        return (x + y, aux_sum + aux), None

    (x, aux_sum), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, {"load_balance": aux_sum / cfg.num_layers}


def forward(cfg: MixtralConfig, params: dict, tokens: jnp.ndarray,
            lengths: jnp.ndarray | None = None, attn_fn: Any = None) -> jnp.ndarray:
    return forward_with_aux(cfg, params, tokens, lengths, attn_fn)[0]


@partial(jax.jit, static_argnums=0, donate_argnums=4)
def prefill(cfg: MixtralConfig, params: dict, tokens: jnp.ndarray, lengths: jnp.ndarray,
            cache: SlotKVCache, slots: jnp.ndarray) -> tuple[jnp.ndarray, SlotKVCache]:
    """Same contract as llama.prefill (llama.py docstring)."""
    cos, sin = _rope(cfg)
    x = params["embed"][tokens].astype(cfg.dtype)
    b, s = tokens.shape
    positions = jnp.arange(s)[None]
    row = jnp.arange(b)

    def body(x, xs):
        lp, k_layer, v_layer = xs
        q, k, v = _qkv(cfg, lp, x)
        q = apply_rope(q, positions, cos, sin)
        k = apply_rope(k, positions, cos, sin)
        k_layer, v_layer = write_prompts(k_layer, v_layer, slots, k, v)
        a = mha_attention(q, k, v, causal=True, kv_lengths=lengths)
        x = x + a.reshape(b, s, -1) @ lp["wo"]
        y, _ = _moe(cfg, lp, x, lengths)
        return x + y, (k_layer, v_layer)

    x, (new_k, new_v) = lax.scan(body, x, (params["blocks"], cache.k, cache.v))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = x[row, lengths - 1]
    logits = (last @ params["lm_head"]).astype(jnp.float32)
    return logits, SlotKVCache(k=new_k, v=new_v)


@partial(jax.jit, static_argnums=0, donate_argnums=4)
def decode_step(cfg: MixtralConfig, params: dict, tokens: jnp.ndarray, positions: jnp.ndarray,
                cache: SlotKVCache) -> tuple[jnp.ndarray, SlotKVCache]:
    """Same contract as llama.decode_step (llama.py docstring)."""
    cos, sin = _rope(cfg)
    x = params["embed"][tokens].astype(cfg.dtype)
    n = tokens.shape[0]
    pos1 = positions[:, None]

    def body(x, xs):
        lp, k_layer, v_layer = xs
        q, k, v = _qkv(cfg, lp, x[:, None])
        q = apply_rope(q, pos1, cos, sin)[:, 0]
        k = apply_rope(k, pos1, cos, sin)[:, 0]
        v = v[:, 0]
        k_layer, v_layer = append_tokens(k_layer, v_layer, positions, k, v)
        a = decode_attention(q, k_layer, v_layer, positions + 1)
        x = x + a.reshape(n, -1) @ lp["wo"]
        # capacity == n: a skewed slot batch can never drop a live token
        y, _ = _moe(cfg, lp, x[:, None], capacity=n)
        return x + y[:, 0], (k_layer, v_layer)

    x, (new_k, new_v) = lax.scan(body, x, (params["blocks"], cache.k, cache.v))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, SlotKVCache(k=new_k, v=new_v)


def make_cache(cfg: MixtralConfig, slots: int, max_len: int | None = None) -> SlotKVCache:
    return SlotKVCache.create(
        cfg.num_layers, slots, max_len or cfg.max_seq_len, cfg.num_kv_heads,
        cfg.head_size, dtype=cfg.dtype,
    )
