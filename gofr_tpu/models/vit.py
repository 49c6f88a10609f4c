"""Vision Transformer classifier (BASELINE.json configs[3]: pubsub → ViT).

Pre-LayerNorm encoder matching HF ``ViTModel``/``ViTForImageClassification``
numerics. Patch embedding is an unfold + matmul (not a conv): identical
math, and a single large [B*N, P²C] × [P²C, E] matmul maps straight onto
the MXU.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.models.base import fan_in_init, truncated_normal
from gofr_tpu.ops import layer_norm, mha_attention


@dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_channels: int = 3
    hidden_size: int = 1024
    intermediate_size: int = 4096
    num_layers: int = 24
    num_heads: int = 16
    num_classes: int = 1000
    norm_eps: float = 1e-12
    dtype: Any = jnp.float32

    @property
    def head_size(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @classmethod
    def large(cls, **kw) -> "ViTConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "ViTConfig":
        return cls(**{**dict(
            image_size=32, patch_size=8, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=2, num_classes=10,
        ), **kw})


def init(cfg: ViTConfig, key: jax.Array) -> dict:
    e, m, nl = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    patch_dim = cfg.patch_size * cfg.patch_size * cfg.num_channels
    ks = jax.random.split(key, 12)
    dt = cfg.dtype
    params = {
        "cls_token": jnp.zeros((1, e), dt),
        "pos_embed": truncated_normal(ks[0], (cfg.num_patches + 1, e), 0.02, dt),
        "patch_w": fan_in_init(ks[1], (patch_dim, e), fan_in=patch_dim, dtype=dt),
        "patch_b": jnp.zeros((e,), dt),
        "blocks": {
            "norm1_w": jnp.ones((nl, e), dt), "norm1_b": jnp.zeros((nl, e), dt),
            "wq": fan_in_init(ks[2], (nl, e, e), fan_in=e, dtype=dt), "bq": jnp.zeros((nl, e), dt),
            "wk": fan_in_init(ks[3], (nl, e, e), fan_in=e, dtype=dt), "bk": jnp.zeros((nl, e), dt),
            "wv": fan_in_init(ks[4], (nl, e, e), fan_in=e, dtype=dt), "bv": jnp.zeros((nl, e), dt),
            "wo": fan_in_init(ks[5], (nl, e, e), fan_in=e, dtype=dt), "bo": jnp.zeros((nl, e), dt),
            "norm2_w": jnp.ones((nl, e), dt), "norm2_b": jnp.zeros((nl, e), dt),
            "w_inter": fan_in_init(ks[6], (nl, e, m), fan_in=e, dtype=dt), "b_inter": jnp.zeros((nl, m), dt),
            "w_out": fan_in_init(ks[7], (nl, m, e), fan_in=m, dtype=dt), "b_out": jnp.zeros((nl, e), dt),
        },
        "final_norm_w": jnp.ones((e,), dt),
        "final_norm_b": jnp.zeros((e,), dt),
    }
    if cfg.num_classes:
        params["head_w"] = fan_in_init(ks[8], (e, cfg.num_classes), fan_in=e, dtype=dt)
        params["head_b"] = jnp.zeros((cfg.num_classes,), dt)
    return params


def param_axes(cfg: ViTConfig) -> dict:
    vec = ("layers", None)
    axes = {
        "cls_token": (None, "embed"),
        "pos_embed": (None, "embed"),
        "patch_w": (None, "embed"),
        "patch_b": ("embed",),
        "blocks": {
            "norm1_w": vec, "norm1_b": vec,
            "wq": ("layers", "embed", "heads"), "bq": ("layers", "heads"),
            "wk": ("layers", "embed", "heads"), "bk": ("layers", "heads"),
            "wv": ("layers", "embed", "heads"), "bv": ("layers", "heads"),
            "wo": ("layers", "heads", "embed"), "bo": vec,
            "norm2_w": vec, "norm2_b": vec,
            "w_inter": ("layers", "embed", "mlp"), "b_inter": ("layers", "mlp"),
            "w_out": ("layers", "mlp", "embed"), "b_out": vec,
        },
        "final_norm_w": (None,),
        "final_norm_b": (None,),
    }
    if cfg.num_classes:
        axes["head_w"] = ("embed", "vocab")
        axes["head_b"] = ("vocab",)
    return axes


def patchify(cfg: ViTConfig, images: jnp.ndarray) -> jnp.ndarray:
    """images [B,H,W,C] → patches [B, N, P*P*C] (row-major within patch,
    matching the transposed HF conv kernel in convert.vit_from_hf)."""
    b, h, w, c = images.shape
    p = cfg.patch_size
    x = images.reshape(b, h // p, p, w // p, p, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # [B, H/P, W/P, P, P, C]
    return x.reshape(b, (h // p) * (w // p), p * p * c)


@partial(jax.jit, static_argnums=0)
def forward(cfg: ViTConfig, params: dict, images: jnp.ndarray) -> jnp.ndarray:
    """images [B,H,W,C] → logits [B,num_classes] (or CLS embedding [B,E]
    when the config has no head)."""
    b = images.shape[0]
    patches = patchify(cfg, images).astype(cfg.dtype)
    x = patches @ params["patch_w"] + params["patch_b"]  # [B,N,E]
    cls = jnp.broadcast_to(params["cls_token"][None], (b, 1, cfg.hidden_size)).astype(cfg.dtype)
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"][None]
    s = x.shape[1]

    def body(x, lp):
        h = layer_norm(x, lp["norm1_w"], lp["norm1_b"], cfg.norm_eps)
        q = (h @ lp["wq"] + lp["bq"]).reshape(b, s, cfg.num_heads, cfg.head_size)
        k = (h @ lp["wk"] + lp["bk"]).reshape(b, s, cfg.num_heads, cfg.head_size)
        v = (h @ lp["wv"] + lp["bv"]).reshape(b, s, cfg.num_heads, cfg.head_size)
        attn = mha_attention(q, k, v, causal=False).reshape(b, s, -1)
        x = x + attn @ lp["wo"] + lp["bo"]
        h2 = layer_norm(x, lp["norm2_w"], lp["norm2_b"], cfg.norm_eps)
        inter = jax.nn.gelu(h2 @ lp["w_inter"] + lp["b_inter"], approximate=False)
        x = x + inter @ lp["w_out"] + lp["b_out"]
        return x, None

    x, _ = lax.scan(body, x, params["blocks"])
    x = layer_norm(x, params["final_norm_w"], params["final_norm_b"], cfg.norm_eps)
    cls_out = x[:, 0].astype(jnp.float32)
    if cfg.num_classes:
        return cls_out @ params["head_w"].astype(jnp.float32) + params["head_b"].astype(jnp.float32)
    return cls_out
