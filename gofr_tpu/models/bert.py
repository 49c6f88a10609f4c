"""BERT encoder for embedding serving (BASELINE.json configs[1]).

Post-LayerNorm transformer encoder matching HF ``BertModel`` numerics
(oracle test in tests/test_models.py). Functional, stacked layers, scanned.
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
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 512
    type_vocab_size: int = 2
    norm_eps: float = 1e-12
    dtype: Any = jnp.float32

    @property
    def head_size(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def base(cls, **kw) -> "BertConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "BertConfig":
        return cls(**{**dict(
            vocab_size=256, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=2, max_seq_len=64,
        ), **kw})


def init(cfg: BertConfig, key: jax.Array) -> dict:
    e, m, nl = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    ks = jax.random.split(key, 12)
    dt = cfg.dtype
    return {
        "word_embed": truncated_normal(ks[0], (cfg.vocab_size, e), 0.02, dt),
        "pos_embed": truncated_normal(ks[1], (cfg.max_seq_len, e), 0.02, dt),
        "type_embed": truncated_normal(ks[2], (cfg.type_vocab_size, e), 0.02, dt),
        "embed_norm_w": jnp.ones((e,), dt),
        "embed_norm_b": jnp.zeros((e,), dt),
        "blocks": {
            "wq": fan_in_init(ks[3], (nl, e, e), fan_in=e, dtype=dt),
            "bq": jnp.zeros((nl, e), dt),
            "wk": fan_in_init(ks[4], (nl, e, e), fan_in=e, dtype=dt),
            "bk": jnp.zeros((nl, e), dt),
            "wv": fan_in_init(ks[5], (nl, e, e), fan_in=e, dtype=dt),
            "bv": jnp.zeros((nl, e), dt),
            "wo": fan_in_init(ks[6], (nl, e, e), fan_in=e, dtype=dt),
            "bo": jnp.zeros((nl, e), dt),
            "attn_norm_w": jnp.ones((nl, e), dt),
            "attn_norm_b": jnp.zeros((nl, e), dt),
            "w_inter": fan_in_init(ks[7], (nl, e, m), fan_in=e, dtype=dt),
            "b_inter": jnp.zeros((nl, m), dt),
            "w_out": fan_in_init(ks[8], (nl, m, e), fan_in=m, dtype=dt),
            "b_out": jnp.zeros((nl, e), dt),
            "mlp_norm_w": jnp.ones((nl, e), dt),
            "mlp_norm_b": jnp.zeros((nl, e), dt),
        },
        "pooler_w": fan_in_init(ks[9], (e, e), fan_in=e, dtype=dt),
        "pooler_b": jnp.zeros((e,), dt),
    }


def param_axes(cfg: BertConfig) -> dict:
    e2 = ("layers", "embed", "heads")
    vec = ("layers", None)
    axes = {
        "word_embed": ("vocab", "embed"),
        "pos_embed": (None, "embed"),
        "type_embed": (None, "embed"),
        "embed_norm_w": (None,),
        "embed_norm_b": (None,),
        "blocks": {
            "wq": e2, "bq": ("layers", "heads"),
            "wk": e2, "bk": ("layers", "heads"),
            "wv": e2, "bv": ("layers", "heads"),
            "wo": ("layers", "heads", "embed"), "bo": vec,
            "attn_norm_w": vec, "attn_norm_b": vec,
            "w_inter": ("layers", "embed", "mlp"), "b_inter": ("layers", "mlp"),
            "w_out": ("layers", "mlp", "embed"), "b_out": vec,
            "mlp_norm_w": vec, "mlp_norm_b": vec,
        },
        "pooler_w": ("embed", None),
        "pooler_b": (None,),
    }
    return axes


@partial(jax.jit, static_argnums=0)
def encode(cfg: BertConfig, params: dict, tokens: jnp.ndarray,
           lengths: jnp.ndarray | None = None,
           token_types: jnp.ndarray | None = None) -> jnp.ndarray:
    """tokens [B,S] → hidden states [B,S,E]."""
    b, s = tokens.shape
    if token_types is None:
        token_types = jnp.zeros_like(tokens)
    x = (
        params["word_embed"][tokens]
        + params["pos_embed"][jnp.arange(s)][None]
        + params["type_embed"][token_types]
    ).astype(cfg.dtype)
    x = layer_norm(x, params["embed_norm_w"], params["embed_norm_b"], cfg.norm_eps)

    def body(x, lp):
        q = (x @ lp["wq"] + lp["bq"]).reshape(b, s, cfg.num_heads, cfg.head_size)
        k = (x @ lp["wk"] + lp["bk"]).reshape(b, s, cfg.num_heads, cfg.head_size)
        v = (x @ lp["wv"] + lp["bv"]).reshape(b, s, cfg.num_heads, cfg.head_size)
        attn = mha_attention(q, k, v, causal=False, kv_lengths=lengths).reshape(b, s, -1)
        x = layer_norm(x + attn @ lp["wo"] + lp["bo"], lp["attn_norm_w"], lp["attn_norm_b"], cfg.norm_eps)
        inter = jax.nn.gelu(x @ lp["w_inter"] + lp["b_inter"], approximate=False)
        x = layer_norm(x + inter @ lp["w_out"] + lp["b_out"], lp["mlp_norm_w"], lp["mlp_norm_b"], cfg.norm_eps)
        return x, None

    x, _ = lax.scan(body, x, params["blocks"])
    return x


@partial(jax.jit, static_argnums=0)
def embed_pooled(cfg: BertConfig, params: dict, tokens: jnp.ndarray,
                 lengths: jnp.ndarray) -> jnp.ndarray:
    """Mean-pooled, L2-normalized sentence embeddings [B,E] (f32) — the
    serving payload of the embedding endpoint."""
    hidden = encode(cfg, params, tokens, lengths).astype(jnp.float32)
    mask = (jnp.arange(tokens.shape[1])[None] < lengths[:, None]).astype(jnp.float32)
    summed = jnp.einsum("bse,bs->be", hidden, mask)
    pooled = summed / jnp.maximum(lengths[:, None].astype(jnp.float32), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
