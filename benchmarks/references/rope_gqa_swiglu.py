"""Plain reference of the dense decoder block both first configurations use
(InternLM2, Mistral): RMSNorm → rotary grouped-query attention → residual →
RMSNorm → SwiGLU → residual, untied head, no biases. Straightforward
``jax.numpy`` in float32 at the highest matmul precision, no kernels, no cache,
no batching tricks — written from the published description (HF
``modeling_mistral`` / ``modeling_internlm2``: half-split rotary, scores scaled
by 1/sqrt(head_dim), softmax in float32), not from ``models/llama.py``.

Departures: the weights are the served ones (bf16, seeded), upcast layer by
layer, because a float32 copy of the whole model does not fit beside the
engine; InternLM2's fused ``wqkv`` is taken as its three parts (same
mathematics, different storage)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta):
    """x [S, H, D], positions 0..S-1, half-split convention."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d // 2, dtype=F32) / (d // 2)))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mm(a, w):
    return jnp.matmul(a, w.astype(F32), precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _layer(hq, hkv, d, theta, eps, x, blocks, i):
    """One block on x [S, E]; ``blocks`` holds every layer's weights stacked
    on a leading axis and ``i`` (traced: one program for all layers) picks."""
    lp = jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(w, i, keepdims=False), blocks)
    s = x.shape[0]
    h = _rms(x, lp["attn_norm"], eps)
    q = _rope(_mm(h, lp["wq"]).reshape(s, hq, d), theta)
    k = _rope(_mm(h, lp["wk"]).reshape(s, hkv, d), theta)
    v = _mm(h, lp["wv"]).reshape(s, hkv, d)
    k, v = jnp.repeat(k, hq // hkv, axis=1), jnp.repeat(v, hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / jnp.sqrt(F32(d))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v, precision=HIGHEST)
    x = x + _mm(a.reshape(s, hq * d), lp["wo"])
    h = _rms(x, lp["mlp_norm"], eps)
    return x + _mm(jax.nn.silu(_mm(h, lp["w_gate"])) * _mm(h, lp["w_up"]), lp["w_down"])


@functools.partial(jax.jit, static_argnums=(0,))
def _head(eps, x_last, final_norm, lm_head):
    return _mm(_rms(x_last, final_norm, eps), lm_head)


def last_logits(spec: dict, params: dict, tokens: list[int], pad_to: int):
    """Logits [V] (float32) at the last position of ``tokens``. The sequence
    is padded on the right to ``pad_to`` so every call has one shape; causal
    attention keeps the padding out of every real position."""
    hq, hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    d = spec.get("head_dim") or spec["hidden_size"] // hq
    theta, eps, n = float(spec["rope_theta"]), float(spec["rms_norm_eps"]), len(tokens)
    ids = jnp.asarray(list(tokens) + [0] * (pad_to - n), jnp.int32)
    x = params["embed"][ids].astype(F32)
    for i in range(spec["num_hidden_layers"]):
        x = _layer(hq, hkv, d, theta, eps, x, params["blocks"], jnp.int32(i))
    head = params["embed"].T if spec.get("tie_word_embeddings") else params["lm_head"]
    return _head(eps, x[n - 1], params["final_norm"], head)
