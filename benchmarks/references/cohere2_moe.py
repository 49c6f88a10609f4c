"""Plain reference of the Cohere2-MoE block (``model_type`` ``cohere2_moe``,
Command A+), written from the published configuration's keys and the HF
``cohere2`` conventions, not from the served module:

    n      = LayerNorm(x)            mean-subtracted, weight only, eps layer_norm_eps; ONE norm a layer
    q,k,v  = n Wq, n Wk, n Wv        no bias, no qk-norm
    layer l is sliding unless l % layer_switch == layer_switch - 1 (local first):
      sliding: q, k rotated in INTERLEAVED pairs (x0,x1),(x2,x3),... (rope_gptj), theta rope_theta;
               key j visible to query i iff i - sliding_window < j <= i
      full:    no positional embedding; key j visible iff j <= i
    attn   = softmax(q k^T / sqrt(head_dim) + mask) v Wo
    s      = sigmoid(n Wr)           float32, over the router's whole width
    I      = top-k of s ;  g_e = s_e / sum_{j in I} s_j     (norm_topk_prob)
    routed = sum_{e in I, e held} g_e Wd_e(silu(Wg_e n) * Wu_e n)
    shared = mean_s Wd_s(silu(Wg_s n) * Wu_s n)             (combination "average")
    y      = x + attn + routed + shared                     (parallel block: both read n)
    logits = LayerNorm(y_L) E^T * logit_scale               (tied embeddings)

Straightforward ``jax.numpy``, float32, every product at the highest matmul
precision, no kernels, no cache, no batching.

Departures, each for memory: the weights are the served ones (bf16, seeded),
upcast ONE MATRIX at a time (a float32 copy of one layer is 4.6 GB and does
not fit beside the engine), so each expert is its own call; attention is
computed ``HEAD_BLOCK`` query heads at a time (128 heads x 5,000 x 5,000
float32 scores are 12.8 GB). The reference is given the same SHARE as the
program: the experts ``first_expert .. first_expert + num_experts`` of a
router ``router_num_experts`` wide (what the absent experts would have added
is left out, the gates are normalised over all k chosen), and the vocabulary
slice the embedding holds. The four shared experts are stored side by side
in one matrix; they are computed one by one here and averaged.

One departure is about what a float32 reference can say of a bf16 program, and
only ``last_logits`` (what the benchmark's ``correct`` reads) makes it. Top-k
routing is a step function: where a token's k-th and (k+1)-th router scores
lie within the rounding noise of the served precision, a faithful bf16 program
may take the other expert, and if either of the two is held here the token's
logits move by that expert's whole g_e FFN_e(n) — 3-7% of their scale, enough
to change the top token, where every other rounding moves them by under 1%
(measured on the chip and, alike, at a tiny size on the CPU: PERF.md §6,
PR 34). No tolerance on logits can tell such a choice from a fault. So where
the routing of the COMPARED position (the last one) is such a near-tie
(``_route_margin`` under ``NEAR_TIE_MARGIN``) at up to ``MAX_NEAR_TIES``
layers, ``last_logits`` also computes the pass with the two experts exchanged
there (every combination), and returns for each token its logit under the
routing that favours it most, each pass measured from its own best token: a
served token then passes if it is the best token (or a near-tie of it) under
SOME routing the precision cannot tell apart, and fails otherwise as before.
Positions with a clear routing — about three in four — are one plain pass."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
HEAD_BLOCK = 4
# In sigmoid score. The bf16 program's own noise in a router score is about
# 0.001 (one standard deviation; the scores' spacing at the k-th rank is about
# 0.01): the largest margin at which it was seen to choose the other way is in
# PERF.md §6 (PR 34), and a program one precision lower (8-bit floats, 3 bits of
# mantissa against 7) would do so up to 16 times further out — and fail the
# comparison on its ordinary rows anyway.
NEAR_TIE_MARGIN = 0.004
MAX_NEAR_TIES = 3


def _mm(a, w):
    return jnp.matmul(a, w.astype(F32), precision=HIGHEST)


def _layer_norm(x, w, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w.astype(F32)


def _rope_pairs(x, theta):
    """x [S, H, D] at positions 0..S-1; pair i is (x[2i], x[2i+1])."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d // 2, dtype=F32) / (d // 2)))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _pick(blocks, name, i):
    return jax.lax.dynamic_index_in_dim(blocks[name], i, keepdims=False)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _qkv(hq, hkv, d, theta, eps, x, blocks, i, sliding):
    """→ (n, q [S,Hq,D], k, v [S,Hkv,D]); q, k rotated where ``sliding``."""
    s = x.shape[0]
    n = _layer_norm(x, _pick(blocks, "norm", i), eps)
    q = _mm(n, _pick(blocks, "wq", i)).reshape(s, hq, d)
    k = _mm(n, _pick(blocks, "wk", i)).reshape(s, hkv, d)
    v = _mm(n, _pick(blocks, "wv", i)).reshape(s, hkv, d)
    q = jnp.where(sliding, _rope_pairs(q, theta), q)
    k = jnp.where(sliding, _rope_pairs(k, theta), k)
    return n, q, k, v


@functools.partial(jax.jit, static_argnums=(0,))
def _attend_block(group, q_block, k, v, first_head, window):
    """HEAD_BLOCK query heads [S, HB, D] against their KV heads; ``window`` is
    the look-back (S or more on a full layer)."""
    s, hb, d = q_block.shape
    kv_of = (first_head + jnp.arange(hb)) // group
    kb, vb = k[:, kv_of], v[:, kv_of]  # [S, HB, D]
    scores = jnp.einsum("qhd,khd->hqk", q_block, kb, precision=HIGHEST) / jnp.sqrt(F32(d))
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    scores = jnp.where(((j <= i) & (j > i - window))[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vb, precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=(0,))
def _route(k, n, blocks, i):
    s = jax.nn.sigmoid(_mm(n, _pick(blocks, "router", i)))
    top, idx = jax.lax.top_k(s, k)
    return top / jnp.sum(top, axis=-1, keepdims=True), idx


@functools.partial(jax.jit, static_argnums=(0,))
def _route_exchanged(k, n, blocks, i, position):
    """``_route`` with the k-th and the (k+1)-th choice of ONE position exchanged."""
    s = jax.nn.sigmoid(_mm(n, _pick(blocks, "router", i)))
    top, idx = jax.lax.top_k(s, k + 1)
    here = (jnp.arange(n.shape[0]) == position)[:, None] & (jnp.arange(k) == k - 1)[None, :]
    top = jnp.where(here, top[:, k:], top[:, :k])
    idx = jnp.where(here, idx[:, k:], idx[:, :k])
    return top / jnp.sum(top, axis=-1, keepdims=True), idx


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _route_margin(k, first, held, n, blocks, i):
    """How near each token's routing is to another outcome that would change
    THIS share: the gap between its k-th and (k+1)-th score where either of
    the two experts is held here, 1.0 where neither is. A program computing
    in bf16 may choose the other way inside a gap of a few bf16 units in the
    last place; the comparison reads its rows with that in mind."""
    s = jax.nn.sigmoid(_mm(n, _pick(blocks, "router", i)))
    top, idx = jax.lax.top_k(s, k + 1)
    edge = idx[:, k - 1:]
    here = jnp.any((edge >= first) & (edge < first + held), axis=-1)
    return jnp.where(here, top[:, k - 1] - top[:, k], 1.0)


@jax.jit
def _ffn(n, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(n, w_gate)) * _mm(n, w_up), w_down)


@jax.jit
def _expert(n, gates, idx, experts, i, held_index, expert_id):
    """One held routed expert's part: g_e FFN_e(n) on the tokens that chose it."""
    g = jnp.sum(jnp.where(idx == expert_id, gates, 0.0), axis=-1)  # [S]
    w = [jax.lax.dynamic_slice(experts[name], (i, held_index, 0, 0), (1, 1) + experts[name].shape[2:])[0, 0]
         for name in ("w_gate", "w_up", "w_down")]  # ONE matrix cut out of the stack
    return g[:, None] * _ffn(n, *w)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _shared_expert(m, which, n, blocks, i):
    cols = slice(which * m, (which + 1) * m)
    return _ffn(n, _pick(blocks, "ws_gate", i)[:, cols], _pick(blocks, "ws_up", i)[:, cols],
                _pick(blocks, "ws_down", i)[cols, :])


@jax.jit
def _out_proj(a, blocks, i):
    return _mm(a.reshape(a.shape[0], -1), _pick(blocks, "wo", i))


@functools.partial(jax.jit, static_argnums=(0,))
def _head(eps, x, final_norm, embed, logit_scale):
    return _mm(_layer_norm(x, final_norm, eps), embed.T) * logit_scale


def hidden_states(spec: dict, params: dict, tokens, margins: list | None = None,
                  exchange: tuple | None = None) -> jnp.ndarray:
    """The residual stream [S, E] after the last layer, tokens at positions
    0..S-1. A list given as ``margins`` receives each layer's
    ``_route_margin`` [S]. ``exchange`` = (position, layers): that position's
    k-th and (k+1)-th choice are exchanged at those layers (``last_logits``)."""
    hq, hkv, d = spec["num_attention_heads"], spec["num_key_value_heads"], spec["head_dim"]
    theta, eps = float(spec["rope_theta"]), float(spec["layer_norm_eps"])
    switch, m = spec["layer_switch"], spec["intermediate_size"]
    held, first = spec["num_experts"], spec.get("first_expert", 0)
    blocks = params["blocks"]
    x = params["embed"][jnp.asarray(list(tokens), jnp.int32)].astype(F32)
    s = x.shape[0]
    for layer in range(spec["num_hidden_layers"]):
        i = jnp.int32(layer)
        sliding = layer % switch != switch - 1
        n, q, k, v = _qkv(hq, hkv, d, theta, eps, x, blocks, i, jnp.bool_(sliding))
        window = jnp.int32(spec["sliding_window"] if sliding else s)
        a = jnp.concatenate([
            _attend_block(hq // hkv, q[:, h:h + HEAD_BLOCK], k, v, jnp.int32(h), window)
            for h in range(0, hq, HEAD_BLOCK)], axis=1)
        y = x + _out_proj(a, blocks, i)
        if exchange is not None and layer in exchange[1]:
            gates, idx = _route_exchanged(spec["num_experts_per_tok"], n, blocks, i, jnp.int32(exchange[0]))
        else:
            gates, idx = _route(spec["num_experts_per_tok"], n, blocks, i)
        if margins is not None:
            margins.append(_route_margin(spec["num_experts_per_tok"], first, held, n, blocks, i))
        for e in range(held):
            y = y + _expert(n, gates, idx, params["experts"], i, jnp.int32(e), jnp.int32(first + e))
        shared = sum(_shared_expert(m, sh, n, blocks, i) for sh in range(spec["num_shared_experts"]))
        x = y + shared / spec["num_shared_experts"]
    return x


def all_logits(spec: dict, params: dict, tokens, margins: list | None = None) -> jnp.ndarray:
    """Logits [S, V] (float32) at every position of ``tokens``."""
    x = hidden_states(spec, params, tokens, margins)
    return _head(float(spec["layer_norm_eps"]), x, params["final_norm"], params["embed"],
                 F32(spec.get("logit_scale", 1)))


def last_logits(spec: dict, params: dict, tokens: list[int], pad_to: int):
    """Logits [V] (float32) at the last position of ``tokens``. The sequence
    is padded on the right to ``pad_to`` so every call has one shape; causal
    attention and per-token routing keep the padding out of every real
    position. Where that position's routing is a near-tie the result is, per
    token, the most favourable of the routings the served precision cannot
    tell apart (the module's docstring says how and why)."""
    import itertools

    n = len(tokens)
    padded = list(tokens) + [0] * (pad_to - n)

    def head(x):
        return _head(float(spec["layer_norm_eps"]), x[n - 1], params["final_norm"], params["embed"],
                     F32(spec.get("logit_scale", 1)))

    margins: list = []
    rows = [head(hidden_states(spec, params, padded, margins))]
    near = [layer for layer, m in enumerate(margins) if float(m[n - 1]) < NEAR_TIE_MARGIN][:MAX_NEAR_TIES]
    for size in range(1, len(near) + 1):
        for layers in itertools.combinations(near, size):
            rows.append(head(hidden_states(spec, params, padded, exchange=(n - 1, frozenset(layers)))))
    if len(rows) == 1:
        return rows[0]
    best = jnp.stack([jnp.max(r) for r in rows])
    return jnp.max(jnp.stack(rows) - best[:, None], axis=0) + jnp.max(best)
