"""Mixture-of-experts routing: top-k gating with static capacity.

GShard/Switch-style dense dispatch, shaped for the TPU compiler: every
tensor is static — tokens route into a fixed [experts, capacity, dim]
buffer via one-hot dispatch/combine einsums, so the whole MoE layer is
three big MXU contractions regardless of routing decisions, and the same
compiled program serves every batch (no recompiles, no ragged shapes).
Tokens beyond an expert's capacity are *dropped* (their combine weight is
zero and the residual stream carries them through) — the standard
capacity-factor trade.

Expert parallelism falls out of sharding: the expert dimension of the
dispatch buffer and the expert weights carry the "expert" logical axis
(→ mesh ``ep``), and GSPMD turns the dispatch/combine einsums into
all-to-alls over ICI (SURVEY.md §2.9 — new subsystem, no reference analog).

``moe_ffn_held`` is the second, DROPLESS path: an expert layer that is told
which experts it holds (``first_expert``, and as many as its weights have),
routes over the router's whole width, and computes the part of the routed
sum that its own experts give, for exactly the tokens routed to them — what
one rank of an expert-parallel deployment computes, without the exchange.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.tracing import scope


class Routing(NamedTuple):
    dispatch: jnp.ndarray  # [T, E, C] one-hot-ish {0,1}
    combine: jnp.ndarray   # [T, E, C] gate probabilities at kept slots
    aux_loss: jnp.ndarray  # [] load-balance loss (Switch §2.2 style)
    router_probs: jnp.ndarray  # [T, E] full softmax (for metrics/tests)


def route_topk(
    router_logits: jnp.ndarray,  # [T, E]
    *,
    k: int,
    capacity: int,
    renormalize: bool = True,
    token_mask: jnp.ndarray | None = None,  # [T] 1 = real token
) -> Routing:
    """Top-k token→expert assignment with per-expert capacity ``C``.

    Priority is choice-major then token-major: every token's 1st choice
    beats any token's 2nd choice; ties break by token order — deterministic
    and batch-order stable.

    ``token_mask`` excludes padding tokens entirely: they take no capacity,
    get zero combine mass, and don't bias the aux loss — so a padded batch
    routes identically to its unpadded equivalent.
    """
    t, e = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)  # [T, E]
    top_probs, top_idx = lax.top_k(probs, k)  # [T, K]
    if renormalize:
        top_probs = top_probs / jnp.maximum(jnp.sum(top_probs, -1, keepdims=True), 1e-9)

    mask = jax.nn.one_hot(top_idx, e, dtype=jnp.int32)  # [T, K, E]
    if token_mask is not None:
        mask = mask * token_mask.astype(jnp.int32)[:, None, None]
    # choice-major flatten → positions within each expert's buffer
    mask_f = mask.transpose(1, 0, 2).reshape(k * t, e)
    pos_f = (jnp.cumsum(mask_f, axis=0) - 1) * mask_f  # [K*T, E]
    pos = pos_f.reshape(k, t, e).transpose(1, 0, 2)  # [T, K, E]
    kept = (pos < capacity) & (mask > 0)  # [T, K, E]

    slot = jax.nn.one_hot(jnp.where(kept, pos, -1), capacity, dtype=jnp.float32)  # [T,K,E,C]
    dispatch = jnp.sum(slot, axis=1)  # [T, E, C]
    combine = jnp.sum(slot * top_probs[:, :, None, None], axis=1)  # [T, E, C]

    # load balance: E * Σ_e (fraction of first-choice tokens to e) * (mean prob of e)
    first = jax.nn.one_hot(top_idx[:, 0], e, dtype=jnp.float32)
    if token_mask is not None:
        w = token_mask.astype(jnp.float32)[:, None]
        denom = jnp.maximum(jnp.sum(w), 1.0)
        frac = jnp.sum(first * w, axis=0) / denom
        mean_prob = jnp.sum(probs * w, axis=0) / denom
    else:
        frac = jnp.mean(first, axis=0)
        mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_prob)
    return Routing(dispatch=dispatch, combine=combine, aux_loss=aux, router_probs=probs)


def default_capacity(tokens: int, experts: int, k: int, factor: float = 1.25) -> int:
    """ceil(T*k/E * factor), at least 1 — static per (shape, config)."""
    return max(1, int((tokens * k + experts - 1) // experts * factor))


def moe_ffn(
    x: jnp.ndarray,          # [T, D] tokens (post-norm)
    router_w: jnp.ndarray,   # [D, E]
    w_gate: jnp.ndarray,     # [E, D, M]
    w_up: jnp.ndarray,       # [E, D, M]
    w_down: jnp.ndarray,     # [E, M, D]
    *,
    k: int,
    capacity_factor: float = 1.25,
    capacity: int | None = None,
    token_mask: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """SwiGLU expert FFN over routed tokens → ([T, D], aux_loss).

    The three einsums (dispatch, expert matmuls, combine) are where EP
    sharding bites: w_* carry the "expert" logical axis. ``capacity``
    overrides the factor-derived default (e.g. decode uses capacity == T so
    a skewed slot batch can never drop a live token).
    """
    t, d = x.shape
    e = router_w.shape[1]
    cap = capacity if capacity is not None else default_capacity(t, e, k, capacity_factor)
    routing = route_topk(
        (x @ router_w.astype(x.dtype)).astype(jnp.float32),
        k=k, capacity=cap, token_mask=token_mask,
    )

    xin = jnp.einsum("tec,td->ecd", routing.dispatch.astype(x.dtype), x)  # [E, C, D]
    gated = jax.nn.silu(jnp.einsum("ecd,edm->ecm", xin, w_gate)) * jnp.einsum(
        "ecd,edm->ecm", xin, w_up
    )
    out = jnp.einsum("ecm,emd->ecd", gated, w_down)  # [E, C, D]
    y = jnp.einsum("tec,ecd->td", routing.combine.astype(x.dtype), out)
    return y, routing.aux_loss


# -- the dropless path: a share of the experts, no capacity ------------------

# Up to this many tokens a call, every held expert is computed on EVERY token
# (one dense product an expert, the gate zero where the token was not routed);
# above it the grouped product (sorted assignments, ``lax.ragged_dot``)
# computes only the rows routed here. Either way every held expert's weights
# are read once. One rule on the call's static token count, set by a chip
# reading (v5e, 16 of 128 experts of 3 x 4096^2 held, top-8, four layers; my
# chip runs, PR 34; docs/kernels.md has the table): the dense products take
# 9.7 ms up to 128 tokens (the weights alone are 7.9 ms at 819 GB/s), 11.1 at
# 256, 19.2 at 512 and 38.5 at 1,024 — compute-bound from about 240 tokens,
# where T FLOP a weight byte pass what the chip does while it reads one — and
# the grouped product 21.8 / 22.1 / 23.4 / 28.7 / 58.1 (4,096): a fixed cost
# of 2.4x the weight read, then 21% of the bf16 peak on the rows it keeps.
# They cross between 512 and 1,024 tokens.
DENSE_MAX_TOKENS = 512


class HeldRouting(NamedTuple):
    gates: jnp.ndarray   # [T, K] f32, normalised over the K chosen (held or not)
    local: jnp.ndarray   # [T, K] int32 index among the held experts; == held where absent or masked
    counts: jnp.ndarray  # [held + 3] int32: assignments per held expert, absent assignments, held experts hit, 1


def route_sigmoid_topk(
    x: jnp.ndarray,          # [T, D]
    router_w: jnp.ndarray,   # [D, E] float32, the router's WHOLE width
    *,
    k: int,
    first_expert: int,
    experts_held: int,
    token_mask: jnp.ndarray | None = None,  # [T] True = real token
) -> HeldRouting:
    """Sigmoid scores over all ``E`` experts in float32, the top ``k`` of
    them, gates normalised over those ``k`` whether or not their experts live
    here (the shares of all ranks then add up to the whole layer). Masked
    tokens are routed nowhere and counted nowhere."""
    t = x.shape[0]
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router_w.astype(jnp.float32), precision=lax.Precision.HIGHEST))
    top, idx = lax.top_k(scores, k)  # [T, K]
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
    local = idx.astype(jnp.int32) - first_expert
    here = (local >= 0) & (local < experts_held)
    live = jnp.ones((t,), bool) if token_mask is None else token_mask.astype(bool)
    here &= live[:, None]
    local = jnp.where(here, local, experts_held)
    per_expert = jnp.sum(local[..., None] == jnp.arange(experts_held), axis=(0, 1), dtype=jnp.int32)
    held_total = jnp.sum(per_expert)
    counts = jnp.concatenate([per_expert, jnp.stack([
        jnp.sum(live.astype(jnp.int32)) * k - held_total,
        jnp.sum((per_expert > 0).astype(jnp.int32)),
        jnp.ones((), jnp.int32)])])
    return HeldRouting(gates=gates, local=local, counts=counts)


def _experts_dense(x, w_gate, w_up, w_down, gates, local):
    """Every held expert on every token; the gate is zero off the routing."""
    held = w_gate.shape[0]
    gate_te = jnp.sum((local[..., None] == jnp.arange(held)) * gates[..., None], axis=1)  # [T, held]
    # the tokens are handed to every expert as a batch of its own: a product
    # batched over e on BOTH sides reads [held, D, M] as it lies, where
    # "td,edm" would have the compiler re-lay the weights as one [held*M, D]
    # matrix (a copy of every layer's experts in front of the layer scan)
    xe = jnp.broadcast_to(x[None], (held,) + x.shape)
    h = jax.nn.silu(jnp.einsum("etd,edm->etm", xe, w_gate)) * jnp.einsum("etd,edm->etm", xe, w_up)
    y = jnp.einsum("etm,emd->etd", h, w_down, preferred_element_type=jnp.float32)
    return jnp.einsum("etd,te->td", y, gate_te)


def moe_ffn_held(
    x: jnp.ndarray,          # [T, D] tokens (post-norm)
    router_w: jnp.ndarray,   # [D, E] float32: E is the router's width, not the experts held
    w_gate: jnp.ndarray,     # [held, D, M], or every layer's [L, held, D, M] with ``layer``
    w_up: jnp.ndarray,       # [held, D, M]
    w_down: jnp.ndarray,     # [held, M, D]
    *,
    k: int,
    first_expert: int,
    token_mask: jnp.ndarray | None = None,
    layer=None,
    router_x: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of ``Σ_{e in top-k} g_e · FFN_e(x)`` → ([T, D]
    in x's dtype, counts [held + 3] int32 as ``HeldRouting.counts``). What the
    absent experts would have added is left out; no token is dropped at any
    skew (there is no capacity: the grouped path's buffer holds all ``T·k``
    assignments). Entered under ``mlp``; its parts carry ``tracing.MOE_SCOPES``.

    A layer scan hands the experts of EVERY layer (``[L, held, ...]``, not
    scanned) and the scanned ``layer`` index: the grouped product is a custom
    call and cannot read a slice where it lies, so a scanned ``[held, D, M]``
    would be copied out of the stack a matrix a layer (1.5 GB a layer at
    Command A+'s widths). It is given the whole stack as ``L·held`` groups
    instead, all empty but this layer's.

    ``router_x`` [T, D] float32 is what the router reads where the caller has
    the tokens in more precision than ``x`` (the norm's float32 result before
    its cast): a router score rounded less takes the other side of a near-tie
    less often."""
    t, _ = x.shape
    held = w_gate.shape[-3]
    stacked = w_gate.ndim == 4
    with scope("moe_router"):
        r = route_sigmoid_topk(x if router_x is None else router_x, router_w, k=k,
                               first_expert=first_expert, experts_held=held, token_mask=token_mask)
    if t <= DENSE_MAX_TOKENS:
        with scope("moe_experts"):
            if stacked:  # the index fuses into the products' operand reads
                w_gate, w_up, w_down = (lax.dynamic_index_in_dim(w, layer, keepdims=False)
                                        for w in (w_gate, w_up, w_down))
            y = _experts_dense(x, w_gate, w_up, w_down, r.gates, r.local)
        return y.astype(x.dtype), r.counts
    with scope("moe_router"):
        flat = r.local.reshape(t * k)           # absent and masked sort last
        order = jnp.argsort(flat, stable=True)  # assignments grouped by held expert
        xs = x[order // k]                      # [T*k, D]
        sizes = r.counts[:held]
        if stacked:
            groups = w_gate.shape[0] * held
            sizes = lax.dynamic_update_slice(jnp.zeros((groups,), jnp.int32), sizes, (layer * held,))
            w_gate, w_up, w_down = (w.reshape(groups, *w.shape[2:]) for w in (w_gate, w_up, w_down))
    with scope("moe_experts"):
        h = jax.nn.silu(lax.ragged_dot(xs, w_gate, sizes)) * lax.ragged_dot(xs, w_up, sizes)
        ys = lax.ragged_dot(h, w_down, sizes)   # rows past sum(sizes) hold nothing meant
    with scope("moe_router"):
        kept = jnp.arange(t * k) < jnp.sum(sizes)
        ys = jnp.where(kept[:, None], ys.astype(jnp.float32), 0.0)
        back = jnp.argsort(order)               # un-sort: assignment (token, choice) → its row
        y = jnp.sum(ys[back].reshape(t, k, -1) * r.gates[..., None], axis=1)
    return y.astype(x.dtype), r.counts
