"""Token sampling with static shapes.

``top_k``/``top_p``/``do_sample`` are static (they change the compiled
program); ``temperature`` is a traced scalar so one compiled step serves
any temperature. Fully-batched: one call samples every decode slot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.tracing import scoped

NEG_INF = -1e30


def truncate_logits(logits: jnp.ndarray, top_k: int = 0, top_p: float = 1.0) -> jnp.ndarray:
    """Mask (to NEG_INF) everything outside the top_k / nucleus-top_p set
    along the last axis; any leading dims. The top-1 is always kept (so
    top_p=0.0 degrades to greedy, not uniform garbage). This is THE
    truncation — sample_token and speculative_sample apply the identical
    mask, which is what makes truncated speculative sampling exact
    w.r.t. the truncated target."""
    if top_k > 0 and top_k < logits.shape[-1]:
        vals, _ = lax.top_k(logits, top_k)
        kth = vals[..., -1:]
        logits = jnp.where(logits < kth, NEG_INF, logits)

    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens while the cumulative prob BEFORE them is < top_p
        keep_sorted = (jnp.roll(cum, 1, axis=-1) < top_p).at[..., 0].set(True)
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, NEG_INF, logits)
    return logits


@scoped("sample")
def sample_token(
    logits: jnp.ndarray,
    key: jax.Array,
    *,
    temperature: jnp.ndarray | float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    do_sample: bool = True,
) -> jnp.ndarray:
    """Sample next tokens from ``logits`` [B, V] → [B] int32.

    ``temperature`` may be a scalar or per-row [B] array; rows with
    temperature <= 0 decode greedily (the continuous-batching engine mixes
    greedy and sampled requests in one step this way).
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if not do_sample:
        return greedy

    temp = jnp.asarray(temperature, jnp.float32)  # scalar or [B]; shape is static under jit
    logits = logits.astype(jnp.float32) / jnp.maximum(
        temp[:, None] if temp.ndim == 1 else temp, 1e-6
    )
    logits = truncate_logits(logits, top_k, top_p)
    sampled = jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
    # temperature <= 0 → greedy, for scalar and per-row alike
    return jnp.where(temp > 0, sampled, greedy)
