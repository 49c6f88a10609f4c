"""Pallas kernels vs the XLA reference path, run under the Pallas
interpreter on the CPU test mesh (SURVEY.md §4 analog: hermetic device
tests without TPU hardware)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops.attention import decode_attention, mha_attention
from gofr_tpu.ops.pallas.decode_attention import decode_attention as pallas_decode
from gofr_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(key, b, sq, skv, hq, hkv, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, hq, d), dtype)
    k = jax.random.normal(kk, (b, skv, hkv, d), dtype)
    v = jax.random.normal(kv, (b, skv, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_flash_matches_xla_causal(hq, hkv):
    q, k, v = _qkv(jax.random.key(0), 2, 64, 64, hq, hkv, 32)
    want = mha_attention(q, k, v, causal=True, backend="xla")
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_kv_lengths_and_offset():
    b, sq, skv = 3, 24, 48
    q, k, v = _qkv(jax.random.key(1), b, sq, skv, 4, 2, 16)
    lengths = jnp.array([48, 17, 1], jnp.int32)
    offset = jnp.array([24, 5, 0], jnp.int32)
    want = mha_attention(
        q, k, v, causal=True, q_offset=offset, kv_lengths=lengths, backend="xla"
    )
    got = flash_attention(
        q, k, v, causal=True, q_offset=offset, kv_lengths=lengths, interpret=True
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_non_causal_padded_blocks():
    # seq lengths that don't divide the block size exercise the pad path
    q, k, v = _qkv(jax.random.key(2), 2, 9, 21, 2, 2, 8)
    lengths = jnp.array([21, 13], jnp.int32)
    want = mha_attention(q, k, v, causal=False, kv_lengths=lengths, backend="xla")
    got = flash_attention(q, k, v, causal=False, kv_lengths=lengths, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_fully_masked_rows_zero():
    q, k, v = _qkv(jax.random.key(3), 1, 8, 8, 2, 2, 8)
    lengths = jnp.array([0], jnp.int32)  # nothing visible
    got = flash_attention(q, k, v, causal=False, kv_lengths=lengths, interpret=True)
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(got, jnp.zeros_like(got), atol=1e-7)


@pytest.mark.parametrize("hq,hkv,smax", [(4, 2, 64), (8, 8, 96)])
def test_decode_matches_xla(hq, hkv, smax):
    b, d = 4, 16
    key = jax.random.key(4)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, hq, d))
    k_cache = jax.random.normal(kk, (b, hkv, smax, d))
    v_cache = jax.random.normal(kv, (b, hkv, smax, d))
    lengths = jnp.array([1, 7, smax, smax // 2], jnp.int32)
    want = decode_attention(q, k_cache, v_cache, lengths, backend="xla")
    got = pallas_decode(q, k_cache, v_cache, lengths, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_auto_backend_dispatches_interpret(monkeypatch):
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    q, k, v = _qkv(jax.random.key(5), 1, 16, 16, 2, 2, 8)
    want = mha_attention(q, k, v, causal=True, backend="xla")
    got = mha_attention(q, k, v, causal=True, backend="auto")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_llama_forward_with_pallas_backend(monkeypatch):
    """Whole-model parity: tiny Llama forward, XLA vs Pallas-interpret
    (GOFR_PALLAS_INTERPRET alone switches sides: unset, 'auto' is XLA on a CPU)."""
    monkeypatch.delenv("GOFR_PALLAS_INTERPRET", raising=False)
    from gofr_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    lengths = jnp.array([32, 20], jnp.int32)
    want = llama.forward(cfg, params, tokens, lengths)

    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    jax.clear_caches()  # backend resolution happens at trace time
    got = llama.forward(cfg, params, tokens, lengths)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    jax.clear_caches()


def test_flash_grad_matches_xla(monkeypatch):
    """Training routes gradients through the _flash_mha custom_vjp when
    backend='auto' resolves to pallas — the backward pass must match XLA,
    including the kv_lengths/q_offset chunked-prefill arguments (ADVICE.md)."""
    b, sq, skv = 2, 16, 32
    q, k, v = _qkv(jax.random.key(7), b, sq, skv, 4, 2, 16)
    lengths = jnp.array([32, 11], jnp.int32)
    offset = jnp.array([16, 3], jnp.int32)

    def loss(q, k, v, backend):
        out = mha_attention(
            q, k, v, causal=True, q_offset=offset, kv_lengths=lengths, backend=backend
        )
        # non-uniform weighting so every output element contributes distinctly
        w = jnp.arange(out.size, dtype=out.dtype).reshape(out.shape)
        return jnp.sum(out * w)

    want = jax.grad(partial(loss, backend="xla"), argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    got = jax.grad(partial(loss, backend="auto"), argnums=(0, 1, 2))(q, k, v)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, atol=2e-3, rtol=2e-3)


def test_flash_grad_matches_xla_plain_causal(monkeypatch):
    q, k, v = _qkv(jax.random.key(8), 2, 32, 32, 8, 2, 32)

    def loss(q, k, v, backend):
        return jnp.sum(mha_attention(q, k, v, causal=True, backend=backend) ** 2)

    want = jax.grad(partial(loss, backend="xla"), argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    got = jax.grad(partial(loss, backend="auto"), argnums=(0, 1, 2))(q, k, v)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, atol=2e-3, rtol=2e-3)


# -- in-place KV append kernels (ops/pallas/kv_append) --------------------------


def test_append_inplace_matches_select(monkeypatch):
    """Slot-cache in-place append == the masked-select path, including
    dropped OOB writes for padding rows."""
    import numpy as np

    from gofr_tpu.ops.kvcache import append_tokens
    from gofr_tpu.ops.pallas.kv_append import append_tokens_inplace

    n, hkv, smax, d = 4, 2, 32, 16
    key = jax.random.key(0)
    k_layer = jax.random.normal(jax.random.fold_in(key, 1), (n, hkv, smax, d))
    v_layer = jax.random.normal(jax.random.fold_in(key, 2), (n, hkv, smax, d))
    k_new = jax.random.normal(jax.random.fold_in(key, 3), (n, hkv, d))
    v_new = jax.random.normal(jax.random.fold_in(key, 4), (n, hkv, d))
    # one row per tile-boundary case + one OOB (dropped) row
    positions = jnp.array([0, 7, 8, smax], jnp.int32)

    want_k, want_v = append_tokens(k_layer, v_layer, positions, k_new, v_new)
    got_k, got_v = append_tokens_inplace(
        k_layer, v_layer, positions, k_new, v_new, block_s=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want_k), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v), rtol=1e-6)


def test_kv_write_env_dispatch(monkeypatch):
    """GOFR_KV_WRITE=pallas routes append_tokens through the kernel (under
    the interpreter here) with identical results to select."""
    import numpy as np

    from gofr_tpu.ops.kvcache import append_tokens

    n, hkv, smax, d = 2, 2, 16, 8
    key = jax.random.key(9)
    k_layer = jax.random.normal(jax.random.fold_in(key, 1), (n, hkv, smax, d))
    v_layer = k_layer + 1
    k_new = jax.random.normal(jax.random.fold_in(key, 2), (n, hkv, d))
    v_new = k_new + 1
    positions = jnp.array([3, smax], jnp.int32)

    want = append_tokens(k_layer, v_layer, positions, k_new, v_new)
    monkeypatch.setenv("GOFR_KV_WRITE", "pallas")
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    got = append_tokens(k_layer, v_layer, positions, k_new, v_new)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), rtol=1e-6)
