"""Packed-int4 KV pages (ISSUE 13 tentpole): the row primitives
(ops/quant.quantize_row_int4 / pack_int4 / unpack_int4 /
fake_quant_row_int4), the Q4PagedKVCache pool helpers, fused-kernel vs
gathered-XLA parity for ``paged_decode_attention_q4`` (interpret mode on
CPU), and engine-level plausibility: an int4 paged engine must serve
deterministically, keep its page accounting clean, and archive a pool
whose bytes-per-token are far below the int8 pool's. Token EXACTNESS vs
the dense reference is deliberately NOT asserted here — 4-bit KV error
flips greedy ties on tiny random-init models; exactness is the int8
suite's contract (tests/test_kv_quant.py) and int4-vs-int4 exactness is
the handoff suite's (tests/test_handoff.py::test_disagg_token_exact_int4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.container import new_mock_container
from gofr_tpu.models import LlamaConfig, llama
from gofr_tpu.ops.paged import (
    Q4PagedKVCache,
    append_tokens_paged_q4,
    gather_kv_q4,
    write_prompts_paged_q4,
)
from gofr_tpu.ops.quant import (
    fake_quant_row_int4,
    pack_int4,
    quantize_row_int4,
    unpack_int4,
)
from gofr_tpu.tpu.engine import GenerateEngine

pytestmark = pytest.mark.quick


# -- row primitives ------------------------------------------------------------


def test_quantize_row_int4_bounds_and_error():
    """Symmetric per-row int4: levels stay in [-7, 7] and the round-trip
    error of every element is at most half a quantization step."""
    x = jax.random.normal(jax.random.key(0), (5, 3, 32), jnp.float32) * 4.0
    q, s = quantize_row_int4(x)
    assert q.dtype == jnp.int8 and s.shape == (5, 3)
    qn = np.asarray(q)
    assert qn.min() >= -7 and qn.max() <= 7
    err = np.abs(np.asarray(x) - qn * np.asarray(s)[..., None])
    assert (err <= np.asarray(s)[..., None] * 0.5 + 1e-6).all()


def test_pack_unpack_roundtrip_and_nibble_order():
    """pack_int4 is lossless over the full [-8, 7] range and uses the
    split-half order: byte j of a D-wide row holds elements j and
    j + D/2 (low/high nibble, +8 biased) — the layout the fused kernel's
    in-register unpack assumes."""
    q = jax.random.randint(jax.random.key(1), (4, 6, 16), -8, 8, jnp.int8)
    b = pack_int4(q)
    assert b.dtype == jnp.uint8 and b.shape == (4, 6, 8)
    np.testing.assert_array_equal(np.asarray(unpack_int4(b)), np.asarray(q))
    qn, bn = np.asarray(q), np.asarray(b)
    want = ((qn[..., :8] + 8) | ((qn[..., 8:] + 8) << 4)).astype(np.uint8)
    np.testing.assert_array_equal(bn, want)


def test_fake_quant_row_int4_matches_pool_roundtrip():
    """fake_quant_row_int4 IS the pool round-trip: quantize → pack →
    unpack → dequant with the pool's bf16 scale cast. The engine's
    reference paths (verify_step history re-reads) rely on this identity."""
    x = jax.random.normal(jax.random.key(2), (3, 2, 32), jnp.float32)
    q, s = quantize_row_int4(x)
    s = s.astype(jnp.bfloat16).astype(jnp.float32)
    want = unpack_int4(pack_int4(q)).astype(jnp.float32) * s[..., None]
    got = fake_quant_row_int4(x, scale_dtype=jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# -- pool helpers --------------------------------------------------------------


def test_q4_pool_create_shapes_and_odd_head_dim_raises():
    pool = Q4PagedKVCache.create(2, 6, 8, 3, 32)
    assert pool.k.shape == (2, 6, 3, 8, 16) and pool.k.dtype == jnp.uint8
    assert pool.ks.shape == (2, 6, 3, 8) and pool.ks.dtype == jnp.bfloat16
    assert (pool.num_layers, pool.num_pages, pool.page_size) == (2, 6, 8)
    with pytest.raises(ValueError, match="even head_dim"):
        Q4PagedKVCache.create(2, 6, 8, 3, 31)


def test_write_append_gather_roundtrip():
    """write_prompts_paged_q4 + append_tokens_paged_q4 through a block
    table, read back via gather_kv_q4: every written position dequantizes
    to its own fake-quant round-trip; positions past the length are
    untouched (zero scale planes)."""
    page, hkv, d = 8, 2, 32
    kq = jnp.zeros((2, 6, hkv, page, d // 2), jnp.uint8)  # 2 layers, layer 1 used
    ks = jnp.zeros((2, 6, hkv, page), jnp.bfloat16)
    table = jnp.asarray([[0, 1], [3, 6]], jnp.int32)  # slot 1 page 1 is OOB
    prompt = jax.random.normal(jax.random.key(3), (2, 5, hkv, d), jnp.float32)
    kq, ks = write_prompts_paged_q4(kq, ks, 1, table, prompt, jnp.asarray([0, 0]))
    step = jax.random.normal(jax.random.key(4), (2, hkv, d), jnp.float32)
    kq, ks = append_tokens_paged_q4(kq, ks, 1, table, jnp.asarray([5, 5]), step)
    assert not np.asarray(kq[0]).any() and not np.asarray(ks[0]).any()

    gq, gs = gather_kv_q4(kq, ks, 1, table)  # [2, hkv, 16, d], [2, hkv, 16]
    view = gq.astype(jnp.float32) * gs.astype(jnp.float32)[..., None]
    full = jnp.concatenate([prompt, step[:, None]], axis=1)  # [2, 6, hkv, d]
    want = fake_quant_row_int4(full, scale_dtype=jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(view[:, :, :6]),
        np.asarray(want).transpose(0, 2, 1, 3), rtol=1e-2, atol=1e-2)
    # untouched tail of slot 0's second page: zero scales → zero view
    assert not np.asarray(view[0, :, 6:]).any()


# -- fused kernel vs gathered-XLA parity ---------------------------------------


def _build_case(key, n, hq, hkv, d, page, max_pages, table):
    """Random q + a packed pool whose pages are filled through the same
    write helper the model uses (so parity covers the layout end to end)."""
    kq = vq = jnp.zeros((1, max_pages * n, hkv, page, d // 2), jnp.uint8)
    ks = vs = jnp.zeros((1, max_pages * n, hkv, page), jnp.bfloat16)
    ka, kb, kc = jax.random.split(key, 3)
    q = jax.random.normal(ka, (n, hq, d), jnp.float32)
    k = jax.random.normal(kb, (n, max_pages * page, hkv, d), jnp.float32)
    v = jax.random.normal(kc, (n, max_pages * page, hkv, d), jnp.float32)
    off = jnp.zeros((n,), jnp.int32)
    kq, ks = write_prompts_paged_q4(kq, ks, 0, table, k, off)
    vq, vs = write_prompts_paged_q4(vq, vs, 0, table, v, off)
    return q, kq, vq, ks, vs


@pytest.mark.parametrize("hq,hkv", [(4, 2), (2, 2)])
def test_paged_decode_q4_kernel_matches_gather(monkeypatch, hq, hkv):
    """The fused in-kernel unpack+dequant path (interpret mode) must match
    the gather-then-unpack XLA reference over ragged lengths, an empty
    slot, OOB table rows, and GQA head grouping."""
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    from gofr_tpu.ops.attention import paged_decode_attention_q4

    n, d, page, maxp = 3, 32, 8, 4
    P = maxp * n  # OOB sentinel
    table = jnp.asarray(
        [[0, 1, 2, 3], [4, 5, P, P], [P, P, P, P]], jnp.int32)
    lengths = jnp.asarray([29, 13, 0], jnp.int32)
    q, kq, vq, ks, vs = _build_case(
        jax.random.key(7), n, hq, hkv, d, page, maxp, table)
    want = paged_decode_attention_q4(
        q, kq, vq, ks, vs, 0, table, lengths, backend="xla")
    got = paged_decode_attention_q4(
        q, kq, vq, ks, vs, 0, table, lengths, backend="pallas")
    np.testing.assert_allclose(
        np.asarray(got[:2]), np.asarray(want[:2]), rtol=2e-2, atol=2e-2)
    assert np.isfinite(np.asarray(got[:2])).all()


def test_paged_decode_q4_explicit_pallas_rejects_bad_page(monkeypatch):
    """Explicit backend='pallas' with a page size that breaks the f32
    sublane tile must raise, never silently degrade (ADVICE r2)."""
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    from gofr_tpu.ops.attention import paged_decode_attention_q4

    n, d, page = 1, 32, 4
    table = jnp.asarray([[0]], jnp.int32)
    q, kq, vq, ks, vs = _build_case(
        jax.random.key(8), n, 2, 2, d, page, 1, table)
    with pytest.raises(ValueError, match="multiple of 8"):
        paged_decode_attention_q4(
            q, kq, vq, ks, vs, 0, table, jnp.asarray([2]), backend="pallas")


# -- engine level --------------------------------------------------------------


class TestEngineInt4KV:
    @pytest.fixture(scope="class")
    def setup(self):
        cfg = LlamaConfig.tiny()
        params = llama.init(cfg, jax.random.key(7))
        return cfg, params

    def test_int4_serving_is_deterministic_and_leak_free(self, setup):
        """Greedy int4 serving is token-plausible: deterministic across
        runs, right count, in-vocab — and the pool accounting stays clean
        after mixed traffic (prefix reuse + slot churn)."""
        cfg, params = setup
        from gofr_tpu.testutil import assert_paged_pool_consistent

        eng = GenerateEngine(llama, cfg, params, new_mock_container(),
                             slots=4, max_len=64, max_prefill_batch=2,
                             kv_layout="paged", page_size=8,
                             kv_quantize="int4")
        try:
            assert isinstance(eng.kv_cache, Q4PagedKVCache)
            a = eng.generate([5, 3, 9], max_new_tokens=8, timeout=300)
            b = eng.generate([5, 3, 9], max_new_tokens=8, timeout=300)
            assert a["tokens"] == b["tokens"]
            assert len(a["tokens"]) == 8
            assert all(0 <= t < cfg.vocab_size for t in a["tokens"])
            c = eng.generate([2, 4], max_new_tokens=4, timeout=300)
            assert len(c["tokens"]) == 4
            assert_paged_pool_consistent(eng, slots_empty=True)

            # pool bytes: packed nibbles + bf16 scales vs an int8 pool of
            # the same geometry — strictly smaller, and the packed planes
            # alone are exactly half the int8 planes
            q4 = sum(x.size * x.dtype.itemsize
                     for x in (eng.kv_cache.k, eng.kv_cache.v,
                               eng.kv_cache.ks, eng.kv_cache.vs))
            q8pool = llama.make_paged_cache_q(
                cfg, eng.total_pages, eng.page_size)
            q8 = sum(x.size * x.dtype.itemsize
                     for x in (q8pool.k, q8pool.v, q8pool.ks, q8pool.vs))
            assert q4 < q8
            assert eng.kv_cache.k.nbytes * 2 == q8pool.k.nbytes
        finally:
            eng.stop()

    def test_build_engine_env_selects_int4(self, setup):
        """ENGINE_KV_DTYPE=int4 is the config-plane spelling: build_engine
        must materialize the packed pool and record kv_quantize='int4'
        (what /debug/engine and the handoff JOIN hello report)."""
        from gofr_tpu.tpu.engine import ModelSpec, build_engine

        cfg, _ = setup
        c = new_mock_container({"ENGINE_KV_DTYPE": "int4",
                                "ENGINE_KV_LAYOUT": "paged",
                                "ENGINE_PAGE_SIZE": "8"})
        spec = ModelSpec("llama", cfg, task="generate", dtype=jnp.float32)
        eng = build_engine(spec, c, slots=2, max_len=32)
        try:
            assert eng.kv_quantize == "int4"
            assert isinstance(eng.kv_cache, Q4PagedKVCache)
            out = eng.generate([1, 2, 3], max_new_tokens=2, timeout=300)
            assert len(out["tokens"]) == 2
        finally:
            eng.stop()

    def test_build_engine_rejects_bad_dtype_and_bf16_is_dense(self, setup):
        from gofr_tpu.tpu.engine import ModelSpec, build_engine

        cfg, _ = setup
        spec = ModelSpec("llama", cfg, task="generate", dtype=jnp.float32)
        with pytest.raises(ValueError, match="ENGINE_KV_DTYPE"):
            build_engine(spec, new_mock_container({"ENGINE_KV_DTYPE": "fp8"}),
                         slots=2, max_len=32)
        c = new_mock_container({"ENGINE_KV_DTYPE": "bf16",
                                "ENGINE_KV_LAYOUT": "paged",
                                "ENGINE_PAGE_SIZE": "8"})
        eng = build_engine(spec, c, slots=2, max_len=32)
        try:
            assert eng.kv_quantize == ""
        finally:
            eng.stop()

    def test_int4_requires_paged_layout(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError, match="kv_quantize"):
            GenerateEngine(llama, cfg, params, new_mock_container(),
                           slots=2, max_len=32, kv_quantize="int4")
