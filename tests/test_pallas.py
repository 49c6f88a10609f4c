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


# -- a decode step's append inside the paged-decode kernel (ops/pallas/paged_decode) --

PAGE, MAXP = 16, 3
# what a lane of the batch is there for -> (its position, whether its table row is allocated)
APPEND_LANES = {
    "length_0": (0, True),                   # nothing attended but the row it writes
    "row_1": (1, True),
    "last_row_of_a_page": (PAGE - 1, True),
    "opens_a_fresh_page": (PAGE, True),      # pos % page == 0 past the first page
    "second_tile_of_a_page": (PAGE + 9, True),
    "last_row_of_the_span": (MAXP * PAGE - 1, True),
    "past_the_span": (MAXP * PAGE, True),    # dropped, never clamped onto the last page
    "idle": (5, False),                      # every entry P: writes nothing
}


def _append_case(dtype, hkv, group, lanes=tuple(APPEND_LANES), d=128, seed=0):
    """One batch with a lane for each name in ``lanes`` → operands of a decode
    step's append + attention at layer 1 of a 2-layer pool whose pages hold
    noise everywhere (rows past a length included: a write must leave them)."""
    n, pool = len(lanes), len(lanes) * MAXP + 1  # the last page is nobody's: clamped reads land there
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (n, hkv * group, d), dtype)
    k_pool = jax.random.normal(ks[1], (2, pool, hkv, PAGE, d), dtype)
    v_pool = jax.random.normal(ks[2], (2, pool, hkv, PAGE, d), dtype)
    k_new = jax.random.normal(ks[3], (n, hkv, d), dtype)
    v_new = jax.random.normal(ks[4], (n, hkv, d), dtype)
    table = np.full((n, MAXP), pool, np.int32)
    perm = np.random.RandomState(seed).permutation(pool - 1)
    pos = np.zeros(n, np.int32)
    for i, name in enumerate(lanes):
        pos[i], allocated = APPEND_LANES[name]
        if allocated:
            need = min(pos[i] // PAGE + 1, MAXP)
            table[i, :need] = perm[i * MAXP: i * MAXP + need]
    return q, k_new, v_new, k_pool, v_pool, jnp.asarray(table), jnp.asarray(pos)


def _assert_append_attend_matches(dtype, got, pools, operands, live, window=None):
    """The fused call against ``append_tokens_paged`` then attention: planes
    bit for bit; the live lanes' output bit for bit against the kernel read
    of the scattered pool, and against XLA's read to 2e-5 in float32 / one
    bf16 ulp in bf16."""
    from gofr_tpu.ops.attention import paged_decode_attention
    from gofr_tpu.ops.paged import append_tokens_paged

    q, k_new, v_new, k_pool, v_pool, table, pos = operands
    want_k, want_v = append_tokens_paged(k_pool, v_pool, 1, table, pos, k_new, v_new)
    for plane, want in zip(pools, (want_k, want_v)):
        assert plane.dtype == want.dtype
        assert np.array_equal(np.asarray(plane, np.float32), np.asarray(want, np.float32))
    live = np.asarray(live, int)
    kernel = paged_decode_attention(q, want_k, want_v, 1, table, pos + 1, backend="pallas", window=window)
    assert np.array_equal(np.asarray(got, np.float32)[live], np.asarray(kernel, np.float32)[live])
    # a lane reads at most its table's span, and a window counts back from there
    span = jnp.minimum(pos + 1, table.shape[1] * k_pool.shape[3])
    xla = np.asarray(paged_decode_attention(q, want_k, want_v, 1, table, span, backend="xla", window=window),
                     np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32)[live], xla[live], atol=_tol(dtype), rtol=_tol(dtype))


def _tol(dtype):
    return 2e-5 if dtype == jnp.float32 else 2.0 ** -7  # one bf16 ulp of a value in [1, 2), relative above


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [2, 4])
@pytest.mark.parametrize("hkv", [1, 2, 8])
def test_paged_decode_append_matches_scatter_then_attend(monkeypatch, dtype, group, hkv):
    """Every kind of lane in ONE call, for the head geometries the repo
    builds (a tp shard has 1 or 2 KV heads, the benchmark's configurations
    8; 2 and 4 query heads a KV head): a decode step's append and attention
    as one kernel call equal the scatter followed by the read."""
    from gofr_tpu.ops.attention import paged_decode_append_attention

    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    operands = _append_case(dtype, hkv, group)
    got, k_out, v_out = paged_decode_append_attention(*operands[:5], 1, *operands[5:])
    live = [i for i, name in enumerate(APPEND_LANES) if name != "idle"]
    _assert_append_attend_matches(dtype, got, (k_out, v_out), operands, live)


@pytest.mark.parametrize("lane", sorted(APPEND_LANES))
def test_paged_decode_append_lane_by_lane(monkeypatch, lane):
    """Each kind of lane between two plain neighbours (the wait for a lane's
    write is put off to the lane after next, and the last lane waits for what
    is left: first, middle and last place each), bf16, G = 2: the planes
    change in exactly the rows the scatter changes — none for the idle lane
    and the one past its table's span."""
    from gofr_tpu.ops.attention import paged_decode_append_attention

    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    for place in range(3):
        lanes = ["row_1", "last_row_of_a_page"]
        lanes.insert(place, lane)
        operands = _append_case(jnp.bfloat16, 2, 2, lanes=tuple(lanes), seed=place)
        got, k_out, v_out = paged_decode_append_attention(*operands[:5], 1, *operands[5:])
        live = [i for i, name in enumerate(lanes) if name != "idle"]
        _assert_append_attend_matches(jnp.bfloat16, got, (k_out, v_out), operands, live)
        changed = np.argwhere(np.asarray(k_out != operands[3]).any(axis=(2, 4)))  # (layer, page, row)
        dropped = lane in ("idle", "past_the_span")
        assert len(changed) == (2 if dropped else 3) and set(changed[:, 0]) == {1}, changed


# Where the idle lanes of one call sit among the live ones (names from APPEND_LANES).
IDLE_LAYOUTS = {
    "idle_first": ("idle", "idle", "row_1", "last_row_of_a_page", "opens_a_fresh_page"),
    "idle_last": ("length_0", "second_tile_of_a_page", "past_the_span", "idle", "idle"),
    "idle_between_live": ("row_1", "idle", "last_row_of_the_span", "idle", "idle", "opens_a_fresh_page",
                          "idle", "length_0"),
    "one_live_of_40": ("idle",) * 23 + ("second_tile_of_a_page",) + ("idle",) * 16,
    "every_lane_idle": ("idle",) * 4,
    "no_lane_idle": tuple(name for name in APPEND_LANES if name != "idle"),
}


def _tpu_interpreter():
    """The TPU interpreter: a copy lands only when it is waited for
    (``dma_execution_mode="on_wait"``), so a copy the kernel never waits
    for shows as a page never attended or a row never written."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.InterpretParams(dma_execution_mode="on_wait")


@pytest.mark.parametrize("window", [None, 21], ids=["unwindowed", "window_21"])
@pytest.mark.parametrize("layout", sorted(IDLE_LAYOUTS))
def test_paged_decode_append_skips_idle_lanes(monkeypatch, layout, window):
    """The fused call over one layout of idle and live lanes, with and without
    a window that cuts inside a page: the live lanes equal the scatter
    followed by the read; an idle lane's output is exactly zero; the planes
    change in the rows the live lanes write and nowhere else, so an idle
    lane's pages are bit for bit what they were."""
    from gofr_tpu.ops.pallas import paged_decode as kernels

    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    lanes = IDLE_LAYOUTS[layout]
    operands = _append_case(jnp.bfloat16, 2, 2, lanes=lanes)
    got, k_out, v_out = kernels.paged_decode_append_attention(
        *operands[:5], 1, *operands[5:], window, interpret=_tpu_interpreter())
    live = [i for i, name in enumerate(lanes) if name != "idle"]
    idle = [i for i, name in enumerate(lanes) if name == "idle"]
    _assert_append_attend_matches(jnp.bfloat16, got, (k_out, v_out), operands, live, window)
    assert not np.asarray(got, np.float32)[idle].any()
    writers = sum(name not in ("idle", "past_the_span") for name in lanes)
    for plane, before in ((k_out, operands[3]), (v_out, operands[4])):
        changed = np.argwhere(np.asarray(plane != before).any(axis=(2, 4)))  # (layer, page, row)
        assert len(changed) == writers and set(changed[:, 0]) <= {1}, changed


@pytest.mark.parametrize("window", [None, 21], ids=["unwindowed", "window_21"])
@pytest.mark.parametrize("layout", sorted(IDLE_LAYOUTS))
def test_paged_decode_read_skips_idle_lanes(layout, window):
    """The read-only kernel over the same layouts against ``ops.paged.gather_kv``
    and XLA's attention, lengths ``position + 1`` — except the ``length_0``
    lanes, read here at length 0: pages in the table and nothing to attend
    is idle too. Live lanes agree to one bf16 ulp; idle lanes are zeros."""
    from gofr_tpu.ops.attention import paged_decode_attention
    from gofr_tpu.ops.pallas import paged_decode as kernels

    lanes = IDLE_LAYOUTS[layout]
    q, _, _, k_pool, v_pool, table, pos = _append_case(jnp.bfloat16, 2, 2, lanes=lanes)
    lengths = jnp.where(jnp.asarray([name == "length_0" for name in lanes]), 0, pos + 1)
    got = np.asarray(kernels.paged_decode_attention(
        q, k_pool, v_pool, 1, table, lengths, window=window, interpret=_tpu_interpreter()), np.float32)
    span = jnp.minimum(lengths, MAXP * PAGE)  # as in _assert_append_attend_matches
    want = np.asarray(paged_decode_attention(q, k_pool, v_pool, 1, table, span, backend="xla",
                                             window=window), np.float32)
    idle = np.asarray([name in ("idle", "length_0") for name in lanes])
    tol = _tol(jnp.bfloat16)
    np.testing.assert_allclose(got[~idle], want[~idle], atol=tol, rtol=tol)
    assert not got[idle].any()


@pytest.mark.parametrize("shape,why", [
    ((2, 7, 2, 16, 64), "head_dim 64: the kernel reads a padded copy of a layer"),
    ((2, 7, 2, 8, 128), "a bf16 page of half a sublane tile"),
])
def test_paged_decode_append_refuses_a_plane_it_cannot_address(monkeypatch, shape, why):
    """Asked BY NAME for a plane whose rows no copy can address, the fused
    call raises where it is traced; ``append_rides_in_kernel`` (the rule the
    model's call site asks) says no for the same planes, so a served program
    never gets here."""
    from gofr_tpu.ops.attention import append_rides_in_kernel, paged_decode_append_attention

    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    pool = jnp.zeros(shape, jnp.bfloat16)
    assert not append_rides_in_kernel(pool), why
    new = jnp.zeros((3, shape[2], shape[4]), jnp.bfloat16)
    with pytest.raises(ValueError, match="cannot write a plane"):
        paged_decode_append_attention(jnp.zeros((3, 4, shape[4]), jnp.bfloat16), new, new, pool, pool, 1,
                                      jnp.zeros((3, 2), jnp.int32), jnp.zeros((3,), jnp.int32))


def test_paged_decode_append_waits_for_every_tile_copy_it_starts():
    """The plain interpreter runs a copy where it is started and keeps no
    count of the semaphores, so a wait that went missing passes the tests
    that use it and faults only on the chip (it did once).
    Counted in the kernel's own jaxpr instead: the read-only kernel starts a
    page copy a plane in two places (the call's first page, the page after)
    and waits in one; the append adds ONE place that starts a live lane's
    tile copies and THREE that wait for them — the live lane after next
    before it stages its own, and after the loop for the last two. Then run,
    on idle lanes between live ones, by the TPU interpreter, which makes a
    copy land only when it is waited for: every row written and every page
    attended means every copy the chain over the live lanes starts is
    waited for."""
    from gofr_tpu.ops.pallas import paged_decode as kernels
    from gofr_tpu.ops.paged import append_tokens_paged

    def copies(fn, *args):
        counts = {}

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
                for value in eqn.params.values():
                    for sub in value if isinstance(value, (list, tuple)) else [value]:
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            walk(sub)

        walk(jax.make_jaxpr(fn)(*args).jaxpr)
        return counts.get("dma_start", 0), counts.get("dma_wait", 0)

    q, new = jnp.zeros((3, 4, 128), jnp.bfloat16), jnp.zeros((3, 2, 128), jnp.bfloat16)
    plane = jnp.zeros((2, 7, 2, 16, 128), jnp.bfloat16)
    table, pos = jnp.zeros((3, 2), jnp.int32), jnp.zeros((3,), jnp.int32)
    planes = 2
    assert copies(kernels.paged_decode_attention, q, plane, plane, 1, table, pos) == (2 * planes, 1 * planes)
    assert copies(kernels.paged_decode_append_attention, q, new, new, plane, plane, 1, table, pos) == (
        (2 + 1) * planes, (1 + 3) * planes)

    operands = _append_case(jnp.bfloat16, 2, 2, lanes=IDLE_LAYOUTS["idle_between_live"])
    got, k_out, v_out = kernels.paged_decode_append_attention(
        *operands[:5], 1, *operands[5:], interpret=_tpu_interpreter())
    q, k_new, v_new, k_pool, v_pool, table, pos = operands
    for plane, want in zip((k_out, v_out), append_tokens_paged(k_pool, v_pool, 1, table, pos, k_new, v_new)):
        assert np.array_equal(np.asarray(plane, np.float32), np.asarray(want, np.float32))
    read = kernels.paged_decode_attention(q, k_out, v_out, 1, table, pos + 1, interpret=True)
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(read, np.float32))
