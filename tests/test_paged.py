"""Paged KV cache: pool write/read semantics and the Pallas paged-decode
kernel vs the XLA gather path (hermetic CPU tests, SURVEY.md §4 analog)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops.attention import decode_attention, paged_decode_attention
from gofr_tpu.ops.kvcache import append_tokens
from gofr_tpu.ops.paged import (
    PagedKVCache,
    append_tokens_paged,
    gather_kv,
    write_prompts_paged,
)


PAGE = 8  # small page for tests; engine default is 128
LAYERS, LAYER = 3, 1  # the ops take whole [L, P, ...] planes and a layer index


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype)


def test_write_prompts_paged_round_trip():
    """A prompt scattered through an arbitrary (non-contiguous) block table
    reads back identical to the slot-cache layout."""
    b, s, hkv, d = 2, 20, 2, 16
    pool_pages, maxp = 12, 4
    k_new = _rand(jax.random.key(0), (b, s, hkv, d))
    v_new = _rand(jax.random.key(1), (b, s, hkv, d))

    # deliberately shuffled, interleaved page assignment
    pages = jnp.array([[7, 2, 9, 11], [0, 5, 3, 1]], jnp.int32)
    k_pool = jnp.zeros((LAYERS, pool_pages, hkv, PAGE, d))
    v_pool = jnp.zeros((LAYERS, pool_pages, hkv, PAGE, d))
    k_pool, v_pool = write_prompts_paged(k_pool, v_pool, LAYER, pages, k_new, v_new)

    k_view, v_view = gather_kv(k_pool, v_pool, LAYER, pages)
    # logical view is [B, Hkv, maxp*PAGE, D]; positions 0..s hold the prompt
    np.testing.assert_allclose(k_view[:, :, :s], k_new.swapaxes(1, 2), rtol=1e-6)
    np.testing.assert_allclose(v_view[:, :, :s], v_new.swapaxes(1, 2), rtol=1e-6)


def test_oob_page_writes_dropped():
    """Padding rows point every logical page at P (out of bounds): their
    writes must vanish, leaving the pool untouched."""
    b, s, hkv, d = 2, PAGE, 2, 8
    pool_pages = 4
    k_new = _rand(jax.random.key(2), (b, s, hkv, d))
    pages = jnp.array([[1], [pool_pages]], jnp.int32)  # row 1 is padding
    k_pool = jnp.zeros((LAYERS, pool_pages, hkv, PAGE, d))
    v_pool = jnp.zeros((LAYERS, pool_pages, hkv, PAGE, d))
    k_pool, v_pool = write_prompts_paged(k_pool, v_pool, LAYER, pages, k_new, k_new)
    # page 1 of the layer holds row 0's prompt; every other page, and every
    # other layer, still zero
    np.testing.assert_allclose(k_pool[LAYER, 1], k_new[0].swapaxes(0, 1), rtol=1e-6)
    assert float(jnp.abs(k_pool[LAYER, jnp.array([0, 2, 3])]).sum()) == 0.0
    assert float(jnp.abs(k_pool[jnp.array([0, 2])]).sum()) == 0.0


def test_append_tokens_paged_matches_slot_semantics():
    """Appending tokens one at a time through block tables must equal the
    slot cache's contiguous append."""
    n, hkv, d = 3, 2, 8
    maxp = 3
    pool_pages = n * maxp
    # identity-ish table: slot i owns pages [3i, 3i+1, 3i+2]
    table = jnp.arange(pool_pages, dtype=jnp.int32).reshape(n, maxp)

    k_pool = jnp.zeros((LAYERS, pool_pages, hkv, PAGE, d))
    v_pool = jnp.zeros((LAYERS, pool_pages, hkv, PAGE, d))
    k_slot = jnp.zeros((n, hkv, maxp * PAGE, d))
    v_slot = jnp.zeros((n, hkv, maxp * PAGE, d))

    positions = jnp.array([0, PAGE - 1, PAGE], jnp.int32)  # page-boundary cases
    for step in range(4):
        kn = _rand(jax.random.key(10 + step), (n, hkv, d))
        vn = _rand(jax.random.key(20 + step), (n, hkv, d))
        pos = positions + step
        k_pool, v_pool = append_tokens_paged(k_pool, v_pool, LAYER, table, pos, kn, vn)
        k_slot, v_slot = append_tokens(k_slot, v_slot, pos, kn, vn)

    k_view, v_view = gather_kv(k_pool, v_pool, LAYER, table)
    np.testing.assert_allclose(k_view, k_slot, rtol=1e-6)
    np.testing.assert_allclose(v_view, v_slot, rtol=1e-6)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_paged_decode_kernel_matches_gather_path(monkeypatch, hq, hkv):
    """Pallas paged-decode (scalar-prefetched block tables) vs the XLA
    gather fallback, with ragged lengths and shuffled tables."""
    n, d, maxp, pool_pages = 3, 32, 4, 16
    page = 16
    q = _rand(jax.random.key(0), (n, hq, d))
    k_pool = _rand(jax.random.key(1), (LAYERS, pool_pages, hkv, page, d))
    v_pool = _rand(jax.random.key(2), (LAYERS, pool_pages, hkv, page, d))
    rng = np.random.RandomState(0)
    perm = rng.permutation(pool_pages)[: n * maxp].reshape(n, maxp)
    table = jnp.asarray(perm, jnp.int32)
    # OOB-mark the unallocated tail of slot 2's table
    table = table.at[2, 2:].set(pool_pages)
    lengths = jnp.array([page * maxp, 19, page + 3], jnp.int32)

    want = paged_decode_attention(q, k_pool, v_pool, LAYER, table, lengths, backend="xla")
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    got = paged_decode_attention(q, k_pool, v_pool, LAYER, table, lengths, backend="pallas")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def _ragged_case(hkv, d, group, page=8, maxp=3, seed=0):
    """Six lanes whose lengths are 0, 1, page - 1, page, page + 1 and the full
    span; each lane's table holds the pages its length needs and P (out of
    bounds) after them, the empty lane's whole row is P."""
    hq = hkv * group
    lens = [0, 1, page - 1, page, page + 1, maxp * page]
    n, pool = len(lens), len(lens) * maxp + 2  # two pages nobody owns
    ks = jax.random.split(jax.random.key(seed), 3)
    q = _rand(ks[0], (n, hq, d))
    k_pool = _rand(ks[1], (LAYERS, pool, hkv, page, d))
    v_pool = _rand(ks[2], (LAYERS, pool, hkv, page, d))
    table = np.full((n, maxp), pool, np.int32)
    perm = np.random.RandomState(seed).permutation(pool)
    for i, ln in enumerate(lens):
        need = -(-ln // page)
        table[i, :need] = perm[i * maxp: i * maxp + need]
    return q, k_pool, v_pool, table, np.asarray(lens, np.int32)


@pytest.mark.parametrize("group", [2, 4, 8])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("hkv", [1, 2, 8])
def test_paged_decode_kernel_ragged_lengths_every_head_geometry(monkeypatch, hkv, d, group):
    """The kernel reads a whole page of every KV head per copy and stops at
    each lane's last live page: lengths 0, 1, page - 1, page, page + 1 and
    full in ONE batch, out-of-bounds table entries after the live pages,
    for every head geometry the repo builds (a tp shard has 1 or 2 KV
    heads; head_dim 64 and 128; 2, 4 and 8 query heads a KV head)."""
    q, k_pool, v_pool, table, lens = _ragged_case(hkv, d, group)
    table, lengths = jnp.asarray(table), jnp.asarray(lens)
    want = paged_decode_attention(q, k_pool, v_pool, LAYER, table, lengths, backend="xla")
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    got = paged_decode_attention(q, k_pool, v_pool, LAYER, table, lengths, backend="pallas")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[0]).any(), "a lane of length 0 returns zeros, not NaN"


@pytest.mark.parametrize("poison", [float("nan"), 1e30])
def test_paged_decode_kernel_reads_nothing_past_lengths(monkeypatch, poison):
    """Poisoned pool: every page a lane does not own, every owned page past
    its length (the table still names it), the other layers, and the K rows
    past the length inside the last live page hold ``poison``. The result
    equals XLA's over the same pool with the poison zeroed: no dead page is
    read into it. (V rows past the length inside the live page stay finite:
    there both paths multiply them by a probability of exactly 0.)"""
    hkv, d, group, page, maxp = 2, 64, 4, 8, 3
    q, k_pool, v_pool, table, lens = _ragged_case(hkv, d, group, page, maxp, seed=1)
    n, pool = table.shape[0], k_pool.shape[1]
    live = np.zeros((LAYERS, pool, hkv, page, d), bool)   # rows a result may depend on
    fetched = np.zeros((LAYERS, pool), bool)              # pages with at least one live row
    for i, ln in enumerate(lens):
        for t in range(ln):
            live[LAYER, table[i, t // page], :, t % page] = True
            fetched[LAYER, table[i, t // page]] = True
    # the table names a dead page for every lane but the full one: owned, never read
    spare = [p for p in range(pool) if not fetched[LAYER, p]]
    for i, ln in enumerate(lens[:-1]):
        table[i, -(-ln // page)] = spare[i]
    k_clean = jnp.where(live, k_pool, 0.0)
    v_clean = jnp.where(live, v_pool, 0.0)
    k_bad = jnp.where(live, k_pool, poison)
    v_bad = jnp.where(live | fetched[:, :, None, None, None], v_clean, poison)
    table, lengths = jnp.asarray(table), jnp.asarray(lens)
    want = paged_decode_attention(q, k_clean, v_clean, LAYER, table, lengths, backend="xla")
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    got = paged_decode_attention(q, k_bad, v_bad, LAYER, table, lengths, backend="pallas")
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_paged_matches_dense_decode():
    """Paged attention over a contiguous table == dense decode over the
    equivalent [N, Hkv, Smax, D] cache."""
    n, hq, hkv, d, maxp = 2, 4, 2, 16, 3
    page = 8
    pool_pages = n * maxp
    table = jnp.arange(pool_pages, dtype=jnp.int32).reshape(n, maxp)
    q = _rand(jax.random.key(5), (n, hq, d))
    k_pool = _rand(jax.random.key(6), (LAYERS, pool_pages, hkv, page, d))
    v_pool = _rand(jax.random.key(7), (LAYERS, pool_pages, hkv, page, d))
    lengths = jnp.array([maxp * page, 11], jnp.int32)

    k_view, v_view = gather_kv(k_pool, v_pool, LAYER, table)
    np.testing.assert_array_equal(
        k_view, k_pool[LAYER].swapaxes(1, 2).reshape(n, maxp * page, hkv, d).swapaxes(1, 2))
    want = decode_attention(q, k_view, v_view, lengths, backend="xla")
    got = paged_decode_attention(q, k_pool, v_pool, LAYER, table, lengths, backend="xla")
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# -- the in-place append against a plain NumPy reference -----------------------

_APPEND_N, _APPEND_MAXP, _APPEND_POOL, _APPEND_HKV, _APPEND_D = 4, 3, 14, 2, 16


def _append_case(case):
    """(table [N, MaxP], positions [N]) for one named case; P = pool size."""
    p = _APPEND_POOL
    table = np.array([[7, 2, 9], [4, 5, 3], [11, 0, 13], [1, 12, 6]], np.int32)
    if case == "idle_lanes":          # lanes 1 and 3 idle: every entry P
        table[1] = table[3] = p
        pos = [PAGE + 3, 0, 5, 2 * PAGE]
    elif case == "page_boundary":     # last row of a page, first row of the next
        pos = [PAGE - 1, PAGE, 2 * PAGE - 1, 2 * PAGE]
    elif case == "past_span":         # lanes 0 and 2 beyond MaxP * page: dropped
        pos = [_APPEND_MAXP * PAGE, 4, _APPEND_MAXP * PAGE + 5, 0]
    elif case == "unallocated_page":  # lane 2's position falls on an entry == P
        table[2, 1:] = p
        pos = [1, 2, PAGE + 1, 3]
    else:                             # "all_live"
        pos = [0, 9, 17, 23]
    return table, np.asarray(pos, np.int32)


def _numpy_append(plane, layer, table, pos, rows):
    """plane[layer, table[n, pos // page], :, pos % page] = rows[n], skip OOB."""
    out = plane.copy()
    page = plane.shape[3]
    for n, q in enumerate(pos):
        if q // page >= table.shape[1] or table[n, q // page] >= plane.shape[1]:
            continue
        out[layer, table[n, q // page], :, q % page] = rows[n]
    return out


@pytest.mark.parametrize("case", ["idle_lanes", "page_boundary", "past_span",
                                  "unallocated_page", "all_live"])
@pytest.mark.parametrize("kind", ["bf16", "int8", "int4"])
def test_append_in_place_matches_numpy_reference(kind, case):
    """Bit-exact: row ``n`` lands at (layer, table[n, pos // page], :,
    pos % page), OOB rows write nothing, and every byte of the pool outside
    the written rows — other layers included — is untouched."""
    from gofr_tpu.ops.kvcache import quantize_row
    from gofr_tpu.ops.paged import append_tokens_paged_q, append_tokens_paged_q4
    from gofr_tpu.ops.quant import pack_int4, quantize_row_int4

    n, hkv, d, pool = _APPEND_N, _APPEND_HKV, _APPEND_D, _APPEND_POOL
    table, pos = _append_case(case)
    rng = np.random.RandomState(3)
    new = jnp.asarray(rng.standard_normal((n, hkv, d)), jnp.bfloat16)
    shape = (LAYERS, pool, hkv, PAGE, d)

    if kind == "bf16":
        k0 = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        v0 = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        got = jax.jit(append_tokens_paged)(k0, v0, LAYER, table, pos, new, new + 1)
        before = (k0, v0)
        rows = (new, new + 1)
    else:
        if kind == "int8":
            append, (q, sc), width, qdtype = append_tokens_paged_q, quantize_row(new), d, np.int8
        else:
            qq, sc = quantize_row_int4(new)
            append, q, width, qdtype = append_tokens_paged_q4, pack_int4(qq), d // 2, np.uint8
        q0 = jnp.asarray(rng.randint(0, 100, shape[:4] + (width,)), qdtype)
        s0 = jnp.asarray(rng.standard_normal(shape[:4]), jnp.bfloat16)
        # op by op, like the reference rows above: a fused quantiser may round one value otherwise
        got = append(q0, s0, LAYER, table, pos, new)
        before = (q0, s0)
        rows = (q, sc.astype(jnp.bfloat16))

    for plane0, plane1, r in zip(before, got, rows):
        want = _numpy_append(np.asarray(plane0), LAYER, table, pos, np.asarray(r))
        np.testing.assert_array_equal(np.asarray(plane1), want)
    # the case is what its name says: this many lanes wrote, the rest dropped
    live = [i for i, q in enumerate(pos)
            if q // PAGE < _APPEND_MAXP and table[i, q // PAGE] < pool]
    assert len(live) == {"idle_lanes": 2, "page_boundary": 4, "past_span": 2,
                         "unallocated_page": 3, "all_live": 4}[case]


def _numpy_write_run(plane, layer, pages, offsets, rows):
    """plane[layer, pages[b, p // page], :, p % page] = rows[b, s] for
    p = offsets[b] + s; pages past the table's span and entries == P skip."""
    out = plane.copy()
    page = plane.shape[3]
    for b in range(rows.shape[0]):
        for i in range(rows.shape[1]):
            q = int(offsets[b]) + i
            if q // page < pages.shape[1] and pages[b, q // page] < plane.shape[1]:
                out[layer, pages[b, q // page], :, q % page] = rows[b, i]
    return out


_RUNS = {  # case -> (S, offsets or None): how ops/paged._put_run writes it
    "whole_prompt": (2 * PAGE, None),             # page-aligned: blocks written as they are
    "unaligned_chunk": (PAGE, [3, PAGE + 5, 0]),  # a page or more, off the grid: read, patch, write back
    "tail_past_span": (2 * PAGE, [2 * PAGE + 2, 5, 0]),  # row 0's tail leaves the table: dropped
    "short_run": (3, [PAGE - 2, 4, 0]),           # shorter than a page (verify): rows, across a boundary
}


@pytest.mark.parametrize("case", sorted(_RUNS))
@pytest.mark.parametrize("kind", ["bf16", "int8", "int4"])
def test_prompt_write_in_place_matches_numpy_reference(kind, case):
    """write_prompts_paged* against the same plain reference, bit-exact,
    with a padding row (every entry P) and an unallocated page in the
    tables, and every byte outside the written rows untouched."""
    from gofr_tpu.ops.kvcache import quantize_row
    from gofr_tpu.ops.paged import write_prompts_paged_q, write_prompts_paged_q4
    from gofr_tpu.ops.quant import pack_int4, quantize_row_int4

    b, hkv, d, pool = 3, 2, 16, 14
    s_len, offsets = _RUNS[case]
    pages = np.array([[7, 2, 9], [4, pool, 3], [pool, pool, pool]], np.int32)  # row 2 = padding
    rng = np.random.RandomState(5)
    new = jnp.asarray(rng.standard_normal((b, s_len, hkv, d)), jnp.bfloat16)
    offs = None if offsets is None else jnp.asarray(offsets, jnp.int32)
    ref_offs = np.zeros(b, np.int32) if offsets is None else np.asarray(offsets)
    shape = (LAYERS, pool, hkv, PAGE, d)

    if kind == "bf16":
        before = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16),
                  jnp.asarray(rng.standard_normal(shape), jnp.bfloat16))
        got = jax.jit(write_prompts_paged)(*before, LAYER, pages, new, new + 1, offs)
        rows = (new, new + 1)
    else:
        if kind == "int8":
            write, (q, sc), width, qdtype = write_prompts_paged_q, quantize_row(new), d, np.int8
        else:
            qq, sc = quantize_row_int4(new)
            write, q, width, qdtype = write_prompts_paged_q4, pack_int4(qq), d // 2, np.uint8
        before = (jnp.asarray(rng.randint(0, 100, shape[:4] + (width,)), qdtype),
                  jnp.asarray(rng.standard_normal(shape[:4]), jnp.bfloat16))
        # op by op, like the reference rows above: a fused quantiser may round one value otherwise
        got = write(*before, LAYER, pages, new, offs)
        rows = (q, sc.astype(jnp.bfloat16))

    for plane0, plane1, r in zip(before, got, rows):
        want = _numpy_write_run(np.asarray(plane0), LAYER, pages, ref_offs, np.asarray(r))
        np.testing.assert_array_equal(np.asarray(plane1), want)
    assert (np.asarray(got[0]) != np.asarray(before[0])).any()


# -- structural guard: the pool is carried, never scanned over or restacked -----
#
# A scan cannot alias its ``ys`` onto its ``xs``: a pool handed to the layer
# scan as ``xs`` and taken back as ``ys`` is rebuilt — a second pool, every
# layer restacked into it, whole-pool copies around it — on every step
# (ROADMAP S3; 41 of 66 ms a decode step on the v5e before PR 27). These two
# tests are what a CPU-only review has against its coming back.

_POOL_KINDS = {"bf16": "make_paged_cache", "int8": "make_paged_cache_q",
               "int4": "make_paged_cache_q4"}


def _scans(jaxpr):
    """Every scan equation of a jaxpr, nested calls and loop bodies included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("kind", sorted(_POOL_KINDS))
@pytest.mark.parametrize("program", ["decode", "prefill", "chunk_prefill", "verify"])
def test_no_layer_scan_streams_a_pool_plane(program, kind):
    from gofr_tpu.models import LlamaConfig, llama

    cfg = LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.key(0))
    cache = getattr(llama, _POOL_KINDS[kind])(cfg, 7, PAGE)
    n, maxp = 3, 2
    table = jnp.zeros((n, maxp), jnp.int32)
    toks, pos = jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32)
    prompt = jnp.zeros((n, PAGE), jnp.int32)
    fn, args = {
        "decode": (llama.decode_step_paged, (params, toks, pos, cache, table)),
        "prefill": (llama.prefill_paged, (params, prompt, pos, cache, table)),
        "chunk_prefill": (llama.prefill_paged, (params, prompt, pos, cache, table, pos)),
        "verify": (llama.verify_step_paged, (params, prompt[:, :3], pos, cache, table)),
    }[program]
    jaxpr = jax.make_jaxpr(lambda *a: fn(cfg, *a))(*args)

    planes = {leaf.shape for leaf in jax.tree.leaves(cache)}
    per_layer = {shape[1:] for shape in planes}
    scans = list(_scans(jaxpr.jaxpr))
    assert scans, "the layer scan is gone: update this guard with the program"
    for eqn in scans:
        carried = eqn.params["num_consts"] + eqn.params["num_carry"]
        streamed = list(eqn.invars[carried:]) + list(eqn.outvars[eqn.params["num_carry"]:])
        shapes = {v.aval.shape for v in streamed}
        assert not shapes & planes, f"a scan streams a whole pool plane: {shapes & planes}"
        # inside the body an xs/ys element has lost its leading axis
        inner = eqn.params["jaxpr"].jaxpr
        body = {v.aval.shape for v in inner.invars[carried:]}
        body |= {v.aval.shape for v in inner.outvars[eqn.params["num_carry"]:]}
        assert not body & per_layer, f"a scan slices the pool per layer: {body & per_layer}"
    # and the pool does ride in a carry
    assert any(planes <= {v.aval.shape for v in e.invars[e.params["num_consts"]:
                                                          e.params["num_consts"] + e.params["num_carry"]]}
               for e in scans)


def test_decode_chunk_needs_less_scratch_than_one_pool_plane():
    """The compiled ``_decode_chunk`` of a tiny paged engine: its temporaries
    stay below ONE plane of the pool it updates (the restacked form needed a
    second pool: two planes and more)."""
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import LlamaConfig, llama
    from gofr_tpu.tpu.engine import GenerateEngine

    cfg = LlamaConfig.tiny(num_layers=8)  # a plane is 8 gathered views deep
    eng = GenerateEngine(llama, cfg, llama.init(cfg, jax.random.key(0)),
                         new_mock_container(), slots=4, max_len=64,
                         prefill_buckets=[8, 16], kv_layout="paged", page_size=PAGE)
    try:
        lowered = eng._decode_chunk.lower(
            eng.params, eng._base_key, eng.cache, eng.decode_chunk,
            jnp.zeros((5 + eng.pages_per_slot, eng.num_slots), jnp.int32), eng._zero_carry())
        plane = eng.cache.k.size * eng.cache.k.dtype.itemsize
    finally:
        eng.stop()
    temp = lowered.compile().memory_analysis().temp_size_in_bytes
    assert temp < plane, f"temporaries {temp} B against a pool plane of {plane} B"
