"""Tensor-parallel paged-KV pool (ISSUE 19 tentpole): the pool's K/V and
scale planes shard over the mesh's tp axis along the KV-head dimension,
and every consumer is shard-aware — decode attention runs per-shard under
shard_map with the reduce folded into the o-projection, writes/spec/
preemption/prefix swap-in operate on shard-local views, and the byte
accounting reports per-device numbers. The contract under test: a sharded
engine serves token-for-token what the SAME configuration serves on a
single device (the only valid comparison for int4, whose quantization
legitimately shifts greedy ties vs a full-precision reference), holds
1/tp of every plane per device, and keeps the page-refcount invariants
through spec rounds, preemption-by-recompute, and host-tier swap-in.
Runs on the conftest-forced 8-virtual-CPU-device mesh (jaxpin.pin_cpu)."""

import re
import time

import jax
import numpy as np
import pytest

from gofr_tpu.container import new_mock_container
from gofr_tpu.models import ModelSpec
from gofr_tpu.ops.paged import kv_plane_bytes_per_position
from gofr_tpu.testutil import (
    assert_page_refs_consistent,
    assert_paged_pool_consistent,
    greedy_reference,
    tiny_f32_llama,
)
from gofr_tpu.tpu.engine import build_engine

pytestmark = pytest.mark.quick

MESH = "dp:2,tp:4"


@pytest.fixture(scope="module")
def setup():
    cfg, params = tiny_f32_llama()
    return cfg, params, greedy_reference(cfg, params)


def _build(cfg, config=None, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_prefill_batch", 2)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("page_size", 8)
    container = new_mock_container(config)
    return build_engine(ModelSpec("llama", cfg, task="generate"),
                        container, seed=3, **kw)


def _sharded(cfg, **kw):
    return _build(cfg, {"TPU_MESH": MESH, "ENGINE_KV_SHARD": "tp"}, **kw)


def _prompts(n=4):
    return [[1 + (13 * i + j) % 200 for j in range(4 + i % 3)]
            for i in range(n)]


def _counter_sum(eng, name):
    m = eng.metrics.get(name)
    return sum(m._values.values()) if m is not None else 0


# -- token exactness vs single device, all three pool dtypes -------------------


@pytest.mark.parametrize("kvq", ["", "int8", "int4"])
def test_sharded_serving_token_exact_vs_single_device(setup, kvq):
    """The tentpole acceptance: for each KV dtype, the tp-sharded pool
    serves exactly the tokens the same engine produces on one device —
    the per-shard decode + o-projection psum changes nothing observable.
    The dense pool must additionally match the incremental f32 greedy
    reference (quantized pools compare same-dtype only)."""
    cfg, params, ref = setup
    prompts = _prompts()
    kw = {"kv_quantize": kvq} if kvq else {}
    ref_eng = _build(cfg, **kw)
    try:
        assert ref_eng.kv_shards == 1
        want = [ref_eng.generate(p, max_new_tokens=8, timeout=300)["tokens"]
                for p in prompts]
    finally:
        ref_eng.stop()
    if not kvq:
        assert want == [ref(p, 8) for p in prompts], (
            "single-device dense engine diverged from the greedy reference")
    eng = _sharded(cfg, **kw)
    try:
        assert eng.kv_shards == 4
        for i, p in enumerate(prompts):
            got = eng.generate(p, max_new_tokens=8, timeout=300)["tokens"]
            assert got == want[i], (
                f"request {i} diverged on the sharded {kvq or 'bf16'} pool: "
                f"{got} != {want[i]}")
        assert_page_refs_consistent(eng)
    finally:
        eng.stop()


def test_pool_planes_sharded_over_tp_and_stay_sharded(setup):
    """Every pool plane commits with the tp axis on the KV-head dim
    (axis 2) and each device holds exactly Hkv/tp heads — and serving
    must not silently reshard: donated step outputs keep the commitment,
    else the capacity win evaporates after the first decode."""
    cfg, params, _ = setup

    def check(eng):
        for leaf in jax.tree.leaves(eng.kv_cache):
            spec = tuple(leaf.sharding.spec)
            assert len(spec) > 2 and spec[2] == "tp", spec
            for sh in leaf.addressable_shards:
                assert sh.data.shape[2] == leaf.shape[2] // 4, (
                    leaf.shape, sh.data.shape)

    eng = _sharded(cfg)
    try:
        check(eng)
        eng.generate(_prompts(1)[0], max_new_tokens=4, timeout=300)
        check(eng)
    finally:
        eng.stop()


@pytest.mark.parametrize("kind", ["bf16", "int8", "int4"])
def test_decode_step_returns_the_pool_sharded_as_given(setup, kind):
    """The model-level program itself, no engine: a decode step under
    ``KVShardCtx`` takes a tp-sharded pool and returns every plane with the
    sharding it was given — the in-place write partitions along the KV-head
    axis like the read, and no plane is gathered onto one device (no
    all-gather of a plane in the compiled step). Logits match the
    unsharded step."""
    import re

    import jax.numpy as jnp

    from gofr_tpu.models import llama
    from gofr_tpu.ops.paged import KVShardCtx, kv_shard_scope, pool_sharding
    from gofr_tpu.parallel.mesh import build_mesh

    cfg, params, _ = setup
    make = {"bf16": llama.make_paged_cache, "int8": llama.make_paged_cache_q,
            "int4": llama.make_paged_cache_q4}[kind]
    mesh = build_mesh("dp:2,tp:4")
    table = jnp.asarray([[5, 2, 9], [0, 7, 12], [12, 12, 12]], jnp.int32)  # lane 2 idle
    toks, pos = jnp.asarray([3, 8, 1], jnp.int32), jnp.asarray([9, 17, 0], jnp.int32)

    want, _ = llama.decode_step_paged(cfg, params, toks, pos, make(cfg, 12, 8), table)
    cache = make(cfg, 12, 8, sharding=pool_sharding(mesh))
    given = [leaf.sharding for leaf in jax.tree.leaves(cache)]
    with kv_shard_scope(KVShardCtx(mesh=mesh, axis="tp", shards=4)):
        compiled = llama.decode_step_paged.lower(cfg, params, toks, pos, cache, table).compile()
        got, out = llama.decode_step_paged(cfg, params, toks, pos, cache, table)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    for leaf, sharding in zip(jax.tree.leaves(out), given):
        assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim), (leaf.sharding, sharding)
        assert {sh.data.shape[2] for sh in leaf.addressable_shards} == {leaf.shape[2] // 4}
    # a plane gathered whole would be an all-gather whose result has all 4 KV heads
    for shape in re.findall(r"= \w+\[([\d,]+)\][^=]* all-gather", compiled.as_text()):
        dims = [int(x) for x in shape.split(",")]
        assert not (len(dims) >= 4 and dims[0] == cfg.num_layers), f"a pool plane is all-gathered: {dims}"


def test_paged_kernel_on_the_sharded_pool_matches_xla(setup, monkeypatch):
    """The bf16 paged-decode kernel under ``shard_map``: each shard runs the
    same kernel over ONE of the 4 KV heads (the block's head count comes from
    the local plane), lengths ragged across a page boundary, lane 2 idle. The
    live lanes' logits match the unsharded XLA read path's; the idle lane's are
    finite (the kernel skips it and hands back zeros, where the XLA path reads
    a clamped page that nobody owns)."""
    import jax.numpy as jnp

    from gofr_tpu.models import llama
    from gofr_tpu.ops.paged import KVShardCtx, kv_shard_scope, pool_sharding
    from gofr_tpu.parallel.mesh import build_mesh

    cfg, params, _ = setup
    mesh = build_mesh(MESH)
    table = jnp.asarray([[5, 2, 9], [0, 7, 12], [12, 12, 12]], jnp.int32)  # lane 2 idle
    toks, pos = jnp.asarray([3, 8, 1], jnp.int32), jnp.asarray([16, 7, 0], jnp.int32)
    fill = jax.random.normal(jax.random.key(5), (2,) + llama.make_paged_cache(cfg, 12, 8).k.shape)

    def cache(**kw):
        c = llama.make_paged_cache(cfg, 12, 8, **kw)
        return type(c)(k=c.k + fill[0], v=c.v + fill[1])

    jax.clear_caches()  # decode_step_paged's trace holds the backend it was first made with
    monkeypatch.delenv("GOFR_PALLAS_INTERPRET", raising=False)  # on the CPU 'auto' is the XLA read path
    want, _ = llama.decode_step_paged(cfg, params, toks, pos, cache(), table)
    from gofr_tpu.ops.pallas import paged_decode as kernels

    seen = []  # (query heads, KV heads) of each kernel trace: a shard's own

    def spy(q, k_pool, *args, _kernel=kernels.paged_decode_attention, **kw):
        seen.append((q.shape[1], k_pool.shape[2]))
        return _kernel(q, k_pool, *args, **kw)

    monkeypatch.setattr(kernels, "paged_decode_attention", spy)
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    jax.clear_caches()
    with kv_shard_scope(KVShardCtx(mesh=mesh, axis="tp", shards=4)):  # interpreter: 'auto' is the kernel
        got, _ = llama.decode_step_paged(
            cfg, params, toks, pos, cache(sharding=pool_sharding(mesh)), table)
    jax.clear_caches()
    assert seen and set(seen) == {(cfg.num_heads // 4, cfg.num_kv_heads // 4)}, seen
    np.testing.assert_allclose(np.asarray(got)[:2], np.asarray(want)[:2], rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(got)[2]).all()


@pytest.mark.parametrize("heads_a_shard", [1, 2])
def test_kernel_append_on_the_sharded_pool_matches_the_unsharded_scatter(monkeypatch, heads_a_shard):
    """A decode step's append AND attention as the kernel's one call under
    ``shard_map``: each of the 4 shards writes and attends its own 1 or 2 KV
    heads (Llama-1B's and Mistral's width at ``tp:4``); the planes come back
    sharded as they went in and equal the unsharded scatter's bit for bit,
    the output the unsharded append-then-attend; lane 2 idle, lane 3 past
    its table's span, lane 0 opening a fresh page. No collective is added:
    the compiled call holds none."""
    import jax.numpy as jnp

    from gofr_tpu.ops import attention
    from gofr_tpu.ops.paged import KVShardCtx, append_tokens_paged, kv_shard_scope, pool_sharding
    from gofr_tpu.ops.pallas import paged_decode as kernels
    from gofr_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MESH)
    hkv, group, page, d, pool = 4 * heads_a_shard, 2, 16, 128, 9
    ks = jax.random.split(jax.random.key(7), 5)
    q = jax.random.normal(ks[0], (4, hkv * group, d))
    k_pool = jax.random.normal(ks[1], (2, pool, hkv, page, d))
    v_pool = jax.random.normal(ks[2], (2, pool, hkv, page, d))
    k_new, v_new = jax.random.normal(ks[3], (4, hkv, d)), jax.random.normal(ks[4], (4, hkv, d))
    table = jnp.asarray([[5, 2], [0, 7], [pool, pool], [3, 6]], jnp.int32)
    pos = jnp.asarray([16, 7, 0, 32], jnp.int32)

    want_k, want_v = append_tokens_paged(k_pool, v_pool, 1, table, pos, k_new, v_new)
    want = attention.paged_decode_attention(q, want_k, want_v, 1, table, pos + 1, backend="xla")

    seen = []  # KV heads of each kernel trace: a shard's own

    def spy(q, k_new, v_new, k_pool, *args, _kernel=kernels.paged_decode_append_attention, **kw):
        seen.append((q.shape[1], k_new.shape[1], k_pool.shape[2]))
        return _kernel(q, k_new, v_new, k_pool, *args, **kw)

    monkeypatch.setattr(kernels, "paged_decode_append_attention", spy)
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    sharding = pool_sharding(mesh)
    step = jax.jit(lambda *a: attention.paged_decode_append_attention(*a[:5], 1, *a[5:]), donate_argnums=(3, 4))
    args = (q, k_new, v_new, jax.device_put(k_pool, sharding), jax.device_put(v_pool, sharding), table, pos)
    with kv_shard_scope(KVShardCtx(mesh=mesh, axis="tp", shards=4)):
        compiled = step.lower(*args).compile().as_text()
        got, k_out, v_out = step(*args)
    assert seen and set(seen) == {(heads_a_shard * group, heads_a_shard, heads_a_shard)}, seen
    assert not re.search(r"all-gather|all-reduce|collective-permute|all-to-all", compiled)
    for plane, ref in ((k_out, want_k), (v_out, want_v)):
        assert plane.sharding.is_equivalent_to(sharding, plane.ndim), plane.sharding
        assert {sh.data.shape[2] for sh in plane.addressable_shards} == {heads_a_shard}
        assert np.array_equal(np.asarray(plane), np.asarray(ref))
    live = [0, 1, 3]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5)


# -- spec rounds + preemption + prefix swap-in on the sharded pool -------------


def test_spec_and_preemption_on_sharded_pool(setup):
    """Speculative rounds and preemption-by-recompute on the sharded pool:
    spec writes and the requeued prompt's re-prefill both go through the
    shard-local write path, and under a minimum-legal pool contention must
    stay token-exact vs the greedy reference while the refcounts survive."""
    cfg, params, ref = setup
    rngs = np.random.RandomState(11)
    prompts = []
    for i in range(8):  # every 3rd arrival long enough to contend the pool
        n = 15 + (i % 2) * 4 if i % 3 == 2 else 2 + i % 4
        prompts.append([int(x) for x in rngs.randint(1, 200, size=n)])
    want = [ref(p, 12) for p in prompts]
    eng = _sharded(cfg, slots=3, total_pages=10, spec_tokens=2, decode_chunk=4)
    try:
        assert eng.kv_shards == 4 and eng.spec_tokens == 2
        reqs = []
        for p in prompts:  # paced arrivals, not one up-front burst
            time.sleep(0.01)
            reqs.append(eng.submit(p, max_new_tokens=12, timeout=300))
        results = [r.result(300) for r in reqs]
        assert _counter_sum(eng, "app_tpu_preemptions") >= 1, (
            "pool was not small enough to exercise preemption")
        for i, r in enumerate(results):
            assert r["tokens"] == want[i], (
                f"request {i} diverged under spec+preemption: "
                f"{r['tokens']} != {want[i]}")
        assert_page_refs_consistent(eng)
    finally:
        eng.stop()


def test_prefix_spill_swapin_on_sharded_pool(setup):
    """Host-tier spill and swap-in on the sharded pool: the spilled host
    copy and the device_put promoting it back must round-trip the
    SHARD-LOCAL views without ever materializing a replicated plane — a
    warm hit after forced spill replays token-exactly."""
    cfg, params, ref = setup
    prompt = [(11 * i) % 190 + 1 for i in range(20)]  # 2 full pages @ 8
    want = ref(prompt, 6)
    eng = _sharded(cfg, total_pages=12, prefix_host_mb=8.0)
    try:
        cold = eng.generate(prompt, max_new_tokens=6, timeout=300)
        assert cold["tokens"] == want, "cold sharded run diverged"
        for r in range(5):  # distinct prompts until pressure spills
            eng.generate([(r * 37 + 13 * i) % 180 + 2 for i in range(18)],
                         max_new_tokens=4, timeout=300)
        assert eng._prefix.host_pages > 0, "pool pressure never spilled"
        warm = eng.generate(prompt, max_new_tokens=6, timeout=300)
        assert warm["tokens"] == want, "host-tier swap-in changed tokens"
        assert _counter_sum(eng, "app_tpu_prefix_swapin_pages_total") >= 1
        assert_page_refs_consistent(eng)
        assert_paged_pool_consistent(eng, slots_empty=True)
    finally:
        eng.stop()


# -- per-device byte accounting ------------------------------------------------


def test_kv_plane_bytes_shard_divisor():
    """The analytic estimator's per-device mode: shards divides the head
    count exactly (never pads) and composes with every dtype contract."""
    for dt in ("bf16", "int8", "int4"):
        full = kv_plane_bytes_per_position(2, 4, 8, dt, dense_bytes=4)
        per = kv_plane_bytes_per_position(2, 4, 8, dt, dense_bytes=4, shards=4)
        assert per * 4 == full, (dt, per, full)
    with pytest.raises(ValueError, match="not divisible"):
        kv_plane_bytes_per_position(2, 4, 8, shards=3)


def test_page_pool_stats_report_shard_local_bytes(setup):
    """/debug/perf and the pool gauges ride page_pool_stats: byte fields
    must be SHARD-LOCAL (per-device) so a fleet rollup that sums parts
    sees parts — and they must equal what is actually resident per
    device, not a logical footprint divided on faith."""
    cfg, params, _ = setup
    eng = _sharded(cfg)
    try:
        stats = eng.page_pool_stats()
        assert stats["kv_shards"] == 4
        logical = sum(leaf.nbytes for leaf in jax.tree.leaves(eng.kv_cache))
        assert stats["pool_bytes_device"] == logical // 4
        assert stats["page_bytes_device"] == eng._page_bytes // 4
        dev0 = jax.devices()[0]
        resident = sum(
            sh.data.nbytes for leaf in jax.tree.leaves(eng.kv_cache)
            for sh in leaf.addressable_shards if sh.device == dev0)
        assert resident == stats["pool_bytes_device"], (
            "per-device gauge diverges from resident bytes")
        assert eng.replay_config()["engine"]["kv_shards"] == 4
        # and the DECLARED gauge actually reaches Prometheus exposition
        # (an undeclared name is silently dropped by the registry)
        cont = eng.container
        cont.register_engine("gen", eng)
        cont._sample_perf_metrics()
        line = next(
            ln for ln in cont.metrics.expose_text().splitlines()
            if ln.startswith("app_tpu_kv_pool_device_bytes{"))
        assert 'kv_shards="4"' in line and str(resident) in line, line
    finally:
        eng.stop()


def test_unsharded_stats_are_unchanged(setup):
    """ENGINE_KV_SHARD=off: kv_shards=1 and the per-device byte fields
    equal the logical footprint — today's accounting bit-for-bit."""
    cfg, params, _ = setup
    eng = _build(cfg, {"TPU_MESH": MESH, "ENGINE_KV_SHARD": "off"})
    try:
        assert eng.kv_shards == 1
        stats = eng.page_pool_stats()
        assert stats["kv_shards"] == 1
        assert stats["page_bytes_device"] == eng._page_bytes
        assert stats["pool_bytes_device"] == sum(
            leaf.nbytes for leaf in jax.tree.leaves(eng.kv_cache))
    finally:
        eng.stop()


# -- resolution gates ----------------------------------------------------------


def test_kv_shard_mode_gating(setup):
    """'auto' stands down silently when the geometry can't split; an
    explicit 'tp' request must raise instead of silently serving a
    replicated pool; unknown modes are config errors."""
    cfg, params, _ = setup
    # no tp axis at all: auto -> unsharded, explicit -> error
    eng = _build(cfg, {"TPU_MESH": "dp:2", "TPU_DEVICES": "2"})
    try:
        assert eng.kv_shards == 1
    finally:
        eng.stop()
    with pytest.raises(ValueError, match="ENGINE_KV_SHARD=tp impossible"):
        _build(cfg, {"TPU_MESH": "dp:2", "TPU_DEVICES": "2",
                     "ENGINE_KV_SHARD": "tp"})
    # tp=8 does not divide num_kv_heads=4: same split
    eng = _build(cfg, {"TPU_MESH": "tp:8"})
    try:
        assert eng.kv_shards == 1
    finally:
        eng.stop()
    with pytest.raises(ValueError, match="do not divide"):
        _build(cfg, {"TPU_MESH": "tp:8", "ENGINE_KV_SHARD": "tp"})
    with pytest.raises(ValueError, match="use 'auto', 'tp' or 'off'"):
        _build(cfg, {"TPU_MESH": MESH, "ENGINE_KV_SHARD": "sideways"})
