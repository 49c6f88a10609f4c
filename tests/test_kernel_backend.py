"""Which implementation serves a decode op: the rule
(ops/attention.resolve_backend), what the engine reports of it, and the
fused int8-KV paged-decode kernel's parity with the XLA gather path.

Kernel parity runs under the Pallas interpreter on the CPU test mesh
(tests/test_pallas.py convention) — the whole module is CPU-safe and quick.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops import pallas
from gofr_tpu.ops.attention import (
    decode_attention,
    paged_decode_attention_q,
    resolve_backend,
)

pytestmark = pytest.mark.quick


LAYER = 1  # the ops read whole [L, P, ...] planes at a layer index


def _qpools(key, pool, hkv, page, d):
    """int8 K/V page pools (2 layers) with non-trivial, DISTINCT
    per-position scales — a wrong ks/vs fold cannot cancel out."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    kq = jax.random.randint(k1, (2, pool, hkv, page, d), -127, 128, jnp.int8)
    vq = jax.random.randint(k2, (2, pool, hkv, page, d), -127, 128, jnp.int8)
    ks = jax.random.uniform(k3, (2, pool, hkv, page), minval=0.005,
                            maxval=0.05).astype(jnp.bfloat16)
    vs = jax.random.uniform(k4, (2, pool, hkv, page), minval=0.02,
                            maxval=0.2).astype(jnp.bfloat16)
    return kq, vq, ks, vs


# -- fused int8 paged-decode kernel parity (interpreter mode) -------------------


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_paged_decode_q_kernel_matches_gather_path(monkeypatch, hq, hkv):
    """Fused kernel vs the XLA gather path: ragged lengths, a shuffled
    block table, an OOB-marked unallocated tail, and GQA group > 1."""
    n, d, maxp, pool, page = 3, 32, 4, 16, 16
    key = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(key, 9), (n, hq, d))
    kq, vq, ks, vs = _qpools(key, pool, hkv, page, d)
    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.permutation(pool)[: n * maxp].reshape(n, maxp), jnp.int32)
    table = table.at[2, 2:].set(pool)  # OOB unallocated tail
    lengths = jnp.array([page * maxp, 19, page + 3], jnp.int32)

    want = paged_decode_attention_q(q, kq, vq, ks, vs, LAYER, table, lengths, backend="xla")
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    got = paged_decode_attention_q(q, kq, vq, ks, vs, LAYER, table, lengths, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_paged_decode_q_empty_slot_zero_not_nan(monkeypatch):
    """A freshly-recycled slot (length 0) must emit zeros, never NaN."""
    n, hq, hkv, d, maxp, pool, page = 2, 4, 2, 16, 2, 6, 8
    key = jax.random.key(1)
    q = jax.random.normal(jax.random.fold_in(key, 9), (n, hq, d))
    kq, vq, ks, vs = _qpools(key, pool, hkv, page, d)
    table = jnp.arange(n * maxp, dtype=jnp.int32).reshape(n, maxp)
    lengths = jnp.array([0, 5], jnp.int32)

    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    got = np.asarray(paged_decode_attention_q(
        q, kq, vq, ks, vs, LAYER, table, lengths, backend="pallas"))
    assert not np.isnan(got).any()
    np.testing.assert_allclose(got[0], np.zeros_like(got[0]), atol=1e-7)
    want = np.asarray(paged_decode_attention_q(
        q, kq, vq, ks, vs, LAYER, table, lengths, backend="xla"))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_paged_decode_q_scale_folds_match_dequantized_dense(monkeypatch):
    """Both in-kernel scale folds carry the dequant semantics exactly: the
    fused output equals dense decode over the explicitly dequantized
    (int8 * scale) logical views."""
    from gofr_tpu.ops.paged import gather_kv_q

    n, hq, hkv, d, maxp, pool, page = 2, 8, 2, 16, 3, 8, 8
    key = jax.random.key(2)
    q = jax.random.normal(jax.random.fold_in(key, 9), (n, hq, d))
    kq, vq, ks, vs = _qpools(key, pool, hkv, page, d)
    rng = np.random.RandomState(1)
    table = jnp.asarray(rng.permutation(pool)[: n * maxp].reshape(n, maxp), jnp.int32)
    lengths = jnp.array([maxp * page, 11], jnp.int32)

    gkq, gks = gather_kv_q(kq, ks, LAYER, table)
    gvq, gvs = gather_kv_q(vq, vs, LAYER, table)
    k_dense = gkq.astype(jnp.float32) * gks.astype(jnp.float32)[..., None]
    v_dense = gvq.astype(jnp.float32) * gvs.astype(jnp.float32)[..., None]
    want = decode_attention(q, k_dense, v_dense, lengths, backend="xla")

    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    got = paged_decode_attention_q(q, kq, vq, ks, vs, LAYER, table, lengths, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_fused_path_skips_gather(monkeypatch):
    """The acceptance-criterion proof: with the pallas backend the fused
    path never materializes a gathered logical view — gather_kv_q is not
    called at all."""
    import gofr_tpu.ops.paged as paged_mod

    def boom(*a, **k):
        raise AssertionError("gather_kv_q called on the fused path")

    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(paged_mod, "gather_kv_q", boom)
    n, hq, hkv, d, maxp, pool, page = 2, 4, 2, 16, 2, 4, 8
    key = jax.random.key(3)
    q = jax.random.normal(jax.random.fold_in(key, 9), (n, hq, d))
    kq, vq, ks, vs = _qpools(key, pool, hkv, page, d)
    table = jnp.arange(n * maxp, dtype=jnp.int32).reshape(n, maxp)
    lengths = jnp.array([page, 3], jnp.int32)
    out = paged_decode_attention_q(q, kq, vq, ks, vs, LAYER, table, lengths, backend="pallas")
    assert np.isfinite(np.asarray(out)).all()


def test_paged_decode_q_explicit_pallas_bad_page_raises(monkeypatch):
    """Explicit backend='pallas' with a page size the kernel cannot tile
    must raise, mirroring paged_decode_attention (ADVICE round 2)."""
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    n, hq, hkv, d, maxp, pool, page = 2, 4, 2, 16, 2, 4, 12  # 12 % 8 != 0
    key = jax.random.key(4)
    q = jax.random.normal(jax.random.fold_in(key, 9), (n, hq, d))
    kq, vq, ks, vs = _qpools(key, pool, hkv, page, d)
    table = jnp.arange(n * maxp, dtype=jnp.int32).reshape(n, maxp)
    lengths = jnp.array([page, 3], jnp.int32)
    with pytest.raises(ValueError, match=r"page_size % 8 == 0.*pages of 12"):
        paged_decode_attention_q(q, kq, vq, ks, vs, LAYER, table, lengths, backend="pallas")
    # 'auto' that resolves to the kernel fails as loudly, with the shape: no quiet second path
    with pytest.raises(ValueError, match=r"pages of 12 \(plane \(2, 4, 2, 12, 16\)\)"):
        paged_decode_attention_q(q, kq, vq, ks, vs, LAYER, table, lengths, backend="auto")
    monkeypatch.delenv("GOFR_PALLAS_INTERPRET")  # where 'auto' is XLA, any page size serves
    got = paged_decode_attention_q(q, kq, vq, ks, vs, LAYER, table, lengths, backend="auto")
    want = paged_decode_attention_q(q, kq, vq, ks, vs, LAYER, table, lengths, backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_decode_attention_explicit_pallas_bad_block_raises(monkeypatch):
    """Regression (ISSUE 6 satellite): decode_attention used to degrade an
    explicit backend='pallas' to XLA silently when the kv-block check
    failed, while paged_decode_attention raised for its analog."""
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    b, hq, hkv, smax, d = 2, 4, 2, 97, 16  # prime Smax: block 97, not % 8
    key = jax.random.key(5)
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, hq, d))
    kc = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, smax, d))
    vc = jax.random.normal(jax.random.fold_in(key, 3), (b, hkv, smax, d))
    lengths = jnp.array([smax, 11], jnp.int32)
    with pytest.raises(ValueError, match="backend='pallas'"):
        decode_attention(q, kc, vc, lengths, backend="pallas")
    # 'auto' still degrades silently to the XLA path
    got = decode_attention(q, kc, vc, lengths, backend="auto")
    want = decode_attention(q, kc, vc, lengths, backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


# -- engine wiring --------------------------------------------------------------


def _tiny_engine(container=None, **kw):
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import LlamaConfig, llama
    from gofr_tpu.tpu.engine import GenerateEngine

    cfg = kw.pop("cfg", None) or LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.key(0))
    kwargs = dict(slots=2, max_len=32, kv_layout="paged", page_size=8,
                  kv_quantize="int8", prefill_buckets=[16])
    kwargs.update(kw)
    return GenerateEngine(llama, cfg, params, container or new_mock_container(),
                          **kwargs)


def test_engine_int8_paged_decode_token_exact_pallas_vs_xla(monkeypatch):
    """Acceptance criterion: serving through the engine, the fused int8
    kernel emits TOKEN-IDENTICAL greedy output to the XLA gather path in
    interpreter mode. Prefill resolves identically in both runs (interpreter
    default), so the only difference between the two engines is the decode
    backend, named explicitly where the model calls the op."""
    from gofr_tpu.models import llama
    from gofr_tpu.ops.attention import paged_decode_attention_q as op

    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    prompts = [[5, 3, 9, 2, 7], [11, 4, 8]]
    tokens = {}
    for backend in ("xla", "pallas"):
        jax.clear_caches()  # backend resolution is a trace-time property
        monkeypatch.setattr(llama, "paged_decode_attention_q", partial(op, backend=backend))
        eng = _tiny_engine(max_len=48)
        try:
            eng.warmup()
            eng.start()
            tokens[backend] = [
                eng.generate(p, max_new_tokens=6, timeout=300)["tokens"]
                for p in prompts
            ]
        finally:
            eng.stop()
    assert tokens["pallas"] == tokens["xla"]
    jax.clear_caches()


def test_engine_tokens_do_not_depend_on_who_appends(monkeypatch):
    """Serving through the engine at heads of 128 (bf16 pool, pages of one
    sublane tile, 2 layers) under the interpreter: the decode chunk whose
    kernel call appends AND attends gives the greedy tokens of the chunk that
    scatters and then reads — across a page boundary (the prompt of 14 decodes
    over row 16), with a lane idle and then joined — and leaves the page pool
    consistent. Which of the two an engine runs is the rule's to say
    (``append_rides_in_kernel``); here the other side is forced at the model's
    call site, the only place that asks."""
    from gofr_tpu.models import LlamaConfig, llama
    from gofr_tpu.testutil import assert_paged_pool_consistent

    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    cfg = LlamaConfig(vocab_size=128, hidden_size=256, intermediate_size=256, num_layers=2,
                      num_heads=2, num_kv_heads=1)
    prompts = [[5 + i % 90 for i in range(14)], [11, 4, 8]]
    tokens, wrote = {}, {}
    for who in ("scatter", "fused"):
        jax.clear_caches()  # who appends is a trace-time property
        if who == "scatter":
            monkeypatch.setattr(llama, "append_rides_in_kernel", lambda pool: False)
        else:
            monkeypatch.undo()
            monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
        eng = _tiny_engine(cfg=cfg, kv_quantize="", page_size=16, max_len=48, prefill_buckets=[16])
        try:
            wrote[who] = eng.autotune_report()["decisions"]["paged_append"]["backend"]
            eng.warmup()
            eng.start()
            first = eng.submit(prompts[0], max_new_tokens=10)
            tokens[who] = [eng.generate(prompts[1], max_new_tokens=6, timeout=300)["tokens"],
                           first.result(timeout=300)["tokens"]]
            assert_paged_pool_consistent(eng, slots_empty=True)
        finally:
            eng.stop()
    jax.clear_caches()
    assert wrote["fused"] == "fused"  # the report reads the rule, not the patched call site
    assert tokens["fused"] == tokens["scatter"] and all(len(t) in (6, 10) for t in tokens["fused"])


# -- the rule -------------------------------------------------------------------

OPS = ("decode", "paged_decode", "paged_decode_q", "paged_decode_q4")
REMOVED_SWITCHES = ("GOFR_PALLAS", "GOFR_AUTOTUNE", "GOFR_AUTOTUNE_CACHE")


@pytest.mark.parametrize("where", ["cpu", "tpu_hint", "interpreter"])
@pytest.mark.parametrize("op", OPS)
def test_auto_resolves_by_platform_and_op(monkeypatch, op, where):
    """'auto' is the kernel only for the op whose kernel won its measurement
    on the chip (the bf16 pool's paged decode) and only where the traced
    computation targets a TPU; every op's kernel under the interpreter; XLA
    otherwise."""
    for var in REMOVED_SWITCHES + ("GOFR_PALLAS_INTERPRET",):
        monkeypatch.delenv(var, raising=False)
    if where == "interpreter":
        monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    with pallas.platform_hint("tpu" if where == "tpu_hint" else "cpu"):
        got = resolve_backend("auto", op)
    want = {"cpu": "xla", "interpreter": "pallas",
            "tpu_hint": "pallas" if op == "paged_decode" else "xla"}[where]
    assert got == want


@pytest.mark.parametrize("backend,hint", [("pallas", "cpu"), ("xla", "tpu")])
def test_explicit_backend_outranks_the_rule(monkeypatch, backend, hint):
    """An argument keeps its meaning: 'pallas' where no kernel can lower
    raises (never a quiet XLA run), 'xla' is honoured where the rule would
    pick the kernel."""
    monkeypatch.delenv("GOFR_PALLAS_INTERPRET", raising=False)
    with pallas.platform_hint(hint):
        if backend == "pallas":
            with pytest.raises(RuntimeError, match="asks for a Pallas kernel"):
                resolve_backend("pallas", "paged_decode")
        else:
            assert resolve_backend("xla", "paged_decode") == "xla"


def test_removed_switches_change_nothing(monkeypatch):
    """GOFR_PALLAS, GOFR_AUTOTUNE and GOFR_AUTOTUNE_CACHE are read by
    nothing (benchmarks/run.py still exports the last): set to anything,
    every op resolves as without them, on every platform."""
    monkeypatch.delenv("GOFR_PALLAS_INTERPRET", raising=False)

    def table():
        out = {}
        for hint in ("cpu", "tpu"):
            with pallas.platform_hint(hint):
                out.update({(hint, op): resolve_backend("auto", op) for op in OPS + (None,)})
        return out

    for var in REMOVED_SWITCHES:
        monkeypatch.delenv(var, raising=False)
    want = table()
    for value in ("0", "1", "/nonexistent/pins.json"):
        for var in REMOVED_SWITCHES:
            monkeypatch.setenv(var, value)
        assert table() == want, value


def test_engine_report_has_the_shape_the_benchmark_reads(monkeypatch):
    """benchmarks/run.py and chip_smoke.py call ``engine.autotune_report()``
    and read ``decisions[op]["backend"]`` and an absent ``errors``; the
    engine answers from the rule for the op its decode program traces, before
    and after warm-up alike, and sets the operator's gauge at warm-up."""
    from gofr_tpu.container import new_mock_container

    monkeypatch.delenv("GOFR_PALLAS_INTERPRET", raising=False)
    c = new_mock_container()
    eng = _tiny_engine(c)
    try:
        report = eng.autotune_report() or {}
        assert {op: rec.get("backend") for op, rec in report["decisions"].items()} == {
            "paged_decode_q": "xla", "paged_append": "scatter"}  # the mock container's devices are the CPU's
        assert report.get("errors") is None
        assert {rec["source"] for rec in report["decisions"].values()} == {"rule"}
        eng.warmup()
        assert eng.autotune_report() == report
    finally:
        eng.stop()
    gauge = c.metrics.get("app_tpu_kernel_backend")
    assert {(dict(ls)["op"], dict(ls)["kv_dtype"], dict(ls)["backend"]): v
            for ls, v in gauge._values.items()} == {
        ("paged_decode_q", "int8", "xla"): 1.0, ("paged_decode_q", "int8", "pallas"): 0.0,
        ("paged_append", "int8", "scatter"): 1.0, ("paged_append", "int8", "fused"): 0.0}
    bf16 = _tiny_engine(kv_quantize="")
    try:
        assert bf16.autotune_report()["decisions"].keys() == {"paged_decode", "paged_append"}
    finally:
        bf16.stop()
    slot = _tiny_engine(kv_layout="slot", kv_quantize="")
    try:
        assert slot.autotune_report()["decisions"].keys() == {"decode"}  # nothing paged to append to
    finally:
        slot.stop()


# (platform the trace targets, kernels interpreted, head_dim, page_size, pool) -> who appends
APPEND_CASES = [
    ("tpu", False, 128, 16, "", "fused"),      # the benchmark's cells: the kernel serves and can address the rows
    ("tpu", False, 64, 16, "", "scatter"),     # Llama-1B: the kernel reads a padded COPY of a layer
    ("tpu", False, 128, 8, "", "scatter"),     # a page of half a bf16 sublane tile
    ("tpu", False, 128, 16, "int8", "scatter"),
    ("tpu", False, 128, 16, "int4", "scatter"),
    ("cpu", False, 128, 16, "", "scatter"),    # the XLA read path serves: the scatter writes
    ("cpu", True, 128, 16, "", "fused"),       # the CPU tests' interpreter
    ("cpu", True, 64, 16, "", "scatter"),
]


@pytest.mark.parametrize("platform,interpret,head_dim,page,kvq,want", APPEND_CASES)
def test_who_appends_follows_from_what_the_code_observes(monkeypatch, platform, interpret, head_dim,
                                                          page, kvq, want):
    """``paged_append`` is no choice of its own: it is ``fused`` exactly
    where the rule gives the ``paged_decode`` kernel AND the plane's rows can
    be addressed (head_dim % 128 == 0, whole sublane tiles a page) AND the
    pool is the dense one; the engine's report, its gauge and the model's
    call site (``models/llama._append_attend_paged``) read the same two
    functions."""
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import LlamaConfig
    from gofr_tpu.ops.attention import append_rides_in_kernel

    if interpret:
        monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("GOFR_PALLAS_INTERPRET", raising=False)
    cfg = LlamaConfig(vocab_size=64, hidden_size=2 * head_dim, intermediate_size=64,
                      num_layers=1, num_heads=2, num_kv_heads=1)
    c = new_mock_container()
    eng = _tiny_engine(c, kv_quantize=kvq, page_size=page, max_len=2 * page, prefill_buckets=[page], cfg=cfg)
    try:
        monkeypatch.setattr(eng.tpu, "platform", platform, raising=False)
        assert eng.autotune_report()["decisions"]["paged_append"] == {"backend": want, "source": "rule"}
        if not kvq:
            with pallas.platform_hint(platform):
                assert append_rides_in_kernel(eng.cache.k) == (want == "fused")
                assert not append_rides_in_kernel(eng.cache.k, backend="xla")
    finally:
        eng.stop()
