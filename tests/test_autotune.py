"""Fused int8-KV paged-decode kernel parity + the warmup backend autotuner.

Kernel parity runs under the Pallas interpreter on the CPU test mesh
(tests/test_pallas.py convention); the autotuner units inject fake timers
so no kernel is ever lowered — the whole module is CPU-safe and quick.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops import autotune
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
    with pytest.raises(ValueError, match="backend='pallas'"):
        paged_decode_attention_q(q, kq, vq, ks, vs, LAYER, table, lengths, backend="pallas")
    # 'auto' may degrade silently — and must agree with the explicit xla path
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


# -- autotuner units (fake timers; no kernel lowering) --------------------------


def _fake_timer(values):
    it = iter(values)

    def timer(fn):
        return next(it)

    return timer


def test_autotuner_pins_winner():
    tuner = autotune.Autotuner(device_kind="v5e", timer=_fake_timer([3e-3, 1e-3]))
    backend = tuner.measure("paged_decode_q", "8x16", "int8",
                            {"xla": lambda: None, "pallas": lambda: None})
    assert backend == "pallas"
    rec = tuner.decisions["paged_decode_q"]
    assert rec["source"] == "measured"
    assert rec["timings_ms"] == {"xla": 3.0, "pallas": 1.0}
    assert tuner.pins() == {"paged_decode_q": "pallas"}


def test_autotuner_failing_candidate_disqualified_loudly(mock_logger):
    """A candidate the compiler refuses loses — and says so: error-level log
    line, ``errors`` in the decision record AND at the top of the report
    (what engine.autotune_report() / /debug/engine serve)."""
    def dies():
        raise RuntimeError("Mosaic rejected the shape")

    tuner = autotune.Autotuner(device_kind="v5e", timer=autotune._default_timer,
                               logger=mock_logger)
    backend = tuner.measure("decode", "4x97", "float32",
                            {"xla": lambda: jnp.zeros(()), "pallas": dies})
    assert backend == "xla"
    assert "Mosaic rejected" in tuner.decisions["decode"]["errors"]["pallas"]
    assert "Mosaic rejected" in tuner.report()["errors"]["decode"]["pallas"]
    logged = [r for r in mock_logger.records if r.get("level") == "ERROR"]
    assert any("decode candidate 'pallas' failed" in str(r.get("message")) for r in logged), \
        mock_logger.lines
    # a clean tuner's report carries no errors key at all
    clean = autotune.Autotuner(device_kind="v5e", timer=lambda fn: 1.0)
    clean.measure("decode", "4x128", "float32", {"xla": lambda: None, "pallas": lambda: None})
    assert "errors" not in clean.report()


def test_pinned_decision_drives_auto_resolution(monkeypatch):
    monkeypatch.delenv("GOFR_PALLAS", raising=False)
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    # interpreter default: 'auto' -> pallas ...
    assert resolve_backend("auto", op="paged_decode_q") == "pallas"
    with autotune.decision_scope({"paged_decode_q": "xla"}):
        # ... but a pinned decision for the op wins ...
        assert resolve_backend("auto", op="paged_decode_q") == "xla"
        # ... and ops without a decision keep the default
        assert resolve_backend("auto", op="decode") == "pallas"
    assert resolve_backend("auto", op="paged_decode_q") == "pallas"  # scope exited


def test_pinned_pallas_needs_kernel_platform(monkeypatch):
    """A 'pallas' pin from a TPU cache file must not make a CPU trace try
    to lower kernels."""
    monkeypatch.delenv("GOFR_PALLAS", raising=False)
    monkeypatch.delenv("GOFR_PALLAS_INTERPRET", raising=False)
    with autotune.decision_scope({"decode": "pallas"}):
        assert resolve_backend("auto", op="decode") == "xla"


def test_gofr_pallas_env_overrides_pin(monkeypatch):
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    with autotune.decision_scope({"paged_decode_q": "xla"}):
        monkeypatch.setenv("GOFR_PALLAS", "1")
        assert resolve_backend("auto", op="paged_decode_q") == "pallas"
    with autotune.decision_scope({"decode": "pallas"}):
        monkeypatch.setenv("GOFR_PALLAS", "0")
        assert resolve_backend("auto", op="decode") == "xla"


def test_autotune_enabled_escape_hatches(monkeypatch):
    monkeypatch.delenv("GOFR_PALLAS", raising=False)
    monkeypatch.delenv("GOFR_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("GOFR_AUTOTUNE", raising=False)
    assert autotune.enabled()
    monkeypatch.setenv("GOFR_AUTOTUNE", "0")
    assert not autotune.enabled()
    monkeypatch.delenv("GOFR_AUTOTUNE", raising=False)
    monkeypatch.setenv("GOFR_PALLAS", "1")  # operator override: nothing to tune
    assert not autotune.enabled()
    monkeypatch.delenv("GOFR_PALLAS", raising=False)
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")  # timings meaningless
    assert not autotune.enabled()


def test_autotune_cache_round_trip(tmp_path):
    path = str(tmp_path / "autotune.json")
    t1 = autotune.Autotuner(device_kind="v5e", cache_file=path,
                            timer=_fake_timer([2e-3, 1e-3]))
    assert t1.measure("paged_decode_q", "8x16", "int8",
                      {"xla": lambda: None, "pallas": lambda: None}) == "pallas"
    doc = json.loads((tmp_path / "autotune.json").read_text())
    assert doc["version"] == autotune.FORMAT_VERSION
    key = autotune.entry_key("v5e", "paged_decode_q", "8x16", "int8")
    assert doc["entries"][key]["backend"] == "pallas"

    def no_timer(fn):
        raise AssertionError("re-timed despite a cache hit")

    t2 = autotune.Autotuner(device_kind="v5e", cache_file=path, timer=no_timer)
    assert t2.measure("paged_decode_q", "8x16", "int8",
                      {"xla": lambda: None, "pallas": lambda: None}) == "pallas"
    assert t2.decisions["paged_decode_q"]["source"] == "cache"
    # a different shape/device is a different key: measured fresh
    t3 = autotune.Autotuner(device_kind="v6e", cache_file=path,
                            timer=_fake_timer([1e-3, 2e-3]))
    assert t3.measure("paged_decode_q", "8x16", "int8",
                      {"xla": lambda: None, "pallas": lambda: None}) == "xla"


def test_sharding_key_isolates_pins_and_stays_read_compatible(tmp_path):
    """ISSUE 19 satellite: per-shard decode shapes change the winner, so a
    tp-sharded engine must never adopt an unsharded pin (or vice versa) —
    the ``|shard=`` suffix isolates them — while "" sharding keeps the
    exact pre-feature key so existing cache files stay valid."""
    # read-compat: no sharding -> the old key, byte for byte
    base = autotune.entry_key("v5e", "paged_decode", "8x16", "bf16")
    assert base == autotune.entry_key("v5e", "paged_decode", "8x16", "bf16",
                                      sharding="")
    assert "shard" not in base
    sharded = autotune.entry_key("v5e", "paged_decode", "8x16", "bf16",
                                 sharding="tp4")
    assert sharded == base + "|shard=tp4"

    # an unsharded engine's pin is STALE for a tp4 engine: same op/shape,
    # fresh measurement under the sharded key, both pins coexist on disk
    path = str(tmp_path / "autotune.json")
    t1 = autotune.Autotuner(device_kind="v5e", cache_file=path,
                            timer=_fake_timer([2e-3, 1e-3]))
    assert t1.measure("paged_decode", "8x16", "bf16",
                      {"xla": lambda: None, "pallas": lambda: None}) == "pallas"
    t2 = autotune.Autotuner(device_kind="v5e", cache_file=path,
                            sharding="tp4", timer=_fake_timer([1e-3, 2e-3]))
    assert t2.measure("paged_decode", "8x16", "bf16",
                      {"xla": lambda: None, "pallas": lambda: None}) == "xla"
    assert t2.decisions["paged_decode"]["source"] == "measured"
    assert t2.report()["sharding"] == "tp4"
    doc = json.loads((tmp_path / "autotune.json").read_text())
    assert doc["entries"][base]["backend"] == "pallas"
    assert doc["entries"][sharded]["backend"] == "xla"

    # and each geometry reloads its OWN pin from the shared file
    def no_timer(fn):
        raise AssertionError("re-timed despite a cache hit")

    for sh, want in (("", "pallas"), ("tp4", "xla")):
        t = autotune.Autotuner(device_kind="v5e", cache_file=path,
                               sharding=sh, timer=no_timer)
        assert t.measure("paged_decode", "8x16", "bf16",
                         {"xla": lambda: None, "pallas": lambda: None}) == want
        assert t.decisions["paged_decode"]["source"] == "cache"


@pytest.mark.parametrize("content", [
    "not json at all {",
    json.dumps({"version": 999, "entries": {"k": {"backend": "pallas"}}}),
    # a well-formed pin for this very key, written under the format version
    # before the paged-decode kernel was rewritten: raced again, not served
    json.dumps({"version": autotune.FORMAT_VERSION - 1,
                "entries": {"v5e|decode|8x16|int8": {"backend": "xla"}}}),
    json.dumps({"version": autotune.FORMAT_VERSION, "entries": "nope"}),
    json.dumps({"version": autotune.FORMAT_VERSION,
                "entries": {"v5e|decode|8x16|int8": {"backend": "cuda"}}}),
])
def test_autotune_corrupt_or_stale_cache_ignored(tmp_path, content):
    path = tmp_path / "autotune.json"
    path.write_text(content)
    tuner = autotune.Autotuner(device_kind="v5e", cache_file=str(path),
                               timer=_fake_timer([2e-3, 1e-3]))
    assert tuner.measure("decode", "8x16", "int8",
                         {"xla": lambda: None, "pallas": lambda: None}) == "pallas"
    assert tuner.decisions["decode"]["source"] == "measured"
    # and the file is rewritten valid
    doc = json.loads(path.read_text())
    assert doc["version"] == autotune.FORMAT_VERSION


# -- engine wiring --------------------------------------------------------------


def _tiny_engine(container=None, **kw):
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import LlamaConfig, llama
    from gofr_tpu.tpu.engine import GenerateEngine

    cfg = LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.key(0))
    kwargs = dict(slots=2, max_len=32, kv_layout="paged", page_size=8,
                  kv_quantize="int8", prefill_buckets=[16])
    kwargs.update(kw)
    return GenerateEngine(llama, cfg, params, container or new_mock_container(),
                          **kwargs)


def test_engine_warmup_autotune_measures_pins_and_caches(tmp_path, monkeypatch):
    """warmup() times both backends on the engine's real shapes (fake timer
    here), pins the winner for its traces, exposes the report + info gauge,
    and a 'restarted' engine re-pins from the cache file without timing."""
    from gofr_tpu.container import new_mock_container

    monkeypatch.delenv("GOFR_PALLAS", raising=False)
    monkeypatch.delenv("GOFR_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("GOFR_AUTOTUNE", raising=False)
    monkeypatch.setenv("GOFR_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    # pretend kernels can lower so BOTH candidates exist; the fake timings
    # make xla win, so no Pallas program is ever actually traced on CPU
    import gofr_tpu.ops.pallas as pallas_pkg

    monkeypatch.setattr(pallas_pkg, "kernel_platform", lambda: True)

    c = new_mock_container()
    eng = _tiny_engine(container=c)
    timed = []

    def fake_timer(fn):
        timed.append(fn)
        return [1e-3, 2e-3][len(timed) - 1]  # xla first (dict order), xla wins

    eng._autotune_timer = fake_timer
    try:
        eng.warmup()
    finally:
        eng.stop()
    assert len(timed) == 2
    assert eng._autotune_pins == {"paged_decode_q": "xla"}
    rep = eng.autotune_report()
    assert rep["decisions"]["paged_decode_q"]["source"] == "measured"
    assert rep["decisions"]["paged_decode_q"]["timings_ms"] == {
        "xla": 1.0, "pallas": 2.0}
    gauge = c.metrics.get("app_tpu_kernel_backend")
    vals = {dict(ls)["backend"]: v for ls, v in gauge._values.items()
            if dict(ls)["op"] == "paged_decode_q"}
    assert vals == {"xla": 1.0, "pallas": 0.0}

    # engine restart (PR5 epochs): the cache file answers, no re-timing
    eng2 = _tiny_engine()

    def no_timer(fn):
        raise AssertionError("re-timed despite the autotune cache")

    eng2._autotune_timer = no_timer
    try:
        eng2.warmup()
    finally:
        eng2.stop()
    assert eng2._autotune_pins == {"paged_decode_q": "xla"}
    assert eng2.autotune_report()["decisions"]["paged_decode_q"]["source"] == "cache"


def test_engine_autotune_escape_hatch_preserves_static_behavior(monkeypatch):
    """GOFR_AUTOTUNE=0 reproduces today's exact behavior: no pins, no
    report, resolution falls through to the static GOFR_PALLAS gate."""
    monkeypatch.setenv("GOFR_AUTOTUNE", "0")
    monkeypatch.delenv("GOFR_PALLAS", raising=False)
    eng = _tiny_engine()
    try:
        eng.warmup()
    finally:
        eng.stop()
    assert eng._autotune_pins == {}
    assert eng.autotune_report() is None


def test_engine_int8_paged_decode_token_exact_pallas_vs_xla(monkeypatch):
    """Acceptance criterion: serving through the engine, the fused int8
    kernel (pinned per op, exactly as the autotuner would pin it) emits
    TOKEN-IDENTICAL greedy output to the XLA gather path in interpreter
    mode. Prefill resolves identically in both runs (interpreter default),
    so the only difference between the two engines is the decode backend."""
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("GOFR_PALLAS", raising=False)
    prompts = [[5, 3, 9, 2, 7], [11, 4, 8]]
    tokens = {}
    for backend in ("xla", "pallas"):
        jax.clear_caches()  # backend resolution is a trace-time property
        eng = _tiny_engine(max_len=48)
        eng._autotune_pins = {"paged_decode_q": backend}
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
