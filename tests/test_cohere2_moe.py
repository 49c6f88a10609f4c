"""The Cohere2-MoE family (models/cohere2_moe.py) against the repository's
plain float32 reference (tests/references/cohere2_moe.py) at a tiny preset on
the CPU: four layers in the published pattern (sliding, sliding, sliding,
full), hidden 64, 8 heads / 2 KV heads of 16, 16 experts top-4 with 2 shared,
window 16, pages of 8 — so contexts start inside the window and end beyond it
on both kinds of layer.

Tolerances. Program and reference both compute in float32 here, in different
operation orders (fused products, an online softmax, the sort's summation
order): agreement is a few float32 ulps at the logits' magnitude, 2e-5
relative to max |logit|. The same comparison with bf16 anywhere on the
program's side misses by 1e-2 (``test_a_bf16_program_would_fail`` shows it),
so the limit separates the precisions by two orders of magnitude."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import family_of, get_family
from gofr_tpu.models.cohere2_moe import Cohere2MoeConfig
from gofr_tpu.ops import moe

pytestmark = pytest.mark.quick  # CPU-sized: about a minute for the lot

HERE = os.path.dirname(os.path.abspath(__file__))
REL_TOL = 2e-5  # of max |logit|: float32 both sides, another operation order
PAGE = 8


def _load(path):
    spec = importlib.util.spec_from_file_location("ref_" + str(abs(hash(path))), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def ref():
    return _load(os.path.join(HERE, "references", "cohere2_moe.py"))


@pytest.fixture(scope="module")
def fam():
    return get_family("cohere2_moe")


def ref_spec(cfg: Cohere2MoeConfig) -> dict:
    """The configuration-file keys the reference reads, from a config object."""
    return {"num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta, "layer_norm_eps": cfg.norm_eps,
            "layer_switch": cfg.layer_switch, "sliding_window": cfg.sliding_window,
            "intermediate_size": cfg.intermediate_size, "num_hidden_layers": cfg.num_layers,
            "num_experts": cfg.experts_held, "first_expert": cfg.first_expert,
            "num_experts_per_tok": cfg.experts_per_token,
            "num_shared_experts": cfg.num_shared_experts, "logit_scale": cfg.logit_scale}


def close(got, want, rel=REL_TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    assert err <= rel * max(1.0, float(np.max(np.abs(want)))), err


def tokens(n, seed=0, vocab=256):
    return [int(t) for t in np.random.RandomState(seed).randint(3, vocab, size=n)]


SHARES = {"whole": dict(), "share": dict(experts_held=4, first_expert=8)}


@pytest.fixture(scope="module", params=sorted(SHARES))
def model(request, fam):
    cfg = Cohere2MoeConfig.tiny(**SHARES[request.param])
    return cfg, fam.init(cfg, jax.random.key(7))


def test_family_is_found_from_the_config_and_imported_lazily(fam):
    assert family_of(Cohere2MoeConfig.tiny()) == "cohere2_moe"
    assert fam.__name__ == "gofr_tpu.models.cohere2_moe"
    hash(Cohere2MoeConfig.tiny())  # a static argument of jitted functions
    with pytest.raises(ValueError, match="not among the router"):
        Cohere2MoeConfig.tiny(experts_held=8, first_expert=12)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_interleaved_rotary_is_the_rotation_of_neighbouring_pairs(dtype):
    """``apply_rope_interleaved`` finds a lane's partner by one product with a
    signed permutation matrix: each pair (x[2i], x[2i+1]) turns by
    ``position * inv_freq[i]`` exactly as the definition by pairs says, in
    both dtypes (a partner is ONE term of the product's sum: exact), and a
    factor of 0 is the identity, bit for bit."""
    from gofr_tpu.ops.rope import apply_rope_interleaved, rope_inv_freq

    x = jax.random.normal(jax.random.key(2), (2, 5, 3, 16), jnp.float32).astype(getattr(jnp, dtype))
    positions = jnp.asarray([[0, 1, 7, 100, 4095], [3, 2, 1, 0, 50000]], jnp.int32)
    inv_freq = rope_inv_freq(16, 50000.0)
    got = apply_rope_interleaved(x, positions, inv_freq)
    angle = positions[..., None, None].astype(jnp.float32) * inv_freq  # [B, S, 1, D/2]
    pairs = x.astype(jnp.float32).reshape(2, 5, 3, 8, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    want = jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle), b * jnp.cos(angle) + a * jnp.sin(angle)],
                     axis=-1).reshape(x.shape).astype(x.dtype)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(apply_rope_interleaved(x, positions, inv_freq, 0.0), np.float32),
                                  np.asarray(x, np.float32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_barrier_in_qkv_heads_changes_no_logit(fam, monkeypatch, dtype):
    """``_norm_qkv`` projects through ``models/base.qkv_heads`` (PR 35: the flat
    products pass a barrier before the split into heads). Against the plain
    split patched in at the call site, ``forward`` over both kinds of layer
    gives the same logits bit for bit."""
    cfg = Cohere2MoeConfig.tiny(dtype=getattr(jnp, dtype))
    params = fam.init(cfg, jax.random.key(7))
    toks = jnp.asarray([tokens(20), tokens(20, seed=1)], jnp.int32)
    jax.clear_caches()
    got = fam.forward(cfg, params, toks)
    monkeypatch.setattr(fam, "qkv_heads", lambda h, lp, d: tuple(
        (h @ lp[w]).reshape(*h.shape[:-1], -1, d) for w in ("wq", "wk", "wv")))
    jax.clear_caches()
    want = fam.forward(cfg, params, toks)
    monkeypatch.undo()
    jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [5, 16, 40])  # inside the window, at its edge, beyond it
def test_forward_equals_the_reference(fam, ref, model, n):
    cfg, params = model
    toks = tokens(n, seed=n)
    got = fam.forward(cfg, params, jnp.asarray([toks + [0] * (48 - n)]), jnp.asarray([n]))[0, :n]
    close(got, ref.all_logits(ref_spec(cfg), params, toks))


def _decode(fam, cfg, params, toks, prompt, steps, chunk=None):
    """Prefill ``prompt`` tokens (in chunks of ``chunk`` if given), then feed
    the next ``steps`` tokens of ``toks`` one decode step each → logits at
    positions prompt-1 .. prompt+steps-1."""
    maxp = -(-(prompt + steps) // PAGE)
    cache = fam.make_paged_cache(cfg, 2 * maxp, PAGE)
    table = jnp.asarray([list(range(maxp, 2 * maxp)), [2 * maxp] * maxp], jnp.int32)  # row 1: an idle lane
    out = []
    if chunk is None:
        pad = -(-prompt // PAGE) * PAGE
        logits, cache, counts = fam.prefill_paged(
            cfg, params, jnp.asarray([toks[:prompt] + [0] * (pad - prompt)]), jnp.asarray([prompt]),
            cache, table[:1])
    else:
        for off in range(0, prompt, chunk):
            part = toks[off:min(off + chunk, prompt)]
            logits, cache, counts = fam.prefill_paged(
                cfg, params, jnp.asarray([part + [0] * (chunk - len(part))]), jnp.asarray([len(part)]),
                cache, table[:1], jnp.asarray([off]))
    out.append(logits[0])
    for i in range(steps):
        logits, cache, counts = fam.decode_step_paged(
            cfg, params, jnp.asarray([toks[prompt + i], 0]), jnp.asarray([prompt + i, 0]), cache, table)
        out.append(logits[0])
    # the idle lane is routed nowhere: one live token a layer, k assignments each
    assert int(counts[:-3].sum() + counts[-3]) == cfg.num_layers * cfg.experts_per_token
    assert int(counts[-1]) == cfg.num_layers
    return jnp.stack(out)


@pytest.mark.parametrize("prompt,chunk", [(6, None), (20, None), (6, 8), (37, 8), (37, 16)])
def test_prefill_then_decode_through_the_pages_equals_the_reference(fam, ref, model, prompt, chunk):
    """≥ 24 decode steps from a context that starts inside the window (6) or
    beyond it (20, 37), the prompt whole or in chunks: logits, not tokens."""
    cfg, params = model
    steps = 26
    toks = tokens(prompt + steps, seed=prompt)
    got = _decode(fam, cfg, params, toks, prompt, steps, chunk)
    want = ref.all_logits(ref_spec(cfg), params, toks)[prompt - 1:]
    close(got, want)


def test_a_bf16_program_would_fail(fam, ref):
    """The tolerance's other side: the same program in bf16 misses the float32
    reference of the SAME (bf16-rounded) weights by orders of magnitude more."""
    cfg = Cohere2MoeConfig.tiny(dtype=jnp.bfloat16)
    params = fam.init(cfg, jax.random.key(7))
    toks = tokens(24, seed=1)
    got = np.asarray(fam.forward(cfg, params, jnp.asarray([toks])), np.float32)[0]
    want = np.asarray(ref.all_logits(ref_spec(cfg), params, toks))
    assert np.max(np.abs(got - want)) > 50 * REL_TOL * max(1.0, float(np.max(np.abs(want))))


def _one_layer(ref, cfg, params, n):
    """The reference's routed + shared sum of layer 0 for normed input n [S, E]."""
    spec = ref_spec(cfg)
    i = jnp.int32(0)
    gates, idx = ref._route(spec["num_experts_per_tok"], n, params["blocks"], i)
    routed = sum(ref._expert(n, gates, idx, params["experts"], i, jnp.int32(e), jnp.int32(cfg.first_expert + e))
                 for e in range(cfg.experts_held))
    shared = sum(ref._shared_expert(cfg.intermediate_size, s, n, params["blocks"], i)
                 for s in range(cfg.num_shared_experts)) / cfg.num_shared_experts
    return routed, shared


@pytest.mark.parametrize("t", [12, moe.DENSE_MAX_TOKENS + 44])  # the per-expert products, the grouped product
def test_the_shares_add_up_to_the_whole_layer(fam, ref, t):
    """The routed parts that all ``num_experts / experts_held`` shares compute,
    plus the shared experts counted once, equal the uncut reference layer."""
    whole = Cohere2MoeConfig.tiny()
    params = fam.init(whole, jax.random.key(11))
    n = jax.random.normal(jax.random.key(3), (t, whole.hidden_size), jnp.float32)
    routed, shared = _one_layer(ref, whole, params, n)
    held = 4
    total = jnp.zeros_like(n)
    for first in range(0, whole.num_experts, held):
        cfg = Cohere2MoeConfig.tiny(experts_held=held, first_expert=first)
        share = {**params, "experts": {k: w[:, first:first + held] for k, w in params["experts"].items()}}
        lp = jax.tree.map(lambda w: w[0], share["blocks"])
        got, counts = fam._experts(cfg, share, lp, jnp.int32(0), n)
        routed_part = got - shared  # every share computes the shared experts alike: counted once
        total = total + routed_part
        assert int(counts[:held].sum() + counts[held]) == t * whole.experts_per_token
    close(total + shared, routed + shared, rel=1e-4)  # 4 partial sums against one: a few more ulps


@pytest.mark.parametrize("t", [12, moe.DENSE_MAX_TOKENS + 44])
def test_no_token_is_dropped_under_a_fully_skewed_router(fam, ref, t):
    """A router that sends EVERY token to held expert 1 first (and to three
    more): the result still equals the reference — no capacity, no drop."""
    cfg = Cohere2MoeConfig.tiny(experts_held=4, first_expert=0)
    params = fam.init(cfg, jax.random.key(5))
    skew = jnp.zeros((cfg.num_experts,)).at[jnp.asarray([1, 0, 2, 3])].set(jnp.asarray([40.0, 30.0, 20.0, 10.0]))
    n = jax.random.normal(jax.random.key(9), (t, cfg.hidden_size), jnp.float32)
    # the bias enters as one more input column of ones (the router has none of its own)
    n1 = jnp.concatenate([n[:, :-1], jnp.ones((t, 1))], axis=1)
    router = params["blocks"]["router"].at[:, -1, :].set(skew)
    params = {**params, "blocks": {**params["blocks"], "router": router}}
    lp = jax.tree.map(lambda w: w[0], params["blocks"])
    got, counts = fam._experts(cfg, params, lp, jnp.int32(0), n1)
    assert [int(c) for c in counts[:4]] == [t, t, t, t] and int(counts[4]) == 0  # all k choices held
    routed, shared = _one_layer(ref, cfg, params, n1)
    close(got, routed + shared, rel=1e-4)


def test_padding_is_routed_nowhere(fam):
    """Padded positions of a prefill take no expert and count nowhere."""
    cfg = Cohere2MoeConfig.tiny()
    params = fam.init(cfg, jax.random.key(2))
    cache = fam.make_paged_cache(cfg, 4, PAGE)
    _, _, counts = fam.prefill_paged(cfg, params, jnp.asarray([tokens(5) + [0] * 11]), jnp.asarray([5]),
                                     cache, jnp.asarray([[0, 1]], jnp.int32))
    assert int(counts[:-3].sum() + counts[-3]) == cfg.num_layers * 5 * cfg.experts_per_token


def test_the_two_reference_files_hold_the_same_text():
    """tests/references/ and benchmarks/references/ each keep a copy: same code."""
    def body(path):
        with open(path) as f:
            text = f.read()
        return text[text.index("    n      = LayerNorm(x)"):]

    bench = os.path.join(os.path.dirname(HERE), "benchmarks", "references", "cohere2_moe.py")
    assert body(bench) == body(os.path.join(HERE, "references", "cohere2_moe.py"))


# -- the ops: window in all three places that attend ------------------------------------


@pytest.mark.parametrize("window", [16, 21, 32, 5, 1, 1 << 30])  # at a page edge, inside a page, never binding
def test_the_kernels_window_equals_the_xla_paths(monkeypatch, window):
    """The paged-decode kernel (interpret mode) against the gathered XLA path:
    pages of 16, so 16 and 32 cut at a page edge, 21 and 5 inside a page; read
    only, and as the fused append. An idle lane (all-OOB table row) and a lane
    of one token ride along."""
    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    from gofr_tpu.ops.attention import paged_decode_append_attention, paged_decode_attention
    from gofr_tpu.ops.paged import append_tokens_paged

    rs = np.random.RandomState(0)
    layers, pool, hkv, page, d, lanes, hq, maxp = 2, 24, 2, 16, 128, 5, 8, 4
    kp = jnp.asarray(rs.randn(layers, pool, hkv, page, d), jnp.float32)
    vp = jnp.asarray(rs.randn(layers, pool, hkv, page, d), jnp.float32)
    q = jnp.asarray(rs.randn(lanes, hq, d), jnp.float32)
    table = jnp.asarray(rs.permutation(pool)[:lanes * maxp].reshape(lanes, maxp), jnp.int32).at[4].set(pool)
    lengths = jnp.asarray([64, 37, 1, 17, 0], jnp.int32)
    got = paged_decode_attention(q, kp, vp, 1, table, lengths, backend="pallas", window=window)
    want = paged_decode_attention(q, kp, vp, 1, table, lengths, backend="xla", window=window)
    close(got[:4], want[:4], rel=1e-5)
    kn, vn = (jnp.asarray(rs.randn(lanes, hkv, d), jnp.float32) for _ in range(2))
    pos = jnp.asarray([63, 36, 0, 16, 0], jnp.int32)
    got, k2, v2 = jax.jit(lambda *a: paged_decode_append_attention(*a, window=jnp.int32(window)))(
        q, kn, vn, kp, vp, 1, table, pos)
    k3, v3 = append_tokens_paged(kp, vp, 1, table, pos, kn, vn)
    want = paged_decode_attention(q, k3, v3, 1, table, pos + 1, backend="xla", window=window)
    close(got[:4], want[:4], rel=1e-5)
    assert bool(jnp.all(k2 == k3)) and bool(jnp.all(v2 == v3))


def test_mha_attention_window_masks_what_the_definition_masks():
    from gofr_tpu.ops import mha_attention

    rs = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rs.randn(2, 12, h, 8), jnp.float32) for h in (4, 2, 2))
    off = jnp.asarray([0, 3])
    got = mha_attention(q[:, :6], k, v, causal=True, q_offset=off, kv_lengths=jnp.asarray([6, 9]), window=4)
    for b in range(2):
        for i in range(6):
            pos = int(off[b]) + i
            keys = [j for j in range(12) if pos - 4 < j <= pos and j < (6, 9)[b]]
            kk, vv = k[b, jnp.asarray(keys)].repeat(2, axis=1), v[b, jnp.asarray(keys)].repeat(2, axis=1)
            p = jax.nn.softmax(jnp.einsum("hd,khd->hk", q[b, i], kk) / 8 ** 0.5, axis=-1)
            close(got[b, i], jnp.einsum("hk,khd->hd", p, vv), rel=1e-5)
    with pytest.raises(ValueError, match="causal"):
        mha_attention(q, k, v, causal=False, window=4)


def test_scope_takes_the_second_list_and_still_refuses_unknown_names():
    from gofr_tpu import tracing

    assert tracing.MOE_SCOPES == ("moe_router", "moe_experts", "moe_shared")
    assert tracing.SCOPES == ("embed", "qkv_rope", "kv_append", "kv_gather", "attention",
                              "o_proj", "mlp", "lm_head", "sample")  # as it was: the benchmark keeps a copy
    for name in tracing.MOE_SCOPES:
        with tracing.scope(name):
            pass
    with pytest.raises(ValueError, match="unknown program scope"):
        tracing.scope("moe_dispatch")


# -- the normal serving path ---------------------------------------------------------------


def _engine(fam, cfg, params, **kw):
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.tpu.engine import GenerateEngine

    return GenerateEngine(fam, cfg, params, new_mock_container(), **{
        "slots": 4, "max_len": 96, "kv_layout": "paged", "page_size": 8, "prefill_buckets": [16, 32], **kw})


def test_the_engine_serves_it_whole_and_chunked_and_counts_the_routing(fam):
    """Prompts inside a bucket (whole prefill) and beyond the largest (chunked
    prefill), 24 greedy tokens each, against the family's own full forward; the
    routing counts arrive with the readbacks and flush by phase."""
    from gofr_tpu.metrics import Registry

    cfg = Cohere2MoeConfig.tiny(experts_held=4, first_expert=4)
    params = fam.init(cfg, jax.random.key(1))
    eng = _engine(fam, cfg, params)
    try:
        for n in (5, 30, 50):
            prompt = tokens(n, seed=n)
            out = eng.generate(prompt, max_new_tokens=24)["tokens"]
            seq = list(prompt)
            for tok in out:
                logits = fam.forward(cfg, params, jnp.asarray([seq + [0] * (96 - len(seq))]),
                                     jnp.asarray([len(seq)]))[0, len(seq) - 1]
                assert int(jnp.argmax(logits)) == tok
                seq.append(tok)
        counts = eng._step_counts
        k, layers = cfg.experts_per_token, cfg.num_layers
        # every prompt token is routed once a layer in prefill (whole or chunked)
        assert int(counts["prefill"][:-2].sum()) == (5 + 30 + 50) * k * layers
        assert int(counts["decode"][:-2].sum()) % (k * layers) == 0 and counts["decode"][-1] > 0
        registry = Registry()
        for name in {name for name, _ in eng._step_counters}:
            registry.new_counter(name, "test")
        eng.flush_step_counters(registry)
        eng.flush_step_counters(registry)  # a second scrape adds nothing
        text = registry.expose_text()
        assert 'app_tpu_moe_assignments_total{expert="4",phase="prefill"}' in text
        assert 'app_tpu_moe_layer_steps_total{phase="decode"} %d' % counts["decode"][-1] in text.replace(".0\n", "\n")
    finally:
        eng.stop()


def test_the_engine_refuses_what_the_family_lacks_by_message(fam):
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import ModelSpec
    from gofr_tpu.tpu.engine import build_engine

    cfg = Cohere2MoeConfig.tiny()
    params = fam.init(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="has no verify_step_paged; speculative decoding needs it"):
        _engine(fam, cfg, params, spec_tokens=2)
    for kvq, attr in (("int8", "make_paged_cache_q"), ("int4", "make_paged_cache_q4")):
        with pytest.raises(ValueError, match=rf"has no {kvq} paged-KV support \({attr}\)"):
            _engine(fam, cfg, params, kv_quantize=kvq)
    with pytest.raises(ValueError, match="has no slot-cache support"):
        _engine(fam, cfg, params, kv_layout="slot")
    spec = ModelSpec("cohere2_moe", cfg, task="generate", dtype=cfg.dtype)
    with pytest.raises(ValueError, match="spec_tokens: family .* has no verify_step_paged"):
        build_engine(spec, new_mock_container(), spec_tokens=2, slots=2, max_len=32)
    with pytest.raises(ValueError, match="kv_quantize: family .* has no make_paged_cache_q "):
        build_engine(spec, new_mock_container(), kv_quantize="int8", slots=2, max_len=32)


def test_build_app_serves_it_over_http(fam):
    """``build_app(model_config=Cohere2MoeConfig(...))`` → App → POST /generate
    and /generate/stream: the family comes from the configuration object."""
    import importlib.util
    import json

    import httpx

    from gofr_tpu.config import DictConfig
    from tests.test_http_server import AppHarness

    path = os.path.join(os.path.dirname(HERE), "examples", "serving-llm", "main.py")
    spec = importlib.util.spec_from_file_location("serving_llm_for_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    cfg = Cohere2MoeConfig.tiny(experts_held=8, first_expert=0)
    app = module.build_app(DictConfig({"APP_NAME": "moe", "LOG_LEVEL": "WARN"}), model_config=cfg, seed=3,
                           kv_layout="paged", slots=4, max_len=64, page_size=8, prefill_buckets=[16, 32])
    with AppHarness(app) as h, httpx.Client(base_url=h.base, timeout=300) as c:
        engine = app.container.engines["lm"]
        assert engine.family is fam and engine.kv_layout == "paged"
        prompt = tokens(12, seed=4)
        r = c.post("/generate", json={"prompt": prompt, "max_new_tokens": 6})
        assert r.status_code == 201, r.text
        out = r.json()["data"]["tokens"]
        seq = list(prompt)
        for tok in out:
            logits = fam.forward(cfg, engine.params, jnp.asarray([seq]), jnp.asarray([len(seq)]))[0, -1]
            assert int(jnp.argmax(logits)) == tok
            seq.append(tok)
        streamed = []
        with c.stream("POST", "/generate/stream", json={"prompt": prompt, "max_new_tokens": 6}) as r:
            assert r.status_code == 200
            event = None
            for line in r.iter_lines():
                if line.startswith("event: "):
                    event = line[len("event: "):]
                elif line.startswith("data: ") and event == "token":
                    streamed.append(json.loads(line[len("data: "):]))
        assert streamed == out
        metrics = httpx.get(f"http://127.0.0.1:{app.metrics_port}/metrics", timeout=30).text
        assert 'app_tpu_moe_layer_steps_total{phase="decode"}' in metrics
        assert 'app_tpu_moe_assignments_absent_total{phase="prefill"}' in metrics


# -- pricing ------------------------------------------------------------------------------


def test_a_step_of_an_expert_family_is_priced_by_what_a_token_touches(fam):
    from gofr_tpu.metrics.perf import CostModel
    from gofr_tpu.models.base import param_bytes, param_count

    cfg = Cohere2MoeConfig(vocab_size=32768, num_layers=4, experts_held=16)  # the benchmark's share
    shapes = jax.eval_shape(lambda: fam.init(cfg, jax.random.key(0)))
    n, nbytes = param_count(shapes), param_bytes(shapes)
    assert n == 4_733_292_544
    parts = fam.token_params(cfg)
    assert parts["always"] + 4 * 16 * parts["expert"] + 4 * 4096 + 4096 == n  # all but the norms' weights
    model = CostModel(n_params=n, weight_bytes=nbytes, kv_bytes_per_pos=16384, experts=parts)
    # a token touches attention + shared + router + the head's slice, and in
    # expectation 8 * 16 / 128 = 1 held expert a layer: 1.71B of 4.73B parameters
    touched = parts["always"] + 4 * 1 * parts["expert"]
    assert model.token_params() == touched and touched < 0.4 * n
    flops, moved = model.decode(lanes=128, k=8, hist_positions=128 * 400)
    assert flops == 2.0 * touched * 128 * 8
    # 128 tokens hit every held expert (0.9375^128 misses): a step reads all the weights
    assert moved == pytest.approx(8 * nbytes + 8 * 128 * 400 * 16384 + 128 * 8 * 16384, rel=2e-3)
    # one token a step reads 8 * 16 / 128 = 1 expert a layer in expectation, not 16
    one = model.step_weight_bytes(1)
    assert one == pytest.approx((nbytes / n) * (parts["always"] + 4 * parts["expert"]), rel=1e-9)
    flops, moved = model.prefill(1000)
    assert flops == 2.0 * touched * 1000 and moved == pytest.approx(nbytes + 1000 * 16384, rel=2e-3)


def test_a_dense_familys_prices_are_what_they_were():
    from gofr_tpu.metrics.perf import CostModel

    model = CostModel(n_params=1e9, weight_bytes=2e9, kv_bytes_per_pos=1000.0)
    assert model.prefill(100) == (2e9 * 100, 2e9 + 100 * 1000.0)
    assert model.chunk(64, 128) == (2e9 * 64, 2e9 + (128 + 64) * 1000.0 + 64 * 1000.0)
    assert model.decode(8, 4, 800) == (2e9 * 8 * 4, 4 * 2e9 + 4 * 800 * 1000.0 + 8 * 4 * 1000.0)
    assert model.token_params() == 1e9 and model.step_weight_bytes(5) == 2e9


# -- the reference's reading of a near-tie in the routing ---------------------------------


def test_last_logits_is_the_plain_pass_where_the_routing_is_clear(ref, fam, monkeypatch):
    cfg = Cohere2MoeConfig.tiny(experts_held=4, first_expert=8)
    params = fam.init(cfg, jax.random.key(7))
    toks = tokens(21, seed=3)
    monkeypatch.setattr(ref, "NEAR_TIE_MARGIN", 0.0)  # nothing is a near-tie
    close(ref.last_logits(ref_spec(cfg), params, toks, 32), ref.all_logits(ref_spec(cfg), params, toks)[-1], rel=1e-6)


def test_last_logits_accepts_either_side_of_a_near_tie_and_nothing_else(ref, fam, monkeypatch):
    """With every held-involved routing of the last position declared a
    near-tie: the best token of the plain pass AND the best token of a pass
    with the two experts exchanged both sit at the top of the returned row; a
    token that is far from the top under every routing stays far."""
    cfg = Cohere2MoeConfig.tiny(experts_held=8, first_expert=0)
    params = fam.init(cfg, jax.random.key(7))
    spec, toks = ref_spec(cfg), tokens(21, seed=3)
    margins = []
    plain = np.asarray(ref.all_logits(spec, params, toks, margins)[-1])
    involved = [layer for layer, m in enumerate(margins) if float(m[-1]) < 1.0]
    assert involved, "no layer routes the last position near a held expert: pick another seed"
    monkeypatch.setattr(ref, "NEAR_TIE_MARGIN", 1.0)
    monkeypatch.setattr(ref, "MAX_NEAR_TIES", 2)
    row = np.asarray(ref.last_logits(spec, params, toks, 32))
    exchanged = np.asarray(ref._head(
        float(spec["layer_norm_eps"]),
        ref.hidden_states(spec, params, toks + [0] * 11, exchange=(20, frozenset(involved[:1])))[20],
        params["final_norm"], params["embed"], jnp.float32(1)))
    assert not np.allclose(exchanged, plain, atol=1e-4)  # the exchange moved the logits
    top = float(np.max(row))
    assert row[int(np.argmax(plain))] == pytest.approx(top, abs=1e-6)
    assert row[int(np.argmax(exchanged))] == pytest.approx(top, abs=1e-6)
    worst = int(np.argmin(np.maximum(plain - plain.max(), exchanged - exchanged.max())))
    assert top - row[worst] > 0.5 * (plain.max() - plain.min())
    # and each token's deficit is its smallest deficit under any of the passes: never below the plain pass's own
    assert np.all(row - top >= plain - plain.max() - 1e-6)
