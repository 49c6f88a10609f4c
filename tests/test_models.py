"""Model-family tests: shapes, cache-consistency, sharding, and HF oracles.

The HF cross-checks build tiny *random* transformers models on CPU torch,
convert their weights (gofr_tpu.models.convert), and require logits to
match — the strongest correctness evidence available without golden files.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import LlamaConfig, BertConfig, ViTConfig, llama, bert, vit, param_count
from gofr_tpu.parallel import ShardingRules, build_mesh, shard_pytree


class TestLlama:
    cfg = LlamaConfig.tiny()

    def test_forward_shapes(self):
        params = llama.init(self.cfg, jax.random.key(0))
        tokens = jnp.ones((2, 10), jnp.int32)
        logits = llama.forward(self.cfg, params, tokens)
        assert logits.shape == (2, 10, self.cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_causality(self):
        """Changing a future token must not change past logits."""
        params = llama.init(self.cfg, jax.random.key(0))
        t1 = jnp.array([[5, 6, 7, 8]], jnp.int32)
        t2 = t1.at[0, 3].set(99)
        l1 = llama.forward(self.cfg, params, t1)
        l2 = llama.forward(self.cfg, params, t2)
        np.testing.assert_allclose(np.asarray(l1[0, :3]), np.asarray(l2[0, :3]), rtol=1e-5)
        assert not np.allclose(np.asarray(l1[0, 3]), np.asarray(l2[0, 3]))

    def test_prefill_matches_forward(self):
        params = llama.init(self.cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 6), 0, 256)
        lengths = jnp.array([6, 4])
        cache = llama.make_cache(self.cfg, slots=4, max_len=32)
        logits, cache = llama.prefill(self.cfg, params, tokens, lengths, cache, jnp.array([0, 2]))
        full = llama.forward(self.cfg, params, tokens, lengths)
        np.testing.assert_allclose(
            np.asarray(logits[0]), np.asarray(full[0, 5]), rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(logits[1]), np.asarray(full[1, 3]), rtol=2e-4, atol=2e-4
        )

    def test_decode_matches_forward(self):
        """Prefill + N decode steps == full forward on the whole sequence."""
        params = llama.init(self.cfg, jax.random.key(0))
        seq = jax.random.randint(jax.random.key(1), (1, 8), 0, 256)
        prompt_len = 5
        cache = llama.make_cache(self.cfg, slots=2, max_len=32)
        logits, cache = llama.prefill(
            self.cfg, params, seq[:, :prompt_len], jnp.array([prompt_len]), cache, jnp.array([0])
        )
        full = llama.forward(self.cfg, params, seq)
        np.testing.assert_allclose(
            np.asarray(logits[0]), np.asarray(full[0, prompt_len - 1]), rtol=2e-4, atol=2e-4
        )
        # decode the remaining tokens one at a time in slot 0 (slot 1 idle)
        for i in range(prompt_len, 8):
            tok = jnp.array([seq[0, i], 0], jnp.int32)
            pos = jnp.array([i, 0], jnp.int32)
            step_logits, cache = llama.decode_step(self.cfg, params, tok, pos, cache)
            np.testing.assert_allclose(
                np.asarray(step_logits[0]), np.asarray(full[0, i]), rtol=2e-4, atol=2e-4
            )

    def test_paged_cache_matches_forward(self):
        """Paged prefill + decode through a shuffled block table must match
        the full forward pass (and therefore the dense slot cache)."""
        params = llama.init(self.cfg, jax.random.key(0))
        seq = jax.random.randint(jax.random.key(1), (1, 8), 0, 256)
        prompt_len = 5
        page_size, maxp, pool = 8, 4, 12
        cache = llama.make_paged_cache(self.cfg, pages=pool, page_size=page_size)
        # slot 0 owns shuffled, non-contiguous pages; slot 1 unallocated
        table = jnp.array([[3, 7, 1, 5], [pool, pool, pool, pool]], jnp.int32)
        logits, cache = llama.prefill_paged(
            self.cfg, params, seq[:, :prompt_len], jnp.array([prompt_len]),
            cache, table[:1],
        )
        full = llama.forward(self.cfg, params, seq)
        np.testing.assert_allclose(
            np.asarray(logits[0]), np.asarray(full[0, prompt_len - 1]), rtol=2e-4, atol=2e-4
        )
        for i in range(prompt_len, 8):
            tok = jnp.array([seq[0, i], 0], jnp.int32)
            pos = jnp.array([i, 0], jnp.int32)
            step_logits, cache = llama.decode_step_paged(
                self.cfg, params, tok, pos, cache, table
            )
            np.testing.assert_allclose(
                np.asarray(step_logits[0]), np.asarray(full[0, i]), rtol=2e-4, atol=2e-4
            )

    def test_paged_chunked_prefill_matches_forward(self):
        """Two prefill chunks (the second at a nonzero offset attending to
        the first through the block table) == one whole-prompt prefill."""
        params = llama.init(self.cfg, jax.random.key(0))
        seq = jax.random.randint(jax.random.key(2), (1, 16), 0, 256)
        page_size, pool = 8, 6
        cache = llama.make_paged_cache(self.cfg, pages=pool, page_size=page_size)
        table = jnp.array([[4, 1, 2]], jnp.int32)
        # chunk 1: positions 0..8 (whole-page), chunk 2: positions 8..16
        _, cache = llama.prefill_paged(
            self.cfg, params, seq[:, :8], jnp.array([8]), cache, table,
        )
        logits, cache = llama.prefill_paged(
            self.cfg, params, seq[:, 8:], jnp.array([8]), cache, table,
            offsets=jnp.array([8], jnp.int32),
        )
        full = llama.forward(self.cfg, params, seq)
        np.testing.assert_allclose(
            np.asarray(logits[0]), np.asarray(full[0, 15]), rtol=2e-4, atol=2e-4
        )

    def test_tied_embeddings(self):
        cfg = LlamaConfig.tiny(tie_embeddings=True)
        params = llama.init(cfg, jax.random.key(0))
        assert "lm_head" not in params
        logits = llama.forward(cfg, params, jnp.ones((1, 4), jnp.int32))
        assert logits.shape == (1, 4, cfg.vocab_size)

    def test_untied_lm_head_is_independent(self):
        params = llama.init(self.cfg, jax.random.key(0))
        assert not np.allclose(
            np.asarray(params["embed"]).ravel(), np.asarray(params["lm_head"]).ravel()
        )

    def test_param_axes_match_params(self):
        params = llama.init(self.cfg, jax.random.key(0))
        axes = llama.param_axes(self.cfg)
        flat_p = jax.tree.leaves(params)
        flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
        assert len(flat_p) == len(flat_a)
        for p, a in zip(flat_p, flat_a):
            assert p.ndim == len(a), f"{p.shape} vs {a}"

    def test_tp_sharding_preserves_numerics(self):
        """Forward on a tp=4 mesh must equal the single-device result."""
        mesh = build_mesh("dp:2,tp:4")
        params = llama.init(self.cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 6), 0, 256)
        want = llama.forward(self.cfg, params, tokens)
        sharded = shard_pytree(params, llama.param_axes(self.cfg), ShardingRules(), mesh)
        got = llama.forward(self.cfg, sharded, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    def test_hf_numerics_oracle(self):
        torch = pytest.importorskip("torch")
        from transformers import LlamaConfig as HFConfig, LlamaForCausalLM

        hf_cfg = HFConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rms_norm_eps=1e-5, tie_word_embeddings=False,
        )
        torch.manual_seed(0)
        hf = LlamaForCausalLM(hf_cfg).eval()
        from gofr_tpu.models.convert import llama_from_hf

        cfg, params = llama_from_hf(hf, dtype=jnp.float32)
        tokens = np.random.RandomState(0).randint(0, 128, (2, 9))
        with torch.no_grad():
            want = hf(torch.tensor(tokens)).logits.numpy()
        got = np.asarray(llama.forward(cfg, params, jnp.asarray(tokens)))
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


class TestBert:
    def test_embed_shapes_and_norm(self):
        cfg = BertConfig.tiny()
        params = bert.init(cfg, jax.random.key(0))
        tokens = jnp.ones((3, 12), jnp.int32)
        emb = bert.embed_pooled(cfg, params, tokens, jnp.array([12, 5, 1]))
        assert emb.shape == (3, cfg.hidden_size)
        np.testing.assert_allclose(np.linalg.norm(np.asarray(emb), axis=-1), 1.0, rtol=1e-5)

    def test_padding_invariance(self):
        """Extra padding must not change the pooled embedding."""
        cfg = BertConfig.tiny()
        params = bert.init(cfg, jax.random.key(0))
        t = jax.random.randint(jax.random.key(1), (1, 6), 0, 256)
        short = bert.embed_pooled(cfg, params, t, jnp.array([6]))
        padded = bert.embed_pooled(
            cfg, params, jnp.pad(t, ((0, 0), (0, 10))), jnp.array([6])
        )
        np.testing.assert_allclose(np.asarray(short), np.asarray(padded), rtol=1e-4, atol=1e-5)

    def test_hf_numerics_oracle(self):
        torch = pytest.importorskip("torch")
        from transformers import BertConfig as HFConfig, BertModel

        hf_cfg = HFConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, max_position_embeddings=64,
        )
        torch.manual_seed(0)
        hf = BertModel(hf_cfg).eval()
        from gofr_tpu.models.convert import bert_from_hf

        cfg, params = bert_from_hf(hf)
        tokens = np.random.RandomState(1).randint(0, 128, (2, 7))
        with torch.no_grad():
            want = hf(torch.tensor(tokens)).last_hidden_state.numpy()
        got = np.asarray(bert.encode(cfg, params, jnp.asarray(tokens)))
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


class TestViT:
    def test_forward_shapes(self):
        cfg = ViTConfig.tiny()
        params = vit.init(cfg, jax.random.key(0))
        images = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
        logits = vit.forward(cfg, params, images)
        assert logits.shape == (2, 10)

    def test_no_head_returns_embedding(self):
        cfg = ViTConfig.tiny(num_classes=0)
        params = vit.init(cfg, jax.random.key(0))
        out = vit.forward(cfg, params, jnp.zeros((1, 32, 32, 3)))
        assert out.shape == (1, cfg.hidden_size)

    def test_hf_numerics_oracle(self):
        torch = pytest.importorskip("torch")
        from transformers import ViTConfig as HFConfig, ViTForImageClassification

        hf_cfg = HFConfig(
            image_size=32, patch_size=8, num_channels=3, hidden_size=32,
            intermediate_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_labels=10,
        )
        torch.manual_seed(0)
        hf = ViTForImageClassification(hf_cfg).eval()
        from gofr_tpu.models.convert import vit_from_hf

        cfg, params = vit_from_hf(hf)
        images = np.random.RandomState(2).randn(2, 3, 32, 32).astype(np.float32)
        with torch.no_grad():
            want = hf(torch.tensor(images)).logits.numpy()
        # ours is channels-last
        got = np.asarray(vit.forward(cfg, params, jnp.asarray(images.transpose(0, 2, 3, 1))))
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_param_count_sanity():
    assert param_count(llama.init(LlamaConfig.tiny(), jax.random.key(0))) > 50_000


# -- models/base.qkv_heads: the barrier between product and split changes no value ------


def _plain_qkv_heads(h, lp, head_size):
    """What every family wrote until PR 35: the split straight after the product."""
    from gofr_tpu.ops.quant import qdot

    return tuple(qdot(h, lp[w]).reshape(*h.shape[:-1], -1, head_size) for w in ("wq", "wk", "wv"))


def _qkv_layer(dtype, quantized=False):
    from gofr_tpu.ops.quant import quantize

    keys = jax.random.split(jax.random.key(3), 4)
    lp = {w: jax.random.normal(k, (64, width), jnp.float32).astype(dtype) * 0.1
          for w, k, width in zip(("wq", "wk", "wv"), keys, (64, 32, 32))}
    if quantized:
        lp = {w: quantize(v) for w, v in lp.items()}
    return jax.random.normal(keys[3], (3, 5, 64), jnp.float32).astype(dtype), lp


@pytest.mark.parametrize("case", ["bfloat16", "float32", "qtensor"])
def test_qkv_heads_equals_the_plain_split_bit_for_bit(case):
    from gofr_tpu.models.base import qkv_heads

    h, lp = _qkv_layer(jnp.float32 if case == "float32" else jnp.bfloat16, quantized=case == "qtensor")
    got = jax.jit(qkv_heads, static_argnums=2)(h, lp, 16)
    want = jax.jit(_plain_qkv_heads, static_argnums=2)(h, lp, 16)
    for g, w, heads in zip(got, want, (4, 2, 2)):
        assert g.shape == (3, 5, heads, 16) and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))


@pytest.mark.parametrize("case", ["grad_of_forward", "interpreted_paged_decode"])
def test_programs_through_qkv_heads_equal_the_plain_split(monkeypatch, case):
    """Whole programs, the helper against the plain split patched in where the
    model calls it: a gradient passes through the barrier unchanged
    (``forward`` is training-shaped), and prefill + three decode steps through
    the paged-decode kernel under the Pallas interpreter (heads of 128, the
    append fused) give the same logits, so the same greedy tokens."""
    tokens = jax.random.randint(jax.random.key(1), (2, 14), 3, 120)

    def run():
        jax.clear_caches()  # which split is a trace-time property
        if case == "grad_of_forward":
            cfg = LlamaConfig.tiny()
            params = llama.init(cfg, jax.random.key(0))
            return jax.grad(lambda p: llama.forward(cfg, p, tokens).astype(jnp.float32).mean())(params)
        cfg = LlamaConfig(vocab_size=128, hidden_size=256, intermediate_size=256, num_layers=2,
                          num_heads=2, num_kv_heads=1)
        params = llama.init(cfg, jax.random.key(0))
        table = jnp.arange(4, dtype=jnp.int32).reshape(2, 2)
        lengths = jnp.array([14, 9])
        logits, cache = llama.prefill_paged(cfg, params, tokens, lengths,
                                            llama.make_paged_cache(cfg, 4, 16), table)
        out = [logits]
        for step in range(3):  # the row of 14 crosses into its second page
            logits, cache = llama.decode_step_paged(cfg, params, jnp.argmax(out[-1], -1).astype(jnp.int32),
                                                    lengths + step, cache, table)
            out.append(logits)
        return out

    monkeypatch.setenv("GOFR_PALLAS_INTERPRET", "1")
    got = run()
    traced = []
    monkeypatch.setattr(llama, "qkv_heads", lambda *a: traced.append(1) or _plain_qkv_heads(*a))
    want = run()
    monkeypatch.undo()
    jax.clear_caches()
    assert traced, "the plain split was not traced: both sides ran the helper"
    jax.tree.map(lambda g, w: np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32)),
                 got, want)


class TestGPT2:
    def test_prefill_decode_matches_forward(self):
        """Greedy via prefill+decode_step must equal argmax of incremental
        dense forward — the engine-contract parity every family needs."""
        from gofr_tpu.models import GPT2Config, gpt2

        cfg = GPT2Config.tiny()
        params = gpt2.init(cfg, jax.random.key(5))
        prompt = [7, 3, 11, 20]
        n_new = 6

        seq = list(prompt)
        for _ in range(n_new):
            logits = gpt2.forward(cfg, params, jnp.asarray([seq], jnp.int32))
            seq.append(int(jnp.argmax(logits[0, -1])))
        want = seq[len(prompt):]

        cache = gpt2.make_cache(cfg, 2, 32)
        toks = jnp.asarray([prompt], jnp.int32)
        logits, cache = gpt2.prefill(cfg, params, toks, jnp.array([4]), cache, jnp.array([0]))
        got = [int(jnp.argmax(logits[0]))]
        pos = len(prompt)
        while len(got) < n_new:
            tokens = jnp.array([got[-1], 0], jnp.int32)
            positions = jnp.array([pos, 0], jnp.int32)
            logits, cache = gpt2.decode_step(cfg, params, tokens, positions, cache)
            got.append(int(jnp.argmax(logits[0])))
            pos += 1
        assert got == want

    def test_engine_serves_gpt2(self):
        from gofr_tpu.container import new_mock_container
        from gofr_tpu.models import GPT2Config, ModelSpec
        from gofr_tpu.tpu.engine import build_engine

        cfg = GPT2Config.tiny()
        eng = build_engine(ModelSpec(family="gpt2", task="generate", config=cfg),
                           new_mock_container(), seed=5, slots=2, max_len=48,
                           max_prefill_batch=2, quantize="int8")
        try:
            out = eng.generate([7, 3, 11], max_new_tokens=5, timeout=120)
            assert len(out["tokens"]) == 5 and out["finish_reason"] == "length"
        finally:
            eng.stop()

    def test_hf_numerics_oracle(self):
        torch = pytest.importorskip("torch")
        from transformers import GPT2Config as HFConfig, GPT2LMHeadModel

        hf_cfg = HFConfig(
            vocab_size=128, n_embd=32, n_layer=2, n_head=4, n_positions=64,
        )
        torch.manual_seed(0)
        hf = GPT2LMHeadModel(hf_cfg).eval()
        from gofr_tpu.models import gpt2
        from gofr_tpu.models.convert import gpt2_from_hf

        cfg, params = gpt2_from_hf(hf, dtype=jnp.float32)
        tokens = np.random.RandomState(2).randint(0, 128, (2, 9))
        with torch.no_grad():
            want = hf(torch.tensor(tokens)).logits.numpy()
        got = np.asarray(gpt2.forward(cfg, params, jnp.asarray(tokens)))
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
