"""The plain reference against the program's own full forward pass at a tiny
float32 size: two independent writings of the same block must agree to
rounding. (On the chip the comparison is served first tokens against this
reference at the published widths — ``run.py``.)"""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))


@pytest.mark.parametrize("heads,kv_heads,n,pad_to", [(8, 4, 21, 32), (4, 4, 32, 32), (4, 1, 5, 16)])
def test_reference_equals_llama_forward(heads, kv_heads, n, pad_to):
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.manifest import load_module
    from gofr_tpu.models import LlamaConfig, llama

    ref = load_module(os.path.join(BENCH, "references", "rope_gqa_swiglu.py"))
    cfg = LlamaConfig.tiny(num_heads=heads, num_kv_heads=kv_heads, rope_theta=1e6)
    params = llama.init(cfg, jax.random.key(3))
    spec = {"num_attention_heads": heads, "num_key_value_heads": kv_heads, "hidden_size": cfg.hidden_size,
            "rope_theta": 1e6, "rms_norm_eps": cfg.norm_eps, "num_hidden_layers": cfg.num_layers,
            "tie_word_embeddings": False}
    toks = [int(t) for t in np.random.RandomState(n).randint(3, cfg.vocab_size, size=n)]
    got = np.asarray(ref.last_logits(spec, params, toks, pad_to))
    want = np.asarray(llama.forward(cfg, params, jnp.asarray([toks]), jnp.asarray([n]))[0, n - 1])
    # float32 both sides, different operation order: a few ulps at the logits' magnitude
    assert np.max(np.abs(got - want)) <= 1e-5 * max(1.0, float(np.max(np.abs(want))))
    assert int(np.argmax(got)) == int(np.argmax(want))
