"""BASELINE row 4 scaffolding: Llama-3-70B sharded over a virtual v5e-64
mesh. No 70B weights exist in this sandbox, so the provable claim is that
the FULL sharded programs (train step; serving prefill + decode) trace and
lower with real dp/fsdp/tp shardings over 64 devices using abstract arrays
only — the exact artifacts a v5e-64 deployment would compile. Runs in a
subprocess so the 64-device CPU platform doesn't leak into other tests."""

import os
import subprocess
import sys
import textwrap

import pytest

# integration tier (CI `integration` job): multi-minute engine/process
# runs — excluded from the tier-1 gate via -m 'not slow' (docs/testing.md)
pytestmark = pytest.mark.slow

_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, "@REPO@")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gofr_tpu.models import LlamaConfig, llama
    from gofr_tpu.parallel import build_mesh
    from gofr_tpu.parallel.sharding import fsdp_rules, sharding_tree

    cfg = LlamaConfig.llama3_70b()
    assert cfg.num_layers == 80 and cfg.hidden_size == 8192, cfg
    mesh = build_mesh("dp:2,fsdp:4,tp:8", devices=jax.devices("cpu")[:64])

    # abstract params with REAL shardings attached — nothing materializes
    shapes = jax.eval_shape(lambda: llama.init(cfg, jax.random.key(0)))
    rules = fsdp_rules()
    shardings = sharding_tree(llama.param_axes(cfg), rules, mesh)
    params_abs = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings,
    )

    SLOTS, SEQ = 64, 2048
    cache_abs = jax.eval_shape(lambda: llama.make_cache(cfg, SLOTS, SEQ))

    def prefill(params, tokens, lengths, cache, slots):
        return llama.prefill(cfg, params, tokens, lengths, cache, slots)

    lowered = jax.jit(prefill).lower(
        params_abs,
        jax.ShapeDtypeStruct((8, 512), jnp.int32),
        jax.ShapeDtypeStruct((8,), jnp.int32),
        cache_abs,
        jax.ShapeDtypeStruct((8,), jnp.int32),
    )
    text = lowered.as_text()
    assert "mhlo.sharding" in text or "sdy.sharding" in text, (
        "no sharding annotations in the lowered 70B prefill")
    print("PREFILL_LOWERED bytes:", len(text))
    hlo_p = lowered.compile().as_text()
    assert "all-reduce" in hlo_p, "compiled 70B prefill has no tp all-reduce"
    print("PREFILL_COMPILED collectives:", hlo_p.count("all-reduce"))

    def decode(params, tokens, positions, cache):
        return llama.decode_step(cfg, params, tokens, positions, cache)

    lowered_d = jax.jit(decode).lower(
        params_abs,
        jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
        jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
        cache_abs,
    )
    print("DECODE_LOWERED bytes:", len(lowered_d.as_text()))
    # full GSPMD partition + compile: the all-reduces the tp sharding implies
    # must appear in the compiled module (this IS the v5e-64 program)
    compiled = lowered_d.compile()
    hlo = compiled.as_text()
    assert "all-reduce" in hlo, "compiled 70B decode has no tp all-reduce"
    print("DECODE_COMPILED collectives:", hlo.count("all-reduce"))

    from gofr_tpu.train import make_train_step
    init_fn, step_fn = make_train_step(cfg, llama, mesh, rules=rules, remat=True)
    state_abs = jax.eval_shape(init_fn, jax.random.key(0))
    lowered_t = jax.jit(step_fn).lower(
        state_abs,
        jax.ShapeDtypeStruct((8, 1024), jnp.int32),
        jax.ShapeDtypeStruct((8,), jnp.int32),
    )
    print("TRAIN_LOWERED bytes:", len(lowered_t.as_text()))
    hlo_t = lowered_t.compile().as_text()
    assert "all-reduce" in hlo_t, "compiled 70B train step has no collectives"
    print("TRAIN_COMPILED collectives:", hlo_t.count("all-reduce"))
    # pipeline-parallel SERVING at 70B (BASELINE row 4's weight-fit
    # topology): blocks + slot KV cache layer-sharded over pp:8, heads
    # over tp:8 — the GPipe decode program a v5e-64 deployment compiles
    # (models/llama_pp.py). ppermute must survive into the compiled HLO.
    from gofr_tpu.models.llama_pp import PPLlamaFamily
    from gofr_tpu.parallel.sharding import ShardingRules

    mesh_pp = build_mesh("pp:8,tp:8", devices=jax.devices("cpu")[:64])
    rules_pp = ShardingRules().with_overrides(layers="pp")
    fam = PPLlamaFamily(mesh_pp, microbatches=8, rules=rules_pp)
    shardings_pp = sharding_tree(llama.param_axes(cfg), rules_pp, mesh_pp)
    params_pp = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings_pp,
    )
    cache_sh = NamedSharding(mesh_pp, fam._cache_spec())
    cache_pp = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=cache_sh),
        jax.eval_shape(lambda: llama.make_cache(cfg, SLOTS, SEQ)),
    )

    def decode_pp(params, tokens, positions, cache):
        return fam.decode_step(cfg, params, tokens, positions, cache)

    lowered_pp = jax.jit(decode_pp).lower(
        params_pp,
        jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
        jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
        cache_pp,
    )
    hlo_pp = lowered_pp.compile().as_text()
    assert "collective-permute" in hlo_pp, (
        "compiled 70B pp decode has no stage-ring collective-permute")
    assert "all-reduce" in hlo_pp, "compiled 70B pp decode has no tp psum"
    print("PP_SERVE_COMPILED collective-permutes:",
          hlo_pp.count("collective-permute"), "all-reduces:", hlo_pp.count("all-reduce"))

    import math
    n_params = sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))
    assert 6.5e10 < n_params < 7.5e10, f"not 70B-scale: {n_params}"
    print(f"SCALE_OK params={n_params/1e9:.1f}B mesh=dp:2,fsdp:4,tp:8 devices=64")
""")


def test_llama70b_sharded_programs_lower_on_v5e64_mesh():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)  # JAX_PLATFORMS=cpu (conftest pin) is inherited
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _WORKER.replace("@REPO@", repo)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SCALE_OK params=" in out.stdout, out.stdout
    assert "PREFILL_LOWERED" in out.stdout
    assert "TRAIN_LOWERED" in out.stdout
    assert "PP_SERVE_COMPILED" in out.stdout
