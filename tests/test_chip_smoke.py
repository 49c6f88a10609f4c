"""chip_smoke.py rehearsed on the CPU mesh, and the loud paths it relies on.

The smoke's body (``run_smoke``) runs here at ``LlamaConfig.tiny()`` width on
the virtual devices — both KV layouts over HTTP, the six kernels under the
Pallas interpreter, the tp:4 pass — so a change that breaks the script is
caught before it costs chip time. Only the script ENTRY insists on a TPU.
"""

import asyncio
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smoke_body_on_cpu_mesh():
    import chip_smoke
    from gofr_tpu.models import LlamaConfig

    # heads divisible by 4 so the tp:4 pass shards the pool, not just weights
    cfg = LlamaConfig.tiny(num_heads=8, num_kv_heads=4)
    shape = chip_smoke.Shape(
        slots=4, max_len=64, page_size=8, prefill_buckets=(16, 32),
        prompt_lens=(9, 12, 16, 20, 24, 28, 30, 32), new_tokens=8)
    out = chip_smoke.run_smoke(cfg, shape, interpret_kernels=True)

    assert out["ok"] is True
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    for name in ("slot", "paged", "four_chip"):
        p = out["passes"][name]
        assert p["requests"] == 11 and p["programs"] > 0 and p["sse_chunks"] > 1, (name, p)
        assert p["first_tokens"] == {"exact": 8, "near_tie": 0}, (name, p)  # f32: exact
    assert out["passes"]["four_chip"]["mesh"] == "tp:4"
    assert out["passes"]["four_chip"]["first_tokens_equal_one_chip"] == 8
    assert out["four_chip"] == "ok"
    assert len(out["kernels"]) == 8 and all(k["compiled"] for k in out["kernels"].values())
    assert out["kernels"]["paged_decode_append_bf16"]["max_err"] <= 1e-4  # f32 here; the planes exact
    for name, backend in (("paged", "paged_decode"), ("four_chip", "paged_decode")):
        assert out["passes"][name]["decode_backend"] == {backend: "xla", "paged_append": "scatter"}, name
    assert out["passes"]["slot"]["decode_backend"] == {"decode": "xla"}
    assert out["compile_cache"]["dir"] == jax.config.jax_compilation_cache_dir
    assert out["planner"] == ("native" if shutil.which("g++") else "python")
    json.dumps(out)  # the script prints it as one line, then the verdict as the last
    assert chip_smoke.verdict(out) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 8}}


@pytest.mark.quick
def test_script_entry_refuses_a_non_tpu_platform():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.startswith(f"jax {jax.__version__}  platform cpu")
    assert "platform is 'cpu', not 'tpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


# -- the loud paths --------------------------------------------------------------
# (a pool the kernel cannot read: tests/test_kernel_backend.py)


@pytest.mark.quick
def test_explicit_pallas_request_raises_where_no_kernel_can_lower(monkeypatch):
    from gofr_tpu.ops.attention import resolve_backend
    from gofr_tpu.ops.kvcache import append_tokens

    monkeypatch.delenv("GOFR_PALLAS_INTERPRET", raising=False)
    assert resolve_backend("auto") == "xla"  # 'auto' may pick XLA; a named kernel may not
    with pytest.raises(RuntimeError, match="backend='pallas' asks for a Pallas kernel.*'cpu'"):
        resolve_backend("pallas")

    kv = jnp.zeros((2, 1, 8, 4))
    new, pos = jnp.ones((2, 1, 4)), jnp.asarray([0, 1])
    monkeypatch.setenv("GOFR_KV_WRITE", "pallas")
    with pytest.raises(RuntimeError, match="GOFR_KV_WRITE=pallas"):
        append_tokens(kv, kv, pos, new, new)


@pytest.mark.quick
def test_engine_warmup_true_with_a_raising_warmup_stops_the_boot():
    from gofr_tpu import App
    from gofr_tpu.config import DictConfig
    from gofr_tpu.container import new_mock_container

    class BrokenEngine:
        started = False

        def warmup(self):
            raise RuntimeError("kernel refused by the compiler")

        def start(self):
            self.started = True

        def stop(self):
            pass

    def boot(warm: str) -> BrokenEngine:
        conf = {"ENGINE_WARMUP": warm, "HTTP_PORT": "0", "METRICS_PORT": "0"}
        app = App(config=DictConfig(conf), container=new_mock_container(conf))
        engine = BrokenEngine()
        app.serve_model("lm", engine=engine)

        async def run():
            ready = asyncio.Event()
            task = asyncio.ensure_future(app.arun(ready=ready))
            await asyncio.wait({task, asyncio.ensure_future(ready.wait())},
                               return_when=asyncio.FIRST_COMPLETED)
            if not task.done():
                app.stop()
            await task

        asyncio.run(run())
        return engine

    with pytest.raises(RuntimeError, match="kernel refused"):
        boot("true")
    assert boot("false").started  # without ENGINE_WARMUP nobody calls warmup


@pytest.mark.quick
def test_one_place_decides_the_compile_cache(monkeypatch):
    from gofr_tpu.tpu.device import ensure_compile_cache

    in_checkout = os.path.join(REPO, ".cache", "jax")
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert ensure_compile_cache() == in_checkout
        assert jax.config.jax_compilation_cache_dir == in_checkout
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

    # set from outside: reported as-is, and nothing is set in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    seen = []
    monkeypatch.setattr(jax.config, "update", lambda *a: seen.append(a))
    assert ensure_compile_cache() == "/some/dir"
    assert seen == []
