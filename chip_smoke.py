"""Quickest proof that the serving path still starts on the chip.

    python chip_smoke.py

One process, no options. Drives the framework's main path — HTTP request →
``App`` handler → ``ctx.agenerate`` → ``GenerateEngine`` → jitted Llama
prefill/decode → streamed tokens — at the full width of ``LlamaConfig.one_b()``
(seeded random weights) through the shipped example app, once per KV layout;
then every Pallas entry point against its XLA counterpart at the same serving
shapes; then, where four devices are visible, the same app on a ``tp:4`` mesh.
Any failed check raises: nothing is caught and continued. The last two lines
of standard output are JSON objects: the summary of the run (parameters,
programs compiled, set-up seconds, compile cache, per-pass and per-kernel
results), then the verdict the driver reads, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

It exits non-zero unless JAX's platform is ``tpu``. Set-up seconds are
reported as set-up; the script prints no rate. ``tests/test_chip_smoke.py``
drives the same body (:func:`run_smoke`) at a tiny config on the CPU mesh.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import socket
import sys
import time
import weakref

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from gofr_tpu import native  # noqa: E402
from gofr_tpu.config import DictConfig  # noqa: E402
from gofr_tpu.models import LlamaConfig, llama  # noqa: E402
from gofr_tpu.testutil import assert_paged_pool_consistent  # noqa: E402
from gofr_tpu.tpu.device import ensure_compile_cache  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Shape:
    """The serving shape every pass runs at. The defaults are the chip's:
    not a toy (32 slots, 1,024 positions each) yet small enough that three
    engines compile inside the time limit. Prefill buckets cover exactly
    the prompt lengths the smoke sends."""

    slots: int = 32
    max_len: int = 1024
    page_size: int = 128
    prefill_buckets: tuple[int, ...] = (128, 256, 512)
    prompt_lens: tuple[int, ...] = (128, 160, 200, 256, 300, 384, 448, 512)
    new_tokens: int = 64


# -- bookkeeping ----------------------------------------------------------------


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def _require(ok, message) -> None:
    """The smoke's checks must survive ``python -O``, so they are not asserts."""
    if not ok:
        raise SmokeFailure(str(message))


class _CompileCounter:
    """Counts what JAX itself reports: compile requests (each one an
    executable built, or fetched from the persistent cache — either way a
    program nobody had ready) and how many of them the cache answered."""

    def __init__(self):
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += seconds

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _load_example_app():
    spec = importlib.util.spec_from_file_location(
        "serving_llm_example", os.path.join(HERE, "examples", "serving-llm", "main.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.build_app


def _metric(text: str, name: str) -> float:
    """Sum of a counter's series in Prometheus text (absent = 0)."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in (" ", "{"):
            total += float(line.rsplit(" ", 1)[1])
    return total


def _near_tie_tol(dtype, logits: np.ndarray) -> float:
    """How far below the reference's best logit a served token may sit and
    still count as the same answer: two ulps of the compute dtype at the
    logits' magnitude. On a random-weight model the top two logits are
    often that close, and the engine's padded, batched prefill need not
    round the way a single unpadded forward does (testutil.tiny_f32_llama
    says the same of sharded reductions). f32: effectively exact."""
    return 2.0 * float(jnp.finfo(dtype).eps) * float(np.max(np.abs(logits)))


def _check_first_tokens(tag: str, firsts: list[int], ref_logits: np.ndarray, tol: float) -> dict:
    exact = near = 0
    for i, tok in enumerate(firsts):
        row = ref_logits[i]
        best = int(np.argmax(row))
        if tok == best:
            exact += 1
        elif row[best] - row[tok] <= tol:
            near += 1
        else:
            raise SmokeFailure(
                f"{tag}: request {i} first token {tok} (reference logit {row[tok]:.4f}) is "
                f"not llama.forward's argmax {best} ({row[best]:.4f}), gap beyond the "
                f"near-tie tolerance {tol:.4f}")
    return {"exact": exact, "near_tie": near}


# -- serve pass -----------------------------------------------------------------


async def _post_generate(session, base: str, prompt: list[int], new_tokens: int) -> list[int]:
    async with session.post(f"{base}/generate", json={
            "prompt": prompt, "max_new_tokens": new_tokens, "timeout": 300}) as resp:
        body = await resp.json()
        _require(resp.status == 201, (resp.status, body))
        return [int(t) for t in body["data"]["tokens"]]


async def _post_stream(session, base: str, prompt: list[int], new_tokens: int) -> tuple[list[int], int]:
    """→ (streamed tokens, number of body chunks the events arrived in)."""
    tokens: list[int] = []
    chunks, saw_done, event, buf = 0, False, None, b""
    async with session.post(f"{base}/generate/stream", json={
            "prompt": prompt, "max_new_tokens": new_tokens, "timeout": 300}) as resp:
        _require(resp.status == 200, resp.status)
        _require(resp.headers["Content-Type"].startswith("text/event-stream"), resp.headers)
        async for chunk in resp.content.iter_any():
            chunks += 1
            buf += chunk
            while b"\n" in buf:
                line, buf = buf.split(b"\n", 1)
                text = line.decode()
                if text.startswith("event: "):
                    event = text[len("event: "):]
                elif text.startswith("data: "):
                    if event == "token":
                        tokens.append(int(json.loads(text[len("data: "):])))
                    elif event == "done":
                        saw_done = True
                    elif event == "error":
                        raise SmokeFailure(f"SSE error event: {text}")
    _require(saw_done, "SSE stream ended without a done event")
    return tokens, chunks


async def _serve_pass(build_app, cfg, shape: Shape, layout: str, prompts: list[list[int]],
                      compiles: _CompileCounter, *, devices: int, mesh: str | None,
                      reference: dict | None) -> dict:
    """Boot the example app at ``cfg`` on ``devices`` device(s), answer the
    requests over HTTP, check everything, stop it → the pass's summary, with
    ``firsts`` (each answer's first token) and ``reference`` set aside for a
    later pass: handed a ``reference``, this one skips llama.forward and
    compares against that."""
    import aiohttp

    tag = f"{layout}@{mesh or 'dp:1'}"
    t0 = time.monotonic()
    conf = {
        "APP_NAME": "chip-smoke", "LOG_LEVEL": "INFO",
        "HTTP_PORT": str(_free_port()), "METRICS_PORT": str(_free_port()),
        "ENGINE_WARMUP": "true", "TPU_DEVICES": str(devices),
    }
    if mesh:
        conf["TPU_MESH"] = mesh
    app = build_app(
        DictConfig(conf), model_config=cfg, kv_layout=layout, slots=shape.slots,
        max_len=shape.max_len, page_size=shape.page_size,
        prefill_buckets=list(shape.prefill_buckets))
    engine = app.container.engine("lm")
    build_s = time.monotonic() - t0
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.params))

    if reference is None:
        # last-position logits of llama.forward on each prompt, on the
        # engine's own weights and device
        longest = max(len(p) for p in prompts)
        toks = np.zeros((len(prompts), longest), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        lens = np.asarray([len(p) for p in prompts], np.int32)
        logits = llama.forward(cfg, engine.params, jnp.asarray(toks), jnp.asarray(lens))
        ref_logits = np.asarray(logits[np.arange(len(prompts)), lens - 1])
        _require(np.all(np.isfinite(ref_logits)), f"{tag}: reference logits not finite")
        del logits
        reference = {"logits": ref_logits, "tol": _near_tie_tol(cfg.dtype, ref_logits)}

    t1 = time.monotonic()
    ready = asyncio.Event()
    server = asyncio.ensure_future(app.arun(ready=ready))
    waiter = asyncio.ensure_future(ready.wait())
    await asyncio.wait({server, waiter}, return_when=asyncio.FIRST_COMPLETED)
    if server.done():
        waiter.cancel()
        server.result()  # a boot failure (warmup raised) surfaces here
        raise SmokeFailure(f"{tag}: app exited before it was ready")
    warmup_s = time.monotonic() - t1
    programs = len(engine._compiled)
    _require(programs > 0, f"{tag}: warmup compiled no programs")
    report = engine.autotune_report()  # what serves the decode op (the rule; a shape the kernel refuses fails the boot above)

    base = f"http://127.0.0.1:{app.http_port}"
    metrics_url = f"http://127.0.0.1:{app.metrics_port}/metrics"
    timeout = aiohttp.ClientTimeout(total=600)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        async with session.get(metrics_url) as r:
            before = await r.text()
        xla_before = compiles.requests

        # the request window: every prompt at once, one of them also streamed
        unary = [_post_generate(session, base, p, shape.new_tokens) for p in prompts]
        stream = _post_stream(session, base, prompts[1], shape.new_tokens)
        *answers, (streamed, chunks) = await asyncio.gather(*unary, stream)
        # then one prompt twice more, each alone on an idle engine (the same
        # programs both times), which must give the same tokens
        again = [await _post_generate(session, base, prompts[0], shape.new_tokens)
                 for _ in range(2)]

        xla_in_window = compiles.requests - xla_before
        async with session.get(metrics_url) as r:
            after = await r.text()
        async with session.get(f"{base}/.well-known/health") as r:
            health = (await r.json())["data"]

    for i, toks in enumerate(answers + [streamed] + again):
        _require(len(toks) == shape.new_tokens, f"{tag}: answer {i} has {len(toks)} tokens")
        _require(all(0 <= t < cfg.vocab_size for t in toks), f"{tag}: answer {i} out of vocabulary")
    _require(chunks > 1, f"{tag}: SSE events arrived in {chunks} chunk(s)")
    _require(again[0] == again[1], f"{tag}: the same prompt gave different tokens: {again}")
    firsts = _check_first_tokens(tag, [a[0] for a in answers], reference["logits"], reference["tol"])
    _check_first_tokens(tag + " sse", [streamed[0]], reference["logits"][1:2], reference["tol"])
    _check_first_tokens(tag + " repeat", [again[0][0]], reference["logits"][0:1], reference["tol"])

    restarts = _metric(after, "app_tpu_engine_restarts")
    _require(restarts == 0, f"{tag}: engine device loop restarted {restarts:g} time(s)")
    grew = _metric(after, "app_tpu_compile_total") - _metric(before, "app_tpu_compile_total")
    _require(grew == 0, f"{tag}: {grew:g} program(s) compiled inside the request window")
    _require(xla_in_window == 0,
             f"{tag}: JAX compiled {xla_in_window} program(s) inside the request window")

    _require(health["status"] == "UP", health)
    tpu = health["services"]["tpu"]["details"]
    platform = jax.devices()[0].platform
    _require(tpu["platform"] == platform and tpu["devices"] == devices, tpu)
    in_use = [m["bytes_in_use"] for m in tpu["memory"].values()]
    if jax.devices()[0].memory_stats() is not None:  # the CPU client reports none
        _require(all(b > 0 for b in in_use), f"{tag}: bytes_in_use {in_use}")
        _require(max(in_use) <= 2 * min(in_use),
                 f"{tag}: per-device bytes_in_use {in_use} differ by more than 2x — "
                 "weights or pool are not spread over the mesh")

    if layout == "paged":
        deadline = time.monotonic() + 10
        while (engine._decode_lanes or engine._prefill_lanes) and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        assert_paged_pool_consistent(engine, slots_empty=True)

    app.stop()
    await server
    stats = {
        "devices": devices, "mesh": mesh or "dp:1", "requests": len(answers) + 3,
        "sse_chunks": chunks, "first_tokens": firsts, "programs": programs,
        "build_s": round(build_s, 1), "warmup_s": round(warmup_s, 1),
        "decode_backend": {op: rec["backend"] for op, rec in report["decisions"].items()},
        "bytes_in_use": in_use, "params": n_params,
        "firsts": [a[0] for a in answers], "reference": reference,
    }
    # stopped AND freed: the next pass needs the device memory
    freed = weakref.ref(engine)
    del app, engine, server
    gc.collect()
    _require(freed() is None, f"{tag}: the engine is still referenced after the app stopped")
    return stats


# -- kernel pass ----------------------------------------------------------------


def _kernel_pass(cfg, shape: Shape, *, interpret: bool) -> dict:
    """Each Pallas entry point at the serve pass's shapes with full-length
    histories (the decode step's fused append + attention at the head
    geometry it serves), against the XLA implementation of the same op. Tolerance:
    attention outputs (unit-normal K/V, so outputs are O(1)) within 3e-2
    absolute at bf16 and 1e-4 at f32 — the quantized kernels fold their
    scales in f32 where XLA folds them in the compute dtype; the appends
    move data and must match exactly."""
    from gofr_tpu.ops import attention as attn
    from gofr_tpu.ops import kvcache, paged
    from gofr_tpu.ops.pallas import decode_attention as k_slot
    from gofr_tpu.ops.pallas import flash_attention as k_flash
    from gofr_tpu.ops.pallas import kv_append as k_append
    from gofr_tpu.ops.pallas import paged_decode as k_paged
    from gofr_tpu.ops.quant import pack_int4, quantize_row_int4

    dt = cfg.dtype
    atol = 3e-2 if dt == jnp.bfloat16 else 1e-4
    n, hq, hkv, d = shape.slots, cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    page, chunk = shape.page_size, 8  # the engine's default decode chunk
    maxp = -(-(shape.max_len + chunk) // page)
    pool = n * maxp
    smax = -(-(shape.max_len + chunk) // 128) * 128
    rng = np.random.RandomState(0)

    def normal(*dims):
        return jnp.asarray(rng.standard_normal(dims), dt)

    q = normal(n, hq, d)
    # the ops take whole [L, P, ...] planes and a layer index: two layers,
    # the second one used
    layer = 1
    k_pool, v_pool = normal(2, pool, hkv, page, d), normal(2, pool, hkv, page, d)
    table = jnp.asarray(rng.permutation(pool).reshape(n, maxp), jnp.int32)
    # lane 0's last logical page is unallocated (the pool-size sentinel), the
    # way the engine marks rows whose write must be dropped
    append_table = table.at[0, maxp - 1].set(pool)
    full = jnp.full((n,), maxp * page, jnp.int32)
    # ragged lanes: empty, one token, either side of a page boundary, full;
    # past its live pages a lane's table holds the sentinel, as the engine's does
    ragged = jnp.asarray(
        [(0, 1, page - 1, page, page + 1, maxp * page)[i % 6] for i in range(n)], jnp.int32)
    ragged_table = jnp.where(
        jnp.arange(maxp)[None, :] * page < ragged[:, None], table, pool)
    reads = ((table, full), (ragged_table, ragged))
    k_cache, v_cache = normal(n, hkv, smax, d), normal(n, hkv, smax, d)
    k8, ks8 = kvcache.quantize_row(k_pool)
    v8, vs8 = kvcache.quantize_row(v_pool)
    k4, ks4 = quantize_row_int4(k_pool)
    v4, vs4 = quantize_row_int4(v_pool)
    k4, v4 = pack_int4(k4), pack_int4(v4)
    sdt = jnp.bfloat16  # the pools' scale-plane dtype
    ks8, vs8, ks4, vs4 = (s.astype(sdt) for s in (ks8, vs8, ks4, vs4))

    b, s = 4, shape.prefill_buckets[-1]  # the engine's default prefill batch
    qp, kp, vp = normal(b, s, hq, d), normal(b, s, hkv, d), normal(b, s, hkv, d)
    plen = jnp.asarray(rng.randint(s // 2, s + 1, size=(b,)), jnp.int32)

    k_new, v_new = normal(n, hkv, d), normal(n, hkv, d)
    # one write position per lane anywhere in its history; lane 0's is dropped
    # (paged: it lands on the unallocated page; slot: it is past the cache)
    pos = jnp.asarray(rng.randint(0, shape.max_len, size=(n,)), jnp.int32)
    paged_pos, slot_pos = pos.at[0].set((maxp - 1) * page + 3), pos.at[0].set(smax + 3)

    def xla(fn, **kw):
        return jax.jit(functools.partial(fn, **kw))

    def jitted(fn):  # the paged kernels' wrappers carry no jit of their own
        return jax.jit(functools.partial(fn, interpret=interpret))

    def numpy_append(pool_plane, new):
        """Row i at (layer, table[i, pos // page], :, pos % page); an
        unallocated page (the pool-size sentinel) drops the row."""
        want = np.array(pool_plane)
        for i, p_i in enumerate(np.asarray(paged_pos)):
            pg = int(np.asarray(append_table)[i, p_i // page])
            if pg < pool:
                want[layer, pg, :, p_i % page] = np.asarray(new[i])
        return want

    # a decode step's append + attention as the paged-decode kernel's ONE call
    # (what serves at head_dim 128: ops/attention.append_rides_in_kernel),
    # against the XLA pair — the scatter, then the gathered read. The
    # benchmark's head geometry (8 KV heads of 128, G = 2), this pass's table
    # and write positions, every third lane from lane 2 on idle (its table
    # row all sentinel, as the engine masks a lane that is not decoding: the
    # kernel skips it and the chain of copies runs between the live lanes
    # around it). Lane 0's write is dropped and what it then attends on its
    # unallocated page means nothing: its output is left out, as are the idle
    # lanes' (zeros from the kernel, a clamped page from XLA).
    q_d, kn_d, vn_d = normal(n, 16, 128), normal(n, 8, 128), normal(n, 8, 128)
    kp_d, vp_d = normal(2, pool, 8, page, 128), normal(2, pool, 8, page, 128)
    idle = np.arange(n) % 3 == 2
    fused_table = jnp.where(jnp.asarray(idle)[:, None], pool, append_table)
    kept = np.flatnonzero(~idle)[1:]

    def fused_step():
        out, k_out, v_out = jitted(k_paged.paged_decode_append_attention)(
            q_d, kn_d, vn_d, kp_d, vp_d, layer, fused_table, paged_pos)
        return out[kept], k_out, v_out

    def xla_pair():
        k_out, v_out = jax.jit(paged.append_tokens_paged)(
            kp_d, vp_d, layer, fused_table, paged_pos, kn_d, vn_d)
        out = xla(attn.paged_decode_attention, backend="xla")(
            q_d, k_out, v_out, layer, fused_table, paged_pos + 1)
        return out[kept], k_out, v_out

    cases = {
        "flash_prefill": (
            lambda: k_flash.flash_attention(qp, kp, vp, causal=True, kv_lengths=plen,
                                            interpret=interpret),
            lambda: xla(attn.mha_attention, causal=True, backend="xla")(
                qp, kp, vp, kv_lengths=plen), atol),
        "slot_decode": (
            lambda: k_slot.decode_attention(q, k_cache, v_cache, jnp.full((n,), smax, jnp.int32),
                                            interpret=interpret),
            lambda: xla(attn.decode_attention, backend="xla")(
                q, k_cache, v_cache, jnp.full((n,), smax, jnp.int32)), atol),
        "paged_decode_bf16": (
            lambda: [jitted(k_paged.paged_decode_attention)(q, k_pool, v_pool, layer, tb, ln)
                     for tb, ln in reads],
            lambda: [xla(attn.paged_decode_attention, backend="xla")(
                q, k_pool, v_pool, layer, tb, ln) for tb, ln in reads], atol),
        "paged_decode_append_bf16": (fused_step, xla_pair, (atol, 0.0, 0.0)),  # output; the planes bit for bit
        "paged_decode_int8": (
            lambda: jitted(k_paged.paged_decode_attention_q)(
                q, k8, v8, ks8, vs8, layer, table, full),
            lambda: xla(attn.paged_decode_attention_q, backend="xla")(
                q, k8, v8, ks8, vs8, layer, table, full), atol),
        "paged_decode_int4": (
            lambda: jitted(k_paged.paged_decode_attention_q4)(
                q, k4, v4, ks4, vs4, layer, table, full),
            lambda: xla(attn.paged_decode_attention_q4, backend="xla")(
                q, k4, v4, ks4, vs4, layer, table, full), atol),
        "slot_append": (
            lambda: jax.jit(functools.partial(k_append.append_tokens_inplace,
                                              interpret=interpret))(
                k_cache, v_cache, slot_pos, k_new, v_new),
            lambda: jax.jit(kvcache.append_tokens)(k_cache, v_cache, slot_pos, k_new, v_new),
            0.0),
        # no kernel: the one paged append (XLA's scatter into the whole
        # planes) against a plain NumPy reference
        "paged_append": (
            lambda: jax.jit(paged.append_tokens_paged)(
                k_pool, v_pool, layer, append_table, paged_pos, k_new, v_new),
            lambda: (numpy_append(k_pool, k_new), numpy_append(v_pool, v_new)),
            0.0),
    }
    verdicts = {}
    for name, (kernel, reference, tol) in cases.items():
        got = jax.block_until_ready(kernel())  # a refusal by the compiler raises here
        want = jax.block_until_ready(reference())
        errs = [float(jnp.max(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32))))
                for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        tols = tol if isinstance(tol, tuple) else (tol,) * len(errs)  # one a result, or one for all
        _require(all(np.isfinite(e) and e <= t for e, t in zip(errs, tols)),
                 f"kernel {name}: max |kernel - xla| = {errs} exceeds {tols}")
        verdicts[name] = {"compiled": True, "max_err": round(max(errs), 5), "tol": max(tols)}
    return verdicts


# -- the body -------------------------------------------------------------------

def run_smoke(cfg, shape: Shape = Shape(), *, interpret_kernels: bool = False) -> dict:
    """All passes at model config ``cfg``; returns the summary the script
    prints. Raises on the first failed check."""
    t_start = time.monotonic()
    planner = native.planner_in_use()
    if shutil.which("g++"):
        _require(planner == "native",
                 f"g++ is present but the native build failed: {native.build_error()}")

    dev = jax.devices()[0]
    compiles = _CompileCounter()
    build_app = _load_example_app()
    rng = np.random.RandomState(1)
    prompts = [[int(t) for t in rng.randint(3, cfg.vocab_size, size=(n,))]
               for n in shape.prompt_lens]

    async def one_chip_passes():
        return {layout: await _serve_pass(build_app, cfg, shape, layout, prompts, compiles,
                                          devices=1, mesh=None, reference=None)
                for layout in ("slot", "paged")}

    passes = asyncio.run(one_chip_passes())
    one_chip = passes["paged"]
    kernels = _kernel_pass(cfg, shape, interpret=interpret_kernels)
    gc.collect()

    if len(jax.devices()) >= 4:
        four = asyncio.run(_serve_pass(
            build_app, cfg, shape, "paged", prompts, compiles, devices=4, mesh="tp:4",
            reference=one_chip["reference"]))
        four["first_tokens_equal_one_chip"] = sum(
            a == b for a, b in zip(four["firsts"], one_chip["firsts"]))
        passes["four_chip"] = four
        four_chip = "ok"
    else:
        four_chip = f"skipped ({len(jax.devices())} device)"
    n_params = passes["slot"]["params"]
    for stats in passes.values():  # set aside for the passes above, not for the summary
        for key in ("firsts", "reference", "params"):
            del stats[key]

    return {
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
        "jax": jax.__version__,
        "params": n_params,
        "programs_compiled": sum(p["programs"] for p in passes.values()),
        "setup_s": round(sum(p["build_s"] + p["warmup_s"] for p in passes.values()), 1),
        "total_s": round(time.monotonic() - t_start, 1),
        "compile_cache": {
            "dir": ensure_compile_cache(), "warm": compiles.cache_hits > 0,
            "hits": compiles.cache_hits, "compile_requests": compiles.requests,
            "compile_s": round(compiles.seconds, 1)},
        "passes": passes,
        "kernels": kernels,
        "four_chip": four_chip,
        "planner": planner,
    }


def verdict(summary: dict) -> dict:
    """The last line of standard output: these keys and no others."""
    return {"ok": summary["ok"], "device": summary["device"]}


def main() -> None:
    native.planner_in_use()  # builds the C++ planner (a g++ child) BEFORE jax touches a device
    dev = jax.devices()[0]
    print(f"jax {jax.__version__}  platform {dev.platform}  device_kind {dev.device_kind}  "
          f"devices {len(jax.devices())}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX platform is {dev.platform!r}, not 'tpu' — this script "
                 "proves the serving path on the chip and has no CPU mode "
                 "(tests/test_chip_smoke.py rehearses its body on the CPU)")
    if "GOFR_PALLAS_INTERPRET" in os.environ:
        sys.exit("chip_smoke: GOFR_PALLAS_INTERPRET is set — the kernels must be compiled, "
                 "not interpreted")
    summary = run_smoke(LlamaConfig.one_b())
    print(json.dumps(summary), flush=True)
    print(json.dumps(verdict(summary)), flush=True)


if __name__ == "__main__":
    main()
