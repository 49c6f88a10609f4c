"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: it boots the shipped example app
(``examples/serving-llm`` ``build_app``) at the cell's configuration and engine
shape, warms it, and serves over HTTP on localhost. A child (``loadgen.py``,
no JAX) sends the cell's traffic; what it saw, the server's ``/metrics`` over
the window and — with ``--trace 1`` — a device trace of a slice of the window
become the metrics. The LAST stdout line is the one JSON object the driver
reads; everything else worth reading is on earlier lines, each a JSON object
with a ``note`` key.

Exits non-zero, printing no result, unless JAX's platform is ``tpu`` with at
least the chips the cell asks for: there is no CPU mode.
``benchmarks/tests/test_bench_rehearsal.py`` drives the same body
(:func:`run_cell`) at a tiny width on the CPU."""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as near as Python can say: setup_s counts from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmarks.harness import serving, stats, trace as tracelib  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402
from benchmarks.harness.traffic import build_schedule, request_prompt  # noqa: E402

CACHE = os.path.join(REPO, ".cache", "bench")  # gitignored; inside the checkout
TRACE_SLICE_S = 5.0  # a decode chunk runs ~0.5 s: some eight whole ones
REFERENCE_SAMPLE = 8
# A served first token may sit this many bf16 units in the last place (at the
# logits' magnitude) under the float32 reference's best and still count as the
# same answer: the engine computes in bf16 through padded, batched programs,
# the reference in float32, and on random weights the top logits are that
# close. A token the model would not have chosen sits many times further down.
NEAR_TIE_ULPS = 2.0


def note(what: str, **fields) -> None:
    print(json.dumps({"note": what, **fields}), flush=True)


class BenchFailure(Exception):
    """The run cannot produce a result (as opposed to ``correct: false``)."""


def program_config(spec: dict):
    """The program's config object from the configuration file: the file's
    ``program`` group names the class and maps its fields to the source's
    keys, so a new configuration of a served family is data only."""
    import importlib

    import jax.numpy as jnp

    module, cls = spec["program"]["config_class"].split(":")
    fields = {field: spec[key] for field, key in spec["program"]["fields"].items()}
    fields["dtype"] = getattr(jnp, spec["torch_dtype"])
    return getattr(importlib.import_module(module), cls)(**fields)


async def _boot(cell: dict, cfg):
    """Build the example app at the cell's shape, start it, wait until it is
    warm and listening. → (app, server task, engine)."""
    from gofr_tpu.config import DictConfig

    conf = {
        "APP_NAME": "bench", "LOG_LEVEL": "WARN",
        "HTTP_PORT": str(serving.free_port()), "METRICS_PORT": str(serving.free_port()),
        "ENGINE_WARMUP": "true", "TPU_DEVICES": str(cell["chips"]),
    }
    conf.update(cell.get("app_config", {}))
    app = serving.load_example_app()(
        DictConfig(conf), model_config=cfg, seed=int(cell["config_spec"]["weights_seed"]),
        **cell["engine"])
    engine = app.container.engine("lm")
    ready = asyncio.Event()
    server = asyncio.ensure_future(app.arun(ready=ready))
    waiter = asyncio.ensure_future(ready.wait())
    await asyncio.wait({server, waiter}, return_when=asyncio.FIRST_COMPLETED)
    if server.done():
        waiter.cancel()
        server.result()  # a boot failure (warmup raised) surfaces here
        raise BenchFailure("the app exited before it was ready")
    return app, server, engine


async def _trace_slice(loop, at: float, trace_dir: str) -> None:
    """Device trace of TRACE_SLICE_S seconds starting at monotonic ``at``;
    start and stop run off the event loop, host Python tracing off."""
    import jax

    await asyncio.sleep(max(0.0, at - time.monotonic()))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    await loop.run_in_executor(
        None, lambda: jax.profiler.start_trace(trace_dir, profiler_options=options))
    await asyncio.sleep(TRACE_SLICE_S)
    await loop.run_in_executor(None, jax.profiler.stop_trace)


async def _generate(loop, schedule: dict, app, workdir: str, trace_dir: str | None) -> dict:
    """Spawn the generator child, wait for it, return what it wrote."""
    sched_path, out_path = os.path.join(workdir, "schedule.json"), os.path.join(workdir, "generator.json")
    with open(sched_path, "w", encoding="utf-8") as f:
        json.dump(schedule, f)
    child = await asyncio.create_subprocess_exec(
        sys.executable, os.path.join(HERE, "loadgen.py"), "--schedule", sched_path,
        "--base", f"http://127.0.0.1:{app.http_port}",
        "--metrics", f"http://127.0.0.1:{app.metrics_port}/metrics", "--out", out_path,
        stdout=asyncio.subprocess.PIPE)
    tracer = None
    try:
        start = json.loads(await child.stdout.readline())
        if trace_dir is not None:
            mid = start["t0"] + schedule["ramp_s"] + schedule["seconds"] / 2
            tracer = asyncio.ensure_future(_trace_slice(loop, mid - TRACE_SLICE_S / 2, trace_dir))
        rc = await child.wait()
        if tracer is not None:
            await tracer
    finally:
        if child.returncode is None:
            child.kill()
            await child.wait()
        if tracer is not None and not tracer.done():
            tracer.cancel()
    if rc != 0:
        raise BenchFailure(f"the load generator exited with code {rc}")
    with open(out_path, encoding="utf-8") as f:
        return json.load(f)


def _end_to_end(gen: dict, window: list[dict], setup_s: float) -> dict:
    """Every end-to-end number this run can state; the cell's list in
    BENCHMARK.json picks the ones it reports."""
    out = {"setup_s": setup_s, **stats.client_latencies(window)}
    out["out_tok_per_s"] = stats.tokens_per_second(
        gen["records"], gen["window_start"], gen["window_end"])
    return out


def _check_reference(manifest: Manifest, cell: dict, engine, schedule: dict,
                     window: list[dict], seed: int) -> dict:
    """First tokens of a seeded sample of the window's answered requests
    against the configuration's plain reference on the engine's own weights."""
    import jax.numpy as jnp
    import numpy as np

    spec = cell["config_spec"]
    answered = [r for r in window if r["ok"]]
    if not answered:
        return {"exact": 0, "near_tie": 0, "miss": 0, "sampled": 0}
    rng = np.random.Generator(np.random.PCG64([seed, 2]))
    picks = [answered[i] for i in rng.choice(len(answered), size=min(REFERENCE_SAMPLE, len(answered)),
                                             replace=False)]
    reference = manifest.reference(spec)
    pad_to = max(cell["engine"]["prefill_buckets"])
    pool = schedule["requests"]
    logits = np.stack([
        np.asarray(reference.last_logits(
            spec, engine.params,
            request_prompt(schedule, dict(pool[r["seq"] % len(pool)], seq=r["seq"])), pad_to))
        for r in picks])
    if not np.all(np.isfinite(logits)):
        raise BenchFailure("reference logits are not finite")
    tol = serving.near_tie_tol(float(jnp.finfo(getattr(jnp, spec["torch_dtype"])).eps), logits, NEAR_TIE_ULPS)
    verdict = serving.check_first_tokens([r["first_token"] for r in picks], logits, tol)
    verdict["sampled"] = len(picks)
    return verdict


async def run_cell(manifest: Manifest, workload: str, *, seed: int, seconds: float,
                   trace: bool, workdir: str) -> dict:
    """The whole run of one cell → the result object. Takes the manifest, so
    a test can hand it a tiny tree; only :func:`main` insists on a TPU."""
    import jax

    from gofr_tpu import native
    from gofr_tpu.testutil import assert_paged_pool_consistent
    from gofr_tpu.tpu.device import ensure_compile_cache

    loop = asyncio.get_running_loop()
    cell = manifest.cell(workload)
    spec = cell["config_spec"]
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)

    # -- set-up: all of it inside setup_s -------------------------------------
    cache_dir = ensure_compile_cache()
    # every program, however small, goes to the persistent cache: most compile
    # requests fall under JAX's default 1 s threshold and would be rebuilt in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = serving.CompileCounter()
    cfg = program_config(spec)
    devices = jax.devices()[: cell["chips"]]
    app, server, engine = await _boot(cell, cfg)
    try:
        report = engine.autotune_report() or {}
        decode_chunk = int(engine.decode_chunk)
        note("setup", build_warm_s=time.monotonic() - T_START, compile_cache=cache_dir,
             compile_requests=compiles.requests, compile_s=compiles.seconds,
             cache_hits=compiles.cache_hits, programs=len(engine._compiled),
             planner=native.planner_in_use(),
             autotune={op: rec.get("backend") for op, rec in (report.get("decisions") or {}).items()},
             autotune_errors=report.get("errors"),
             bytes_in_use=[(d.memory_stats() or {}).get("bytes_in_use") for d in devices])
        load = dict(cell.get("load", {}))
        if os.environ.get("BENCH_LOAD_OVERRIDE"):  # benchmarks/sweep.py's handle, by hand only
            load.update(json.loads(os.environ["BENCH_LOAD_OVERRIDE"]))
            note("load_override", load=load)
        schedule = build_schedule(cell["traffic_spec"], load, seed=seed,
                                  seconds=seconds, vocab=spec["vocab_size"])
        compiles_before = compiles.requests
        trace_dir = os.path.join(workdir, "trace") if trace else None

        # -- ramp + measured window + drain (the child's clock) ---------------
        gen = await _generate(loop, schedule, app, workdir, trace_dir)
        compiles_in_window = compiles.requests - compiles_before
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
        w0, w1 = gen["window_start"], gen["window_end"]
        sent_key = "due" if schedule["loop"] == "open" else "sent"
        window = [r for r in gen["records"] if w0 <= r[sent_key] < w1]
        failed = [r for r in window if not r["ok"]]
        late = [r["sent"] - r["due"] for r in window if "sent" in r]
        note("window", loop=schedule["loop"], attempted=len(window), failed=len(failed),
             sent_in_ramp=sum(1 for r in gen["records"] if r[sent_key] < w0),
             first_errors=[r.get("error") for r in failed[:3]],
             generator_lateness=stats.histogram_ms(late), compiles_in_window=compiles_in_window,
             drain_s=max((r["done"] for r in gen["records"]), default=w1) - w1)

        # -- checks, outside the window ---------------------------------------
        restarts = serving.metric(gen["metrics_after"], "app_tpu_engine_restarts")
        first_tokens = _check_reference(manifest, cell, engine, schedule, window, seed)
        deadline = time.monotonic() + 10
        while (engine._decode_lanes or engine._prefill_lanes) and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        pool_ok, pool_error = True, None
        if cell["engine"].get("kv_layout") == "paged":
            try:
                assert_paged_pool_consistent(engine, slots_empty=True)
            except AssertionError as e:
                pool_ok, pool_error = False, str(e)[:300]
        checks = {
            "all_answered_whole": not failed and bool(window),
            "first_tokens": first_tokens["miss"] == 0 and first_tokens.get("sampled", 0) > 0,
            "no_compile_in_window": compiles_in_window == 0,
            "no_engine_restart": restarts == 0,
            "page_pool_consistent": pool_ok,
        }
        note("checks", **checks, first_token_detail=first_tokens, pool_error=pool_error)
    finally:
        app.stop()
        await server

    # -- metrics ---------------------------------------------------------------
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    result = {"correct": all(checks.values()), "attempted": len(window), "failed": len(failed)}
    if not trace:
        values = _end_to_end(gen, window, w0 - T_START)
        wanted = manifest.metrics("end_to_end", workload)
    else:
        raw = tracelib.read_xplane(trace_dir)
        note("trace_layout", layout=raw["layout"])
        reduced = tracelib.reduce_trace(raw["planes"])
        if reduced is None:
            raise BenchFailure("the traced slice holds no device operation")
        note("trace_programs", programs=tracelib.top_by_total(
            (tracelib.program(n), d) for n, _, d in reduced["modules"]))
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = tracelib.breakdown(reduced)
        ctx = {"cell": cell, "config": spec, "schedule": schedule, "window": window,
               "records": gen["records"], "metrics_before": gen["metrics_before"],
               "metrics_after": gen["metrics_after"], "trace": reduced,
               "device_kind": dev.device_kind, "engine": cell["engine"],
               "decode_chunk": decode_chunk}
        values = {}
        for reader in manifest.layer_readers():
            values.update(reader.read(ctx) or {})
        wanted = manifest.metrics("per_layer", workload)
        # the tuples the reduction saw, beside the run's other files: a test
        # fixture is a trimmed copy of one of these
        with open(os.path.join(workdir, "trace_tuples.json"), "w", encoding="utf-8") as f:
            json.dump({"modules": reduced["modules"], "ops": reduced["ops"]}, f)
    result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                         for m in wanted if values.get(m["name"]) is not None}
    result["device"] = device
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    manifest = Manifest()
    cell = manifest.cell(args.workload)  # an unknown cell fails here, before JAX starts
    # one autotune decision per cell and checkout: a near-tie between backends
    # would otherwise pin a different one in each process (PERF.md §6, PR 21: 2.74 against 2.85 ms)
    os.environ["GOFR_AUTOTUNE_CACHE"] = os.path.join(CACHE, "autotune", args.workload + ".json")
    os.makedirs(os.path.dirname(os.environ["GOFR_AUTOTUNE_CACHE"]), exist_ok=True)

    from gofr_tpu import native

    native.planner_in_use()  # builds the C++ planner (a g++ child) BEFORE jax touches a device
    import jax

    devs = jax.devices()
    note("device", jax=jax.__version__, platform=devs[0].platform, kind=devs[0].device_kind,
         count=len(devs))
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        sys.exit(f"benchmarks/run.py: platform {devs[0].platform!r} with {len(devs)} device(s); "
                 f"cell {args.workload!r} needs {cell['chips']} TPU chip(s). There is no CPU mode "
                 "(benchmarks/tests/test_bench_rehearsal.py rehearses the body on the CPU).")
    result = asyncio.run(run_cell(
        manifest, args.workload, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        workdir=os.path.join(CACHE, "runs", args.workload)))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    # nothing may follow the result line on stdout (PERF.md §6, PR 21): whatever an
    # exit hook or a logger still writes goes nowhere
    os.dup2(os.open(os.devnull, os.O_WRONLY), 1)


if __name__ == "__main__":
    main()
