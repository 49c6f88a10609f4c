"""Layer: transport. Mean client time to first token, counted from the SEND,
minus the engine's own mean time to first token over the same window
(Δsum/Δcount of ``app_tpu_ttft_seconds``): what HTTP parsing, the handler
pool and the SSE path add on top of the engine."""

from benchmarks.harness import serving, stats

NAMES = ("transport_ttft_ms",)


def read(ctx: dict) -> dict:
    if not ctx["schedule"]["stream"]:
        return {}
    client = [r["first"] - r["sent"] for r in ctx["window"] if r["ok"]]
    engine = serving.histogram_mean_delta(
        ctx["metrics_before"], ctx["metrics_after"], "app_tpu_ttft_seconds")
    if not client or engine is None:
        return {}
    return {"transport_ttft_ms": (stats.mean(client) - engine) * 1e3}
