"""Layer: transport. Mean client gap between a request's tokens minus the
engine's own mean time per output token over the window (Δsum/Δcount of
``app_tpu_tpot_seconds``): what streaming adds to the decode cadence."""

from benchmarks.harness import serving, stats

NAMES = ("stream_lag_ms",)


def read(ctx: dict) -> dict:
    if not ctx["schedule"]["stream"]:
        return {}
    gaps = stats.request_gaps_s(ctx["window"])
    engine = serving.histogram_mean_delta(
        ctx["metrics_before"], ctx["metrics_after"], "app_tpu_tpot_seconds")
    if not gaps or engine is None:
        return {}
    return {"stream_lag_ms": (stats.mean(gaps) - engine) * 1e3}
