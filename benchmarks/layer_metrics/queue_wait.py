"""Layer: scheduler. Mean wait between enqueue and first pick-up by the
device loop over the window: Δsum/Δcount of ``app_tpu_queue_wait_seconds``."""

from benchmarks.harness import serving

NAMES = ("queue_wait_ms",)


def read(ctx: dict) -> dict:
    wait = serving.histogram_mean_delta(
        ctx["metrics_before"], ctx["metrics_after"], "app_tpu_queue_wait_seconds")
    return {} if wait is None else {"queue_wait_ms": wait * 1e3}
