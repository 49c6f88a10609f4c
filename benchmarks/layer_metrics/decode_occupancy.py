"""Layer: scheduler. Mean share of the slots that a decode step carried over
the window: Δsum/Δcount of ``app_tpu_batch_occupancy{kind="decode"}``."""

from benchmarks.harness import serving

NAMES = ("decode_occupancy",)


def read(ctx: dict) -> dict:
    occ = serving.histogram_mean_delta(
        ctx["metrics_before"], ctx["metrics_after"], "app_tpu_batch_occupancy", kind="decode")
    return {} if occ is None else {"decode_occupancy": occ * 100.0}
