"""Layers: scheduler (``loop_admit_ms``), dispatch (``loop_dispatch_ms``,
``loop_fold_ms``). Host self time of the device loop's phases per decode
chunk dispatched, over the window: Δ``app_tpu_loop_phase_seconds_total{phase}``
÷ Δ``app_tpu_loop_phase_total{phase="dispatch_decode"}`` (``dispatch`` =
``dispatch_prefill`` + ``dispatch_decode``). A program without these counters
reads nothing."""

from benchmarks.harness import serving

PHASES = {"loop_admit_ms": ("admit",), "loop_dispatch_ms": ("dispatch_prefill", "dispatch_decode"),
          "loop_fold_ms": ("fold",)}
NAMES = tuple(PHASES)


def _delta(ctx: dict, name: str, phase: str) -> float:
    return (serving.metric(ctx["metrics_after"], name, phase=phase)
            - serving.metric(ctx["metrics_before"], name, phase=phase))


def read(ctx: dict) -> dict:
    chunks = _delta(ctx, "app_tpu_loop_phase_total", "dispatch_decode")
    if chunks <= 0:
        return {}
    return {metric: sum(_delta(ctx, "app_tpu_loop_phase_seconds_total", p) for p in phases)
            / chunks * 1e3 for metric, phases in PHASES.items()}
