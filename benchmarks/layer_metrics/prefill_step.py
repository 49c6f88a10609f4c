"""Layer: model programs. Median device duration of the prefill programs'
whole-program events (batched ``_prefill_sample`` and ``_chunk_prefill``) in
the traced slice."""

from benchmarks.harness import trace

NAMES = ("prefill_step_ms",)
PROGRAM = r"prefill"


def read(ctx: dict) -> dict:
    batch = trace.median_module_s(ctx["trace"], PROGRAM)
    return {} if batch is None else {"prefill_step_ms": batch * 1e3}
