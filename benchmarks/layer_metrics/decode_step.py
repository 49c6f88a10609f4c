"""Layer: model programs. Median device duration of the decode program's
whole-program events in the traced slice (those its edges did not cut), per
decode step: one event runs ``decode_chunk`` steps."""

from benchmarks.harness import trace

NAMES = ("decode_step_ms",)
PROGRAM = r"decode_chunk"


def read(ctx: dict) -> dict:
    chunk = trace.median_module_s(ctx["trace"], PROGRAM)
    return {} if chunk is None else {"decode_step_ms": chunk / ctx["decode_chunk"] * 1e3}
