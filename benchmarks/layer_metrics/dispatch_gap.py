"""Layer: dispatch. Median idle time on the device between the end of one
serving program and the start of the next, from the traced slice's
whole-program events. Programs that overlap or abut leave no gap and are not
counted."""

import statistics

from benchmarks.harness import trace

NAMES = ("dispatch_gap_ms",)


def read(ctx: dict) -> dict:
    gaps = [ns for _, ns in trace.idle_gaps(ctx["trace"]["modules"])]
    return {"dispatch_gap_ms": statistics.median(gaps) / 1e6} if gaps else {}
