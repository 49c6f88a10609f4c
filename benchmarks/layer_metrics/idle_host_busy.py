"""Layer: device. Device idle time of the traced slice (gaps between ``XLA
Ops`` busy intervals) that passed while the device loop was in a WORKING host
phase (control, admit, dispatch_*, fold — not waiting in ``readback`` or
``wait_work``), in ms per second of slice: what the host's own work costs the
device. Prints one ``{"note": "idle_by_phase", ...}`` line with every phase
and ``unattributed`` (ms over the whole slice)."""

import json

from benchmarks.harness import names

NAMES = ("idle_host_busy_ms",)


def read(ctx: dict) -> dict:
    tuples = names.load(ctx)
    if tuples is None or not tuples["host"]:
        return {}
    idle, slice_ns = names.idle_by_phase(tuples["ops"], tuples["host"])
    if slice_ns <= 0:
        return {}
    print(json.dumps({"note": "idle_by_phase", "slice_s": slice_ns / 1e9,
                      "idle_ms": {phase: ns / 1e6 for phase, ns in idle.items()}}), flush=True)
    return {"idle_host_busy_ms": sum(idle[p] for p in names.WORKING) / 1e6 / (slice_ns / 1e9)}
