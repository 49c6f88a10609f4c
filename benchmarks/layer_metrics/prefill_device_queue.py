"""Layer: dispatch. How long a prefill program sits in the device's queue:
median, over the traced slice's whole prefill program runs, of device start −
end of the paired ``loop.dispatch_prefill`` annotation (``harness/names``
pairs them by the dispatch sequence number, anchored at the readbacks). With
pipeline depth 2 a prefill is dispatched just as the next decode chunk starts,
and waits that chunk out."""

import statistics

from benchmarks.harness import names

NAMES = ("prefill_device_queue_ms",)


def read(ctx: dict) -> dict:
    tuples = names.load(ctx)
    if tuples is None:
        return {}
    waits = names.prefill_queue_ns(tuples["modules"], tuples["host"])
    return {"prefill_device_queue_ms": statistics.median(waits) / 1e6} if waits else {}
