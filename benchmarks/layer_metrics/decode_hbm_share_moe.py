"""Layer: ops / kernels. The whole decode step of an expert configuration
against the chip's HBM peak: every weight a step touches once — attention, the
shared experts, the router, the experts HIT (the program's counters), the
head's slice — plus the live KV read and the rows written
(``harness/moe_work.decode_step_bytes``), over 819 GB/s, as a share of the
decode step's measured device time. Bound: memory. ``decode_hbm_share`` counts
a dense block from ``intermediate_size`` and does not list such a cell."""

from benchmarks.harness import moe_work, peaks, trace

NAMES = ("decode_hbm_share_moe",)
PROGRAM = r"decode_chunk"


def read(ctx: dict) -> dict:
    chunk = trace.median_module_s(ctx["trace"], PROGRAM)
    counted = moe_work.per_layer_step(ctx, "decode")
    shape = moe_work.lanes_and_context(ctx)
    if chunk is None or counted is None or shape is None:
        return {}
    lanes, context = shape
    need = moe_work.decode_step_bytes(ctx["config"], live_tokens=lanes * context, lanes=lanes,
                                      experts_hit=counted["experts_hit"],
                                      assignments=counted["assignments"])
    least = need / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return {"decode_hbm_share_moe": 100.0 * least / (chunk / ctx["decode_chunk"])}
