"""Layer: ops / kernels. The experts' products inside prefill, against the
chip's bf16 peak: the held assignments one prefill layer-step computes (the
program's counters over the window, ``phase="prefill"``) × 6 × hidden ×
expert width FLOP, over the time one layer-step spends under ``moe_experts``
in the slice's whole prefill programs (whole-prompt and chunked). Bound:
compute. Only the rows routed to held experts count: a product that computes
an expert on tokens not routed to it reads lower, as it should."""

from benchmarks.harness import moe_work, names, peaks

NAMES = ("prefill_moe_experts_mfu",)
PROGRAM = r"prefill"


def read(ctx: dict) -> dict:
    tuples = names.load(ctx)
    counted = moe_work.per_layer_step(ctx, "prefill")
    if tuples is None or counted is None:
        return {}
    sums, runs = moe_work.moe_scope_sums(tuples["ops"], tuples["modules"], PROGRAM)
    if not runs or not sums["moe_experts"]:
        return {}
    c = ctx["config"]
    seconds = sums["moe_experts"] / 1e9 / (runs * c["num_hidden_layers"])
    flops = counted["assignments"] * moe_work.assignment_flops(c)
    return {"prefill_moe_experts_mfu": 100.0 * flops / peaks.peaks(ctx["device_kind"])["flops_bf16"] / seconds}
