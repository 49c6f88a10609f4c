"""Layer: ops / kernels. How many tokens one held expert sees in one layer-step,
over the window and over every program that ran (prefill and decode): the
program's counters, assignments to held experts ÷ experts held ÷ layer-steps.
What the stated deployment gives at these lanes is lanes × k ÷ router width."""

from benchmarks.harness import moe_work

NAMES = ("moe_tokens_per_expert",)


def read(ctx: dict) -> dict:
    steps = moe_work.counter_delta(ctx, moe_work.LAYER_STEPS)
    if steps <= 0:
        return {}
    held = ctx["config"]["num_experts"]
    return {"moe_tokens_per_expert": moe_work.counter_delta(ctx, moe_work.ASSIGNMENTS) / held / steps}
