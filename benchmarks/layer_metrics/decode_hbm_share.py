"""Layer: ops / kernels. The bytes one decode step MUST move (weights once,
the live KV of every lane once, one new KV row per lane — from shapes, by
``harness/peaks.decode_step_bytes``) over the chip's HBM peak, as a share of
the decode step's measured device time. Bound: memory. Lanes come from the
decode occupancy over the window; a lane's live context is the mean, over the
window's answered requests weighted by their output length, of prompt +
half the output."""

from benchmarks.harness import peaks, serving, trace

NAMES = ("decode_hbm_share",)
PROGRAM = r"decode_chunk"


def read(ctx: dict) -> dict:
    chunk = trace.median_module_s(ctx["trace"], PROGRAM)
    occ = serving.histogram_mean_delta(
        ctx["metrics_before"], ctx["metrics_after"], "app_tpu_batch_occupancy", kind="decode")
    done = [r for r in ctx["window"] if r["ok"]]
    if chunk is None or occ is None or not done:
        return {}
    step = chunk / ctx["decode_chunk"]
    lanes = occ * ctx["engine"]["slots"]
    context = (sum(r["n_tokens"] * (r["prompt_len"] + r["n_tokens"] / 2) for r in done)
               / sum(r["n_tokens"] for r in done))
    need = peaks.decode_step_bytes(ctx["config"], live_tokens=lanes * context, lanes=lanes)
    least = need / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return {"decode_hbm_share": 100.0 * least / step}
