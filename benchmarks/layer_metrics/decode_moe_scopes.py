"""Layer: ops / kernels. The expert layer's parts of a decode step, by the
program's second list of scope names (``harness/moe_work.MOE_SCOPES``, nested
inside ``mlp``, so ``decode_dense_ms`` holds all three): leaf operations inside
the slice's whole ``_decode_chunk`` runs, summed by innermost expert scope, ÷
runs ÷ ``decode_chunk``. ``router`` = scores, top-k, the dispatch and the
combine; ``experts`` = the products over the held experts; ``shared`` = the
shared experts.

``decode_moe_experts_hbm_share``: the bytes the experts' products of one
layer-step must move (each expert HIT read once — from the program's counters —
and each assignment's rows, ``moe_work.experts_layer_bytes``) × layers, over the
chip's HBM peak, as a share of ``decode_moe_experts_ms``. Bound: memory.

A program without these scopes (every dense family) reads nothing."""

from benchmarks.harness import moe_work, names, peaks

NAMES = ("decode_moe_router_ms", "decode_moe_experts_ms", "decode_moe_shared_ms",
         "decode_moe_experts_hbm_share")
PROGRAM = r"decode_chunk"


def read(ctx: dict) -> dict:
    tuples = names.load(ctx)
    if tuples is None:
        return {}
    sums, runs = moe_work.moe_scope_sums(tuples["ops"], tuples["modules"], PROGRAM)
    if not runs or not any(sums.values()):
        return {}
    steps = runs * ctx["decode_chunk"]
    out = {f"decode_{scope}_ms": ns / steps / 1e6 for scope, ns in sums.items()}
    counted = moe_work.per_layer_step(ctx, "decode")
    if counted is not None and out["decode_moe_experts_ms"] > 0:
        c = ctx["config"]
        need = c["num_hidden_layers"] * moe_work.experts_layer_bytes(
            c, counted["experts_hit"], counted["assignments"])
        least_ms = need / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] * 1e3
        out["decode_moe_experts_hbm_share"] = 100.0 * least_ms / out["decode_moe_experts_ms"]
    return out
