"""Layer: ops / kernels. Where a decode step's device time goes, by the
program's own scope names (``harness/names``): leaf operations inside the
slice's whole ``_decode_chunk`` runs, summed by innermost scope, ÷ runs ÷
``decode_chunk``. ``dense`` = embed + qkv_rope + o_proj + mlp + lm_head;
``unscoped`` = no program scope (the layer scan's own stacking of the pool,
compiler-inserted copies). The six sum to a step's leaf-operation time. A
fusion counts under the scope of its root.

``decode_attention_hbm_share``: the live KV bytes one step must read
(``peaks.kv_bytes_per_token`` × lanes × mean live context, as
``decode_hbm_share`` reckons them) over the chip's HBM peak, as a share of
``decode_kv_gather_ms`` + ``decode_attention_ms``. Bound: memory."""

from benchmarks.harness import names, peaks, serving

PARTS = {"kv_append": ("kv_append",), "kv_gather": ("kv_gather",), "attention": ("attention",),
         "dense": names.DENSE, "sample": ("sample",), "unscoped": ("unscoped",)}
NAMES = tuple(f"decode_{part}_ms" for part in PARTS) + ("decode_attention_hbm_share",)
PROGRAM = r"decode_chunk"


def read(ctx: dict) -> dict:
    tuples = names.load(ctx)
    if tuples is None:
        return {}
    sums, runs = names.scope_sums(tuples["ops"], tuples["modules"], PROGRAM)
    if not runs or not any(sums[s] for s in names.SCOPES):
        return {}  # no whole decode run in the slice, or a program without scopes
    steps = runs * ctx["decode_chunk"]
    out = {f"decode_{part}_ms": sum(sums[s] for s in scopes) / steps / 1e6
           for part, scopes in PARTS.items()}
    occ = serving.histogram_mean_delta(
        ctx["metrics_before"], ctx["metrics_after"], "app_tpu_batch_occupancy", kind="decode")
    done = [r for r in ctx["window"] if r["ok"]]
    read_ms = out["decode_kv_gather_ms"] + out["decode_attention_ms"]
    if occ is not None and done and read_ms > 0:
        lanes = occ * ctx["engine"]["slots"]
        context = (sum(r["n_tokens"] * (r["prompt_len"] + r["n_tokens"] / 2) for r in done)
                   / sum(r["n_tokens"] for r in done))
        need = peaks.kv_bytes_per_token(ctx["config"]) * lanes * context
        least_ms = need / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"] * 1e3
        out["decode_attention_hbm_share"] = 100.0 * least_ms / read_ms
    return out
