"""Layer: load generator. How late the generator sent, 95th percentile of
send time − due time on the child's clock. A starved generator must not be
read as a slow server."""

from benchmarks.harness import stats

NAMES = ("gen_late_p95_ms",)


def read(ctx: dict) -> dict:
    if ctx["schedule"]["loop"] != "open":
        return {}
    late = [(r["sent"] - r["due"]) * 1e3 for r in ctx["window"] if "sent" in r]
    return {"gen_late_p95_ms": stats.percentile(late, 95)} if late else {}
