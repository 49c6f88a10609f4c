"""Metric arithmetic: percentiles, per-request token gaps, rates.
Pure Python on plain numbers, so the tests can check it against hand-computed
cases."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between the
    two nearest ranks (numpy's default). Raises on an empty sample: a metric
    with nothing to read is left out, never reported as 0."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = (len(xs) - 1) * q / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def mean(values) -> float:
    xs = [float(v) for v in values]
    if not xs:
        raise ValueError("mean of an empty sample")
    return sum(xs) / len(xs)


def per_request_gap_s(first_s: float, last_s: float, n_tokens: int) -> float | None:
    """Mean gap between a request's tokens: (last − first) / (tokens − 1).
    Per request and not per gap: the engine emits ``decode_chunk`` tokens at a
    time, so single gaps are bimodal by design. None for a one-token answer."""
    if n_tokens < 2:
        return None
    return (last_s - first_s) / (n_tokens - 1)


def request_gaps_s(records) -> list[float]:
    """The per-request token gap of every answered record that has one."""
    return [g for r in records if r["ok"]
            if (g := per_request_gap_s(r["first"], r["last"], r["n_tokens"])) is not None]


def client_latencies(window) -> dict:
    """What an open loop's clients saw, over the answered requests of the
    window: time to first token from the DUE time, and the per-request token
    gap, in milliseconds. The tail is the 90th percentile: a window holds some
    120 requests, and the 95th would have six samples beyond it where ten are
    wanted. A statistic with nothing to read is left out."""
    ok = [r for r in window if r["ok"]]
    out = {}
    ttft = [(r["first"] - r["due"]) * 1e3 for r in ok]
    if ttft:
        out["ttft_p50_ms"], out["ttft_p90_ms"] = percentile(ttft, 50), percentile(ttft, 90)
    gaps = [g * 1e3 for g in request_gaps_s(ok)]
    if gaps and any(g > 0 for g in gaps):  # a unary answer's tokens arrive at once: no gap to state
        out["tpot_p90_ms"] = percentile(gaps, 90)
    return out


def tokens_per_second(records, t0: float, t1: float) -> float:
    """Output tokens of the requests ANSWERED in [t0, t1), per second of that
    window — whenever they were sent."""
    if t1 <= t0:
        raise ValueError("empty window")
    done = sum(r["n_tokens"] for r in records
               if r.get("ok") and r.get("done") is not None and t0 <= r["done"] < t1)
    return done / (t1 - t0)


def histogram_ms(values_s, edges_ms=(0.5, 1, 2, 5, 10, 20, 50, 100, 500)) -> dict:
    """Counts of ``values_s`` (seconds) under each edge (milliseconds), and
    beyond the last: how late the generator ran, readable at a glance."""
    out = {f"<={e}ms": 0 for e in edges_ms}
    out[f">{edges_ms[-1]}ms"] = 0
    for v in values_s:
        ms = v * 1e3
        for e in edges_ms:
            if ms <= e:
                out[f"<={e}ms"] += 1
                break
        else:
            out[f">{edges_ms[-1]}ms"] += 1
    return out
