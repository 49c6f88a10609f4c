"""The one general traffic generator. A mix is a data file under
``benchmarks/traffic/``; this turns it, the cell's load and ``--seed`` into a
schedule. Imports no JAX: the load generator child uses it too.

The shape of the traffic — lengths, arrival gaps and their order — is drawn
from the mix's ``shape_seed`` and is the same under every ``--seed``; the seed
draws the token ids. Measured (PERF.md §2): two runs of one schedule agree to
a fraction of a percent, while the same lengths and gaps in another order moved
tokens per second by 4.5% and the median time to first token by 20% — with a
hundred requests in a window, the order IS the work."""

from __future__ import annotations

import math

import numpy as np

_PREFIX_STREAM = 1 << 40  # token streams of shared prefixes, apart from any request's


def _rng(*words: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64([int(w) for w in words]))


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole lengths from a distribution given as data:
    ``{"dist": "fixed", "value": v}``, ``{"dist": "uniform", "min", "max"}``,
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` (clipped), or
    ``{"dist": "mixture", "parts": [{"weight": w, ...a distribution}, ...]}``."""
    dist = spec["dist"]
    if dist == "fixed":
        out = np.full(n, int(spec["value"]))
    elif dist == "uniform":
        out = rng.integers(int(spec["min"]), int(spec["max"]) + 1, size=n)
    elif dist == "lognormal":
        out = np.rint(rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n))
        out = np.clip(out, spec["min"], spec["max"])
    elif dist == "mixture":
        weights = np.asarray([p["weight"] for p in spec["parts"]], float)
        which = rng.choice(len(weights), size=n, p=weights / weights.sum())
        parts = [draw_lengths(p, n, rng) for p in spec["parts"]]
        out = np.choose(which, parts)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return out.astype(np.int64)


def _warp(unit_times: np.ndarray, rate: float, burst: dict | None) -> np.ndarray:
    """Arrival times of a (possibly on/off) Poisson process from unit-rate
    arrival times: the inverse of the cumulative rate. Without ``burst`` that
    is a division by ``rate``."""
    if not burst:
        return unit_times / rate
    on_s, off_s = float(burst["on_s"]), float(burst["off_s"])
    r_on, r_off = rate * burst["on_factor"], rate * burst["off_factor"]
    per_cycle = r_on * on_s + r_off * off_s
    cycles, rest = np.divmod(unit_times, per_cycle)
    in_on = rest < r_on * on_s
    within = np.where(in_on, rest / r_on, on_s + (rest - r_on * on_s) / r_off)
    return cycles * (on_s + off_s) + within


def prompt_tokens(seed: int, stream: int, n: int, vocab: int) -> list[int]:
    """Token ids of stream ``stream`` under ``seed``: uniform over the
    vocabulary above the three lowest ids (kept clear of special tokens)."""
    return [int(t) for t in _rng(seed, stream).integers(3, vocab, size=n)]


def request_prompt(schedule: dict, req: dict) -> list[int]:
    """The prompt of one scheduled request (its shared prefix first)."""
    seed, vocab = schedule["seed"], schedule["vocab"]
    shared = req.get("prefix_len", 0)
    head = prompt_tokens(seed, _PREFIX_STREAM + req["prefix_group"], shared, vocab) if shared else []
    return head + prompt_tokens(seed, req["seq"], req["prompt_len"] - shared, vocab)


def build_schedule(traffic: dict, load: dict, *, seed: int, seconds: float, vocab: int) -> dict:
    """The schedule of one run — a pure function of its arguments; ``seed``
    enters only through the token ids ``request_prompt`` draws.

    Open loop: ``requests`` carry a ``due`` offset (seconds from the ramp's
    start) and cover ramp + window. Closed loop: ``requests`` is the pool the
    callers draw from in order, ``clients`` at a time."""
    p = {**traffic, **load}
    ramp_s = float(p["ramp_s"])
    shape = _rng(p["shape_seed"])
    if p["loop"] == "open":
        rate, span, unit = float(p["rate_rps"]), ramp_s + seconds, []
        total = 0.0
        while True:  # unit-rate gaps until the warped time passes ramp + window
            total += float(shape.exponential(1.0))
            if _warp(np.asarray([total]), rate, p.get("burst"))[0] >= span:
                break
            unit.append(total)
        due = _warp(np.asarray(unit), rate, p.get("burst"))
        n = len(due)
    elif p["loop"] == "closed":
        n, due = int(p["request_pool"]), None
    else:
        raise ValueError(f"unknown loop {p['loop']!r}")
    prompt_len = draw_lengths(p["prompt_len"], n, shape)
    new_tokens = draw_lengths(p["output_len"], n, shape)
    requests = [{"seq": i, "prompt_len": int(prompt_len[i]), "new_tokens": int(new_tokens[i])}
                for i in range(n)]
    if due is not None:
        for r, t in zip(requests, due):
            r["due"] = float(t)
    shared = p.get("shared_prefix")
    if shared:
        groups = int(shared["groups"])
        weights = 1.0 / np.arange(1, groups + 1) ** float(shared.get("zipf_s", 0.0))
        group_len = draw_lengths(shared["len"], groups, shape)
        picks = shape.choice(groups, size=n, p=weights / weights.sum())
        for r, g in zip(requests, picks):
            r["prefix_group"] = int(g)
            r["prefix_len"] = int(min(group_len[g], r["prompt_len"] - 1))
    return {
        "loop": p["loop"], "endpoint": p["endpoint"], "stream": bool(p["stream"]),
        "ramp_s": ramp_s, "seconds": float(seconds), "clients": int(p.get("clients", 0)),
        "client_timeout_s": float(p["client_timeout_s"]), "drain_s": float(p["drain_s"]),
        "seed": int(seed), "vocab": int(vocab), "requests": requests,
    }
