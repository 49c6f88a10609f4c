"""Find an open-loop cell's knee, once, by hand — not part of any check.

    python3 benchmarks/sweep.py --workload <cell> --rates 4,8,12 --seconds 20 [--seed 1]

Runs ``run.py`` once per rate (a process each: one process holds the chip at a
time), overriding the cell's ``rate_rps`` through ``BENCH_LOAD_OVERRIDE``, and
prints one table row per rate: answered against offered requests per second,
``ttft_p95_ms`` by third of the window, and how late the generator ran. The
knee is the highest rate at which answered stays within 3% of offered and the
last third's ``ttft_p95_ms`` is under twice the first third's; the cell file
gets four fifths of it, rounded down to 0.5 req/s, written in as a number."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks.harness import stats  # noqa: E402


def row(rate: float, gen: dict) -> dict:
    w0, w1 = gen["window_start"], gen["window_end"]
    window = [r for r in gen["records"] if w0 <= r["due"] < w1]
    thirds = []
    for i in range(3):
        lo, hi = w0 + (w1 - w0) * i / 3, w0 + (w1 - w0) * (i + 1) / 3
        ttft = [(r["first"] - r["due"]) * 1e3 for r in window if r["ok"] and lo <= r["due"] < hi]
        thirds.append(stats.percentile(ttft, 95) if ttft else None)
    gaps = [g * 1e3 for g in stats.request_gaps_s(window)]
    ttft_all = [(r["first"] - r["due"]) * 1e3 for r in window if r["ok"]]
    answered = sum(1 for r in gen["records"] if r["ok"] and w0 <= r["done"] < w1)
    late = [(r["sent"] - r["due"]) * 1e3 for r in window]
    return {"rate_rps": rate, "offered_rps": len(window) / (w1 - w0), "answered_rps": answered / (w1 - w0),
            "failed": sum(1 for r in window if not r["ok"]),
            "ttft_p95_ms_by_third": thirds,
            "ttft_p50_ms": stats.percentile(ttft_all, 50) if ttft_all else None,
            "tpot_p95_ms": stats.percentile(gaps, 95) if gaps else None,
            "gen_late_p95_ms": stats.percentile(late, 95),
            "drain_s": max(r["done"] for r in gen["records"]) - w1}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    for rate in (float(r) for r in args.rates.split(",")):
        env = dict(os.environ, BENCH_LOAD_OVERRIDE=json.dumps({"rate_rps": rate}))
        proc = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload", args.workload,
             "--seed", str(args.seed), "--seconds", str(args.seconds), "--trace", "0"],
            env=env, capture_output=True, text=True)
        if proc.returncode != 0:
            print(json.dumps({"rate_rps": rate, "error": proc.stderr[-2000:]}), flush=True)
            continue
        last = json.loads(proc.stdout.strip().splitlines()[-1])
        with open(os.path.join(os.path.dirname(HERE), ".cache", "bench", "runs", args.workload,
                               "generator.json"), encoding="utf-8") as f:
            out = row(rate, json.load(f))
        out.update(correct=last["correct"], setup_s=last["metrics"]["setup_s"]["value"])
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
