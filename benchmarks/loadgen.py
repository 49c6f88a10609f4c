"""The load generator: a child process of ``run.py`` that imports no JAX.

    python benchmarks/loadgen.py --schedule <json> --base <url> --metrics <url> --out <json>

It sends the schedule ``harness/traffic.build_schedule`` made, on its own
clock, and writes what it saw: per request the due, send, first-token,
last-token and done times (``time.monotonic()``, which parent and child
share), the token count and the first token; and the server's ``/metrics``
text scraped at the window's start and end. Its first stdout line tells the
parent when the ramp starts. An in-process generator would share the GIL with
the device loop and the server, and its lateness would read as theirs."""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import aiohttp  # noqa: E402

from benchmarks.harness.traffic import request_prompt  # noqa: E402

LEAD_S = 0.5  # between the start line and the ramp


def _body(schedule: dict, req: dict) -> bytes:
    return json.dumps({"prompt": request_prompt(schedule, req),
                       "max_new_tokens": req["new_tokens"],
                       "timeout": schedule["client_timeout_s"]}).encode()


async def _send(session, url: str, body: bytes, rec: dict, stream: bool, vocab: int) -> None:
    """One request; fills ``rec`` in place. Never raises: a failure is a
    record with ``error`` set."""
    rec["sent"] = time.monotonic()
    n, first_tok, in_vocab, saw_done = 0, None, True, False
    try:
        async with session.post(url, data=body, headers={"Content-Type": "application/json"}) as resp:
            rec["status"] = resp.status
            if stream:
                event, buf = None, b""
                async for chunk in resp.content.iter_any():
                    now = time.monotonic()
                    buf += chunk
                    while b"\n" in buf:
                        line, buf = buf.split(b"\n", 1)
                        if line.startswith(b"event: "):
                            event = line[7:].decode()
                        elif line.startswith(b"data: "):
                            if event == "token":
                                tok = int(json.loads(line[6:]))
                                if n == 0:
                                    first_tok, rec["first"] = tok, now
                                n += 1
                                rec["last"] = now
                                in_vocab = in_vocab and 0 <= tok < vocab
                            elif event == "done":
                                saw_done = True
                            elif event == "error":
                                rec["error"] = "sse error: " + line[6:].decode()[:200]
            else:
                payload = await resp.json()
                now = time.monotonic()
                if 200 <= resp.status < 300:
                    toks = [int(t) for t in payload["data"]["tokens"]]
                    n, saw_done = len(toks), True
                    if toks:
                        first_tok, rec["first"], rec["last"] = toks[0], now, now
                    in_vocab = all(0 <= t < vocab for t in toks)
                else:
                    rec["error"] = json.dumps(payload)[:200]
    except asyncio.CancelledError:
        rec["error"] = "no answer before the drain ended"
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError, KeyError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    rec["done"] = time.monotonic()
    rec["n_tokens"], rec["first_token"] = n, first_tok
    rec["ok"] = bool("error" not in rec and 200 <= rec.get("status", 0) < 300 and saw_done
                     and in_vocab and n == rec["new_tokens"])
    if not rec["ok"] and "error" not in rec:
        rec["error"] = (f"status {rec.get('status')}, {n} of {rec['new_tokens']} tokens, "
                        f"done={saw_done}, in_vocab={in_vocab}")


async def _scrape(session, url: str, at: float) -> str:
    await asyncio.sleep(max(0.0, at - time.monotonic()))
    async with session.get(url) as r:
        return await r.text()


async def run(schedule: dict, base: str, metrics_url: str) -> dict:
    reqs, stream, vocab = schedule["requests"], schedule["stream"], schedule["vocab"]
    url = base + schedule["endpoint"]
    timeout = aiohttp.ClientTimeout(total=schedule["client_timeout_s"])
    conn = aiohttp.TCPConnector(limit=0)
    bodies = [_body(schedule, r) for r in reqs] if schedule["loop"] == "open" else []
    # the start line: everything after it is on the clock the parent now knows
    t0 = time.monotonic() + LEAD_S
    print(json.dumps({"event": "start", "t0": t0}), flush=True)
    w0 = t0 + schedule["ramp_s"]
    w1 = w0 + schedule["seconds"]
    records: list[dict] = []
    tasks: set[asyncio.Task] = set()
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        scrapes = [asyncio.ensure_future(_scrape(session, metrics_url, at)) for at in (w0, w1)]

        def launch(req: dict, body: bytes, due: float) -> asyncio.Task:
            rec = {"seq": req["seq"], "due": due, "prompt_len": req["prompt_len"],
                   "new_tokens": req["new_tokens"]}
            records.append(rec)
            task = asyncio.ensure_future(_send(session, url, body, rec, stream, vocab))
            tasks.add(task)
            task.add_done_callback(tasks.discard)
            return task

        if schedule["loop"] == "open":
            for req, body in zip(reqs, bodies):
                due = t0 + req["due"]
                if due >= w1:
                    break
                await asyncio.sleep(max(0.0, due - time.monotonic()))
                launch(req, body, due)
        else:
            nxt = iter(range(10 ** 9))

            async def caller() -> None:
                await asyncio.sleep(max(0.0, t0 - time.monotonic()))
                while time.monotonic() < w1:
                    i = next(nxt)
                    req = dict(reqs[i % len(reqs)], seq=i)
                    await launch(req, _body(schedule, req), time.monotonic())

            callers = [asyncio.ensure_future(caller()) for _ in range(schedule["clients"])]
            tasks.update(callers)  # a caller ends when its last request has ended
        # the window is over: nothing new is sent; what is in flight is awaited
        # until the drain's bound, then cancelled (and recorded as unanswered)
        _, pending = await asyncio.wait(
            set(tasks), timeout=max(0.0, w1 + schedule["drain_s"] - time.monotonic()))
        for task in pending:
            task.cancel()
        if pending:
            await asyncio.wait(pending)
        before, after = await asyncio.gather(*scrapes)
    return {"t0": t0, "window_start": w0, "window_end": w1, "records": records,
            "metrics_before": before, "metrics_after": after}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--schedule", required=True)
    ap.add_argument("--base", required=True)
    ap.add_argument("--metrics", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    with open(args.schedule, encoding="utf-8") as f:
        schedule = json.load(f)
    result = asyncio.run(run(schedule, args.base, args.metrics))
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main()
