"""The trace reduction on hand-made interval tuples, the layer-metric readers
on a hand-made context, and both on a trimmed fixture of a real v5e trace."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmarks.harness import trace  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402

MS = 1_000_000
# two decode chunks with a prefill between them; times in ns
MODULES = [
    ("jit__decode_chunk(111)", 0 * MS, 80 * MS),       # (cut by the slice's start: not a whole event)
    ("jit__prefill_sample(222)", 82 * MS, 40 * MS),    # 2 ms idle before it
    ("jit__decode_chunk(111)", 122 * MS, 80 * MS),     # abuts: no gap
    ("jit__decode_chunk(111)", 206 * MS, 88 * MS),     # 4 ms idle before it (cut by the slice's end)
]
OPS = [
    ("while.1", 0 * MS, 80 * MS),                      # a loop wrapper around its body
    ("fusion.7", 0 * MS, 30 * MS), ("fusion.8", 30 * MS, 50 * MS),
    ("fusion.9", 82 * MS, 40 * MS),
    ("fusion.7", 122 * MS, 80 * MS),
    ("fusion.7", 206 * MS, 44 * MS), ("copy.3", 250 * MS, 44 * MS),
]


def test_busy_union_merges_nested_and_overlapping_events():
    assert trace.busy_union_ns([("a", 0, 10), ("b", 2, 3), ("c", 8, 6), ("d", 20, 5)]) == 14 + 5
    assert trace.busy_union_ns(OPS) == (80 + 40 + 80 + 88) * MS
    assert trace.busy_union_ns([]) == 0


def test_program_and_operation_names():
    assert trace.short_op(
        "%copy.83 = bf16[24,440,8,128,128]{4,3,2,1,0:T(8,128)(2,1)} copy(bf16[24,440,8,128,128]{4,3,2,1,0} %gte.1)"
    ) == "%copy.83 copy bf16[24,440,8,128,128]"
    assert trace.short_op(
        "%f.4 = (bf16[24,440]{1,0}, bf16[440]{0}) fusion(bf16[24,440]{1,0} %a, s32[] %b), kind=kLoop, calls=%c"
    ) == "%f.4 fusion bf16[24,440]"
    assert trace.short_op("jit__decode_chunk(111)") == "jit__decode_chunk(111)"


def test_program_names_and_gaps():
    assert trace.program("jit__decode_chunk(111)") == "_decode_chunk"
    assert trace.program("jit_forward") == "forward"
    assert trace.idle_gaps(MODULES) == [
        ("_decode_chunk->_prefill_sample", 2 * MS), ("_decode_chunk->_decode_chunk", 4 * MS)]
    assert trace.module_durations_ns(MODULES, r"decode_chunk") == [80 * MS, 80 * MS, 88 * MS]
    assert trace.module_durations_ns(MODULES, r"prefill") == [40 * MS]


def test_reduce_and_breakdown():
    planes = {"/device:TPU:0": {trace.MODULE_LINE: MODULES, trace.OP_LINE: OPS}}
    r = trace.reduce_trace(planes)
    assert r["busy_s"] == pytest.approx(0.288) and r["window_s"] == pytest.approx(0.294)
    assert r["chips"] == 1 and r["whole_modules"] == MODULES[1:3]
    b = trace.breakdown(r)
    # leaves only: while.1 contains fusion.7 and fusion.8 and drops out
    assert b["device_ops"][0] == ["fusion.7", pytest.approx(0.154)]
    assert ["while.1", pytest.approx(0.08)] not in b["device_ops"]
    assert b["idle_gaps"] == [["_decode_chunk->_decode_chunk", pytest.approx(0.004)],
                              ["_decode_chunk->_prefill_sample", pytest.approx(0.002)]]
    assert trace.reduce_trace({"/device:TPU:0": {}}) is None  # no device work: the run is refused


def test_four_chips_average_busy_time():
    planes = {f"/device:TPU:{i}": {trace.OP_LINE: [("f", 0, (i + 1) * 10 * MS), ("g", 90 * MS, 10 * MS)]}
              for i in range(4)}
    r = trace.reduce_trace(planes)
    assert r["chips"] == 4 and r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx((20 + 30 + 40 + 50) / 4 / 1000)


def _context(modules, ops, loop):
    prom = lambda s, c, occ: (  # noqa: E731
        f"app_tpu_ttft_seconds_sum {s}\napp_tpu_ttft_seconds_count {c}\n"
        f"app_tpu_tpot_seconds_sum {s / 10}\napp_tpu_tpot_seconds_count {c}\n"
        f"app_tpu_queue_wait_seconds_sum {s / 2}\napp_tpu_queue_wait_seconds_count {c}\n"
        f'app_tpu_batch_occupancy_sum{{kind="decode"}} {occ}\napp_tpu_batch_occupancy_count{{kind="decode"}} {c}\n')
    window = [{"ok": True, "due": 1.0, "sent": 1.002, "first": 1.302, "last": 2.302, "done": 2.31,
               "n_tokens": 101, "prompt_len": 200, "new_tokens": 101}] * 4
    with open(os.path.join(BENCH, "configs", "internlm2-1.8b.json")) as f:
        config = json.load(f)
    reduced = trace.reduce_trace({"/device:TPU:0": {trace.MODULE_LINE: modules, trace.OP_LINE: ops}})
    return {"schedule": {"loop": loop, "stream": loop == "open"}, "window": window, "records": window,
            "metrics_before": prom(0.0, 0, 0.0), "metrics_after": prom(1.0, 4, 3.6), "trace": reduced,
            "config": config, "device_kind": "TPU v5 lite", "engine": {"slots": 64}, "decode_chunk": 8}


def test_layer_readers_on_a_hand_made_context():
    readers = Manifest().layer_readers()
    got = {}
    for r in readers:
        got.update(r.read(_context(MODULES, OPS, "open")))
    assert got["gen_late_p95_ms"] == pytest.approx(2.0)
    assert got["transport_ttft_ms"] == pytest.approx(300 - 250)     # client 300 ms, engine 1.0/4 s
    assert got["stream_lag_ms"] == pytest.approx(10 - 25)           # client 10 ms, engine 0.1/4 s
    assert got["queue_wait_ms"] == pytest.approx(125)
    assert got["decode_occupancy"] == pytest.approx(90)
    assert got["dispatch_gap_ms"] == pytest.approx(3.0)             # median of 2 and 4
    assert got["prefill_step_ms"] == pytest.approx(40)
    assert got["decode_step_ms"] == pytest.approx(10)               # the one whole chunk: 80 ms / 8 steps
    assert got["device_idle_share.serve"] == got["device_idle_share.batch"] == pytest.approx(100 * 6 / 294)
    # 57.6 lanes at 250.5 live tokens: (weights − embedding table) + KV, over 819 GB/s, of a 10 ms step
    lanes = 0.9 * 64
    need = (1_889_110_016 - 92_544 * 2048) * 2 + lanes * 2048 * 2 + (lanes * 250.5 + lanes) * 98_304
    assert got["decode_hbm_share"] == pytest.approx(100 * need / 819e9 / 0.010)
    closed = {}
    for r in readers:
        closed.update(r.read(_context(MODULES, OPS, "closed")))
    assert not {"gen_late_p95_ms", "transport_ttft_ms", "stream_lag_ms"} & set(closed)


def test_readers_with_nothing_to_read_return_nothing():
    ctx = _context(MODULES, OPS, "open")
    ctx.update(window=[], metrics_after=ctx["metrics_before"])
    ctx["trace"] = dict(ctx["trace"], modules=[], whole_modules=[])
    got = {}
    for r in Manifest().layer_readers():
        got.update(r.read(ctx))
    assert set(got) == {"device_idle_share.serve", "device_idle_share.batch"}


FIXTURE = os.path.join(BENCH, "data", "trace_v5e_fixture.json")


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace fixture")
def test_reduction_on_a_recorded_v5e_trace():
    with open(FIXTURE) as f:
        fx = json.load(f)
    planes = {"/device:TPU:0": {trace.MODULE_LINE: [tuple(e) for e in fx["modules"]],
                                trace.OP_LINE: [tuple(e) for e in fx["ops"]]}}
    r = trace.reduce_trace(planes)
    want = fx["expected"]
    assert r["busy_s"] == pytest.approx(want["busy_s"]) and r["window_s"] == pytest.approx(want["window_s"])
    assert 0 < r["busy_s"] <= r["window_s"]
    programs = {trace.program(n) for n, _, _ in r["modules"]}
    assert "_decode_chunk" in programs
    b = trace.breakdown(r)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert [o[0] for o in b["device_ops"]] == want["top_ops"]
    # ten program events in the slice, the first and the last cut by its edges
    assert trace.module_durations_ns(r["whole_modules"], "decode_chunk") == want["whole_decode_chunks_ns"]
    assert len(want["whole_decode_chunks_ns"]) == 4 and min(want["whole_decode_chunks_ns"]) > 500e6
    assert [list(g) for g in trace.idle_gaps(r["modules"])] == want["gaps"]
    # leaves do not double count: their total is within the busy union
    assert sum(d for _, _, d in trace.leaf_events(r["ops"])) / 1e9 <= r["busy_s"] * 1.0001
