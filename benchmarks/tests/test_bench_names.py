"""``harness/names``: the wire-format adaptor on a hand-encoded XSpace, the
arithmetic on hand-made tuples (scope sums, idle attribution, dispatch
pairing), both on a trimmed fixture of a real v5e trace, and the readers
built on them on a hand-made context."""

import json
import os
import struct
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmarks.harness import names  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402

MS = 1_000_000
PRE = "jit(_decode_chunk)/while/body/closed_call/jit(decode_step_paged)/while/body/"


# -- a protobuf encoder just large enough to write an XSpace by hand -----------------


def _varint(v: int) -> bytes:
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def _f(field: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(field << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _stat(stat_ids: dict, name: str, value) -> bytes:
    sid = stat_ids.setdefault(name, len(stat_ids) + 1)
    if isinstance(value, str):
        return _f(1, sid) + _f(5, value)
    if isinstance(value, float):
        return _f(1, sid) + _f(2, value)
    return _f(1, sid) + _f(4 if value < 0 else 3, value)


def _plane(name: str, lines: dict) -> bytes:
    """``lines``: {line: (timestamp_ns, [(event name, offset_ps, dur_ps,
    event stats, metadata stats)])}."""
    stat_ids, meta_ids, meta, out = {}, {}, b"", _f(2, name)
    for lname, (t0, events) in lines.items():
        line = _f(2, lname) + _f(3, t0)
        for ename, offset, dur, stats, mstats in events:
            if ename not in meta_ids:
                meta_ids[ename] = len(meta_ids) + 1
                record = _f(1, meta_ids[ename]) + _f(2, ename) + b"".join(
                    _f(5, _stat(stat_ids, k, v)) for k, v in mstats.items())
                meta += _f(4, _f(1, meta_ids[ename]) + _f(2, record))
            line += _f(4, _f(1, meta_ids[ename]) + _f(2, offset) + _f(3, dur) + b"".join(
                _f(4, _stat(stat_ids, k, v)) for k, v in stats.items()))
        out += _f(3, line)
    out += meta
    for sname, sid in stat_ids.items():
        out += _f(5, _f(1, sid) + _f(2, _f(1, sid) + _f(2, sname)))
    return out


def test_adaptor_reads_scope_paths_run_ids_and_annotations(tmp_path):
    device = _plane("/device:TPU:0", {
        "XLA Modules": (1_000, [
            ("jit__decode_chunk(77)", 0, 9_000_000, {"run_id": 154}, {}),
            ("jit__prefill_sample(88)", 9_000_000, 1_000_000, {"run_id": 155}, {})]),
        "XLA Ops": (1_000, [
            ("%fusion.1 = bf16[8]{0} fusion(...)", 0, 4_000_000, {"device_offset_ps": 5},
             {"tf_op": PRE + "closed_call/kv_append/dot_general:", "flops": 12, "hlo_category": "loop fusion"}),
            ("%copy.2 = bf16[8]{0} copy(...)", 4_000_000, 5_000_000, {}, {}),
            ("%fusion.1 = bf16[8]{0} fusion(...)", 9_000_000, 1_000_000, {}, {})]),
        "Async XLA Ops": (1_000, [("%copy-start.1", 0, 1_000, {}, {})]),
    })
    idle_chip = _plane("/device:TPU:1", {"XLA Ops": (1_000, [("%x", 0, 1_000, {}, {})])})
    host = _plane("/host:CPU", {
        "python3": (2_000, [
            ("loop.dispatch_prefill", 0, 500_000, {"seq": 9, "kind": "prefill"}, {}),
            ("PjitFunction(_prefill_sample)", 100_000, 200_000, {}, {}),
            ("loop.wait_work", 600_000, 100_000, {}, {})]),
        "tfrt-non-blocking-queue/3": (2_000, [("DoEnqueueProgram", 0, 1_000, {"run_id": 155}, {})]),
    })
    other = _plane("/host:metadata", {})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"".join(_f(1, p) for p in (other, device, idle_chip, host)))
    got = names.read_names(str(path))
    assert got["modules"] == [("_decode_chunk", 1_000, 9_000, 154), ("_prefill_sample", 10_000, 1_000, 155)]
    # the path comes from the event's METADATA record, shared by both runs of fusion.1
    assert got["ops"] == [(PRE + "closed_call/kv_append/dot_general:", 1_000, 4_000),
                          ("", 5_000, 5_000),
                          (PRE + "closed_call/kv_append/dot_general:", 10_000, 1_000)]
    assert got["host"] == [("dispatch_prefill", 9, "prefill", 2_000, 500),
                           ("wait_work", None, None, 2_600, 100)]
    empty = tmp_path / "e.xplane.pb"
    empty.write_bytes(_f(1, host))
    assert names.read_names(str(empty)) is None  # no device operation


def test_names_are_the_programs_own():
    from gofr_tpu import tracing

    assert names.SCOPES == tracing.SCOPES and names.LOOP_PHASES == tracing.LOOP_PHASES
    assert set(names.DENSE) < set(names.SCOPES) and set(names.WORKING) < set(names.LOOP_PHASES)
    assert names.program("jit__decode_chunk(9064851394890411846)") == "_decode_chunk"


# -- scope sums ----------------------------------------------------------------------


def test_innermost_scope():
    assert names.innermost_scope(PRE + "closed_call/kv_append/np,nhd->phd/dot_general:") == "kv_append"
    # a gather inside the attention op: the innermost scope wins
    assert names.innermost_scope(PRE + "closed_call/attention/kv_gather/gather:") == "kv_gather"
    assert names.innermost_scope(PRE + "closed_call/attention/attention/jit(_where)/select_n:") == "attention"
    # the layer scan's own stacking, a compiler-inserted copy: no program scope
    assert names.innermost_scope(PRE + "dynamic_update_slice:") is None
    assert names.innermost_scope("") is None
    # a scope name inside another word is not a scope
    assert names.innermost_scope("jit(f)/sample_token/argmax:") is None


def _chunk_case():
    modules = [
        ("_decode_chunk", 0 * MS, 50 * MS, 10),       # cut by the slice's start
        ("_decode_chunk", 50 * MS, 100 * MS, 11),     # whole
        ("_prefill_sample", 150 * MS, 20 * MS, 12),   # whole, another program
        ("_decode_chunk", 170 * MS, 100 * MS, 13),    # whole
        ("_decode_chunk", 270 * MS, 30 * MS, 14),     # cut by the slice's end
    ]
    ops = [
        (PRE + "closed_call/kv_append/dot_general:", 10 * MS, 30 * MS),           # in the cut run: out
        (PRE + "while:", 50 * MS, 100 * MS),                                       # a wrapper: not a leaf
        (PRE + "closed_call/kv_append/dot_general:", 50 * MS, 30 * MS),
        (PRE + "closed_call/attention/kv_gather/gather:", 80 * MS, 20 * MS),       # fusion under a nested scope
        (PRE + "closed_call/attention/dot_general:", 100 * MS, 10 * MS),
        ("", 110 * MS, 25 * MS),                                                   # compiler's copy
        (PRE + "dynamic_update_slice:", 135 * MS, 5 * MS),                         # the scan's stacking
        (PRE + "closed_call/mlp/dot_general:", 140 * MS, 6 * MS),
        ("jit(_decode_chunk)/while/body/closed_call/sample/argmax:", 146 * MS, 4 * MS),
        ("jit(_prefill_sample)/jit(prefill_paged)/while/body/closed_call/attention/dot_general:",
         150 * MS, 20 * MS),                                                       # the prefill's: out
        (PRE + "closed_call/kv_append/dot_general:", 170 * MS, 40 * MS),
        (PRE + "closed_call/qkv_rope/mul:", 210 * MS, 60 * MS),
        (PRE + "closed_call/lm_head/dot_general:", 280 * MS, 10 * MS),             # in the cut run: out
    ]
    return ops, modules


def test_scope_sums_over_whole_decode_chunks():
    ops, modules = _chunk_case()
    sums, runs = names.scope_sums(ops, modules, r"decode_chunk")
    assert runs == 2
    assert sums["kv_append"] == 70 * MS and sums["kv_gather"] == 20 * MS
    assert sums["attention"] == 10 * MS and sums["unscoped"] == 30 * MS
    assert sums["mlp"] == 6 * MS and sums["qkv_rope"] == 60 * MS and sums["sample"] == 4 * MS
    assert sums["lm_head"] == sums["embed"] == sums["o_proj"] == 0
    # the parts are the leaf-operation time of the two whole runs: nothing twice, nothing lost
    assert sum(sums.values()) == 200 * MS
    assert names.scope_sums(ops, modules[:2], r"decode_chunk") == (dict.fromkeys(sums, 0), 0)


# -- idle attribution ------------------------------------------------------------------


def test_self_intervals_cut_nested_phases_out_of_their_parent():
    host = [("admit", None, None, 10, 90),            # 10..100
            ("dispatch_prefill", 5, "prefill", 30, 20),    # 30..50 inside admit
            ("readback", 4, "plain", 40, 5),               # 40..45 inside the dispatch
            ("fold", 4, "plain", 120, 10)]
    assert names.self_intervals(host) == [
        ("admit", 10, 30), ("dispatch_prefill", 30, 40), ("readback", 40, 45),
        ("dispatch_prefill", 45, 50), ("admit", 50, 100), ("fold", 120, 130)]


def test_idle_gaps_are_split_by_the_phase_that_was_innermost():
    ops = [("", 0, 100), ("", 40, 20),          # busy 0..100 (nested op changes nothing)
           ("", 160, 40),                        # gap 100..160
           ("", 200, 50), ("", 260, 40)]         # abuts; then gap 250..260
    host = [("readback", 7, "plain", 0, 110),    # covers 100..110 of the first gap
            ("fold", 7, "plain", 110, 20),       # 110..130
            ("admit", None, None, 135, 40),      # 135..175, with a dispatch nested at 150..170
            ("dispatch_prefill", 8, "prefill", 150, 20),
            ("wait_work", None, None, 240, 100)]
    idle, slice_ns = names.idle_by_phase(ops, host)
    assert slice_ns == 300
    # the 60 ns gap spans four phases and 5 ns of nothing
    assert idle["readback"] == 10 and idle["fold"] == 20 and idle["admit"] == 15
    assert idle["dispatch_prefill"] == 10 and idle["unattributed"] == 5
    assert idle["wait_work"] == 10
    assert sum(idle.values()) == 70
    assert names.idle_by_phase([], host) == (dict.fromkeys(idle, 0), 0)
    assert names.idle_by_phase(ops, [])[0]["unattributed"] == 70


# -- pairing a program run with the dispatch that launched it -------------------------


def _pairing_case():
    """A slice that opens with two programs already in flight: runs 40 and 41
    were dispatched before it began, so the first dispatch annotation in the
    slice (seq 12) belongs to the THIRD module event."""
    modules = [
        ("_decode_chunk", 0 * MS, 300 * MS, 40),      # cut by the start; its readback (seq 10) is in the slice
        ("_prefill_sample", 300 * MS, 60 * MS, 41),   # dispatched before the slice began (seq 11)
        ("_decode_chunk", 360 * MS, 500 * MS, 42),    # seq 12
        ("_prefill_sample", 860 * MS, 80 * MS, 43),   # seq 13: dispatched at 366, waits the chunk out
        ("_decode_chunk", 940 * MS, 500 * MS, 44),    # seq 14
        ("_decode_chunk", 1440 * MS, 100 * MS, 45),   # cut by the end
    ]
    host = [
        ("dispatch_decode", 12, None, 1 * MS, 2 * MS),
        ("readback", 10, "plain", 3 * MS, 300 * MS),              # ends 303: run 40 ended at 300
        ("fold", 10, "plain", 303 * MS, 1 * MS),
        ("readback", 11, "prefill", 304 * MS, 59 * MS),           # ends 363: run 41 ended at 360
        ("fold", 11, "prefill", 363 * MS, 1 * MS),
        ("admit", None, None, 364 * MS, 3 * MS),
        ("dispatch_prefill", 13, "prefill", 365 * MS, 1 * MS),    # ends 366
        ("dispatch_decode", 14, None, 367 * MS, 2 * MS),
        ("readback", 12, "plain", 369 * MS, 494 * MS),            # ends 863: run 42 ended at 860
        ("fold", 12, "plain", 863 * MS, 1 * MS),
        ("readback", 13, "prefill", 864 * MS, 79 * MS),           # ends 943
        ("admit", None, None, 944 * MS, 3 * MS),
        ("dispatch_prefill", 15, "prefill", 945 * MS, 1 * MS),    # its program starts after the slice: no pair
        ("dispatch_decode", 16, None, 947 * MS, 2 * MS),
        ("readback", 14, "plain", 949 * MS, 494 * MS),            # ends 1443
    ]
    return modules, host


def test_dispatch_pairing_with_programs_in_flight_at_the_slice_start():
    modules, host = _pairing_case()
    assert names.run_of_seq(modules, host) == 30
    # run 41 is whole but its dispatch lies before the slice; run 43 pairs with seq 13
    assert names.prefill_queue_ns(modules, host) == [(860 - 366) * MS]
    # without run ids the modules are numbered in start order: same pairs
    bare = [(p, s, d, None) for p, s, d, _ in modules]
    assert names.prefill_queue_ns(bare, host) == [(860 - 366) * MS]
    # a readback that lagged a whole program (ends after the NEXT run did) names
    # the wrong kind and casts no vote; the others still carry the pairing
    lagged = [h if h[:2] != ("readback", 12) else ("readback", 12, "plain", 369 * MS, 1080 * MS)
              for h in host]
    assert names.run_of_seq(modules, lagged) == 30
    # no readback in the slice, or a program without annotations: nothing to pair
    assert names.prefill_queue_ns(modules, [h for h in host if h[0] != "readback"]) == []
    assert names.prefill_queue_ns(modules, []) == []


# -- the recorded fixture ---------------------------------------------------------------


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(BENCH, "data", "names_v5e_fixture.json"), encoding="utf-8") as f:
        fx = json.load(f)
    fx["ops"] = [(fx["paths"][i], start, dur) for i, start, dur in fx["ops"]]
    fx["modules"] = [tuple(m) for m in fx["modules"]]
    fx["host"] = [tuple(h) for h in fx["host"]]
    return fx


def test_fixture_scope_sums_add_up_to_the_chunk(fixture):
    around = [m for m in fixture["modules"] if m[3] in (155, 156, 157)]
    sums, runs = names.scope_sums(fixture["ops"], around, r"decode_chunk")
    assert runs == 1 and sums == fixture["expected"]["scope_ns"]
    leaf = sum(dur for _, _, dur in names.leaf_ops(fixture["ops"]))
    assert sum(sums.values()) == leaf == fixture["expected"]["leaf_ns"]
    # the leaf operations fill the program event: a step is all device work
    chunk = next(m for m in around if m[3] == 156)
    assert 0.995 < leaf / chunk[2] <= 1.0
    # what the trace showed (PERF.md §6, PR 26): the gather and the unscoped
    # pool traffic are the two largest parts, every scope of a decode step is there
    assert all(sums[s] > 0 for s in names.SCOPES)
    assert sorted(sums, key=sums.get)[-2:] == ["kv_gather", "unscoped"]


def test_fixture_pairs_every_prefill_with_its_dispatch(fixture):
    assert names.run_of_seq(fixture["modules"], fixture["host"]) == fixture["expected"]["run_minus_seq"]
    waits = names.prefill_queue_ns(fixture["modules"], fixture["host"])
    assert waits == fixture["expected"]["prefill_queue_ns"] and len(waits) == 6
    # each prefill waited out one decode chunk of 8 steps of ~66 ms
    assert all(500 * MS < w < 540 * MS for w in waits)
    idle, slice_ns = names.idle_by_phase(fixture["ops"], fixture["host"])
    assert sum(idle.values()) < 1e-3 * slice_ns  # the device never waits for the host here


# -- the readers -----------------------------------------------------------------------


def _metrics(seconds: dict, counts: dict, occupancy=(0.0, 0)) -> str:
    lines = [f'app_tpu_loop_phase_seconds_total{{phase="{p}"}} {v}' for p, v in seconds.items()]
    lines += [f'app_tpu_loop_phase_total{{phase="{p}"}} {v}' for p, v in counts.items()]
    lines += [f'app_tpu_batch_occupancy_sum{{kind="decode"}} {occupancy[0]}',
              f'app_tpu_batch_occupancy_count{{kind="decode"}} {occupancy[1]}']
    return "\n".join(lines) + "\n"


def test_readers_on_a_hand_made_context(monkeypatch, capsys):
    manifest = Manifest()
    readers = {name: r for r in manifest.layer_readers() for name in r.NAMES}
    ops, modules = _chunk_case()
    pair_modules, host = _pairing_case()
    with open(os.path.join(BENCH, "configs", "internlm2-1.8b.json"), encoding="utf-8") as f:
        config = json.load(f)
    ctx = {
        "cell": {"name": "no-such-cell"}, "trace": {"modules": []}, "decode_chunk": 8,
        "config": config, "device_kind": "TPU v5 lite", "engine": {"slots": 40},
        "window": [{"ok": True, "n_tokens": 100, "prompt_len": 250}],
        "metrics_before": _metrics({"admit": 1.0, "dispatch_prefill": 0.5, "dispatch_decode": 1.0, "fold": 2.0},
                                   {"dispatch_decode": 100}, (10.0, 20)),
        "metrics_after": _metrics({"admit": 1.3, "dispatch_prefill": 0.6, "dispatch_decode": 1.4, "fold": 2.2},
                                  {"dispatch_decode": 200}, (28.0, 40)),
    }
    # no trace file for this cell: the trace readers read nothing, and do not raise
    for name in ("decode_kv_append_ms", "idle_host_busy_ms", "prefill_device_queue_ms"):
        assert readers[name].read(ctx) == {}
    # the counters alone are enough for the loop readers
    loop = readers["loop_admit_ms"].read(ctx)
    assert loop == pytest.approx({"loop_admit_ms": 3.0, "loop_dispatch_ms": 5.0, "loop_fold_ms": 2.0})
    parent = dict(ctx, metrics_before="", metrics_after="app_tpu_tokens_total 5\n")
    assert readers["loop_fold_ms"].read(parent) == {}  # a program without the counters

    monkeypatch.setattr(names, "load", lambda ctx: {"ops": ops, "modules": modules, "host": host})
    got = readers["decode_kv_append_ms"].read(ctx)
    per_step = 2 * 8 * MS  # two whole chunks of 8 steps, ns → ms
    assert got["decode_kv_append_ms"] == pytest.approx(70 * MS / per_step)
    assert got["decode_dense_ms"] == pytest.approx(66 * MS / per_step)  # mlp 6 + qkv_rope 60
    assert got["decode_unscoped_ms"] == pytest.approx(30 * MS / per_step)
    six = [got[f"decode_{p}_ms"] for p in ("kv_append", "kv_gather", "attention", "dense", "sample", "unscoped")]
    assert sum(six) == pytest.approx(200 * MS / per_step)
    # 0.9 occupancy × 40 slots × 300 live tokens × 98,304 B over 819 GB/s, against gather + attention
    least_ms = 0.9 * 40 * 300 * 98_304 / 819e9 * 1e3
    assert got["decode_attention_hbm_share"] == pytest.approx(100 * least_ms / (30 * MS / per_step))
    # a program without scopes (the parent): every operation unscoped → nothing is reported
    bare = [("", s, d) for _, s, d in ops]
    monkeypatch.setattr(names, "load", lambda ctx: {"ops": bare, "modules": modules, "host": []})
    assert readers["decode_kv_append_ms"].read(ctx) == {}
    assert readers["idle_host_busy_ms"].read(ctx) == {}
    assert readers["prefill_device_queue_ms"].read(ctx) == {}

    monkeypatch.setattr(names, "load", lambda ctx: {"ops": ops, "modules": pair_modules, "host": host})
    assert readers["prefill_device_queue_ms"].read(ctx) == {"prefill_device_queue_ms": 494.0}
    capsys.readouterr()
    idle = readers["idle_host_busy_ms"].read(ctx)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["note"] == "idle_by_phase" and set(note["idle_ms"]) == set(names.LOOP_PHASES) | {"unattributed"}
    assert idle["idle_host_busy_ms"] == pytest.approx(
        sum(note["idle_ms"][p] for p in names.WORKING) / note["slice_s"])


def test_load_finds_the_runs_newest_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(names, "REPO", str(tmp_path))
    ctx = {"cell": {"name": "a-cell"}, "trace": {"modules": []}}
    assert names.load(ctx) is None and names.load({"cell": {"name": "a-cell"}}) is None
    folder = tmp_path / ".cache" / "bench" / "runs" / "a-cell" / "trace" / "plugins" / "profile" / "t1"
    folder.mkdir(parents=True)
    device = _plane("/device:TPU:0", {
        "XLA Modules": (0, [("jit__decode_chunk(1)", 0, 2_000_000, {"run_id": 1}, {})]),
        "XLA Ops": (0, [("%a", 0, 2_000_000, {}, {"tf_op": "jit(f)/mlp/dot_general:"})])})
    (folder / "host.xplane.pb").write_bytes(_f(1, device))
    assert names.load(ctx)["ops"] == [("jit(f)/mlp/dot_general:", 0, 2_000)]
