"""The program's own names in a profiler trace: the scope path of every device
operation (``gofr_tpu.tracing.SCOPES``, put there by ``jax.named_scope``) and
the device loop's host phases (``gofr_tpu.tracing.LOOP_PHASES``, put there by
``jax.profiler.TraceAnnotation``). One adaptor re-reads the run's
``.xplane.pb`` into plain tuples; everything after it is arithmetic on tuples
and is tested without a chip on ``data/names_v5e_fixture.json``.

What a v5e trace looks like (read by hand, PR 26, jax 0.9.0 / libtpu 0.0.34,
``host_tracer_level`` 1, ``python_tracer_level`` 0):

- ``/device:TPU:0``, line ``XLA Modules``: one event per program run, named
  ``jit__decode_chunk(<fingerprint>)``, with a ``run_id`` stat that counts up
  by one per run. Line ``XLA Ops``: one event per operation, named by its whole
  HLO line WITHOUT ``metadata={...}``. The scope path is not on the event: it
  is the ``tf_op`` stat of the event's METADATA record (shared by every run of
  that operation), e.g.
  ``jit(_decode_chunk)/while/body/closed_call/jit(decode_step_paged)/while/body/closed_call/kv_append/np,nhd->phd/dot_general:``;
  a fusion carries the path of its root. ``jax.profiler.ProfileData`` shows
  event stats only, never metadata stats, so this module decodes the protobuf
  wire format itself (a few fields of ``XSpace``; no dependency beyond the
  standard library). The same record has ``hlo_category``, ``flops`` and
  ``bytes_accessed``.
- ``/host:CPU``, line ``python3`` (the engine's device-loop thread): events
  ``loop.<phase>`` with stats ``seq`` and ``kind``, nested as the phases nest,
  beside JAX's own (``PjitFunction(_decode_chunk)``, ``np.asarray(jax.Array)``).
  The runtime's ``DoEnqueueProgram`` events carry the same ``run_id`` as the
  device's module events, but on another thread and after the dispatch
  annotation has ended, so they cannot be matched to an annotation by
  containment. What pairs a module run with its dispatch is the order:
  ``run_id − seq`` is one constant while every program run is a ``_dq``
  entry, and it is read off the readbacks — a ``loop.readback`` ends about
  3.4 ms after its program's module event does (521–530 ms apart), and its
  ``kind`` says which program that was.
- Host and device events share one clock (line ``timestamp_ns`` + event
  ``offset_ps``): the 3.4 ms above is constant over a slice.

``ctx`` hands a reader neither the trace directory nor scope paths (a
``benchmark`` issue should), so :func:`load` finds the file by the layout
``run.py:main`` fixes: ``.cache/bench/runs/<cell>/trace``."""

from __future__ import annotations

import functools
import glob
import os
import re
import struct

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# COPIES of gofr_tpu.tracing.SCOPES / LOOP_PHASES: the yardstick keeps its own
# spelling, so renaming a scope in the program silences the metric instead of
# moving it (tests/test_bench_names.py compares the two lists).
SCOPES = ("embed", "qkv_rope", "kv_append", "kv_gather", "attention", "o_proj", "mlp",
          "lm_head", "sample")
DENSE = ("embed", "qkv_rope", "o_proj", "mlp", "lm_head")
LOOP_PHASES = ("control", "admit", "dispatch_prefill", "dispatch_decode", "readback", "fold",
               "wait_work")
WORKING = ("control", "admit", "dispatch_prefill", "dispatch_decode", "fold")
# which program a _dq entry of each kind runs (gofr_tpu/tpu/programs.py)
KIND_PROGRAM = {"plain": "_decode_chunk", "spec": "_spec_chunk", "prefill": "_prefill_sample",
                "chunk": "_chunk_prefill", "swapin": "swap_in_pages"}

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"


# -- the adaptor: protobuf wire format → tuples -------------------------------------


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf: memoryview):
    """Yield ``(field number, wire type, value)`` of one protobuf message:
    varints as int, length-delimited fields as memoryview, fixed64 as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, wire, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, wire, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield field, wire, bytes(buf[i:i + size])
            i += size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: memoryview, stat_names: dict):
    """One ``XStat`` → ``(name, value)``."""
    name, value = None, None
    for field, wire, v in _fields(buf):
        if field == 1:
            name = stat_names.get(v)
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field in (3, 7):
            value = stat_names.get(v, v) if field == 7 else v
        elif field == 4:
            value = _signed(v)
        elif field == 5:
            value = bytes(v).decode("utf-8", "replace")
    return name, value


def _plane(buf: memoryview) -> dict | None:
    """One ``XPlane`` → ``{"name", "lines": {line: [(name, start_ns, dur_ns,
    event stats, metadata stats)]}}``: every line of the host plane, the
    operation and module lines of a device plane; None for any other plane."""
    name, lines, metadata, stat_names = "", [], [], {}
    for field, wire, v in _fields(buf):
        if field == 2:
            name = bytes(v).decode()
        elif field == 3:
            lines.append(v)
        elif field == 4:  # map<int64, XEventMetadata>
            for f2, _, entry in _fields(v):
                if f2 == 2:
                    metadata.append(entry)
        elif field == 5:  # map<int64, XStatMetadata>
            for f2, _, entry in _fields(v):
                if f2 == 2:
                    sid, sname = None, ""
                    for f3, _, x in _fields(entry):
                        if f3 == 1:
                            sid = x
                        elif f3 == 2:
                            sname = bytes(x).decode()
                    stat_names[sid] = sname
    if not (DEVICE_PLANE.match(name) or name == HOST_PLANE):
        return None
    meta = {}
    for entry in metadata:
        mid, mname, mstats = None, "", {}
        for f, _, x in _fields(entry):
            if f == 1:
                mid = x
            elif f == 2:
                mname = bytes(x).decode("utf-8", "replace")
            elif f == 5:
                k, val = _stat(x, stat_names)
                if k == "tf_op":  # the record also has hlo_category, flops, bytes_accessed
                    mstats[k] = val
        meta[mid] = (mname, mstats)
    out = {}
    for raw in lines:
        lname, t0, events = "", 0, []
        for f, _, x in _fields(raw):
            if f == 2:
                lname = bytes(x).decode()
            elif f == 3:
                t0 = x
            elif f == 4:
                events.append(x)
        if name != HOST_PLANE and lname not in ("XLA Ops", "XLA Modules"):
            continue
        rows = []
        for ev in events:
            mid = offset = dur = 0
            stats = {}
            for f, _, x in _fields(ev):
                if f == 1:
                    mid = x
                elif f == 2:
                    offset = x
                elif f == 3:
                    dur = x
                elif f == 4:
                    k, val = _stat(x, stat_names)
                    if k in ("seq", "kind", "run_id"):
                        stats[k] = val
            mname, mstats = meta.get(mid, ("", {}))
            rows.append((mname, t0 + offset // 1000, dur // 1000, stats, mstats))
        out[lname] = rows
    return {"name": name, "lines": out}


def read_names(path: str) -> dict | None:
    """An ``.xplane.pb`` → the tuples the arithmetic below takes:

    - ``ops``: ``(scope path, start_ns, dur_ns)`` per ``XLA Ops`` event of the
      busiest chip (the path is the metadata's ``tf_op``; ``""`` if none);
    - ``modules``: ``(program, start_ns, dur_ns, run_id)`` per ``XLA Modules`` event;
    - ``host``: ``(phase, seq, kind, start_ns, dur_ns)`` per ``loop.<phase>``
      event of the host plane (``seq`` / ``kind`` None where the phase has none).

    None when the file holds no device operation."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    chips, host = [], []
    for field, _, v in _fields(buf):
        if field != 1:
            continue
        plane = _plane(v)
        if plane is None:
            continue
        if plane["name"] == HOST_PLANE:
            for rows in plane["lines"].values():
                host += [(name[5:], stats.get("seq"), stats.get("kind"), start, dur)
                         for name, start, dur, stats, _ in rows if name.startswith("loop.")]
        elif plane["lines"].get("XLA Ops"):
            chips.append(plane["lines"])
    if not chips:
        return None
    main = max(chips, key=lambda lines: sum(r[2] for r in lines["XLA Ops"]))
    return {
        "ops": [(m.get("tf_op") or "", start, dur) for _, start, dur, _, m in main["XLA Ops"]],
        "modules": [(program(name), start, dur, stats.get("run_id"))
                    for name, start, dur, stats, _ in main.get("XLA Modules", [])],
        "host": sorted(host, key=lambda e: e[3]),
    }


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> dict | None:
    return read_names(path)


def load(ctx: dict) -> dict | None:
    """The tuples of this run's trace, or None when there is none to read
    (the readers built on this then return ``{}``)."""
    cell = (ctx.get("cell") or {}).get("name")
    if not cell or not ctx.get("trace"):
        return None
    files = sorted(glob.glob(os.path.join(
        REPO, ".cache", "bench", "runs", cell, "trace", "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    return _load(files[-1], os.path.getmtime(files[-1]))


# -- arithmetic on tuples ------------------------------------------------------------


def program(name: str) -> str:
    """``jit__decode_chunk(1234)`` → ``_decode_chunk``."""
    name = name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def innermost_scope(path: str) -> str | None:
    """The last component of an operation's scope path that is a program
    scope: ``.../attention/kv_gather/gather:`` → ``kv_gather``."""
    for part in reversed(path.rstrip(":").split("/")):
        if part in SCOPES:
            return part
    return None


def whole_modules(modules: list, pattern: str) -> list:
    """The program runs the slice's edges did not cut (the profiler clips the
    first and the last) whose program name matches."""
    ordered = sorted(modules, key=lambda m: m[1])[1:-1]
    rx = re.compile(pattern)
    return [m for m in ordered if rx.search(m[0])]


def leaf_ops(ops: list) -> list:
    """Operations that contain no other: ``while`` and ``call`` wrappers drop
    out, what runs inside them stays (as ``harness/trace.leaf_events``)."""
    ordered = sorted(ops, key=lambda e: (e[1], -e[2]))
    leaves = []
    for i, (path, start, dur) in enumerate(ordered):
        if i + 1 < len(ordered):
            _, s2, d2 = ordered[i + 1]
            if s2 < start + dur and s2 + d2 <= start + dur and d2 < dur:
                continue
        leaves.append((path, start, dur))
    return leaves


def scope_sums(ops: list, modules: list, pattern: str) -> tuple[dict, int]:
    """Leaf-operation time inside the whole runs of the programs matching
    ``pattern``, by innermost scope (``"unscoped"`` for none) → ``({scope:
    ns}, runs)``."""
    runs = whole_modules(modules, pattern)
    spans = [(start, start + dur) for _, start, dur, _ in runs]
    sums = dict.fromkeys(SCOPES + ("unscoped",), 0)
    j = 0
    for path, start, dur in leaf_ops(ops):  # ordered by start, as the spans are
        while j < len(spans) and spans[j][1] <= start:
            j += 1
        if j < len(spans) and spans[j][0] <= start and start + dur <= spans[j][1]:
            sums[innermost_scope(path) or "unscoped"] += dur
    return sums, len(runs)


def busy_intervals(ops: list) -> list:
    """Merged ``[start, end)`` intervals in which some operation runs."""
    out = []
    for _, start, dur in sorted(ops, key=lambda e: e[1]):
        if out and start <= out[-1][1]:
            if start + dur > out[-1][1]:
                out[-1][1] = start + dur
        else:
            out.append([start, start + dur])
    return out


def self_intervals(host: list) -> list:
    """``[(phase, start, end)]`` pieces in which each phase is the INNERMOST
    one entered (a nested phase cuts its piece out of its parent's), in time
    order. Phases come from one thread, so they nest or follow one another."""
    pieces, stack = [], []  # stack of [phase, cursor, end]

    def close(upto: int) -> None:
        while stack and stack[-1][2] <= upto:
            phase, cursor, end = stack.pop()
            if end > cursor:
                pieces.append((phase, cursor, end))
            if stack:
                stack[-1][1] = max(stack[-1][1], end)

    for phase, _, _, start, dur in sorted(host, key=lambda e: (e[3], -e[4])):
        close(start)
        if stack and start > stack[-1][1]:
            pieces.append((stack[-1][0], stack[-1][1], start))
        if stack:
            stack[-1][1] = start
        stack.append([phase, start, start + dur])
    close(1 << 62)
    return sorted(pieces, key=lambda p: p[1])


def idle_by_phase(ops: list, host: list) -> tuple[dict, int]:
    """Device idle time of the slice (the gaps between busy intervals), split
    by the host phase that was innermost while it passed; a gap that spans
    two phases is split between them, time under no phase is
    ``unattributed`` → ``({phase: ns}, slice ns)``."""
    busy = busy_intervals(ops)
    out = dict.fromkeys(LOOP_PHASES + ("unattributed",), 0)
    if not busy:
        return out, 0
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    pieces = self_intervals(host)
    j = 0
    for g0, g1 in gaps:
        covered = 0
        while j < len(pieces) and pieces[j][2] <= g0:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][1] < g1:
            lo, hi = max(g0, pieces[k][1]), min(g1, pieces[k][2])
            if hi > lo:
                out[pieces[k][0]] += hi - lo
                covered += hi - lo
            k += 1
        out["unattributed"] += (g1 - g0) - covered
    return out, busy[-1][1] - busy[0][0]


def run_of_seq(modules: list, host: list) -> int | None:
    """The constant ``run_id − seq`` of the slice, read off the readbacks: the
    module run that ended last before a ``readback`` did is that entry's
    program, if its name is the one the entry's ``kind`` runs. The commonest
    difference wins; None when no readback names its program. Modules
    without a ``run_id`` are numbered in start order."""
    ordered = sorted(modules, key=lambda m: m[1])
    runs = [(m[3] if m[3] is not None else i, m[0], m[1] + m[2]) for i, m in enumerate(ordered)]
    votes: dict[int, int] = {}
    for phase, seq, kind, start, dur in host:
        if phase != "readback" or seq is None:
            continue
        done = [r for r in runs if r[2] <= start + dur]
        if done and done[-1][1] == KIND_PROGRAM.get(kind):
            diff = done[-1][0] - seq
            votes[diff] = votes.get(diff, 0) + 1
    return max(votes, key=votes.get) if votes else None


def prefill_queue_ns(modules: list, host: list) -> list[int]:
    """For every whole prefill program run of the slice whose dispatch is in
    the slice too: device start − end of the paired ``dispatch_prefill``
    annotation (how long the program sat in the device's queue)."""
    diff = run_of_seq(modules, host)
    if diff is None:
        return []
    ordered = sorted(modules, key=lambda m: m[1])
    by_run = {(m[3] if m[3] is not None else i): m for i, m in enumerate(ordered)}
    whole = {id(m) for m in ordered[1:-1]}
    out = []
    for phase, seq, kind, start, dur in host:
        if phase != "dispatch_prefill" or seq is None:
            continue
        m = by_run.get(seq + diff)
        if m is not None and id(m) in whole and m[0] == KIND_PROGRAM.get(kind):
            out.append(m[1] - (start + dur))
    return out
