"""From a profiler trace to numbers. The reduction is arithmetic over
``(name, start_ns, duration_ns)`` tuples, so it is tested without a chip; the
adaptor around ``jax.profiler.ProfileData`` is the only part that needs JAX.

What a v5e trace looks like (read by hand, PR 24): one plane per chip named
``/device:TPU:<n>``; on it the line ``XLA Modules`` holds one event per run of a
whole compiled program (``jit__decode_chunk(<fingerprint>)``), and ``XLA Ops``
one event per operation inside it."""

from __future__ import annotations

import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def short_op(name: str) -> str:
    """The trace names an operation by its whole HLO line (hundreds of
    characters); keep ``%name opcode result-shape``:
    ``%copy.83 copy bf16[24,440,8,128,128]``."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name
    shape = rhs.lstrip("(").split("{", 1)[0].split(" ", 1)[0]
    opcode = _OPCODE.search(rhs)
    return f"{lhs} {opcode.group(1) if opcode else '?'} {shape}"


def read_xplane(trace_dir: str) -> dict:
    """→ ``{"planes": {plane: {line: [(name, start_ns, dur_ns), ...]}},
    "layout": {plane: {line: n_events}}}`` for the device planes of the newest
    ``.xplane.pb`` under ``trace_dir``. ``layout`` covers every plane, so a
    run can print what the trace held."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    planes, layout = {}, {}
    for plane in data.planes:
        lines = {}
        layout[plane.name] = {}
        keep = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            events = [(short_op(ev.name), int(ev.start_ns), int(ev.duration_ns)) for ev in line.events] \
                if keep and line.name in (MODULE_LINE, OP_LINE) else None
            layout[plane.name][line.name] = len(events) if events is not None else sum(1 for _ in line.events)
            if events is not None:
                lines[line.name] = events
        if keep:
            planes[plane.name] = lines
    return {"planes": planes, "layout": layout}


def busy_union_ns(events) -> int:
    """Nanoseconds covered by at least one event (events may nest or overlap)."""
    total, end = 0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def extent_ns(events) -> tuple[int, int]:
    return min(e[1] for e in events), max(e[1] + e[2] for e in events)


def program(name: str) -> str:
    """``jit__decode_chunk(1234)`` → ``_decode_chunk``: the program's name as
    the source has it, without the fingerprint the compiler appends."""
    name = name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def idle_gaps(modules) -> list[tuple[str, int]]:
    """Idle time between consecutive whole-program events, labelled by the
    programs on either side: ``[("_decode_chunk→_prefill_sample", ns), ...]``.
    Overlapping or abutting programs leave no gap."""
    out = []
    ordered = sorted(modules, key=lambda e: e[1])
    end, last = None, None
    for name, start, dur in ordered:
        if end is not None and start > end:
            out.append((f"{program(last)}->{program(name)}", start - end))
        if end is None or start + dur > end:
            end, last = start + dur, name
    return out


def module_durations_ns(modules, pattern: str) -> list[int]:
    """Durations of the whole-program events whose program name matches."""
    rx = re.compile(pattern)
    return [dur for name, _, dur in modules if rx.search(program(name))]


def median_module_s(reduced: dict, pattern: str) -> float | None:
    """Median device duration, in seconds, of the whole (uncut) events of the
    programs whose name matches; None when the slice holds none."""
    durs = module_durations_ns(reduced["whole_modules"], pattern)
    return statistics.median(durs) / 1e9 if durs else None


def reduce_trace(planes: dict) -> dict | None:
    """Per-chip busy time and window, averaged over the chips that ran
    anything; module events of the busiest chip — all of them (``modules``,
    for gaps) and those the slice's edges did not cut (``whole_modules``, for
    durations: the profiler clips the first and the last program to the
    slice). None when no operation ran on any device."""
    chips = []
    for name, lines in planes.items():
        ops = lines.get(OP_LINE) or lines.get(MODULE_LINE) or []
        if not ops:
            continue
        t0, t1 = extent_ns(ops)
        chips.append({"plane": name, "busy_ns": busy_union_ns(ops), "t0": t0, "t1": t1,
                      "ops": ops, "modules": lines.get(MODULE_LINE, [])})
    if not chips:
        return None
    t0, t1 = min(c["t0"] for c in chips), max(c["t1"] for c in chips)
    main = max(chips, key=lambda c: c["busy_ns"])
    return {
        "busy_s": statistics.fmean(c["busy_ns"] for c in chips) / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "chips": len(chips),
        "modules": main["modules"],
        "whole_modules": sorted(main["modules"], key=lambda e: e[1])[1:-1],
        "ops": main["ops"],
    }


def top_by_total(pairs, n: int = 10) -> list[list]:
    """``[(label, ns), ...]`` → the ``n`` labels with most total time, in
    seconds: ``[[label, seconds], ...]``."""
    totals: dict[str, int] = {}
    for label, ns in pairs:
        totals[label] = totals.get(label, 0) + ns
    return [[k, v / 1e9] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(reduced: dict) -> dict:
    """The ``breakdown`` of a traced run's result line: device operations by
    total time (leaf operations: an event that contains another is a loop or
    a call, and would count its children twice), and idle gaps by the
    programs on either side."""
    return {
        "device_ops": top_by_total((name, dur) for name, _, dur in leaf_events(reduced["ops"])),
        "idle_gaps": top_by_total(idle_gaps(reduced["modules"])),
    }


def leaf_events(events) -> list:
    """Events that contain no later-starting event: ``while`` and ``call``
    wrappers drop out, the operations inside them stay."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    leaves = []
    for i, (name, start, dur) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt[1] < start + dur and nxt[1] + nxt[2] <= start + dur and nxt[2] < dur:
            continue  # the next event lies inside this one
        leaves.append((name, start, dur))
    return leaves
