"""Stable names inside the program (PR 26): ``tracing.SCOPES`` on the served
programs' phases, ``tracing.LoopPhases`` on the device loop's host phases.

A scope is metadata — the token-exactness suites (test_engine, test_paged,
test_models) pass unedited and prove it changes no value; here the names
themselves are checked: in the lowered and compiled text of the programs an
engine serves, in the profiler's trace of a real (CPU) session, and in
``/metrics``.

The fake-clock, lint and span-clock tests are ``quick``; the ones that build a
tiny engine ride the unit tier."""

import collections
import glob
import os
import re
import threading

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.container import new_mock_container
from gofr_tpu.models import llama
from gofr_tpu.models.llama import LlamaConfig
from gofr_tpu.tpu import executor
from gofr_tpu.tpu.engine import GenerateEngine
from gofr_tpu.tracing import (
    LOOP_PHASES,
    SCOPES,
    LoopPhases,
    MemoryExporter,
    Tracer,
    scope,
    scoped,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny()
    return cfg, llama.init(cfg, jax.random.key(0))


def _engine(tiny, container=None, **kw):
    cfg, params = tiny
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_prefill_batch", 2)
    kw.setdefault("prefill_buckets", [8, 16])
    return GenerateEngine(llama, cfg, params, container or new_mock_container(), **kw)


# -- device side: the scope tuple ----------------------------------------------


def _lowered_programs(eng) -> dict:
    """The engine's own jitted programs, lowered with warm-up's shapes."""
    n, k, lb = eng.num_slots, eng.decode_chunk, eng.prefill_buckets[0]
    w = executor.prefill_cols(eng)
    wt = eng.pages_per_slot if eng.kv_layout == "paged" else 0
    args = (eng.params, eng._base_key, eng.cache)
    out = {
        "prefill": eng._prefill_sample.lower(*args, jnp.zeros((2, lb + w + 3), jnp.int32)),
        "decode": eng._decode_chunk.lower(
            *args, k, jnp.zeros((5 + wt, n), jnp.int32), eng._zero_carry()),
    }
    if eng._chunked_ok:
        out["chunk"] = eng._chunk_prefill.lower(*args, jnp.zeros((1, lb + w + 4), jnp.int32))
    return out


def _scopes_in(text: str) -> set:
    """Scope names that occur as a path component of some operation's name."""
    return {name for name in SCOPES if re.search(rf'[/"]{name}[/"]', text)}


@pytest.mark.parametrize("layout", ["paged", "slot"])
def test_served_programs_carry_every_scope(tiny, layout):
    kw = {"kv_layout": "paged", "page_size": 8} if layout == "paged" else {"kv_layout": "slot"}
    eng = _engine(tiny, **kw)
    try:
        lowered = _lowered_programs(eng)
    finally:
        eng.stop()
    # the slot layout attends its cache in place: it has no gather
    everything = set(SCOPES) - ({"kv_gather"} if layout == "slot" else set())
    found = {name: _scopes_in(low.as_text(debug_info=True)) for name, low in lowered.items()}
    assert found["decode"] == everything, sorted(everything - found["decode"])
    # whole-prompt prefill attends prompt-locally; the chunked program gathers
    assert found["prefill"] == set(SCOPES) - {"kv_gather"}
    if "chunk" in found:
        assert found["prefill"] | found["chunk"] == everything
    # and the names survive compilation: they ride op_name in the optimized HLO
    compiled = lowered["decode"].compile().as_text()
    in_op_names = set()
    for op_name in re.findall(r'op_name="([^"]*)"', compiled):
        in_op_names |= set(op_name.split("/")) & set(SCOPES)
    assert in_op_names == everything, sorted(everything - in_op_names)


@pytest.mark.quick
def test_scope_refuses_a_name_outside_the_tuple():
    with pytest.raises(ValueError, match="unknown program scope"):
        scope("attn")
    with pytest.raises(ValueError):
        scoped("attn")(lambda x: x)(1)

    @scoped("mlp")
    def f(x, *, y=2):
        """doc"""
        return x * y

    assert f(3, y=4) == 12 and f.__name__ == "f" and f.__doc__ == "doc"


@pytest.mark.quick
def test_names_are_spelled_in_one_place_only():
    """``named_scope`` / ``TraceAnnotation`` appear nowhere in the package but
    in ``gofr_tpu/tracing``; every Pallas kernel's ``name=`` is a scope."""
    offenders, kernel_names = [], []
    for folder, _, files in os.walk(os.path.join(REPO, "gofr_tpu")):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(folder, f)
            with open(path, encoding="utf-8") as fh:
                text = fh.read()
            rel = os.path.relpath(path, REPO)
            if re.search(r"named_scope|TraceAnnotation", text) and rel != "gofr_tpu/tracing/__init__.py":
                offenders.append(rel)
            if "/ops/pallas/" in path:
                calls = text.count("pl.pallas_call(")
                names = re.findall(r'^\s+name="([a-z_]+)",', text, re.M)
                assert len(names) == calls, (rel, names, calls)
                kernel_names += names
    assert not offenders, offenders
    assert kernel_names and set(kernel_names) <= set(SCOPES), kernel_names


# -- host side: the phase helper -----------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Sink:
    def __init__(self):
        self.values = collections.defaultdict(float)

    def increment_counter(self, name, value, **labels):
        self.values[(name, labels["phase"])] += value


@pytest.mark.quick
def test_phase_self_time_nesting_exception_and_counts():
    clock = _Clock()
    lp = LoopPhases(clock=clock)
    with lp.phase("admit"):
        clock.t += 1.0
        with lp.phase("dispatch_prefill", seq=7, kind="prefill"):
            clock.t += 2.0
            with lp.phase("readback", seq=6):  # a depth-1 drain inside a dispatch
                clock.t += 4.0
        clock.t += 8.0
        with lp.phase("dispatch_prefill", seq=8) as ph:
            ph.tag(kind="chunk")
            clock.t += 16.0
    # self time: children subtracted at every level, nothing counted twice
    assert lp.seconds["admit"] == 9.0
    assert lp.seconds["dispatch_prefill"] == 18.0
    assert lp.seconds["readback"] == 4.0
    assert sum(lp.seconds.values()) == clock.t == 31.0
    assert (lp.counts["admit"], lp.counts["dispatch_prefill"], lp.counts["readback"]) == (1, 2, 1)

    # an exception inside a phase: the time and the count still land, the
    # stack unwinds, the parent's self time excludes the child
    with pytest.raises(RuntimeError):
        with lp.phase("control"):
            clock.t += 1.0
            with lp.phase("fold", seq=1):
                clock.t += 2.0
                raise RuntimeError("boom")
    assert lp.seconds["control"] == 1.0 and lp.seconds["fold"] == 2.0
    assert lp.counts["control"] == lp.counts["fold"] == 1 and not lp._stack

    # a phase that found nothing to do keeps its time and drops its count
    with lp.phase("dispatch_decode") as ph:
        clock.t += 0.5
        ph.uncount()
    assert lp.seconds["dispatch_decode"] == 0.5 and lp.counts["dispatch_decode"] == 0

    with pytest.raises(ValueError, match="unknown loop phase"):
        lp.phase("sampling")
    assert set(lp.seconds) == set(lp.counts) == set(LOOP_PHASES)


@pytest.mark.quick
def test_phase_flush_exports_deltas_once():
    clock = _Clock()
    lp = LoopPhases(clock=clock)
    sink = _Sink()
    with lp.phase("wait_work"):
        clock.t += 0.2
    lp.flush(sink)
    lp.flush(sink)  # nothing new: adds nothing
    assert sink.values[("app_tpu_loop_phase_seconds_total", "wait_work")] == pytest.approx(0.2)
    assert sink.values[("app_tpu_loop_phase_total", "wait_work")] == 1
    with lp.phase("wait_work"):
        clock.t += 0.3
    lp.flush(sink)
    assert sink.values[("app_tpu_loop_phase_seconds_total", "wait_work")] == pytest.approx(0.5)
    assert sink.values[("app_tpu_loop_phase_total", "wait_work")] == 2
    assert sink.values[("app_tpu_loop_phase_total", "fold")] == 0


# -- the device loop under load --------------------------------------------------


class _SpyQueue(collections.deque):
    """Drop-in ``_dq`` that remembers every entry dispatched."""

    def __init__(self):
        super().__init__()
        self.seen = []

    def append(self, entry):
        self.seen.append(entry)
        super().append(entry)


def _drive(eng, prompts, new_tokens=6):
    outs, threads = {}, []
    for i, p in enumerate(prompts):
        t = threading.Thread(target=lambda i=i, p=p: outs.__setitem__(
            i, eng.generate(p, max_new_tokens=new_tokens, timeout=120)))
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=180)
    assert len(outs) == len(prompts)
    return outs


def test_loop_phase_counters_match_the_dispatches(tiny):
    c = new_mock_container()
    c.tracer = Tracer(MemoryExporter())
    eng = _engine(tiny, c, kv_layout="paged", page_size=8)
    c.register_engine("lm", eng)
    spy = _SpyQueue()
    eng._dq = spy
    try:
        # five prompts over four slots, one of them longer than the largest
        # bucket (chunked prefill): every kind of dispatch rides the queue
        _drive(eng, [[1, 2, 3], [4, 5, 6, 7], [8, 9], list(range(1, 21)), [3, 1, 4, 1, 5]])
    finally:
        eng.stop()
    kinds = collections.Counter(e[0] for e in spy.seen)
    assert kinds["plain"] >= 2 and kinds["prefill"] >= 1 and kinds["chunk"] >= 2
    # every entry carries its dispatch sequence number, 1, 2, 3, ... in order
    assert [e[7] for e in spy.seen] == list(range(1, len(spy.seen) + 1))

    text = c.metrics.expose_text()  # the scrape flushes the engine's phases
    total = c.metrics.get("app_tpu_loop_phase_total")
    secs = c.metrics.get("app_tpu_loop_phase_seconds_total")
    assert total.value(phase="dispatch_decode") == kinds["plain"]
    assert total.value(phase="dispatch_prefill") == kinds["prefill"] + kinds["chunk"]
    assert total.value(phase="readback") == total.value(phase="fold") == len(spy.seen)
    assert total.value(phase="wait_work") >= 1 and total.value(phase="control") >= 1
    for phase in LOOP_PHASES:
        assert secs.value(phase=phase) >= 0.0
        assert f'app_tpu_loop_phase_total{{phase="{phase}"}}' in text
    assert secs.value(phase="readback") > 0 and secs.value(phase="fold") > 0
    assert eng._phases.counts["dispatch_decode"] == kinds["plain"] and not eng._phases._stack

    # the request spans are joined to the loop, not doubled: engine.prefill
    # carries the sequence number of the dispatch that prefilled it
    prefill_seqs = {e[7] for e in spy.seen if e[0] in ("prefill", "chunk")}
    spans = c.tracer._exporter.by_name("engine.prefill")
    assert len(spans) == 5
    assert all(s.attributes["step.seq"] in prefill_seqs for s in spans)


def test_profiler_session_shows_loop_phases_with_their_seq(tiny, tmp_path):
    """A real ``jax.profiler`` session (CPU backend, the benchmark runner's
    options): the loop's phases are events named ``loop.<phase>`` on a host
    plane, on the loop thread's line, with ``seq`` and ``kind`` as stats —
    and a dispatch, its readback and its fold share the number."""
    from jax.profiler import ProfileData

    eng = _engine(tiny, kv_layout="paged", page_size=8)
    try:
        eng.generate([9, 9, 9], max_new_tokens=2, timeout=120)  # compile outside the session
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            _drive(eng, [[1, 2, 3], [4, 5, 6, 7]], new_tokens=10)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert files
    found = collections.defaultdict(list)  # name -> [(plane, line, stats)]
    for plane in ProfileData.from_file(files[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("loop."):
                    found[ev.name].append((plane.name, line.name, dict(ev.stats)))
    assert set(found) <= {"loop." + p for p in LOOP_PHASES}
    for phase in ("admit", "dispatch_prefill", "dispatch_decode", "readback", "fold"):
        assert found["loop." + phase], (phase, sorted(found))
    where = {(plane, line) for events in found.values() for plane, line, _ in events}
    assert len(where) == 1 and next(iter(where))[0].startswith("/host:"), where
    by_seq = collections.defaultdict(set)
    for name in ("loop.dispatch_prefill", "loop.dispatch_decode", "loop.readback", "loop.fold"):
        for _, _, stats in found[name]:
            if "seq" in stats:  # a decode attempt with no lane dispatched nothing
                by_seq[int(stats["seq"])].add(name)
    whole = [names for names in by_seq.values() if len(names) == 3]
    assert len(whole) >= 3, dict(by_seq)
    for names in whole:
        assert {"loop.readback", "loop.fold"} < names
    kinds = {stats.get("kind") for _, _, stats in found["loop.readback"]}
    assert {"prefill", "plain"} <= kinds


# -- spans: one epoch reading, then the monotonic clock ---------------------------


@pytest.mark.quick
def test_span_duration_survives_a_wall_clock_step(monkeypatch):
    import time as time_module

    from gofr_tpu import tracing

    wall = [1_000_000.0]
    mono = [50_000_000_000]
    monkeypatch.setattr(tracing.time, "time", lambda: wall[0])
    monkeypatch.setattr(tracing.time, "perf_counter_ns", lambda: mono[0])
    t = Tracer(MemoryExporter())
    span = t.start_span("s", set_current=False)
    wall[0] -= 3600.0            # the wall clock steps back an hour mid-span
    mono[0] += 250_000_000       # 0.25 s really passed
    span.add_event("e")
    mono[0] += 250_000_000
    span.finish()
    assert span.start == 1_000_000.0
    assert span.end == pytest.approx(1_000_000.5)
    assert span.duration_us == 500_000
    assert span.events[0]["ts"] == pytest.approx(1_000_000.25)
    assert time_module is tracing.time  # the patch is undone with the fixture
