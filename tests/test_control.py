"""Online step-controller suite (gofr_tpu.control): the perf plane
closed into actuation.

Three layers, cheapest first:

- **hysteresis core** — the flap-damping state machine extracted from the
  PR 11 ScaleDecider, driven entirely on fake clocks (sustain, per-
  direction cooldown anchored on executed actions, band behavior, stale
  freeze), plus the structural proof that ScaleDecider now delegates to
  the SAME machine instead of a parallel reimplementation.
- **controller units** — StepController with injected windows/clock/
  knobs: the try→judge→commit trial loop, worsening-move revert with
  doubling backoff, a→b→a oscillation freeze, lockstep stand-down,
  evidence starvation accumulating across ticks, and the
  pin persistence (versioned JSON, corrupt file tolerance, read-merge-
  write preserving foreign keys, resume-from-pin on restart).
- **engine seams** — the live-knob contract on a real (tiny, CPU)
  engine: request_knobs clamps to the boot envelope, spec_tokens swaps
  the per-g compiled handle, and — the drill that matters — flipping
  every knob MID-STREAM never changes a single emitted token versus an
  untouched engine, because knobs only move work placement, never the
  sampled distribution. CONTROL_ENABLE=0 constructs no controller at
  all (the quality-plane off-path discipline).

A metric-registration lint rides along (satellite): every literal metric
name the package records must be registered somewhere, so a typo'd
increment_counter can no longer vanish into the registry's silent-drop
path.
"""

import json
import re
import pathlib

import pytest

from gofr_tpu.control.controller import (
    ControlPolicy,
    FORMAT_VERSION,
    KnobSpec,
    StepController,
    entry_key,
)
from gofr_tpu.control.hysteresis import HysteresisGate

pytestmark = pytest.mark.quick

REPO = pathlib.Path(__file__).resolve().parents[1]


# -- hysteresis core -----------------------------------------------------------


def make_gate(**kw):
    kw.setdefault("sustain_s", 2.0)
    kw.setdefault("idle_s", 4.0)
    kw.setdefault("cooldown_hot_s", 3.0)
    kw.setdefault("cooldown_calm_s", 5.0)
    kw.setdefault("stale_s", 60.0)
    return HysteresisGate(**kw)


class TestHysteresisGate:
    def test_hot_requires_sustain(self):
        g = make_gate()
        assert g.decide(hot=True, calm=False, now=0.0) == "hold"
        assert g.decide(hot=True, calm=False, now=1.9) == "hold"
        assert g.decide(hot=True, calm=False, now=2.0) == "hot"

    def test_blip_resets_the_streak(self):
        g = make_gate()
        g.decide(hot=True, calm=False, now=0.0)
        # one calm reading restarts the pressure clock
        g.decide(hot=False, calm=True, now=1.0)
        assert g.decide(hot=True, calm=False, now=2.0) == "hold"
        assert g.decide(hot=True, calm=False, now=4.0) == "hot"

    def test_band_accumulates_neither(self):
        g = make_gate()
        g.decide(hot=True, calm=False, now=0.0)
        g.decide(hot=False, calm=False, now=1.0)  # inside the band
        # pressure restarted: 2s of fresh sustain needed again
        assert g.decide(hot=True, calm=False, now=2.5) == "hold"

    def test_cooldown_anchors_on_note_action(self):
        g = make_gate()
        g.decide(hot=True, calm=False, now=0.0)
        assert g.decide(hot=True, calm=False, now=2.0) == "hot"
        g.note_action(2.0)
        # sustained again, but inside the 3s cooldown from the ACTION
        assert g.decide(hot=True, calm=False, now=2.5) == "hold"
        assert g.decide(hot=True, calm=False, now=4.9) == "hold"
        assert g.decide(hot=True, calm=False, now=5.0) == "hot"

    def test_calm_uses_idle_and_its_own_cooldown(self):
        g = make_gate()
        g.note_action(0.0)
        g.decide(hot=False, calm=True, now=1.0)
        # idle satisfied at 5.0 but calm cooldown (5s) holds until then too
        assert g.decide(hot=False, calm=True, now=4.9) == "hold"
        assert g.decide(hot=False, calm=True, now=5.0) == "calm"

    def test_stale_freezes_and_forgets(self):
        g = make_gate()
        g.decide(hot=True, calm=False, now=0.0)
        assert g.decide(hot=True, calm=False, now=1.0, age_s=61.0) == "freeze"
        # the streak did not survive the signal gap
        assert g.decide(hot=True, calm=False, now=2.0) == "hold"
        assert g.decide(hot=True, calm=False, now=4.0) == "hot"

    def test_scale_decider_delegates_to_the_shared_gate(self):
        """PR 11's decider and the step controller must damp oscillation
        with ONE state machine — the extraction is only real if the
        decider actually holds a HysteresisGate."""
        from gofr_tpu.fleet.autoscaler import AutoscalePolicy, ScaleDecider

        d = ScaleDecider(AutoscalePolicy())
        assert isinstance(d._gate, HysteresisGate)
        src = (REPO / "gofr_tpu" / "fleet" / "autoscaler.py").read_text()
        assert "HysteresisGate" in src


# -- controller units ----------------------------------------------------------


def win(score: float, *, steps: int = 10, band: str = "hi",
        bubble_ratio: float = 0.0) -> dict:
    """A synthetic band_totals payload whose _summarize score is exactly
    ``score`` (attainment = score / (1 - bubble_ratio), caps = 1)."""
    attain = score / (1.0 - bubble_ratio)
    busy = 1.0
    bubble = bubble_ratio * busy / (1.0 - bubble_ratio)
    return {f"decode|bf16|{band}": {
        "flops": attain, "bytes": 0.0, "device_s": busy,
        "steps": float(steps), "bubble_s": bubble,
        "flops_cap": 1.0, "bytes_cap": 1.0,
    }}


class ValueKnob:
    def __init__(self, name, values, value):
        self.value = value
        self.applied = []
        self.spec = KnobSpec(name, tuple(values), self._read, self._apply)

    def _read(self):
        return self.value

    def _apply(self, v):
        self.value = v
        self.applied.append(v)


def make_ctl(windows, *, knob=None, standdown=None, cache="", **policy_kw):
    """Fake-clock controller: ``windows`` is a list consumed one per tick
    (the last entry repeats); tick it with explicit `now` values."""
    policy_kw.setdefault("interval_s", 1.0)
    policy_kw.setdefault("sustain_s", 1.0)
    policy_kw.setdefault("idle_s", 100.0)
    policy_kw.setdefault("cooldown_s", 1.0)
    policy_kw.setdefault("stale_s", 1000.0)
    policy_kw.setdefault("min_steps", 2)
    policy_kw.setdefault("backoff_s", 10.0)
    policy_kw.setdefault("cache_path", cache)
    policy_kw.setdefault("knobs", ("pipeline_depth",))
    knob = knob or ValueKnob("pipeline_depth", (1, 2, 3), 1)
    seen_since = []

    def window_fn(now, since):
        seen_since.append(since)
        w = windows.pop(0) if len(windows) > 1 else windows[0]
        return w

    ctl = StepController(
        ControlPolicy(**policy_kw), [knob.spec],
        window_fn=window_fn, standdown_fn=standdown, clock=lambda: 0.0)
    ctl._seen_since = seen_since  # test hook
    return ctl, knob


class TestStepController:
    def test_hot_window_proposes_then_commits_and_pins(self):
        ctl, knob = make_ctl([win(0.10), win(0.10), win(0.20)])
        assert ctl.maybe_tick(1.0) is None          # sustain pending
        d = ctl.maybe_tick(2.0)
        assert d.verdict == "try" and d.frm == 1 and d.to == 2
        assert knob.value == 2
        d = ctl.maybe_tick(3.0)                      # judged: 0.20 >= 0.10*1.03
        assert d.verdict == "commit" and d.score > d.baseline
        assert knob.value == 2
        assert ctl.pin_for("pipeline_depth", "hi") == 2

    def test_worsening_move_reverts_and_backs_off(self):
        ctl, knob = make_ctl([win(0.20), win(0.20), win(0.10)])
        ctl.maybe_tick(1.0)
        assert ctl.maybe_tick(2.0).verdict == "try"
        d = ctl.maybe_tick(3.0)                      # 0.10 < 0.20*1.03
        assert d.verdict == "revert"
        assert knob.value == 1                       # restored
        # +1 is backed off for backoff_s and -1 has no neighbor from the
        # bottom value: sustained pressure proposes NOTHING until 13.0
        for t in (5.0, 8.0, 12.0):
            assert ctl.maybe_tick(t) is None
        tries = [d for d in ctl.decisions if d.verdict == "try"]
        assert len(tries) == 1

    def test_backoff_doubles_per_direction(self):
        ctl, knob = make_ctl([win(0.20), win(0.20), win(0.10)],
                             backoff_s=2.0, backoff_cap_s=3.0)
        ctl.maybe_tick(1.0)
        ctl.maybe_tick(2.0)
        assert ctl.maybe_tick(3.0).verdict == "revert"
        until, delay = ctl._backoff[("pipeline_depth", 1)]
        assert until == 5.0 and delay == 3.0         # doubled 2->4, capped 3

    def test_oscillating_commits_freeze_the_knob(self):
        knob = ValueKnob("pipeline_depth", (1, 2), 1)
        # scores climb 4% (> epsilon) every window, so every trial commits:
        # the knob ping-pongs 1->2->1->2 and the a->b->a history freezes it
        scores = [win(0.10 * (1.04 ** i)) for i in range(12)]
        ctl, knob = make_ctl(scores, knob=knob)
        t = 0.0
        while not ctl.oscillating and t < 40.0:
            t += 1.0
            ctl.maybe_tick(t)
        assert ctl.oscillating, "a->b->a commits never flagged"
        assert "pipeline_depth" in ctl._frozen
        commits = [d.to for d in ctl.decisions if d.verdict == "commit"]
        assert commits[-3:] in ([2, 1, 2], [1, 2, 1])
        # frozen: sustained pressure proposes nothing ever again
        n_tries = sum(1 for d in ctl.decisions if d.verdict == "try")
        for dt in range(1, 10):
            ctl.maybe_tick(t + dt)
        assert sum(1 for d in ctl.decisions if d.verdict == "try") == n_tries

    def test_standdown_parks_with_one_decision(self):
        ctl, _ = make_ctl([win(0.10)], standdown=lambda: "lockstep")
        d = ctl.maybe_tick(1.0)
        assert d.verdict == "standdown" and d.reason == "lockstep"
        assert ctl.standdown == "lockstep"
        for t in (2.0, 3.0, 4.0):
            assert ctl.maybe_tick(t) is None         # parked, not spamming
        assert ctl.report()["standdown"] == "lockstep"

    def test_starved_window_accumulates_instead_of_discarding(self):
        ctl, _ = make_ctl([win(0.10, steps=1), win(0.10, steps=1),
                           win(0.10)], min_steps=5)
        assert ctl.maybe_tick(1.0) is None
        assert ctl.maybe_tick(2.0) is None
        ctl.maybe_tick(3.0)
        # every starved tick re-read from the ORIGINAL window start — the
        # evidence accumulated rather than being thrown away per tick
        assert ctl._seen_since == [0.0, 0.0, 0.0]

    def test_trial_without_evidence_reverts_unjudged(self):
        ctl, knob = make_ctl([win(0.10), win(0.10), win(0.10, steps=0)],
                             max_trial_ticks=2)
        ctl.maybe_tick(1.0)
        assert ctl.maybe_tick(2.0).verdict == "try"
        assert ctl.maybe_tick(3.0) is None           # starved tick 1
        d = ctl.maybe_tick(4.0)                      # starved tick 2: abort
        assert d.verdict == "revert" and d.reason == "no-evidence"
        assert knob.value == 1

    def test_persistence_roundtrip_resume_and_foreign_keys(self, tmp_path):
        cache = str(tmp_path / "control.json")
        # a foreign replica's pin must survive our read-merge-write
        foreign = entry_key("pipeline_depth", "hi", kv_dtype="int8",
                            device_kind="v5e", shard="tp4")
        (tmp_path / "control.json").write_text(json.dumps({
            "version": FORMAT_VERSION,
            "entries": {foreign: {"value": 3, "at": 0, "score": 0.5}}}))
        ctl, knob = make_ctl([win(0.10), win(0.10), win(0.20)], cache=cache)
        ctl.maybe_tick(1.0)
        ctl.maybe_tick(2.0)
        assert ctl.maybe_tick(3.0).verdict == "commit"
        data = json.loads((tmp_path / "control.json").read_text())
        assert data["version"] == FORMAT_VERSION
        assert data["entries"][foreign]["value"] == 3   # preserved
        ours = entry_key("pipeline_depth", "hi", kv_dtype="bf16",
                         device_kind="cpu", shard="tp1")
        assert data["entries"][ours]["value"] == 2
        # a fresh controller (restart) resumes from the pin without a trial
        knob2 = ValueKnob("pipeline_depth", (1, 2, 3), 1)
        ctl2, knob2 = make_ctl([win(0.10)], knob=knob2, cache=cache)
        d = ctl2.maybe_tick(1.0)
        assert d.verdict == "resume" and d.to == 2
        assert knob2.value == 2

    def test_corrupt_or_missing_cache_is_empty(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        ctl, _ = make_ctl([win(0.10)], cache=str(bad))
        assert ctl._pins == {}
        wrong = tmp_path / "wrong.json"
        wrong.write_text(json.dumps({"version": 999, "entries": {"k": 1}}))
        ctl, _ = make_ctl([win(0.10)], cache=str(wrong))
        assert ctl._pins == {}

    def test_summarize_math(self):
        ev = StepController._summarize({
            "decode|bf16|hi": {"flops": 3.0, "bytes": 1.0, "device_s": 2.0,
                               "steps": 4, "bubble_s": 0.5,
                               "flops_cap": 10.0, "bytes_cap": 10.0},
            "prefill|bf16|lo": {"flops": 1.0, "bytes": 7.0, "device_s": 0.5,
                                "steps": 2, "bubble_s": 0.0,
                                "flops_cap": 10.0, "bytes_cap": 10.0},
        })
        assert ev["steps"] == 6
        assert ev["attainment"] == pytest.approx(0.4)   # bytes side wins
        assert ev["bubble_ratio"] == pytest.approx(0.5 / 3.0)
        assert ev["band"] == "hi"                       # by device_s share
        assert ev["score"] == pytest.approx(0.4 * (1 - 0.5 / 3.0))

    def test_neighbor_snaps_out_of_range_current(self):
        spec = KnobSpec("k", (16, 32, 64), lambda: 0, lambda v: None)
        # a current value outside the list snaps to the nearest member —
        # the snap IS the proposed move, regardless of direction
        assert spec.neighbor(48, 1) in (32, 64)
        assert spec.neighbor(20, 1) == 16
        assert spec.neighbor(16, -1) is None
        assert spec.neighbor(64, 1) is None
        assert spec.neighbor(32, 1) == 64 and spec.neighbor(32, -1) == 16

    def test_policy_rejects_inverted_bands(self):
        with pytest.raises(ValueError):
            ControlPolicy(bubble_lo=0.5, bubble_hi=0.1)
        with pytest.raises(ValueError):
            ControlPolicy(attain_lo=0.8, attain_hi=0.4)
        with pytest.raises(ValueError):
            ControlPolicy(interval_s=0.0)


# -- band-labeled perf evidence ------------------------------------------------


class TestBandEvidence:
    def test_occupancy_band_edges(self):
        from gofr_tpu.metrics.perf import occupancy_band

        assert occupancy_band(None) == "lo"
        assert occupancy_band(0.0) == "lo"
        assert occupancy_band(0.34) == "lo"
        assert occupancy_band(0.35) == "mid"
        assert occupancy_band(0.69) == "mid"
        assert occupancy_band(0.70) == "hi"
        assert occupancy_band(1.0) == "hi"

    def test_band_totals_keys_and_since_delta(self):
        from gofr_tpu.metrics.perf import CostModel, PerfPlane

        plane = PerfPlane(CostModel(
            n_params=1e6, weight_bytes=2e6, kv_bytes_per_pos=16.0,
            page_bytes=0.0, page_size=0, kv_dtype="bf16", kv_shards=1),
            "cpu", window_s=60.0)
        s1 = plane.step("decode", 1e9, 1e6, 100.0)
        s1.t_ready = 100.5
        plane.note(s1, 100.5, band="hi")
        s2 = plane.step("decode", 2e9, 2e6, 101.0)
        s2.t_ready = 102.0
        plane.note(s2, 102.0, band="lo")
        bands = plane.band_totals(102.0)
        assert set(bands) == {"decode|bf16|hi", "decode|bf16|lo"}
        hi = bands["decode|bf16|hi"]
        assert hi["steps"] == 1 and hi["flops"] == pytest.approx(1e9)
        # capacity denominators priced from the device peaks x busy time
        assert hi["flops_cap"] > 0 and hi["bytes_cap"] > 0
        # `since` restricts to buckets after the cut: only s2 remains
        later = plane.band_totals(102.0, since=101.0)
        assert "decode|bf16|hi" not in later
        assert later["decode|bf16|lo"]["steps"] == 1
        # unbanded window_totals must not double-count the band rows
        kinds = plane.window_totals(102.0)["kinds"]
        assert kinds["decode|bf16"]["steps"] == 2
        assert not any(k.startswith("bd.") for k in kinds)


# -- engine seams (tiny CPU engine) --------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    import jax

    from gofr_tpu.models import LlamaConfig, llama

    cfg = LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.key(7))
    return cfg, params


def make_engine(tiny, **kw):
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.engine import GenerateEngine

    cfg, params = tiny
    conf = kw.pop("conf", None)
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 96)
    kw.setdefault("max_prefill_batch", 2)
    kw.setdefault("decode_chunk", 2)
    kw.setdefault("prefill_buckets", [16, 32, 48])
    return GenerateEngine(llama, cfg, params, new_mock_container(conf), **kw)


class TestEngineKnobSeams:
    def test_apply_clamps_to_boot_envelope(self, tiny):
        eng = make_engine(tiny, pipeline_depth=2, spec_tokens=2,
                          kv_layout="paged", page_size=8)
        try:
            assert eng.knob_vector() == {
                "pipeline_depth": 2, "prefill_chunk": 48,
                "prefill_batch": 2, "spec_tokens": 2}
            eng.request_knobs(pipeline_depth=4, prefill_batch=9,
                              spec_tokens=7)
            eng._apply_pending_knobs()
            # every move clamped to the operator's boot ceiling
            assert eng.pipeline_depth == 2
            assert eng.max_prefill_batch == 2
            assert eng.spec_tokens == 2
            # prefill_chunk snaps DOWN to a bucket member
            eng.request_knobs(prefill_chunk=40)
            eng._apply_pending_knobs()
            assert eng.prefill_chunk == 32
            eng.request_knobs(prefill_chunk=1)
            eng._apply_pending_knobs()
            assert eng.prefill_chunk == 16
            # an unknown knob is logged and dropped, never raises
            eng.request_knobs(warp_factor=9)
            eng._apply_pending_knobs()
        finally:
            eng.stop()

    def test_spec_g_change_swaps_compiled_handle(self, tiny):
        eng = make_engine(tiny, spec_tokens=2, kv_layout="paged",
                          page_size=8)
        try:
            boot_fn = eng._spec_chunk_fn
            assert set(eng._spec_fns) == {2}
            eng.request_knobs(spec_tokens=1)
            eng._apply_pending_knobs()
            assert eng.spec_tokens == 1
            assert eng._spec_chunk_fn is not boot_fn
            assert set(eng._spec_fns) == {1, 2}
            # back up: the per-g map caches, no rebuild
            fn1 = eng._spec_fns[1]
            eng.request_knobs(spec_tokens=2)
            eng._apply_pending_knobs()
            assert eng._spec_chunk_fn is boot_fn
            assert eng._spec_fns[1] is fn1
            # the cache-slack span stays at the BOOT worst case
            assert eng._chunk_span == eng.decode_chunk * 3 + 2
        finally:
            eng.stop()

    def test_spec_knob_rejected_when_spec_off_at_boot(self, tiny):
        eng = make_engine(tiny)
        try:
            eng.request_knobs(spec_tokens=2)
            eng._apply_pending_knobs()  # logged, not applied, not raised
            assert eng.spec_tokens == 0
            assert "spec_tokens" not in eng.knob_vector()
        finally:
            eng.stop()

    def test_control_enable_off_constructs_nothing(self, tiny):
        eng = make_engine(tiny)
        try:
            assert eng._control is None
            rep = eng.control_report()
            assert rep["enabled"] is False and "knobs" in rep
        finally:
            eng.stop()

    def test_control_enable_builds_wired_controller(self, tiny):
        eng = make_engine(tiny, control_enable=True, spec_tokens=2,
                          kv_layout="paged", page_size=8,
                          conf={"CONTROL_INTERVAL_S": "0.5"})
        try:
            assert eng._control is not None
            rep = eng.control_report()
            assert rep["enabled"] is True
            assert set(rep["knobs"]) == {"pipeline_depth", "prefill_chunk",
                                         "prefill_batch", "spec_tokens"}
            # allowed ranges are the boot envelope
            assert rep["knobs"]["pipeline_depth"]["allowed"] == [1, 2]
            assert rep["knobs"]["spec_tokens"]["allowed"] == [1, 2]
            assert rep["knobs"]["prefill_chunk"]["allowed"] == [16, 32, 48]
            assert eng._control.policy.interval_s == 0.5
        finally:
            eng.stop()

    def test_lockstep_role_stands_the_controller_down(self, tiny):
        eng = make_engine(tiny, control_enable=True)
        try:
            assert eng._control is not None
            eng.lockstep_role = "leader"  # runtime role flip
            d = eng._control.maybe_tick(100.0)
            assert d is not None and d.verdict == "standdown"
            assert eng._control.standdown == "lockstep"
        finally:
            eng.lockstep_role = None
            eng.stop()

    def test_midstream_knob_flips_are_token_exact(self, tiny):
        """THE drill: flip every live knob while requests are decoding and
        prefilling; the emitted tokens must be identical to an untouched
        engine's — knobs move work placement, never the distribution."""
        import numpy as np

        rng = np.random.RandomState(3)
        prompts = [rng.randint(1, tiny[0].vocab_size,
                               size=rng.randint(8, 40)).tolist()
                   for _ in range(10)]
        kw = dict(pipeline_depth=2, spec_tokens=2, kv_layout="paged",
                  page_size=8)

        def run(flip: bool) -> list:
            eng = make_engine(tiny, **kw)
            try:
                reqs = []
                for i, p in enumerate(prompts):
                    reqs.append(eng.submit(p, max_new_tokens=12, timeout=60))
                    if flip and i == 2:
                        eng.request_knobs(prefill_chunk=16, spec_tokens=1,
                                          pipeline_depth=1, prefill_batch=1)
                    if flip and i == 6:
                        eng.request_knobs(prefill_chunk=48, spec_tokens=2,
                                          pipeline_depth=2, prefill_batch=2)
                return [r.result(60)["tokens"] for r in reqs]
            finally:
                eng.stop()

        assert run(True) == run(False)


# -- metric-registration lint (satellite) --------------------------------------


def test_every_recorded_metric_literal_is_registered():
    """The registry silently drops writes to unregistered names — correct
    for optional planes, but it means a typo'd name vanishes without a
    trace. Lint the package: every literal name passed to a record call
    must appear in some registration call."""
    record_re = re.compile(
        r"(?:increment_counter|set_gauge|record_histogram)\(\s*\n?\s*"
        r"[\"']([a-z0-9_]+)[\"']")
    register_re = re.compile(
        r"(?:new_counter|new_updown_counter|new_gauge|new_histogram)\(\s*\n?\s*"
        r"[\"']([a-z0-9_]+)[\"']")
    recorded: dict[str, set] = {}
    registered: set = set()
    for p in (REPO / "gofr_tpu").rglob("*.py"):
        text = p.read_text(errors="ignore")
        for m in record_re.finditer(text):
            recorded.setdefault(m.group(1), set()).add(
                str(p.relative_to(REPO)))
        registered.update(m.group(1) for m in register_re.finditer(text))
    assert registered, "registration scan found nothing — regex rotted?"
    missing = {name: sorted(files) for name, files in sorted(recorded.items())
               if name not in registered}
    assert not missing, (
        f"metric names recorded but never registered (writes are silently "
        f"dropped): {missing}")
    # the controller family is registered (satellite acceptance)
    for name in ("app_tpu_control_decisions_total", "app_tpu_control_knob",
                 "app_tpu_control_active"):
        assert name in registered, f"{name} not registered in the container"
