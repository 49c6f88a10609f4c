"""Elastic fleet subsystem (gofr_tpu/fleet; docs/parallelism.md):

- quick tier: chaos-injection determinism, Supervisor restart policy
  (including the sliding-window restart budget and FleetSupervisor's
  fleet-wide generation monotonicity, on fake clocks/procs), and the
  fleet announce channel's frame/handshake/rejoin protocol — pure
  host-side code, no jax;
- process tier: 4 REAL processes (1 leader + 3 followers, each with a
  process-local dp:2,tp:2 mesh over 4 virtual CPU devices) serving
  token-exact over the host-side announce channel, and the leader-kill
  drill — chaos kills the leader's device loop mid-generation, the
  engine's supervised restart recovers it, the follower rejoins at a new
  epoch (no exit-17 fleet death), queued requests finish token-exact, and
  health reports DEGRADED exactly during the restart window.
"""

import os
import socket
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from gofr_tpu.fleet import (
    ChannelClosed,
    FleetFollowerChannel,
    FleetLeaderChannel,
    FleetProtocolError,
    FleetSupervisor,
    Supervisor,
    chaos,
)
from gofr_tpu.logging import MockLogger
from gofr_tpu.tpu.lockstep import TAG_EPOCH, TAG_PREFILL


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- chaos layer (quick) ---------------------------------------------------------


@pytest.mark.quick
class TestChaos:
    def test_disabled_is_zero_cost(self, monkeypatch):
        monkeypatch.delenv("GOFR_CHAOS", raising=False)
        chaos.reset()
        assert not chaos.active()
        assert chaos.hook("engine.step") is None  # call sites bind None → one branch
        assert chaos.fire("engine.step") is False
        chaos.reset()

    def test_nth_every_after_gates(self):
        with chaos.override("a:drop,nth=2;b:drop,every=3;c:drop,after=2"):
            a = chaos.hook("a")
            assert [a() for _ in range(4)] == [False, True, False, False]
            b = chaos.hook("b")
            assert [b() for _ in range(7)] == [False, False, True, False, False, True, False]
            c = chaos.hook("c")
            assert [c() for _ in range(5)] == [False, False, True, True, True]

    def test_at_step_fires_once_on_state(self):
        with chaos.override("engine.step:drop,at_step=5"):
            h = chaos.hook("engine.step")
            assert not h(step=1) and not h(step=4)
            assert h(step=7)       # first time the counter reaches the gate
            assert not h(step=8)   # once only
            assert not h(step=5)

    def test_raise_action_and_fire(self):
        with chaos.override("pubsub.commit:raise,nth=1"):
            with pytest.raises(chaos.ChaosFault):
                chaos.fire("pubsub.commit", topic="orders")
            assert chaos.fire("pubsub.commit") is False  # nth=1 consumed

    def test_seeded_probability_is_replayable(self):
        def schedule(seed):
            with chaos.override("x:drop,p=0.5", seed=seed):
                h = chaos.hook("x")
                return [h() for _ in range(32)]

        assert schedule(7) == schedule(7)
        assert schedule(7) != schedule(8)  # 2^-32 false-failure odds

    def test_delay_returns_false(self):
        with chaos.override("y:delay,ms=1"):
            t0 = time.monotonic()
            assert chaos.fire("y") is False
            assert time.monotonic() - t0 >= 0.001

    def test_hold_waits_for_latch(self, tmp_path):
        latch = tmp_path / "go"
        latch.write_text("")
        with chaos.override(f"z:hold,file={latch}"):
            assert chaos.fire("z") is False  # latch exists: no wait

    def test_override_restores(self):
        chaos.reset()
        with chaos.override("q:drop"):
            assert chaos.active()
        assert not chaos.active()


# -- supervisor (quick) ----------------------------------------------------------


class TestSupervisor:
    # not quick: spawns (tiny) real subprocesses — the quick tier's
    # no-process rule (docs/testing.md) stays honest
    @staticmethod
    def _spawn_codes(codes, seen):
        def spawn(gen):
            seen.append(gen)
            return subprocess.Popen(
                [sys.executable, "-c", f"import sys; sys.exit({codes[gen]})"])

        return spawn

    def test_exit17_restarts_into_rejoin_then_clean(self):
        seen: list = []
        sup = Supervisor(self._spawn_codes([17, 5, 0], seen), name="t",
                         max_restarts=5, backoff_s=0.01, logger=MockLogger())
        assert sup.run() == 0
        assert seen == [0, 1, 2]       # exit 17 AND the crash both restarted
        assert sup.restarts == 2 and sup.generation == 2

    def test_budget_exhaustion_gives_up(self):
        seen: list = []
        sup = Supervisor(self._spawn_codes([1] * 10, seen), name="t",
                         max_restarts=2, backoff_s=0.01, logger=MockLogger())
        assert sup.run() == 1
        assert seen == [0, 1, 2]  # initial + 2 budgeted restarts, then give up

    def test_restart_policy_hook(self):
        seen: list = []
        sup = Supervisor(self._spawn_codes([3, 0], seen), name="t",
                         max_restarts=5, backoff_s=0.01,
                         restart_on=lambda rc: rc == 17)
        assert sup.run() == 3  # policy: only leader-loss exits restart
        assert seen == [0]

    def test_stop_terminates_child(self):
        def spawn(gen):
            return subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])

        sup = Supervisor(spawn, name="t", backoff_s=0.01)
        t = sup.start()
        time.sleep(0.2)
        sup.stop()
        t.join(timeout=10)
        assert not t.is_alive()


# -- supervisor restart-budget window (quick: fake clocks, fake procs) -----------


class _FakeProc:
    """Popen-shaped stand-in that has already exited with ``rc``."""

    def __init__(self, rc: int):
        self.returncode = rc

    def poll(self):
        return self.returncode

    def wait(self, timeout=None):
        return self.returncode

    def terminate(self):
        pass

    def kill(self):
        pass


@pytest.mark.quick
class TestSupervisorWindow:
    """The restart budget is a TRUE sliding window over crash timestamps
    (a deque pruned to ``window_s``), not a reset-on-gap counter — the
    give-up exists for crash loops, not lifetime fault totals."""

    @staticmethod
    def _drip(codes, gap_s, **kw):
        t = {"now": 0.0}
        seen: list = []

        def spawn(gen):
            t["now"] = gap_s * gen
            seen.append(gen)
            return _FakeProc(codes[gen])

        sup = Supervisor(spawn, name="t", backoff_s=0.001,
                         logger=MockLogger(), now=lambda: t["now"], **kw)
        return sup, seen

    def test_slow_drip_never_exhausts(self):
        # isolated faults 250s apart against a 300s window: no single
        # window ever holds more than 2 crashes, so a budget of 2 is never
        # exhausted — the reset-on-gap counter this replaced accumulated
        # them (each gap < window_s) and gave up on the 3rd drip fault
        sup, seen = self._drip([1, 1, 1, 1, 0], 250.0,
                               max_restarts=2, window_s=300.0)
        assert sup.run() == 0
        assert seen == [0, 1, 2, 3, 4]

    def test_overlapping_windows_counted_exactly(self):
        # crashes at t=0/100/200 overlap pairwise: a 300s window holds all
        # three at once (crash loop — give up after the budgeted 2
        # restarts), while a 150s window holds at most two (keep serving)
        sup, seen = self._drip([1, 1, 1, 1, 0], 100.0,
                               max_restarts=2, window_s=300.0)
        assert sup.run() == 1
        assert seen == [0, 1, 2]
        sup, seen = self._drip([1, 1, 1, 1, 0], 100.0,
                               max_restarts=2, window_s=150.0)
        assert sup.run() == 0
        assert seen == [0, 1, 2, 3, 4]

    def test_restarts_attribute_tracks_window_occupancy(self):
        sup, _ = self._drip([1, 1, 1, 0], 250.0,
                            max_restarts=2, window_s=300.0)
        assert sup.run() == 0
        # last crash (t=500) shares its window only with t=250 — the
        # exported restart count is window occupancy, not a lifetime total
        assert sup.restarts == 2


@pytest.mark.quick
class TestFleetSupervisorGenerations:
    def test_generations_monotonic_under_rapid_kill_rejoin(self):
        """Rapid kill/rejoin across DIFFERENT members: every spawn —
        initial or respawn — draws from ONE fleet-wide counter, so the
        FLEET_EPOCH base derived from it is never reused and is strictly
        increasing per member (the ring's bumped-epoch re-admission gate
        stays sound across members)."""
        import threading as _threading

        lock = _threading.Lock()
        seen: list[tuple[str, int]] = []
        lives = {"a": 3, "b": 3}  # 2 crashes then a clean exit, each

        def spawn_member(name, gen):
            with lock:
                seen.append((name, gen))
                lives[name] -= 1
                rc = 1 if lives[name] > 0 else 0
            return _FakeProc(rc)

        fs = FleetSupervisor(spawn_member, members=["a", "b"],
                             max_restarts=10, backoff_s=0.001,
                             logger=MockLogger())
        threads = fs.start()
        for t in threads.values():
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads.values())
        assert len(seen) == 6
        gens = [g for _, g in seen]
        # unique and gapless from 1: no generation is ever reused, even
        # with both members respawning concurrently
        assert sorted(gens) == list(range(1, 7))
        per: dict[str, list[int]] = {}
        for name, g in seen:
            per.setdefault(name, []).append(g)
        for gs in per.values():
            assert gs == sorted(gs)  # strictly increasing per member
        assert fs.generation == 6


# -- announce channel (quick) ----------------------------------------------------


@pytest.mark.quick
class TestFleetChannel:
    def test_handshake_frames_and_follower_loss(self):
        gauges, counters = {}, {}

        class _Metrics:
            def set_gauge(self, name, value, **kw):
                gauges[name] = value

            def increment_counter(self, name, value=1, **kw):
                counters[name] = counters.get(name, 0) + value

        leader = FleetLeaderChannel(0, fingerprint="fp", host="127.0.0.1",
                                    metrics=_Metrics())
        try:
            fol = FleetFollowerChannel(f"127.0.0.1:{leader.port}", fingerprint="fp",
                                       connect_timeout_s=5, rejoin_timeout_s=2)
            fol.connect()
            leader.wait_ready(1, epoch=0, timeout_s=5)
            assert leader.follower_count() == 1
            h = fol.recv_header()
            assert (int(h[0]), int(h[3])) == (TAG_EPOCH, 0)

            payload = np.arange(12, dtype=np.int32).reshape(3, 4)
            leader.send(np.array([TAG_PREFILL, 4, 3, 0], np.int32), payload)
            h = fol.recv_header()
            assert [int(x) for x in h] == [TAG_PREFILL, 4, 3, 0]
            got = fol.recv_payload((3, 4))
            assert np.array_equal(got, payload)

            # follower dies: a subsequent fan-out drops it (TCP surfaces
            # the peer close on the first send AFTER the RST lands, so the
            # leader may need a couple of sends to observe it) and serving
            # continues
            fol.close()
            deadline = time.monotonic() + 5
            while leader.follower_count() and time.monotonic() < deadline:
                leader.send(np.array([TAG_PREFILL, 4, 3, 0], np.int32), payload)
                time.sleep(0.01)
            assert leader.follower_count() == 0
            # the drop path keeps the active-follower gauge truthful (a
            # for-good loss never reaches an epoch bump to refresh it)
            assert gauges.get("app_fleet_followers") == 0
            assert counters.get("app_fleet_followers_lost_total") == 1
        finally:
            leader.close()

    def test_rejoin_after_leader_restart_bumps_epoch(self):
        port = _free_port()
        leader1 = FleetLeaderChannel(port, fingerprint="fp", host="127.0.0.1")
        fol = FleetFollowerChannel(f"127.0.0.1:{port}", fingerprint="fp",
                                   connect_timeout_s=5, rejoin_timeout_s=10)
        fol.connect()
        leader1.wait_ready(1, epoch=0, timeout_s=5)
        assert int(fol.recv_header()[0]) == TAG_EPOCH
        # leader PROCESS dies and a new one binds the same endpoint. The
        # follower's redial starts first (its abort releases the old
        # connection — with a dead leader process the kernel would have
        # reset it already) and retries until the new leader is up.
        leader1.close()
        import threading

        joined = threading.Thread(target=fol.rejoin, daemon=True)
        joined.start()
        leader2 = FleetLeaderChannel(port, fingerprint="fp", host="127.0.0.1")
        try:
            joined.join(timeout=10)
            assert not joined.is_alive()
            deadline = time.monotonic() + 5
            while not leader2.has_pending() and time.monotonic() < deadline:
                time.sleep(0.01)
            assert leader2.has_pending()
            assert leader2.admit_pending(epoch=1) == 1
            h = fol.recv_header()
            assert (int(h[0]), int(h[3])) == (TAG_EPOCH, 1)  # the new epoch
        finally:
            fol.close()
            leader2.close()

    def test_torn_frame_and_abort_surface_channel_closed(self):
        """Leader death between a frame's header and payload — and the
        watchdog's abort() landing in the same window — must both surface
        the RECOVERABLE ChannelClosed from recv_payload (the follower loop
        discards the torn frame and redials), never some unrelated error
        that would kill the follower instead of rejoining it."""
        from gofr_tpu.fleet.channel import _HEADER, _NBYTES

        leader = FleetLeaderChannel(0, fingerprint="fp", host="127.0.0.1")
        try:
            fol = FleetFollowerChannel(f"127.0.0.1:{leader.port}",
                                       fingerprint="fp",
                                       connect_timeout_s=5, rejoin_timeout_s=1)
            fol.connect()
            leader.wait_ready(1, epoch=0, timeout_s=5)
            assert int(fol.recv_header()[0]) == TAG_EPOCH
            # header + nbytes promise 48 payload bytes that never arrive
            with leader._lock:
                conn = leader._active[0]
            conn.sendall(_HEADER.pack(TAG_PREFILL, 4, 3, 0) + _NBYTES.pack(48))
            assert [int(x) for x in fol.recv_header()] == [TAG_PREFILL, 4, 3, 0]
            leader.reset_connections()  # leader dies mid-frame
            with pytest.raises(ChannelClosed):
                fol.recv_payload((3, 4))
            # watchdog abort() between header and payload: same signal,
            # not an AttributeError on the nulled socket
            fol.abort()
            with pytest.raises(ChannelClosed):
                fol.recv_payload((3, 4))
            fol.close()
        finally:
            leader.close()

    def test_fingerprint_mismatch_rejected_at_the_door(self):
        leader = FleetLeaderChannel(0, fingerprint="right", host="127.0.0.1")
        try:
            fol = FleetFollowerChannel(f"127.0.0.1:{leader.port}",
                                       fingerprint="wrong",
                                       connect_timeout_s=5, rejoin_timeout_s=1)
            fol.connect()
            with pytest.raises(FleetProtocolError, match="fingerprint"):
                fol.recv_header()
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if leader.follower_count() == 0 and not leader.has_pending():
                    break
                time.sleep(0.01)
            assert not leader.has_pending()  # never parked in pending
        finally:
            leader.close()


# -- 4-process token-exact serving ----------------------------------------------

_FLEET_WORKER = textwrap.dedent("""
    import faulthandler, os, sys
    faulthandler.dump_traceback_later(400, exit=True)  # post-mortem on hang
    sys.path.insert(0, {repo!r})
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import ModelSpec
    from gofr_tpu.testutil import greedy_reference, tiny_f32_llama
    from gofr_tpu.tpu.engine import build_engine

    role = sys.argv[1]
    conf = {{"TPU_MESH": "dp:2,tp:2", "ENGINE_KV_LAYOUT": "slot"}}
    if role == "leader":
        conf["FLEET_LISTEN"] = "{port}"
        conf["FLEET_FOLLOWERS"] = "3"
    else:
        conf["FLEET_LEADER"] = "127.0.0.1:{port}"
    c = new_mock_container(conf)
    cfg, _ = tiny_f32_llama()
    eng = build_engine(ModelSpec("llama", cfg, task="generate"), c, seed=3,
                       slots=2, max_len=64, max_prefill_batch=1,
                       prefill_buckets=[16], decode_chunk=4)
    assert eng.lockstep_role == role, eng.lockstep_role

    if role == "leader":
        assert eng._ls.follower_count() == 3
        from gofr_tpu.models import llama
        ref = greedy_reference(cfg, llama.init(cfg, jax.random.key(3)))
        prompts = [[3, 7, 11], [5, 2, 9, 4]]
        try:
            outs = [eng.generate(p, max_new_tokens=5, timeout=240) for p in prompts]
            for p, o in zip(prompts, outs):
                want = ref(p, 5)
                assert o["tokens"] == want, (o["tokens"], want)
            prev = np.asarray(eng._prev_last).tolist()
        finally:
            eng.stop()
        print("FLEET_PREV", prev, flush=True)
        print("FLEET_OK leader served token-exact to 3 followers, epoch",
              eng._ls.epoch, flush=True)
    else:
        eng.serve_follower()
        assert eng._prev_last is not None, "follower never replayed a live decode"
        print("FLEET_PREV", np.asarray(eng._prev_last).tolist(), flush=True)
        print("FLEET_OK follower drained and exited on stop", flush=True)
""")


def _run_workers(src: str, roles: list[str], tmp_path, timeout: float,
                 extra_env: dict | None = None):
    env = dict(os.environ)  # JAX_PLATFORMS=cpu (conftest pin) is inherited
    env.pop("XLA_FLAGS", None)
    env.pop("GOFR_CHAOS", None)
    logs = [open(tmp_path / f"{role}{i}.log", "w+") for i, role in enumerate(roles)]
    procs = []
    for i, role in enumerate(roles):
        penv = dict(env)
        if extra_env and role in extra_env:
            penv.update(extra_env[role])
        procs.append(subprocess.Popen([sys.executable, "-c", src, role],
                                      env=penv, stdout=logs[i],
                                      stderr=subprocess.STDOUT, text=True))

    def slurp():
        out = []
        for f in logs:
            f.flush()
            f.seek(0)
            out.append(f.read())
        return out

    try:
        for p in procs:
            p.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(f"fleet workers hung:\n{chr(10).join(slurp())[-6000:]}")
    finally:
        outs = slurp()
        for f in logs:
            f.close()
    return procs, outs


def test_four_process_fleet_token_exact(tmp_path):
    """1 leader + 3 followers, each a full replica on its own 2-axis
    (dp:2,tp:2) virtual-CPU mesh, lockstepped over the host-side announce
    channel: the leader serves token-exact vs the single-device greedy
    reference, every follower replays to the IDENTICAL device-resident
    decode carry, and stop() drains the whole fleet cleanly."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = _FLEET_WORKER.format(repo=repo, port=_free_port())
    roles = ["leader", "follower", "follower", "follower"]
    procs, outs = _run_workers(src, roles, tmp_path, timeout=420)
    for role, p, out in zip(roles, procs, outs):
        assert p.returncode == 0, f"{role} failed:\n{out[-4000:]}"
        assert "FLEET_OK" in out, out[-4000:]
    prevs = {out.split("FLEET_PREV", 1)[1].splitlines()[0].strip() for out in outs}
    assert len(prevs) == 1, f"decode carries diverged across the fleet: {prevs}"


# -- leader kill → supervised restart → epoch rejoin -----------------------------

_KILL_LEADER = textwrap.dedent("""
    import faulthandler, os, sys, time
    faulthandler.dump_traceback_later(400, exit=True)  # post-mortem on hang
    sys.path.insert(0, {repo!r})
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import ModelSpec
    from gofr_tpu.testutil import greedy_reference, tiny_f32_llama
    from gofr_tpu.tpu.engine import build_engine

    role = sys.argv[1]
    conf = {{"TPU_MESH": "dp:2,tp:2", "ENGINE_KV_LAYOUT": "slot"}}
    if role == "leader":
        conf["FLEET_LISTEN"] = "{port}"
        conf["FLEET_FOLLOWERS"] = "1"
    else:
        conf["FLEET_LEADER"] = "127.0.0.1:{port}"
    c = new_mock_container(conf)
    cfg, _ = tiny_f32_llama()
    eng = build_engine(ModelSpec("llama", cfg, task="generate"), c, seed=3,
                       slots=1, max_len=64, max_prefill_batch=1,
                       prefill_buckets=[16], decode_chunk=4)

    if role == "leader":
        # GOFR_CHAOS (set by the test): kill the device loop once the step
        # counter reaches 2 — request A is mid-decode (mid-STREAM), B and C
        # are still queued — and HOLD the restart window open on the latch
        # file so DEGRADED health and the follower rejoin are observable
        # without any sleep-based synchronization.
        prompts = [[3, 7, 11], [5, 2, 9, 4], [2, 8]]
        reqs = [eng.submit(p, max_new_tokens=6, timeout=240) for p in prompts]

        deadline = time.monotonic() + 120
        while eng.health_check()["status"] != "DEGRADED":
            assert time.monotonic() < deadline, "never saw DEGRADED"
            time.sleep(0.005)
        # the follower saw our dropped connection and redialed into the
        # pending set; only THEN release the restart hold, so the first
        # loop iteration of the new life admits it at the bumped epoch
        while not eng._ls.has_pending():
            assert time.monotonic() < deadline, "follower never redialed"
            time.sleep(0.005)
        assert eng.health_check()["status"] == "DEGRADED"
        open({latch!r}, "w").close()

        # in-flight request A rode the killed device loop: fails fast with
        # the injected fault; queued B and C survive the restart and
        # complete token-exact at the NEW epoch
        try:
            reqs[0].result(240)
            raise AssertionError("in-flight request survived the device-loop kill")
        except RuntimeError as e:
            assert type(e).__name__ == "ChaosFault", repr(e)
        from gofr_tpu.models import llama
        ref = greedy_reference(cfg, llama.init(cfg, jax.random.key(3)))
        for p, r in zip(prompts[1:], reqs[1:]):
            out = r.result(240)
            want = ref(p, 6)
            assert out["tokens"] == want, (out["tokens"], want)
        assert eng.health_check()["status"] == "UP"  # DEGRADED only during the window
        assert eng._ls.epoch == 1, eng._ls.epoch     # exactly one rejoin bump
        assert eng._ls.follower_count() == 1
        prev = np.asarray(eng._prev_last).tolist()
        eng.stop()
        print("FLEET_PREV", prev, flush=True)
        print("KILL_OK leader restarted, follower rejoined at epoch 1, "
              "queued requests finished token-exact", flush=True)
    else:
        eng.serve_follower()  # EOF -> redial -> TAG_EPOCH 1 -> replay -> STOP
        assert eng._prev_last is not None, "follower never replayed a live decode"
        print("FLEET_PREV", np.asarray(eng._prev_last).tolist(), flush=True)
        print("KILL_OK follower rejoined and drained cleanly", flush=True)
""")


def test_leader_kill_supervised_restart_epoch_rejoin(tmp_path):
    """The VERDICT #4 drill, as a test: chaos kills the leader's device
    loop mid-generation under load. The supervised restart recovers it —
    in-flight work fails fast, queued work survives and completes
    token-exact, health is DEGRADED exactly during the (latch-held)
    restart window — and the follower rejoins at a new fleet epoch instead
    of exiting 17 (no fleet death)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    latch = str(tmp_path / "release-restart")
    src = _KILL_LEADER.format(repo=repo, port=_free_port(), latch=latch)
    chaos_env = {"leader": {"GOFR_CHAOS":
                            f"engine.step:raise,at_step=2;engine.restart:hold,file={latch},timeout=120"}}
    procs, outs = _run_workers(src, ["leader", "follower"], tmp_path,
                               timeout=420, extra_env=chaos_env)
    for role, p, out in zip(["leader", "follower"], procs, outs):
        assert p.returncode == 0, f"{role} failed (exit {p.returncode}):\n{out[-4000:]}"
        assert "KILL_OK" in out, out[-4000:]
    prevs = {out.split("FLEET_PREV", 1)[1].splitlines()[0].strip() for out in outs}
    assert len(prevs) == 1, f"decode carries diverged after the rejoin: {prevs}"
