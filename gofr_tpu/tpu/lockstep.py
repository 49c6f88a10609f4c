"""Multi-host lockstep serving (SURVEY §5.8; BASELINE row 4; docs/parallelism.md).

A multi-host mesh (v5e-64 = 16 hosts) runs ONE XLA program per step across
every process: all processes must issue identical jit calls in identical
order, but only one process sees the request queue. The reference scales
out with NCCL/MPI ranks driven by an external launcher; the TPU-native
analog is leader/follower lockstep:

- the LEADER (process 0) runs the full GenerateEngine — admission, EDF
  planning, slot bookkeeping, streaming — and before every device call
  announces a small header (program tag + shape/flag fields + the fleet
  epoch) followed by the packed host inputs;
- FOLLOWERS run ``engine.serve_follower()``: receive the header,
  reconstruct the packed array's shape from it plus engine config,
  receive the payload, and issue the SAME jit call. Their host loops never
  touch requests; their contribution is their device shards inside the
  sharded programs (collective transport) or their replica's compute
  (fleet transport).

Announces ride one of two transports (fleet/channel.py):

- ``CollectiveChannel`` — ``multihost_utils.broadcast_one_to_all``, a
  device collective on the same ICI/DCN fabric as the programs. v1
  semantics: membership is frozen, any process death is group-fatal
  (an announce IS a collective; a dead peer wedges everyone inside it),
  so lockstep engines on this transport never restart (max_restarts=0)
  and recovery is full group re-formation by the supervisor.
- ``FleetLeaderChannel``/``FleetFollowerChannel`` — host-side TCP.
  Followers execute the announced programs on their own process-local
  mesh, so membership changes are handled OUTSIDE the compiled programs:
  announces carry a fleet EPOCH, and any membership event (leader
  device-loop restart, follower rejoin after leader or follower death)
  is a step-boundary epoch bump — the leader requeues slot-resident work
  (preemption-by-recompute), resets per-epoch device state (cache,
  carries), and frames TAG_EPOCH; every follower resets the same state
  on receipt. Weights and jit caches stay resident across epochs — the
  warm-rejoin that makes a leader restart a blip instead of fleet death.

Determinism makes replay sound: params come from the same seed, the RNG
step rides inside the packed inputs, decode-chunk length is static, and
the device-resident ``prev_last`` carry is reproduced on every process
because each executes the same calls in the same order (warmup decode
announces a live=0 flag so followers mirror the leader's no-carry warmup
exactly). Epoch resets restore the virgin-cache state on every process
at the same stream position, so the property holds across rejoins.

Liveness: with ``LOCKSTEP_DEADLINE_S`` set, the leader heartbeats
(TAG_NOP) from its device thread when idle for deadline/3, and each
follower arms a watchdog. On a silent leader the collective-transport
follower hard-exits ``LOCKSTEP_EXIT_CODE`` (it is wedged inside a dead
collective; only the process supervisor can recover it) while the fleet
follower aborts its socket and redials — only a failed redial within
``FLEET_REJOIN_S`` escalates to the same exit code. Exit 17 is therefore
the one cross-transport signal meaning "leader presumed dead"; the
fleet.Supervisor restarts on it into rejoin-wait.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from gofr_tpu.fleet import chaos

TAG_STOP = 0
TAG_PREFILL = 1
TAG_CHUNK = 2
TAG_DECODE = 3
TAG_SPEC = 4
TAG_NOP = 5    # leader heartbeat: header only, no payload, no device call
TAG_EPOCH = 6  # fleet epoch bump: reset per-epoch state, adopt header epoch

LOCKSTEP_EXIT_CODE = 17  # follower watchdog hard-exit (distinct for supervisors)

_HEADER_LEN = 4  # (tag, a, b, epoch)


class LockstepLeader:
    """Leader-side announcer: one (header, payload) frame per device call,
    fanned out over the configured channel. Called from the engine's
    device thread only (interleaving a second announce stream would
    corrupt the replay order on every transport)."""

    def __init__(self, channel=None, epoch: int = 0):
        from gofr_tpu.fleet.channel import CollectiveChannel

        self.channel = channel if channel is not None else CollectiveChannel()
        self.epoch = int(epoch)
        self._stopped = False
        self._last_announce = time.monotonic()
        # chaos point "lockstep.announce": drop (skip the frame) or delay
        # (sleep before sending) — the fault schedule the follower-liveness
        # and desync tests inject (fleet/chaos.py; zero-cost when unarmed)
        self._chaos = chaos.hook("lockstep.announce")

    @property
    def supports_rejoin(self) -> bool:
        return bool(getattr(self.channel, "supports_rejoin", False))

    def _header(self, tag: int, a: int, b: int) -> np.ndarray:
        return np.array([tag, a, b, self.epoch], np.int32)

    def announce(self, tag: int, a: int, b: int, packed: np.ndarray) -> None:
        if self._chaos is not None and self._chaos(tag=tag):
            return  # injected drop: the frame never reaches the fabric
        self.channel.send(self._header(tag, a, b), np.asarray(packed, np.int32))
        self._last_announce = time.monotonic()

    def maybe_heartbeat(self, interval_s: float) -> None:
        """NOP-header frame when idle past ``interval_s`` — resets the
        followers' liveness watchdogs. Device-thread only."""
        if not self._stopped and time.monotonic() - self._last_announce > interval_s:
            self.channel.send(self._header(TAG_NOP, 0, 0), None)
            self._last_announce = time.monotonic()

    def stop(self) -> None:
        if not self._stopped:
            self._stopped = True
            self.channel.send(self._header(TAG_STOP, 0, 0), None)
            close = getattr(self.channel, "close", None)
            if close is not None:
                close()

    # -- fleet membership (no-ops on the collective transport) -----------------

    def has_pending(self) -> bool:
        fn = getattr(self.channel, "has_pending", None)
        return bool(fn()) if fn is not None else False

    def admit_pending(self) -> int:
        """Bump the fleet epoch and admit every pending follower (plus
        re-frame the epoch to survivors). The caller — the engine's device
        loop, at a step boundary — has already reset its per-epoch state."""
        self.epoch += 1
        return self.channel.admit_pending(self.epoch)

    def wait_ready(self, expect: int, timeout_s: float) -> None:
        self.channel.wait_ready(expect, self.epoch, timeout_s)

    def reset_connections(self) -> None:
        """Leader device-loop restart: a crash mid-``send`` may have left a
        partial frame on some wire; close every follower socket so each
        redials into pending and rejoins at the bumped epoch with framing
        intact (fleet/channel.py)."""
        fn = getattr(self.channel, "reset_connections", None)
        if fn is not None:
            fn()

    def follower_count(self) -> int:
        fn = getattr(self.channel, "follower_count", None)
        return int(fn()) if fn is not None else 0


class LockstepFollower:
    """Follower-side replay loop bound to an engine built with the same
    config/seed. Blocks in the channel until the leader's next frame;
    returns when the leader announces stop.

    ``deadline_s > 0`` arms a liveness watchdog. Over the collective
    transport a silent leader means this process is wedged inside a dead
    collective — ``on_timeout`` (default: CRITICAL log +
    ``os._exit(LOCKSTEP_EXIT_CODE)``) is the only release. Over a fleet
    channel the watchdog aborts the socket instead, which surfaces as
    ``ChannelClosed`` on the replay thread and enters the REJOIN path:
    redial the leader endpoint until ``rejoin_timeout_s``; only redial
    failure escalates to ``on_timeout``."""

    def __init__(self, engine, deadline_s: float = 0.0, on_timeout=None,
                 channel=None):
        from gofr_tpu.fleet.channel import CollectiveChannel

        self.engine = engine
        self.channel = channel if channel is not None else CollectiveChannel()
        self.deadline_s = float(deadline_s)
        self.epoch: int | None = None  # adopted from the first frame
        self.rejoins = 0
        self._on_timeout = on_timeout or self._default_timeout
        self._progress_at = time.monotonic()
        self._done = threading.Event()

    def _default_timeout(self) -> None:  # pragma: no cover - exits hard
        self.engine.logger.fatal(
            f"lockstep follower: no leader traffic for {self.deadline_s:.0f}s "
            f"— leader presumed dead; exiting {LOCKSTEP_EXIT_CODE}"
        )
        os._exit(LOCKSTEP_EXIT_CODE)

    def _watch(self) -> None:
        step = min(1.0, self.deadline_s / 4)
        while not self._done.wait(step):
            if time.monotonic() - self._progress_at > self.deadline_s:
                if getattr(self.channel, "supports_rejoin", False):
                    # not wedged — a socket abort unblocks the replay
                    # thread into the rejoin path; the deadline clock
                    # restarts there, so this fires at most once per
                    # silence window
                    self._progress_at = time.monotonic()
                    self.channel.abort()
                else:
                    self._on_timeout()
                    return

    def _recv(self, shape) -> np.ndarray:
        return self.channel.recv_payload(shape)

    def run(self) -> None:
        import jax.numpy as jnp

        from gofr_tpu.ops.pallas import platform_hint

        if self.deadline_s > 0:
            threading.Thread(target=self._watch, name="lockstep-watchdog",
                             daemon=True).start()
        try:
            self._run_inner(jnp, platform_hint)
        finally:
            self._done.set()

    def _rejoin(self) -> None:
        """Leader went away (EOF / reset / watchdog abort): redial into the
        leader endpoint until the channel's rejoin deadline. State is NOT
        reset here — the admitting leader's TAG_EPOCH frame is the one
        reset trigger, so a reconnect and a survivor epoch bump take the
        identical path."""
        from gofr_tpu.fleet.channel import ChannelClosed

        eng = self.engine
        eng.logger.warn("fleet follower: leader connection lost; redialing")
        try:
            self.channel.rejoin()
        except ChannelClosed:
            self._on_timeout()
            raise  # on_timeout overrides that don't exit: surface the loss
        self.rejoins += 1
        eng.metrics.increment_counter("app_fleet_rejoins_total", 1)
        self._progress_at = time.monotonic()

    def _run_inner(self, jnp, platform_hint) -> None:
        from gofr_tpu.fleet.channel import ChannelClosed

        eng = self.engine
        from gofr_tpu.tpu.executor import prefill_cols
        w = prefill_cols(eng)  # paged+spec carries a trailing slot-id column
        wt = eng.pages_per_slot if eng.kv_layout == "paged" else 0
        n, k = eng.num_slots, eng.decode_chunk
        rejoinable = getattr(self.channel, "supports_rejoin", False)
        # same platform pin as the leader's device thread (engine._run):
        # first-time traces here must resolve kernels for the engine's
        # actual backend, not whatever jax.default_backend() guesses —
        # plus the engine's paged KV write-mode pin (engine._trace_scope)
        with platform_hint(getattr(eng.tpu, "platform", None)), eng._trace_scope():
            while True:
                # the WHOLE frame — header, payload, and dispatch — rides
                # inside the rejoin guard: leader death surfaces as
                # ChannelClosed from the payload recv just as readily as
                # from the header (mid-frame crash, or the watchdog abort()
                # landing between the two). The torn frame is discarded and
                # the reconnect restarts at a frame boundary (channel.py
                # framing note); engine state is safe because every branch
                # receives its full payload before touching it.
                try:
                    header = self.channel.recv_header()
                    self._progress_at = time.monotonic()
                    tag, a, b, epoch = (int(header[0]), int(header[1]),
                                        int(header[2]), int(header[3]))
                    if tag == TAG_STOP:
                        return
                    if tag == TAG_NOP:
                        continue  # leader heartbeat: liveness only
                    if tag == TAG_EPOCH:
                        # membership changed at a step boundary: reset per-epoch
                        # device state (virgin cache, no carries) exactly like
                        # the leader just did, then replay the new epoch's
                        # stream. Weights and jit caches stay warm.
                        if self.epoch is not None and epoch != self.epoch:
                            eng.logger.warn(
                                f"fleet follower: epoch {self.epoch} -> {epoch}; "
                                "resetting per-epoch device state")
                        eng._reset_device_state()
                        self.epoch = epoch
                        eng.metrics.set_gauge("app_fleet_epoch", epoch)
                        continue
                    if self.epoch is None:
                        self.epoch = epoch  # collective transport: no TAG_EPOCH
                    elif epoch != self.epoch:
                        raise RuntimeError(
                            f"lockstep follower: frame epoch {epoch} != current "
                            f"{self.epoch} (protocol corruption)")
                    if tag == TAG_PREFILL:
                        packed = self._recv((b, a + w + 3))
                        toks, eng.cache = eng._prefill_sample(
                            eng.params, eng._base_key, eng.cache, jnp.asarray(packed))
                        del toks
                    elif tag == TAG_CHUNK:
                        packed = self._recv((1, a + w + 4))
                        toks, eng.cache = eng._chunk_prefill(
                            eng.params, eng._base_key, eng.cache, jnp.asarray(packed))
                        del toks
                    elif tag == TAG_DECODE:
                        live = bool(a)  # 0 = leader warmup: zeros carry, no store
                        packed = self._recv((5 + wt, n))
                        prev = eng._prev_last if live else None
                        if prev is None:
                            prev = eng._zero_carry()
                        out, last, eng.cache = eng._decode_chunk(
                            eng.params, eng._base_key, eng.cache, k,
                            jnp.asarray(packed), prev)
                        if live:
                            eng._prev_last = last
                        del out
                    elif tag == TAG_SPEC:
                        # unified spec frame: a is the packed row count ([5, n]
                        # slot, [5 + pages_per_slot, n] paged), b is the live
                        # flag (0 = leader warmup: zeros carry in, output carry
                        # DISCARDED — the TAG_DECODE convention). Live rounds
                        # reproduce the device-resident (token, hlen) carry
                        # because every process executes the same deterministic
                        # calls in order (sampled requests too: the rng step
                        # rides the payload and folds into the same
                        # config-seeded base key). Paged spec rounds stay
                        # pipelined under lockstep: the leader announces at
                        # dispatch time, so frame order on the wire is the
                        # leader's _dq dispatch order and the carry chain
                        # matches step for step.
                        live = bool(b)
                        packed = self._recv((a, n))
                        carry = eng._spec_carry if live else None
                        if carry is None:
                            carry = (eng._zero_carry(), eng._zero_carry())
                        toks, accs, eng.cache, carry_out = eng._spec_chunk_fn(
                            eng.params, eng._base_key, eng.cache, k,
                            jnp.asarray(packed), carry)
                        if live:
                            eng._spec_carry = carry_out
                        del toks, accs
                    else:  # pragma: no cover - protocol corruption
                        raise RuntimeError(f"lockstep follower: unknown tag {tag}")
                except ChannelClosed:
                    if not rejoinable:
                        raise
                    self._rejoin()
                    continue
