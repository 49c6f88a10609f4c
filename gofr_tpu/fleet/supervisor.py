"""Process supervision for fleet members: the restart runbook as code.

One process per chip: the supervising parent MUST NOT have initialised a
JAX backend. A process that has touched JAX holds its chips, and a spawned
member that needs them then fails or hangs — so run the supervisor from a
process that only imports this (import-light) module, and let each member
be the first and only process to touch its devices.

A fleet process dies in one of three recognizable ways:

- **exit 0** — clean stop (leader announced STOP, follower drained): done;
- **exit 17** (``LOCKSTEP_EXIT_CODE``) — the follower's liveness watchdog
  declared the leader dead after the rejoin deadline. The right response
  is a *restart into rejoin-wait*: the fresh follower redials the
  leader's endpoint and joins the next epoch (fleet/channel.py);
- **any other code / signal** — a crash (device fault, OOM, kill -9).
  Restart with the same config; the channel handshake plus the epoch
  bump make the rejoin safe without state transfer (weights re-init from
  the same seed; the announce channel is the only state that matters).

The restart budget is windowed like the engine's device-loop budget:
only crashes inside the trailing ``window_s`` count against it — the
give-up exists for crash LOOPS, not lifetime fault totals. The budget is
a true sliding window (a deque of crash timestamps pruned to the
window), not a reset-on-gap counter: a slow steady drip of isolated
faults each a few minutes apart never exhausts it, because no single
window ever holds more than a couple of crashes. Each respawn passes
the new generation number to ``spawn`` so the process can derive its
base fleet epoch (``FLEET_EPOCH``) and logs can correlate lives;
:class:`FleetSupervisor` hands all members ONE shared monotonic counter
so rapid kill/rejoin across different members can never reuse an epoch.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Iterable

# == tpu.lockstep.LOCKSTEP_EXIT_CODE; literal here because lockstep imports
# the fleet package (chaos hooks) and this module must stay import-light
LOCKSTEP_EXIT_CODE = 17


class Supervisor:
    """Supervise ONE fleet process (leader or follower).

    ``spawn(generation) -> Popen-like`` starts the process; the returned
    object needs ``wait(timeout)``/``poll()``/``returncode`` and
    ``terminate()``/``kill()`` (subprocess.Popen satisfies all of it).
    ``run()`` blocks until the process exits cleanly, the budget is
    exhausted, or ``stop()`` is called; it returns the last exit code.
    """

    def __init__(self, spawn: Callable[[int], Any], *, name: str = "fleet-proc",
                 max_restarts: int = 3, window_s: float = 300.0,
                 backoff_s: float = 0.5, backoff_cap_s: float = 10.0,
                 restart_on: Callable[[int], bool] | None = None,
                 logger=None, metrics=None,
                 now: Callable[[], float] = time.monotonic):
        self.spawn = spawn
        self.name = name
        self.max_restarts = max_restarts
        self.window_s = window_s
        self.backoff_s = backoff_s
        self.backoff_cap_s = backoff_cap_s
        self.restart_on = restart_on or (lambda rc: rc != 0)
        self.logger = logger
        self.metrics = metrics
        self.generation = 0
        self.restarts = 0
        self.proc: Any = None
        self._stop = threading.Event()
        self._now = now
        self._crashes: collections.deque[float] = collections.deque()

    def _crashes_in_window(self, now: float) -> int:
        """Record a crash at ``now`` and return how many crashes the
        trailing window holds (sliding, not reset-on-gap: see module doc)."""
        self._crashes.append(now)
        while self._crashes and now - self._crashes[0] > self.window_s:
            self._crashes.popleft()
        return len(self._crashes)

    # -- lifecycle -------------------------------------------------------------

    def _log(self, msg: str) -> None:
        if self.logger is not None:
            self.logger.warn(f"supervisor[{self.name}]: {msg}")

    def run(self) -> int:
        """The watchdog→restart→warm-rejoin loop. Returns the supervised
        process's final exit code (0 = clean stop)."""
        self.proc = self.spawn(self.generation)
        while True:
            while self.proc.poll() is None:
                if self._stop.wait(0.05):
                    self._log("stop requested; terminating child")
                    self.proc.terminate()
                    try:
                        self.proc.wait(timeout=10)
                    except Exception:  # noqa: BLE001 - unkillable child
                        self.proc.kill()
                        self.proc.wait()
                    return int(self.proc.returncode or 0)
            rc = int(self.proc.returncode)
            if rc == 0:
                self._log(f"generation {self.generation} exited cleanly")
                return 0
            if not self.restart_on(rc):
                self._log(f"generation {self.generation} exited {rc}; policy says no restart")
                return rc
            in_window = self._crashes_in_window(self._now())
            if in_window > self.max_restarts:
                self._log(
                    f"generation {self.generation} exited {rc}; restart budget "
                    f"({self.max_restarts} within {self.window_s:.0f}s) exhausted — giving up")
                return rc
            self.restarts = in_window
            why = ("liveness watchdog: leader presumed dead — restarting into rejoin-wait"
                   if rc == LOCKSTEP_EXIT_CODE else f"crash (exit {rc})")
            delay = min(self.backoff_s * (2 ** (self.restarts - 1)), self.backoff_cap_s)
            self._log(
                f"generation {self.generation} died: {why}; restart "
                f"{self.restarts}/{self.max_restarts} in {delay:.2f}s")
            if self._stop.wait(delay):
                return rc
            if self.metrics is not None:
                self.metrics.increment_counter("app_fleet_supervisor_restarts_total", 1)
            self.generation += 1
            self.proc = self.spawn(self.generation)

    def start(self) -> threading.Thread:
        """Run the supervision loop on a daemon thread (the in-app shape);
        the returned thread's liveness is the fleet member's liveness."""
        t = threading.Thread(target=self.run, name=f"supervisor-{self.name}", daemon=True)
        t.start()
        return t

    def stop(self) -> None:
        self._stop.set()


class FleetSupervisor:
    """Supervise N named fleet members with ONE shared, lock-protected,
    strictly monotonic generation counter. Every spawn — initial bring-up
    or post-crash respawn of ANY member — draws the next number, so the
    ``FLEET_EPOCH`` base derived from it can never be reused even under
    rapid kill/rejoin across different members (two replicas crashing in
    the same window get distinct, ordered generations; a ring re-admission
    gate keyed on a strictly bumped epoch therefore always passes for the
    newer life and never for a stale one).

    ``spawn_member(name, generation) -> Popen-like`` starts one member;
    the autoscaler drives the same protocol at a higher level, and each
    member individually keeps the windowed restart budget of
    :class:`Supervisor`.
    """

    def __init__(self, spawn_member: Callable[[str, int], Any], *,
                 members: Iterable[str], logger=None, metrics=None,
                 now: Callable[[], float] = time.monotonic, **supervisor_kw):
        self.spawn_member = spawn_member
        self._lock = threading.Lock()
        self._generation = 0
        self.members: dict[str, Supervisor] = {}
        for name in members:
            self.members[name] = Supervisor(
                self._spawner(name), name=name, logger=logger,
                metrics=metrics, now=now, **supervisor_kw)

    @property
    def generation(self) -> int:
        with self._lock:
            return self._generation

    def next_generation(self) -> int:
        with self._lock:
            self._generation += 1
            return self._generation

    def _spawner(self, name: str) -> Callable[[int], Any]:
        # the member Supervisor's own per-life counter is ignored on
        # purpose: the FLEET-WIDE counter is the monotonicity contract
        def spawn(_local_generation: int) -> Any:
            return self.spawn_member(name, self.next_generation())
        return spawn

    def start(self) -> dict[str, threading.Thread]:
        return {name: sup.start() for name, sup in self.members.items()}

    def stop(self) -> None:
        for sup in self.members.values():
            sup.stop()
