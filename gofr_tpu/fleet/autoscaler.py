"""SLO-driven elastic-fleet control loop: the fleet breathes with traffic
(ROADMAP O2; docs/resilience.md "Autoscaler runbook").

The pieces PRs 7-10 built are composed here into one closed loop:

- **pressure** comes from the PR 9 burn-rate plane (``SLOEngine.pressure()``
  — the worst fast-window burn across every tracked class/objective) and
  the QoS predicted-wait estimator
  (``AdmissionController.max_predicted_wait()``);
- **scale-out** spawns a warm spare through the driver: weights are
  pre-loaded by the replica factory and its programs come from the
  persistent compile cache, so warmup is near-free. Gossip admits the
  spare at a bumped epoch and the PR 7 ring moves only the keys it takes;
- **scale-in** puts a cooling replica into the ``draining`` registry
  state (router/registry.py: out of BOTH rings, keys migrate to ring
  successors), lets its in-flight streams finish via the engine drain
  entrypoint (tpu/engine.py ``GenerateEngine.drain``), requeues its
  queued work onto a peer (:func:`requeue` — the Request OBJECTS move,
  so caller handles, stream queues and deadlines survive), and retires
  it with a terminal DOWN;
- **robustness core**: decisions pass through a pure, fake-clock-testable
  :class:`ScaleDecider` with hysteresis (pressure/calm must be
  *sustained*), per-direction cooldown windows, and a min/max replica
  clamp — the fleet never flaps. Spawn failure retries with backoff
  (chaos point ``autoscale.spawn``); replica death mid-drain aborts the
  drain and re-admits the replica (chaos point ``replica.drain`` fires
  inside the engine drain); stale signals (gossip silence) FREEZE the
  decision loop instead of acting on fiction.

Config (``AutoscalePolicy.from_config``, docs/configs.md):

    FLEET_AUTOSCALE_MIN / _MAX        replica clamp (default 1 / 4)
    FLEET_AUTOSCALE_BURN_OUT          fast-window burn that counts as
                                      pressure (default 2.0; 1.0 = exactly
                                      sustainable burn)
    FLEET_AUTOSCALE_BURN_IN           burn below which the fleet is calm
                                      (default 1.0 — the hysteresis band)
    FLEET_AUTOSCALE_WAIT_OUT_S / _IN_S  predicted-wait pressure/calm bounds
    FLEET_AUTOSCALE_SUSTAIN_S         pressure must persist this long
    FLEET_AUTOSCALE_IDLE_S            calm must persist this long
    FLEET_AUTOSCALE_COOLDOWN_OUT_S / _IN_S  lockout after ANY scale action
    FLEET_AUTOSCALE_STALE_S           signal age that freezes decisions
    FLEET_AUTOSCALE_INTERVAL_S        control-loop tick
    FLEET_AUTOSCALE_SPAWN_RETRIES     spawn attempts before giving up a tick
    FLEET_AUTOSCALE_SPAWN_BACKOFF_S   first retry delay (doubles, capped)
    FLEET_AUTOSCALE_DRAIN_TIMEOUT_S   in-flight settle budget at scale-in

Driver protocol (duck-typed): ``count() -> int``, ``spawn() -> name``,
``pick_victim() -> name | None``, ``drain(name, timeout_s) -> bool``,
``readmit(name)``, ``retire(name)``. :class:`LocalEngineFleet` is the
in-process implementation (one warmed ``GenerateEngine`` per replica,
membership mirrored into a ``ReplicaRegistry`` exactly as gossip would)
used by the diurnal bench and the drill tests; the process tier wires the
same protocol over ``fleet/supervisor.py`` ``FleetSupervisor`` members.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable

from gofr_tpu.fleet import chaos

__all__ = [
    "AutoscalePolicy",
    "Autoscaler",
    "FleetSignals",
    "LocalEngineFleet",
    "ScaleDecider",
    "requeue",
]


@dataclass
class AutoscalePolicy:
    min_replicas: int = 1
    max_replicas: int = 4
    burn_out: float = 2.0          # fast-window burn counting as pressure
    burn_in: float = 1.0           # burn below which the fleet is calm
    wait_out_s: float = 2.0        # predicted wait counting as pressure
    wait_in_s: float = 0.25        # predicted wait below which it's calm
    sustain_s: float = 3.0         # pressure persistence before scale-out
    idle_s: float = 10.0           # calm persistence before scale-in
    cooldown_out_s: float = 5.0    # post-action lockout for scale-out
    cooldown_in_s: float = 20.0    # post-action lockout for scale-in
    stale_s: float = 5.0           # signal age that freezes decisions
    interval_s: float = 1.0        # control-loop tick
    spawn_retries: int = 3
    spawn_backoff_s: float = 0.2
    spawn_backoff_cap_s: float = 2.0
    drain_timeout_s: float = 30.0

    def __post_init__(self) -> None:
        if self.min_replicas < 1:
            raise ValueError("FLEET_AUTOSCALE_MIN must be >= 1")
        if self.max_replicas < self.min_replicas:
            raise ValueError("FLEET_AUTOSCALE_MAX must be >= FLEET_AUTOSCALE_MIN")
        if self.burn_in > self.burn_out or self.wait_in_s > self.wait_out_s:
            # an inverted hysteresis band would make one signal reading
            # simultaneously "pressure" and "calm" — flap by construction
            raise ValueError("scale-in thresholds must sit at or below scale-out")

    @classmethod
    def from_config(cls, conf) -> "AutoscalePolicy":
        return cls(
            min_replicas=conf.get_int("FLEET_AUTOSCALE_MIN", 1),
            max_replicas=conf.get_int("FLEET_AUTOSCALE_MAX", 4),
            burn_out=conf.get_float("FLEET_AUTOSCALE_BURN_OUT", 2.0),
            burn_in=conf.get_float("FLEET_AUTOSCALE_BURN_IN", 1.0),
            wait_out_s=conf.get_float("FLEET_AUTOSCALE_WAIT_OUT_S", 2.0),
            wait_in_s=conf.get_float("FLEET_AUTOSCALE_WAIT_IN_S", 0.25),
            sustain_s=conf.get_float("FLEET_AUTOSCALE_SUSTAIN_S", 3.0),
            idle_s=conf.get_float("FLEET_AUTOSCALE_IDLE_S", 10.0),
            cooldown_out_s=conf.get_float("FLEET_AUTOSCALE_COOLDOWN_OUT_S", 5.0),
            cooldown_in_s=conf.get_float("FLEET_AUTOSCALE_COOLDOWN_IN_S", 20.0),
            stale_s=conf.get_float("FLEET_AUTOSCALE_STALE_S", 5.0),
            interval_s=conf.get_float("FLEET_AUTOSCALE_INTERVAL_S", 1.0),
            spawn_retries=conf.get_int("FLEET_AUTOSCALE_SPAWN_RETRIES", 3),
            spawn_backoff_s=conf.get_float("FLEET_AUTOSCALE_SPAWN_BACKOFF_S", 0.2),
            spawn_backoff_cap_s=conf.get_float(
                "FLEET_AUTOSCALE_SPAWN_BACKOFF_CAP_S", 2.0),
            drain_timeout_s=conf.get_float("FLEET_AUTOSCALE_DRAIN_TIMEOUT_S", 30.0),
        )


@dataclass
class FleetSignals:
    """One pressure reading. ``burn`` is the worst fast-window burn across
    tracked (class, objective) pairs (None = not enough samples anywhere —
    an IDLE fleet, which together with an empty queue reads as calm, so a
    quiet fleet can still scale in); ``predicted_wait_s`` is the worst QoS
    queue-wait estimate across replicas; ``age_s`` is how stale the reading
    is — the *signal plane going silent* (gossip loss, dead scraper) shows
    up here and freezes the decider rather than letting it act on
    fiction."""

    burn: float | None
    predicted_wait_s: float
    replicas: int
    age_s: float = 0.0


class ScaleDecider:
    """Pure decision math — hysteresis + cooldowns + clamp — over an
    explicit ``now`` so the quick-tier units drive it with fake clocks.
    Returns one of ``"out" | "in" | "hold" | "freeze"``; the executor
    reports actions back via :meth:`note_action` so cooldowns anchor on
    what actually happened, not on what was decided.

    The hysteresis/sustain/cooldown/stale core lives in
    :class:`gofr_tpu.control.hysteresis.HysteresisGate` (extracted from
    here so the step-level knob controller damps flapping with the same
    semantics); this class keeps only what is fleet-specific — the
    hot/calm signal classification and the replica clamp."""

    def __init__(self, policy: AutoscalePolicy):
        from gofr_tpu.control.hysteresis import HysteresisGate

        self.policy = policy
        self._gate = HysteresisGate(
            sustain_s=policy.sustain_s, idle_s=policy.idle_s,
            cooldown_hot_s=policy.cooldown_out_s,
            cooldown_calm_s=policy.cooldown_in_s,
            stale_s=policy.stale_s)

    @property
    def _last_action_at(self) -> float:
        # pre-extraction attribute, still read by drills/operators
        return self._gate.last_action_at

    def note_action(self, now: float) -> None:
        self._gate.note_action(now)

    def decide(self, sig: FleetSignals, now: float) -> str:
        p = self.policy
        hot = ((sig.burn is not None and sig.burn >= p.burn_out)
               or sig.predicted_wait_s >= p.wait_out_s)
        calm = ((sig.burn is None or sig.burn <= p.burn_in)
                and sig.predicted_wait_s <= p.wait_in_s)
        verdict = self._gate.decide(hot=hot, calm=calm, now=now,
                                    age_s=sig.age_s)
        if verdict == "freeze":
            return "freeze"
        if verdict == "hot":
            return "out" if sig.replicas < p.max_replicas else "hold"
        if verdict == "calm":
            return "in" if sig.replicas > p.min_replicas else "hold"
        return "hold"


class Autoscaler:
    """The control loop: read signals, decide, execute through the driver.

    ``signals()`` returns a :class:`FleetSignals`; a raising signal source
    is treated exactly like stale gossip (freeze). Every chaos contract
    lives here or one call below:

    - ``autoscale.spawn`` fires before each spawn attempt — an injected
      raise is a spawn failure, answered with bounded retry-with-backoff
      (and the cooldown still engages, so a permanently failing spawn
      can't hammer the driver every tick);
    - ``replica.drain`` fires inside the engine drain path — an injected
      raise (or real replica death mid-drain) aborts the drain and
      RE-ADMITS the victim, leaving the fleet routable and the loop live.
    """

    def __init__(self, driver, policy: AutoscalePolicy | None = None, *,
                 signals: Callable[[], FleetSignals], logger=None,
                 metrics=None, now: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep):
        self.driver = driver
        self.policy = policy or AutoscalePolicy()
        self.decider = ScaleDecider(self.policy)
        self._signals = signals
        self.logger = logger
        self.metrics = metrics
        self._now = now
        self._sleep = sleep
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # -- logging/metrics helpers ----------------------------------------------

    def _log(self, msg: str) -> None:
        if self.logger is not None:
            self.logger.warn(f"autoscaler: {msg}")

    def _count(self, name: str, **labels: Any) -> None:
        if self.metrics is not None:
            self.metrics.increment_counter(name, 1, **labels)

    # -- one tick --------------------------------------------------------------

    def step(self, now: float | None = None) -> str:
        """One decision tick. Safe to call directly (the fake-clock tests
        and the trace-driven bench do); ``run()`` just calls it on a
        timer. Returns the decision taken."""
        t = self._now() if now is None else now
        try:
            sig = self._signals()
        except Exception as e:  # noqa: BLE001 - dead signal source == stale
            self._log(f"signal source failed ({e!r}); freezing decisions")
            sig = FleetSignals(burn=None, predicted_wait_s=0.0,
                               replicas=self.driver.count(),
                               age_s=self.policy.stale_s + 1.0)
        decision = self.decider.decide(sig, t)
        self._count("app_fleet_autoscale_decisions_total", decision=decision)
        if decision == "out":
            self._scale_out()
        elif decision == "in":
            self._scale_in()
        if self.metrics is not None:
            self.metrics.set_gauge("app_fleet_replicas", self.driver.count())
        return decision

    def _scale_out(self) -> str | None:
        p = self.policy
        delay = p.spawn_backoff_s
        try:
            for attempt in range(1, max(1, p.spawn_retries) + 1):
                try:
                    chaos.fire("autoscale.spawn", attempt=attempt)
                    name = self.driver.spawn()
                    self._log(f"scaled out: spawned {name} "
                              f"({self.driver.count()} replicas)")
                    return name
                except Exception as e:  # noqa: BLE001 - injected or real
                    self._count("app_fleet_autoscale_spawn_failures_total")
                    self._log(f"spawn attempt {attempt}/{p.spawn_retries} "
                              f"failed: {e!r}")
                    if attempt >= p.spawn_retries:
                        return None
                    self._sleep(min(delay, p.spawn_backoff_cap_s))
                    delay *= 2
            return None
        finally:
            # cooldown engages whether or not the spawn landed: a driver
            # whose spawns keep failing must not be hammered every tick
            self.decider.note_action(self._now())

    def _scale_in(self) -> str | None:
        victim = self.driver.pick_victim()
        if victim is None:
            return None
        try:
            ok = self.driver.drain(victim, self.policy.drain_timeout_s)
        except Exception as e:  # noqa: BLE001 - chaos or real death mid-drain
            ok = False
            self._log(f"drain of {victim} aborted ({e!r}); re-admitting")
        if not ok:
            self._count("app_fleet_autoscale_drain_aborts_total")
            try:
                self.driver.readmit(victim)
            except Exception as e:  # noqa: BLE001 - replica truly gone
                self._log(f"re-admit of {victim} failed: {e!r}")
            self.decider.note_action(self._now())
            return None
        self.driver.retire(victim)
        self._log(f"scaled in: retired {victim} "
                  f"({self.driver.count()} replicas)")
        self.decider.note_action(self._now())
        return victim

    # -- loop lifecycle --------------------------------------------------------

    def run(self) -> None:
        while not self._stop.is_set():
            try:
                self.step()
            except Exception as e:  # noqa: BLE001 - the loop must stay live
                self._log(f"tick failed: {e!r}")
            self._stop.wait(self.policy.interval_s)

    def start(self) -> "Autoscaler":
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self.run, name="gofr-autoscaler", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=2 * self.policy.interval_s + 1.0)


# -- zero-drop requeue ----------------------------------------------------------


def requeue(requests, peer) -> int:
    """Move drained-but-unserved Requests onto ``peer``'s queue — the
    Request OBJECTS move, so caller handles, stream queues, deadlines and
    accumulated kw (QoS class, preemption history) all survive; tokens
    start flowing from the peer the moment it admits them. Cancelled or
    already-expired requests complete immediately instead of travelling;
    with no peer everything left completes with a retryable 503 (shed, not
    dropped: the caller gets a definitive answer either way)."""
    from gofr_tpu.http.errors import RequestTimeout, ServiceUnavailable

    now = time.monotonic()
    moved = 0
    for req in requests:
        if req.cancelled or req.expired(now):
            req.complete(error=RequestTimeout())
        elif peer is None:
            req.complete(error=ServiceUnavailable(
                "replica drained with no peer to requeue to", retry_after=1.0))
        else:
            peer._queue.put(req)
            moved += 1
    if moved and peer is not None and getattr(peer, "metrics", None) is not None:
        peer.metrics.increment_counter("app_fleet_requeued_total", moved)
    return moved


# -- in-process driver -----------------------------------------------------------


class LocalEngineFleet:
    """In-process replica set: one warmed ``GenerateEngine`` per replica,
    built by ``factory(name)`` (the factory pre-loads weights and warms
    against the persistent compile cache, which is what makes the
    spare *warm*). Membership transitions are mirrored into an optional
    ``ReplicaRegistry`` with the SAME observe() messages gossip would
    carry — UP at a bumped epoch on spawn, ``draining`` during scale-in,
    terminal DOWN on retire — so the PR 7 ring moves keys exactly as it
    would across processes. The process tier swaps this driver for
    ``FleetSupervisor`` members without touching the control loop."""

    def __init__(self, factory: Callable[[str], Any], *, registry=None,
                 name_prefix: str = "rep", logger=None):
        self.factory = factory
        self.registry = registry
        self.logger = logger
        self.name_prefix = name_prefix
        self.replicas: dict[str, Any] = {}
        self._counter = 0
        self._epoch = 0
        self._lock = threading.Lock()

    # -- registry mirroring ----------------------------------------------------

    def _observe(self, name: str, **over: Any) -> None:
        if self.registry is None:
            return
        msg = {"replica": name, "url": f"local://{name}", "status": "UP",
               "epoch": self._epoch, "ts": time.time()}
        msg.update(over)
        self.registry.observe(msg)

    # -- driver protocol -------------------------------------------------------

    def count(self) -> int:
        with self._lock:
            return len(self.replicas)

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self.replicas)

    def engine(self, name: str):
        with self._lock:
            return self.replicas[name]

    def engines(self) -> list[Any]:
        with self._lock:
            return list(self.replicas.values())

    def spawn(self) -> str:
        with self._lock:
            name = f"{self.name_prefix}{self._counter}"
            self._counter += 1
        eng = self.factory(name)  # warm: weights pre-loaded
        with self._lock:
            self.replicas[name] = eng
            self._epoch += 1  # gossip admits the spare at a bumped epoch
        self._observe(name)
        return name

    def pick_victim(self) -> str | None:
        """The cooling replica: the LIGHTEST backlog loses its slot —
        draining it strands the least in-flight work, and ties break to
        the newest name so the fleet contracts in spawn order."""
        with self._lock:
            if len(self.replicas) <= 1:
                return None
            return min(sorted(self.replicas, reverse=True),
                       key=lambda n: self.replicas[n]._backlog())

    def drain(self, name: str, timeout_s: float) -> bool:
        """Registry first (router stops routing new work), then the engine
        drain (in-flight streams finish; queued work comes back), then the
        zero-drop requeue onto a surviving peer."""
        eng = self.engine(name)
        self._observe(name, draining=True)
        pending = eng.drain(timeout_s=timeout_s)  # chaos "replica.drain" fires inside
        peers = [e for n, e in self.replicas.items() if n != name]
        requeue(pending, peers[0] if peers else None)
        return True

    def readmit(self, name: str) -> None:
        """Drain abort (death-mid-drain chaos, or a drain that failed):
        the replica goes back to serving — engine flag cleared, registry
        told it is UP and not draining."""
        eng = self.replicas.get(name)
        if eng is not None and hasattr(eng, "abort_drain"):
            eng.abort_drain()
        self._observe(name, draining=False)

    def retire(self, name: str) -> None:
        with self._lock:
            eng = self.replicas.pop(name, None)
        if eng is not None:
            eng.stop()
        self._observe(name, status="DOWN")

    def stop_all(self) -> None:
        for name in self.names():
            self.retire(name)

    # -- signal helpers --------------------------------------------------------

    def max_predicted_wait(self, qos=None) -> float:
        """Worst queue-wait estimate across replicas: through the bound
        AdmissionController when QoS is wired, else a backlog-only
        estimate (steps of work per lane x a nominal step)."""
        worst = 0.0
        for eng in self.engines():
            ctl = qos or getattr(eng, "qos", None)
            if ctl is not None:
                worst = max(worst, ctl.predicted_wait(eng))
            else:
                lanes = max(1, int(getattr(eng, "num_slots", 1)))
                import math

                worst = max(worst, 0.05 * math.ceil(eng._backlog() / lanes))
        return worst
