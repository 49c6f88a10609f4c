"""Continuous-batching serving engines.

This replaces the reference's goroutine-per-request hot path
(`pkg/gofr/handler.go:58-92`, SURVEY.md §3.2) with the TPU-native shape:
handlers *enqueue* work and block on a future; a single device thread
drains the queue, packs requests into fixed-shape batches, and runs one
compiled XLA program per step.

Two engines:

- ``BatchEngine`` — stateless models (embed / classify): drain up to
  max_batch, pad to a (length, batch) bucket, run, scatter results.
- ``GenerateEngine`` — decoder LMs: slot-based continuous batching.
  N decode slots share one SlotKVCache; arriving prompts are prefilled
  (batched per length bucket) into free slots while decode keeps stepping
  the active ones; every step samples all slots in one program. A
  cancelled/timed-out request just frees its slot — its lane computes
  garbage until reused (slot invalidation; SURVEY.md §7 hard part (b)).

Shape discipline: every compiled signature is (batch_bucket, len_bucket)
with power-of-two buckets, so the compile-cache population is tiny and
steady-state serving is 100% cache hits (tracked in app_tpu_* metrics).

Dispatch discipline (round-6 unification): every asynchronous device
call — batched prefill, chunked prefill, decode chunk, slot-layout spec
round — goes through ONE bounded in-flight queue (``_dq``, depth
``pipeline_depth``). Dispatch claims slot/page state and enqueues the
device futures; readback + slot bookkeeping happen at dequeue,
overlapped with younger dispatches, so arriving prompts no longer stall
decoding slots for a prefill round trip (the mixed-arrival device-idle
bubble). Results are folded only if the lane's slot object is unchanged
since dispatch — preemption, cancel, stop(), and crash recovery all ride
that identity check.

Module layout (round-5 split): tpu/programs.py builds the jitted packed
programs and documents every packed layout; tpu/decode.py holds the
decode dispatch paths and the unified queue processing; this file keeps
engine state, admission/prefill, streaming, supervision, and the
build_engine factory.
"""

from __future__ import annotations

import collections
import itertools
import math
import os
import queue
import threading
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from gofr_tpu.fleet import chaos
from gofr_tpu.http.errors import DeadlineExceeded, RequestTimeout, ServiceUnavailable
from gofr_tpu.qos.scheduler import QoSQueue
from gofr_tpu.tracing import LoopPhases, RequestTrace, current_span
from gofr_tpu.tpu.lockstep import TAG_CHUNK, TAG_DECODE, TAG_PREFILL, TAG_SPEC
from gofr_tpu.native import plan_prefill, planner_in_use
from gofr_tpu.models.base import ModelSpec, get_family
from gofr_tpu.parallel import shard_pytree
from gofr_tpu.tpu import executor
from gofr_tpu.tpu.executor import (
    dispatch_decode,
    dispatch_spec,
    dispatch_spec_paged,
    process_decode,
)
from gofr_tpu.tpu.programs import build_programs


def next_bucket(n: int, buckets: list[int]) -> int:
    """Smallest bucket ≥ n (buckets sorted ascending); raises if too long."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"input length {n} exceeds max bucket {buckets[-1]}")


def _pow2_buckets(lo: int, hi: int) -> list[int]:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return out


class EngineClosed(RuntimeError):
    pass


class Request:
    _ids = itertools.count()

    __slots__ = ("id", "inputs", "kw", "enqueued_at", "deadline", "stream_q",
                 "_done", "_result", "_error", "cancelled", "cancel_reason",
                 "_complete_lock", "_callbacks")

    def __init__(self, inputs: Any, kw: dict[str, Any], timeout: float | None, stream: bool = False):
        self.id = next(Request._ids)
        self.inputs = inputs
        self.kw = kw
        self.enqueued_at = time.monotonic()
        self.deadline = self.enqueued_at + timeout if timeout else None
        self.stream_q: queue.SimpleQueue | None = queue.SimpleQueue() if stream else None
        self._done = threading.Event()
        self._complete_lock = threading.Lock()
        self._result: Any = None
        self._error: Exception | None = None
        self._callbacks: list = []
        self.cancelled = False
        self.cancel_reason: str | None = None

    def complete(self, result: Any = None, error: Exception | None = None) -> None:
        # Idempotent, first-writer-wins: stop()'s _fail_all can race a stuck
        # device thread that later produces a result — the late writer must
        # not overwrite the recorded outcome (ADVICE.md round 1).
        with self._complete_lock:
            if self._done.is_set():
                return
            self._result, self._error = result, error
            if self.stream_q is not None:
                self.stream_q.put(None)  # sentinel
            self._done.set()
            callbacks, self._callbacks = self._callbacks, []
        for cb in callbacks:  # outside the lock: callbacks may be arbitrary
            try:
                cb(self)
            except Exception:  # noqa: BLE001 - a bad callback must not kill the engine
                import traceback

                traceback.print_exc()  # surfaced, not swallowed: a dropped
                # callback means some awaiter never resolves

    def add_done_callback(self, fn) -> None:
        """Invoke ``fn(request)`` on completion (immediately if already
        done). This is how asyncio transports await an engine future without
        parking a thread per in-flight request."""
        with self._complete_lock:
            if not self._done.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def outcome(self) -> tuple[Any, Exception | None]:
        """(result, error) once complete — the non-blocking accessor done
        callbacks use, so outcome extraction lives in one place."""
        if not self._done.is_set():
            raise RuntimeError("request is not complete")
        return self._result, self._error

    def cancel(self, reason: str = "cancelled") -> None:
        """Cooperative: flags the request; the device loop reclaims the
        slot/pages at its next bookkeeping pass. ``reason`` lands in the
        flight-recorder timeline (``client_disconnect``, ``timeout``,
        ``hedge_loser``, ...) — first caller wins."""
        if not self.cancelled:
            self.cancel_reason = reason
        self.cancelled = True

    def result(self, timeout: float | None = None) -> Any:
        # Unify on remaining budget: a request constructed with a deadline
        # never blocks past it, even with no explicit wait — previously
        # result() with its own timeout could outlive the deadline by the
        # full wait (the double-timeout bug).
        wait = timeout
        if self.deadline is not None:
            budget = max(0.0, self.deadline - time.monotonic())
            wait = budget if wait is None else min(wait, budget)
        if not self._done.wait(wait):
            self.cancel("timeout")
            raise RequestTimeout()
        if self._error is not None:
            raise self._error
        return self._result

    def expired(self, now: float) -> bool:
        return self.deadline is not None and now > self.deadline


class _EngineBase:
    """Queue + device thread + metrics plumbing shared by both engines."""

    def __init__(self, container, *, default_timeout: float | None = None,
                 max_restarts: int = 3):
        self.container = container
        self.logger = container.logger
        self.metrics = container.metrics
        self.tpu = container.tpu
        self.default_timeout = default_timeout
        # observability plumbing (docs/observability.md): the tracer drives
        # the engine span timeline ONLY while a real exporter is configured
        # (Tracer.enabled guards every span construction); the flight
        # recorder is always on — a bounded ring of completed request
        # timelines + device steps served at /debug/requests, /debug/engine
        self.tracer = getattr(container, "tracer", None)
        self.flight = getattr(container, "flight", None)
        # SLO engine (metrics/slo.py): fed from the exact callsites that
        # record the raw latency histograms, so attainment and the
        # histograms can never disagree about what was measured
        self.slo = getattr(container, "slo", None)
        self._obs_lock = threading.Lock()
        self._inflight_requests = 0
        # QoS-capable queue: pure FIFO (byte-for-byte queue.Queue behavior)
        # until an AdmissionController binds this engine and flips it into
        # weighted-fair priority mode (gofr_tpu.qos; App.enable_qos).
        self._queue: QoSQueue = QoSQueue()
        self.qos = None  # AdmissionController once bound; None = QoS off
        self._thread: threading.Thread | None = None
        # requests currently inside a device call — visible to _fail_all so a
        # wedged step can't strand its batch (their complete is idempotent)
        self._inflight: list[Request] = []
        self._stop = threading.Event()
        self._poisoned = False  # set when a wedged thread failed to join
        # Serializes _pending/_inflight/slot bookkeeping between the device
        # thread and stop()/_fail_all on the caller thread (VERDICT r2 weak
        # #3: unsynchronized list mutation could corrupt state mid-_admit).
        self._state_lock = threading.RLock()
        self._compiled: set[tuple] = set()
        self._startup_error: Exception | None = None
        # Supervision (SURVEY §5.3; reference reconnects SQL in a loop,
        # sql.go:108-133): a crashed device loop restarts with backoff
        # instead of dying permanently. In-flight/slot-resident work fails
        # (its device state is suspect); queued work survives the restart.
        self.max_restarts = max_restarts
        self._restarts = 0
        self._restarting = False
        # scale-in drain (fleet/autoscaler.py): while set, _submit sheds new
        # arrivals with a retryable 503 and the device loop stops claiming
        # slots for queued work — in-flight slot work runs to completion
        self._draining = False
        # crashes further apart than this don't count against the restart
        # budget — the give-up is for crash LOOPS, not lifetime fault totals
        self.restart_window_s = 60.0
        self._last_crash_at = 0.0
        # chaos fault points (fleet/chaos.py; None — one branch — unless a
        # GOFR_CHAOS spec arms them): "engine.step" fires at the top of
        # every device-loop iteration, "engine.restart" inside the restart
        # backoff window (the deterministic latch the DEGRADED-window
        # contract tests pin open)
        self._chaos_step = chaos.hook("engine.step")
        self._chaos_restart = chaos.hook("engine.restart")

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> None:
        if self._poisoned:
            # the wedged device thread from the previous life may still wake;
            # a fresh thread would share (and race) its state
            raise EngineClosed(
                "engine was stopped with a wedged device thread; build a new engine"
            )
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, name=f"gofr-engine-{id(self):x}", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            if self._thread.is_alive():
                # Stuck device step: Request.complete is first-writer-wins,
                # so failing everything now cannot be overwritten by a late
                # result from the wedged thread. Poison the engine so that a
                # LATE-waking loop iteration exits before touching slot/page
                # bookkeeping we are about to mutate here (ADVICE.md round 2).
                self._poisoned = True
                self.logger.warn("engine thread did not stop within 10s; failing in-flight requests")
            self._thread = None
        self._fail_all(EngineClosed("engine stopped"))

    def _fail_all(self, error: Exception) -> None:
        """Fail everything waiting — the queue AND the drained-but-unadmitted
        pending list (GenerateEngine extends this with slot-resident requests)."""
        with self._state_lock:
            while True:
                try:
                    self._queue.get_nowait().complete(error=error)
                except queue.Empty:
                    break
            for req, _ in getattr(self, "_pending", []):
                req.complete(error=error)
            if hasattr(self, "_pending"):
                self._pending = []
            for req, _ in getattr(self, "_pending_long", []):
                req.complete(error=error)
            if hasattr(self, "_pending_long"):
                self._pending_long = []
            for req in self._inflight:
                req.complete(error=error)

    def _crash_recover(self, error: Exception) -> None:
        """Fail work whose device state the crash made suspect (in-flight
        batches; GenerateEngine adds slot-resident requests + page pool
        reset). Queued/pending work survives — it re-plans after restart."""
        with self._state_lock:
            for req in self._inflight:
                req.complete(error=error)
            self._inflight = []

    def _backlog(self) -> int:
        return (self._queue.qsize() + len(getattr(self, "_pending", []))
                + len(getattr(self, "_pending_long", [])))

    def _trace_scope(self):
        """Context every trace-driving section runs under: engines with a
        tp-sharded pool pin its KVShardCtx (ops/paged.kv_shard_scope), so
        every trace this engine drives reads the pool per shard."""
        import contextlib

        ctx = self._kv_shard_ctx() if hasattr(self, "_kv_shard_ctx") else None
        if ctx is None:
            return contextlib.nullcontext()
        from gofr_tpu.ops.paged import kv_shard_scope

        return kv_shard_scope(ctx)

    def _run(self) -> None:
        from gofr_tpu.ops.pallas import platform_hint

        while True:
            try:
                # Pin kernel-backend resolution to where this engine's device
                # actually is (a CPU test mesh under an attached TPU would
                # otherwise trace Pallas kernels it can't lower).
                with platform_hint(getattr(self.tpu, "platform", None)), self._trace_scope():
                    self._loop()
                return  # clean stop
            except Exception as e:  # noqa: BLE001
                self.logger.log_exception(e, "model engine step crashed")
                self._crash_recover(e)
                now = time.monotonic()
                if now - self._last_crash_at > self.restart_window_s:
                    self._restarts = 0  # isolated fault, not a crash loop
                self._last_crash_at = now
                if self._stop.is_set() or self._restarts >= self.max_restarts:
                    self._startup_error = e
                    self._fail_all(e)
                    ls = getattr(self, "_ls", None)
                    if ls is not None:
                        # dying ON the device thread: no concurrent
                        # collective exists, so release blocked followers
                        try:
                            ls.stop()
                        except Exception:  # noqa: BLE001
                            pass
                    return
                self._restarts += 1
                self.metrics.increment_counter("app_tpu_engine_restarts", 1)
                self._restarting = True
                try:
                    ls = getattr(self, "_ls", None)
                    if ls is not None:
                        # rejoin-capable fleet leader (a collective-transport
                        # leader never reaches here: max_restarts is 0): the
                        # crash may have cut an announce mid-frame, so drop
                        # every follower connection — each redials into the
                        # pending set and the restarted loop admits them all
                        # at a bumped epoch (_fleet_admit)
                        ls.reset_connections()
                    if self._chaos_restart is not None:
                        self._chaos_restart(attempt=self._restarts)
                except Exception as e2:  # noqa: BLE001
                    # an exception ESCAPING this handler would kill the
                    # device thread without _fail_all — every queued caller
                    # would hang to its timeout. Restart-path faults must
                    # never outrank the restart itself.
                    self.logger.log_exception(e2, "engine restart path")
                time.sleep(min(0.1 * (2 ** self._restarts), 5.0))
                self._restarting = False
                self.logger.warn(
                    f"engine device loop restarting (attempt {self._restarts}/{self.max_restarts})"
                )

    def _loop(self) -> None:  # pragma: no cover - abstract
        raise NotImplementedError

    # -- submission ------------------------------------------------------------

    def _submit(self, inputs: Any, timeout: float | None, stream: bool = False, **kw: Any) -> Request:
        if self._thread is None:
            self.start()
        if self._startup_error is not None:
            raise self._startup_error
        if self._draining:
            # draining replica (scale-in): the registry already stopped
            # routing here, so anything arriving now raced the transition —
            # shed retryable, the ring successor owns the key by the retry
            self.metrics.increment_counter("app_tpu_drain_shed_total", 1)
            raise ServiceUnavailable("replica draining", retry_after=1.0)
        if "qos_class" in kw:  # public spelling of the internal routing key
            kw["_qos_class"] = kw.pop("qos_class")
        # the inbound server span, carried EXPLICITLY (contextvars don't
        # cross the submit-thread → device-loop boundary); popped even when
        # tracing is off so a span object never lingers in request kw
        parent_span = kw.pop("_parent_span", None)
        # optional caller hook: receives the Request the moment it exists,
        # so transports can track in-flight work for disconnect-driven
        # cancellation (Context._qos_kw, docs/resilience.md)
        on_submit = kw.pop("_on_submit", None)
        # chaos point "replica.slow" (fleet/chaos.py): a delay action here
        # simulates a slow replica's admission path — the hedging drill's
        # way of making one ring member consistently late
        chaos.fire("replica.slow")
        eff_timeout = timeout if timeout is not None else self.default_timeout
        if eff_timeout is not None and eff_timeout <= 0:
            # the propagated deadline is already spent: shed pre-queue with
            # 504 — computing tokens nobody can wait for helps no one
            self.metrics.increment_counter(
                "app_request_deadline_exceeded_total", 1, where="engine")
            raise DeadlineExceeded(
                "request deadline already expired at submission")
        # multi-LoRA routing (gofr_tpu.adapters; docs/serving.md): resolve
        # the adapter BEFORE QoS admission — an adapter's declared default
        # class must key the class gates below — and take its per-adapter
        # concurrency share (429 at the cap, the per-tenant analog of the
        # per-class cap; released on the done callback like qos.track).
        if "adapter_id" in kw:  # public spelling of the internal routing key
            kw["_adapter"] = kw.pop("adapter_id")
        registry = getattr(self, "adapters", None)
        aname = kw.get("_adapter") or None
        aspec = None
        if aname:
            if registry is None:
                raise ValueError(
                    f"request names adapter {aname!r} but this engine has no "
                    "adapter plane (set ADAPTER_SLOTS or ADAPTER_POOL_MB)")
            try:
                aspec = registry.admit(aname)
            except KeyError as e:
                raise ValueError(str(e.args[0]) if e.args else str(e)) from None
            if aspec.qos_class and not kw.get("_qos_class"):
                kw["_qos_class"] = aspec.qos_class
        we = getattr(self, "weights_epoch", None)
        if we is not None:
            # base-weight epoch at submission: surfaced by the flight
            # recorder so "which weights answered this" stays debuggable
            # across live hot-swaps (engine.adopt_weights)
            kw["_weights_epoch"] = we
        qos, cls = self.qos, None
        if qos is not None:
            # admission BEFORE the request exists: backlog cap, per-class
            # concurrency cap, and the predicted-wait-vs-deadline check —
            # hopeless work is rejected with 429/503 + Retry-After here
            # instead of burning a slot and timing out later (docs/qos.md)
            try:
                cls = qos.admit_engine(self, kw.get("_qos_class"), eff_timeout)
            except Exception:
                if aspec is not None:
                    registry.release(aname)  # the class gate shed us first
                raise
            kw["_qos_class"] = cls.name
        req = Request(inputs, kw, eff_timeout, stream)
        if cls is not None:
            qos.track(req, cls)
        if aspec is not None:
            req.add_done_callback(lambda _r, _n=aname: registry.release(_n))
        if on_submit is not None:
            on_submit(req)
        self._observe_submit(req, parent_span)
        self._queue.put(req)
        self.metrics.set_gauge("app_tpu_queue_depth", self._backlog())
        return req

    # -- request-lifecycle observability ---------------------------------------

    def _observe_submit(self, req: Request, parent_span) -> None:
        """Open the request's observability lifecycle: span timeline (only
        behind ``Tracer.enabled`` — with ``TRACE_EXPORTER=none`` this whole
        path costs one branch and allocates nothing), the in-flight gauge,
        and the completion hook that records SLO metrics + the flight
        timeline however the request ends (result, error, timeout, stop)."""
        tracer = self.tracer
        if tracer is not None and tracer.enabled:
            if parent_span is None:
                parent_span = current_span()
            if parent_span is None or parent_span.sampled:
                rt = RequestTrace(tracer, parent_span)
                req.kw["_rt"] = rt
                rt.begin("engine.queue_wait",
                         **{"qos.class": req.kw.get("_qos_class") or "none",
                            "queue.depth": self._backlog()})
        with self._obs_lock:
            # per-engine counter; the app_tpu_inflight_requests gauge is
            # summed across registered engines at scrape time (container
            # collect hook) — an engine-side set here would flap the global
            # gauge between per-engine values when several engines serve
            self._inflight_requests += 1
        req.add_done_callback(self._observe_done)

    def _observe_done(self, req: Request) -> None:
        now = time.monotonic()
        with self._obs_lock:
            self._inflight_requests -= 1
        result, error = req.outcome()
        kw = req.kw
        rt = kw.pop("_rt", None)
        if rt is not None:
            rt.close_all(error)
        e2e = now - req.enqueued_at
        if self.slo is not None:
            # availability counts EVERY outcome (errors, timeouts, sheds all
            # burn budget); the e2e latency objective, like the histogram
            # below, judges completed work only
            self.slo.observe_outcome(kw.get("_qos_class"), error is None)
        if error is None:
            # completed work only: a timeout/shed storm must not drag the
            # served-latency SLO histogram toward its own failure mode
            self.metrics.record_histogram(
                "app_tpu_e2e_seconds", e2e, qos_class=kw.get("_qos_class") or "none")
            if self.slo is not None:
                self.slo.observe(kw.get("_qos_class"), "e2e", e2e)
        spec_proposed = kw.get("_spec_proposed")
        if spec_proposed:
            # lifetime per-adapter acceptance numerators for the
            # app_tpu_spec_accept_ratio gauge (container scrape divides;
            # keeping raw counts is what lets federation sum, not average)
            with self._obs_lock:
                tot = self._spec_totals.setdefault(
                    kw.get("_adapter") or "base", [0.0, 0.0])
                tot[0] += float(kw.get("_spec_accepted", 0))
                tot[1] += float(spec_proposed)
        if self.flight is None:
            return
        admitted = kw.get("_admitted_at")
        first = kw.get("_first_token_at")
        entry: dict[str, Any] = {
            "id": req.id,
            "completed_at": time.time(),
            "qos_class": kw.get("_qos_class"),
            "e2e_s": round(e2e, 6),
            "queue_wait_s": round(admitted - req.enqueued_at, 6) if admitted is not None else None,
            "ttft_s": round(first - req.enqueued_at, 6) if first is not None else None,
            "slot": kw.get("_slot"),
            "prompt_len": kw.get("_prompt_len"),
            "preemptions": kw.get("_preemptions", 0),
            "trace_id": rt.trace_id if rt is not None else None,
        }
        if kw.get("_adapter"):
            # which LoRA adapter served this request (None lanes omit the
            # field entirely — the common base-model case stays compact)
            entry["adapter"] = kw.get("_adapter")
        if kw.get("_weights_epoch") is not None:
            entry["weights_epoch"] = kw.get("_weights_epoch")
        dev = {label: round(kw[f], 6) for label, f in (
            ("prefill_s", "_dev_prefill_s"), ("decode_s", "_dev_decode_s"),
            ("swapin_s", "_dev_swapin_s")) if kw.get(f)}
        if dev:
            # device-queue residency while this request had work in flight,
            # per phase (folds accumulate it from the perf plane's clipped
            # step times) — with queue_wait_s and e2e_s this answers
            # "queue, device, or fold?" for a slow request
            entry["device"] = dev
        proposed = kw.get("_spec_proposed")
        if proposed:
            entry["spec_accept_rate"] = round(
                kw.get("_spec_accepted", 0) / proposed, 4)
        prefix = kw.get("_prefix")
        if prefix:
            # per-tier prefix-cache hit breakdown (hbm/host tokens + pages
            # swapped in from host DRAM) — docs/observability.md
            entry["prefix"] = prefix
        if req.cancelled and req.cancel_reason:
            # why the lifetime ended early (client_disconnect, timeout,
            # hedge_loser, ...) — the /debug/requests timeline's answer to
            # "who killed this request" (docs/resilience.md)
            entry["cancel_reason"] = req.cancel_reason
        if error is not None:
            entry["error"] = type(error).__name__
        elif isinstance(result, dict) and "finish_reason" in result:
            entry["finish_reason"] = result.get("finish_reason")
            toks = result.get("tokens")
            if toks is not None:
                entry["new_tokens"] = len(toks)
                if first is not None and len(toks) > 1:
                    entry["tpot_s"] = round((now - first) / (len(toks) - 1), 6)
        self.flight.record_request(entry)

    def _mark_admitted(self, req: Request, now: float) -> None:
        """First pick-up by the device loop: close the queue-wait phase.
        Guarded so preemption-by-recompute re-admissions don't double-count
        the SLO histogram."""
        if "_admitted_at" not in req.kw:
            req.kw["_admitted_at"] = now
            self.metrics.record_histogram(
                "app_tpu_queue_wait_seconds", now - req.enqueued_at)
        rt = req.kw.get("_rt")
        if rt is not None:
            rt.end("engine.queue_wait")

    def _mark_first_token(self, req: Request) -> None:
        """Stamp TTFT exactly once (preemption preserves the original)."""
        if "_first_token_at" not in req.kw:
            ft = time.monotonic()
            req.kw["_first_token_at"] = ft
            self.metrics.record_histogram(
                "app_tpu_ttft_seconds", ft - req.enqueued_at)
            if self.slo is not None:
                self.slo.observe(req.kw.get("_qos_class"), "ttft",
                                 ft - req.enqueued_at)

    def _record_step(self, kind: str, seconds: float, occupancy: float,
                     signature: tuple, pstep=None, adapter_ids=None) -> float:
        # called at COMPLETION (dequeue) time under the unified pipeline:
        # `seconds` spans dispatch→fold, so it includes the overlapped
        # in-flight wait, not just device compute. `pstep` (a perf.StepPerf
        # built at dispatch, t_ready stamped right after readback) carries
        # the roofline side: the perf plane clips it to true device-queue
        # residency and bubble, recorded separately from this wall span.
        self.metrics.record_histogram("app_tpu_step_seconds", seconds, kind=kind)
        self.metrics.record_histogram("app_tpu_batch_occupancy", occupancy, kind=kind)
        device_s = 0.0
        perf = getattr(self, "perf", None)
        if pstep is not None and perf is not None:
            from gofr_tpu.metrics.perf import occupancy_band

            now_perf = time.monotonic()
            # band label keys the controller's evidence windows: the same
            # knob can win at high occupancy and lose near-empty, so
            # judgments (and persisted pins) are per occupancy band
            perf.note(pstep, now_perf, band=occupancy_band(occupancy))
            if adapter_ids:
                # per-adapter roofline attribution (metrics/perf.py): one
                # id per dispatched lane ("base" for adapterless lanes), a
                # complete partition of the step — per-adapter device-
                # seconds sum exactly to the step's, the COGS invariant
                perf.note_adapters(adapter_ids, pstep, now_perf)
            device_s = pstep.device_s
            self.metrics.record_histogram(
                "app_tpu_step_device_seconds", device_s, kind=kind)
        if self.flight is not None:
            # active knob vector on every step entry: a replayed anomaly
            # bundle shows WHICH tuning the anomalous step ran under
            # (BatchEngine has no knobs — None elides the field)
            kv_fn = getattr(self, "knob_vector", None)
            knobs = kv_fn() if kv_fn is not None else None
            if pstep is not None:
                self.flight.record_step(
                    kind, seconds, occupancy, signature,
                    self._backlog(), len(getattr(self, "_dq", ())),
                    device_s=device_s, bytes_=pstep.bytes,
                    flops=pstep.flops, bubble_s=pstep.bubble_s, knobs=knobs)
            else:
                self.flight.record_step(kind, seconds, occupancy, signature,
                                        self._backlog(), len(getattr(self, "_dq", ())),
                                        knobs=knobs)
        if self.qos is not None:
            self.qos.observe_step(seconds)  # feeds the queue-wait estimator
        # the signatures this engine has run (warm-up and the benchmark read
        # the set); compiles themselves are counted from JAX's own events
        # (tpu/device.py)
        self._compiled.add(signature)
        return device_s

    def health_check(self) -> dict[str, Any]:
        if self._startup_error is not None:
            return {"status": "DOWN", "details": {"error": str(self._startup_error)}}
        if self._restarting:
            return {"status": "DEGRADED",
                    "details": {"restarting": True, "restarts": self._restarts}}
        detail: dict[str, Any] = {"queue_depth": self._backlog(), "restarts": self._restarts}
        if self._draining:
            detail["draining"] = True
        return {
            "status": "UP" if self._thread is not None and self._thread.is_alive() else "DEGRADED",
            "details": detail,
        }


# -- stateless batching (embed / classify) -------------------------------------


class BatchEngine(_EngineBase):
    """Drain-and-batch engine for stateless models.

    ``apply_fn(padded_inputs, lengths) -> outputs[B, ...]`` must be
    jit-compiled with static shapes per (len_bucket, batch_bucket).
    ``encode_fn`` turns one request's inputs into a 1-D token array (or
    fixed-shape array for images, in which case buckets only apply to
    batch).
    """

    def __init__(
        self,
        apply_fn: Callable,
        container,
        *,
        encode_fn: Callable[[Any], np.ndarray] | None = None,
        decode_fn: Callable[[np.ndarray], Any] | None = None,
        max_batch: int = 32,
        len_buckets: list[int] | None = None,
        max_wait_ms: float = 2.0,
        default_timeout: float | None = None,
        max_restarts: int = 3,
    ):
        super().__init__(container, default_timeout=default_timeout, max_restarts=max_restarts)
        self.apply_fn = apply_fn
        self.encode_fn = encode_fn or (lambda x: np.asarray(x))
        self.decode_fn = decode_fn or (lambda row: row)
        self.max_batch = max_batch
        self.len_buckets = sorted(len_buckets) if len_buckets else _pow2_buckets(16, 512)
        self.max_wait = max_wait_ms / 1000.0
        self.batch_buckets = _pow2_buckets(1, max_batch)

    def infer(self, inputs: Any, timeout: float | None = None, **kw: Any) -> Any:
        req = self._submit(inputs, timeout, **kw)
        return req.result(timeout if timeout is not None else self.default_timeout)

    def warmup(self, example: Any, len_buckets: list[int] | None = None,
               batch_buckets: list[int] | None = None) -> int:
        """Pre-compile the (len bucket × batch bucket) apply signatures so no
        XLA compile lands in the serving window (GenerateEngine.warmup
        parity). ``example`` is one representative request input — token
        sequences warm every (len, batch) pair, fixed-shape inputs (images)
        warm batch buckets only. Call before serving traffic."""
        from gofr_tpu.ops.pallas import platform_hint

        arr = np.asarray(self.encode_fn(example))
        bbs = sorted(batch_buckets) if batch_buckets else self.batch_buckets
        count = 0
        with platform_hint(getattr(self.tpu, "platform", None)):
            if arr.ndim == 1:
                lbs = sorted(len_buckets) if len_buckets else self.len_buckets
                for lb in lbs:
                    for nb in bbs:
                        # via numpy so dtype canonicalization matches _step
                        # (a direct jnp.zeros(int64) would warn per bucket)
                        tokens = jnp.asarray(np.zeros((nb, lb), arr.dtype))
                        lens = jnp.asarray(np.ones((nb,), np.int32))
                        jax.block_until_ready(self.apply_fn(tokens, lens))
                        self._compiled.add(("batch", lb, nb))
                        count += 1
            else:
                for nb in bbs:
                    stacked = jnp.asarray(np.zeros((nb, *arr.shape), arr.dtype))
                    jax.block_until_ready(self.apply_fn(stacked))
                    self._compiled.add(("batch", arr.shape, nb))
                    count += 1
        return count

    def _drain(self) -> list[Request]:
        """Block for one request, then grab whatever arrives within
        max_wait (micro-batch accumulation), up to max_batch."""
        try:
            first = self._queue.get(timeout=0.2)
        except queue.Empty:
            return []
        batch = [first]
        deadline = time.monotonic() + self.max_wait
        while len(batch) < self.max_batch:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                batch.append(self._queue.get(timeout=remaining))
            except queue.Empty:
                break
        self.metrics.set_gauge("app_tpu_queue_depth", self._queue.qsize())
        now = time.monotonic()
        live = []
        for r in batch:
            if r.cancelled or r.expired(now):
                r.complete(error=RequestTimeout())
            else:
                live.append(r)
        return live

    def _loop(self) -> None:
        while not self._stop.is_set() and not self._poisoned:
            batch = self._drain()
            if not batch:
                continue
            try:
                self._step(batch)
            except Exception as e:  # noqa: BLE001
                self.logger.log_exception(e, "batch engine step")
                for r in batch:
                    r.complete(error=e)

    def _step(self, batch: list[Request]) -> None:
        arrays = [np.asarray(self.encode_fn(r.inputs)) for r in batch]
        n = len(arrays)
        nb = next_bucket(n, self.batch_buckets)
        now = time.monotonic()
        for r in batch:
            self._mark_admitted(r, now)
            rt = r.kw.get("_rt")
            if rt is not None:
                rt.begin("engine.infer", **{"batch.size": n, "batch.bucket": nb})
        self._inflight = list(batch)
        t0 = time.monotonic()

        if arrays[0].ndim == 1:  # token sequences: pad to a length bucket
            lengths = np.array([a.shape[0] for a in arrays], np.int32)
            lb = next_bucket(int(lengths.max()), self.len_buckets)
            tokens = np.zeros((nb, lb), arrays[0].dtype)
            for i, a in enumerate(arrays):
                tokens[i, : a.shape[0]] = a
            lens = np.zeros((nb,), np.int32)
            lens[:n] = lengths
            lens[n:] = 1  # padded rows: nonzero length avoids div-by-zero paths
            signature = ("batch", lb, nb)
            out = self.apply_fn(jnp.asarray(tokens), jnp.asarray(lens))
        else:  # fixed-shape inputs (images): batch bucket only
            stacked = np.zeros((nb, *arrays[0].shape), arrays[0].dtype)
            for i, a in enumerate(arrays):
                stacked[i] = a
            signature = ("batch", arrays[0].shape, nb)
            out = self.apply_fn(jnp.asarray(stacked))

        out = np.asarray(out)
        self._inflight = []
        self._record_step("batch", time.monotonic() - t0, n / nb, signature)
        self.metrics.increment_counter("app_tpu_tokens_total", int(n))
        for i, r in enumerate(batch):
            rt = r.kw.get("_rt")
            if rt is not None:
                rt.end("engine.infer", **{"batch.occupancy": n / nb})
            r.complete(result=self.decode_fn(out[i]))  # idempotent: no-op if already failed


# -- continuous batching (generate) --------------------------------------------


class _Slot:
    """One active generation. Invariants: ``generated`` holds every output
    token so far (last one's K/V not yet in cache); ``pos`` is the cache
    position the last token will be written to on the next decode step,
    i.e. ``prompt_len + len(generated) - 1``.

    A slot admitted with ``first_token=None`` is in the *prefill* stage —
    its lane is claimed (reserved against decode, admission, and page
    reuse) while the prefill device work is in flight. Batched prefills
    dispatch the whole prompt at once (``dispatched == prompt_len``) and
    activate at dequeue; chunked prefills stream the prompt in
    bucket-sized chunks (``written`` counts tokens whose write was read
    back), joining decode once the final chunk's dequeue samples the
    first token (SURVEY §7 hard parts (a)/(b): long prompts stream into
    the cache between decode steps instead of inflating one batch's
    padding or being rejected)."""

    __slots__ = ("request", "prompt_len", "pos", "generated", "max_total", "eos",
                 "last_token", "first_token_at", "admit_seq", "prompt_tokens",
                 "written", "dispatched", "inflight", "adapter_id", "adapter_slot",
                 "handoff")

    def __init__(self, request: Request, prompt_len: int, max_total: int, eos: int | None,
                 first_token: int | None, admit_seq: int = 0, prompt_tokens: Any = None,
                 adapter_id: str | None = None, adapter_slot: int = 0):
        self.request = request
        self.prompt_len = prompt_len
        self.pos = prompt_len
        self.generated = [first_token] if first_token is not None else []
        self.max_total = max_total
        self.eos = eos
        self.last_token = first_token
        self.first_token_at = time.monotonic()
        self.admit_seq = admit_seq       # preemption order (paged layout)
        self.prompt_tokens = prompt_tokens  # kept for preemption re-prefill
        self.written = prompt_len if first_token is not None else 0
        # prompt tokens whose device write is DISPATCHED (>= written, which
        # counts tokens whose write was read back): the chunked path advances
        # `dispatched` at dispatch and `written` at dequeue, so several
        # chunks of one prompt can ride the in-flight queue at once
        self.dispatched = self.written
        self.inflight = 0  # decode chunks dispatched but not yet processed
        # multi-LoRA lane binding (gofr_tpu.adapters): the registry name
        # and the device pool slot whose factors this lane gathers in
        # every step; (None, 0) is the base model (pool slot 0 is the
        # reserved all-zeros adapter — bit-identical to no adapters)
        self.adapter_id = adapter_id
        self.adapter_slot = adapter_slot
        # streaming KV handoff transfer (prefill role, tpu/handoff.py
        # StreamTransfer): pages of a still-prefilling slot ship per
        # chunk fold instead of all-at-once at activation
        self.handoff = None

    @property
    def prefilling(self) -> bool:
        # the lane-set stage predicate (engine._claim_slot / testutil.
        # assert_lane_sets_consistent): a batched-prefill slot has
        # written == 0 but leaves the prefill stage only when its fold
        # delivers the first token
        return self.last_token is None


class _StreamIterator:
    """Token-stream iterator with an explicit ``cancel()`` so transports can
    free the slot when the client disconnects mid-generation (otherwise the
    engine would decode to max_new_tokens for a client that is gone)."""

    def __init__(self, req: Request, gen: Iterator[Any]):
        self._req = req
        self._gen = gen

    def __iter__(self) -> "_StreamIterator":
        return self

    def __next__(self) -> Any:
        return next(self._gen)

    def cancel(self, reason: str = "client_disconnect") -> None:
        self._req.cancel(reason)


class GenerateEngine(_EngineBase):
    """Slot-based continuous batching for decoder LMs (family must expose
    ``prefill``, ``decode_step``, ``make_cache`` — see models.llama)."""

    def __init__(
        self,
        family: Any,
        cfg: Any,
        params: Any,
        container,
        *,
        slots: int = 8,
        max_len: int = 2048,
        prefill_buckets: list[int] | None = None,
        max_prefill_batch: int = 4,
        decode_chunk: int = 8,
        eos_token_id: int | None = None,
        top_k: int = 0,
        top_p: float = 1.0,
        tokenizer: Any = None,
        default_timeout: float | None = None,
        seed: int = 0,
        kv_layout: str = "slot",
        page_size: int = 128,
        total_pages: int | None = None,
        max_restarts: int = 3,
        decode_pipeline: int = 2,
        prefix_cache: bool = True,
        prefix_host_mb: float = 0.0,
        spec_tokens: int = 0,
        kv_quantize: str = "",
        kv_shard: str = "auto",
        prefill_attn_fn: Any = None,
        prefill_attn_divisor: int = 1,
        lockstep_role: str | None = None,
        fleet: Any = None,
        spec_draft: tuple | None = None,
        pipeline_depth: int | None = None,
        role: str = "both",
        handoff_target: str | None = None,
        handoff_listen: str | None = None,
        handoff_timeout_s: float = 5.0,
        handoff_streams: int = 2,
        handoff_chunk_pages: int = 4,
        handoff_pace_mbps: float = 0.0,
        adapter_slots: int = 0,
        adapter_rank: int = 16,
        adapter_pool_mb: float = 0.0,
        adapter_host_mb: float = 256.0,
        adapter_hotswap_dir: str | None = None,
        adapter_hotswap_poll_s: float = 5.0,
        quality_shadow_rate: float = 0.0,
        quality_seed: int | None = None,
        quality_max_pending: int = 16,
        quality_max_tokens: int = 64,
        quality_top1_min: float = 0.9,
        quality_kl_max: float = 1.0,
        quality_recent: int = 32,
        control_enable: bool = False,
    ):
        super().__init__(container, default_timeout=default_timeout, max_restarts=max_restarts)
        self.family = family
        self.cfg = cfg
        self.params = params
        self.num_slots = slots
        self.max_len = min(max_len, cfg.max_seq_len)
        self.prefill_buckets = sorted(prefill_buckets) if prefill_buckets else _pow2_buckets(
            16, self.max_len
        )
        if prefill_attn_fn is not None and prefill_attn_divisor > 1:
            bad = [b for b in self.prefill_buckets if b % prefill_attn_divisor]
            if bad:
                # fail at BUILD time, not on the first prompt that lands in
                # an indivisible bucket mid-serving (the top bucket is
                # max_len itself, which need not be a power of two)
                raise ValueError(
                    f"prefill buckets {bad} are not divisible by the "
                    f"sequence-parallel axis size {prefill_attn_divisor}; "
                    f"set ENGINE_MAX_LEN (or prefill_buckets) to multiples of it"
                )
        self.max_prefill_batch = max_prefill_batch
        self.eos_token_id = eos_token_id
        self.tokenizer = tokenizer
        self.top_k = top_k
        self.top_p = top_p

        # K decode steps run on-device per host round trip, with sampling
        # fused into the step — the host sees [slots, K] int32 tokens, never
        # logits. This is the difference between per-token host syncs (the
        # reference's per-request goroutine equivalent) and a device-resident
        # loop; it also keeps serving fast over high-latency device links.
        self.decode_chunk = max(1, decode_chunk)

        # Speculative decoding (VERDICT r3 #6): each outer decode step
        # proposes spec_tokens continuation tokens — prompt-lookup from the
        # slot's own device-resident history, or a draft MODEL (spec_draft)
        # — then ONE target forward verifies all of them. Acceptance is
        # distribution-exact rejection sampling (programs.speculative_
        # sample): sampled requests emit tokens distributed exactly as
        # plain sampled decode, and greedy requests (temperature 0) are the
        # special case whose outputs are bit-identical to plain greedy
        # decode — up to spec_tokens+1 tokens per target forward at the
        # memory-bound occupancies where decode wastes bandwidth.
        self.spec_tokens = max(0, int(spec_tokens))
        if self.spec_tokens:
            need = "verify_step" if kv_layout == "slot" else "verify_step_paged"
            if not hasattr(family, need):
                raise ValueError(
                    f"family {getattr(family, '__name__', family)!r} has no {need}; "
                    "speculative decoding needs it"
                )
        # Draft-model speculative decoding (VERDICT r4 #4): spec_draft is a
        # (family, cfg, params) triple for a small model sharing the target's
        # tokenizer/vocab. Drafts come from g autoregressive draft-model
        # steps on device instead of prompt lookup (tpu/programs.py); the
        # bit-exact greedy verify is unchanged, so the draft only moves the
        # acceptance rate — real text accepts far more than lookup can.
        if spec_draft is not None:
            if not self.spec_tokens:
                raise ValueError("spec_draft requires spec_tokens > 0")
            if kv_layout != "slot":
                raise ValueError(
                    "spec_draft (draft-model speculative decoding) is "
                    "slot-layout only (v1): the paged layout's page allocation "
                    "would need the draft cache paged too — use "
                    "kv_layout='slot' or drop spec_draft"
                )
            dfam = spec_draft[0]
            missing = [a for a in ("prefill", "decode_step", "make_cache")
                       if not hasattr(dfam, a)]
            if missing:
                raise ValueError(
                    f"spec_draft family {getattr(dfam, '__name__', dfam)!r} "
                    f"lacks {missing}; the draft must follow the slot-cache "
                    "decoder protocol"
                )
            if (getattr(family, "SLOT_CHUNKED_PREFILL", False)
                    and not getattr(dfam, "SLOT_CHUNKED_PREFILL", False)):
                raise ValueError(
                    "spec_draft family has no chunked (offset) prefill, but the "
                    "target serves long prompts through it — use a draft "
                    "family with SLOT_CHUNKED_PREFILL"
                )
        self._draft = None  # (family, cfg) once validated (slot branch below)
        # Unified device pipeline (depth 2 = one call in flight): EVERY
        # device call — batched prefill, chunked prefill, decode chunk,
        # slot-layout spec round — is dispatched onto one bounded in-flight
        # queue (self._dq) and its readback + host bookkeeping happen at
        # DEQUEUE, overlapped with the next dispatch. The decode data
        # dependency (t+1's input token = t's last output) stays ON DEVICE
        # via the `prev_last` carry — or, for speculative rounds on the
        # slot layout, the (token, hlen) spec carry plus the device-resident
        # history (tpu/programs.py); prefill has no such dependency (the
        # prompt is host-known), so its futures simply ride the queue.
        # Depth 1 drains the queue every iteration (the synchronous path,
        # token-identical).
        # `pipeline_depth` is the canonical knob (ENGINE_PIPELINE);
        # `decode_pipeline` (ENGINE_DECODE_PIPELINE) is the legacy alias.
        depth = pipeline_depth if pipeline_depth is not None else decode_pipeline
        self.pipeline_depth = max(1, min(4, int(depth)))
        self.decode_pipeline = self.pipeline_depth  # legacy alias (bench/tests)
        # Online-controller knob state (gofr_tpu.control): boot values are
        # the operator-provisioned CEILINGS — the step controller explores
        # within [1 .. boot], never past what the deployment was sized for.
        # ``prefill_chunk`` caps how much of a long prompt one chunked-
        # prefill dispatch takes (_advance_chunked); it is always a member
        # of prefill_buckets so the compiled-signature population stays the
        # boot set. Foreign threads (controller ticks run on the device
        # thread, but debug endpoints and bench drills do not) enqueue
        # changes via request_knobs; the device loop drains them at its
        # loop-top safe seam, the ONLY place knobs mutate.
        self._boot_pipeline_depth = self.pipeline_depth
        self._boot_prefill_batch = self.max_prefill_batch
        self._boot_spec_tokens = self.spec_tokens
        self.prefill_chunk = self.prefill_buckets[-1]
        self._knob_requests: collections.deque = collections.deque()
        self._control = None
        # cache slack one chunk can write past max_len: each spec round
        # writes up to spec_tokens+1 positions plus spec_tokens draft slots.
        # Sized from the BOOT spec_tokens and never resized: the controller
        # only lowers g below boot, so the dispatch-time masking bound
        # (pos + chunk_span*inflight) and the paged over-claim stay
        # conservative for every live g <= boot.
        chunk_span = (self.decode_chunk * (self.spec_tokens + 1) + self.spec_tokens
                      if self.spec_tokens else self.decode_chunk)
        self._chunk_span = chunk_span
        # One chunk_span of slack suffices at ANY pipeline depth: dispatch
        # masks a lane once its worst-case in-flight position
        # (pos + chunk_span*inflight) reaches max_total, so at dispatch
        # time the device-side hlen is < max_total and the new round's
        # writes stay < max_total + chunk_span — the same dead-lane bound
        # plain pipelined decode relies on (decode.dispatch_spec).
        requested_max_len = self.max_len
        self.max_len = min(self.max_len, cfg.max_seq_len - chunk_span)
        if self.max_len < requested_max_len:
            # Chunked decode needs decode_chunk of cache headroom past the
            # last admitted position; surface the shrink so operators see why
            # prompts near the advertised limit are rejected (ADVICE.md).
            self.logger.warn(
                f"engine max_len reduced {requested_max_len} -> {self.max_len} "
                f"(decode_chunk={self.decode_chunk} headroom within cfg.max_seq_len={cfg.max_seq_len})"
            )

        if kv_layout not in ("slot", "paged"):
            raise ValueError(f"kv_layout {kv_layout!r}: use 'slot' or 'paged'")
        # pp serving (models/llama_pp.py): decode runs microbatches over the
        # slot dimension. A non-dividing value would silently degrade to
        # gcd(slots, microbatches) — potentially 1 microbatch, the WORST
        # bubble fraction — so fail at build time like the sp bucket guard
        # (docs/configs.md documents the divisibility requirement).
        fam_mb = getattr(family, "microbatches", 0)
        if fam_mb and slots % fam_mb:
            raise ValueError(
                f"pipeline microbatches {fam_mb} (ENGINE_PP_MICROBATCHES, "
                f"default = the pp mesh degree) does not divide the slot "
                f"count {slots}: decode would fall back to "
                f"gcd={math.gcd(slots, fam_mb)} microbatches "
                f"(worse pipeline bubbles); align it with ENGINE_SLOTS"
            )
        if kv_layout == "paged" and not hasattr(family, "make_paged_cache"):
            raise ValueError(f"model family {family.__name__} has no paged-cache support")
        if kv_layout == "slot" and not hasattr(family, "make_cache"):
            raise ValueError(f"model family {family.__name__} has no slot-cache support "
                             "(kv_layout='paged' serves it)")
        self.kv_layout = kv_layout

        # Engine role (disaggregated serving; tpu/handoff.py): "both"
        # keeps today's colocated behavior bit-for-bit; "prefill" exports
        # each prompt's full KV pages to the decode pool after prefill
        # instead of decoding locally; "decode" imports handed-off pages
        # as host-tier prefix nodes and serves the decode phase. Role
        # workers need the paged layout — the handoff payload IS pool
        # pages — and cannot combine with lockstep (followers could
        # never replay a transfer that arrived over a side channel).
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"ENGINE_ROLE {role!r}: use 'both', 'prefill' or 'decode'")
        if role != "both" and kv_layout != "paged":
            raise ValueError(
                f"ENGINE_ROLE={role} needs kv_layout='paged' "
                "(the KV handoff ships pool pages)")
        if role != "both" and lockstep_role:
            raise ValueError(
                "ENGINE_ROLE prefill/decode cannot combine with lockstep")
        self.role = role

        if kv_quantize not in ("", "int8", "int4"):
            raise ValueError(
                f"kv_quantize={kv_quantize!r}: use '', 'int8' or 'int4'")
        if kv_quantize == "int4" and kv_layout != "paged":
            # int4 exists as a PAGE format (two nibbles per byte packed
            # along head_dim; ops/paged.Q4PagedKVCache) — the slot layout
            # keeps int8 as its only quantized option
            raise ValueError(
                "kv_quantize='int4' needs kv_layout='paged' (packed-nibble "
                "pages); the slot layout supports '' or 'int8'")
        # tensor-parallel pool sharding (ENGINE_KV_SHARD): 1 = unsharded.
        # Resolved before the cache is built; the slot layout never shards.
        self.kv_shards = 1
        self._kv_pool_sharding = None
        if kv_layout == "paged":
            kvq_attr = ("make_paged_cache_q4" if kv_quantize == "int4"
                        else "make_paged_cache_q")
            if kv_quantize and not hasattr(family, kvq_attr):
                raise ValueError(
                    f"family {getattr(family, '__name__', family)!r} has no "
                    f"{kv_quantize} paged-KV support ({kvq_attr})"
                )
            self.kv_quantize = kv_quantize
            # Paged cache (ops.paged): HBM scales with tokens in flight, not
            # slots x max_len. Per-slot logical capacity stays max_len +
            # decode_chunk; physical pages are pooled and allocated on demand
            # (admission gate + preemption-by-recompute in _admit/_decode).
            self.page_size = page_size
            self.pages_per_slot = -(-(self.max_len + self._chunk_span) // page_size)
            # default pool = same HBM as the slot cache; shrink to
            # oversubscribe, or keep and raise `slots` for more concurrency
            self.total_pages = total_pages if total_pages else slots * self.pages_per_slot
            # Shard the pool over the mesh's tp axis along KV heads
            # (ops/paged.pool_sharding): per-device plane bytes drop to
            # 1/tp, and every trace this engine drives pins a KVShardCtx
            # (_trace_scope) so the paged decode ops run per-shard under
            # shard_map. "auto" stands down (1 shard, bit-identical to the
            # unsharded engine) whenever the mesh/geometry can't split.
            self.kv_shards, self._kv_pool_sharding = self._resolve_kv_shard(kv_shard)
            if self.total_pages < self.pages_per_slot:
                raise ValueError(
                    f"total_pages {self.total_pages} < pages_per_slot "
                    f"{self.pages_per_slot}: one max-length request cannot fit"
                )
            self.cache = self._build_paged_cache()
            self._free_pages: list[int] = list(range(self.total_pages))
            self._slot_pages: list[list[int]] = [[] for _ in range(slots)]
            # OOB convention: unallocated entries point one past the pool
            self._table = np.full((slots, self.pages_per_slot), self.total_pages, np.int32)
            # Pages are refcounted: slots AND the prefix cache hold shares,
            # and a page returns to the free pool only at refcount zero —
            # a prefix hit splices cached pages into several slots' tables
            # at once (tpu/prefix.py invariants).
            self._page_refs = np.zeros(self.total_pages, np.int64)
            from gofr_tpu.tpu.prefix import PrefixCache

            # Hierarchical cache host tier (ENGINE_PREFIX_HOST_MB): pages the
            # LRU eviction would drop are spilled to a bounded host-DRAM
            # buffer instead and swapped back in asynchronously over the
            # unified pipeline on a later hit (docs/serving.md). 0 keeps the
            # single-tier behavior bit-for-bit. Not wired under lockstep:
            # swap-in payloads are host-resident K/V that followers never
            # saw, so announcing the upload cannot reproduce it.
            host_mb = max(0.0, float(prefix_host_mb))
            if host_mb and lockstep_role:
                container.logger.warn(
                    "ENGINE_PREFIX_HOST_MB ignored under lockstep (swap-in "
                    "payloads cannot be announced to followers)"
                )
                host_mb = 0.0
            # per-page host-copy footprint across every cache plane (k/v for
            # bf16; k/v/ks/vs for int8) — the page axis is always axis 1
            self._page_bytes = sum(
                leaf.nbytes // self.total_pages for leaf in jax.tree.leaves(self.kv_cache)
            )
            # whole-pool LOGICAL footprint (.nbytes is global even for a
            # sharded array); page_pool_stats and /debug/perf report the
            # per-device slice (// kv_shards) so fleet sum-of-parts rollups
            # stay exact on sharded engines
            self._pool_bytes = sum(
                leaf.nbytes for leaf in jax.tree.leaves(self.kv_cache)
            )
            host_budget = int(host_mb * (1 << 20))
            if host_budget and host_budget < self._page_bytes:
                # a budget that cannot hold even one page would turn every
                # pool-pressure eviction into a gather+copy that is then
                # immediately dropped — pure overhead, no caching
                container.logger.warn(
                    f"ENGINE_PREFIX_HOST_MB={host_mb:g} is below one page's "
                    f"footprint ({self._page_bytes} bytes); host tier disabled"
                )
                host_budget = 0
            if role == "decode" and prefix_cache and not host_budget:
                # a decode worker IMPORTS handed-off pages as host-tier
                # nodes — without a budget every transfer would be dropped
                # at the door. Default a working buffer (the budget is a
                # cap, not an allocation); ENGINE_PREFIX_HOST_MB overrides.
                host_budget = max(self._page_bytes, 256 << 20)
            self._prefix = (PrefixCache(page_size, host_budget_bytes=host_budget)
                            if prefix_cache else None)
            if role == "decode" and (self._prefix is None
                                     or not self._prefix.host_budget):
                raise ValueError(
                    "ENGINE_ROLE=decode needs the prefix cache with a host "
                    "tier (the handoff import target); keep "
                    "ENGINE_PREFIX_CACHE on")
            self._cache_treedef = jax.tree.structure(self.kv_cache)
            # swap-in upload widths: a power-of-two bucket ladder like the
            # prefill buckets — one compiled upload program per bucket, and
            # a 1-page hit never ships pages_per_slot pages of zero padding
            self._swapin_buckets = _pow2_buckets(1, self.pages_per_slot)
            # swap-ins staged by _prefix_hit under the state lock, dispatched
            # by _admit right after releasing it; spills staged by
            # _evict_prefix_page, materialized to host by _materialize_spills
            # (both device-thread only)
            self._pending_swapins: list = []
            self._pending_spills: list = []
            if self._prefix is not None and (self._prefix.host_budget
                                             or role == "prefill"):
                # compile the spill gather EAGERLY: it is the one program
                # dispatched while the state lock is held (_evict_prefix_
                # page — and the prefill-role handoff export, which
                # gathers every exported page the same way), and warmup()
                # is optional — a first-spill JIT compile under the lock
                # would stall submit()/stop() for the compile duration.
                # The swap-in upload programs compile in warmup() or
                # lazily at dispatch, which runs unlocked.
                from gofr_tpu.ops.paged import gather_page

                jax.block_until_ready(
                    jax.tree.leaves(gather_page(self.kv_cache, jnp.int32(0)))[0])
            self._set_prefix_gauges()  # authoritative from construction on
        else:
            # cache headroom so a chunk never writes past Smax; round to a
            # kernel-friendly multiple of 128 when the model allows it
            cache_len = min(-(-(self.max_len + self._chunk_span) // 128) * 128,
                            cfg.max_seq_len)
            self._cache_len = cache_len
            # int8 KV (kvcache.QSlotKVCache): halves the cache bytes decode
            # attention streams per step — the long-context bandwidth lever
            # on top of weight-only int8 (VERDICT r3 #2)
            if kv_quantize and not hasattr(family, "make_cache_q"):
                raise ValueError(
                    f"family {getattr(family, '__name__', family)!r} has no int8 KV support"
                )
            self.kv_quantize = kv_quantize
            if spec_draft is not None:
                dfam, dcfg, dparams = spec_draft
                if getattr(dcfg, "max_seq_len", cache_len) < cache_len:
                    raise ValueError(
                        f"spec_draft max_seq_len {dcfg.max_seq_len} < engine "
                        f"cache length {cache_len}: the draft cache must cover "
                        "every position the target serves"
                    )
                self._draft = (dfam, dcfg)
                # every compiled program sees one params pytree; with a
                # draft it is {'t': target, 'd': draft} (tpu/programs.py)
                params = {"t": params, "d": dparams}
                self.params = params
            self.cache = self._build_slot_cache()
            self._prefix = None  # prefix caching needs the paged layout
        # -- live perf plane (metrics/perf.py; ROADMAP O3) -------------------
        # Exact accounting from the live pytrees: parameter bytes post-
        # quantization and the per-position pool footprint read off the
        # cache leaves (the 512/144/80 bf16/int8/int4 planes on the tiny
        # CPU config — NOT a nominal-dtype estimate, which would be 2x off
        # on backends that promote bf16 to fp32). Defensive: an exotic
        # family/pytree must never take the engine down with its meter.
        try:
            from gofr_tpu.metrics.perf import CostModel, PerfPlane
            from gofr_tpu.ops.quant import quantized_bytes

            if kv_layout == "paged":
                positions = self.total_pages * self.page_size
            else:
                positions = slots * self._cache_len
            pool_bytes = sum(
                leaf.nbytes for leaf in jax.tree.leaves(self.kv_cache))
            devices = getattr(self.tpu, "devices", None)
            dev_kind = (getattr(devices[0], "device_kind", None) if devices
                        else None) or getattr(self.tpu, "platform", "cpu")
            # per-DEVICE pricing: a tp-sharded pool moves 1/kv_shards of
            # every plane byte through each device, and the fleet rollup
            # (sum-of-parts, metrics/perf.py) multiplies back by summing
            # over devices — the gap vs the single-chip roofline is then
            # the measured interconnect cost
            shards = max(1, getattr(self, "kv_shards", 1))
            self.perf = PerfPlane(
                CostModel(
                    n_params=sum(
                        leaf.size for leaf in jax.tree.leaves(self.params)),
                    weight_bytes=quantized_bytes(self.params),
                    kv_bytes_per_pos=pool_bytes / max(1, positions) / shards,
                    page_bytes=getattr(self, "_page_bytes", 0.0) / shards,
                    page_size=page_size if kv_layout == "paged" else 0,
                    kv_dtype=self.kv_quantize or "bf16",
                    kv_shards=shards,
                    # an expert family says how a token meets its parameters
                    experts=(family.token_params(cfg)
                             if hasattr(family, "token_params") else None),
                ),
                str(dev_kind))
        except Exception as e:  # pragma: no cover - meter must not gate serving
            container.logger.warn(f"perf plane disabled: {e}")
            self.perf = None
        # multi-host lockstep (tpu/lockstep.py): the leader announces every
        # device call so follower processes issue the same global programs.
        # ``fleet`` (a fleet.FleetConfig) switches the announce transport to
        # the host-side channel (fleet/channel.py): membership becomes
        # elastic (epoch-based rejoin) and the device-loop restart budget
        # stays available — a leader restart is an epoch bump, not fleet
        # death. Without it the collective transport's v1 semantics hold:
        # a crash-RESTART would reset step/carry state on the leader only,
        # desynchronizing followers — never restart in collective lockstep.
        self.lockstep_role = lockstep_role
        self._ls = None
        self._fleet = fleet
        self._seed = seed
        if lockstep_role and fleet is None:
            self.max_restarts = 0
        # follower liveness deadline (lockstep.py): leader heartbeats at a
        # third of it so watchdogs only fire on true leader death
        deadline = container.config.get_float("LOCKSTEP_DEADLINE_S", 0.0)
        self._hb_interval = deadline / 3 if deadline > 0 else 0.0
        # the cache is created uncommitted and process-locally: commit it to
        # its serving placement before the first program sees it (see
        # _place_cache)
        self.cache = self._place_cache(self.cache)
        self.slots: list[_Slot | None] = [None] * slots
        # Lane sets, maintained INCREMENTALLY at claim/free/stage-transition
        # time: the device loop consults free/decoding/prefilling lanes
        # several times per iteration, and rescanning self.slots was three
        # O(num_slots) attribute-chasing sweeps per step (hot at slots≥128).
        # Invariant: the three sets partition range(num_slots); a lane is in
        # _prefill_lanes iff its slot exists and has no first token yet.
        self._free_lanes: set[int] = set(range(slots))
        self._decode_lanes: set[int] = set()
        self._prefill_lanes: set[int] = set()
        # Reusable packed staging buffers keyed by (kind, shape): a steady-
        # state step re-zeroes a preallocated int32 buffer per signature
        # instead of paying an np.zeros allocation per device call. Buffers
        # rotate through a ring (STAGING_RING; see _staging) because the
        # per-replica host→device fetch of a dispatched call is async —
        # immediate reuse could be rewritten under a lagging replica. All
        # packing runs on the device thread; the population is bounded
        # like _compiled (bucket ladder).
        self._staging_bufs: dict[tuple, tuple] = {}
        self._pending: list[tuple[Request, np.ndarray]] = []
        # builds the C++ planner now (not inside the first admission) and
        # says which one serves; a failed build was already logged loudly
        self.logger.infof("prefill planner: %s", planner_in_use())
        # prompts longer than the largest prefill bucket: admitted one at a
        # time and streamed into the cache chunk-by-chunk. Paged always
        # supports this (prefill_paged offsets); slot layouts need the
        # family's prefill to accept offsets (SLOT_CHUNKED_PREFILL flag).
        self._pending_long: list[tuple[Request, np.ndarray]] = []
        self._chunked_ok = (kv_layout == "paged"
                            or getattr(family, "SLOT_CHUNKED_PREFILL", False))
        self._admit_seq = 0  # admission order (preemption picks newest)
        self._base_key = jax.random.key(seed)
        self._step_count = 0
        self._dq: collections.deque = collections.deque()  # dispatched, unprocessed
        # every dispatch onto _dq takes the next number; its entry, the
        # loop.dispatch_* / loop.readback / loop.fold annotations and the
        # request's engine.prefill span carry it (docs/observability.md)
        self._dispatch_seq = 0
        self._phases = LoopPhases()  # host time of _loop by phase, for /metrics and the profiler
        # what a counting family's steps report (models/cohere2_moe.step_counters:
        # (counter, labels) a row), summed as the token readbacks bring them in
        # (decode.process_decode) and flushed at scrape (flush_step_counters)
        counters = getattr(family, "step_counters", None)
        self._step_counters = tuple(counters(cfg)) if callable(counters) else ()
        self._step_counts = {phase: np.zeros(len(self._step_counters), np.int64)
                             for phase in ("prefill", "decode")}
        self._step_counts_flushed = {phase: c.copy() for phase, c in self._step_counts.items()}
        self._prev_last = None  # device-resident [slots] last-sampled-token carry
        self._spec_carry = None  # device-resident ([slots] token, [slots] hlen)

        # -- multi-LoRA adapter plane (gofr_tpu.adapters; docs/serving.md) ---
        # Registry = host tier (named specs, per-adapter concurrency caps,
        # ADAPTER_HOST_MB budget); pool = device tier (fixed-shape HBM
        # slots, refcounted + LRU like KV pages; slot 0 is the reserved
        # all-zeros BASE adapter). The pool arrays ride every program call
        # as DYNAMIC jit args, so uploads/evictions — and the full-model
        # hot-swap below — never recompile. Disabled (the default), the
        # packed layouts and program signatures are byte-identical to the
        # pre-adapter engine.
        ad_slots = int(adapter_slots)
        ad_rank = max(1, int(adapter_rank))
        if adapter_pool_mb and not ad_slots:
            from gofr_tpu.adapters import AdapterPool

            ad_slots = AdapterPool.slots_for_budget(
                float(adapter_pool_mb), cfg.hidden_size, cfg.vocab_size, ad_rank)
        if ad_slots and lockstep_role:
            # the ENGINE_PREFIX_HOST_MB precedent (above): adapter uploads
            # are host-initiated device writes the announce stream cannot
            # reproduce on followers
            container.logger.warn(
                "ADAPTER_* ignored under lockstep (pool uploads cannot be "
                "announced to followers)")
            ad_slots = 0
        if ad_slots and not getattr(family, "SUPPORTS_ADAPTERS", False):
            raise ValueError(
                f"family {getattr(family, '__name__', family)!r} does not "
                "support per-lane adapters (no SUPPORTS_ADAPTERS entry "
                "points); drop ADAPTER_SLOTS/ADAPTER_POOL_MB")
        self._adapters_enabled = bool(ad_slots)
        self.adapters = None
        self._adapter_pool = None
        if self._adapters_enabled:
            from gofr_tpu.adapters import AdapterPool, AdapterRegistry

            self._adapter_pool = AdapterPool(
                max(2, ad_slots), cfg.hidden_size, cfg.vocab_size, ad_rank)
            self.adapters = AdapterRegistry(
                host_budget_mb=float(adapter_host_mb))
        # -- live weight hot-swap (adopt_weights / adopt_checkpoint) ---------
        # weights_epoch counts full-model adoptions; it feeds fleet.epoch_of
        # so router gossip sees a strict epoch bump and never routes one
        # request across mismatched weights (docs/serving.md).
        self.weights_epoch = 0
        self._pending_weights = None
        self._swap_lock = threading.Lock()
        hotswap_dir = str(adapter_hotswap_dir or "") or None
        if hotswap_dir and lockstep_role:
            container.logger.warn(
                "ADAPTER_HOTSWAP_DIR ignored under lockstep (weight adoption "
                "cannot be announced to followers)")
            hotswap_dir = None
        self._hotswap_dir = hotswap_dir
        self._hotswap_poll_s = max(0.5, float(adapter_hotswap_poll_s))
        self._hotswap_last = 0.0
        # steps already present at build time ARE the serving weights —
        # only checkpoints that appear later trigger adoption
        self._hotswap_seen = (self._scan_hotswap_steps()
                              if self._hotswap_dir else None)

        # -- quality plane (metrics/quality.py; docs/observability.md) -------
        # Shadow-score a sampled fraction of completed requests against the
        # reference configuration (dense bf16 KV, base weights), on idle
        # device-loop iterations only. Rate 0 (the default) never constructs
        # the plane: the serving path pays exactly one `is None` branch and
        # stays bit-identical to the pre-quality engine.
        self._quality = None
        rate = max(0.0, min(1.0, float(quality_shadow_rate)))
        if rate > 0.0 and not hasattr(family, "forward"):
            container.logger.warn(
                "QUALITY_SHADOW_RATE ignored: family "
                f"{getattr(family, '__name__', family)!r} has no teacher-"
                "forcing `forward` entry point")
            rate = 0.0
        if rate > 0.0:
            from gofr_tpu.metrics.quality import QualityPlane

            def _adapter_factors(name: str):
                if self.adapters is None:
                    return None
                try:
                    spec = self.adapters.get(name)
                except KeyError:
                    return None
                return (spec.a, spec.b, spec.scale)

            self._quality = QualityPlane(
                family, cfg,
                # late-bound: hot-swap replaces self.params; the reference
                # arm must always score with the CURRENTLY served weights
                lambda: self.params,
                metrics=self.metrics,
                slo=self.slo,
                rate=rate,
                # QUALITY_SEED unset (None / negative) → the engine's own
                # sampler seed, so one knob replays the shadow schedule too
                seed=(self._seed if quality_seed is None
                      or int(quality_seed) < 0 else int(quality_seed)),
                kv_dtype=self.kv_quantize or "bf16",
                backend_fn=self._decode_backend,
                adapter_fn=_adapter_factors,
                max_pending=quality_max_pending,
                max_tokens=quality_max_tokens,
                top1_min=quality_top1_min,
                kl_max=quality_kl_max,
                recent=quality_recent,
            )
        # per-adapter lifetime (accepted, proposed) speculative-decode
        # totals — the always-on quality proxy the container samples into
        # the app_tpu_spec_accept_ratio gauge (sum-of-parts, never averaged)
        self._spec_totals: dict[str, list[float]] = {}

        # Compiled packed-program handles (tpu/programs.py documents the
        # packed layouts; lockstep followers call the same handles).
        progs = build_programs(
            family, cfg,
            kv_layout=kv_layout,
            spec_tokens=self.spec_tokens,
            top_k=top_k,
            top_p=top_p,
            pages_per_slot=getattr(self, "pages_per_slot", 0),
            page_size=page_size,
            cache_len=getattr(self, "_cache_len", 0),
            prefill_attn_fn=prefill_attn_fn,
            draft=self._draft,
            adapters=self._adapters_enabled,
        )
        self._prefill_sample = progs.prefill_sample
        if progs.chunk_prefill is not None:
            self._chunk_prefill = progs.chunk_prefill
        self._decode_chunk = progs.decode_chunk
        if progs.spec_chunk is not None:
            self._spec_chunk_fn = progs.spec_chunk
        # per-g spec program map for the controller's spec_tokens knob: the
        # round length g is baked into the jitted spec round, so moving the
        # knob swaps the compiled handle rather than re-tracing mid-flight.
        # Build kwargs are kept so other g values (always < boot) compile
        # lazily on first use (_spec_fn_for); only spec_chunk is taken from
        # those rebuilds — every other program handle is g-independent.
        self._progs_kw = dict(
            kv_layout=kv_layout, top_k=top_k, top_p=top_p,
            pages_per_slot=getattr(self, "pages_per_slot", 0),
            page_size=page_size, cache_len=getattr(self, "_cache_len", 0),
            prefill_attn_fn=prefill_attn_fn, draft=self._draft,
            adapters=self._adapters_enabled)
        self._spec_fns = ({self.spec_tokens: progs.spec_chunk}
                          if progs.spec_chunk is not None else {})

        # Online step controller (gofr_tpu.control, docs/serving.md): OFF
        # by default — CONTROL_ENABLE=0 never constructs it, leaving the
        # engine bit-identical to the pre-controller build (the quality-
        # plane discipline). Lockstep replicas never get one either:
        # leader-only knob moves would change compiled signatures the
        # followers are not announced.
        if control_enable and self.perf is not None and lockstep_role is None:
            try:
                self._control = self._build_controller(container)
            except Exception as e:  # pragma: no cover - control must not gate serving
                container.logger.warn(f"step controller disabled: {e}")

        # lockstep announcer, last: a fleet LEADER starts listening here
        # and blocks until FLEET_FOLLOWERS identical-fingerprint followers
        # dialed in — the whole engine must exist first (the fingerprint
        # covers the resolved geometry, and admitted followers immediately
        # receive whatever warmup()/the device loop announces next)
        if lockstep_role == "leader":
            from gofr_tpu.tpu.lockstep import LockstepLeader

            if fleet is not None:
                from gofr_tpu.fleet import FleetLeaderChannel

                ch = FleetLeaderChannel(
                    fleet.listen, fingerprint=self.fleet_fingerprint(),
                    logger=self.logger, metrics=self.metrics)
                self._ls = LockstepLeader(channel=ch, epoch=fleet.epoch)
                self.metrics.set_gauge("app_fleet_epoch", self._ls.epoch)
                if fleet.followers:
                    self._ls.wait_ready(fleet.followers, fleet.ready_timeout_s)
                    self.metrics.set_gauge(
                        "app_fleet_followers", self._ls.follower_count())
                    self.logger.infof(
                        "fleet leader ready: %d follower(s) at epoch %d (port %d)",
                        self._ls.follower_count(), self._ls.epoch, ch.port)
            else:
                self._ls = LockstepLeader()

        # -- disaggregation handoff plumbing (tpu/handoff.py) ----------------
        # decode role: listen for KV frames from prefill workers; prefill
        # role: export to HANDOFF_TARGET (without a target the worker
        # decodes locally — the colocated fallback keeps it correct while
        # the decode pool is still coming up). handoff_addr rides the
        # gossip snapshot so the router's fleet view can show the wiring.
        self.handoff_timeout_s = float(handoff_timeout_s)
        # GOFR-HANDOFF2 streaming knobs (docs/serving.md "Streaming
        # handoff"): streams=0 forces the HANDOFF1 blob path outright;
        # chunk_pages batches staged pages per wire chunk; pace_mbps is
        # the emulated/egress bandwidth cap (0 = off)
        self.handoff_streams = max(0, int(handoff_streams))
        self.handoff_chunk_pages = max(1, int(handoff_chunk_pages))
        self.handoff_pace_mbps = max(0.0, float(handoff_pace_mbps))
        self._handoff_exporter = None
        self._handoff_server = None
        self.handoff_addr = ""
        if self.role == "decode":
            from gofr_tpu.tpu.handoff import HandoffServer

            self._handoff_server = HandoffServer(
                self, handoff_listen or "127.0.0.1:0",
                logger=self.logger, metrics=self.metrics)
            self.handoff_addr = self._handoff_server.addr
            self.logger.infof("kv handoff import listening at %s",
                              self.handoff_addr)
        elif self.role == "prefill":
            if handoff_target:
                from gofr_tpu.tpu.handoff import HandoffExporter

                self._handoff_exporter = HandoffExporter(
                    handoff_target, engine=self,
                    timeout_s=self.handoff_timeout_s,
                    streams=self.handoff_streams,
                    chunk_pages=self.handoff_chunk_pages,
                    pace_mbps=self.handoff_pace_mbps,
                    logger=self.logger, metrics=self.metrics)
            else:
                self.logger.warn(
                    "ENGINE_ROLE=prefill without HANDOFF_TARGET: prompts "
                    "decode locally (colocated fallback)")

    # -- public API ------------------------------------------------------------

    def warmup(self, len_buckets: list[int] | None = None,
               batch_buckets: list[int] | None = None) -> int:
        """Pre-compile every (prefill len-bucket × batch-bucket) signature
        plus the decode program, so no XLA compile lands inside the serving
        window (compiles cost seconds and would dominate early-traffic
        latency). Safe for cache contents: prefill warmup rows
        use out-of-bounds slot ids / block tables, whose scatter writes XLA
        drops; decode warmup writes are below any live slot's attention
        length mask. Call before serving traffic, not concurrently with it.
        Returns the number of programs compiled."""
        from gofr_tpu.ops.pallas import platform_hint

        lbs = sorted(len_buckets) if len_buckets else self.prefill_buckets
        bbs = sorted(batch_buckets) if batch_buckets else _pow2_buckets(1, self.max_prefill_batch)
        # same platform pin as the device thread (_run): without it, warmup
        # traces on the caller thread could resolve kernels for the wrong
        # backend (e.g. Pallas for a CPU test mesh under an attached TPU),
        # and jit would cache that mis-resolved program per shape
        with platform_hint(getattr(self.tpu, "platform", None)), self._trace_scope():
            # info-style gauge: 1 on the backend the rule resolves an op of
            # this engine's decode program to, 0 on the other
            for op, rec in self.autotune_report()["decisions"].items():
                for b in {"paged_append": ("fused", "scatter")}.get(op, ("pallas", "xla")):
                    self.metrics.set_gauge(
                        "app_tpu_kernel_backend", 1.0 if b == rec["backend"] else 0.0,
                        op=op, backend=b, kv_dtype=self.kv_quantize or "bf16")
                self.logger.infof("decode op %s -> %s (rule)", op, rec["backend"])
            return self._warmup_traced(lbs, bbs)

    def _warmup_traced(self, lbs: list[int], bbs: list[int]) -> int:
        # the compile body lives in the executor layer (tpu/executor.py,
        # warmup_compile) and is ROLE-scoped there: a prefill worker
        # skips the decode/spec compiles, a decode worker skips the
        # batched-prefill ladder — most of a role spare's warmup win
        return executor.warmup_compile(self, lbs, bbs)

    def _decode_op(self) -> str:
        """The decode attention op this engine's decode program traces
        (ops/attention.resolve_backend's ``op`` key; the slot int8 read,
        ``decode_q``, has no kernel)."""
        quant = {"int8": "_q", "int4": "_q4"}.get(self.kv_quantize or "", "")
        return ("paged_decode" if self.kv_layout == "paged" else "decode") + quant

    def _decode_backend(self) -> str:
        """What serves that op, read from the rule for this engine's platform."""
        from gofr_tpu.ops.attention import resolve_backend
        from gofr_tpu.ops.pallas import platform_hint

        with platform_hint(getattr(self.tpu, "platform", None)):
            return resolve_backend("auto", self._decode_op())

    def _append_backend(self) -> str:
        """Who writes a decode token's K/V into this engine's paged pool:
        ``fused`` — the paged-decode kernel's own call appends and attends
        (ops/attention.append_rides_in_kernel: the dense pool, where that
        kernel serves and can address the plane's rows) — or ``scatter``
        (ops/paged.append_tokens_paged*, then the read path)."""
        from gofr_tpu.ops.attention import append_rides_in_kernel
        from gofr_tpu.ops.paged import PagedKVCache
        from gofr_tpu.ops.pallas import platform_hint

        cache = self.cache  # models/llama._append_attend_paged asks the same of the pool it is given
        with platform_hint(getattr(self.tpu, "platform", None)):
            fused = isinstance(cache, PagedKVCache) and append_rides_in_kernel(cache.k)
        return "fused" if fused else "scatter"

    def flush_step_counters(self, metrics) -> None:
        """Scrape-time export of a counting family's step counters (the
        loop-phase counters' discipline): add what the readbacks brought in
        since the last call, by the kind of program that counted it
        (``phase`` = ``prefill`` — whole and chunked — or ``decode``)."""
        for phase, live in self._step_counts.items():
            counts = live.copy()
            for (name, labels), now, done in zip(
                    self._step_counters, counts, self._step_counts_flushed[phase]):
                metrics.increment_counter(name, float(now - done), phase=phase, **labels)
            self._step_counts_flushed[phase] = counts

    def autotune_report(self) -> dict:
        """Which backend serves this engine's decode op — and, for a paged
        pool, which path writes a decode token's K/V (``paged_append``) — in
        the shape benchmarks/run.py, chip_smoke.py and /debug/engine read
        (the name is theirs; nothing is tuned — the rules in ops/attention
        decide)."""
        decisions = {self._decode_op(): {"backend": self._decode_backend(), "source": "rule"}}
        if self.kv_layout == "paged":
            decisions["paged_append"] = {"backend": self._append_backend(), "source": "rule"}
        return {"decisions": decisions}

    def spec_accept_totals(self) -> dict[str, tuple[float, float]]:
        """Lifetime per-adapter (accepted, proposed) speculative-decode
        token totals ("base" = no adapter). Raw summable numerators — the
        container divides at scrape time, federation sums across engines."""
        with self._obs_lock:
            return {k: (v[0], v[1]) for k, v in self._spec_totals.items()}

    def quality_snapshot(self) -> dict | None:
        """The /debug/quality + capture-bundle join: plane totals and recent
        divergence reports, keyed by the serving state that produced them —
        decode backend, weights epoch, kv dtype — plus the replay config
        scripts/replay_bundle.py needs to re-execute samples offline."""
        if self._quality is None:
            return None
        snap = self._quality.snapshot()
        snap["weights_epoch"] = self.weights_epoch
        snap["backend"] = self._decode_backend()
        snap["replay"] = self.replay_config()
        return snap

    def replay_config(self) -> dict:
        """Everything scripts/replay_bundle.py needs to rebuild THIS engine
        offline: model family/config, sampler seed, the engine knobs that
        shape compiled programs, adapter digest, weights epoch, fingerprint,
        and the chaos spec that was armed (corruption is part of the repro)."""
        import dataclasses

        cfg = self.cfg
        cfg_d = None
        if dataclasses.is_dataclass(cfg):
            cfg_d = dataclasses.asdict(cfg)
            dt = cfg_d.get("dtype")
            if dt is not None:
                cfg_d["dtype"] = jnp.dtype(dt).name
        return {
            "family": getattr(self.family, "__name__",
                              type(self.family).__name__).rsplit(".", 1)[-1],
            "config": cfg_d,
            "seed": self._seed,
            "engine": {
                "slots": self.num_slots,
                "max_len": self.max_len,
                "decode_chunk": self.decode_chunk,
                "kv_layout": self.kv_layout,
                "page_size": self.page_size if self.kv_layout == "paged" else 0,
                "total_pages": getattr(self, "total_pages", 0),
                "spec_tokens": self.spec_tokens,
                "kv_quantize": self.kv_quantize,
                "kv_shards": getattr(self, "kv_shards", 1),
                "top_k": self.top_k,
                "top_p": self.top_p,
            },
            "weights_epoch": self.weights_epoch,
            "adapter_digest": self.adapters_digest(),
            "fingerprint": self.fleet_fingerprint(),
            # the LIVE armed spec (env or test override), not the env var:
            # an armed corruption is part of the deterministic repro
            "chaos": chaos.active_spec(),
        }

    def page_pool_stats(self) -> dict | None:
        """Paged-pool waste view for the perf plane: occupancy (allocated
        fraction of usable pages) and fragmentation (claimed page positions
        no live sequence has written yet — trailing partial pages plus
        spec over-claim not yet trimmed). None on the slot layout."""
        if self.kv_layout != "paged":
            return None
        with self._state_lock:
            free = len(self._free_pages)
            held = sum(len(p) for p in self._slot_pages)
            live = sum(s.pos for s in self.slots if s is not None)
        covered = held * self.page_size
        # Byte fields are SHARD-LOCAL (per-device): on a tp-sharded pool
        # each device holds 1/kv_shards of every plane, and a fleet rollup
        # that sums parts must see parts, not the logical-global footprint
        # multiplied per engine. Occupancy/fragmentation are ratios over
        # page COUNTS (replicated bookkeeping) and are shard-invariant.
        shards = max(1, getattr(self, "kv_shards", 1))
        return {
            "total_pages": self.total_pages,
            "free_pages": free,
            "slot_pages": held,
            "kv_shards": shards,
            "page_bytes_device": getattr(self, "_page_bytes", 0) // shards,
            "pool_bytes_device": getattr(self, "_pool_bytes", 0) // shards,
            "occupancy": round(1.0 - free / self.total_pages, 4),
            "fragmentation": round(1.0 - min(1.0, live / covered), 4)
            if covered else 0.0,
        }

    def submit(
        self,
        prompt: Any,
        max_new_tokens: int = 64,
        temperature: float = 0.0,
        timeout: float | None = None,
        **kw: Any,
    ) -> Request:
        """Non-blocking enqueue: returns the Request future (``.result()``
        blocks; ``.cancel()`` frees the slot). One caller thread can keep
        hundreds of generations in flight — the shape async transports use."""
        return self._submit(
            prompt, timeout,
            max_new_tokens=max_new_tokens, temperature=temperature, **kw,
        )

    def generate(
        self,
        prompt: Any,
        max_new_tokens: int = 64,
        temperature: float = 0.0,
        timeout: float | None = None,
        stream: bool = False,
        **kw: Any,
    ):
        """Generate a completion. ``prompt`` is a string (needs a
        tokenizer) or a sequence of token ids. Greedy when temperature=0.
        ``stream=True`` returns an iterator of tokens (strings when a
        tokenizer is attached) instead of blocking for the full result."""
        req = self._submit(
            prompt, timeout, stream=stream,
            max_new_tokens=max_new_tokens, temperature=temperature, **kw,
        )
        if stream:
            return self._stream_iter(req, timeout)
        return req.result(timeout if timeout is not None else self.default_timeout)

    def infer(self, inputs: Any, **kw: Any):
        return self.generate(inputs, **kw)

    def _stream_iter(self, req: Request, timeout: float | None) -> "_StreamIterator":
        per_token_timeout = timeout if timeout is not None else self.default_timeout

        def it():
            while True:
                try:
                    item = req.stream_q.get(timeout=per_token_timeout or 3600.0)
                except queue.Empty:
                    req.cancel()
                    raise RequestTimeout() from None
                if item is None:
                    # surface a terminal error (engine death) if any
                    if req._error is not None:
                        raise req._error
                    return
                yield item

        return _StreamIterator(req, it())

    def _announce(self, tag: int, a: int, b: int, packed) -> None:
        if self._ls is not None:
            self._ls.announce(tag, a, b, packed)

    def stop(self) -> None:
        super().stop()
        if self._handoff_exporter is not None:
            self._handoff_exporter.close()
        if self._handoff_server is not None:
            self._handoff_server.close()
        if self._ls is not None and not self._poisoned:
            # after a CLEAN device-thread join no concurrent collective can
            # interleave with the terminal broadcast. A wedged thread may
            # still be inside one — broadcasting would corrupt the stream;
            # followers must be torn down externally then (lockstep.py).
            self._ls.stop()

    def serve_follower(self) -> None:
        """Run this process as a lockstep FOLLOWER (multi-host serving,
        tpu/lockstep.py): blocks executing the leader's announced programs
        until the leader stops. Do not call start(). With
        LOCKSTEP_DEADLINE_S set, a liveness watchdog hard-exits this
        process if the leader goes silent (kill -9/OOM — lockstep.py).

        Under a fleet config (FLEET_LEADER) the announce stream rides the
        host-side channel instead of the device collective: this dials the
        leader (retrying for FLEET_CONNECT_TIMEOUT_S), replays its epochs,
        and on leader loss REDIALS for FLEET_REJOIN_S before declaring the
        leader dead — the epoch-based warm rejoin (docs/parallelism.md)."""
        if self.lockstep_role != "follower":
            raise RuntimeError("engine was not built with lockstep_role='follower'")
        from gofr_tpu.tpu.lockstep import LockstepFollower

        deadline = self.container.config.get_float("LOCKSTEP_DEADLINE_S", 0.0)
        if self._fleet is not None:
            from gofr_tpu.fleet import FleetFollowerChannel

            channel = FleetFollowerChannel(
                self._fleet.leader, fingerprint=self.fleet_fingerprint(),
                connect_timeout_s=self._fleet.connect_timeout_s,
                rejoin_timeout_s=self._fleet.rejoin_timeout_s,
                logger=self.logger)
            channel.connect()
            try:
                LockstepFollower(self, deadline_s=deadline, channel=channel).run()
            finally:
                channel.close()
            return
        LockstepFollower(self, deadline_s=deadline).run()

    # -- multi-LoRA adapters (gofr_tpu.adapters; docs/serving.md) --------------

    def register_adapter(self, spec) -> None:
        """Install (or replace) a named LoRA adapter for serving. Host-tier
        registration only — the device upload happens lazily at the first
        admission that names it (AdapterPool.acquire). Replacing an adapter
        whose pool slot is referenced by a live lane raises: weights must
        never change under an in-flight request (drain first)."""
        if not self._adapters_enabled:
            raise RuntimeError(
                "engine built without the adapter plane; set ADAPTER_SLOTS "
                "or ADAPTER_POOL_MB")
        if spec.rank > self._adapter_pool.rank:
            raise ValueError(
                f"adapter {spec.name!r} rank {spec.rank} exceeds the pool "
                f"rank {self._adapter_pool.rank} (ADAPTER_RANK)")
        with self._state_lock:
            self.adapters.register(spec, pool=self._adapter_pool)
        self.metrics.set_gauge(
            "app_tpu_adapters_registered", len(self.adapters.names()))

    def unregister_adapter(self, name: str) -> None:
        """Remove an adapter from both tiers. Raises while lanes still
        reference its pool slot (same discipline as register-replace)."""
        if not self._adapters_enabled:
            return
        with self._state_lock:
            self.adapters.unregister(name, pool=self._adapter_pool)
        self.metrics.set_gauge(
            "app_tpu_adapters_registered", len(self.adapters.names()))

    def adapter_stats(self) -> dict[str, Any]:
        """Both tiers' occupancy + the weights epoch, for /debug/engine."""
        if not self._adapters_enabled:
            return {"enabled": False, "weights_epoch": self.weights_epoch}
        with self._state_lock:
            pool = self._adapter_pool.stats()
        out = {"enabled": True, "registry": self.adapters.stats(),
               "pool": pool, "weights_epoch": self.weights_epoch}
        return out

    def adapters_digest(self) -> str:
        """Adapter-set fingerprint for the handoff JOIN gate (empty when
        the plane is disabled — pre-adapter peers send/expect nothing)."""
        return self.adapters.digest() if self._adapters_enabled else ""

    def _adapter_args(self) -> tuple:
        """The device pool triple threaded into every adapter-enabled
        program call as trailing DYNAMIC jit args (tpu/programs.py) —
        uploads and hot-swaps never recompile."""
        p = self._adapter_pool
        return (p.a, p.b, p.scale)

    def _acquire_adapter(self, req: Request):
        """Resolve ``req``'s adapter to a device pool slot at admission
        (caller holds the state lock). Returns ``(adapter_id, pool_slot)``
        when bound — base requests bind ``(None, 0)`` — the string
        ``"wait"`` when every pool slot is referenced by a live lane (the
        caller requeues, exactly like KV page exhaustion), or ``None``
        when the adapter vanished since submission (the request was failed
        here)."""
        name = req.kw.get("_adapter")
        if not name or not self._adapters_enabled:
            return (None, 0)
        try:
            spec = self.adapters.get(name)
        except KeyError as e:
            req.complete(error=ValueError(
                str(e.args[0]) if e.args else str(e)))
            return None
        aslot = self._adapter_pool.acquire(spec)
        if aslot is None:
            return "wait"
        return (name, aslot)

    # -- live weight hot-swap (zero-drop; docs/serving.md) ---------------------

    def adopt_weights(self, new_params, *, timeout_s: float | None = 30.0) -> int:
        """Adopt a full replacement weight tree with no restart and no
        dropped requests: the device loop drains the in-flight queue,
        requeues slot-resident work whole (preemption-by-recompute — a
        request either finished on the old weights or re-enters the queue
        as a fresh prefill; tokens from the two epochs never mix inside
        one decode step), resets per-epoch device state (the prefix cache
        and KV pages carry old-weight K/V), swaps ``params`` and bumps
        ``weights_epoch`` — which feeds fleet.epoch_of, so router gossip
        sees a strict epoch bump. Returns the new epoch. Blocks up to
        ``timeout_s`` for the adoption (None = stage and return)."""
        if self.lockstep_role:
            raise RuntimeError(
                "live weight hot-swap is not supported under lockstep "
                "(weight adoption cannot be announced to followers)")
        new_params = self._match_weights(new_params)
        done = threading.Event()
        with self._swap_lock:
            self._pending_weights = (new_params, done)
        if self._thread is None or not self._thread.is_alive():
            # not serving yet (tests, pre-start swap): adopt inline
            self._apply_pending_weights()
            return self.weights_epoch
        if timeout_s is not None and not done.wait(timeout_s):
            raise TimeoutError(
                f"weight hot-swap not adopted within {timeout_s:.1f}s")
        return self.weights_epoch

    def adopt_checkpoint(self, directory: str, *,
                         timeout_s: float | None = 30.0) -> int:
        """Adopt the latest orbax checkpoint under ``directory``
        (train/checkpoint.py layout) as the serving weights — the scripted
        train→serve hot-swap path. The raw tree is resolved through the
        same post-processing the ctor weights got (mesh sharding, weight
        quantization when the serving tree is quantized)."""
        from gofr_tpu.train.checkpoint import load_params

        like = jax.eval_shape(
            lambda: self.family.init(self.cfg, jax.random.key(0)))
        raw = load_params(directory, like)
        return self.adopt_weights(self._prepare_weights(raw),
                                  timeout_s=timeout_s)

    def _match_weights(self, new_params):
        """Validate a replacement tree against the serving tree: identical
        structure, shapes, and dtypes — anything else would recompile
        every program (or garble decode) mid-serving. A draft-spec engine
        may pass just the target tree; the live draft is grafted in."""
        if (self._draft is not None and isinstance(self.params, dict)
                and not (isinstance(new_params, dict) and "t" in new_params)):
            new_params = {"t": new_params, "d": self.params["d"]}
        if jax.tree.structure(new_params) != jax.tree.structure(self.params):
            raise ValueError(
                "adopt_weights: replacement tree structure does not match "
                "the serving tree (same family/config/quantization required)")
        for new, old in zip(jax.tree.leaves(new_params),
                            jax.tree.leaves(self.params)):
            if (tuple(new.shape) != tuple(old.shape)
                    or jnp.asarray(new).dtype != jnp.asarray(old).dtype):
                raise ValueError(
                    f"adopt_weights: leaf {tuple(new.shape)}/{new.dtype} != "
                    f"serving {tuple(old.shape)}/{old.dtype}")
        return new_params

    def _prepare_weights(self, raw):
        """Run a raw (checkpoint) tree through the ctor weights' post-
        processing: shard over the mesh by the family's logical axes, then
        weight-only quantization when the serving tree is quantized."""
        rules = getattr(self.tpu, "rules", None)
        mesh = getattr(self.tpu, "mesh", None)
        if rules is not None:
            raw = shard_pytree(raw, self.family.param_axes(self.cfg),
                               rules, mesh)
        target = (self.params["t"] if self._draft is not None
                  else self.params)
        if jax.tree.structure(raw) != jax.tree.structure(target):
            from gofr_tpu.ops.quant import quantize_tree

            raw = jax.jit(quantize_tree)(raw)
        return raw

    def _apply_pending_weights(self) -> bool:
        """Device-loop half of the hot-swap (also run inline pre-start):
        the zero-drop drain. Mirrors ``_fleet_admit``'s epoch bump — fold
        every in-flight device call, requeue slot-resident work whole via
        preemption-by-recompute, reset per-epoch device state OUTSIDE the
        lock, then swap the tree and bump the epoch."""
        with self._swap_lock:
            pending, self._pending_weights = self._pending_weights, None
        if pending is None:
            return False
        new_params, done = pending
        while self._dq:
            process_decode(self)
        with self._state_lock:
            while self._preempt_newest():
                pass
        # outside the lock — _reset_device_state blocks on still-executing
        # device work first (_drain_device_state), and that wait must never
        # run under _state_lock (the _fleet_admit discipline)
        self._reset_device_state()
        self.params = new_params
        self.weights_epoch += 1
        self.metrics.set_gauge("app_tpu_weights_epoch", self.weights_epoch)
        self.metrics.increment_counter("app_tpu_weight_swaps_total", 1)
        self.logger.warn(
            f"live weight hot-swap adopted (weights epoch "
            f"{self.weights_epoch}); slot-resident work requeued")
        done.set()
        return True

    def _scan_hotswap_steps(self) -> int | None:
        """Newest checkpoint step under ADAPTER_HOTSWAP_DIR, by a light
        directory scan — orbax step dirs are bare integers and appear
        atomically (saves land in a tmp dir and rename), so this never
        opens a CheckpointManager on the device thread's poll path."""
        try:
            steps = [int(d) for d in os.listdir(self._hotswap_dir)
                     if d.isdigit()]
        except OSError:
            return None
        return max(steps) if steps else None

    def _poll_hotswap(self) -> None:
        """Device-loop tick: adopt any checkpoint step newer than the last
        one seen (throttled to ADAPTER_HOTSWAP_POLL_S)."""
        now = time.monotonic()
        if now - self._hotswap_last < self._hotswap_poll_s:
            return
        self._hotswap_last = now
        step = self._scan_hotswap_steps()
        if step is None or (self._hotswap_seen is not None
                            and step <= self._hotswap_seen):
            return
        self._hotswap_seen = step
        try:
            from gofr_tpu.train.checkpoint import load_params

            like = jax.eval_shape(
                lambda: self.family.init(self.cfg, jax.random.key(0)))
            raw = load_params(self._hotswap_dir, like)
            with self._swap_lock:
                self._pending_weights = (
                    self._match_weights(self._prepare_weights(raw)),
                    threading.Event())
            self._apply_pending_weights()
        except Exception as e:  # noqa: BLE001 - a bad checkpoint must not kill serving
            self.logger.log_exception(e, "hot-swap checkpoint adoption")

    # -- device loop -----------------------------------------------------------

    def _encode_prompt(self, prompt: Any) -> np.ndarray:
        if isinstance(prompt, str):
            if self.tokenizer is None:
                raise ValueError("string prompt but engine has no tokenizer; pass token ids")
            return np.asarray(self.tokenizer.encode(prompt), np.int32)
        return np.asarray(prompt, np.int32)

    def _fail_all(self, error: Exception) -> None:
        """Slot-resident requests must fail too — without this, a caller of a
        request already admitted into a slot would block forever when the
        engine stops with a wedged device thread."""
        super()._fail_all(error)
        with self._state_lock:
            for i, s in enumerate(self.slots):
                if s is not None:
                    self._free_slot(i)
                    s.request.complete(error=error)

    def _crash_recover(self, error: Exception) -> None:
        """Slot-resident requests rode the crashed device state — fail them
        and reset slot/page bookkeeping; queued/pending prompts survive and
        re-plan after the restart."""
        super()._crash_recover(error)
        with self._state_lock:
            for i, s in enumerate(self.slots):
                if s is not None:
                    self._free_slot(i)
                    s.request.complete(error=error)
        # The crashed call may have DONATED the cache buffer before
        # dying — self.cache can reference a deleted array, and every
        # post-restart step would fail on it, burning the whole restart
        # budget on one fault. Rebuild it (all slots are empty now);
        # _reset_device_state first SETTLES still-executing dispatches, so
        # the rebuild cannot reuse memory a stale program is writing into.
        self._reset_device_state()

    def _drain_device_state(self) -> None:
        """Settle every possibly-still-executing device computation of the
        dying epoch BEFORE its buffers are dropped. A host-side crash (or
        an epoch bump) can leave dispatched calls running: rebinding
        ``self.cache``/clearing ``_dq`` frees their output buffers, and the
        allocator may hand that memory to the NEXT epoch's fresh cache
        while the stale program is still writing into it — scribbling the
        new state (observed as deterministic-under-load token corruption in
        the fleet chaos drill). Blocking here bounds recovery by the last
        step's runtime. A crashed program raising out of the wait is
        expected — its buffers are settled either way. NEVER call this
        holding the state lock: a truly wedged program would then deadlock
        ``stop()``'s ``_fail_all`` behind the lock forever (the wedged path
        must stay poison-and-abandon, lockstep.py semantics)."""
        for entry in list(self._dq):
            try:
                jax.block_until_ready(entry[1])
            except Exception:  # noqa: BLE001 - crashed call: settled anyway
                pass
        self._dq.clear()
        for ref in (self.cache, self._prev_last, self._spec_carry):
            if ref is not None:
                try:
                    jax.block_until_ready(ref)
                except Exception:  # noqa: BLE001
                    pass

    def _place_cache(self, cache):
        """Cache placement shared by the ctor and every rebuild site: commit
        the cache to the engine's mesh BEFORE any program sees it. jit keys
        its compiled programs on whether each argument is committed and how
        it is sharded, and every program returns a committed cache — so an
        uncommitted fresh cache makes the first program that touches it
        compile once for the fresh cache and AGAIN, inside serving, for the
        committed one (the same after every crash-restart rebuild). Under
        lockstep the placement must also be a GLOBAL array on the mesh, or
        the first rebuilt-cache program would re-place it differently from
        the other processes. A tp-sharded pool keeps its plane sharding
        (head axis split, everything else — spec history — replicated);
        unsharded leaves place replicated. On one device this re-labels
        the buffer; it does not copy it."""
        from jax.sharding import NamedSharding, PartitionSpec as _P

        from gofr_tpu.ops.paged import plane_partition_spec

        sharded = getattr(self, "kv_shards", 1) > 1

        def place(leaf):
            if leaf.committed and not self.lockstep_role:
                # built under its own sharding (tp pool planes, the pp
                # family's layer-sharded cache): already where it serves
                return leaf
            spec = (plane_partition_spec(leaf.ndim)
                    if sharded and leaf.ndim >= 4 else _P())
            return jax.device_put(leaf, NamedSharding(self.tpu.mesh, spec))

        return jax.tree.map(place, cache)

    def _zero_carry(self):
        """A fresh all-zeros [slots] int32 device carry (the decode
        ``prev_last`` / each half of the spec carry before any token was
        sampled), committed like the carries the programs return — an
        uncommitted one would cost a second compile of the decode program
        the first time a returned carry is fed back (see _place_cache)."""
        return self._place_cache(jnp.zeros((self.num_slots,), jnp.int32))

    def _reset_device_state(self) -> None:
        """Reset every piece of per-epoch device state to its virgin value:
        fresh cache (the crashed call may have donated the old buffer; a
        fleet epoch bump needs leader and followers on identical state),
        empty page pool/tables, no decode or spec carries. Slots must
        already be empty (failed by _crash_recover or requeued by
        _fleet_admit); weights and compiled programs are untouched — this
        is the warm part of warm-rejoin. Safe on followers (their slot
        bookkeeping is never populated) and re-entrant under the state
        lock."""
        self._drain_device_state()  # before the lock — see its docstring
        with self._state_lock:
            if self.kv_layout == "paged":
                self.cache = self._place_cache(self._build_paged_cache())
                self._free_pages = list(range(self.total_pages))
                self._slot_pages = [[] for _ in range(self.num_slots)]
                self._table = np.full(
                    (self.num_slots, self.pages_per_slot), self.total_pages, np.int32
                )
                self._page_refs[:] = 0
                self._pending_swapins = []
                self._pending_spills = []
                if self._prefix is not None:
                    # cached pages (both tiers) rode the dead epoch's device
                    # state; the gauges must say so (a stale cached_pages /
                    # host_pages reading after a reset would misreport
                    # capacity until the next eviction touched them)
                    self._prefix.clear()
                    self._set_prefix_gauges()
            else:
                self.cache = self._place_cache(self._build_slot_cache())
            self._prev_last = None
            self._spec_carry = None  # rode the same dead-epoch device state

    def fleet_fingerprint(self) -> str:
        """Engine-config fingerprint for the fleet handshake: two processes
        form a fleet only when everything that determines the compiled
        programs and the replayed state transitions is identical
        (fleet/channel.py rejects mismatches at the door)."""
        from gofr_tpu.fleet import fingerprint_of

        return fingerprint_of(
            getattr(self.family, "__name__", type(self.family).__name__),
            self.cfg, self._seed, self.num_slots, self.max_len,
            self.decode_chunk, self.prefill_buckets, self.max_prefill_batch,
            self.kv_layout, self.page_size if self.kv_layout == "paged" else 0,
            getattr(self, "total_pages", 0), self.spec_tokens,
            self.kv_quantize, self.top_k, self.top_p,
            getattr(self, "kv_shards", 1),
        )

    def _fleet_admit(self) -> bool:
        """Step-boundary membership change (device thread, loop top): when
        followers are parked in the channel's pending set — fresh joins,
        rejoins after a leader or follower death — bump the fleet epoch and
        bring EVERYONE onto identical virgin per-epoch state. Slot-resident
        work is REQUEUED by recompute (the preemption machinery), not
        failed: the leader's device state is healthy here, so nothing is
        lost — requests re-prefill under the new epoch and their replay is
        announced to the whole (new) fleet."""
        ls = self._ls
        if ls is None or not ls.has_pending():
            return False
        # drain in-flight device work first: queued folds reference the
        # pre-bump cache and slot objects
        while self._dq:
            process_decode(self)
        with self._state_lock:
            while self._preempt_newest():
                pass
        # outside the lock: _reset_device_state blocks on still-executing
        # device work first (_drain_device_state), and that wait must never
        # run under _state_lock — a wedged program would deadlock stop()'s
        # _fail_all behind the lock. Slots cannot repopulate in the gap:
        # admission runs on this (device) thread only.
        self._reset_device_state()
        n = ls.admit_pending()
        self.metrics.set_gauge("app_fleet_epoch", ls.epoch)
        self.metrics.set_gauge("app_fleet_followers", ls.follower_count())
        self.metrics.increment_counter("app_fleet_rejoins_total", n)
        self.logger.warn(
            f"fleet: admitted {n} follower(s) at epoch {ls.epoch} "
            f"({ls.follower_count()} active); slot-resident work requeued"
        )
        return True

    # -- scale-in drain (fleet/autoscaler.py; docs/resilience.md) --------------

    def begin_drain(self) -> None:
        """Flip the replica into draining: _submit sheds new arrivals with a
        retryable 503 and _admit_prefill stops claiming slots for queued
        work. In-flight slot work is untouched — streams keep streaming."""
        self._draining = True
        self.metrics.set_gauge("app_tpu_draining", 1)

    def abort_drain(self) -> None:
        """Drain abort (autoscaler re-admit after death-mid-drain chaos or a
        failed scale-in): back to serving — admission resumes on the very
        next loop iteration; nothing was torn down."""
        self._draining = False
        self.metrics.set_gauge("app_tpu_draining", 0)

    def drain_queued(self) -> list[Request]:
        """Pull every queued-but-unadmitted request off this replica for
        requeue onto a peer (fleet.autoscaler.requeue). Must run AFTER
        begin_drain: _admit_prefill holds the state lock across its whole
        queue→pending→slot move and returns early while draining, so under
        the same lock nothing can be half-moved here."""
        out: list[Request] = []
        with self._state_lock:
            while True:
                try:
                    out.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            out.extend(r for r, _ in self._pending)
            self._pending = []
            out.extend(r for r, _ in self._pending_long)
            self._pending_long = []
        self.metrics.set_gauge("app_tpu_queue_depth", self._backlog())
        return out

    def drained(self) -> bool:
        """True once every slot is empty and no device work is in flight —
        the point where retiring the process drops zero streams."""
        with self._state_lock:
            return all(s is None for s in self.slots) and not self._dq

    def drain(self, *, timeout_s: float = 30.0) -> list[Request]:
        """The scale-in drain entrypoint: stop admitting, hand back queued
        work for peer requeue, and wait for in-flight streams to finish.
        Past ``timeout_s`` the stragglers are cooperatively cancelled (the
        PR10 lifetime plane frees their slots and KV pages) with a bounded
        grace for the reclaim. Returns the requests the caller must requeue;
        the chaos point ``replica.drain`` fires after the flag flips, so an
        injected fault leaves the engine draining — exactly the state a
        replica that died mid-drain is in — for the autoscaler's
        abort→re-admit path to undo."""
        self.begin_drain()
        chaos.fire("replica.drain")
        pending = self.drain_queued()
        deadline = time.monotonic() + max(0.0, timeout_s)
        cancelled = False
        while not self.drained():
            if time.monotonic() >= deadline:
                if cancelled:
                    break
                with self._state_lock:
                    for s in self.slots:
                        if s is not None:
                            s.request.cancel("drain_timeout")
                cancelled = True
                deadline = time.monotonic() + 5.0  # reclaim grace
            time.sleep(0.01)
        return pending

    # -- slot/page bookkeeping -------------------------------------------------

    def _build_slot_cache(self):
        """One construction site for ctor AND crash-restart rebuild. With
        speculative decoding on, the cache is a 2-tuple pytree: (kv, hist)
        for prompt-lookup — the device-resident token history the
        prefill/spec programs maintain (tpu/programs.py), so the host never
        ships history — or (kv, draft_kv) with a draft model."""
        kv = (self.family.make_cache_q(self.cfg, self.num_slots, self._cache_len)
              if self.kv_quantize
              else self.family.make_cache(self.cfg, self.num_slots, self._cache_len))
        if self._draft is not None:
            dfam, dcfg = self._draft
            return (kv, dfam.make_cache(dcfg, self.num_slots, self._cache_len))
        if self.spec_tokens:
            return (kv, jnp.zeros((self.num_slots, self._cache_len), jnp.int32))
        return kv

    @property
    def kv_cache(self):
        """The KV pool alone, regardless of whether the live cache is the
        bare pool or the (kv, hist) 2-tuple spec decoding wraps around it.
        Page-granular plumbing (page-byte accounting, gather_page eviction
        and handoff export, swap-in protos) targets the pool only — the
        history plane is slot-indexed, not page-indexed."""
        return self.cache[0] if isinstance(self.cache, tuple) else self.cache

    def _paged_make_fn(self):
        if self.kv_quantize == "int4":
            return self.family.make_paged_cache_q4
        if self.kv_quantize:
            return self.family.make_paged_cache_q
        return self.family.make_paged_cache

    def _resolve_kv_shard(self, kv_shard: str):
        """(shards, pool NamedSharding) for ENGINE_KV_SHARD: 'off'/'0' → 1
        (unsharded, today's placement bit-for-bit); 'auto' → the mesh's tp
        size when the geometry can split (tp > 1, head counts divide, the
        family's cache constructor takes a sharding); explicit 'tp' raises
        when it can't — an operator who asked for sharding must not get a
        silently replicated pool."""
        mode = str(kv_shard or "auto").strip().lower()
        if mode in ("", "0", "off", "none", "no"):
            return 1, None
        if mode not in ("auto", "1", "tp"):
            raise ValueError(
                f"unknown ENGINE_KV_SHARD {kv_shard!r}; use 'auto', 'tp' or 'off'")
        import inspect

        axis = "tp"
        mesh = getattr(self.tpu, "mesh", None)
        tp = 0
        if mesh is not None and axis in getattr(mesh, "axis_names", ()):
            tp = int(mesh.shape[axis])
        hkv = int(getattr(self.cfg, "num_kv_heads", 0) or 0)
        hq = int(getattr(self.cfg, "num_heads", 0) or 0)
        try:
            supports = "sharding" in inspect.signature(self._paged_make_fn()).parameters
        except (TypeError, ValueError):
            supports = False
        why = None
        if tp <= 1:
            why = "mesh has no tp axis with more than one device"
        elif not supports:
            why = "family cache constructor takes no sharding"
        elif hkv <= 0 or hkv % tp or hq <= 0 or hq % tp:
            why = (f"head counts (num_heads={hq}, num_kv_heads={hkv}) do not "
                   f"divide by tp={tp}")
        if why is not None:
            if mode == "tp":
                raise ValueError(f"ENGINE_KV_SHARD=tp impossible: {why}")
            return 1, None
        from gofr_tpu.ops.paged import pool_sharding

        return tp, pool_sharding(mesh, axis)

    def _kv_shard_ctx(self):
        """The paged.KVShardCtx this engine pins for its traces, or None."""
        if getattr(self, "kv_shards", 1) <= 1:
            return None
        from gofr_tpu.ops.paged import KVShardCtx

        return KVShardCtx(self.tpu.mesh, "tp", self.kv_shards)

    def _build_paged_cache(self):
        """One construction site for ctor AND crash-restart rebuild: the
        two must always agree on the cache kind (int4 vs int8 vs dense).
        With speculative decoding on, the paged cache is the same 2-tuple
        pytree the slot layout uses — (kv, hist), hist [num_slots, Hcap]
        int32 with Hcap = pages_per_slot * page_size — so the device keeps
        the prompt-lookup history and spec rounds ride the pipeline without
        the host shipping history rows every dispatch (tpu/programs.py).
        A sharded pool is allocated DIRECTLY under its NamedSharding (no
        replicated transient); the hist plane is slot-indexed, not
        head-indexed, so it stays replicated on the same mesh."""
        make = self._paged_make_fn()
        if self._kv_pool_sharding is not None:
            kv = make(self.cfg, self.total_pages, self.page_size,
                      sharding=self._kv_pool_sharding)
        else:
            kv = make(self.cfg, self.total_pages, self.page_size)
        if self.spec_tokens:
            hcap = self.pages_per_slot * self.page_size
            hist = jnp.zeros((self.num_slots, hcap), jnp.int32)
            if self._kv_pool_sharding is not None:
                from jax.sharding import NamedSharding, PartitionSpec as _P

                hist = jax.device_put(hist, NamedSharding(self.tpu.mesh, _P()))
            return (kv, hist)
        return kv

    def _ref_page(self, p: int) -> None:
        self._page_refs[p] += 1

    def _unref_page(self, p: int) -> None:
        self._page_refs[p] -= 1
        if self._page_refs[p] == 0:
            self._free_pages.append(p)

    # staging buffers per (kind, shape) rotate through a ring this long
    # before reuse. One shared buffer is NOT safe: the host→device fetch of
    # a dispatched call's packed input is asynchronous PER DEVICE REPLICA
    # (jnp.asarray does not copy for every device before dispatch returns),
    # so rewriting the buffer for the next same-kind dispatch can corrupt
    # what a lagging replica reads — divergent per-device KV writes, then
    # garbage collectives (found by the fleet chaos drill: deterministic
    # wrong tokens after a crash-restart under load). A device cannot lag a
    # full ring behind the newest dispatch: every program carries a
    # collective, so all replicas advance together within the bounded
    # in-flight window (pipeline depth ≤ 4, plus abandoned crash-path
    # dispatches) — 8 is comfortably past both.
    STAGING_RING = 8

    def _staging(self, kind: str, shape: tuple[int, ...]) -> np.ndarray:
        """A zeroed int32 staging buffer for one packed dispatch, drawn
        from a per-(kind, shape) ring so allocation is amortized without
        ever rewriting a buffer a still-fetching replica may read.
        Device-thread only."""
        key = (kind, shape)
        ring = self._staging_bufs.get(key)
        if ring is None:
            ring = ([np.zeros(shape, np.int32) for _ in range(self.STAGING_RING)], [0])
            self._staging_bufs[key] = ring
        bufs, idx = ring
        buf = bufs[idx[0]]
        idx[0] = (idx[0] + 1) % len(bufs)
        buf.fill(0)
        return buf

    def _claim_slot(self, idx: int, slot: _Slot) -> None:
        """Occupy lane ``idx`` (caller holds the state lock). The lane is
        reserved from this moment — admission skips it, decode masks it,
        and its pages stay held — until _free_slot or the prefill fold
        moves it to the decode stage."""
        self.slots[idx] = slot
        self._free_lanes.discard(idx)
        if slot.last_token is None:
            self._prefill_lanes.add(idx)
        else:
            self._decode_lanes.add(idx)

    def _lane_to_decode(self, idx: int) -> None:
        """Prefill fold completed: the lane starts decoding next dispatch."""
        self._prefill_lanes.discard(idx)
        self._decode_lanes.add(idx)

    def _free_slot(self, idx: int) -> None:
        """Vacate a slot; in the paged layout its share of each page is
        released (pages also held by the prefix cache or other slots stay
        allocated — refcount zero is what returns a page to the pool).
        The slot's adapter pool reference drops with it."""
        s = self.slots[idx]
        self.slots[idx] = None
        self._decode_lanes.discard(idx)
        self._prefill_lanes.discard(idx)
        self._free_lanes.add(idx)
        if self.kv_layout == "paged":
            pages = self._slot_pages[idx]
            if pages:
                self._slot_pages[idx] = []
                self._table[idx, :] = self.total_pages
                for p in pages:
                    self._unref_page(p)
            self.metrics.set_gauge("app_tpu_kv_pages_free", len(self._free_pages))
        if s is not None and s.adapter_slot and self._adapter_pool is not None:
            self._adapter_pool.release(s.adapter_slot)
        if s is not None and s.handoff is not None:
            # a mid-prefill streaming transfer whose slot died (preemption,
            # cancel, deadline): tear down the WIRE state only — the
            # request itself is settled by whoever freed the slot, and a
            # preempted prompt re-prefills and re-streams from page 0
            # (the importer touch-skips pages it already holds)
            t, s.handoff = s.handoff, None
            self._handoff_exporter.abort(t)

    def _set_prefix_gauges(self) -> None:
        """One authoritative write of every prefix-cache occupancy gauge —
        eviction, insertion, swap-in, clear(), and crash-restart all funnel
        here so no path can leave a stale reading behind."""
        if self._prefix is None:
            return
        self.metrics.set_gauge("app_tpu_prefix_cached_pages", len(self._prefix))
        self.metrics.set_gauge("app_tpu_prefix_host_pages", self._prefix.host_pages)
        self.metrics.set_gauge("app_tpu_prefix_host_bytes", self._prefix.host_bytes)

    def _evict_prefix_page(self) -> bool:
        """Release LRU prefix-cache leaves until a page actually lands in
        the free pool (an evicted page still shared with a live slot frees
        nothing — keep going). With the host tier enabled the page's K/V is
        spilled instead of dropped: the per-page gather is DISPATCHED here
        (asynchronous — no device round trip ever blocks under the state
        lock, or a wedged device call would deadlock stop()'s _fail_all
        behind it) and the node temporarily holds the small gathered device
        buffers; _materialize_spills completes the device→host read outside
        the lock on the next loop iteration. False when the cache has
        nothing left."""
        if self._prefix is None:
            return False
        freed = False
        while not self._free_pages:
            if self._prefix.host_budget:
                ent = self._prefix.spill_lru()
                if ent is None:
                    break
                key, p = ent
                from gofr_tpu.ops.paged import gather_page

                payload = tuple(
                    jax.tree.leaves(gather_page(self.kv_cache, jnp.int32(p)))
                )
                dropped = self._prefix.commit_spill(key, payload, self._page_bytes)
                self._pending_spills.append((key, payload))
                if dropped:
                    self.metrics.increment_counter(
                        "app_tpu_prefix_evicted_pages_total", dropped, tier="host")
            else:
                p = self._prefix.evict_lru()
                if p is None:
                    break
            self.metrics.increment_counter(
                "app_tpu_prefix_evicted_pages_total", 1, tier="hbm")
            self._unref_page(p)
            freed = True
        if freed:
            self._set_prefix_gauges()
        return bool(self._free_pages)

    def _ensure_pages(self, slot_idx: int, upto_pos: int) -> bool:
        """Grow slot_idx's block table until it covers logical position
        ``upto_pos``; False when the pool is exhausted. Failure rolls back
        the pages allocated by THIS call: a partial allocation on a slot
        that stays unoccupied (the admission path) would be invisible to
        preemption and strand pool capacity forever (ADVICE.md round 2)."""
        need = upto_pos // self.page_size + 1
        cur = self._slot_pages[slot_idx]
        added = 0
        while len(cur) < need:
            if not self._free_pages and not self._evict_prefix_page():
                for _ in range(added):
                    p = cur.pop()
                    self._table[slot_idx, len(cur)] = self.total_pages
                    self._unref_page(p)
                return False
            p = self._free_pages.pop()
            self._page_refs[p] = 1
            self._table[slot_idx, len(cur)] = p
            cur.append(p)
            added += 1
        return True

    def _usable_hit(self, toks: np.ndarray) -> list:
        """``(key, node)`` chain entries (tpu/prefix.py, both tiers)
        covering a prefix of ``toks``, capped below the prompt length so
        the final prompt token's logits — and therefore the first sampled
        token — are always recomputed. The single source of truth for both
        admission routing and slot claim. Touches cache LRU clocks; takes
        no references. Deliberately NOT the lookup/miss counting point:
        admission planning may re-run for a request bounced by pool
        exhaustion, and per-round counting would drown the hit-rate ratio
        in retry noise — counting happens once per claim/admission
        (_prefix_hit and the batched-path admission loop)."""
        if self._prefix is None:
            return []
        chain = self._prefix.lookup_tiered(toks)
        n_hit = min(len(chain), (int(toks.shape[0]) - 1) // self.page_size)
        return chain[:n_hit]

    def _prefix_hit(self, idx: int, slot: _Slot, toks: np.ndarray,
                    chain: list | None = None) -> None:
        """Splice the longest cached full-page prefix of ``toks`` into a
        freshly claimed slot's block table (caller holds the state lock;
        the slot owns no pages yet); chunked prefill then starts at
        ``slot.written``. Device-resident chain nodes splice directly;
        host-resident nodes claim a FREE device page each (stopping the
        chain where none is available — table rows must stay contiguous),
        are promoted back to the device tier, and their payload upload is
        staged on ``_pending_swapins`` — ``_admit`` dispatches it onto the
        unified in-flight queue right after releasing the lock, before any
        chunk of this prompt's tail can dispatch, so the cache data
        dependency orders the upload ahead of every read of those pages."""
        if self._prefix is None:
            return
        # lookup/miss accounting at CLAIM time, once per request — never in
        # _usable_hit, whose planning caller can re-run for a pool-bounced
        # request (hit rate = 1 - miss_total / lookup_total)
        self.metrics.increment_counter("app_tpu_prefix_lookup_total", 1)
        if chain is None:
            chain = self._usable_hit(toks)
        if not chain:
            self.metrics.increment_counter("app_tpu_prefix_miss_total", 1)
            return
        pages: list[int] = []
        swap_keys: list[int] = []
        swap_pids: list[int] = []
        swap_payloads: list = []
        hbm_toks = host_toks = 0
        for key, node in chain:
            if node.page_id >= 0:
                p = node.page_id
                self._ref_page(p)
                hbm_toks += self.page_size
            else:
                if not self._free_pages:
                    break  # no device page for the swap-in: tail recomputes
                p = self._free_pages.pop()
                # two shares at once: this slot's and the cache's (the node
                # is promoted below — never double-freed across tiers)
                self._page_refs[p] = 2
                swap_keys.append(key)
                swap_pids.append(p)
                swap_payloads.append(node.host)
                self._prefix.promote(key, p)
                host_toks += self.page_size
            pages.append(p)
        if not pages:
            # a chain whose first node is host-resident with no free device
            # page serves NOTHING from cache — that is a miss for hit-rate
            # purposes, or pool-pressure episodes would over-report hits
            self.metrics.increment_counter("app_tpu_prefix_miss_total", 1)
            return
        self._slot_pages[idx] = list(pages)
        self._table[idx, :len(pages)] = pages
        slot.written = len(pages) * self.page_size
        slot.dispatched = slot.written  # cached tokens need no prefill write
        if hbm_toks:
            self.metrics.increment_counter(
                "app_tpu_prefix_hit_tokens", hbm_toks, tier="hbm")
        if host_toks:
            self.metrics.increment_counter(
                "app_tpu_prefix_hit_tokens", host_toks, tier="host")
        slot.request.kw["_prefix"] = {
            "hbm_tokens": hbm_toks, "host_tokens": host_toks,
            "swapin_pages": len(swap_pids),
        }
        if swap_pids:
            self._pending_swapins.append(
                (idx, slot, swap_keys, swap_pids, swap_payloads))
            self._set_prefix_gauges()  # host bytes shrank at promotion

    def _prefix_insert(self, idx: int) -> None:
        """Retain the full prompt pages of a slot whose prefill just
        completed (caller holds the state lock). The cache takes one pool
        reference per newly registered page; pages already cached at their
        chain position are skipped — identical tokens produce identical
        K/V, so the existing page serves both chains."""
        s = self.slots[idx]
        if self._prefix is None or s is None:
            return
        n_full = s.prompt_len // self.page_size
        if n_full == 0:
            return
        new = self._prefix.insert(
            np.asarray(s.prompt_tokens), self._slot_pages[idx][:n_full]
        )
        for p in new:
            self._ref_page(p)
        if new:
            self._set_prefix_gauges()

    def _alloc_lane_pages(self, i: int, s: "_Slot", upto_pos: int) -> None:
        """Grow lane i's block table to cover ``upto_pos``, preempting the
        newest-admitted OTHER slot under pool pressure (LIFO, recompute on
        return). Caller holds the state lock and must re-check lane
        identity afterwards — preemption may have evicted lanes, including
        this one via another lane's pressure."""
        if self.slots[i] is not s:
            return  # evicted by an earlier lane's pool pressure
        while not self._ensure_pages(i, upto_pos):
            if not self._preempt_newest(except_slot=i):
                # alone and still short — can't happen when
                # total_pages >= pages_per_slot (ctor guard)
                self._free_slot(i)
                s.request.complete(error=RuntimeError(
                    "KV page pool exhausted for a single request"))
                break

    def _trim_lane_pages(self, i: int, s: "_Slot", keep_pos: int) -> int:
        """Release lane i's TRAILING pages beyond the page holding logical
        position ``keep_pos`` (caller holds the state lock). Only valid
        with no round in flight for the lane — an in-flight dispatch's
        table snapshot may write any page claimed at its dispatch time.
        This is the fold-side release of the over-claim
        ``decode.dispatch_spec_paged`` makes for the worst-case accepted
        span; rejected drafts' surplus pages return to the pool here.
        Pages also held by the prefix cache or other slots stay allocated
        (refcount discipline). Returns the number of shares released."""
        keep = keep_pos // self.page_size + 1
        cur = self._slot_pages[i]
        released = 0
        while len(cur) > keep:
            p = cur.pop()
            self._table[i, len(cur)] = self.total_pages
            self._unref_page(p)
            released += 1
        if released:
            self.metrics.set_gauge(
                "app_tpu_kv_pages_free", len(self._free_pages))
        return released

    def _masked_table(self, live: set) -> np.ndarray:
        """Block-table snapshot with NON-decoding rows forced all-OOB: a
        chunk-prefilling slot owns real pages, and a uniform decode write
        would corrupt its position 0 otherwise; empty slots are already
        all-OOB via _free_slot. Caller holds the state lock."""
        snapshot = self._table.copy()
        for i in range(self.num_slots):
            if i not in live:
                snapshot[i, :] = self.total_pages
        return snapshot

    def _preempt_newest(self, except_slot: int | None = None) -> bool:
        """Pool pressure valve: evict the MOST RECENTLY admitted active slot
        (LIFO keeps almost-done requests running), fold its generated tokens
        into its prompt, and requeue it for re-prefill — preemption by
        recompute. Greedy decode continues bit-identically; sampled decode
        resumes from a fresh RNG fold (documented engine semantics)."""
        candidates = [
            (self.slots[i].admit_seq, i)
            for i in self._decode_lanes | self._prefill_lanes
            if i != except_slot
        ]
        if not candidates:
            return False
        _, idx = max(candidates)
        s = self.slots[idx]
        self._free_slot(idx)
        req = s.request
        req.kw["_preemptions"] = req.kw.get("_preemptions", 0) + 1
        rt = req.kw.get("_rt")
        if rt is not None:
            # whichever phase the slot was in ends here (a slot still mid-
            # chunked-prefill has no decode span yet; end() no-ops on the
            # other); re-admission opens a fresh engine.prefill span, so the
            # trace shows the recompute round-trip
            rt.end("engine.prefill", preempted=True)
            rt.end("engine.decode", preempted=True)
        req.kw["_prior_tokens"] = list(req.kw.get("_prior_tokens", [])) + list(s.generated)
        req.kw["max_new_tokens"] = max(
            1, int(req.kw.get("max_new_tokens", 64)) - len(s.generated)
        )
        new_prompt = np.concatenate(
            [np.asarray(s.prompt_tokens, np.int32), np.asarray(s.generated, np.int32)]
        ).astype(np.int32)
        if new_prompt.shape[0] > self.prefill_buckets[-1]:
            # the regrown prompt outgrew the bucket ladder: it re-enters
            # through the chunked-prefill path rather than being expired
            # (ADVICE.md round 2 medium)
            self._pending_long.append((req, new_prompt))
        else:
            self._pending.append((req, new_prompt))
        self.metrics.increment_counter("app_tpu_preemptions", 1)
        return True

    # The accessors sort for determinism (lowest-lane-first claiming, and
    # lockstep leaders must pack lanes identically run-to-run); membership
    # itself is maintained incrementally, never by rescanning self.slots.

    def _free_slots(self) -> list[int]:
        return sorted(self._free_lanes)

    def _active(self) -> list[int]:
        """Slots in the decode stage (prefill-stage slots excluded)."""
        return sorted(self._decode_lanes)

    def _activate_lane(self, idx: int, s: _Slot, tok: int, now: float) -> None:
        """Shared tail of both prefill folds: give the slot its sampled
        first token and move it into the decode stage (caller holds the
        state lock and has already verified slot identity/liveness)."""
        self._mark_first_token(s.request)
        s.written = s.prompt_len
        s.generated = [tok]
        s.last_token = tok
        s.pos = s.prompt_len
        s.first_token_at = now
        self._lane_to_decode(idx)
        self._prefix_insert(idx)
        if self.role == "prefill" and self._export_handoff(idx, s, tok, now):
            return
        self._emit(s, tok)
        self._maybe_finish(idx)

    def _stream_handoff_chunk(self, idx: int, s: _Slot) -> None:
        """Streaming handoff, mid-prefill half (caller holds the state
        lock, the slot just folded a NON-final chunk): stage every newly
        full page's gather on the slot's StreamTransfer and kick the
        exporter thread. The gathers are dispatched HERE, under the lock,
        so they capture the page contents before preemption or eviction
        could recycle a page (the `_evict_prefix_page` discipline); the
        exporter blocks on them — device→host readback — outside every
        engine lock, overlapped with the prompt's next chunk's compute."""
        exp = self._handoff_exporter
        if (exp is None or self.handoff_streams <= 0 or self._prefix is None
                or self.kv_layout != "paged" or exp.known_blob()):
            return  # blob peer or blob config: pages ship at activation
        n_full = min(s.written, s.prompt_len) // self.page_size
        t = s.handoff
        if t is None:
            if n_full == 0:
                return  # no full page yet; nothing to ship
            t = s.handoff = exp.begin_stream(
                s.request, np.asarray(s.prompt_tokens), self._page_bytes,
                time.monotonic())
        ready = min(n_full, len(self._slot_pages[idx]))
        if ready > t.staged_pages:
            t.add(executor.gather_pages(
                self, self._slot_pages[idx][t.staged_pages:ready]))
            exp.kick(t)

    def _export_handoff(self, idx: int, s: _Slot, tok: int, now: float) -> bool:
        """Prefill-role terminal: ship the slot's full KV pages to the decode
        pool and complete the request with just its first token
        (finish_reason="handoff"). Returns False → colocated fallback (no
        exporter wired, unpaged prompt shorter than one page, lane state
        already torn down).

        The pages survive `_free_slot` because `_prefix_insert` one line
        earlier retained them in the prefix cache; the per-page gathers are
        dispatched HERE, under the state lock, so they capture the cache
        value before any later step can recycle a page (the
        `_evict_prefix_page` discipline — JAX's functional updates make the
        gathered payload immune to subsequent pool writes).

        With streaming negotiated (GOFR-HANDOFF2) most pages already left
        during the chunk folds (`_stream_handoff_chunk`); this terminal
        stages only the tail, detaches the transfer from the slot (so
        `_free_slot` doesn't abort it) and hands the exporter the first
        token to close the stream with."""
        exp = self._handoff_exporter
        if exp is None or self._prefix is None:
            return False
        n_full = s.prompt_len // self.page_size
        if n_full == 0 or len(self._slot_pages[idx]) < n_full:
            if s.handoff is not None:
                t, s.handoff = s.handoff, None
                exp.abort(t)  # partial stream of a slot that fell back
            return False
        pages = self._slot_pages[idx][:n_full]
        rt = s.request.kw.get("_rt")
        if rt is not None:
            rt.end("engine.decode")
            rt.begin("engine.handoff", **{"pages": n_full})
        if self.handoff_streams > 0 and not exp.known_blob():
            # streaming path (also carries the negotiated-down blob case:
            # the exporter accumulates and ships one frame at finish)
            t = s.handoff
            if t is None:
                t = exp.begin_stream(
                    s.request, np.asarray(s.prompt_tokens),
                    self._page_bytes, now)
            else:
                s.handoff = None  # detach BEFORE _free_slot's abort hook
            if n_full > t.staged_pages:
                t.add(executor.gather_pages(self, pages[t.staged_pages:]))
            self._free_slot(idx)
            exp.finish(t, tok, now)
            return True
        payloads = executor.gather_pages(self, pages)
        self._free_slot(idx)
        from gofr_tpu.tpu.handoff import HandoffJob

        exp.submit(HandoffJob(
            request=s.request, prompt_tokens=np.asarray(s.prompt_tokens),
            first_token=tok, payloads=payloads,
            nbytes_page=self._page_bytes, t0=now))
        return True

    def handoff_import(self, toks, payloads, nbytes_page: int) -> int:
        """Decode-role ingest (called from the HandoffServer thread): park
        the shipped pages as HOST-tier prefix nodes for `toks`' chain. The
        next admission of that prompt claims them through `_usable_hit` and
        re-uploads via the ordinary swap-in path, so the upload overlaps
        live decode on the `_dq` exactly like any other host-tier hit.
        Returns the number of chain positions newly registered."""
        if self.kv_layout != "paged" or self._prefix is None:
            raise ValueError("handoff import needs the paged prefix cache")
        if not self._prefix.host_budget:
            raise ValueError("handoff import needs a host-tier budget")
        want = [((leaf.shape[0],) + tuple(leaf.shape[2:]), leaf.dtype)
                for leaf in jax.tree.leaves(self.kv_cache)]
        for planes in payloads:
            if len(planes) != len(want):
                raise ValueError(
                    f"handoff page has {len(planes)} planes, pool has {len(want)}")
            for plane, (shape, dtype) in zip(planes, want):
                if tuple(plane.shape) != shape or plane.dtype != dtype:
                    raise ValueError(
                        f"handoff plane {plane.dtype}{tuple(plane.shape)} != "
                        f"pool {dtype}{shape}")
        with self._state_lock:
            # the engine's OWN page-byte size, not the wire value: both sides
            # must agree on geometry for the planes to validate above, and
            # budget accounting must match this pool's arithmetic
            added = self._prefix.insert_host(
                np.asarray(toks), payloads, self._page_bytes)
            self._set_prefix_gauges()
        return added

    def handoff_stats(self) -> dict:
        """Role + transfer counters for /debug/fleet."""
        out: dict[str, Any] = {"role": self.role}
        if self._handoff_exporter is not None:
            out["export"] = self._handoff_exporter.stats()
        if self._handoff_server is not None:
            out["import"] = self._handoff_server.stats()
            out["addr"] = self.handoff_addr
        return out

    # -- online knob actuation (gofr_tpu.control) ------------------------------

    def _build_controller(self, container):
        """Wire a StepController to this engine's knob seams. Each KnobSpec
        APPLY enqueues through request_knobs — the controller ticks on the
        device thread, so the change lands at the very next loop top, but
        routing through the queue keeps one audited mutation path for
        controller, debug endpoints, and bench drills alike."""
        from gofr_tpu.control.controller import (ControlPolicy, KnobSpec,
                                                 StepController)

        policy = ControlPolicy.from_config(container.config)
        specs = [
            KnobSpec("pipeline_depth",
                     tuple(range(1, self._boot_pipeline_depth + 1)),
                     lambda: self.pipeline_depth,
                     lambda v: self.request_knobs(pipeline_depth=v)),
            KnobSpec("prefill_chunk", tuple(self.prefill_buckets),
                     lambda: self.prefill_chunk,
                     lambda v: self.request_knobs(prefill_chunk=v)),
            KnobSpec("prefill_batch",
                     tuple(range(1, self._boot_prefill_batch + 1)),
                     lambda: self.max_prefill_batch,
                     lambda v: self.request_knobs(prefill_batch=v)),
        ]
        if self._boot_spec_tokens:
            # g=0 <-> g>0 is not a knob move (the spec carry changes the
            # cache pytree and the dispatch path): explore [1 .. boot g]
            specs.append(KnobSpec(
                "spec_tokens", tuple(range(1, self._boot_spec_tokens + 1)),
                lambda: self.spec_tokens,
                lambda v: self.request_knobs(spec_tokens=v)))

        def on_decision(d):
            if self.flight is not None:
                self.flight.record_control(d.to_dict())
            self.metrics.increment_counter(
                "app_tpu_control_decisions_total", 1, verdict=d.verdict)

        return StepController(
            policy, specs,
            kv_dtype=self.perf.model.kv_dtype,
            device_kind=self.perf.device_kind,
            shard=f"tp{max(1, getattr(self, 'kv_shards', 1))}",
            window_fn=self.perf.band_totals,
            standdown_fn=lambda: "lockstep" if self.lockstep_role else None,
            on_decision=on_decision,
            logger=self.logger)

    def _spec_fn_for(self, g: int):
        """The compiled spec-round handle for round length ``g`` (g is a
        static arg of the jitted program); builds and caches on first use."""
        fn = self._spec_fns.get(g)
        if fn is None:
            progs = build_programs(self.family, self.cfg, spec_tokens=g,
                                   **self._progs_kw)
            fn = self._spec_fns[g] = progs.spec_chunk
        return fn

    def request_knobs(self, **knobs) -> None:
        """Thread-safe: enqueue knob changes for the device loop to apply
        at its loop-top safe seam (_apply_pending_knobs) — no dispatch is
        in flight-construction there, so every dispatch snapshots a
        consistent knob vector."""
        self._knob_requests.append(dict(knobs))

    def _apply_pending_knobs(self) -> None:
        while self._knob_requests:
            req = self._knob_requests.popleft()
            for name, value in req.items():
                try:
                    self._apply_knob_now(name, value)
                except Exception as e:  # a bad knob must never kill the loop
                    self.logger.warn(f"knob {name}={value!r} rejected: {e}")

    def _apply_knob_now(self, name: str, value) -> None:
        """Device-thread only. Clamps every move to the boot ceiling (the
        operator's provisioned envelope) and, for prefill_chunk, snaps to a
        bucket member so next_bucket stays exact and the compiled-signature
        population never grows past the boot set."""
        v = int(value)
        if name == "pipeline_depth":
            self.pipeline_depth = max(1, min(v, self._boot_pipeline_depth))
            self.decode_pipeline = self.pipeline_depth  # keep the alias true
        elif name == "prefill_chunk":
            allowed = [b for b in self.prefill_buckets if b <= v]
            self.prefill_chunk = (allowed[-1] if allowed
                                  else self.prefill_buckets[0])
        elif name == "prefill_batch":
            self.max_prefill_batch = max(1, min(v, self._boot_prefill_batch))
        elif name == "spec_tokens":
            if not self._boot_spec_tokens:
                raise ValueError(
                    "spec is off at boot; g=0<->g>0 changes the cache pytree")
            g = max(1, min(v, self._boot_spec_tokens))
            if g != self.spec_tokens:
                # swap the compiled handle FIRST: a failed (re)build leaves
                # the old g fully consistent. In-flight rounds fold with
                # their dispatch-time g (decode._fold_spec reads sig), and
                # _chunk_span stays at the boot worst case, so masking and
                # paged over-claim remain conservative.
                self._spec_chunk_fn = self._spec_fn_for(g)
                self.spec_tokens = g
        else:
            raise ValueError(f"unknown knob {name!r}")

    def knob_vector(self) -> dict[str, int]:
        """Live knob values — stamped on flight-recorder steps, gossiped in
        the fleet digest, and compared by the bench's exactness drill."""
        out = {"pipeline_depth": self.pipeline_depth,
               "prefill_chunk": self.prefill_chunk,
               "prefill_batch": self.max_prefill_batch}
        if self._boot_spec_tokens:
            out["spec_tokens"] = self.spec_tokens
        return out

    def control_report(self) -> dict[str, Any]:
        """/debug/control payload (app.py)."""
        if self._control is None:
            return {"enabled": False, "knobs": self.knob_vector()}
        return self._control.report()

    def _loop(self) -> None:
        self._dq.clear()  # a restarted loop must not read a dead life's futures
        self._prev_last = None
        self._spec_carry = None
        if getattr(self, "_pending_swapins", None):
            self._pending_swapins = []  # staged by a dead life; never dispatch
        if getattr(self, "_pending_spills", None):
            self._pending_spills = []
        while not self._stop.is_set() and not self._poisoned:
            # loop-top safe seam: no dispatch is being constructed here, so
            # queued knob changes (controller commits/reverts, debug pokes)
            # land before anything snapshots them; the controller itself
            # ticks right after, ON this thread, so its applies take effect
            # at the very next iteration. ``depth`` is re-read every
            # iteration — a live pipeline_depth move simply changes how far
            # the drain below lets the queue refill.
            phase = self._phases.phase
            with phase("control"):
                self._apply_pending_knobs()
                if self._control is not None:
                    self._control.maybe_tick(time.monotonic())
                if self._chaos_step is not None:
                    self._chaos_step(step=self._step_count)
                if self._ls is not None and self._ls.has_pending():
                    # fleet membership change: admit (re)joining followers at
                    # this step boundary via an epoch bump (requeue + reset)
                    self._fleet_admit()
                if self._pending_weights is not None:
                    # live hot-swap staged by adopt_weights: drain + requeue +
                    # epoch bump at this step boundary (zero-drop)
                    self._apply_pending_weights()
                if self._hotswap_dir is not None:
                    self._poll_hotswap()
            depth = self.pipeline_depth
            # One bounded in-flight device queue (self._dq): batched
            # prefill, chunked prefill, and decode/spec chunks all DISPATCH
            # here (enqueueing their device futures) and are read back +
            # folded into slot state at DEQUEUE below — so every readback's
            # device→host round trip and host bookkeeping overlap the
            # compute of whatever was dispatched after it. Spec rounds ride
            # the queue on BOTH layouts: the paged dispatcher over-claims
            # pages for the worst-case accepted span at dispatch time and
            # the fold releases the surplus, so page allocation never waits
            # on readback (decode.dispatch_spec_paged).
            processed = False
            # phases nested in "admit" (the prefill dispatches, a depth-1
            # drain's readback and fold) are counted as themselves: a phase's
            # seconds are its SELF time
            with phase("admit"):
                admitted = self._admit()
                if depth == 1:
                    # TRULY synchronous at depth 1: each dispatch is read back
                    # before the next phase dispatches (the pre-unification
                    # behavior, and what "fully synchronous" promises operators
                    # debugging with ENGINE_PIPELINE=1 — also the honest "off"
                    # arm of the bench's overlap A/B)
                    while self._dq:
                        processed = process_decode(self) or processed
                # one chunk of ONE long prompt per iteration, so decode of the
                # other slots keeps stepping between chunks (TTFT fairness)
                chunked = self._advance_chunked()
            if depth == 1:
                while self._dq:
                    processed = process_decode(self) or processed
            with phase("dispatch_decode") as ph:
                seq = self._dispatch_seq
                if not self.spec_tokens:
                    dispatched = dispatch_decode(self)
                elif self.kv_layout == "slot":
                    dispatched = dispatch_spec(self)
                else:
                    dispatched = dispatch_spec_paged(self)
                if self._dispatch_seq != seq:
                    ph.tag(seq=self._dispatch_seq, kind=self._dq[-1][0])
                else:
                    ph.uncount()  # no lane to decode: nothing went to the device
            busy = admitted or chunked or dispatched
            # drain to depth-1 in-flight entries while work keeps arriving
            # (each blocking readback overlaps every younger dispatch);
            # drain fully when the engine goes quiet so no future lingers
            while len(self._dq) > (depth - 1 if busy else 0):
                processed = process_decode(self) or processed
            if not busy and not processed:
                if self._ls is not None and self._hb_interval:
                    # idle leader: heartbeat so follower watchdogs see
                    # liveness between announcements (LOCKSTEP_DEADLINE_S)
                    self._ls.maybe_heartbeat(self._hb_interval)
                if self._quality is not None and self._quality.step():
                    # quality plane: ONE shadow-scoring arm per idle
                    # iteration, then straight back to the top of the loop —
                    # interactive work that arrived during the forward is
                    # picked up before the next arm runs, and shadow work
                    # claims no slots or pages (it is a standalone
                    # teacher-forced forward), so preemption is free
                    continue
                # idle: block briefly for work without consuming (a get/put
                # round trip would skew QoS wait metrics and fair credits,
                # and could reorder same-class FIFO arrivals)
                with phase("wait_work"):
                    self._queue.wait_nonempty(0.2)
                if self.perf is not None:
                    # nothing queued, nothing in flight: advance the bubble
                    # floor so true idleness never counts as pipeline bubble
                    self.perf.mark_no_work(time.monotonic())

    # -- admission / prefill ---------------------------------------------------

    def _drain_pending(self) -> None:
        """Move queued requests into the encoded pending list (invalid ones
        complete with their error immediately). With QoS on, at most a
        couple of admission rounds' worth is drained per iteration — a full
        drain would freeze class priorities at arrival order inside the
        FIFO ``_pending`` list, while a bounded one keeps late-arriving
        interactive traffic able to overtake queued batch work."""
        budget = (2 * self.num_slots + self.max_prefill_batch
                  if self.qos is not None else -1)
        while budget != 0:
            budget -= 1
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            try:
                toks = self._encode_prompt(req.inputs)
                if toks.ndim != 1 or toks.shape[0] == 0:
                    raise ValueError(f"prompt must be a non-empty 1-D token sequence, got shape {toks.shape}")
                if toks.shape[0] >= self.max_len:
                    raise ValueError(f"prompt length {toks.shape[0]} ≥ engine max_len {self.max_len}")
                if toks.shape[0] > self.prefill_buckets[-1]:
                    if not self._chunked_ok:
                        raise ValueError(
                            f"prompt length {toks.shape[0]} exceeds the largest prefill "
                            f"bucket {self.prefill_buckets[-1]} (chunked prefill needs "
                            f"the paged layout or a family with SLOT_CHUNKED_PREFILL)"
                        )
                    self._pending_long.append((req, toks))
                else:
                    self._pending.append((req, toks))
            except Exception as e:  # noqa: BLE001
                req.complete(error=e)

    def _admit_long(self) -> None:
        """Claim a free slot for each waiting long prompt (paged layout).
        No device work here — _advance_chunked streams the prompt into the
        cache one bucket-sized chunk per loop iteration. Caller holds the
        state lock."""
        while self._pending_long and self._free_slots():
            req, toks = self._pending_long.pop(0)
            if req.cancelled or req.expired(time.monotonic()):
                req.complete(error=RequestTimeout())
                continue
            ad = self._acquire_adapter(req)
            if ad is None:
                continue  # adapter vanished since submit; request failed
            if ad == "wait":
                # every adapter pool slot is referenced by a live lane:
                # requeue at the head, exactly like KV page exhaustion
                self._pending_long.insert(0, (req, toks))
                break
            idx = self._free_slots()[0]
            slot = _Slot(
                req,
                prompt_len=int(toks.shape[0]),
                max_total=min(int(toks.shape[0]) + int(req.kw.get("max_new_tokens", 64)),
                              self.max_len),
                eos=req.kw.get("eos_token_id", self.eos_token_id),
                first_token=None,
                admit_seq=self._admit_seq,
                prompt_tokens=toks,
                adapter_id=ad[0],
                adapter_slot=ad[1],
            )
            self._admit_seq += 1
            self._claim_slot(idx, slot)
            self._mark_admitted(req, time.monotonic())
            req.kw["_slot"] = idx
            req.kw["_prompt_len"] = slot.prompt_len
            rt = req.kw.get("_rt")
            if rt is not None:
                rt.begin("engine.prefill",
                         **{"slot": idx, "prompt.tokens": slot.prompt_len,
                            "prefill.chunked": True})
            self._prefix_hit(idx, slot, toks)

    def _advance_chunked(self) -> bool:
        """DISPATCH the next chunk of the OLDEST-admitted prefilling slot
        onto the in-flight queue; readback + slot bookkeeping happen at
        dequeue (_fold_chunk), overlapped with later dispatches — the final
        chunk's dequeue samples the request's first token and flips the
        slot to the decode stage. One chunk dispatched per loop iteration
        keeps decode stepping between chunks; successive iterations can
        keep several chunks of one prompt in flight (``dispatched`` tracks
        the frontier). Returns True when device work was dispatched."""
        if not self._chunked_ok:
            return False
        with self._state_lock:
            pre = [i for i in self._prefill_lanes
                   if self.slots[i].dispatched < self.slots[i].prompt_len]
            if not pre:
                return False
            idx = min(pre, key=lambda i: self.slots[i].admit_seq)
            s = self.slots[idx]
            if s.request.cancelled or s.request.expired(time.monotonic()):
                self._free_slot(idx)
                s.request.complete(error=RequestTimeout())
                return True  # state changed; re-loop without idling
            offset = s.dispatched
            # prefill_chunk is the controller's chunked-prefill knob: a
            # bucket member <= buckets[-1], so smaller values trade TTFT of
            # the long prompt for tighter decode interleave without ever
            # minting a new compiled signature
            chunk = min(s.prompt_len - offset, self.prefill_chunk)
            lb = next_bucket(chunk, self.prefill_buckets)
            table_row = None
            if self.kv_layout == "paged":
                # pages must cover this chunk's writes before the table
                # snapshot; they stay reserved until the fold (or _free_slot)
                while not self._ensure_pages(idx, offset + chunk - 1):
                    if not self._preempt_newest(except_slot=idx):
                        self._free_slot(idx)
                        s.request.complete(error=RuntimeError(
                            "KV page pool exhausted for a single request"))
                        return True  # state changed; re-loop without idling
                if self.slots[idx] is None:  # preemption pressure evicted US
                    return True
                table_row = self._table[idx].copy()
            last = offset + chunk == s.prompt_len
            s.dispatched = offset + chunk
            self._step_count += 1
            step = self._step_count
            seq = self._next_seq()
            rt = s.request.kw.get("_rt")
            if rt is not None:
                rt.tag("engine.prefill", **{"step.seq": seq})  # the newest chunk's
            temp = float(s.request.kw.get("temperature", 0.0))
            t0 = time.monotonic()

        # device dispatch OUTSIDE the state lock: everything in the plan is
        # immutable (prompt_tokens) or snapshotted above (table row, step)
        self._dispatch_prefill(executor.dispatch_chunk, executor.ChunkPlan(
            idx, s, chunk, offset, last, lb, table_row, temp, step, t0, seq))
        return True

    def _fold_chunk(self, first: np.ndarray, meta, t0: float,
                    occupancy: float, sig: tuple, pstep=None) -> None:
        """Dequeue side of one prefill chunk (called by process_decode with
        the tokens already read back). Lanes freed/preempted since dispatch
        are discarded by identity — the same discipline decode uses."""
        idx, s, chunk, offset, last = meta
        lb = sig[1]
        with self._state_lock:
            dev_s = self._record_step(
                "prefill_chunk", time.monotonic() - t0, occupancy, sig, pstep,
                adapter_ids=([s.adapter_id or "base"]
                             if self._adapters_enabled else None))
            if self.slots[idx] is not s:
                return  # stop()/preemption/cancel took over while in flight
            if s.request.cancelled or s.request.expired(time.monotonic()):
                self._free_slot(idx)
                s.request.complete(error=RequestTimeout())
                return
            if dev_s:
                kw = s.request.kw
                kw["_dev_prefill_s"] = kw.get("_dev_prefill_s", 0.0) + dev_s
            self.metrics.increment_counter("app_tpu_tokens_total", chunk)
            s.written += chunk
            rt = s.request.kw.get("_rt")
            if rt is not None:
                rt.event("engine.prefill", "chunk",
                         offset=offset, tokens=chunk, bucket=lb)
            if last:
                if rt is not None:
                    rt.end("engine.prefill")
                    rt.begin("engine.decode", **{"slot": idx})
                self._activate_lane(idx, s, int(first[0]), time.monotonic())
            elif self.role == "prefill":
                # streaming handoff (GOFR-HANDOFF2): pages this fold just
                # made durable start shipping NOW, overlapped with the
                # prompt's remaining prefill chunks still on the device
                self._stream_handoff_chunk(idx, s)

    def _admit(self) -> bool:
        """Admission round: plan/claim/dispatch prefills, then dispatch any
        host-tier swap-ins the claims staged. The swap-in dispatch MUST
        happen before this device thread can dispatch a tail chunk for the
        claimed slot (_advance_chunked runs next in the loop): all device
        calls thread ``self.cache``, so issue order is data-dependency
        order and the upload lands before any read of those pages."""
        busy = self._admit_prefill()
        if getattr(self, "_pending_spills", None):
            self._materialize_spills()
        if getattr(self, "_pending_swapins", None):
            busy = self._dispatch_swapins() or busy
        return busy

    def _materialize_spills(self) -> None:
        """Complete staged spill copies OUTSIDE the state lock: eviction
        dispatched each page's gather asynchronously (so pool pressure
        never blocks the lock on a device round trip) and left the node
        holding the small gathered device buffers; this step — device
        thread, once per loop iteration — blocks on those buffers, copies
        them to host memory, and swaps the node payload. Nodes dropped or
        promoted in between simply skip the replacement. Body lives in
        the executor layer (tpu/executor.py)."""
        executor.materialize_spills(self)

    def _dispatch_swapins(self) -> bool:
        """Dispatch one async host→device page upload per staged hit onto
        the unified in-flight queue. Body lives in the executor layer
        (tpu/executor.py, dispatch_swapins) — see its docstring for the
        locking/fold contract."""
        return executor.dispatch_swapins(self)

    def _fold_swapin(self, meta, t0: float, occupancy: float, sig: tuple,
                     pstep=None) -> None:
        """Dequeue side of one swap-in (process_decode already blocked on
        the upload's completion marker). Settles the promoted nodes — they
        become spillable again — whatever happened to the slot; per-slot
        bookkeeping is discarded by identity (preemption/cancel/stop while
        in flight): the upload still landed in cache-owned pages holding
        exactly the content their chain nodes advertise, so nothing needs
        undoing."""
        idx, s, keys, n_pages, nbytes = meta
        now = time.monotonic()
        with self._state_lock:
            dev_s = self._record_step("swapin", now - t0, occupancy, sig, pstep)
            if self._prefix is not None:
                for key in keys:
                    self._prefix.settle(key)
            self.metrics.increment_counter(
                "app_tpu_prefix_swapin_pages_total", n_pages)
            self.metrics.record_histogram(
                "app_tpu_prefix_swapin_seconds", now - t0)
            self.metrics.record_histogram(
                "app_tpu_prefix_swapin_bytes", nbytes)
            if self.slots[idx] is not s:
                return  # freed/preempted/cancelled mid-swap-in
            if dev_s:
                kw = s.request.kw
                kw["_dev_swapin_s"] = kw.get("_dev_swapin_s", 0.0) + dev_s
            rt = s.request.kw.get("_rt")
            if rt is not None:
                rt.event("engine.prefill", "swapin",
                         pages=n_pages, bytes=nbytes)

    def _admit_prefill(self) -> bool:
        # Plan + claim under the state lock; token packing and the device
        # call OUTSIDE it (a wedged device call must never hold the lock,
        # or stop()'s _fail_all would deadlock behind it — and the pure-
        # numpy packing doesn't need it either). The dispatched prefill's
        # future rides the in-flight queue; readback + slot activation
        # happen at dequeue (_fold_prefill), overlapped with later
        # dispatches. Slots (and their pages) are CLAIMED here at dispatch
        # so the lane stays reserved until the matching dequeue — visible
        # to preemption, _fail_all, and crash recovery like any other
        # occupied lane.
        with self._state_lock:
            if self._draining:
                # scale-in drain: no new slot claims; queued work stays put
                # for drain_queued() to requeue onto a peer. Under the same
                # lock drain_queued takes, so a request can never be mid-move
                # from queue to slot when it runs.
                return False
            self._drain_pending()
            self.metrics.set_gauge("app_tpu_queue_depth", self._backlog())
            self._admit_long()
            free = self._free_slots()
            if not self._pending:
                return False
            still = []
            for r, t in self._pending:
                if r.cancelled:
                    r.complete(error=RequestTimeout())
                else:
                    still.append((r, t))
            self._pending = still

            # EDF + bucket-affinity packing (native planner when available):
            # the most urgent request leads and sets the length bucket; only
            # prompts fitting that bucket join, so one long prompt never
            # inflates the whole batch's padding.
            now_us = int(time.monotonic() * 1e6)
            plan = plan_prefill(
                [t.shape[0] for _, t in self._pending],
                [int(r.deadline * 1e6) if r.deadline else 0 for r, _ in self._pending],
                now_us, len(free), self.max_prefill_batch, self.prefill_buckets,
            )
            for i in plan.expired:
                self._pending[i][0].complete(error=RequestTimeout())
            ready = [self._pending[i] for i in plan.chosen]
            taken = set(plan.chosen) | set(plan.expired)
            self._pending = [p for i, p in enumerate(self._pending) if i not in taken]

            ad_of: dict[int, tuple] | None = None
            if self._adapters_enabled:
                # bind each chosen request's adapter to a device pool slot
                # BEFORE any slot/page claims below — dropping a request
                # after its pages were ensured would misalign the
                # row↔pages mapping of the batched dispatch
                ad_of = {}
                bound = []
                ad_wait = False
                for req, toks in ready:
                    ad = None if ad_wait else self._acquire_adapter(req)
                    if ad is None and not ad_wait:
                        continue  # adapter vanished since submit; failed
                    if ad_wait or ad == "wait":
                        # pool fully referenced by live lanes: requeue
                        # (order preserved — later picks wait behind it,
                        # exactly like the KV page-exhaustion gate)
                        ad_wait = True
                        self._pending.append((req, toks))
                        continue
                    ad_of[id(req)] = ad
                    bound.append((req, toks))
                ready = bound

            chunk_claimed = False
            if self.kv_layout == "paged" and self._prefix is not None:
                # EDF-chosen prompts whose cached prefix covers ≥ HALF their
                # tokens claim a slot on the CHUNKED path: its offset prefill
                # computes only the uncached remainder (the batched prefill
                # program has no offset support). Below the threshold the
                # recompute is cheap relative to losing prefill batching, so
                # the request stays on the EDF batch. Routing happens here —
                # for requests the plan already chose — so the lookup cost is
                # bounded by free slots per admission, not backlog size per
                # loop iteration, and EDF ordering is preserved.
                still = []
                for req, toks in ready:
                    chain = self._usable_hit(toks)
                    if 2 * len(chain) * self.page_size >= int(toks.shape[0]):
                        idx = self._free_slots()[0]
                        ad = (ad_of.get(id(req), (None, 0))
                              if ad_of is not None else (None, 0))
                        slot = _Slot(
                            req,
                            prompt_len=int(toks.shape[0]),
                            max_total=min(
                                int(toks.shape[0]) + int(req.kw.get("max_new_tokens", 64)),
                                self.max_len,
                            ),
                            eos=req.kw.get("eos_token_id", self.eos_token_id),
                            first_token=None,
                            admit_seq=self._admit_seq,
                            prompt_tokens=toks,
                            adapter_id=ad[0],
                            adapter_slot=ad[1],
                        )
                        self._admit_seq += 1
                        self._claim_slot(idx, slot)
                        self._mark_admitted(req, time.monotonic())
                        req.kw["_slot"] = idx
                        req.kw["_prompt_len"] = slot.prompt_len
                        self._prefix_hit(idx, slot, toks, chain=chain)
                        rt = req.kw.get("_rt")
                        if rt is not None:
                            # hit_pages is what was actually SPLICED — the
                            # chain can stop short of the planning-time
                            # length when a host node finds no free page
                            rt.begin("engine.prefill",
                                     **{"slot": idx, "prompt.tokens": slot.prompt_len,
                                        "prefill.chunked": True,
                                        "prefix.hit_pages": len(self._slot_pages[idx])})
                        chunk_claimed = True
                    else:
                        still.append((req, toks))
                ready = still
                free = self._free_slots()

            if self.kv_layout == "paged":
                # admission gate: each admitted prompt needs pages covering its
                # prefill writes NOW. On pool exhaustion the leader (most urgent)
                # stops admission entirely — later arrivals must not starve it.
                admitted: list[tuple[Request, np.ndarray]] = []
                exhausted = False
                for req, toks in ready:
                    if not exhausted and self._ensure_pages(free[len(admitted)], int(toks.shape[0]) - 1):
                        admitted.append((req, toks))
                    else:
                        exhausted = True
                        if ad_of is not None:
                            # bounced back to pending: drop the adapter
                            # pool reference taken above (re-acquired at
                            # the next admission attempt)
                            a = ad_of.pop(id(req), None)
                            if a and a[1]:
                                self._adapter_pool.release(a[1])
                        self._pending.append((req, toks))
                ready = admitted
            if not ready:
                return chunk_claimed
            if self.kv_layout == "paged" and self._prefix is not None:
                # cache-consultation accounting at ADMISSION, not per
                # planning round (a pool-bounced request must not recount):
                # batched-path admissions serve nothing from cache — a
                # below-threshold hit goes unused — so each counts one
                # lookup and one miss
                self.metrics.increment_counter(
                    "app_tpu_prefix_lookup_total", len(ready))
                self.metrics.increment_counter(
                    "app_tpu_prefix_miss_total", len(ready))

            # one prefill call, padded to (len_bucket, batch_bucket), shipped
            # as ONE packed array (layout documented at the jit definitions).
            # Padding rows point at slot index == num_slots, which is out of
            # bounds for the cache's slot dimension — XLA scatter DROPS
            # out-of-bounds updates, so they write nowhere (verified in
            # tests). Paged rows use the same trick through all-OOB
            # block-table rows (ops.paged).
            n = len(ready)
            nb = plan.batch_bucket
            lb = plan.len_bucket
            w = executor.prefill_cols(self)
            rows = free[:n]
            table_rows = (self._table[rows].copy()
                          if self.kv_layout == "paged" else None)
            t0 = time.monotonic()
            seq = self._next_seq()
            meta: list[tuple[int, _Slot]] = []
            for i, (req, toks) in enumerate(ready):
                self._mark_admitted(req, t0)
                req.kw["_slot"] = rows[i]
                req.kw["_prompt_len"] = int(toks.shape[0])
                rt = req.kw.get("_rt")
                if rt is not None:
                    rt.begin("engine.prefill",
                             **{"prefill.len_bucket": lb, "prefill.batch": nb,
                                "step.seq": seq})
                ad = (ad_of.get(id(req), (None, 0))
                      if ad_of is not None else (None, 0))
                slot = _Slot(
                    req,
                    prompt_len=int(toks.shape[0]),
                    max_total=min(int(toks.shape[0]) + int(req.kw.get("max_new_tokens", 64)),
                                  self.max_len),
                    eos=req.kw.get("eos_token_id", self.eos_token_id),
                    first_token=None,
                    admit_seq=self._admit_seq,
                    prompt_tokens=toks,
                    adapter_id=ad[0],
                    adapter_slot=ad[1],
                )
                slot.dispatched = slot.prompt_len  # whole prompt in this call
                self._admit_seq += 1
                self._claim_slot(rows[i], slot)
                meta.append((rows[i], slot))
            self._step_count += 1
            step = self._step_count

        # device dispatch OUTSIDE the state lock (executor layer): token/
        # temp data rides the immutable `ready` list, lanes and table rows
        # were snapshotted under the lock above
        self._dispatch_prefill(executor.dispatch_prefill, executor.PrefillPlan(
            ready, meta, nb, lb, w, rows, table_rows, step, t0, seq))
        return True

    def _next_seq(self) -> int:
        """The number the next ``_dq`` entry carries (device thread only)."""
        self._dispatch_seq += 1
        return self._dispatch_seq

    def _dispatch_prefill(self, dispatch, plan) -> None:
        """The ONE site of the ``dispatch_prefill`` loop phase: staging,
        ``jnp.asarray`` and the program call of a batched prefill
        (``executor.dispatch_prefill``) or a prefill chunk
        (``executor.dispatch_chunk``), outside the state lock."""
        with self._phases.phase("dispatch_prefill", seq=plan.seq, kind=plan.kind):
            dispatch(self, plan)

    def _fold_prefill(self, first: np.ndarray, meta, t0: float,
                      occupancy: float, sig: tuple, pstep=None) -> None:
        """Dequeue side of a batched prefill: activate each slot claimed at
        dispatch with its sampled first token. Lanes whose slot object
        changed since dispatch (stop()'s _fail_all, preemption, cancel)
        are discarded by identity — their requests were already completed
        and their pages returned by _free_slot."""
        with self._state_lock:
            dev_s = self._record_step(
                "prefill", time.monotonic() - t0, occupancy, sig, pstep,
                adapter_ids=([s.adapter_id or "base" for _, s in meta]
                             if self._adapters_enabled else None))
            now = time.monotonic()
            tokens = 0
            for row, (idx, s) in enumerate(meta):
                if self.slots[idx] is not s:
                    continue  # freed/preempted/failed while in flight
                if s.request.cancelled or s.request.expired(now):
                    self._free_slot(idx)
                    s.request.complete(error=RequestTimeout())
                    continue
                if dev_s:
                    kw = s.request.kw
                    kw["_dev_prefill_s"] = kw.get("_dev_prefill_s", 0.0) + dev_s
                tokens += s.prompt_len + 1
                rt = s.request.kw.get("_rt")
                if rt is not None:
                    rt.end("engine.prefill",
                           **{"slot": idx, "batch.occupancy": occupancy})
                    rt.begin("engine.decode", **{"slot": idx})
                self._activate_lane(idx, s, int(first[row]), now)
            self.metrics.increment_counter("app_tpu_tokens_total", tokens)

    # -- completion ------------------------------------------------------------

    # stream detokenizer bounds: ctx anchors in-context decoding (a few
    # tokens suffice for space-marker/merge effects); tail max bounds
    # worst-case hold latency and per-token re-decode cost
    STREAM_CTX_TOKENS = 8
    STREAM_TAIL_MAX = 32

    def _stream_diff(self, kw: dict, tail: list) -> str:
        """decode(ctx + tail) minus decode(ctx) — the next stream piece."""
        ctx = kw.get("_stream_ctx", [])
        if not ctx:
            return self.tokenizer.decode(tail)
        return self.tokenizer.decode(ctx + tail)[len(self.tokenizer.decode(ctx)):]

    def _emit(self, slot: _Slot, tok: int) -> None:
        if slot.request.stream_q is None or tok == slot.eos:
            return
        if self.tokenizer is None:
            slot.request.stream_q.put(tok)
            return
        # Incremental detokenization: unflushed token ids accumulate in a
        # TAIL and are emitted as the decode DIFF against a short context
        # of already-flushed ids — piece = decode(ctx + tail) minus
        # decode(ctx). The diff keeps tokenizers whose per-group decode
        # differs from in-context decode exact (SentencePiece strips a
        # leading space marker per decode call; the shared ctx prefix makes
        # any such artifact identical in both decodes and cancel). A piece
        # ending in U+FFFD holds a split multi-byte character until the
        # next token completes it, but the tail never grows past
        # STREAM_TAIL_MAX tokens — a model stuck on undecodable or
        # empty-decoding ids must not stall the stream or grow an O(n)
        # re-decode. State lives on the REQUEST so it survives preemption-
        # by-recompute; _maybe_finish flushes the remainder so the joined
        # stream equals the final result text.
        tail = slot.request.kw.setdefault("_stream_tail", [])
        tail.append(tok)
        piece = self._stream_diff(slot.request.kw, tail)
        if (piece and not piece.endswith("�")) or len(tail) > self.STREAM_TAIL_MAX:
            if piece:
                slot.request.stream_q.put(piece)
            slot.request.kw["_stream_ctx"] = (
                slot.request.kw.get("_stream_ctx", []) + tail)[-self.STREAM_CTX_TOKENS:]
            tail.clear()

    def _maybe_finish(self, slot_idx: int) -> None:
        s = self.slots[slot_idx]
        if s.eos is not None and s.generated[-1] == s.eos:
            finish = "stop"
        elif s.prompt_len + len(s.generated) >= s.max_total:
            finish = "length"
        else:
            return
        # tokens generated before any preemption round-trips lead the result
        prior = list(s.request.kw.get("_prior_tokens", []))
        tokens = prior + (s.generated[:-1] if finish == "stop" else list(s.generated))
        tail = s.request.kw.get("_stream_tail")
        if tail and s.request.stream_q is not None and self.tokenizer is not None:
            # flush any held (possibly incomplete) trailing characters so
            # the joined stream equals the result text exactly — without
            # this, a generation cut mid-character would silently drop its
            # tail from the stream
            text = self._stream_diff(s.request.kw, tail)
            if text:
                s.request.stream_q.put(text)
            tail.clear()
        now = time.monotonic()
        ft = s.request.kw.get("_first_token_at", s.first_token_at)
        if len(tokens) > 1:
            # steady-state decode pace: first token excluded (that's TTFT's
            # job), so tpot isolates the per-token device-loop cost
            self.metrics.record_histogram(
                "app_tpu_tpot_seconds", (now - ft) / (len(tokens) - 1))
            if self.slo is not None:
                self.slo.observe(s.request.kw.get("_qos_class"), "tpot",
                                 (now - ft) / (len(tokens) - 1))
        rt = s.request.kw.get("_rt")
        if rt is not None:
            attrs: dict[str, Any] = {"tokens": len(tokens), "finish.reason": finish}
            proposed = s.request.kw.get("_spec_proposed", 0)
            if proposed:
                attrs["spec.accept_rate"] = round(
                    s.request.kw.get("_spec_accepted", 0) / proposed, 4)
            rt.end("engine.decode", **attrs)
            # covers detokenization + completion bookkeeping; closed by the
            # done callback's close_all right after complete() below
            rt.begin("engine.finish")
        result = {
            "tokens": tokens,
            "text": self.tokenizer.decode(tokens) if self.tokenizer is not None else None,
            "finish_reason": finish,
            "ttft_s": ft - s.request.enqueued_at,
        }
        if self._quality is not None:
            # shadow-sampling dice roll (host-cheap; scoring happens later
            # on idle loop iterations). Captured BEFORE the slot is freed so
            # prompt/emitted are read from live state, keyed by exactly what
            # served the request: adapter, qos class, weights epoch. Uses
            # THIS life's prompt/emitted split (after a preemption the slot
            # prompt already contains the prior tokens — `tokens` above
            # would double-count them).
            self._quality.maybe_capture(
                [int(t) for t in np.asarray(s.prompt_tokens).reshape(-1)],
                s.generated[:-1] if finish == "stop" else list(s.generated),
                adapter=s.adapter_id,
                qos_class=s.request.kw.get("_qos_class"),
                weights_epoch=s.request.kw.get("_weights_epoch",
                                               self.weights_epoch) or 0,
                request_id=s.request.id,
            )
        self._free_slot(slot_idx)
        s.request.complete(result=result)


# -- factory (app.serve_model → here) ------------------------------------------


def _resolve_config(family_name: str, config: Any):
    if config is not None and not isinstance(config, dict):
        return config
    from gofr_tpu.models import BertConfig, GPT2Config, LlamaConfig, ViTConfig

    defaults = {"llama": LlamaConfig, "gpt2": GPT2Config, "bert": BertConfig, "vit": ViTConfig}
    cls = defaults.get(family_name)
    if cls is None:
        raise ValueError(f"no default config for family {family_name!r}; pass spec.config")
    return cls(**config) if isinstance(config, dict) else cls()


def _resolve_weights(spec, family, container, *, seed, rules, mesh, what=None):
    """One weights-to-(cfg, params) resolution path for the target AND the
    speculative draft: orbax checkpoint dir, HF converter, or random init
    (dev/bench), then shard over the mesh by the family's logical axes."""
    name = what or f"model {spec.family}"
    if spec.weights:
        from gofr_tpu.train.checkpoint import is_checkpoint_dir, load_params

        if is_checkpoint_dir(spec.weights):
            # orbax checkpoint dir (train/checkpoint.py): config must be given
            cfg = _resolve_config(spec.family, spec.config)
            like = jax.eval_shape(lambda: family.init(cfg, jax.random.key(0)))
            params = load_params(spec.weights, like)
        else:
            from gofr_tpu.models import convert

            converter = getattr(convert, f"{spec.family}_from_hf", None)
            if converter is None:
                raise ValueError(f"no weight converter for family {spec.family!r}")
            cfg, params = converter(spec.weights, dtype=spec.dtype)
    else:
        cfg = _resolve_config(spec.family, spec.config)
        params = family.init(cfg, jax.random.key(seed))
        container.logger.warn(
            f"{name}: no weights given — randomly initialized (dev/bench mode)"
        )
    return cfg, shard_pytree(params, family.param_axes(cfg), rules, mesh)


def _load_tokenizer(path_or_id):
    if not path_or_id:
        return None
    if hasattr(path_or_id, "encode") and hasattr(path_or_id, "decode") \
            and not isinstance(path_or_id, str):
        return path_or_id  # already a tokenizer object (e.g. utils.ByteTokenizer)
    from transformers import AutoTokenizer

    return AutoTokenizer.from_pretrained(path_or_id)


def build_engine(spec: ModelSpec, container, **kw: Any):
    """Materialize an engine from a ModelSpec: resolve config, load or init
    weights, cast + shard onto the container's TPU mesh, pick the engine
    for the task. Engine knobs come from config (ENGINE_*) overridden by
    ``kw`` — the reference's "config decides, code composes" rule
    (`container/container.go:91-122`)."""
    family = get_family(spec.family)
    tpu = container.tpu
    conf = container.config

    rules = tpu.rules
    # the PRE-pp-override rules: the speculative draft shards with these —
    # it is replicated/tp-sharded, never pipeline-layer-sharded (a 2-layer
    # draft's stacked blocks cannot divide a pp axis, and sharding it over
    # pp would contradict the draft's replicated-everywhere contract)
    base_rules = rules
    mesh = tpu.mesh
    # popped unconditionally: the knob must be ignorable on non-pp meshes,
    # not crash GenerateEngine with an unexpected-keyword TypeError
    pp_microbatches = int(kw.pop("pp_microbatches",
                                 conf.get_int("ENGINE_PP_MICROBATCHES", 0)))
    if (spec.task == "generate" and mesh is not None
            and "pp" in getattr(mesh, "axis_names", ()) and mesh.shape["pp"] > 1):
        # pipeline-parallel serving: blocks + slot KV cache shard over pp on
        # the layer dim; engine device calls run the GPipe schedule
        # (models/llama_pp.py). The 70B-on-v5e-64 weight-fit path.
        if spec.family != "llama":
            raise ValueError(
                f"pp-mesh serving is implemented for the llama family only "
                f"(got {spec.family!r}); drop the pp axis or use llama"
            )
        from gofr_tpu.models.llama_pp import PPLlamaFamily

        rules = rules.with_overrides(layers="pp")
        family = PPLlamaFamily(mesh, microbatches=pp_microbatches or None, rules=rules)

    prefill_attn = kw.pop("prefill_attn_fn", None)
    sp_size = (int(mesh.shape["sp"])
               if mesh is not None and "sp" in getattr(mesh, "axis_names", ()) else 1)

    # resolved ONCE: the same seed feeds random weight init AND the engine's
    # sampling RNG — with checkpoint/HF weights a caller-supplied seed was
    # previously popped here and silently dropped before it could reach
    # GenerateEngine's _base_key (ADVICE r5)
    seed = int(kw.pop("seed", 0))
    cfg, params = _resolve_weights(
        spec, family, container, seed=seed, rules=rules, mesh=mesh)

    quantize_kw = kw.pop("quantize", None)
    quantize = str(quantize_kw if quantize_kw is not None else conf.get_or_default("ENGINE_QUANTIZE", ""))
    if quantize == "int8":
        # weight-only int8 AFTER sharding (logical-axis rules apply to the
        # original tree; quantized arrays inherit shardings). Halves the
        # per-step weight reads decode is bound by — measured 1.33x decode
        # throughput on v5e (ops/quant.py). Families whose forwards don't
        # route linears through ops.quant.qdot can't serve QTensors: an
        # explicit per-model request errors, while the process-wide
        # ENGINE_QUANTIZE config only warns (it may legitimately target a
        # different engine in the same app).
        if getattr(family, "QUANTIZABLE", False):
            from gofr_tpu.ops.quant import quantize_tree

            params = jax.jit(quantize_tree)(params)
        elif quantize_kw is not None:
            raise ValueError(
                f"family {spec.family!r} does not support weight-only quantization"
            )
        else:
            container.logger.warn(
                f"ENGINE_QUANTIZE=int8 ignored for family {spec.family!r} (no qdot support)"
            )
    elif quantize:
        raise ValueError(f"ENGINE_QUANTIZE={quantize!r}: only 'int8' is supported")

    tokenizer = _load_tokenizer(spec.tokenizer)
    default_timeout = conf.get_float("ENGINE_TIMEOUT", 0.0) or None
    kw.setdefault("max_restarts", conf.get_int("ENGINE_MAX_RESTARTS", 3))

    if spec.task == "generate":
        eos = kw.pop("eos_token_id", None)
        if eos is None and tokenizer is not None:
            eos = tokenizer.eos_token_id
        default_layout = "paged" if hasattr(family, "make_paged_cache") else "slot"
        kv_layout = str(kw.pop("kv_layout", conf.get_or_default("ENGINE_KV_LAYOUT", default_layout)))
        # spec_tokens follows the quantize precedent (above): an explicit
        # per-model request errors on an incompatible setup, while the
        # process-wide ENGINE_SPEC_TOKENS config only warns — it may
        # legitimately target a different engine in the same app.
        spec_kw = kw.pop("spec_tokens", None)
        spec_tokens = int(spec_kw if spec_kw is not None else conf.get_int("ENGINE_SPEC_TOKENS", 0))
        spec_attr = "verify_step" if kv_layout == "slot" else "verify_step_paged"
        if spec_tokens and not hasattr(family, spec_attr):
            if spec_kw is not None:
                raise ValueError(
                    f"spec_tokens: family {getattr(family, '__name__', family)!r} "
                    f"has no {spec_attr} (speculative verification for the "
                    f"{kv_layout} layout)"
                )
            container.logger.warn(
                f"ENGINE_SPEC_TOKENS ignored for family "
                f"{getattr(family, '__name__', family)!r} (no {spec_attr})"
            )
            spec_tokens = 0
        # draft model for speculative decoding: a ModelSpec (resolved and
        # sharded through the same _resolve_weights path as the target) or
        # a prebuilt (family, cfg, params) triple. Engine-level validation
        # covers layout/protocol fit. Deliberately NOT routed through the
        # target-only extras: pp-family wrapping (the draft is replicated,
        # never pipeline-sharded) and ENGINE_QUANTIZE (a tiny draft's
        # weight reads are noise; quantize the target instead).
        draft_kw = kw.pop("spec_draft", None)
        if isinstance(draft_kw, ModelSpec):
            dfamily = get_family(draft_kw.family)
            dcfg, dparams = _resolve_weights(
                draft_kw, dfamily, container, seed=1, rules=base_rules,
                mesh=mesh, what=f"spec_draft {draft_kw.family}")
            draft_kw = (dfamily, dcfg, dparams)
        elif draft_kw is not None:
            # prebuilt (family, cfg, params) triple: shard the draft over
            # the mesh like everything else the programs close over
            # (base_rules: never the pp layer override — see above)
            dfamily, dcfg, dparams = draft_kw
            draft_kw = (dfamily, dcfg,
                        shard_pytree(dparams, dfamily.param_axes(dcfg), base_rules, mesh))
        if draft_kw is not None:
            kw["spec_draft"] = draft_kw
        # multi-host: every process must issue identical global programs;
        # the leader (process 0) serves, followers run serve_follower()
        # (tpu/lockstep.py). A crash-restart would desynchronize followers,
        # so lockstep engines don't restart.
        lockstep_role = kw.pop("lockstep_role", None)
        # elastic fleet (gofr_tpu.fleet; FLEET_LISTEN / FLEET_LEADER): the
        # announce stream rides the host-side channel with epoch-based
        # rejoin, so the restart budget STAYS available — a leader device-
        # loop restart is an epoch bump, not fleet death
        fleet = kw.pop("fleet", None)
        if fleet is None:
            from gofr_tpu.fleet import FleetConfig

            fleet = FleetConfig.from_config(conf)
        if fleet is not None:
            if lockstep_role not in (None, fleet.role):
                raise ValueError(
                    f"lockstep_role {lockstep_role!r} contradicts the FLEET_* "
                    f"config (role {fleet.role!r})")
            lockstep_role = fleet.role
        elif (lockstep_role is None and getattr(tpu, "distributed", False)
                and jax.process_count() > 1):
            lockstep_role = "leader" if jax.process_index() == 0 else "follower"
        if lockstep_role and fleet is None:
            kw["max_restarts"] = 0
        if fleet is not None:
            kw["fleet"] = fleet

        prefix_cache = bool(kw.pop("prefix_cache", conf.get_bool("ENGINE_PREFIX_CACHE", True)))
        if prefill_attn is None and sp_size > 1 and spec.task == "generate":
            # sequence-parallel PREFILL: whole-prompt attention shards the
            # sequence over sp (ring online-softmax, parallel/ring.py) —
            # the long-context lever for prompt-heavy serving. Batch stays
            # replicated inside the region (prefill batches are small).
            # NOT wired when it would break a contract, with a loud warn:
            # - prefix cache on (paged): a cache hit replays the remainder
            #   through gathered-view attention, whose reduction order
            #   differs from ring's — cold/hit bit-identity would be lost;
            # - non-llama families / the pp family: no attn_fn hook.
            supported = (spec.family == "llama"
                         and getattr(family, "__name__", "") != "llama_pp")
            if not supported:
                container.logger.warn(
                    f"mesh has sp:{sp_size} but sequence-parallel prefill is "
                    f"not wired for family {getattr(family, '__name__', family)!r}"
                )
            elif kv_layout == "paged" and prefix_cache:
                container.logger.warn(
                    f"mesh has sp:{sp_size} but sequence-parallel prefill is "
                    "disabled while the prefix cache is on (ring vs gathered-"
                    "view reduction order would break cold/hit bit-identity); "
                    "set ENGINE_PREFIX_CACHE=false to enable it"
                )
            else:
                from gofr_tpu.parallel.ring import make_seq_parallel_attn

                strategy = conf.get_or_default("ENGINE_SP_STRATEGY", "ring")
                if strategy == "ulysses":
                    # ulysses all-to-alls heads across sp — per-device query
                    # heads must divide (ring.py ulysses check). Fail at
                    # BUILD time like the bucket guard, not mid-serving.
                    tp_size = int(mesh.shape.get("tp", 1))
                    local_heads = cfg.num_heads // max(1, tp_size)
                    if local_heads % sp_size:
                        raise ValueError(
                            f"ENGINE_SP_STRATEGY=ulysses needs per-device query "
                            f"heads ({cfg.num_heads}/tp:{tp_size} = {local_heads}) "
                            f"divisible by sp:{sp_size}"
                        )
                prefill_attn = make_seq_parallel_attn(
                    mesh, batch_axes=(), strategy=strategy)
        # same precedent for the quantized-KV knob. ENGINE_KV_DTYPE is the
        # canonical spelling (bf16 | int8 | int4 — the bench A/B axis);
        # ENGINE_KV_QUANTIZE ("" | int8 | int4) stays as the legacy alias.
        kvq_kw = kw.pop("kv_quantize", None)
        kvd_env = str(conf.get_or_default("ENGINE_KV_DTYPE", "")).lower()
        if kvd_env in ("bf16", "bfloat16"):
            kvd_env = "dense"  # sentinel: explicit request for the dense pool
        if kvq_kw is not None:
            kv_quantize = str(kvq_kw)
        elif kvd_env:
            if kvd_env not in ("dense", "int8", "int4"):
                raise ValueError(
                    f"ENGINE_KV_DTYPE={kvd_env!r}: use bf16, int8 or int4")
            kv_quantize = "" if kvd_env == "dense" else kvd_env
        else:
            kv_quantize = str(conf.get_or_default("ENGINE_KV_QUANTIZE", ""))
        if kv_quantize == "int4":
            kvq_attr = "make_paged_cache_q4"
        else:
            kvq_attr = ("make_cache_q" if kv_layout == "slot"
                        else "make_paged_cache_q")
        if kv_quantize and not hasattr(family, kvq_attr):
            if kvq_kw is not None or kvd_env:
                raise ValueError(
                    f"kv_quantize: family {getattr(family, '__name__', family)!r} "
                    f"has no {kvq_attr} (quantized KV support for the "
                    f"{kv_layout} layout)"
                )
            container.logger.warn(
                f"ENGINE_KV_QUANTIZE ignored for family "
                f"{getattr(family, '__name__', family)!r} (no {kvq_attr})"
            )
            kv_quantize = ""
        # disaggregated serving (ENGINE_ROLE, docs/serving.md): a prefill
        # worker ships finished prompts' KV pages to a decode worker over
        # the handoff channel; "both" (the default) is colocated serving,
        # byte-identical to the pre-role engine.
        role = str(kw.pop("role", conf.get_or_default("ENGINE_ROLE", "both")) or "both")
        handoff_target = kw.pop(
            "handoff_target", conf.get_or_default("HANDOFF_TARGET", "")) or None
        handoff_listen = kw.pop(
            "handoff_listen", conf.get_or_default("HANDOFF_LISTEN", "")) or None
        handoff_timeout = float(kw.pop(
            "handoff_timeout_s", conf.get_float("HANDOFF_TIMEOUT_S", 5.0)))
        # GOFR-HANDOFF2 streaming pipeline knobs (docs/serving.md):
        # HANDOFF_STREAMS=0 pins the exporter to HANDOFF1 blob framing
        handoff_streams = int(kw.pop(
            "handoff_streams", conf.get_int("HANDOFF_STREAMS", 2)))
        handoff_chunk_pages = int(kw.pop(
            "handoff_chunk_pages", conf.get_int("HANDOFF_CHUNK_PAGES", 4)))
        handoff_pace = float(kw.pop(
            "handoff_pace_mbps", conf.get_float("HANDOFF_PACE_MBPS", 0.0)))
        return GenerateEngine(
            family, cfg, params, container,
            slots=int(kw.pop("slots", conf.get_int("ENGINE_SLOTS", 8))),
            max_len=int(kw.pop("max_len", conf.get_int("ENGINE_MAX_LEN", 2048))),
            decode_chunk=int(kw.pop("decode_chunk", conf.get_int("ENGINE_DECODE_CHUNK", 8))),
            max_prefill_batch=int(kw.pop("max_prefill_batch", conf.get_int("ENGINE_PREFILL_BATCH", 4))),
            kv_layout=kv_layout,
            page_size=int(kw.pop("page_size", conf.get_int("ENGINE_PAGE_SIZE", 128))),
            total_pages=int(kw.pop("total_pages", conf.get_int("ENGINE_TOTAL_PAGES", 0))) or None,
            seed=seed,
            prefix_cache=prefix_cache,
            prefix_host_mb=float(kw.pop("prefix_host_mb",
                                        conf.get_float("ENGINE_PREFIX_HOST_MB", 0.0))),
            spec_tokens=spec_tokens,
            kv_quantize=kv_quantize,
            kv_shard=str(kw.pop("kv_shard",
                                conf.get_or_default("ENGINE_KV_SHARD", "auto"))),
            prefill_attn_fn=prefill_attn,
            prefill_attn_divisor=sp_size if prefill_attn is not None else 1,
            lockstep_role=lockstep_role,
            # unified pipeline depth: ENGINE_PIPELINE is canonical; the
            # pre-unification ENGINE_DECODE_PIPELINE spelling (and the
            # decode_pipeline kwarg) keep working as aliases
            pipeline_depth=int(kw.pop("pipeline_depth", kw.pop(
                "decode_pipeline",
                conf.get_int("ENGINE_PIPELINE", 0)
                or conf.get_int("ENGINE_DECODE_PIPELINE", 2)))),
            eos_token_id=eos,
            tokenizer=tokenizer,
            default_timeout=default_timeout,
            role=role,
            handoff_target=handoff_target,
            handoff_listen=handoff_listen,
            handoff_timeout_s=handoff_timeout,
            handoff_streams=handoff_streams,
            handoff_chunk_pages=handoff_chunk_pages,
            handoff_pace_mbps=handoff_pace,
            # multi-LoRA adapter plane (gofr_tpu.adapters, docs/serving.md):
            # off by default — both spellings disabled keeps the engine
            # byte-identical to the pre-adapter build
            adapter_slots=int(kw.pop("adapter_slots",
                                     conf.get_int("ADAPTER_SLOTS", 0))),
            adapter_rank=int(kw.pop("adapter_rank",
                                    conf.get_int("ADAPTER_RANK", 16))),
            adapter_pool_mb=float(kw.pop("adapter_pool_mb",
                                         conf.get_float("ADAPTER_POOL_MB", 0.0))),
            adapter_host_mb=float(kw.pop("adapter_host_mb",
                                         conf.get_float("ADAPTER_HOST_MB", 256.0))),
            adapter_hotswap_dir=kw.pop(
                "adapter_hotswap_dir",
                conf.get_or_default("ADAPTER_HOTSWAP_DIR", "")) or None,
            adapter_hotswap_poll_s=float(kw.pop(
                "adapter_hotswap_poll_s",
                conf.get_float("ADAPTER_HOTSWAP_POLL_S", 5.0))),
            # quality plane (metrics/quality.py): rate 0 (the default)
            # never constructs the plane — bit-identical off path
            quality_shadow_rate=float(kw.pop(
                "quality_shadow_rate",
                conf.get_float("QUALITY_SHADOW_RATE", 0.0))),
            quality_seed=kw.pop(
                "quality_seed",
                conf.get_int("QUALITY_SEED", -1)),
            quality_max_pending=int(kw.pop(
                "quality_max_pending",
                conf.get_int("QUALITY_MAX_PENDING", 16))),
            quality_max_tokens=int(kw.pop(
                "quality_max_tokens",
                conf.get_int("QUALITY_MAX_TOKENS", 64))),
            quality_top1_min=float(kw.pop(
                "quality_top1_min",
                conf.get_float("QUALITY_TOP1_MIN", 0.9))),
            quality_kl_max=float(kw.pop(
                "quality_kl_max",
                conf.get_float("QUALITY_KL_MAX", 1.0))),
            quality_recent=int(kw.pop(
                "quality_recent",
                conf.get_int("QUALITY_RECENT", 32))),
            # online step controller (gofr_tpu.control): off by default —
            # CONTROL_ENABLE=0 never constructs it (bit-identical off path)
            control_enable=bool(kw.pop(
                "control_enable", conf.get_int("CONTROL_ENABLE", 0))),
            **kw,
        )

    max_batch = int(kw.pop("max_batch", conf.get_int("ENGINE_MAX_BATCH", 32)))
    wait_ms = float(kw.pop("max_wait_ms", conf.get_float("ENGINE_MAX_WAIT_MS", 2.0)))

    if spec.task == "embed":
        def encode(inputs):
            if isinstance(inputs, str):
                if tokenizer is None:
                    raise ValueError("string input but no tokenizer on the embed engine")
                return np.asarray(tokenizer.encode(inputs), np.int32)
            return np.asarray(inputs, np.int32)

        def apply(tokens, lengths):
            return family.embed_pooled(cfg, params, tokens, lengths)

        return BatchEngine(
            apply, container, encode_fn=encode, max_batch=max_batch,
            max_wait_ms=wait_ms, default_timeout=default_timeout, **kw,
        )

    if spec.task == "classify":
        def apply_images(images):
            return family.forward(cfg, params, images)

        return BatchEngine(
            apply_images, container,
            encode_fn=lambda x: np.asarray(x, np.float32),
            max_batch=max_batch, max_wait_ms=wait_ms,
            default_timeout=default_timeout, **kw,
        )

    raise ValueError(f"unknown task {spec.task!r}; use generate|embed|classify")
