"""Warmup-time Pallas-vs-XLA backend autotuner for the decode attention ops.

The static ``GOFR_PALLAS=1`` gate encoded one round-3 measurement ("XLA
faster on v5e") as policy. This module replaces it with the same
measure-then-pin philosophy GSPMD applies to sharding (PAPERS.md,
2105.04663): at ``engine.warmup()`` each decode op in play — ``decode``
(slot bf16), ``paged_decode`` (paged bf16), ``paged_decode_q`` (paged
int8) and ``paged_decode_q4`` (paged packed-int4; both fused kernels live
in ops/pallas/paged_decode.py) — is timed with BOTH
backends on the engine's real post-sharding serving shapes, the winner is
pinned via :func:`decision_scope`, and every trace the engine drives
(warmup + device loop, ``engine._trace_scope``) resolves ``backend="auto"``
to the pinned winner.

Precedence, highest first (docs/kernels.md):

1. an explicit ``backend=`` argument at an op call site;
2. an explicit ``GOFR_PALLAS`` env value (``0`` or ``1``) — the operator
   override; when it is set the autotuner does not even run;
3. a pinned autotune decision for the op (this module);
4. the legacy default (``pallas.flash_attention_available()``: XLA on
   hardware, Pallas under the interpreter).

Decisions persist to a JSON cache file (``GOFR_AUTOTUNE_CACHE``) keyed by
``device_kind|op|shape|kv_dtype`` so fleet restarts (PR5 epochs, the
Supervisor runbook) skip re-timing: a restarted engine's warmup finds its
exact key and pins without touching the device. Corrupt files, version
mismatches and malformed entries are ignored (re-measured), never fatal.

``GOFR_AUTOTUNE=0`` is the escape hatch: no timing, no pins — today's
static resolution, bit-for-bit. The autotuner also stands down under the
Pallas interpreter (interpreter timings say nothing about hardware) and
under lockstep (engine-side gate: a leader-only pin would desynchronize
follower traces).

Caveat: jit caches traces process-globally, so the first engine to trace a given program signature
fixes that signature's backend for the life of the process — A/B across
processes, not by re-tuning in one.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import os
import time
from typing import Any, Callable

# 2: the bf16 paged-decode kernel was rewritten (a whole page of every KV head
# per copy, live pages only); a version-1 pin was raced against the old one.
FORMAT_VERSION = 2
BACKENDS = ("pallas", "xla")

# {op: backend} pinned for the traces inside a decision_scope — consulted
# by ops.attention.resolve_backend for backend="auto". Same engine-pins-
# for-its-traces pattern as paged.kv_shard_scope / pallas.platform_hint.
_PINS: contextvars.ContextVar[dict[str, str] | None] = contextvars.ContextVar(
    "gofr_autotune_pins", default=None
)

# Most recent report produced by an Autotuner in this process — bench.py
# records it in the bench JSON after the headline engine is gone.
_LAST_REPORT: dict[str, Any] | None = None


def enabled() -> bool:
    """Should warmup measure and pin? ``GOFR_AUTOTUNE=0`` disables; an
    explicit ``GOFR_PALLAS`` (0/1) is an operator override that makes
    timing pointless; interpreter-mode timings are meaningless for
    hardware (and the CPU test suite relies on 'auto' → interpreter)."""
    from gofr_tpu.ops.pallas import interpret_mode

    if os.environ.get("GOFR_AUTOTUNE", "") == "0":
        return False
    if os.environ.get("GOFR_PALLAS", "") in ("0", "1"):
        return False
    return not interpret_mode()


def cache_path() -> str | None:
    return os.environ.get("GOFR_AUTOTUNE_CACHE") or None


@contextlib.contextmanager
def decision_scope(pins: dict[str, str] | None):
    """Pin ``{op: backend}`` decisions for every trace inside the scope."""
    tok = _PINS.set(pins)
    try:
        yield
    finally:
        _PINS.reset(tok)


def pinned_backend(op: str | None) -> str | None:
    """The pinned backend for ``op`` in the current decision scope, or None
    (no scope / no decision for this op → caller falls back to defaults)."""
    if op is None:
        return None
    pins = _PINS.get()
    if not pins:
        return None
    return pins.get(op)


def shape_key(*dims: int) -> str:
    return "x".join(str(int(d)) for d in dims)


def entry_key(device_kind: str, op: str, shape: str, kv_dtype: str,
              role: str = "", sharding: str = "") -> str:
    """Cache key. ``role`` (ENGINE_ROLE, disaggregated serving) and
    ``sharding`` (pool mesh sharding, e.g. ``"tp4"``) are appended only
    when they narrow the decision — ``""``/``"both"`` role and ``""``
    sharding keep the exact pre-feature key, so existing cache files stay
    valid and an unsharded engine never reads a sharded pin (or vice
    versa: per-shard shapes change the winner, so pins must not leak
    across mesh geometries)."""
    key = "|".join((str(device_kind), op, shape, str(kv_dtype)))
    if role and role != "both":
        key += f"|role={role}"
    if sharding:
        key += f"|shard={sharding}"
    return key


def set_last_report(report: dict[str, Any] | None) -> None:
    global _LAST_REPORT
    _LAST_REPORT = report


def last_report() -> dict[str, Any] | None:
    return _LAST_REPORT


def _default_timer(fn: Callable[[], Any], repeats: int = 3) -> float:
    """Seconds for one call, best-of-``repeats`` with the compile paid
    outside the timed window (the candidate closures are jitted on real
    device-shaped inputs, so call 0 is the XLA/Mosaic compile)."""
    import jax

    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _load_cache(path: str | None, logger: Any = None) -> dict[str, dict]:
    """Entries from the cache file; {} for missing/corrupt/stale files —
    a bad cache must cost one re-measure, never a failed warmup."""
    if not path or not os.path.exists(path):
        return {}
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        if not isinstance(doc, dict) or doc.get("version") != FORMAT_VERSION:
            raise ValueError(f"version {doc.get('version')!r} != {FORMAT_VERSION}")
        entries = doc.get("entries")
        if not isinstance(entries, dict):
            raise ValueError("no entries dict")
        out = {}
        for key, rec in entries.items():
            if isinstance(rec, dict) and rec.get("backend") in BACKENDS:
                out[key] = rec
        return out
    except Exception as e:  # noqa: BLE001 - corrupt/stale cache is re-measured
        if logger is not None:
            logger.warn(f"ignoring autotune cache {path}: {e}")
        return {}


class Autotuner:
    """Times backend candidates per (op, shape, kv dtype) and records the
    winner. ``timer`` is injectable (tests pin deterministic fake timings
    without lowering any kernel); ``cache_file`` round-trips decisions
    across process restarts."""

    def __init__(self, device_kind: str = "cpu", cache_file: str | None = None,
                 timer: Callable[[Callable[[], Any]], float] | None = None,
                 logger: Any = None, role: str = "", sharding: str = ""):
        self.device_kind = device_kind
        self.cache_file = cache_file
        self.timer = timer or _default_timer
        self.logger = logger
        # role-scoped keys (disaggregation): a decode-role spare's pins live
        # under their own cache keys, so its warmup neither waits on nor
        # clobbers a colocated engine's measurements for the same shapes
        self.role = role if role not in ("", "both") else ""
        # sharding-scoped keys (tp pool sharding): per-shard shapes are
        # 1/tp the replicated ones, so a pin measured on one mesh geometry
        # is stale for another; "" (unsharded) keeps pre-feature keys
        self.sharding = sharding or ""
        self.decisions: dict[str, dict] = {}  # op -> decision record
        self._cache = _load_cache(cache_file, logger)  # lookups only
        self._own: dict[str, dict] = {}  # keys THIS tuner decided (persisted)

    def measure(self, op: str, shape: str, kv_dtype: str,
                candidates: dict[str, Callable[[], Any]]) -> str:
        """Pin a backend for ``op``: cache hit > timed winner > the single
        candidate (no timing when there is nothing to compare — the CPU
        path costs zero device work). A candidate that raises (e.g. the
        kernel compiler rejects the shape) is disqualified LOUDLY: the
        error is logged at error level and kept under ``errors`` in the
        decision record, which ``engine.autotune_report()`` and
        ``/debug/engine`` serve — a kernel that does not compile is a
        defect to repair, not a race it quietly lost. If every candidate
        fails, 'xla' — the everywhere-correct path — is pinned."""
        key = entry_key(self.device_kind, op, shape, kv_dtype, self.role,
                        self.sharding)
        cached = self._cache.get(key)
        if cached is not None and cached.get("backend") in candidates:
            rec = {"backend": cached["backend"], "shape": shape, "kv_dtype": kv_dtype,
                   "timings_ms": cached.get("timings_ms", {}), "source": "cache"}
            self.decisions[op] = rec
            return rec["backend"]

        if len(candidates) == 1:
            backend = next(iter(candidates))
            rec = {"backend": backend, "shape": shape, "kv_dtype": kv_dtype,
                   "timings_ms": {}, "source": "only_candidate"}
        else:
            timings: dict[str, float] = {}
            errors: dict[str, str] = {}
            for name, fn in candidates.items():
                try:
                    timings[name] = round(self.timer(fn) * 1000.0, 4)
                except Exception as e:  # noqa: BLE001 - recorded and logged below
                    errors[name] = f"{type(e).__name__}: {e}"[:2000]
                    (self.logger or logging.getLogger(__name__)).error(
                        f"autotune: {op} candidate {name!r} failed at shape "
                        f"{shape} ({kv_dtype}): {errors[name]}")
            if timings:
                backend = min(timings, key=lambda n: timings[n])
            else:
                backend = "xla" if "xla" in candidates else next(iter(candidates))
            rec = {"backend": backend, "shape": shape, "kv_dtype": kv_dtype,
                   "timings_ms": timings, "source": "measured"}
            if errors:
                rec["errors"] = errors
        self.decisions[op] = rec
        self._persist(key, rec)
        return rec["backend"]

    def _persist(self, key: str, rec: dict) -> None:
        entry = {"backend": rec["backend"],
                 "timings_ms": rec.get("timings_ms", {}),
                 "at": time.time()}
        self._cache[key] = entry
        self._own[key] = entry
        if not self.cache_file:
            return
        try:
            # read-merge-write, merging ONLY the keys this tuner decided:
            # re-writing the whole init-time snapshot could revert another
            # process's fresher measurement for a key we never touched.
            # Atomic rename so a crash never leaves a torn file.
            merged = _load_cache(self.cache_file, self.logger)
            merged.update(self._own)
            tmp = f"{self.cache_file}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"version": FORMAT_VERSION, "entries": merged}, f, indent=1)
            os.replace(tmp, self.cache_file)
        except Exception as e:  # noqa: BLE001 - persistence is an optimization
            if self.logger is not None:
                self.logger.warn(f"could not persist autotune cache {self.cache_file}: {e}")

    def pins(self) -> dict[str, str]:
        return {op: rec["backend"] for op, rec in self.decisions.items()}

    def report(self) -> dict[str, Any]:
        out: dict[str, Any] = {"device_kind": self.device_kind,
                               "decisions": dict(self.decisions)}
        errors = {op: rec["errors"] for op, rec in self.decisions.items()
                  if rec.get("errors")}
        if errors:
            out["errors"] = errors  # {op: {backend: message}}; absent = clean
        if self.role:
            out["role"] = self.role
        if self.sharding:
            out["sharding"] = self.sharding
        return out


__all__ = [
    "Autotuner", "cache_path", "decision_scope", "enabled", "entry_key",
    "last_report", "pinned_backend", "set_last_report", "shape_key",
]
