"""Native (C++) host-runtime core: prefill planner + token data loader.

The shared library is compiled from ``src/gofr_native.cc`` on first use
(g++) and bound via ctypes — no pybind11, no build step for users. The
built file is named by a hash of the source's CONTENTS
(``src/libgofr_native.<hash>.so``, gitignored), so what gets loaded was
built from exactly the tracked source: a stale or copied ``.so`` is never
picked up, whatever its mtime. Every entry point has a pure-Python twin
with IDENTICAL semantics (tested against each other) for hosts without a
toolchain; ``GOFR_NATIVE=0`` forces it. :func:`planner_in_use` says which
of the two serves (engines log it at construction); a build that FAILS
where ``g++`` exists is logged at error level with the compiler's output
and kept in :func:`build_error`.

Reference capability map: GoFr's runtime is Go (SURVEY.md §2) — the TPU
build keeps Python as the orchestration layer and moves the schedule/IO
hot paths native, mirroring how the reference leans on its compiled
runtime rather than an interpreter.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from dataclasses import dataclass

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "src", "gofr_native.cc")
_log = logging.getLogger(__name__)
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_lib_failed = False
_build_error: str | None = None


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(os.path.dirname(_SRC), f"libgofr_native.{digest}.so")


def _build() -> str | None:
    global _build_error
    lib_path = _lib_path()
    if os.path.exists(lib_path):
        return lib_path
    # compile to a private temp path and publish atomically so a concurrent
    # process can never dlopen a half-written .so
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
             "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, lib_path)
        return lib_path
    except FileNotFoundError:
        return None  # no toolchain on this host: the Python twin serves
    except (OSError, subprocess.SubprocessError) as e:
        stderr = getattr(e, "stderr", b"") or b""
        _build_error = f"{e}: {stderr.decode(errors='replace')[-2000:]}"
        _log.error("native build failed, Python planner in use: %s", _build_error)
        return None
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def load_native() -> ctypes.CDLL | None:
    """The shared library, building it if needed; None when unavailable."""
    global _lib, _lib_failed, _build_error
    if os.environ.get("GOFR_NATIVE", "") == "0":
        return None
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        path = _build()
        if path is None:
            _lib_failed = True
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            _lib_failed = True
            _build_error = f"dlopen {path}: {e}"
            _log.error("native library unusable, Python planner in use: %s", _build_error)
            return None
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.gofr_plan_prefill.restype = ctypes.c_int32
        lib.gofr_plan_prefill.argtypes = [
            i32p, i64p, ctypes.c_int32, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int32, i32p, ctypes.c_int32, i32p, i32p, i32p, i32p, i32p,
        ]
        lib.gofr_loader_create.restype = ctypes.c_void_p
        lib.gofr_loader_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_uint64, ctypes.c_int32,
        ]
        lib.gofr_loader_next.restype = ctypes.c_int32
        lib.gofr_loader_next.argtypes = [ctypes.c_void_p, i32p]
        lib.gofr_loader_num_tokens.restype = ctypes.c_int64
        lib.gofr_loader_num_tokens.argtypes = [ctypes.c_void_p]
        lib.gofr_loader_destroy.restype = None
        lib.gofr_loader_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return load_native() is not None


def planner_in_use() -> str:
    """'native' or 'python' — which prefill planner ``plan_prefill`` runs."""
    return "native" if native_available() else "python"


def build_error() -> str | None:
    """Why the native build or load failed on a host that has ``g++``;
    None when it succeeded, was never tried, or there is no toolchain."""
    return _build_error


# ---------------------------------------------------------------------------
# Prefill planner
# ---------------------------------------------------------------------------


@dataclass
class PrefillPlan:
    chosen: list[int]       # indices into the pending list, EDF order
    expired: list[int]      # past deadline OR longer than every bucket
    len_bucket: int
    batch_bucket: int


def _plan_prefill_py(
    lens, deadlines_us, now_us: int, free_slots: int, max_batch: int, len_buckets
) -> PrefillPlan:
    """Reference implementation — semantics identical to gofr_plan_prefill."""
    max_bucket = len_buckets[-1]
    expired = [
        i for i in range(len(lens))
        if 0 < deadlines_us[i] < now_us or lens[i] > max_bucket
    ]
    dead = set(expired)
    valid = [i for i in range(len(lens)) if i not in dead]
    if not valid or free_slots <= 0 or max_batch <= 0:
        return PrefillPlan([], expired, 0, 0)
    valid.sort(key=lambda i: (deadlines_us[i] if deadlines_us[i] > 0 else 2**62, i))
    lead_len = lens[valid[0]]
    bucket = next((b for b in len_buckets if b >= lead_len), len_buckets[-1])
    cap = min(free_slots, max_batch)
    chosen = [i for i in valid if lens[i] <= bucket][:cap]
    bb = 1
    while bb < len(chosen):
        bb <<= 1
    return PrefillPlan(chosen, expired, bucket, min(bb, max_batch))


def plan_prefill(
    lens, deadlines_us, now_us: int, free_slots: int, max_batch: int, len_buckets
) -> PrefillPlan:
    """EDF + bucket-affinity prefill packing: the earliest-deadline request
    leads and sets the length bucket; only requests fitting that bucket
    join the batch, so one long prompt never inflates everyone's padding.
    ``deadlines_us[i] <= 0`` means no deadline. Requests longer than the
    largest bucket are unschedulable and come back in ``expired`` (the
    caller fails them) rather than starving silently."""
    lib = load_native()
    n = len(lens)
    if lib is None or n == 0:
        return _plan_prefill_py(lens, deadlines_us, now_us, free_slots, max_batch, len_buckets)
    lens_a = np.ascontiguousarray(lens, np.int32)
    dl_a = np.ascontiguousarray(deadlines_us, np.int64)
    bk_a = np.ascontiguousarray(len_buckets, np.int32)
    chosen = np.zeros((max(max_batch, 1),), np.int32)
    expired = np.zeros((n,), np.int32)
    n_exp = ctypes.c_int32(0)
    lb = ctypes.c_int32(0)
    bb = ctypes.c_int32(0)
    i32p = ctypes.POINTER(ctypes.c_int32)
    count = lib.gofr_plan_prefill(
        lens_a.ctypes.data_as(i32p),
        dl_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, now_us, free_slots, max_batch,
        bk_a.ctypes.data_as(i32p), len(len_buckets),
        chosen.ctypes.data_as(i32p), expired.ctypes.data_as(i32p),
        ctypes.byref(n_exp), ctypes.byref(lb), ctypes.byref(bb),
    )
    return PrefillPlan(
        chosen[:count].tolist(), expired[: n_exp.value].tolist(), int(lb.value), int(bb.value)
    )


# ---------------------------------------------------------------------------
# Token data loader
# ---------------------------------------------------------------------------


class TokenLoader:
    """Batches of [batch, seqlen+1] int32 crops from a flat token file
    (raw little-endian int32), prefetched by a native background thread.
    Falls back to numpy memmap + same splitmix64 crop stream."""

    def __init__(self, path: str, batch: int, seqlen: int, *, seed: int = 0, prefetch: int = 4):
        self.path, self.batch, self.seqlen, self.seed = path, batch, seqlen, seed
        self._lib = load_native()
        self._handle = None
        self._mm = None
        self._counter = 0
        if self._lib is not None:
            h = self._lib.gofr_loader_create(
                path.encode(), batch, seqlen, ctypes.c_uint64(seed), prefetch
            )
            if h:
                self._handle = ctypes.c_void_p(h)
                self.num_tokens = int(self._lib.gofr_loader_num_tokens(self._handle))
                return
        self._mm = np.memmap(path, dtype=np.int32, mode="r")
        self.num_tokens = int(self._mm.shape[0])
        if self.num_tokens < seqlen + 1:
            raise ValueError(f"corpus {path} shorter than seqlen+1={seqlen + 1}")

    @staticmethod
    def _splitmix64(z: int) -> int:
        """The splitmix64 finalizer — bit-for-bit the C++ loader's mix."""
        m = 2**64 - 1
        z &= m
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & m
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & m
        return z ^ (z >> 31)

    def next(self) -> np.ndarray:
        """→ [batch, seqlen+1] int32 (inputs are [:, :-1], targets [:, 1:])."""
        span = self.seqlen + 1
        if self._handle is not None:
            out = np.empty((self.batch, span), np.int32)
            rc = self._lib.gofr_loader_next(
                self._handle, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
            )
            if rc != 0:
                raise RuntimeError("native loader stopped")
            return out
        out = np.empty((self.batch, span), np.int32)
        max_start = self.num_tokens - span
        for b in range(self.batch):
            self._counter += 1
            z = self._splitmix64(self.seed + 0x9E3779B97F4A7C15 * self._counter)
            start = z % (max_start + 1) if max_start > 0 else 0
            out[b] = self._mm[start : start + span]
        return out

    def __iter__(self):
        while True:
            yield self.next()

    def close(self) -> None:
        if self._handle is not None:
            self._lib.gofr_loader_destroy(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
