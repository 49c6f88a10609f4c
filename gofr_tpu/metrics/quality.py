"""Online numerics/quality plane: shadow-score sampled traffic against a
golden reference configuration (docs/observability.md "Quality plane").

The serving stack answers *how fast* everywhere (tracing, SLO, perf
rooflines) but nothing answers *is the math still right*: int8/int4 KV
with fused dequant, Pallas kernels, LoRA deltas and live weight
hot-swap all produce plausible-looking tokens when they drift. This module
closes that gap with a teacher-forced shadow scorer:

- For a sampled fraction of completed requests (``QUALITY_SHADOW_RATE``),
  the request's *exact emitted token sequence* is re-scored — no
  re-sampling, so the check is deterministic by construction — through two
  configurations:

  * the **serving arm**: base weights + the live KV dtype's fake-quant
    round-trip (ops/kvcache.fake_quant_row for int8, ops/quant.
    fake_quant_row_int4 for int4 — the exact scale-dtype semantics the
    pool stores) + the request's LoRA head delta;
  * the **reference arm**: slot-0 base weights, dense bf16 KV via the
    plain XLA attention path, no adapter.

- Per-token divergence rolls up into ``app_tpu_quality_{logprob_delta,
  kl,top1_agree}`` keyed by what the serving path actually used
  (``kv_dtype``, ``backend``, ``adapter``), a first-divergence-token-index
  histogram, and summable good/total counters that ride the gossip digest
  (metrics/federation.py) for exact sum-of-parts fleet rollups.

- Each scored sample keeps a bounded replay payload (prompt ids, emitted
  tokens, divergence report) that the SLO CaptureWatcher joins into
  anomaly bundles; ``scripts/replay_bundle.py`` re-executes them offline.

Scoring runs on the engine device thread only during idle loop iterations
— one bounded forward per iteration, re-checking the interactive backlog
between arms — and claims no decode slots or KV pages, so interactive
traffic always wins and the plane can never leak pool state. With the
rate at 0 (the default) the plane is never constructed and the engine is
bit-identical to the pre-quality build.
"""

from __future__ import annotations

import collections
import random
import threading
import time
from typing import Any, Callable

import numpy as np

__all__ = [
    "QualityPlane",
    "divergence_report",
    "make_adapter_head_fn",
    "make_serving_attn_fn",
    "teacher_forced_rows",
]


# -- pure scoring helpers ------------------------------------------------------


def _pow2_bucket(n: int, max_len: int) -> int:
    """Pad shadow sequences to a power-of-two ladder (min 16) so the
    teacher-forced forward compiles O(log max_len) signatures, not one per
    request length — the same discipline as the engine's prefill buckets."""
    b = 16
    while b < n:
        b *= 2
    return max(n, min(b, max_len)) if max_len else b


_ATTN_CACHE: dict[str, Any] = {}


def make_serving_attn_fn(kv_dtype: str):
    """Attention wrapper reproducing the live KV pool's quantization on the
    teacher-forced path: k/v round-trip through the pool's exact row-quant
    + scale-dtype semantics before attention. Returns None for the dense
    pool (the serving arm IS the reference attention there). Cached per
    dtype so every call reuses one function object — jit retraces once."""
    kv_dtype = kv_dtype or "bf16"
    if kv_dtype in ("", "bf16", "dense"):
        return None
    if kv_dtype in _ATTN_CACHE:
        return _ATTN_CACHE[kv_dtype]
    from gofr_tpu.ops.attention import mha_attention

    if kv_dtype == "int8":
        from gofr_tpu.ops.kvcache import fake_quant_row as _fq
    elif kv_dtype == "int4":
        from gofr_tpu.ops.quant import fake_quant_row_int4 as _fq
    else:
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}: use bf16, int8 or int4")

    def attn(q, k, v, *, causal=True, kv_lengths=None):
        return mha_attention(q, _fq(k), _fq(v), causal=causal,
                             kv_lengths=kv_lengths)

    _ATTN_CACHE[kv_dtype] = attn
    return attn


def make_adapter_head_fn(a: np.ndarray, b: np.ndarray, scale: float):
    """lm_head hook adding the request's LoRA delta exactly as serving does
    (ops/lora.lora_logits_delta f32 math over a one-slot pool): base logits
    in model dtype + f32 low-rank delta — promotion is exact, so a zero
    delta keeps the base path bit-identical."""
    import jax.numpy as jnp

    from gofr_tpu.ops.lora import lora_logits_delta
    from gofr_tpu.ops.quant import qdot

    pool = (jnp.zeros((1,), jnp.int32),
            jnp.asarray(a, jnp.float32)[None],
            jnp.asarray(b, jnp.float32)[None],
            jnp.asarray([float(scale)], jnp.float32))

    def head_fn(x, head):
        # x [B,S,E] maps onto lora_logits_delta's [N,T,E] verify layout
        return qdot(x, head) + lora_logits_delta(x, pool)

    return head_fn


def teacher_forced_rows(family, cfg, params, prompt, emitted, *,
                        attn_fn=None, head_fn=None) -> np.ndarray:
    """Teacher-forced logits over the emitted positions: feed the full
    ``prompt + emitted`` sequence through ``family.forward`` (padded to a
    pow2 bucket, lengths-masked) and slice the rows that *predicted* each
    emitted token — rows ``[len(prompt)-1, len(prompt)-1+T)``. Returns
    f32 ``[T, vocab]``. Deterministic: same inputs → same bucket → same
    compiled program → bitwise-identical rows."""
    import jax.numpy as jnp

    seq = list(map(int, prompt)) + list(map(int, emitted))
    n = len(seq)
    t = len(emitted)
    if t < 1 or len(prompt) < 1:
        raise ValueError("teacher-forced scoring needs >=1 prompt and emitted token")
    bucket = _pow2_bucket(n, int(getattr(cfg, "max_seq_len", 0)))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = np.asarray(seq, np.int32)
    lengths = jnp.asarray([n], jnp.int32)
    logits = family.forward(cfg, params, jnp.asarray(padded), lengths,
                            attn_fn, head_fn)
    lo = len(prompt) - 1
    return np.asarray(logits[0, lo:lo + t], np.float32)


def _log_softmax(rows: np.ndarray) -> np.ndarray:
    z = rows.astype(np.float64)
    z = z - z.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))


def divergence_report(serving_rows: np.ndarray, ref_rows: np.ndarray,
                      emitted) -> dict[str, Any]:
    """Per-token divergence between the serving-configuration re-score and
    the reference re-score of one emitted sequence.

    - ``logprob_delta``: serving minus reference log-prob of each emitted
      token (mean/max absolute values reported);
    - ``kl``: KL(serving ‖ reference) per position;
    - ``top1_agree``: fraction of positions where the REFERENCE argmax
      equals the token the live engine actually emitted — this compares
      the golden path against production output, so it catches live
      corruption the re-score arms cannot reproduce (e.g. a miscompiled
      decode kernel);
    - ``first_divergence``: first position whose reference argmax
      disagrees with the emitted token (-1 = full agreement);
    - ``agree``: the per-token agreement mask, kept for offline replay
      diffing (scripts/replay_bundle.py matches it token-by-token).
    """
    emitted = np.asarray(list(emitted), np.int64)
    t = emitted.shape[0]
    ls = _log_softmax(serving_rows)
    lr = _log_softmax(ref_rows)
    idx = np.arange(t)
    delta = ls[idx, emitted] - lr[idx, emitted]
    kl = (np.exp(ls) * (ls - lr)).sum(axis=-1)
    ref_top1 = lr.argmax(axis=-1)
    agree = ref_top1 == emitted
    first = int(np.argmax(~agree)) if not agree.all() else -1
    return {
        "tokens": int(t),
        "logprob_delta_mean_abs": float(np.abs(delta).mean()),
        "logprob_delta_max_abs": float(np.abs(delta).max()),
        "kl_mean": float(np.maximum(kl, 0.0).mean()),
        "kl_max": float(np.maximum(kl, 0.0).max()),
        "top1_agree": float(agree.mean()),
        "first_divergence": first,
        "agree": [int(x) for x in agree],
    }


# -- the plane -----------------------------------------------------------------


class QualityPlane:
    """Per-engine shadow-scoring state machine.

    ``maybe_capture`` (device thread, request completion) samples finished
    requests into a bounded pending queue — drop-oldest under pressure,
    counted, never blocking. ``step`` (device thread, idle loop) advances
    ONE arm of one sample per call and reports whether it did work, so the
    loop re-checks the interactive backlog between forwards. ``snapshot``
    (any thread) serves /debug/quality and capture-bundle enrichment."""

    def __init__(self, family, cfg, params_fn: Callable[[], Any], *,
                 metrics=None, slo=None, rate: float = 0.0, seed: int = 0,
                 kv_dtype: str = "bf16", backend_fn: Callable[[], str] | None = None,
                 adapter_fn: Callable[[str], tuple | None] | None = None,
                 max_pending: int = 16, max_tokens: int = 64,
                 top1_min: float = 0.9, kl_max: float = 1.0,
                 recent: int = 32):
        self.family = family
        self.cfg = cfg
        self.params_fn = params_fn
        self.metrics = metrics
        self.slo = slo
        self.rate = max(0.0, min(1.0, float(rate)))
        self.kv_dtype = kv_dtype or "bf16"
        self.backend_fn = backend_fn
        self.adapter_fn = adapter_fn
        self.max_pending = max(1, int(max_pending))
        self.max_tokens = max(1, int(max_tokens))
        self.top1_min = float(top1_min)
        self.kl_max = float(kl_max)
        # seeded sampling: a given seed replays the same shadow schedule
        self._rng = random.Random((int(seed) << 1) ^ 0x9E3779B9)
        self._pending: collections.deque = collections.deque()
        self._inflight: dict[str, Any] | None = None
        self._recent: collections.deque = collections.deque(maxlen=max(1, int(recent)))
        self._lock = threading.Lock()
        self.samples = 0   # fully scored
        self.good = 0      # scored and within thresholds
        self.dropped = 0   # sampled but evicted from the pending queue
        self.errors = 0    # scoring failures (never propagate to serving)
        # per-adapter head_fn cache: head_fn is a STATIC jit arg, so reusing
        # one function object per (adapter, factors) identity keeps repeat
        # samples of the same adapter from retracing the forward
        self._head_cache: dict[str, tuple[tuple, Any]] = {}

    # -- capture (request completion path) ---------------------------------

    def maybe_capture(self, prompt_tokens, emitted, *, adapter: str | None = None,
                      qos_class: str | None = None, weights_epoch: int = 0,
                      request_id: str | None = None) -> bool:
        """Roll the sampling dice for one finished request; when selected,
        enqueue a shadow-scoring sample. O(prompt) copy at most — all
        device work happens later, on idle iterations."""
        if self.rate <= 0.0 or len(emitted) < 1 or len(prompt_tokens) < 1:
            return False
        if self.rate < 1.0 and self._rng.random() >= self.rate:
            return False
        sample = {
            "request_id": request_id,
            "prompt": [int(x) for x in prompt_tokens],
            "emitted": [int(x) for x in emitted[: self.max_tokens]],
            "emitted_total": int(len(emitted)),
            "adapter": adapter,
            "qos_class": qos_class,
            "weights_epoch": int(weights_epoch),
            "ts": time.time(),
        }
        if adapter and self.adapter_fn is not None:
            # resolve the LoRA factors NOW — the registry entry may be
            # replaced before the idle loop gets to scoring
            sample["_adapter_factors"] = self.adapter_fn(adapter)
        with self._lock:
            self._pending.append(sample)
            while len(self._pending) > self.max_pending:
                self._pending.popleft()
                self.dropped += 1
                if self.metrics is not None:
                    self.metrics.increment_counter(
                        "app_tpu_quality_shadow_dropped_total", 1)
        return True

    @property
    def pending(self) -> int:
        with self._lock:
            n = len(self._pending)
        return n + (1 if self._inflight is not None else 0)

    # -- scoring (engine idle loop) ----------------------------------------

    def step(self) -> bool:
        """Advance one arm of one sample. Returns True when device work was
        done (the caller should re-check its backlog before calling again).
        Failures are counted and the sample dropped — the quality plane
        must never take the serving loop down with it."""
        s = self._inflight
        if s is None:
            with self._lock:
                if not self._pending:
                    return False
                s = self._inflight = self._pending.popleft()
        try:
            if "_serving_rows" not in s:
                s["_serving_rows"] = self._score(s, serving=True)
                return True
            ref_rows = self._score(s, serving=False)
            self._finalize(s, s.pop("_serving_rows"), ref_rows)
        except Exception:  # noqa: BLE001 - diagnostics plane, never fatal
            with self._lock:
                self.errors += 1
            self._inflight = None
        else:
            if "_serving_rows" not in s:
                self._inflight = None
        return True

    def _score(self, s: dict[str, Any], *, serving: bool) -> np.ndarray:
        params = self.params_fn()
        attn_fn = make_serving_attn_fn(self.kv_dtype) if serving else None
        head_fn = None
        if serving:
            factors = s.get("_adapter_factors")
            if factors is not None:
                a, b, scale = factors
                key = (id(a), id(b), float(scale))
                cached = self._head_cache.get(s["adapter"])
                if cached is None or cached[0] != key:
                    cached = (key, make_adapter_head_fn(a, b, scale))
                    self._head_cache[s["adapter"]] = cached
                head_fn = cached[1]
        return teacher_forced_rows(
            self.family, self.cfg, params, s["prompt"], s["emitted"],
            attn_fn=attn_fn, head_fn=head_fn)

    def _finalize(self, s: dict[str, Any], serving_rows: np.ndarray,
                  ref_rows: np.ndarray) -> None:
        report = divergence_report(serving_rows, ref_rows, s["emitted"])
        ok = (report["top1_agree"] >= self.top1_min
              and report["kl_mean"] <= self.kl_max)
        labels = {
            "kv_dtype": self.kv_dtype,
            "backend": self.backend_fn() if self.backend_fn is not None else "xla",
            "adapter": s.get("adapter") or "base",
        }
        m = self.metrics
        if m is not None:
            m.record_histogram("app_tpu_quality_logprob_delta",
                               report["logprob_delta_mean_abs"], **labels)
            m.record_histogram("app_tpu_quality_kl", report["kl_mean"], **labels)
            m.set_gauge("app_tpu_quality_top1_agree", report["top1_agree"],
                        **labels)
            if report["first_divergence"] >= 0:
                m.record_histogram("app_tpu_quality_first_divergence_token",
                                   report["first_divergence"], **labels)
            m.increment_counter("app_tpu_quality_samples_total", 1, **labels)
            if ok:
                m.increment_counter("app_tpu_quality_good_total", 1, **labels)
        if self.slo is not None:
            observe = getattr(self.slo, "observe_quality", None)
            if callable(observe):
                observe(s.get("qos_class"), ok)
        entry = {k: v for k, v in s.items() if not k.startswith("_")}
        entry["labels"] = labels
        entry["ok"] = ok
        entry["report"] = report
        with self._lock:
            self.samples += 1
            if ok:
                self.good += 1
            self._recent.append(entry)

    # -- host-side helpers --------------------------------------------------

    def drain(self, timeout: float = 30.0) -> bool:
        """Block (host thread) until the engine's idle loop has scored every
        pending sample, or the timeout passes. Test/bench helper only."""
        deadline = time.monotonic() + timeout
        while self.pending:
            if time.monotonic() > deadline:
                return False
            time.sleep(0.01)
        return True

    def snapshot(self, *, replay: bool = True) -> dict[str, Any]:
        """The /debug/quality + capture-bundle view: plane totals plus the
        recent per-sample reports (with replay payloads unless trimmed)."""
        with self._lock:
            recent = list(self._recent)
            out = {
                "rate": self.rate,
                "kv_dtype": self.kv_dtype,
                "pending": len(self._pending) + (1 if self._inflight else 0),
                "samples": self.samples,
                "good": self.good,
                "dropped": self.dropped,
                "errors": self.errors,
                "thresholds": {"top1_min": self.top1_min, "kl_max": self.kl_max},
            }
        if not replay:
            recent = [{k: v for k, v in e.items()
                       if k not in ("prompt", "emitted")} for e in recent]
        out["recent"] = recent
        return out
