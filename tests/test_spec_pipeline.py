"""Speculative rounds INSIDE the unified pipeline (ISSUE 13 tentpole b):
paged spec_rounds no longer run synchronously — ``dispatch_spec_paged``
enqueues each round onto the engine's one bounded in-flight queue
(``engine._dq``) with worst-case page over-claim at dispatch and surplus
trim at fold. This module proves the OVERLAP (a spec round is dispatched
while an older entry is still in flight), and drills the allocator edges
the over-claim creates: cancellation mid-round, preemption under a tight
pool, and trim-at-fold accounting — zero page leaks throughout
(testutil.assert_page_refs_consistent). Token exactness of paged spec vs
plain greedy lives in tests/test_spec_decode.py; this file is about the
queue discipline and page lifecycle."""

import collections
import threading
import time

import jax
import pytest

from gofr_tpu.container import new_mock_container
from gofr_tpu.models import LlamaConfig, llama
from gofr_tpu.testutil import (
    assert_page_refs_consistent,
    assert_paged_pool_consistent,
)
from gofr_tpu.tpu.engine import GenerateEngine

pytestmark = pytest.mark.quick


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.key(7))

    def ref(prompt, n_new):
        import jax.numpy as jnp

        seq = list(prompt)
        for _ in range(n_new):
            logits = llama.forward(cfg, params, jnp.asarray([seq], jnp.int32))
            seq.append(int(jnp.argmax(logits[0, -1])))
        return seq[len(prompt):]

    return cfg, params, ref


def make_engine(cfg, params, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_prefill_batch", 2)
    kw.setdefault("decode_chunk", 2)
    kw.setdefault("spec_tokens", 2)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("page_size", 8)
    return GenerateEngine(llama, cfg, params, new_mock_container(), **kw)


class _SpyDeque(collections.deque):
    """Drop-in _dq that records, at every dispatch, what kind of entry
    went in and how deep the queue already was — the direct witness that
    spec rounds ride the pipelined queue instead of serializing."""

    def __init__(self):
        super().__init__()
        self.events = []  # (kind, depth_before_append)

    def append(self, entry):
        self.events.append((entry[0], len(self)))
        super().append(entry)


class _QueueSpy:
    def __init__(self, eng):
        self._eng = eng

    def __enter__(self):
        spy = _SpyDeque()
        spy.extend(self._eng._dq)
        self._eng._dq = spy
        self.events = spy.events
        return self

    def __exit__(self, *exc):
        pass  # the spy stays a perfectly good deque


def test_spec_rounds_ride_the_inflight_queue(setup):
    """With pipeline depth 2, some spec round must be APPENDED while an
    older entry is still un-processed (depth_before >= 1): speculation is
    pipelined, not a synchronous side-channel. Tokens stay exact."""
    cfg, params, ref = setup
    eng = make_engine(cfg, params, decode_pipeline=2)
    prompts = [[i + 1, (3 * i) % 200 + 1, (5 * i) % 150 + 1] for i in range(4)]
    want = [ref(p, 12) for p in prompts]
    results = [None] * 4
    try:
        with _QueueSpy(eng) as spy:
            threads = [
                threading.Thread(
                    target=lambda i=i: results.__setitem__(
                        i, eng.generate(prompts[i], max_new_tokens=12,
                                        timeout=300)))
                for i in range(4)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
        for i, r in enumerate(results):
            assert r is not None and r["tokens"] == want[i], f"request {i}"
        kinds = {k for k, _ in spy.events}
        assert "spec" in kinds, f"no spec round ever dispatched: {kinds}"
        assert any(k == "spec" and depth >= 1 for k, depth in spy.events), (
            "every spec round was dispatched against an empty queue — "
            f"speculation is NOT overlapping readback: {spy.events[:20]}")
        assert_page_refs_consistent(eng)
    finally:
        eng.stop()


def test_depth_one_keeps_spec_synchronous(setup):
    """ENGINE_PIPELINE=1 is the debugging escape hatch: every spec round
    must see an EMPTY queue at dispatch (fully synchronous), and tokens
    still match the reference."""
    cfg, params, ref = setup
    eng = make_engine(cfg, params, decode_pipeline=1)
    try:
        with _QueueSpy(eng) as spy:
            out = eng.generate([5, 3, 9], max_new_tokens=10, timeout=300)
        assert out["tokens"] == ref([5, 3, 9], 10)
        spec_depths = [d for k, d in spy.events if k == "spec"]
        assert spec_depths and max(spec_depths) == 0, spec_depths
    finally:
        eng.stop()


def test_cancel_mid_spec_round_releases_overclaimed_pages(setup):
    """Cancel a request while its spec rounds (and their over-claimed
    pages) are in flight: the victim completes with its error, the
    surplus pages return to the free list, and a survivor stays exact."""
    cfg, params, ref = setup
    eng = make_engine(cfg, params, decode_pipeline=2)
    try:
        victim = eng.submit([9, 9, 9], max_new_tokens=40)
        survivor = eng.submit([5, 3, 9, 2], max_new_tokens=12)
        time.sleep(0.2)
        victim.cancel()
        out = survivor.result(timeout=300)
        assert out["tokens"] == ref([5, 3, 9, 2], 12)
        with pytest.raises(Exception):
            victim.result(timeout=60)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            with eng._state_lock:
                if all(s is None for s in eng.slots) and not eng._dq:
                    break
            time.sleep(0.02)
        assert_paged_pool_consistent(eng, slots_empty=True)
    finally:
        eng.stop()


def test_overclaim_trims_to_actual_position_at_fold(setup):
    """After a generation finishes, no lane may keep pages beyond what its
    final position needs: the dispatch-time worst-case claim
    (pos + chunk_span * (inflight + 1) - 1) must have been trimmed back by
    the fold (engine._trim_lane_pages). With the engine idle, every
    non-prefix-cached page is back on the free list."""
    cfg, params, _ = setup
    eng = make_engine(cfg, params, decode_pipeline=2, prefix_cache=False)
    try:
        eng.generate([7, 1, 4], max_new_tokens=9, timeout=300)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            with eng._state_lock:
                if all(s is None for s in eng.slots) and not eng._dq:
                    break
            time.sleep(0.02)
        with eng._state_lock:
            held = int(eng._page_refs.sum())
        assert held == 0, f"{held} pages leaked past the fold's trim"
        assert_paged_pool_consistent(eng, slots_empty=True)
    finally:
        eng.stop()


def test_preemption_under_tight_pool_with_pipelined_spec(setup):
    """Worst-case-span over-claim against a pool that cannot hold every
    lane's worst case at once: preemption, speculation, and the pipelined
    queue interleave without deadlock, divergence, or page leaks."""
    cfg, params, ref = setup
    eng = make_engine(cfg, params, total_pages=14, decode_pipeline=2)
    prompts = [[i + 1, (3 * i) % 200 + 1, (5 * i) % 150 + 1] for i in range(4)]
    want = [ref(p, 12) for p in prompts]
    results = [None] * 4
    try:
        threads = [
            threading.Thread(
                target=lambda i=i: results.__setitem__(
                    i, eng.generate(prompts[i], max_new_tokens=12,
                                    timeout=300)))
            for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for i, r in enumerate(results):
            assert r is not None and r["tokens"] == want[i], f"request {i}"
        assert_paged_pool_consistent(eng, slots_empty=True)
    finally:
        eng.stop()
