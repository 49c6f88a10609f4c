"""Test utilities — the reference's `pkg/gofr/testutil` analog (SURVEY §2.7),
extended with the TPU build's own needs: shared mesh-serving correctness
checks used by both the pytest tier and the driver's multichip dryrun, so
the two can't silently drift apart.
"""

from __future__ import annotations

import threading
from typing import Callable

import jax
import jax.numpy as jnp


def tiny_f32_llama():
    """A tiny FLOAT32 llama config + params for cross-sharding greedy-token
    comparisons. f32 matters: sharded matmul reduction order differs from
    the dense single-device order, and on a random bf16 model near-tie
    argmaxes flip — which would test numerics, not the serving path."""
    from gofr_tpu.models import LlamaConfig, llama

    cfg = LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        num_layers=2, num_heads=8, num_kv_heads=4, max_seq_len=128,
        dtype=jnp.float32,
    )
    params = llama.init(cfg, jax.random.key(3))
    return cfg, params


def greedy_reference(cfg, params) -> Callable[[list[int], int], list[int]]:
    """Single-device incremental-forward greedy decoder (the ground truth
    every engine/sharding path must reproduce token-for-token)."""
    from gofr_tpu.models import llama

    def ref(prompt: list[int], n: int) -> list[int]:
        seq = list(prompt)
        for _ in range(n):
            logits = llama.forward(cfg, params, jnp.asarray([seq], jnp.int32))
            seq.append(int(jnp.argmax(logits[0, -1])))
        return seq[len(prompt):]

    return ref


def assert_paged_pool_consistent(engine, slots_empty: bool = False) -> None:
    """Paged-pool accounting invariant: every page is free XOR held, and
    ``_page_refs`` equals the true holder count (slot block tables + the
    prefix cache's DEVICE tier — host-resident nodes hold no pool pages).
    With ``slots_empty`` (end-of-test quiescence) additionally require that
    only the prefix cache still holds pages — the old "everything is free"
    assertion generalized for prefix retention."""
    import numpy as np

    refs = np.zeros(engine.total_pages, np.int64)
    for pages in engine._slot_pages:
        for p in pages:
            refs[p] += 1
    if slots_empty:
        assert not refs.any(), "a vacated slot still holds pages"
    if engine._prefix is not None:
        for node in engine._prefix._nodes.values():
            if node.page_id >= 0:
                refs[node.page_id] += 1
    assert (refs == engine._page_refs).all(), "refcounts diverge from holders"
    free = set(engine._free_pages)
    assert len(free) == len(engine._free_pages), "free list holds duplicates"
    for p in range(engine.total_pages):
        assert (p in free) == (refs[p] == 0), f"page {p}: free/held mismatch"


def assert_page_refs_consistent(engine) -> None:
    """Full paged-cache accounting cross-check, safe to call at any point
    (takes the engine state lock): ``_page_refs`` vs the true holders (slot
    page lists + device-tier prefix nodes), free-list/refcount duality,
    block-table rows vs ``_slot_pages``, and both prefix-cache tiers'
    internal invariants (host nodes carry payloads and no page; device
    nodes carry a page and no payload; host byte/page accounting matches
    the stored payloads). No-op on slot-layout engines — used as a shared
    teardown by tests/test_prefix.py and tests/test_async_pipeline.py."""
    if getattr(engine, "kv_layout", "slot") != "paged":
        return
    import numpy as np

    with engine._state_lock:
        assert_paged_pool_consistent(engine)
        for i, pages in enumerate(engine._slot_pages):
            row = engine._table[i]
            assert list(row[: len(pages)]) == list(pages), (
                f"slot {i}: block table row diverges from _slot_pages")
            assert (row[len(pages):] == engine.total_pages).all(), (
                f"slot {i}: table rows past the owned pages must be OOB")
            if engine.slots[i] is None:
                assert not pages, f"empty lane {i} still owns pages"
        cache = engine._prefix
        if cache is None:
            return
        dev = host = 0
        host_bytes = 0
        for key, node in cache._nodes.items():
            if node.page_id >= 0:
                dev += 1
                assert node.host is None and node.host_nbytes == 0, (
                    "device-tier node still holds a host payload")
            else:
                host += 1
                assert node.host is not None, "host-tier node lost its payload"
                assert not node.pending, "host-tier node marked upload-pending"
                host_bytes += node.host_nbytes
        # child counters: recompute from parent links across both tiers
        children = {k: [0, 0] for k in cache._nodes}
        for node in cache._nodes.values():
            ent = children.get(node.parent_key)
            if ent is not None:
                ent[0] += 1
                if node.page_id >= 0:
                    ent[1] += 1
        for key, node in cache._nodes.items():
            want_all, want_dev = children[key]
            assert node.children == want_all, (
                f"node {key}: children counter {node.children} != {want_all}")
            assert node.dev_children == want_dev, (
                f"node {key}: dev_children counter {node.dev_children} != {want_dev}")
        assert len(cache) == dev, "device-tier count diverges"
        assert cache.host_pages == host, "host-tier count diverges"
        assert cache.host_bytes == host_bytes, "host byte accounting diverges"
        assert np.all(engine._page_refs >= 0), "negative page refcount"


def check_mesh_serving(config: dict[str, str], *, n_requests: int = 6,
                       max_new: int = 5, timeout: float = 600.0,
                       **engine_kw) -> None:
    """Build an engine on a mesh container (per ``config``, e.g.
    ``{"TPU_MESH": "dp:2,tp:4"}``), serve ``n_requests`` concurrent greedy
    requests, and require token-exact agreement with single-device decoding.
    Raises AssertionError on divergence."""
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import ModelSpec
    from gofr_tpu.tpu.engine import build_engine

    cfg, params = tiny_f32_llama()
    ref = greedy_reference(cfg, params)

    container = new_mock_container(config)
    engine_kw.setdefault("slots", 4)
    engine_kw.setdefault("max_len", 64)
    engine_kw.setdefault("max_prefill_batch", 2)
    if engine_kw.pop("spec_self_draft", False):
        # draft-model speculation with the target as its own draft: the
        # sharded draft path compiles/executes, every proposal is accepted,
        # and tokens must still match the single-device reference. The
        # draft params must be the ENGINE's sharded tree, so rebuild from
        # the same seed the engine will use.
        from gofr_tpu.models import llama as _llama

        engine_kw["spec_draft"] = (_llama, cfg, _llama.init(cfg, jax.random.key(3)))
    eng = build_engine(ModelSpec(family="llama", task="generate", config=cfg),
                       container, seed=3, **engine_kw)
    prompts = [[i + 1, (2 * i) % 200 + 1, (7 * i) % 150 + 1] for i in range(n_requests)]
    want = [ref(p, max_new) for p in prompts]
    results: list = [None] * len(prompts)

    def worker(i):
        results[i] = eng.generate(prompts[i], max_new_tokens=max_new, timeout=timeout)

    try:
        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=timeout)
        for i, r in enumerate(results):
            assert r is not None, f"request {i} did not complete"
            assert r["tokens"] == want[i], (
                f"request {i} diverged on mesh {config.get('TPU_MESH')}: "
                f"{r['tokens']} != {want[i]}"
            )
    finally:
        eng.stop()


def assert_lane_sets_consistent(engine) -> None:
    """The incrementally-maintained lane sets (engine._free_lanes /
    _prefill_lanes / _decode_lanes) must always agree with a fresh rescan
    of ``engine.slots`` — they replace the per-iteration O(num_slots)
    sweeps, so drift would silently corrupt admission/decode masking."""
    with engine._state_lock:
        free = {i for i, s in enumerate(engine.slots) if s is None}
        prefill = {i for i, s in enumerate(engine.slots)
                   if s is not None and s.last_token is None}
        decode = {i for i, s in enumerate(engine.slots)
                  if s is not None and s.last_token is not None}
        assert engine._free_lanes == free, (engine._free_lanes, free)
        assert engine._prefill_lanes == prefill, (engine._prefill_lanes, prefill)
        assert engine._decode_lanes == decode, (engine._decode_lanes, decode)
