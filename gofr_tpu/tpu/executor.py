"""Device-dispatch executor layer for ``GenerateEngine``.

The scheduler/executor split (ROADMAP O1/O4; the seam engine roles are
built on): tpu/engine.py keeps the SCHEDULER half — admission planning,
slot/lane/page bookkeeping, QoS/deadline accounting, and the ``_dq``
fold loop — while this module owns DEVICE DISPATCH: packed-array
assembly and the compiled-program calls for batched prefill, chunked
prefill, host-tier swap-ins and spill materialization, warmup
compilation, and the handoff page gathers. tpu/decode.py's decode/spec
dispatch paths are re-exported here, so this module is the single
device-dispatch façade an engine role composes over (``ENGINE_ROLE`` —
a prefill worker never calls :func:`dispatch_decode`; a decode worker
never warms the batched-prefill programs).

Locking contract: everything here runs on the engine's device thread
and — with one documented exception — OUTSIDE the state lock. The
scheduler snapshots whatever a dispatch needs into a plan object before
releasing the lock (packing is pure numpy; a wedged device call must
never hold the lock, or ``stop()``'s ``_fail_all`` would deadlock
behind it). The exception is :func:`gather_pages`: a pure DISPATCH
(async, no readback) that is safe under the lock — the same discipline
``_evict_prefix_page`` established for spill gathers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from gofr_tpu.tpu.decode import (  # noqa: F401 - the decode half of the façade
    dispatch_decode,
    dispatch_spec,
    dispatch_spec_paged,
    process_decode,
)
from gofr_tpu.tpu.lockstep import TAG_CHUNK, TAG_DECODE, TAG_PREFILL, TAG_SPEC


def prefill_cols(eng) -> int:
    """Width of the packed prefill ``rows`` block: the block-table columns
    (paged) or the slot-id column (slot) — plus, for paged with spec on,
    ONE trailing slot-id column so the prefill programs can seed the
    device-resident history rows by lane (tpu/programs.py docstring).
    Every prefill pack site (dispatch, warmup, lockstep replay) must
    agree with build_programs' W, so they all call this."""
    if eng.kv_layout != "paged":
        return 1
    return eng.pages_per_slot + (1 if eng.spec_tokens else 0)


class PrefillPlan:
    """Snapshot of one batched-prefill admission round, taken under the
    state lock by ``engine._admit_prefill``: everything the unlocked
    packing + device call needs. ``ready`` is immutable (requests +
    prompt token arrays); lanes/table rows were copied under the lock."""

    __slots__ = ("ready", "meta", "nb", "lb", "w", "rows", "table_rows",
                 "step", "t0", "seq")
    kind = "prefill"  # the _dq entry kind this plan dispatches

    def __init__(self, ready, meta, nb, lb, w, rows, table_rows, step, t0,
                 seq):
        self.ready = ready
        self.meta = meta
        self.nb = nb
        self.lb = lb
        self.w = w
        self.rows = rows
        self.table_rows = table_rows
        self.step = step
        self.t0 = t0
        self.seq = seq


class ChunkPlan:
    """Snapshot of one chunked-prefill dispatch (``_advance_chunked``'s
    locked planning half): slot identity, chunk geometry, and the copied
    block-table row."""

    __slots__ = ("idx", "slot", "chunk", "offset", "last", "lb",
                 "table_row", "temp", "step", "t0", "seq")
    kind = "chunk"

    def __init__(self, idx, slot, chunk, offset, last, lb, table_row,
                 temp, step, t0, seq):
        self.idx = idx
        self.slot = slot
        self.chunk = chunk
        self.offset = offset
        self.last = last
        self.lb = lb
        self.table_row = table_row
        self.temp = temp
        self.step = step
        self.t0 = t0
        self.seq = seq


def dispatch_prefill(eng, plan: PrefillPlan) -> None:
    """Pack and dispatch one batched prefill (the device half of
    ``_admit_prefill``). Pure-numpy packing outside the state lock:
    token/temp data rides the immutable ``plan.ready`` list, lanes and
    table rows were snapshotted under the lock."""
    nb, lb, w = plan.nb, plan.lb, plan.w
    # block-table columns (w may add a trailing slot-id col on top)
    wp = eng.pages_per_slot if eng.kv_layout == "paged" else 0
    # ae: one extra column carrying each row's adapter pool slot, between
    # the rows block and temps (zero = base — padding rows' zero sel
    # selects the all-zeros base adapter, whose delta is exactly 0.0).
    # OFF keeps the pack byte-identical to the pre-adapter layout.
    ae = 1 if eng._adapters_enabled else 0
    packed = eng._staging("prefill", (nb, lb + w + 3 + ae))
    packed[:, lb] = 1  # padding rows: length 1
    temps = np.zeros((nb,), np.float32)
    if eng.kv_layout == "paged":
        packed[:, lb + 1:lb + 1 + wp] = eng.total_pages
        if eng.spec_tokens:
            # padding rows' hist seeding drops via an OOB lane id
            packed[:, lb + 1 + wp] = eng.num_slots
    else:
        packed[:, lb + 1] = eng.num_slots
    for i, (req, toks) in enumerate(plan.ready):
        packed[i, : toks.shape[0]] = toks
        packed[i, lb] = toks.shape[0]
        if eng.kv_layout == "paged":
            packed[i, lb + 1:lb + 1 + wp] = plan.table_rows[i]
            if eng.spec_tokens:
                packed[i, lb + 1 + wp] = plan.rows[i]
        else:
            packed[i, lb + 1] = plan.rows[i]
        if ae:
            packed[i, lb + 1 + w] = plan.meta[i][1].adapter_slot
        temps[i] = float(req.kw.get("temperature", 0.0))
    packed[:, lb + 1 + w + ae] = temps.view(np.int32)
    packed[0, lb + 2 + w + ae] = plan.step

    eng._announce(TAG_PREFILL, lb, nb, packed)
    first_dev, eng.cache = eng._prefill_sample(
        eng.params, eng._base_key, eng.cache, jnp.asarray(packed),
        *((eng._adapter_args(),) if ae else ())
    )
    # tokens, never logits — and NEVER read back here: the future rides
    # the in-flight queue; _fold_prefill activates the claimed slots at
    # dequeue, overlapped with whatever dispatches after this call
    pstep = (eng.perf.step_prefill(
        sum(toks.shape[0] for _, toks in plan.ready), plan.t0)
        if eng.perf is not None else None)
    eng._dq.append(("prefill", first_dev, plan.meta, plan.t0,
                    len(plan.ready) / nb, ("prefill", lb, nb), pstep,
                    plan.seq))


def dispatch_chunk(eng, plan: ChunkPlan) -> None:
    """Pack and dispatch one prefill chunk (the device half of
    ``_advance_chunked``). Everything below is immutable
    (prompt_tokens) or snapshotted under the lock (table row, step)."""
    s, lb, chunk, offset = plan.slot, plan.lb, plan.chunk, plan.offset
    w = prefill_cols(eng)
    wp = eng.pages_per_slot if eng.kv_layout == "paged" else 0
    ae = 1 if eng._adapters_enabled else 0  # sel col after the offset
    packed = eng._staging("chunk", (1, lb + w + 4 + ae))
    packed[0, :chunk] = s.prompt_tokens[offset:offset + chunk]
    packed[0, lb] = chunk
    if eng.kv_layout == "paged":
        packed[0, lb + 1:lb + 1 + wp] = plan.table_row
        if eng.spec_tokens:
            packed[0, lb + 1 + wp] = plan.idx  # hist row to seed
    else:
        packed[0, lb + 1] = plan.idx
    packed[0, lb + 1 + w] = offset  # chunk offset
    if ae:
        packed[0, lb + 2 + w] = s.adapter_slot
    packed[0, lb + 2 + w + ae] = np.float32(plan.temp).view(np.int32)
    packed[0, lb + 3 + w + ae] = plan.step

    eng._announce(TAG_CHUNK, lb, 1, packed)
    first_dev, eng.cache = eng._chunk_prefill(
        eng.params, eng._base_key, eng.cache, jnp.asarray(packed),
        *((eng._adapter_args(),) if ae else ())
    )
    pstep = (eng.perf.step_chunk(chunk, offset, plan.t0)
             if eng.perf is not None else None)
    eng._dq.append(("chunk", first_dev,
                    (plan.idx, s, chunk, offset, plan.last),
                    plan.t0, chunk / lb, ("prefill_chunk", lb, 1), pstep,
                    plan.seq))


def dispatch_swapins(eng) -> bool:
    """Dispatch one async host→device page upload per staged prefix hit
    onto the unified in-flight queue (outside the state lock — packing
    is host memcpy and the device call must never wedge under the
    lock). Pages were claimed and nodes promoted at hit time; the fold
    (``_fold_swapin``) settles the nodes and records the metrics, and
    discards slot bookkeeping by identity like every other entry."""
    from gofr_tpu.ops.paged import swap_in_pages
    from gofr_tpu.tpu.engine import next_bucket
    import time

    items, eng._pending_swapins = eng._pending_swapins, []
    # uploads target the KV pool only (the spec history plane, when the
    # cache is the (kv, hist) tuple, is slot-indexed — never swapped)
    leaves_proto = jax.tree.leaves(eng.kv_cache)
    for idx, slot, keys, pids, payloads in items:
        t0 = time.monotonic()
        n = len(pids)
        # smallest bucketed upload width: padding is at most 2x the
        # pages actually swapped, never the full pages_per_slot
        w = next_bucket(n, eng._swapin_buckets)
        ids = np.full((w,), eng.total_pages, np.int32)  # pad rows: OOB, dropped
        ids[:n] = pids
        stacked = []
        for li, proto in enumerate(leaves_proto):
            buf = np.zeros((proto.shape[0], w) + tuple(proto.shape[2:]),
                           np.asarray(payloads[0][li]).dtype)
            for j in range(n):
                buf[:, j] = payloads[j][li]
            stacked.append(buf)
        payload_tree = jax.tree.unflatten(eng._cache_treedef, stacked)
        kv, marker = swap_in_pages(
            eng.kv_cache, jnp.asarray(ids), payload_tree)
        eng.cache = ((kv, eng.cache[1])
                     if isinstance(eng.cache, tuple) else kv)
        leaves_proto = jax.tree.leaves(kv)
        # the histogram records the ACTUAL transfer (padded width) so
        # swap-in latency and bytes stay comparable
        nbytes = w * eng._page_bytes
        pstep = (eng.perf.step_swapin(nbytes, t0)
                 if eng.perf is not None else None)
        eng._dq.append(("swapin", marker, (idx, slot, keys, n, nbytes),
                        t0, n / w, ("swapin", w), pstep, eng._next_seq()))
    return True


def materialize_spills(eng) -> None:
    """Complete staged spill copies OUTSIDE the state lock: eviction
    dispatched each page's gather asynchronously (so pool pressure
    never blocks the lock on a device round trip) and left the node
    holding the small gathered device buffers; this step — device
    thread, once per loop iteration — blocks on those buffers, copies
    them to host memory, and swaps the node payload. Nodes dropped or
    promoted in between simply skip the replacement."""
    items, eng._pending_spills = eng._pending_spills, []
    for key, dev_payload in items:
        host_payload = tuple(np.asarray(x) for x in dev_payload)
        with eng._state_lock:
            if eng._prefix is not None:
                eng._prefix.replace_host_payload(key, host_payload)


def gather_pages(eng, pages: list[int]) -> list[tuple]:
    """DISPATCH one per-page gather per pool page id and return the
    device-buffer tuples (no readback — callers block on them outside
    the lock). Safe under the state lock: async dispatch only, the
    ``_evict_prefix_page`` discipline. Used by the prefill-role handoff
    export (tpu/handoff.py) and shaped exactly like a host-tier spill
    payload, so the decode side can register it as a host node."""
    from gofr_tpu.ops.paged import gather_page

    return [tuple(jax.tree.leaves(gather_page(eng.kv_cache, jnp.int32(p))))
            for p in pages]


def warmup_compile(eng, lbs: list[int], bbs: list[int]) -> int:
    """Compile every program signature this engine's ROLE can dispatch
    (the body of ``engine.warmup()``; see its docstring for the cache-
    safety argument). Role scoping is the disaggregation warmup win: a
    prefill-only worker skips the decode/spec compiles, a decode-only
    worker skips the batched-prefill ladder — both keep chunked prefill
    (the decode side computes post-hit remainders through it) and the
    host-tier/handoff programs their role needs."""
    count = 0
    warm_prefill = eng.role != "decode"
    warm_decode = eng.role != "prefill"
    w = prefill_cols(eng)
    wp = eng.pages_per_slot if eng.kv_layout == "paged" else 0
    # adapter-enabled engines compile the sel-bearing signatures (every
    # pack grows by the sel row/column; zero sel = base adapter, and the
    # warmup ships the pool args exactly like live dispatch)
    ae = 1 if eng._adapters_enabled else 0
    ad = (eng._adapter_args(),) if ae else ()
    oob = eng.total_pages if eng.kv_layout == "paged" else eng.num_slots
    if warm_prefill:
        for lb in lbs:
            for nb in bbs:
                packed = np.zeros((nb, lb + w + 3 + ae), np.int32)
                packed[:, lb] = 1  # lengths
                packed[:, lb + 1:lb + 1 + w] = oob  # all-OOB rows: writes dropped
                if eng.kv_layout == "paged" and eng.spec_tokens:
                    packed[:, lb + 1 + wp] = eng.num_slots  # OOB hist lanes
                eng._announce(TAG_PREFILL, lb, nb, packed)
                toks, eng.cache = eng._prefill_sample(
                    eng.params, eng._base_key, eng.cache,
                    jnp.asarray(packed), *ad
                )
                jax.block_until_ready(toks)
                eng._compiled.add(("prefill", lb, nb))
                count += 1
    if eng._chunked_ok:
        # chunked-prefill programs (batch 1, one per len bucket). OOB
        # rows — block-table entries (paged) or the slot id (slot) —
        # drop their writes, so a warmup never touches live cache state.
        # Both roles need these: prefill serves long prompts through
        # them, decode computes the post-hit prompt remainder.
        for lb in lbs:
            packed = np.zeros((1, lb + w + 4 + ae), np.int32)
            packed[0, lb] = 1
            packed[0, lb + 1:lb + 1 + w] = oob
            if eng.kv_layout == "paged" and eng.spec_tokens:
                packed[0, lb + 1 + wp] = eng.num_slots  # OOB hist lane
            eng._announce(TAG_CHUNK, lb, 1, packed)
            toks, eng.cache = eng._chunk_prefill(
                eng.params, eng._base_key, eng.cache, jnp.asarray(packed),
                *ad
            )
            jax.block_until_ready(toks)
            eng._compiled.add(("prefill_chunk", lb, 1))
            count += 1
    n, k = eng.num_slots, eng.decode_chunk
    wt = eng.pages_per_slot if eng.kv_layout == "paged" else 0
    packed = np.zeros((5 + ae + wt, n), np.int32)
    if eng.kv_layout == "paged":
        packed[5 + ae:] = eng.total_pages  # OOB table: writes dropped
    else:
        packed[1, :] = eng._cache_len  # OOB positions: writes dropped
    if warm_decode and not eng.spec_tokens:
        # spec mode never calls decode.dispatch_decode — don't compile
        # the (expensive) plain decode program it would throw away
        eng._announce(TAG_DECODE, 0, 0, packed)  # a=0: warmup, no carry
        out, _, eng.cache = eng._decode_chunk(
            eng.params, eng._base_key, eng.cache, k, jnp.asarray(packed),
            eng._zero_carry(), *ad
        )
        jax.block_until_ready(out)
        eng._compiled.add(("decode", n, k))
        count += 1
    if warm_decode and eng.spec_tokens:
        # BOTH layouts: all lanes host-arbitrated and OOB, so no
        # cache/history write survives. Announced with b=0 (warmup,
        # mirroring the TAG_DECODE convention): both sides feed a
        # zeros carry and DISCARD the output carry, so leader and
        # followers stay carry-identical without relying on a
        # warmup-produced value (ADVICE r5).
        if eng.kv_layout == "paged":
            sw = eng.pages_per_slot
            spec_packed = np.zeros((5 + ae + sw, n), np.int32)
            spec_packed[1, :] = sw * eng.page_size + 1  # all lanes OOB
            spec_packed[2, :] = 1
            spec_packed[5 + ae:] = eng.total_pages  # all-OOB tables
        else:
            spec_packed = np.zeros((5 + ae, n), np.int32)
            spec_packed[1, :] = eng._cache_len + 1
            spec_packed[2, :] = 1
        eng._announce(TAG_SPEC, spec_packed.shape[0], 0, spec_packed)
        carry = (eng._zero_carry(), eng._zero_carry())
        toks, _, eng.cache, _warm_carry = eng._spec_chunk_fn(
            eng.params, eng._base_key, eng.cache, k,
            jnp.asarray(spec_packed), carry, *ad)
        del _warm_carry  # never stored: _loop starts from None
        jax.block_until_ready(toks)
        eng._compiled.add(("decode_spec", n, k, eng.spec_tokens))
        count += 1
    if (eng.kv_layout == "paged" and eng._prefix is not None
            and (eng._prefix.host_budget or eng.role == "prefill")):
        # host-tier spill/swap-in programs: a first spill or swap-in
        # mid-serving would otherwise pay its XLA compile inside the
        # latency window the tier exists to shrink. The swap-in warmup
        # uses an all-OOB id vector, so every upload write is dropped.
        # A prefill-role worker compiles the gather too — its handoff
        # export dispatches per-page gathers under the state lock.
        from gofr_tpu.ops.paged import gather_page, swap_in_pages

        jax.block_until_ready(
            jax.tree.leaves(gather_page(eng.kv_cache, jnp.int32(0)))[0])
        count += 1
        if eng._prefix.host_budget:
            for wb in eng._swapin_buckets:
                ids = np.full((wb,), eng.total_pages, np.int32)
                payload = jax.tree.unflatten(eng._cache_treedef, [
                    np.zeros((leaf.shape[0], wb) + tuple(leaf.shape[2:]), leaf.dtype)
                    for leaf in jax.tree.leaves(eng.kv_cache)])
                kv, marker = swap_in_pages(
                    eng.kv_cache, jnp.asarray(ids), payload)
                eng.cache = ((kv, eng.cache[1])
                             if isinstance(eng.cache, tuple) else kv)
                jax.block_until_ready(marker)
                eng._compiled.add(("swapin", wb))
                count += 1
    return count


__all__ = [
    "ChunkPlan", "PrefillPlan", "dispatch_chunk", "dispatch_decode",
    "dispatch_prefill", "dispatch_spec", "dispatch_spec_paged",
    "dispatch_swapins", "gather_pages", "materialize_spills",
    "prefill_cols", "process_decode", "warmup_compile",
]
