"""Decode dispatch + unified pipeline processing for ``GenerateEngine``.

Split out of tpu/engine.py (the engine's device thread calls these once
per loop iteration). The interface to the engine is its documented state:
slot table + page bookkeeping under ``eng._state_lock``, the compiled
program handles from tpu/programs.py, the UNIFIED in-flight device queue
``eng._dq`` with the device-resident carries (``eng._prev_last`` for
plain decode, ``eng._spec_carry`` for speculative rounds), and the
emit/finish callbacks.

Every asynchronous device call rides ``eng._dq``: plain decode chunks and
speculative rounds on BOTH layouts (dispatched here), plus batched and
chunked prefills (dispatched by ``engine._admit``/``_advance_chunked``).
``process_decode`` dequeues the OLDEST entry, blocks on its readback —
overlapping every younger dispatch's compute — and folds the result into
slot state. Decode can pipeline because the data-dependent state (token,
hlen, token history) is device-resident — the host never needs chunk
t-1's output to assemble chunk t; prefill can because the prompt is
host-known. Paged spec used to be the one synchronous discipline left
(page allocation depended on acceptance counts the host only learned at
readback); ``dispatch_spec_paged`` breaks that dependency by OVER-
CLAIMING pages for the worst-case accepted span at dispatch time and
releasing the rejected surplus at fold time (``_fold_spec`` →
``engine._trim_lane_pages``), so paged spec rounds overlap prefill
chunks and other in-flight work exactly like the slot layout's.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from gofr_tpu.http.errors import RequestTimeout
from gofr_tpu.tpu.lockstep import TAG_DECODE, TAG_SPEC


def _fold_spec(eng, toks, accs, meta, k, g, dev_s: float = 0.0) -> None:
    """Replay one spec round's device acceptance into slot state. Caller
    holds the state lock. ``toks`` [k, n, g+1], ``accs`` [k, n]. ``g`` is
    the round length AT DISPATCH (from the entry's signature): the step
    controller may move ``eng.spec_tokens`` between dispatch and fold,
    and this round's proposal accounting belongs to the g that priced
    and shaped it."""
    now = time.monotonic()
    emitted = accepted = folded = trimmed = 0
    for i, s in meta:
        if eng.slots[i] is not s:
            continue  # freed/preempted/reassigned while in flight
        s.inflight = max(0, s.inflight - 1)
        if s.request.cancelled or s.request.expired(now):
            eng._free_slot(i)
            s.request.complete(error=RequestTimeout())
            continue
        folded += 1
        # per-request acceptance, mirroring the aggregate convention
        # (full-round proposed even when EOS cuts the fold short; accepted
        # credited per round BEFORE its tokens emit, so _maybe_finish —
        # which may complete the request mid-loop — reads counters that
        # include the finishing round). Surfaces as the spec.accept_rate
        # span attribute and flight-recorder field.
        kw = s.request.kw
        if dev_s:
            kw["_dev_decode_s"] = kw.get("_dev_decode_s", 0.0) + dev_s
        kw["_spec_proposed"] = kw.get("_spec_proposed", 0) + k * g
        for kk in range(k):
            a = int(accs[kk, i])
            accepted += a
            kw["_spec_accepted"] = kw.get("_spec_accepted", 0) + a
            for j in range(a + 1):
                tok = int(toks[kk, i, j])
                s.pos += 1
                s.last_token = tok
                s.generated.append(tok)
                emitted += 1
                eng._emit(s, tok)
                eng._maybe_finish(i)
                if eng.slots[i] is not s:  # EOS/budget: rest discarded
                    break
            if eng.slots[i] is not s:
                break
        if (eng.kv_layout == "paged" and eng.slots[i] is s
                and s.inflight == 0):
            # release the over-claim's rejected surplus — safe only with
            # no round in flight for this lane: an in-flight dispatch's
            # table snapshot may write to any page claimed at its
            # dispatch (dispatch_spec_paged over-claims for the
            # worst-case accepted span)
            trimmed += eng._trim_lane_pages(i, s, max(s.pos - 1, 0))
    eng.metrics.increment_counter("app_tpu_tokens_total", emitted)
    # proposed counts only lanes whose acceptance was folded — a lane
    # discarded mid-flight (freed/preempted/cancelled) contributes to
    # neither side, keeping accepted/proposed a true acceptance rate
    eng.metrics.increment_counter(
        "app_tpu_spec_proposed", k * g * folded)
    eng.metrics.increment_counter("app_tpu_spec_accepted", accepted)
    # over-claim policy waste, metered where it happens: pages claimed at
    # dispatch for drafts the fold rejected, and the rejected tokens
    # themselves — target flops spent without tokens emitted
    if trimmed:
        eng.metrics.increment_counter(
            "app_tpu_spec_pages_trimmed_total", trimmed)
    rejected = k * g * folded - accepted
    if rejected > 0:
        eng.metrics.increment_counter(
            "app_tpu_spec_tokens_rejected_total", rejected)


def dispatch_spec_paged(eng) -> bool:
    """Assemble and asynchronously dispatch one PAGED-layout speculative
    round onto the unified in-flight queue — the paged twin of
    ``dispatch_spec``, with the same ``[token, hlen, use_host, temps,
    step]`` carry arbitration plus the block-table rows (packed
    ``[5 + Wp, n]``; tpu/programs.py docstring). Token history lives in
    the cache pytree (kv, hist); prefill seeded it, the spec program
    maintains it — the old synchronous round shipped O(Hcap) history per
    lane per round.

    What used to force paged spec synchronous was page allocation: the
    host only learns acceptance counts at readback. This dispatcher
    breaks the dependency by OVER-CLAIMING — every dispatch grows the
    lane's table to cover its worst case, ``pos + chunk_span *
    (inflight + 1) - 1`` (each un-folded in-flight round may advance pos
    by a full chunk_span) — and the fold releases the rejected surplus
    once the lane has no round in flight (``_fold_spec`` →
    ``engine._trim_lane_pages``). Lanes whose worst-case position
    reaches max_total are masked until their in-flight rounds process,
    the same single-chunk_span cache-slack bound plain pipelined decode
    relies on."""
    with eng._state_lock:
        n = eng.num_slots
        k = eng.decode_chunk
        span = eng._chunk_span
        Wp = eng.pages_per_slot
        Hcap = Wp * eng.page_size
        lanes = []
        for i in eng._active():
            s = eng.slots[i]
            if s.pos + span * s.inflight >= s.max_total:
                continue  # masked until in-flight rounds process
            lanes.append((i, s))
        if not lanes:
            return False
        # claim pages covering the full worst case NOW (the device
        # cannot allocate mid-chunk, and the fold that would refine the
        # estimate hasn't happened yet — that's the point)
        for i, s in list(lanes):
            eng._alloc_lane_pages(i, s, s.pos + span * (s.inflight + 1) - 1)
        lanes = [(i, s) for i, s in lanes if eng.slots[i] is s]
        if not lanes:
            return True  # preemption work happened
        # ae: one extra packed row carrying each lane's adapter pool slot
        # (row 5; zero = base). OFF keeps the layout byte-identical to the
        # pre-adapter engine (tpu/programs.py documents both).
        ae = 1 if eng._adapters_enabled else 0
        packed = eng._staging("spec", (5 + ae + Wp, n))
        packed[1, :] = Hcap + 1  # inactive: every hist/cache write lands OOB
        packed[2, :] = 1         # inactive lanes are host-arbitrated
        temps = np.zeros((n,), np.float32)
        packed[5 + ae:] = eng._masked_table({i for i, _ in lanes}).T
        for i, s in lanes:
            if s.inflight == 0:
                # host knows this lane's exact (token, hlen) — it just
                # (re)joined from prefill or a fully-processed round
                packed[0, i] = s.last_token
                packed[1, i] = s.pos + 1
            else:
                packed[2, i] = 0  # device carry owns (token, hlen)
            if ae:
                packed[5, i] = s.adapter_slot
            temps[i] = float(s.request.kw.get("temperature", 0.0))
        packed[3] = temps.view(np.int32)
        eng._step_count += 1
        packed[4, 0] = eng._step_count
        for _, s in lanes:
            s.inflight += 1
        occupancy = len(lanes) / n
        # perf-plane history floor: pages the attention stream can touch
        # this round (the tables snapshotted above), in positions
        hist = sum(len(eng._slot_pages[i]) for i, _ in lanes) * eng.page_size
        t0 = time.monotonic()

    eng._announce(TAG_SPEC, packed.shape[0], 1, packed)  # b=1: live, carry applies
    carry = eng._spec_carry
    if carry is None:
        carry = (eng._zero_carry(), eng._zero_carry())
    toks_dev, accs_dev, eng.cache, eng._spec_carry = eng._spec_chunk_fn(
        eng.params, eng._base_key, eng.cache, k, jnp.asarray(packed), carry,
        *((eng._adapter_args(),) if ae else ()))
    pstep = (eng.perf.step_spec(len(lanes), k, eng.spec_tokens, hist, t0)
             if eng.perf is not None else None)
    eng._dq.append(("spec", (toks_dev, accs_dev), [(i, s) for i, s in lanes],
                    t0, occupancy, ("decode_spec", n, k, eng.spec_tokens),
                    pstep, eng._next_seq()))
    return True


def dispatch_spec(eng) -> bool:
    """Assemble and asynchronously dispatch one SLOT-layout speculative
    round. The host ships only [5, n]: per-lane (token, hlen, use_host,
    temperature) plus the rng step — never history, never logits.
    A lane with a round already in flight is driven by the device-
    resident spec carry (use_host=0); its worst-case advance is
    chunk_span per in-flight round, so lanes whose worst-case position
    reaches max_total are masked until their in-flight rounds process —
    which bounds any round's writes to max_total + chunk_span, the same
    single-chunk_span cache slack plain decode uses (engine ctor
    comment). Token history lives in the cache pytree
    (kv, hist); prefill seeded it, the spec program maintains it."""
    with eng._state_lock:
        n = eng.num_slots
        k = eng.decode_chunk
        span = eng._chunk_span
        lanes = []
        for i in eng._active():
            s = eng.slots[i]
            if s.pos + span * s.inflight >= s.max_total:
                continue  # masked until in-flight rounds process
            lanes.append((i, s))
        if not lanes:
            return False
        ae = 1 if eng._adapters_enabled else 0  # row 5: adapter pool slots
        packed = eng._staging("spec", (5 + ae, n))
        packed[1, :] = eng._cache_len + 1  # inactive: every write lands OOB
        packed[2, :] = 1                   # inactive lanes are host-arbitrated
        temps = np.zeros((n,), np.float32)
        for i, s in lanes:
            if s.inflight == 0:
                # host knows this lane's exact (token, hlen) — it just
                # (re)joined from prefill or a fully-processed round
                packed[0, i] = s.last_token
                packed[1, i] = s.pos + 1
            else:
                packed[2, i] = 0  # device carry owns (token, hlen)
            if ae:
                packed[5, i] = s.adapter_slot
            temps[i] = float(s.request.kw.get("temperature", 0.0))
        packed[3] = temps.view(np.int32)
        eng._step_count += 1
        packed[4, 0] = eng._step_count
        for _, s in lanes:
            s.inflight += 1
        occupancy = len(lanes) / n
        # perf-plane history floor: worst-case positions this round's
        # attention streams per lane (device carry may be ahead of pos)
        hist = sum(min(s.pos + span * s.inflight + 1, s.max_total)
                   for _, s in lanes)
        t0 = time.monotonic()

    eng._announce(TAG_SPEC, packed.shape[0], 1, packed)  # b=1: live, carry applies
    carry = eng._spec_carry
    if carry is None:
        carry = (eng._zero_carry(), eng._zero_carry())
    toks_dev, accs_dev, eng.cache, eng._spec_carry = eng._spec_chunk_fn(
        eng.params, eng._base_key, eng.cache, k, jnp.asarray(packed), carry,
        *((eng._adapter_args(),) if ae else ()))
    pstep = (eng.perf.step_spec(len(lanes), k, eng.spec_tokens, hist, t0)
             if eng.perf is not None else None)
    eng._dq.append(("spec", (toks_dev, accs_dev), [(i, s) for i, s in lanes],
                    t0, occupancy, ("decode_spec", n, k, eng.spec_tokens),
                    pstep, eng._next_seq()))
    return True


def dispatch_decode(eng) -> bool:
    """Assemble and asynchronously dispatch one decode chunk. Positions
    are SPECULATIVE: a lane with a chunk already in flight decodes from
    ``pos + k*inflight`` and takes its input token from the on-device
    ``prev_last`` carry rather than the host (which hasn't read that
    chunk back yet). Lanes guaranteed dead once their in-flight chunk is
    processed (speculative pos >= max_total) are masked out, so writes
    never exceed the existing decode_chunk cache slack. Returns True when
    a chunk was dispatched."""
    with eng._state_lock:
        n = eng.num_slots
        k = eng.decode_chunk

        # (slot index, slot, speculative position) for lanes that decode
        lanes = []
        for i in eng._active():
            s = eng.slots[i]
            p = s.pos + k * s.inflight
            if p >= s.max_total:
                continue  # will be freed when its in-flight chunk processes
            lanes.append((i, s, p))
        if not lanes:
            return False

        if eng.kv_layout == "paged":
            # every decoding lane must own pages covering this chunk's
            # writes (p .. p+k-1) BEFORE the table snapshot
            for i, s, p in list(lanes):
                eng._alloc_lane_pages(i, s, p + k - 1)
            lanes = [(i, s, p) for i, s, p in lanes if eng.slots[i] is s]
            if not lanes:
                return False

        # always the FULL chunk — one compiled decode program for the whole
        # serving lifetime. A slot that hits its budget/EOS mid-chunk simply
        # has its surplus tokens discarded (the cache carries decode_chunk
        # slack past max_len, so overshoot writes stay in bounds; paged
        # slots' tables carry the same slack via pages_per_slot). All host
        # inputs ride ONE packed array (layout at the jit definitions).
        wt = eng.pages_per_slot if eng.kv_layout == "paged" else 0
        ae = 1 if eng._adapters_enabled else 0  # row 5: adapter pool slots
        packed = eng._staging("decode", (5 + ae + wt, n))
        temps = np.zeros((n,), np.float32)
        if eng.kv_layout != "paged":
            # non-decoding rows (empty, chunk-prefilling, or dead-lane-
            # masked) write at an out-of-bounds position so the masked-
            # select append drops them — a position-0 write would corrupt
            # a prefilling slot's first token (paged masks via OOB table
            # rows instead)
            packed[1, :] = eng._cache_len
        for i, s, p in lanes:
            if s.inflight == 0:
                # host knows this lane's exact last token (from prefill or
                # its last processed chunk); otherwise the device carry
                # from the in-flight chunk supplies it (use_host stays 0)
                packed[0, i] = s.last_token
                packed[4, i] = 1
            packed[1, i] = p
            if ae:
                packed[5, i] = s.adapter_slot
            temps[i] = float(s.request.kw.get("temperature", 0.0))
        packed[2] = temps.view(np.int32)
        eng._step_count += 1
        packed[3, 0] = eng._step_count
        if eng.kv_layout == "paged":
            packed[5 + ae:] = eng._masked_table({i for i, _, _ in lanes}).T

        for _, s, _ in lanes:
            s.inflight += 1
        occupancy = len(lanes) / n
        # perf-plane history floor: positions (slot) / pages-touched
        # (paged) this chunk's attention streams, from dispatch shapes
        if eng.kv_layout == "paged":
            hist = sum(len(eng._slot_pages[i])
                       for i, _, _ in lanes) * eng.page_size
        else:
            hist = sum(p + 1 for _, _, p in lanes)
        t0 = time.monotonic()

    eng._announce(TAG_DECODE, 1, 0, packed)  # a=1: live, carry applies
    prev = eng._prev_last
    if prev is None:
        prev = eng._zero_carry()
    chunk_dev, last_dev, eng.cache = eng._decode_chunk(
        eng.params, eng._base_key, eng.cache, k, jnp.asarray(packed), prev,
        *((eng._adapter_args(),) if ae else ())
    )
    eng._prev_last = last_dev
    pstep = (eng.perf.step_decode(len(lanes), k, hist, t0)
             if eng.perf is not None else None)
    eng._dq.append(("plain", chunk_dev, [(i, s) for i, s, _ in lanes],
                    t0, occupancy, ("decode", n, k), pstep, eng._next_seq()))
    return True


def process_decode(eng) -> bool:
    """Block on the OLDEST dispatched entry's readback (overlapping any
    younger dispatch's compute) and fold it into slot state. Lanes whose
    slot object changed since dispatch (freed, preempted, reassigned)
    have their results discarded — the identity check is what makes
    dispatch-time claiming safe. Handles every entry kind on ``eng._dq``:
    plain decode, spec rounds, batched prefill, prefill chunks, and
    prefix-cache host→device page swap-ins."""
    if not eng._dq:
        return False
    kind, dev, meta, t0, occupancy, sig, pstep, seq = eng._dq.popleft()
    with eng._phases.phase("readback", seq=seq, kind=kind):
        # int32 tokens, never logits (spec: [k, n, g+1] tokens and [k, n]
        # acceptance counts)
        host = (tuple(np.asarray(d) for d in dev) if kind == "spec"
                else np.asarray(dev))
    if pstep is not None:
        # the result just landed on the host: everything from here on is
        # fold time, not device time (perf plane separates the two)
        pstep.t_ready = time.monotonic()
    if eng._step_counters and kind in ("plain", "prefill", "chunk"):
        # a counting family's tail of the token array (tpu/programs.py):
        # [S, K] a decode chunk, [S] a prefill
        tail = host[-len(eng._step_counters):]
        if kind == "plain":
            eng._step_counts["decode"] += tail.sum(axis=1)
        else:
            eng._step_counts["prefill"] += tail
    if eng._poisoned:
        # stop() declared this thread wedged and already failed/cleared
        # everything; the slot/page state now belongs to the caller.
        return False
    with eng._phases.phase("fold", seq=seq, kind=kind):
        _fold(eng, kind, host, meta, t0, occupancy, sig, pstep)
    return True


def _fold(eng, kind, host, meta, t0, occupancy, sig, pstep) -> None:
    """Fold one read-back ``_dq`` entry into slot state (the body of the
    loop's ``fold`` phase)."""
    if kind == "swapin":
        # host is the upload's completion marker (already read back, i.e.
        # the host→device page copy has landed); fold is bookkeeping
        eng._fold_swapin(meta, t0, occupancy, sig, pstep)
        return
    if kind == "prefill":
        eng._fold_prefill(host, meta, t0, occupancy, sig, pstep)
        return
    if kind == "chunk":
        eng._fold_chunk(host, meta, t0, occupancy, sig, pstep)
        return
    n, k = sig[1], sig[2]
    with eng._state_lock:
        # per-adapter attribution covers DISPATCHED lanes — a lane freed
        # while in flight still had device time spent on its behalf
        ads = ([s.adapter_id or "base" for _, s in meta]
               if eng._adapters_enabled else None)
        if kind == "spec":
            # sig[3] is the round length g AT DISPATCH — the live
            # eng.spec_tokens may already be a different (controller-
            # moved) value by the time this round folds
            dev_s = eng._record_step(
                "decode_spec", time.monotonic() - t0, occupancy,
                sig, pstep, adapter_ids=ads)
            _fold_spec(eng, host[0], host[1], meta, k, sig[3], dev_s)
            return
        dev_s = eng._record_step("decode", time.monotonic() - t0, occupancy,
                                 ("decode", n, k), pstep, adapter_ids=ads)

        now = time.monotonic()
        accepted = 0
        for i, s in meta:
            if eng.slots[i] is not s:
                continue  # freed/preempted/reassigned while in flight
            s.inflight -= 1
            if dev_s:
                kw = s.request.kw
                kw["_dev_decode_s"] = kw.get("_dev_decode_s", 0.0) + dev_s
            if s.request.cancelled or s.request.expired(now):
                # slot invalidation: free the lane; in-flight work is discarded
                eng._free_slot(i)
                s.request.complete(error=RequestTimeout())
                continue
            for j in range(k):
                tok = int(host[i, j])
                s.pos += 1
                s.last_token = tok
                s.generated.append(tok)
                accepted += 1
                eng._emit(s, tok)
                eng._maybe_finish(i)
                if eng.slots[i] is not s:  # EOS/length mid-chunk: rest discarded
                    break
        eng.metrics.increment_counter("app_tpu_tokens_total", accepted)
