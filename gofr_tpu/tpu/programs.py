"""Jitted packed-program builders for ``GenerateEngine``.

Every serving step ships its host inputs as ONE packed int32 array (floats
bitcast, RNG step folded in on device from the resident base key): each
separate H2D transfer and out-of-jit RNG op is its own dispatch, and
packing turns 4-6 of them into one (what that saves per step on a local
chip is not measured — PERF.md). This module holds the compiled-program
side of that contract; the engine (tpu/engine.py) packs the host side.

Packed layouts (W = 1 slot-id column for the slot layout; for paged,
pages_per_slot block-table columns, plus ONE trailing slot-id column when
speculative decoding is on — the prefill programs need the lane index to
seed the device-resident history rows):

- Prefill ``[nb, lb + W + 3]``:
  ``[:, :lb]`` tokens | ``[:, lb]`` lengths | ``[:, lb+1:lb+1+W]`` rows
  | ``[:, lb+1+W]`` temps (f32 bitcast) | ``[0, lb+2+W]`` rng step.
  Chunked prefill adds an offsets column before temps.
- Decode ``[5 + W_t, n]`` (W_t = pages_per_slot table rows for paged, 0
  for slot): ``[0]`` tokens | ``[1]`` positions | ``[2]`` temps | ``[3,0]``
  rng step | ``[4]`` use_host flags | ``[5:]`` table.T. Row 4 arbitrates
  the input token per lane: 1 = take the host's packed token (lane just
  (re)joined decode); 0 = take the on-device ``prev_last`` carry from the
  previous dispatched chunk (lane has a chunk in flight the host hasn't
  read back yet).
- Spec (slot) ``[5, n]``: ``[0]`` input token | ``[1]`` history length
  (the input token is hist[hlen-1], its KV goes to position hlen-1)
  | ``[2]`` use_host flags — same arbitration as decode row 4, against a
  device-resident ``(token, hlen)`` carry, which is what lets spec rounds
  ride the pipelined dispatch queue | ``[3]`` temps (f32 bitcast)
  | ``[4, 0]`` rng step. The token HISTORY itself never leaves the
  device: with spec on, the slot cache is the pytree ``(kv, hist)`` and
  the prefill programs write each admitted prompt (plus its sampled
  first token) into ``hist`` rows on device, so the host never re-ships
  O(pos) history per round. Inactive lanes ship use_host=1 with
  hlen = H + 1: every cache/history write lands out of bounds and drops.
- Spec (paged) ``[5 + Wp, n]``: ``[0]`` input token | ``[1]`` history
  length | ``[2]`` use_host flags | ``[3]`` temps (f32 bitcast)
  | ``[4, 0]`` rng step | ``[5:]`` table.T — the SAME carry arbitration
  and (kv, hist) cache pytree as the slot layout, so paged spec rounds
  ride the pipelined dispatch queue too (pages are over-claimed at
  dispatch for the worst-case accepted span; tpu/decode.py). Inactive
  lanes ship use_host=1, hlen = Hcap + 1 AND an all-OOB table row, so
  every cache/history write drops. History never rides the wire in
  either layout.

With multi-LoRA adapters on (``build_programs(adapters=True)``), every
layout grows the per-lane adapter-pool slot id ``sel``: prefill packs one
extra column between the offsets (if chunked) and temps columns, and the
decode/spec packs insert a ``sel`` row at ``[5]`` (the block table moves
to ``[6:]``). Each program then takes a trailing ``ad = (a, b, scale)``
pool-array argument (dynamic, like ``params`` — uploads and live
hot-swap never recompile). With adapters off, layouts and traces are
byte-identical to the above.

A family that counts what its steps do (``family.step_counters(cfg)``: the
mixture-of-experts family's routing counts) returns one int32 vector more
from ``prefill_paged`` / ``decode_step_paged``; the paged programs then hand
it back INSIDE the token array the host already reads — ``[nb + S]`` from a
prefill, ``[n + S, K]`` from a decode chunk (S counters a step, after the
rows the fold reads) — so counting adds no readback and no synchronisation.
Every other family's programs are what they were.

Backend resolution is a TRACE-time property of these programs: the decode
attention ops inside them resolve ``backend="auto"`` when a program first
traces (warmup), by the one rule in ``ops/attention.resolve_backend``
(platform and op). A compiled program keeps what its trace resolved for
its whole life, same as the KV write lowerings.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from gofr_tpu.ops.sampling import sample_token, truncate_logits
from gofr_tpu.tracing import scoped


@scoped("sample")
def speculative_sample(key, p_logits, drafts, temps, q_logits=None,
                       top_k=0, top_p=1.0):
    """Distribution-exact speculative sampling for one verify step
    (Leviathan/Chen rejection scheme): accept draft j with probability
    min(1, p_j(d_j)/q_j(d_j)) while the prefix holds, then sample the
    correction from norm((p_acc − q_acc)+) — or, on full acceptance, the
    bonus token from p_g. Each emitted token is distributed EXACTLY as a
    plain sampled decode at the same position; rows with temperature <= 0
    reduce bit-exactly to greedy (p collapses to the argmax one-hot, so
    acceptance == argmax-match and the correction == the argmax).

    ``p_logits`` [n, g+1, V] target logits; ``drafts`` [n, g] proposals;
    ``temps`` [n]; ``q_logits`` [n, g, V] draft-model logits, or None for
    DETERMINISTIC proposals (prompt-lookup: q is the one-hot at the
    proposal, so the accept test is u < p(d) and the residual is p with
    the rejected token zeroed).

    ``top_k``/``top_p`` (static) truncate p AND q with the IDENTICAL mask
    `ops.sampling.truncate_logits` applies in plain decode — making each
    emitted token exact w.r.t. the truncated target distribution (the
    same distribution plain truncated sampling serves). The draft's
    proposals must be sampled with the same truncation (the spec program
    routes them through sample_token with these settings).

    Returns ``(out [n, g+1] int32, acc [n] int32)``: ``out[:, :acc]`` are
    the accepted drafts, ``out[:, acc]`` the correction/bonus; entries
    past ``acc`` are garbage the caller discards. Exposed at module level
    so the distribution guarantee is testable directly (test_spec_decode).
    """
    n, gp1, vocab = p_logits.shape
    g = gp1 - 1
    greedy_rows = (temps <= 0)[:, None, None]
    temp = jnp.maximum(temps, 1e-6)[:, None, None]
    p = jax.nn.softmax(
        truncate_logits(p_logits.astype(jnp.float32) / temp, top_k, top_p),
        axis=-1)
    p = jnp.where(
        greedy_rows,
        jax.nn.one_hot(jnp.argmax(p_logits, -1), vocab, dtype=jnp.float32),
        p,
    )
    if q_logits is None:
        q_d = jnp.ones((n, g), jnp.float32)
    else:
        q = jax.nn.softmax(
            truncate_logits(q_logits.astype(jnp.float32) / temp, top_k, top_p),
            axis=-1)
        q = jnp.where(
            greedy_rows,
            jax.nn.one_hot(jnp.argmax(q_logits, -1), vocab, dtype=jnp.float32),
            q,
        )
        q_d = jnp.take_along_axis(q, drafts[..., None], axis=-1)[..., 0]
    p_d = jnp.take_along_axis(p[:, :g], drafts[..., None], axis=-1)[..., 0]
    ku, kr = jax.random.split(key)
    u = jax.random.uniform(ku, (n, g))
    ok = (u * q_d < p_d).astype(jnp.int32)
    ok = jnp.cumprod(ok, axis=1)
    acc = ok.sum(axis=1)  # leading accepted drafts per lane, 0..g
    p_sel = jnp.take_along_axis(p, acc[:, None, None], axis=1)[:, 0]  # [n, V]
    if q_logits is None:
        d_at = jnp.take_along_axis(
            drafts, jnp.minimum(acc, g - 1)[:, None], axis=1)[:, 0]
        q_sel = jnp.where((acc < g)[:, None],
                          jax.nn.one_hot(d_at, vocab, dtype=jnp.float32), 0.0)
    else:
        q_pad = jnp.concatenate([q, jnp.zeros((n, 1, vocab), q.dtype)], axis=1)
        q_sel = jnp.take_along_axis(q_pad, acc[:, None, None], axis=1)[:, 0]
    resid = jnp.maximum(p_sel - q_sel, 0.0)
    rs = resid.sum(-1, keepdims=True)
    # p == q at the rejection point is a zero residual only when the
    # rejection had probability zero — sampling p there is equivalent
    resid = jnp.where(rs > 0, resid, p_sel)
    corr = jax.random.categorical(
        kr, jnp.log(jnp.maximum(resid, 1e-38)), axis=-1).astype(jnp.int32)
    out = jnp.concatenate([drafts, jnp.zeros((n, 1), jnp.int32)], axis=1)
    out = out.at[jnp.arange(n), acc].set(corr)
    return out, acc


def unpack_prefill(packed, w, chunked=False, adapters=False):
    extra = (1 if chunked else 0) + (1 if adapters else 0)
    lb = packed.shape[1] - (w + 3 + extra)
    tokens = packed[:, :lb]
    lengths = packed[:, lb]
    rows = packed[:, lb + 1:lb + 1 + w]
    offsets = packed[:, lb + 1 + w] if chunked else None
    # adapter-pool slot id per row, between offsets and temps (0 = base;
    # padding rows pack 0, whose delta is exactly zero — ops/lora.py)
    sel = (packed[:, lb + 1 + w + (1 if chunked else 0)]
           if adapters else None)
    temps = jax.lax.bitcast_convert_type(
        packed[:, lb + 1 + w + extra], jnp.float32)
    step = packed[0, lb + 2 + w + extra]
    return tokens, lengths, rows, offsets, temps, step, sel


@dataclass
class Programs:
    """Compiled-program handles the engine (and lockstep followers) call.

    ``chunk_prefill`` is None when the layout/family has no chunked-prefill
    support; ``spec_chunk`` is None unless speculative decoding is on.
    """

    prefill_sample: Any
    chunk_prefill: Any | None
    decode_chunk: Any
    spec_chunk: Any | None


def build_programs(
    family: Any,
    cfg: Any,
    *,
    kv_layout: str,
    spec_tokens: int,
    top_k: int,
    top_p: float,
    pages_per_slot: int = 0,
    page_size: int = 0,
    cache_len: int = 0,
    prefill_attn_fn: Any = None,
    draft: Any = None,
    adapters: bool = False,
) -> Programs:
    """``draft`` (slot layout + spec only) is a ``(family, cfg)`` pair for a
    DRAFT MODEL: instead of prompt-lookup, each spec round runs
    ``spec_tokens`` autoregressive draft-model decode steps on device, then
    the one target verify forward. With a draft, ``params`` to every program
    is the pytree ``{"t": target_params, "d": draft_params}``, and the
    engine cache is ``(kv, draft_kv)`` — the draft's slot KV cache replaces
    the token-history buffer (the draft needs no history, killing the
    history writes too). Verification is unchanged, so outputs stay
    bit-identical to plain greedy decode regardless of draft quality — the
    draft only moves the acceptance rate."""
    if adapters and not getattr(family, "SUPPORTS_ADAPTERS", False):
        raise ValueError(
            f"model family {family.__name__!r} has no adapter support "
            "(SUPPORTS_ADAPTERS); disable ADAPTER_* or use a family whose "
            "serving entry points accept the adapters kwarg")

    # With ``adapters`` on, every program takes a trailing ``ad = (a, b,
    # scale)`` — the device adapter-pool arrays (adapters.AdapterPool),
    # DYNAMIC jit args like ``params`` so uploads/evictions/hot-swap never
    # recompile — and the packed layouts grow the per-lane pool slot id
    # ``sel``: one prefill column before temps, and row [5] of the
    # decode/spec packs (the block table moves to [6:]). With it off
    # (default), layouts, signatures, and traces are EXACTLY the
    # pre-adapter ones — the adapter_id=None bit-exactness contract.
    def _akw(sel, ad):
        return {"adapters": (sel, ad[0], ad[1], ad[2])} if adapters else {}

    def _with_counts(toks, counts):
        """A counting family's step counters ride behind the tokens (module
        docstring); every other family returns none and keeps its array."""
        return jnp.concatenate([toks, *counts]) if counts else toks

    ts = (top_k, top_p)
    Wp = pages_per_slot
    # paged + spec adds one trailing slot-id column after the block-table
    # columns: hist rows are indexed by LANE, and the paged layout's packed
    # prefill otherwise carries only page ids (module docstring)
    W = (Wp + (1 if spec_tokens else 0)) if kv_layout == "paged" else 1
    # whole-prompt prefill attention override (e.g. ring/Ulysses
    # sequence-parallel attention on an sp mesh — build_engine wires it);
    # chunked prefill keeps the gathered-view attention either way
    pf = {"attn_fn": prefill_attn_fn} if prefill_attn_fn is not None else {}
    chunk_prefill = None
    spec_chunk = None

    if kv_layout == "paged":
        # With spec on, the paged cache is the same 2-tuple pytree the
        # slot layout uses: (kv, hist) — prefill seeds hist rows on
        # device, the spec program maintains them, and no program input
        # ever carries token history (the old paged spec shipped
        # O(Hcap) history rows per round).
        tuple_cache = bool(spec_tokens)

        def _split(cache):
            return cache if tuple_cache else (cache, None)

        def _join(kv, hist):
            return (kv, hist) if tuple_cache else kv

        def _seed_hist(hist, srows, tokens, lengths, toks, offsets=None):
            """Write an admitted prompt chunk (and its sampled token) into
            the device history. OOB lane ids (padding rows) drop. On
            non-final chunks the sampled-token write at offset+length is
            garbage the NEXT chunk overwrites — final state is always
            (prompt .. first sampled token)."""
            lb = tokens.shape[1]
            base = offsets if offsets is not None else jnp.zeros_like(lengths)
            cols = base[:, None] + jnp.arange(lb)[None, :]
            hist = hist.at[srows[:, None], cols].set(tokens, mode="drop")
            return hist.at[srows, base + lengths].set(toks, mode="drop")

        @partial(jax.jit, donate_argnums=(2,))
        def _prefill_sample(params, base_key, cache, packed, ad=None):
            kv, hist = _split(cache)
            tokens, lengths, rows, _, temps, step, sel = unpack_prefill(
                packed, W, adapters=adapters)
            key = jax.random.fold_in(base_key, step)
            logits, kv, *counts = family.prefill_paged(
                cfg, params, tokens, lengths, kv, rows[:, :Wp], **pf,
                **_akw(sel, ad))
            toks = sample_token(logits, key, temperature=temps, top_k=ts[0], top_p=ts[1])
            if tuple_cache:
                hist = _seed_hist(hist, rows[:, Wp], tokens, lengths, toks)
            return _with_counts(toks, counts), _join(kv, hist)

        @partial(jax.jit, donate_argnums=(2,))
        def _chunk_prefill(params, base_key, cache, packed, ad=None):
            kv, hist = _split(cache)
            tokens, lengths, rows, offsets, temps, step, sel = unpack_prefill(
                packed, W, chunked=True, adapters=adapters)
            key = jax.random.fold_in(base_key, step)
            logits, kv, *counts = family.prefill_paged(
                cfg, params, tokens, lengths, kv, rows[:, :Wp], offsets,
                **_akw(sel, ad)
            )
            toks = sample_token(logits, key, temperature=temps, top_k=ts[0], top_p=ts[1])
            if tuple_cache:
                hist = _seed_hist(hist, rows[:, Wp], tokens, lengths, toks,
                                  offsets)
            return _with_counts(toks, counts), _join(kv, hist)

        chunk_prefill = _chunk_prefill

        @partial(jax.jit, static_argnums=(3,), donate_argnums=(2,))
        def _decode_chunk(params, base_key, cache, steps, packed, prev_last,
                          ad=None):
            kv, hist = _split(cache)
            tokens = jnp.where(packed[4] != 0, packed[0], prev_last)
            positions = packed[1]
            temps = jax.lax.bitcast_convert_type(packed[2], jnp.float32)
            key = jax.random.fold_in(base_key, packed[3, 0])
            sel = packed[5] if adapters else None
            table = packed[6:].T if adapters else packed[5:].T

            def body(carry, _):
                toks, pos, kv, key = carry
                logits, kv, *counts = family.decode_step_paged(
                    cfg, params, toks, pos, kv, table, **_akw(sel, ad))
                key, sub = jax.random.split(key)
                nxt = sample_token(logits, sub, temperature=temps, top_k=ts[0], top_p=ts[1])
                return (nxt, pos + 1, kv, key), (nxt, *counts)

            (toks, pos, kv, key), (out, *counts) = jax.lax.scan(
                body, (tokens, positions, kv, key), None, length=steps
            )
            out = jnp.concatenate([out, *counts], axis=1) if counts else out
            return out.T, toks, _join(kv, hist)  # [slots (+ counters), K], [slots] carry

        if spec_tokens:
            g = spec_tokens
            Hcap = Wp * page_size  # logical per-slot capacity

            @partial(jax.jit, static_argnums=(3,), donate_argnums=(2, 5))
            def _spec_chunk(params, base_key, cache, steps, packed, carry,
                            ad=None):
                kv, hist0 = cache
                n_l = packed.shape[1]
                use_host = packed[2] != 0
                tok0 = jnp.where(use_host, packed[0], carry[0])
                hlen0 = jnp.where(use_host, packed[1], carry[1])
                temps = jax.lax.bitcast_convert_type(packed[3], jnp.float32)
                key0 = jax.random.fold_in(base_key, packed[4, 0])
                sel = packed[5] if adapters else None
                table = (packed[6:] if adapters else packed[5:]).T  # [n, Wp]
                idx = jnp.arange(Hcap)

                def outer(loop, _):
                    tok, hlen, hist, kv, key = loop
                    key, ks = jax.random.split(key)
                    pos = hlen - 1
                    # prompt-lookup draft: continuation after the most
                    # recent EARLIER occurrence of the current token
                    # (a DETERMINISTIC proposal — one-hot q)
                    match = (hist == tok[:, None]) & (idx[None, :] < pos[:, None])
                    j = jnp.where(match, idx[None, :], -1).max(axis=1)
                    take = jnp.clip(j[:, None] + 1 + jnp.arange(g)[None, :], 0, Hcap - 1)
                    drafts = jnp.take_along_axis(hist, take, axis=1)
                    seq = jnp.concatenate([tok[:, None], drafts], axis=1)
                    logits, kv = family.verify_step_paged(
                        cfg, params, seq, pos, kv, table, **_akw(sel, ad))
                    out, acc = speculative_sample(ks, logits, drafts, temps,
                                                  None, ts[0], ts[1])
                    nxt = jnp.take_along_axis(out, acc[:, None], axis=1)[:, 0]
                    emit = jnp.arange(g + 1)[None, :] <= acc[:, None]
                    wpos = jnp.where(emit, hlen[:, None] + jnp.arange(g + 1)[None, :], Hcap)
                    hist = hist.at[jnp.arange(n_l)[:, None], wpos].set(out, mode="drop")
                    return (nxt, hlen + acc + 1, hist, kv, key), (out, acc)

                (tok_f, hlen_f, hist, kv, _), (toks, accs) = jax.lax.scan(
                    outer, (tok0, hlen0, hist0, kv, key0), None, length=steps
                )
                # [K, n, g+1], [K, n], cache, next-round (token, hlen) carry
                return toks, accs, (kv, hist), (tok_f, hlen_f)

            spec_chunk = _spec_chunk
    else:
        # With spec on, the engine's cache is a 2-tuple pytree: (kv, hist)
        # for prompt-lookup — the prefill programs seed hist rows on device
        # and the spec program maintains them, so no program input ever
        # carries token history — or (kv, draft_kv) with a draft model.
        tuple_cache = bool(spec_tokens)
        dfamily, dcfg = draft if draft is not None else (None, None)

        def _tparams(params):
            return params["t"] if draft is not None else params

        def _split(cache):
            return cache if tuple_cache else (cache, None)

        def _join(kv, aux):
            return (kv, aux) if tuple_cache else kv

        def _seed_hist(hist, rows, tokens, lengths, toks, offsets=None):
            """Write an admitted prompt chunk (and its sampled token) into
            the device history. OOB rows (padding: slot id == num_slots)
            drop. On non-final chunks the sampled-token write at
            offset+length is garbage the NEXT chunk overwrites — final
            state is always (prompt .. first sampled token)."""
            lb = tokens.shape[1]
            base = offsets if offsets is not None else jnp.zeros_like(lengths)
            cols = base[:, None] + jnp.arange(lb)[None, :]
            hist = hist.at[rows[:, None], cols].set(tokens, mode="drop")
            return hist.at[rows, base + lengths].set(toks, mode="drop")

        def _seed_aux(params, aux, rows, tokens, lengths, toks, offsets=None):
            """Bring the spec sidecar state up to date with an admitted
            prompt: prefill the draft model's KV cache over the same
            tokens, or seed the prompt-lookup history rows."""
            if draft is None:
                return _seed_hist(aux, rows, tokens, lengths, toks, offsets)
            if offsets is None:
                _, aux = dfamily.prefill(
                    dcfg, params["d"], tokens, lengths, aux, rows)
            else:
                _, aux = dfamily.prefill(
                    dcfg, params["d"], tokens, lengths, aux, rows, offsets)
            return aux

        @partial(jax.jit, donate_argnums=(2,))
        def _prefill_sample(params, base_key, cache, packed, ad=None):
            kv, aux = _split(cache)
            tokens, lengths, rows, _, temps, step, sel = unpack_prefill(
                packed, W, adapters=adapters)
            key = jax.random.fold_in(base_key, step)
            logits, kv = family.prefill(
                cfg, _tparams(params), tokens, lengths, kv, rows[:, 0], **pf,
                **_akw(sel, ad))
            toks = sample_token(logits, key, temperature=temps, top_k=ts[0], top_p=ts[1])
            if tuple_cache:
                aux = _seed_aux(params, aux, rows[:, 0], tokens, lengths, toks)
            return toks, _join(kv, aux)

        if getattr(family, "SLOT_CHUNKED_PREFILL", False):
            @partial(jax.jit, donate_argnums=(2,))
            def _chunk_prefill(params, base_key, cache, packed, ad=None):
                kv, aux = _split(cache)
                tokens, lengths, rows, offsets, temps, step, sel = unpack_prefill(
                    packed, W, chunked=True, adapters=adapters)
                key = jax.random.fold_in(base_key, step)
                logits, kv = family.prefill(
                    cfg, _tparams(params), tokens, lengths, kv, rows[:, 0],
                    offsets, **_akw(sel, ad)
                )
                toks = sample_token(logits, key, temperature=temps, top_k=ts[0], top_p=ts[1])
                if tuple_cache:
                    aux = _seed_aux(params, aux, rows[:, 0], tokens, lengths,
                                    toks, offsets)
                return toks, _join(kv, aux)

            chunk_prefill = _chunk_prefill

        @partial(jax.jit, static_argnums=(3,), donate_argnums=(2,))
        def _decode_chunk(params, base_key, cache, steps, packed, prev_last,
                          ad=None):
            kv, aux = _split(cache)
            tokens = jnp.where(packed[4] != 0, packed[0], prev_last)
            positions = packed[1]
            temps = jax.lax.bitcast_convert_type(packed[2], jnp.float32)
            key = jax.random.fold_in(base_key, packed[3, 0])
            sel = packed[5] if adapters else None

            def body(carry, _):
                toks, pos, kv, key = carry
                logits, kv = family.decode_step(
                    cfg, _tparams(params), toks, pos, kv, **_akw(sel, ad))
                key, sub = jax.random.split(key)
                nxt = sample_token(logits, sub, temperature=temps, top_k=ts[0], top_p=ts[1])
                return (nxt, pos + 1, kv, key), nxt

            (toks, pos, kv, key), out = jax.lax.scan(
                body, (tokens, positions, kv, key), None, length=steps
            )
            return out.T, toks, _join(kv, aux)  # [slots, K], [slots] carry

        if spec_tokens:
            g = spec_tokens
            H = cache_len

            @partial(jax.jit, static_argnums=(3,), donate_argnums=(2, 5))
            def _spec_chunk(params, base_key, cache, steps, packed, carry,
                            ad=None):
                kv, aux0 = cache
                n_l = packed.shape[1]
                use_host = packed[2] != 0
                tok0 = jnp.where(use_host, packed[0], carry[0])
                hlen0 = jnp.where(use_host, packed[1], carry[1])
                temps = jax.lax.bitcast_convert_type(packed[3], jnp.float32)
                key0 = jax.random.fold_in(base_key, packed[4, 0])
                sel = packed[5] if adapters else None
                idx = jnp.arange(H)

                def outer(loop, _):
                    tok, hlen, aux, kv, key = loop
                    key, kd, ks = jax.random.split(key, 3)
                    pos = hlen - 1
                    q_logits = None
                    if draft is None:
                        # prompt-lookup draft: continuation after the most
                        # recent EARLIER occurrence of the current token
                        # (a DETERMINISTIC proposal — one-hot q)
                        match = (aux == tok[:, None]) & (idx[None, :] < pos[:, None])
                        j = jnp.where(match, idx[None, :], -1).max(axis=1)  # -1 = miss
                        take = jnp.clip(j[:, None] + 1 + jnp.arange(g)[None, :], 0, H - 1)
                        drafts = jnp.take_along_axis(aux, take, axis=1)  # [n, g]
                    else:
                        # draft-model proposal: g+1 autoregressive steps of
                        # the (tiny) draft, its KV cache riding in aux,
                        # SAMPLED at each lane's temperature (greedy rows
                        # decode greedily — sample_token semantics). g+1,
                        # not g: the extra step's OUTPUT is discarded but
                        # its input write puts the g-th draft's KV at
                        # pos+g — without it, a fully-accepted round would
                        # leave a PERMANENT hole there (the next round
                        # starts writing at pos+g+1) and acceptance would
                        # silently decay with generation length, worst in
                        # the high-acceptance regime the draft exists for.
                        def dstep(c, _):
                            dtok, dpos, dkv, dkey = c
                            dlogits, dkv = dfamily.decode_step(
                                dcfg, params["d"], dtok, dpos, dkv)
                            dkey, dsub = jax.random.split(dkey)
                            nxt_d = sample_token(dlogits, dsub, temperature=temps,
                                                 top_k=ts[0], top_p=ts[1])
                            return (nxt_d, dpos + 1, dkv, dkey), (nxt_d, dlogits)

                        (_, _, aux, _), (drafts_t, dlogits_t) = jax.lax.scan(
                            dstep, (tok, pos, aux, kd), None, length=g + 1)
                        drafts = drafts_t[:g].T            # [n, g]
                        q_logits = dlogits_t[:g].swapaxes(0, 1)  # [n, g, V]
                    seq = jnp.concatenate([tok[:, None], drafts], axis=1)
                    logits, kv = family.verify_step(
                        cfg, _tparams(params), seq, pos, kv, **_akw(sel, ad))
                    out, acc = speculative_sample(ks, logits, drafts, temps,
                                                  q_logits, ts[0], ts[1])
                    nxt = jnp.take_along_axis(out, acc[:, None], axis=1)[:, 0]
                    if draft is None:
                        emit = jnp.arange(g + 1)[None, :] <= acc[:, None]
                        wpos = jnp.where(emit, hlen[:, None] + jnp.arange(g + 1)[None, :], H)
                        aux = aux.at[jnp.arange(n_l)[:, None], wpos].set(
                            out, mode="drop")
                    return (nxt, hlen + acc + 1, aux, kv, key), (out, acc)

                (tok_f, hlen_f, aux, kv, _), (toks, accs) = jax.lax.scan(
                    outer, (tok0, hlen0, aux0, kv, key0), None, length=steps
                )
                # [K, n, g+1], [K, n], cache, next-round (token, hlen) carry
                return toks, accs, (kv, aux), (tok_f, hlen_f)

            spec_chunk = _spec_chunk

    return Programs(
        prefill_sample=_prefill_sample,
        chunk_prefill=chunk_prefill,
        decode_chunk=_decode_chunk,
        spec_chunk=spec_chunk,
    )
