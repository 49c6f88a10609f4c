"""Pipeline-parallel SERVING family for llama (slot KV layout).

``build_engine`` wraps the llama family with :class:`PPLlamaFamily` when the
container's mesh has a ``pp`` axis of size > 1: block params AND the slot KV
cache shard over ``pp`` on the layer dim — the 70B weight-fit story
(BASELINE.json configs[4]) — and every engine device call runs a GPipe-style
schedule (``parallel.pipeline.spmd_pipeline_stateful``) where microbatches
of slots stream through the stage ring. Composes with ``tp``: head/mlp dims
of the stage weights and the cache's kv-head dim stay tp-sharded inside the
pipeline region with Megatron-style psums (same layout as
``llama.forward_pipelined``). A ``dp`` axis, if present, replicates the
serving work — shard serving replicas at the engine level instead.

The reference has no model execution at all (SURVEY.md §2.9); within this
framework the shim matches the GenerateEngine family contract
(``prefill`` / ``decode_step`` / ``make_cache``, engine.py:508) so slot
continuous batching, chunked decode, pipelined dispatch, and warmup all work
unchanged over a pp mesh.

Correctness relies on the engine's dropped-write conventions:
- bubble ticks carry OOB positions (decode) / OOB slot ids (prefill), so
  their cache writes vanish exactly like the engine's padding rows;
- drain-tick re-feeds recompute identical K/V (deterministic), so their
  rewrites are no-ops.

v1 limits: no chunked prefill (prompts must fit the largest prefill
bucket), no weight-only int8 (QUANTIZABLE False), no paged layout.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from gofr_tpu.models import llama
from gofr_tpu.models.base import qkv_heads
from gofr_tpu.models.llama import LlamaConfig, _rope
from gofr_tpu.ops.attention import decode_attention, mha_attention
from gofr_tpu.ops.kvcache import SlotKVCache, append_tokens, write_prompts
from gofr_tpu.ops.norms import rms_norm
from gofr_tpu.ops.rope import apply_rope
from gofr_tpu.parallel.pipeline import spmd_pipeline_stateful


class PPLlamaFamily:
    """llama with pp-sharded blocks/cache behind the engine family API."""

    __name__ = "llama_pp"
    SLOT_CHUNKED_PREFILL = False
    QUANTIZABLE = False

    def __init__(self, mesh, microbatches: int | None = None, rules=None):
        self.mesh = mesh
        self.pp = int(mesh.shape["pp"])
        self.microbatches = int(microbatches) if microbatches else self.pp
        self.tp = "tp" if "tp" in mesh.axis_names and mesh.shape["tp"] > 1 else None
        # the SAME rules build_engine shards the params with (layers→pp
        # already applied) — shard_map in_specs asserting a different
        # layout would silently reshard the full blocks every call
        if rules is None:
            from gofr_tpu.parallel.sharding import ShardingRules

            rules = ShardingRules().with_overrides(layers="pp")
        self.rules = rules

    # passthroughs so build_engine treats this like the plain family
    def init(self, cfg, key):
        return llama.init(cfg, key)

    def param_axes(self, cfg):
        return llama.param_axes(cfg)

    def _block_specs(self, cfg) -> dict:
        return {
            name: self.rules.spec(axes, self.mesh)
            for name, axes in llama.param_axes(cfg)["blocks"].items()
        }

    def _cache_spec(self) -> P:
        # [L, N, Hkv, Smax, D]: layers over pp, kv-heads over tp
        return P("pp", None, self.tp) if self.tp else P("pp")

    def make_cache(self, cfg: LlamaConfig, slots: int, max_len: int | None = None) -> SlotKVCache:
        if cfg.num_layers % self.pp:
            raise ValueError(f"num_layers {cfg.num_layers} not divisible by pp {self.pp}")
        cache = llama.make_cache(cfg, slots, max_len)
        sharding = NamedSharding(self.mesh, self._cache_spec())
        return SlotKVCache(
            k=jax.device_put(cache.k, sharding), v=jax.device_put(cache.v, sharding)
        )

    # -- decode ---------------------------------------------------------------

    def decode_step(self, cfg: LlamaConfig, params: dict, tokens: jnp.ndarray,
                    positions: jnp.ndarray, cache: SlotKVCache):
        n = tokens.shape[0]
        m = self.microbatches if n % self.microbatches == 0 else math.gcd(n, self.microbatches)
        mbs = n // m
        d = cfg.head_size
        tp = self.tp
        cos, sin = _rope(cfg)
        smax = cache.k.shape[3]
        x = params["embed"][tokens].astype(cfg.dtype)  # [N,E]
        cspec = self._cache_spec()

        @partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(self._block_specs(cfg), (cspec, cspec),
                      P(None, None), P(None), P(None)),
            out_specs=(P(None, None), (cspec, cspec)),
            check_vma=False,
        )
        def run(blocks, state, x_mb, pos_mb, off_mb):
            def stage_fn(blocks, st, act):
                k_all, v_all = st
                x, pos, off = act  # [mbs,E], [mbs], scalar slot-row offset
                pos1 = pos[:, None]

                def body(x, xs):
                    lp, k_layer, v_layer = xs  # k_layer [N, Hkv_local, Smax, D]
                    h = rms_norm(x[:, None], lp["attn_norm"], cfg.norm_eps)
                    q, k, v = qkv_heads(h, lp, d)
                    q = apply_rope(q, pos1, cos, sin)[:, 0]
                    k = apply_rope(k, pos1, cos, sin)[:, 0]
                    v = v[:, 0]
                    k_sl = lax.dynamic_slice_in_dim(k_layer, off, mbs, axis=0)
                    v_sl = lax.dynamic_slice_in_dim(v_layer, off, mbs, axis=0)
                    k_sl, v_sl = append_tokens(k_sl, v_sl, pos, k, v)
                    attn = decode_attention(q, k_sl, v_sl, pos + 1)
                    k_layer = lax.dynamic_update_slice_in_dim(k_layer, k_sl, off, axis=0)
                    v_layer = lax.dynamic_update_slice_in_dim(v_layer, v_sl, off, axis=0)
                    o = attn.reshape(mbs, -1) @ lp["wo"]
                    if tp:
                        o = lax.psum(o, tp)
                    x = x + o
                    h2 = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
                    mo = (jax.nn.silu(h2 @ lp["w_gate"]) * (h2 @ lp["w_up"])) @ lp["w_down"]
                    if tp:
                        mo = lax.psum(mo, tp)
                    return x + mo, (k_layer, v_layer)

                x, (k_all, v_all) = lax.scan(body, x, (blocks, k_all, v_all))
                return (k_all, v_all), (x, pos, off)

            # bubble ticks: OOB positions -> append's masked select drops
            # every write (same convention as engine padding rows)
            init_act = (
                jnp.zeros((mbs, x.shape[1]), x.dtype),
                jnp.full((mbs,), smax, pos_mb.dtype),
                jnp.zeros((), off_mb.dtype),
            )
            (x_out, _, _), state = spmd_pipeline_stateful(
                stage_fn, blocks, state, (x_mb, pos_mb, off_mb),
                microbatches=m, init_act=init_act,
            )
            return x_out, state

        x_mb = x.reshape(m, mbs, -1)
        pos_mb = positions.reshape(m, mbs)
        off_mb = jnp.arange(m, dtype=jnp.int32) * mbs
        x_mb, (new_k, new_v) = run(
            params["blocks"], (cache.k, cache.v), x_mb, pos_mb, off_mb)
        x = x_mb.reshape(n, -1)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (x @ head).astype(jnp.float32)
        return logits, SlotKVCache(k=new_k, v=new_v)

    # -- prefill --------------------------------------------------------------

    def prefill(self, cfg: LlamaConfig, params: dict, tokens: jnp.ndarray,
                lengths: jnp.ndarray, cache: SlotKVCache, slots: jnp.ndarray,
                offsets: jnp.ndarray | None = None):
        if offsets is not None:
            raise ValueError("pp serving does not support chunked prefill (v1)")
        b, s = tokens.shape
        m = self.microbatches if b % self.microbatches == 0 else math.gcd(b, self.microbatches)
        mbs = b // m
        d = cfg.head_size
        tp = self.tp
        cos, sin = _rope(cfg)
        num_slots = cache.k.shape[1]
        positions = jnp.arange(s)[None]
        x = params["embed"][tokens].astype(cfg.dtype)  # [B,S,E]
        cspec = self._cache_spec()

        @partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(self._block_specs(cfg), (cspec, cspec),
                      P(None, None, None), P(None, None), P(None, None)),
            out_specs=(P(None, None, None), (cspec, cspec)),
            check_vma=False,
        )
        def run(blocks, state, x_mb, len_mb, row_mb):
            def stage_fn(blocks, st, act):
                k_all, v_all = st
                x, lens, rows = act  # [mbs,S,E], [mbs], [mbs]

                def body(x, xs):
                    lp, k_layer, v_layer = xs
                    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
                    q, k, v = qkv_heads(h, lp, d)
                    q = apply_rope(q, positions, cos, sin)
                    k = apply_rope(k, positions, cos, sin)
                    # OOB rows (bubbles / padding) scatter nowhere
                    k_layer, v_layer = write_prompts(k_layer, v_layer, rows, k, v)
                    a = mha_attention(q, k, v, causal=True, kv_lengths=lens)
                    o = a.reshape(mbs, s, -1) @ lp["wo"]
                    if tp:
                        o = lax.psum(o, tp)
                    x = x + o
                    h2 = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
                    mo = (jax.nn.silu(h2 @ lp["w_gate"]) * (h2 @ lp["w_up"])) @ lp["w_down"]
                    if tp:
                        mo = lax.psum(mo, tp)
                    return x + mo, (k_layer, v_layer)

                x, (k_all, v_all) = lax.scan(body, x, (blocks, k_all, v_all))
                return (k_all, v_all), (x, lens, rows)

            init_act = (
                jnp.zeros((mbs, s, x.shape[2]), x.dtype),
                jnp.ones((mbs,), len_mb.dtype),
                jnp.full((mbs,), num_slots, row_mb.dtype),  # OOB slot ids
            )
            (x_out, _, _), state = spmd_pipeline_stateful(
                stage_fn, blocks, state, (x_mb, len_mb, row_mb),
                microbatches=m, init_act=init_act,
            )
            return x_out, state

        x_mb = x.reshape(m, mbs, s, -1)
        len_mb = lengths.reshape(m, mbs)
        row_mb = slots.reshape(m, mbs)
        x_mb, (new_k, new_v) = run(
            params["blocks"], (cache.k, cache.v), x_mb, len_mb, row_mb)
        x = x_mb.reshape(b, s, -1)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        last = x[jnp.arange(b), lengths - 1]
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (last @ head).astype(jnp.float32)
        return logits, SlotKVCache(k=new_k, v=new_v)
