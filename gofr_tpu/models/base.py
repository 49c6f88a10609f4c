"""Model layer foundations.

Models in gofr_tpu are *functional modules*: a frozen config dataclass plus
pure functions ``init(cfg, key) → params``, ``param_axes(cfg) → logical
axes pytree``, and jittable ``forward_*`` functions. No module classes, no
framework state — params are plain pytrees the parallel layer can shard by
logical axes (gofr_tpu.parallel.sharding) and orbax can checkpoint.

Layer parameters are *stacked*: every per-layer weight carries a leading
``layers`` dimension and the forward pass runs ``lax.scan`` over it — one
traced block regardless of depth, which keeps XLA compile time flat and
maps cleanly onto pipeline stages later.

``ModelSpec`` is what users hand to ``app.serve_model`` — the serving-side
description (family, config, weights source, task) that ``build_engine``
turns into a running engine.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.ops.quant import qdot


def truncated_normal(key, shape, stddev: float, dtype=jnp.float32):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32).astype(dtype) * stddev


def fan_in_init(key, shape, fan_in: int | None = None, dtype=jnp.float32):
    fan = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[-1]
    return truncated_normal(key, shape, 1.0 / math.sqrt(fan), dtype)


def param_count(params: Any) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def param_bytes(params: Any) -> int:
    return sum(int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(params))


def cast_floats(params: Any, dtype) -> Any:
    """Cast floating-point leaves (weights) to ``dtype``; leave ints alone."""
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, params
    )


def qkv_heads(h: jnp.ndarray, lp: dict, head_size: int):
    """h [..., E] → q [..., Hq, D], k, v [..., Hkv, D]: a layer's three
    projections whose results are split into heads, the head counts read off
    the weights' widths.

    The flat products pass a barrier before the split. A reshape written
    straight after the product is folded INTO it by the TPU compiler, which
    then wants the weight as [heads, D, E]: it slices the layer's matrix out
    of the stacked parameter, transposes the copy and only then multiplies —
    every layer of every step (at 128 query heads it re-lays the whole stack).
    Behind the barrier the product is a plain [.., E] x [E, heads*D] that reads
    the scanned weight where it lies, as the MLP's products do (docs/kernels.md,
    "Products that take a scanned weight"; tests/test_v5e_compile.py holds
    it). The barrier changes no value."""
    flat = lax.optimization_barrier(tuple(qdot(h, lp[w]) for w in ("wq", "wk", "wv")))
    return tuple(y.reshape(*y.shape[:-1], -1, head_size) for y in flat)


@dataclass
class ModelSpec:
    """What ``app.serve_model`` consumes.

    family: "llama" | "bert" | "vit" (extensible via ``models.register``)
    config: the family's config dataclass (or dict of overrides)
    task: "generate" | "embed" | "classify" — selects the engine path
    weights: None (random init), a checkpoint path (orbax), or an HF model
             id/path to convert (gofr_tpu.models.convert)
    tokenizer: HF tokenizer id/path OR an object with encode/decode (e.g.
             utils.ByteTokenizer) for text models (optional — the engine
             also accepts pre-tokenized int arrays)
    """

    family: str
    config: Any = None
    task: str = "generate"
    weights: str | None = None
    tokenizer: Any = None
    dtype: Any = jnp.bfloat16
    extra: dict[str, Any] = dataclasses.field(default_factory=dict)


_FAMILIES: dict[str, Any] = {}
# Families whose module is imported when they are first asked for (a name →
# its module's path): a process that serves another family never pays for them.
_LAZY_FAMILIES: dict[str, str] = {}


def register_family(name: str, module: Any) -> None:
    """``module`` is the family's module, or the dotted path of one to import
    on first use."""
    if isinstance(module, str):
        _LAZY_FAMILIES[name] = module
    else:
        _FAMILIES[name] = module


def get_family(name: str):
    if name not in _FAMILIES and name in _LAZY_FAMILIES:
        import importlib

        _FAMILIES[name] = importlib.import_module(_LAZY_FAMILIES[name])
    try:
        return _FAMILIES[name]
    except KeyError:
        raise KeyError(
            f"unknown model family {name!r}; registered: "
            f"{sorted(set(_FAMILIES) | set(_LAZY_FAMILIES))}") from None


def family_of(config: Any) -> str:
    """The registered family a config object belongs to: the one whose module
    defines the config's class (``LlamaConfig`` lives in ``models/llama.py``,
    family ``llama``)."""
    module = type(config).__module__
    for name, fam in _FAMILIES.items():
        if getattr(fam, "__name__", None) == module:
            return name
    for name, path in _LAZY_FAMILIES.items():
        if path == module:
            return name
    raise KeyError(f"no registered model family defines {type(config).__name__} ({module})")
