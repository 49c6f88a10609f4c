"""What the v5e compiler makes of the paged programs' pool — checked here,
without the chip: the TPU compiler is installed and compiles for a chip that
is described, not attached. Nothing runs, so this says nothing about times.

Why it exists (PR 27): the CPU-side guard (tests/test_paged.py) sees that
the pool is carried and written by a scatter, but not what the TPU compiler
then does with it. Twice it answered a reasonable-looking write by re-laying
the whole pool out inside the program and copying it in and out — a second
pool, and at head_dim 64 a minute of compile time a program: a scatter whose
window spans the KV heads, and a scatter of thousands of single rows. Both
show as ``copy`` operations typed like a whole pool plane.

All in ONE file, topology described inside a fixture: only one process at a
time may load the TPU's library, and it keeps it until it exits."""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.models import LlamaConfig, llama

pytestmark = pytest.mark.quick  # six compiles of about three seconds; skips where no topology can be described

SLOTS, PAGE, PAGES_PER_SLOT = 32, 128, 9
WIDTHS = {  # published widths AND depths: a pool small enough for fast memory is laid out otherwise
    "llama-1b-d64": dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                         num_layers=22, num_heads=32, num_kv_heads=4),
    "internlm2-1.8b-d128": dict(vocab_size=92544, hidden_size=2048, intermediate_size=8192,
                                num_layers=24, num_heads=16, num_kv_heads=8),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the topology from being described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("program", ["decode", "prefill", "chunk_prefill"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_pool_is_updated_in_place_on_the_v5e(one_chip, no_compile_cache, width, program):
    from gofr_tpu.ops import pallas

    cfg = LlamaConfig(**WIDTHS[width])
    pool = SLOTS * PAGES_PER_SLOT

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree.map(described, jax.eval_shape(lambda: llama.init(cfg, jax.random.key(0))))
    cache = jax.tree.map(described, jax.eval_shape(lambda: llama.make_paged_cache(cfg, pool, PAGE)))
    fn, args = {
        "decode": (llama.decode_step_paged,
                   (ints(SLOTS), ints(SLOTS), cache, ints(SLOTS, PAGES_PER_SLOT))),
        "prefill": (llama.prefill_paged,
                    (ints(4, 512), ints(4), cache, ints(4, PAGES_PER_SLOT))),
        "chunk_prefill": (llama.prefill_paged,
                          (ints(1, 512), ints(1), cache, ints(1, PAGES_PER_SLOT), ints(1))),
    }[program]
    with pallas.platform_hint("tpu"):
        compiled = jax.jit(lambda p, *a: fn(cfg, p, *a), donate_argnums=(3,)).lower(
            params, *args).compile()

    plane = "bf16[%d,%d,%d,%d,%d]" % cache.k.shape
    copies = [line.strip()[:160] for line in compiled.as_text().splitlines()
              if re.search(r"= %s\S* copy\(" % re.escape(plane), line)]
    assert not copies, f"the compiler copies a whole pool plane: {copies}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < cache.k.size * 2, f"temporaries {temp} B: a second pool plane is back"
