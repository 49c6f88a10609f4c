"""Does each cell's engine shape fit a v5e chip? The TPU compiler is installed
here and compiles for a chip that is described, not attached
(``on-chip-measurement`` §2.3): the decode and the widest prefill program of
every cell in ``BENCHMARK.json`` are compiled at the real size, and what they
need is held against the chip's memory. Nothing runs, so this says nothing
about times. About a minute a cell; by hand, like the rest of this directory.

Why it exists (PR 24): at 64 slots both first configurations were refused —
the decode program keeps two more copies of the whole KV pool as temporaries —
which a chip call would have found at a cost of minutes each."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

HBM_BYTES = 15.75 * 2 ** 30  # what the compiler reports of a v5e's 16 GB
DECODE_CHUNK = 8             # the engine's default

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"] if w["chips"] == 1]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the topology from being described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", CELLS)
def test_cell_fits_one_v5e(one_chip, name):
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.manifest import Manifest
    from benchmarks.run import program_config
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.programs import build_programs

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    cell = Manifest().cell(name)
    cfg, e = program_config(cell["config_spec"]), cell["engine"]
    slots, page = e["slots"], e["page_size"]
    per_slot = -(-(e["max_len"] + DECODE_CHUNK) // page)
    pages = slots * per_slot + 1
    params = jax.tree.map(described, jax.eval_shape(lambda: llama.init(cfg, jax.random.key(0))))
    cache = jax.tree.map(described, jax.eval_shape(lambda: llama.make_paged_cache(cfg, pages, page)))
    key = described(jax.eval_shape(lambda: jax.random.key(0)))
    programs = build_programs(llama, cfg, kv_layout="paged", spec_tokens=0, top_k=0, top_p=1.0,
                              pages_per_slot=per_slot, page_size=page)
    packed = jax.ShapeDtypeStruct((5 + per_slot, slots), jnp.int32, sharding=one_chip)
    carry = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    bucket = max(e["prefill_buckets"])
    prefill_in = jax.ShapeDtypeStruct((4, bucket + per_slot + 3), jnp.int32, sharding=one_chip)
    for label, lowered in (
            ("decode", programs.decode_chunk.lower(params, key, cache, DECODE_CHUNK, packed, carry)),
            (f"prefill 4x{bucket}", programs.prefill_sample.lower(params, key, cache, prefill_in))):
        ma = lowered.compile().memory_analysis()  # a program that does not fit raises here
        need = ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
        print(f"{name} {label}: arguments {ma.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {ma.temp_size_in_bytes / 1e9:.2f} GB, needs {need / 1e9:.2f} GB")
        assert need <= HBM_BYTES
