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

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.models import LlamaConfig, llama

pytestmark = pytest.mark.quick  # a dozen and a half compiles of three to ten seconds; skips where no topology can be described

SLOTS, PAGE, PAGES_PER_SLOT = 32, 128, 9
WIDTHS = {  # published widths AND depths: a pool small enough for fast memory is laid out otherwise
    "llama-1b-d64": dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                         num_layers=22, num_heads=32, num_kv_heads=4),
    "internlm2-1.8b-d128": dict(vocab_size=92544, hidden_size=2048, intermediate_size=8192,
                                num_layers=24, num_heads=16, num_kv_heads=8),
    "mistral-7b-l16-d128": dict(vocab_size=32768, hidden_size=4096, intermediate_size=14336,
                                num_layers=16, num_heads=32, num_kv_heads=8),
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


@functools.cache
def _compile(one_chip, width, program):
    """One paged program at ``width`` compiled for the described chip, its
    read path resolved as an engine on a TPU resolves it (the rule in
    ops/attention.resolve_backend under ``platform_hint("tpu")``) →
    (compiled, cache shapes). Compiled once a (width, program): the tests
    below read the same text for different things."""
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
    jax.clear_caches()  # a trace made for another platform would be served again
    with pallas.platform_hint("tpu"):
        compiled = jax.jit(lambda p, *a: fn(cfg, p, *a), donate_argnums=(3,)).lower(
            params, *args).compile()
    return compiled, cache


def _ops_typed(compiled, kinds, dims):
    """Lines of the compiled program whose result is bf16[dims] made by one of ``kinds``."""
    typed = re.escape("bf16[%s]" % ",".join(str(x) for x in dims))
    return [line.strip()[:160] for line in compiled.as_text().splitlines()
            if re.search(r"= %s\S* (%s)\(" % (typed, "|".join(kinds)), line)]


def _assert_qkv_reads_the_stacked_weights(compiled, layers, embed, q_width, kv_width):
    """No operation of the compiled program is typed like one layer's ``wq`` /
    ``wk`` / ``wv`` or like their stack (a ``copy``, or a fusion — whose root
    is then a lone ``dynamic-slice``: the bad case of docs/kernels.md,
    "products that take a scanned weight"), and three ``qkv_rope`` products
    take a stacked parameter ``[L, E, heads*D]`` as an operand."""
    for dims in [(1, embed, q_width), (1, embed, kv_width), (layers, embed, q_width), (layers, embed, kv_width)]:
        lone = _ops_typed(compiled, ["copy", "fusion"], dims)
        assert not lone, f"a layer's q/k/v weight (or the stack) is sliced out, copied or re-laid: {lone}"
    text = compiled.as_text()
    types = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = ([a-z0-9]+\[[\d,]*\])", text, re.M))
    stacked = {"bf16[%d,%d,%d]" % (layers, embed, w) for w in (q_width, kv_width)}
    reading = 0  # the qkv_rope products that take a stacked parameter (a rotary step may add products of its own)
    for line in text.splitlines():
        if " fusion(" in line and re.search(r'op_name="[^"]*/qkv_rope/dot_general"', line):
            operands = re.findall(r"%[\w.\-]+", line.split(" fusion(", 1)[1].split("), kind=", 1)[0])
            reading += bool(stacked & {types.get(name) for name in operands})
    assert reading == 3, f"{reading} of the three q/k/v products read the stacked parameter"


@pytest.mark.parametrize("program", ["decode", "prefill", "chunk_prefill"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_qkv_products_read_the_stacked_weights_on_the_v5e(one_chip, no_compile_cache, width, program):
    """PR 35: written as ``qdot(h, wq).reshape(.., heads, D)`` the compiler
    folded the split into the product, wanted the weight as [heads, D, E], and
    every layer of every step sliced ``wq[l]`` out of the stack into fast
    memory and transposed the copy before multiplying. Behind
    ``models/base.qkv_heads``' barrier the three products take the stacked
    parameter and the layer index, as the MLP's do."""
    compiled, _ = _compile(one_chip, width, program)
    w = WIDTHS[width]
    head = w["hidden_size"] // w["num_heads"]
    _assert_qkv_reads_the_stacked_weights(
        compiled, w["num_layers"], w["hidden_size"], w["num_heads"] * head, w["num_kv_heads"] * head)


@pytest.mark.parametrize("program", ["decode", "prefill", "chunk_prefill"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_pool_is_updated_in_place_on_the_v5e(one_chip, no_compile_cache, width, program):
    compiled, cache = _compile(one_chip, width, program)
    copies = _ops_typed(compiled, ["copy"], cache.k.shape)
    assert not copies, f"the compiler copies a whole pool plane: {copies}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < cache.k.size * 2, f"temporaries {temp} B: a second pool plane is back"


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_decode_with_the_paged_kernel_gathers_nothing_on_the_v5e(one_chip, no_compile_cache, width):
    """The decode step as an engine on a TPU traces it, no pin and no
    environment variable set: the kernel is in the
    program, no operation builds a gathered view of the pool — neither
    ``[N, MaxP, Hkv, page, D]`` as the gather leaves it (also named by its
    flat form ``[N*MaxP, Hkv, page, D]``) nor ``[N, Hkv, MaxP, page, D]`` as
    the re-layout did — and no pool plane is copied in front of the kernel.
    At head_dim 64 the kernel's wrapper pads ONE layer's pages to the lane
    width a call: two layer-sized temporaries, not a pool."""
    compiled, cache = _compile(one_chip, width, "decode")
    assert "tpu_custom_call" in compiled.as_text(), "the kernel is not in the program"
    layers, _, hkv, page, d = cache.k.shape
    for dims in [(SLOTS, PAGES_PER_SLOT, hkv, page, d), (SLOTS * PAGES_PER_SLOT, hkv, page, d),
                 (SLOTS, hkv, PAGES_PER_SLOT, page, d)]:
        views = _ops_typed(compiled, ["copy", "fusion"], dims)
        assert not views, f"a gathered view of the pool is back: {views}"
    copies = _ops_typed(compiled, ["copy"], cache.k.shape)
    assert not copies, f"the compiler copies a whole pool plane in front of the kernel: {copies}"
    padded_layers = 2 * (cache.k.size * 2 // layers) * 128 // d if d % 128 else 0
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < padded_layers + (4 << 20), (
        f"temporaries {temp} B (the XLA read path's were 76 MB at InternLM2's width)")


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_decode_appends_inside_the_kernel_where_it_can_address_the_rows(one_chip, no_compile_cache, width):
    """At head_dim 128 (both benchmark widths) the decode step holds NO
    scatter: its one ``attention`` custom call a layer takes both pool planes
    and returns them aliased, so the layer scan's carry is written where it
    lies, by the kernel. At head_dim 64 the kernel reads a padded copy of one
    layer, nothing can be written through that, and the two scatters (typed
    like the plane they alias) are still there. Nothing chose either: the
    rule (``ops/attention.append_rides_in_kernel``) read the plane's shape.
    Nor is there a sort: the kernel finds its live lanes itself, where an
    order handed in by the wrapper was sorted inside the layer loop."""
    compiled, cache = _compile(one_chip, width, "decode")
    text = compiled.as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 and re.search(r'op_name="[^"]*/attention[/"]', kernels[0]), kernels
    plane = re.escape("bf16[%s]" % ",".join(str(x) for x in cache.k.shape))
    scatters = [line.strip()[:120] for line in text.splitlines() if re.search(r" scatter\(", line)]
    if cache.k.shape[4] % 128:
        assert len(scatters) == 2 and all(re.search(plane, line) for line in scatters), scatters
        assert "output_to_operand_aliasing" not in kernels[0]
        return
    assert not scatters, f"a scatter is back in the decode step: {scatters}"
    assert " sort(" not in text, "the decode step sorts: the kernel lists its live lanes itself"
    assert not re.search(r'op_name="[^"]*/kv_append[/"]', text), "an operation under kv_append is back"
    result, operands = kernels[0].split(" custom-call(", 1)
    assert len(re.findall(plane, result)) == 2, f"the call does not return both planes: {result[:300]}"
    aliased = re.search(r"output_to_operand_aliasing=\{\{1\}: \((\d+), \{\}\), \{2\}: \((\d+), \{\}\)\}", operands)
    assert aliased and int(aliased.group(2)) == int(aliased.group(1)) + 1, (
        f"the planes are not aliased through the call: {operands[-400:]}")


# -- the engine's own decode chunk, read path chosen by the rule alone -----------


def _engine_decode_chunk(one_chip, traced_before=None, **engine_kw):
    """A small engine (heads of 128, pages of 128) built on the CPU, its OWN
    jitted decode chunk lowered for the described chip under the scopes its
    warm-up enters → the lowered program. No pin exists and no environment
    variable is set: what is in the program is what the rule put there.
    ``traced_before`` runs after the caches are cleared, before the lowering."""
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.ops import pallas
    from gofr_tpu.tpu.engine import GenerateEngine

    cfg = LlamaConfig(vocab_size=256, hidden_size=256, intermediate_size=512,
                      num_layers=2, num_heads=2, num_kv_heads=1)
    eng = GenerateEngine(llama, cfg, llama.init(cfg, jax.random.key(0)), new_mock_container(),
                         slots=4, max_len=256, kv_layout="paged", page_size=128,
                         prefill_buckets=[128], **engine_kw)

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    try:
        args = jax.tree.map(described, (eng.params, eng._base_key, eng.cache))
        packed = jax.ShapeDtypeStruct((5 + eng.pages_per_slot, eng.num_slots), jnp.int32,
                                      sharding=one_chip)
        carry = jax.tree.map(described, eng._zero_carry())
        jax.clear_caches()
        if traced_before is not None:
            traced_before()
        with pallas.platform_hint("tpu"), eng._trace_scope():
            return eng._decode_chunk.lower(*args, eng.decode_chunk, packed, carry)
    finally:
        eng.stop()


def _located(lowered) -> dict:
    """{operation line: its location, references expanded} of the lowered
    program's kernel calls — the names and call sites the compile cache keys on."""
    text = lowered.as_text(debug_info=True)
    defs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))

    def expand(ref, depth=0):
        body = defs.get(ref, ref)
        return re.sub(r"#loc\d+", lambda m: expand(m.group(0), depth + 1), body) if depth < 64 else body

    out = {}
    for line in text.splitlines():
        if "tpu_custom_call" in line and "stablehlo.custom_call" in line:
            ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
            out[re.sub(r"loc\(#loc\d+\)\s*$", "", line).strip()[:80]] = expand(ref.group(1)) if ref else None
    return out


@pytest.mark.parametrize("lockstep_role", [None, "leader"])
def test_engine_decode_chunk_holds_the_kernel_by_rule(one_chip, no_compile_cache, monkeypatch, lockstep_role):
    """Solo and as a lockstep leader (which used to stand down from the
    warm-up race and so decode through ``gather_kv``): the engine's decode
    chunk for a v5e holds the ``attention`` custom call and no operation
    under ``kv_gather`` — every rank resolves the rule alike."""
    for var in ("GOFR_PALLAS", "GOFR_AUTOTUNE", "GOFR_AUTOTUNE_CACHE", "GOFR_PALLAS_INTERPRET"):
        monkeypatch.delenv(var, raising=False)
    compiled = _engine_decode_chunk(one_chip, lockstep_role=lockstep_role).compile().as_text()
    kernels = [line for line in compiled.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all(re.search(r'op_name="[^"]*/attention[/"]', line) for line in kernels), kernels[:2]
    assert not re.search(r'op_name="[^"]*/kv_gather[/"]', compiled), "an operation under kv_gather is back"
    assert not re.search(r'op_name="[^"]*/kv_append[/"]', compiled), "an operation under kv_append is back"
    assert " scatter(" not in compiled, "the decode chunk scatters (heads of 128: the kernel appends)"


def test_kernel_operations_are_named_by_the_program_alone(one_chip, no_compile_cache):
    """The compile cache keys on operation names and call sites. The decode
    chunk's kernel calls carry the same ones whether the program is the
    first to trace the kernel, traces it a second time, or comes after
    something else traced the kernel alone from another call site (the
    warm-up race did; the wrapper's own ``jax.jit`` then kept that trace)."""
    from gofr_tpu.ops import pallas
    from gofr_tpu.ops.attention import paged_decode_attention

    def kernel_alone():  # at the engine's shapes, as a race would trace it
        def spec(*shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        with pallas.platform_hint("tpu"):
            jax.jit(lambda *a: paged_decode_attention(*a, backend="pallas")).lower(
                spec(4, 2, 128), spec(2, 12, 1, 128, 128), spec(2, 12, 1, 128, 128),
                spec(dtype=jnp.int32), spec(4, 3, dtype=jnp.int32), spec(4, dtype=jnp.int32))

    first = _located(_engine_decode_chunk(one_chip))
    assert first and all(first.values()), first
    assert _located(_engine_decode_chunk(one_chip)) == first
    assert _located(_engine_decode_chunk(one_chip, traced_before=kernel_alone)) == first


# -- PR 34: a second family shares the kernel and the paged helpers -----------------------


def _kernel_operands(text: str) -> list[int]:
    """Operand counts of the compiled program's kernel calls."""
    counts = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            operands = line.split(" custom-call(", 1)[1].split("), custom_call_target", 1)[0]
            counts.append(len(re.findall(r"%[\w.\-]+", operands)))
    return counts


@pytest.mark.parametrize("width,operands", [("mistral-7b-l16-d128", 11), ("llama-1b-d64", 7)])
def test_llama_programs_carry_no_window_and_no_expert_scope(one_chip, no_compile_cache, width, operands):
    """The accepted configurations keep their programs: the llama family's
    kernel call takes what it took — lengths, table, layer (+ write page and
    row), q, the head mask (+ the step's K and V), the two planes: 11 operands
    fused, 7 read-only — and NO window operand, and no operation of its decode
    or prefill program sits under a ``tracing.MOE_SCOPES`` name."""
    from gofr_tpu import tracing

    decode = _compile(one_chip, width, "decode")[0].as_text()
    assert _kernel_operands(decode) == [operands], _kernel_operands(decode)
    for program in (decode, _compile(one_chip, width, "prefill")[0].as_text()):
        for name in tracing.MOE_SCOPES:
            assert not re.search(r'op_name="[^"]*/%s[/"]' % name, program), name


CELL_SLOTS, CELL_MAX_LEN, CELL_BUCKET = 128, 1536, 1024  # benchmarks/cells/command-a-plus-…: the shape it is timed at


@pytest.fixture(scope="module")
def cohere2_programs(one_chip):
    """The new family's three served programs at the benchmark cell's engine
    shape and the configuration's published widths, lowered as the engine
    lowers them (tpu/programs.build_programs) for the described chip."""
    from gofr_tpu.models import get_family
    from gofr_tpu.models.cohere2_moe import Cohere2MoeConfig
    from gofr_tpu.ops import pallas
    from gofr_tpu.tpu.programs import build_programs

    fam = get_family("cohere2_moe")
    cfg = Cohere2MoeConfig(vocab_size=32768, num_layers=4, experts_held=16)
    per_slot = -(-(CELL_MAX_LEN + 8) // PAGE)
    pages = CELL_SLOTS * per_slot

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree.map(described, jax.eval_shape(lambda: fam.init(cfg, jax.random.key(0))))
    cache = jax.tree.map(described, jax.eval_shape(lambda: fam.make_paged_cache(cfg, pages, PAGE)))
    key = described(jax.eval_shape(lambda: jax.random.key(0)))
    programs = build_programs(fam, cfg, kv_layout="paged", spec_tokens=0, top_k=0, top_p=1.0,
                              pages_per_slot=per_slot, page_size=PAGE)
    jax.clear_caches()
    with pallas.platform_hint("tpu"):
        lowered = {
            "decode": programs.decode_chunk.lower(params, key, cache, 8, ints(5 + per_slot, CELL_SLOTS),
                                                  ints(CELL_SLOTS)),
            "prefill": programs.prefill_sample.lower(params, key, cache, ints(4, CELL_BUCKET + per_slot + 3)),
            "chunk": programs.chunk_prefill.lower(params, key, cache, ints(1, CELL_BUCKET + per_slot + 4)),
        }
    # compiled on first use, once a program (under the test's ``no_compile_cache``)
    return functools.cache(lambda program: lowered[program].compile()), cache


@pytest.mark.parametrize("program,temp_gb", [("decode", 0.1), ("prefill", 2.0), ("chunk", 1.0)])
def test_cohere2_moe_programs_fit_the_v5e_at_the_cells_shape(cohere2_programs, no_compile_cache, program, temp_gb):
    """128 query heads, 16 held experts of 3 x 4096^2 a layer, 128 lanes x 13
    pages: each program compiles for the v5e and fits beside 9.47 GB of
    weights and a 3.49 GB pool; no layer's expert stack is copied out of the
    parameters (the grouped product reads the whole stack: ops/moe.py), the
    pool is carried, and the temporaries stay what they were measured to be
    (the decode chunk's were 742 MB until PR 35: the re-laid ``wq`` stack and
    a layer of it; 8 MB since)."""
    compiled_program, cache = cohere2_programs
    compiled = compiled_program(program)  # a program that does not fit raises here
    ma = compiled.memory_analysis()
    need = ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
    assert need <= 15.75 * 2 ** 30, need
    assert ma.temp_size_in_bytes < temp_gb * 1e9, ma.temp_size_in_bytes
    text = compiled.as_text()
    assert not _ops_typed(compiled, ["copy"], cache.k.shape), "a pool plane is copied"
    assert not _ops_typed(compiled, ["copy"], (4, 16, 4096, 4096)), "the experts of every layer are re-laid"
    for name in ("moe_router", "moe_experts", "moe_shared"):
        assert re.search(r'op_name="[^"]*/mlp/%s[/"]' % name, text), f"no operation under mlp/{name}"
    if program == "decode":
        # the kernel serves at a group of 16, appends in place, and takes the window: 12 operands
        assert _kernel_operands(text) == [12], _kernel_operands(text)
        assert " scatter(" not in text and not re.search(r'op_name="[^"]*/kv_gather[/"]', text)
        assert "ragged-dot" not in text  # 128 tokens take the per-expert products
    else:
        assert "ragged-dot" in text  # 1,024 and 4,096 tokens take the grouped product


@pytest.mark.parametrize("program", ["decode", "prefill", "chunk"])
def test_cohere2_moe_qkv_products_read_the_stacked_weights(cohere2_programs, no_compile_cache, program):
    """At 128 query heads a layer's ``wq`` is 134 MB, too large for fast
    memory: the folded split (test_qkv_products_read_the_stacked_weights_on_the_v5e)
    re-laid the WHOLE ``[4, 4096, 16384]`` stack once a decode chunk and copied
    a layer of it HBM to HBM every layer-step. Neither is left in any program."""
    compiled_program, _ = cohere2_programs
    _assert_qkv_reads_the_stacked_weights(compiled_program(program), 4, 4096, 128 * 128, 8 * 128)
