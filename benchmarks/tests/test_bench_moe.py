"""What PR 34 added to the benchmark for expert configurations: the plain
reference against the family's own forward pass, the least-work arithmetic of
``harness/moe_work`` on the configuration file, the new readers on hand-made
tuples and counters, and ``run.py``'s body rehearsed on the CPU with a tiny
configuration of the family (family, reference and counters all found from
the configuration file: no ``.py`` names them)."""

import asyncio
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmarks.harness import moe_work, names  # noqa: E402
from benchmarks.harness.manifest import Manifest, load_module  # noqa: E402

CONFIG = os.path.join(BENCH, "configs", "command-a-plus-05-2026-l4e16.json")
MS = 1_000_000

TINY = {  # the real file's keys at a CPU size: a share of 4 of 16 experts, window 16
    "name": "tiny-moe", "source": "none: a CPU rehearsal size", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 48, "num_hidden_layers": 4, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 16, "num_experts": 4, "router_num_experts": 16, "first_expert": 8, "num_experts_per_tok": 4,
    "num_shared_experts": 2, "layer_switch": 4, "sliding_window": 16, "rope_theta": 10000.0,
    "max_position_embeddings": 256, "layer_norm_eps": 1e-5, "logit_scale": 1, "torch_dtype": "float32",
    "tie_word_embeddings": True, "weights_seed": 0, "reference": "cohere2_moe",
}


def _real():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.mark.parametrize("n,pad_to", [(21, 32), (32, 32), (5, 16)])
def test_reference_equals_the_familys_forward(n, pad_to):
    import jax
    import jax.numpy as jnp

    from benchmarks.run import program_config

    ref = load_module(os.path.join(BENCH, "references", "cohere2_moe.py"))
    spec = {**TINY, "program": _real()["program"]}
    cfg = program_config(spec)
    from gofr_tpu.models import family_of, get_family

    fam = get_family(family_of(cfg))
    params = fam.init(cfg, jax.random.key(3))
    toks = [int(t) for t in np.random.RandomState(n).randint(3, cfg.vocab_size, size=n)]
    got = np.asarray(ref.last_logits(spec, params, toks, pad_to))
    want = np.asarray(fam.forward(cfg, params, jnp.asarray([toks]), jnp.asarray([n]))[0, n - 1])
    # float32 both sides, different operation order: a few ulps at the logits' magnitude
    assert np.max(np.abs(got - want)) <= 2e-5 * max(1.0, float(np.max(np.abs(want))))
    assert int(np.argmax(got)) == int(np.argmax(want))


def test_the_file_holds_the_published_widths_and_the_stated_share():
    c = _real()
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]) == (4096, 128, 8, 128)
    assert (c["intermediate_size"], c["router_num_experts"], c["num_experts_per_tok"]) == (4096, 128, 8)
    assert (c["num_shared_experts"], c["sliding_window"], c["rope_theta"], c["layer_switch"]) == (4, 4096, 50000, 4)
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (4, 16, 32768)
    assert c["reduced_from"] == {"num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144}
    assert any("EIGHT chips share each layer" in n for n in c["notes"])
    # the least-work arithmetic, by hand: one expert 3 x 4096^2 x 2 B; a layer's
    # always-read weights; a 128-lane step at 400 live tokens a lane, all 16 hit
    assert moe_work.expert_bytes(c) == 3 * 4096 * 4096 * 2 == 100_663_296
    assert moe_work.assignment_flops(c) == 6 * 4096 * 4096
    always = (2 * 4096 * 16384 + 2 * 4096 * 1024 + 4 * 3 * 4096 * 4096 + 4096) * 2 + 4096 * 128 * 4
    assert moe_work.always_layer_bytes(c) == always
    layer = always + 16 * 100_663_296 + 128 * 2 * 4096 * 2
    want = 4 * layer + (32768 * 4096 + 128 * 4096 + 4096) * 2 + (128 * 400 + 128) * 16384
    assert moe_work.decode_step_bytes(c, live_tokens=128 * 400, lanes=128, experts_hit=16, assignments=128) == want
    # every weight of the model is in it once but the embedding's gather: 9.47 GB of parameters
    params = 4 * (always - 4096 * 128 * 4 + 16 * 100_663_296) // 2 + 4 * 4096 * 128 + 32768 * 4096 + 4096
    assert params == 4_733_292_544


def test_the_yardsticks_names_are_the_programs():
    from gofr_tpu import tracing

    assert moe_work.MOE_SCOPES == tracing.MOE_SCOPES
    assert not set(moe_work.MOE_SCOPES) & set(names.SCOPES)
    from gofr_tpu.models.cohere2_moe import Cohere2MoeConfig, step_counters

    counted = {name for name, _ in step_counters(Cohere2MoeConfig.tiny())}
    assert counted == {moe_work.ASSIGNMENTS, moe_work.ABSENT, moe_work.EXPERTS_HIT, moe_work.LAYER_STEPS}


def _metrics(scale: int) -> str:
    """A /metrics text: per phase, 16 held experts' assignments, hits, layer-steps."""
    lines = []
    for phase, (per_expert, hit, steps) in {"decode": (80, 160, 10), "prefill": (100, 32, 2)}.items():
        for e in range(16):
            lines.append(f'{moe_work.ASSIGNMENTS}{{expert="{e}",phase="{phase}"}} {per_expert * scale}')
        lines.append(f'{moe_work.EXPERTS_HIT}{{phase="{phase}"}} {hit * scale}')
        lines.append(f'{moe_work.LAYER_STEPS}{{phase="{phase}"}} {steps * scale}')
    lines += [f'app_tpu_batch_occupancy_sum{{kind="decode"}} {0.75 * scale}',
              f'app_tpu_batch_occupancy_count{{kind="decode"}} {scale}']
    return "\n".join(lines)


def test_readers_on_hand_made_tuples_and_counters(monkeypatch):
    """Three whole decode chunks and three whole prefills (the slice's first and
    last run are cut and dropped), expert scopes nested in ``mlp``."""
    pre = "jit(_decode_chunk)/while/body/closed_call/jit(decode_step_paged)/while/body/closed_call/mlp/"
    pf = "jit(_prefill_sample)/jit(prefill_paged)/while/body/closed_call/mlp/"
    modules, ops, t = [], [], 0
    for i in range(5):
        modules.append((f"jit__decode_chunk({i})", t, 100 * MS, i))
        ops += [(pre + "moe_router/top_k:", t + 1 * MS, 8 * MS), (pre + "moe_experts/dot_general:", t + 10 * MS, 40 * MS),
                (pre + "moe_shared/dot_general:", t + 52 * MS, 16 * MS), (pre + "dot_general:", t + 70 * MS, 4 * MS),
                ("jit(_decode_chunk)/while/body/closed_call/attention/custom-call:", t + 80 * MS, 10 * MS)]
        t += 100 * MS
    for i in range(5):
        modules.append((f"jit__prefill_sample({i})", t, 50 * MS, 5 + i))
        ops += [(pf + "moe_experts/ragged_dot:", t + 5 * MS, 8 * MS), (pf + "moe_router/sort:", t + 20 * MS, 2 * MS)]
        t += 50 * MS
    sums, runs = moe_work.moe_scope_sums(ops, modules, r"decode_chunk")
    assert runs == 4 and sums == {"moe_router": 4 * 8 * MS, "moe_experts": 4 * 40 * MS, "moe_shared": 4 * 16 * MS}
    # the accepted reader files the same operations under mlp, the innermost name IT knows
    old, _ = names.scope_sums(ops, modules, r"decode_chunk")
    assert old["mlp"] == 4 * (8 + 40 + 16 + 4) * MS

    monkeypatch.setattr(names, "load", lambda ctx: {"ops": ops, "modules": modules})
    c = _real()
    ctx = {"cell": {"name": "x"}, "trace": {"modules": modules, "ops": ops}, "config": c, "decode_chunk": 8,
           "device_kind": "TPU v5 lite", "engine": {"slots": 128}, "metrics_before": _metrics(1),
           "metrics_after": _metrics(3),
           "window": [{"ok": True, "n_tokens": 100, "prompt_len": 350}]}
    readers = {os.path.basename(r.__file__): r for r in Manifest().layer_readers()}
    got = readers["decode_moe_scopes.py"].read(ctx)
    assert got["decode_moe_router_ms"] == 1.0 and got["decode_moe_experts_ms"] == 5.0 and got["decode_moe_shared_ms"] == 2.0
    # decode: 128 assignments and 16 experts hit a layer-step; 4 layers
    need = 4 * (16 * 100_663_296 + 128 * 2 * 4096 * 2)
    assert got["decode_moe_experts_hbm_share"] == pytest.approx(100 * need / 819e9 / 5e-3)
    # prefill: 800 held assignments a layer-step, 8 ms / 4 layers under moe_experts a layer-step
    mfu = readers["prefill_moe_experts_mfu.py"].read(ctx)["prefill_moe_experts_mfu"]
    assert mfu == pytest.approx(100 * 800 * 6 * 4096 * 4096 / 197e12 / 2e-3)
    assert 0 < mfu < 100
    assert readers["moe_tokens_per_expert.py"].read(ctx)["moe_tokens_per_expert"] == pytest.approx(
        (80 * 16 * 2 + 100 * 16 * 2) / 16 / 24)
    flat = [(n, s, d) for n, s, d, _ in modules]
    ctx["trace"] = {"modules": flat, "whole_modules": flat[1:-1], "ops": ops}
    share = readers["decode_hbm_share_moe.py"].read(ctx)["decode_hbm_share_moe"]
    want = moe_work.decode_step_bytes(c, live_tokens=96 * 400, lanes=96, experts_hit=16, assignments=128)
    assert share == pytest.approx(100 * want / 819e9 / (100e-3 / 8))
    # a program without the scopes and the counters (the parent; a dense family): nothing, and no raise
    bare = {**ctx, "metrics_before": "", "metrics_after": ""}
    monkeypatch.setattr(names, "load", lambda ctx: {"ops": [(p.replace("moe_", "x_"), s, d) for p, s, d in ops],
                                                    "modules": modules})
    for name in ("decode_moe_scopes.py", "prefill_moe_experts_mfu.py", "decode_hbm_share_moe.py",
                 "moe_tokens_per_expert.py"):
        assert readers[name].read(bare) == {}


def test_body_on_cpu_with_a_tiny_expert_configuration(tmp_path, capsys):
    """``run_cell`` end to end on the CPU: the family comes from the
    configuration's ``program`` group through ``build_app``, the reference by
    name, and the window's counters reach ``/metrics`` with their phases."""
    from benchmarks import run

    bench = tmp_path / "benchmarks"
    for d in ("configs", "traffic", "cells"):
        (bench / d).mkdir(parents=True)
    for d in ("layer_metrics", "references"):
        os.symlink(os.path.join(BENCH, d), bench / d)
    (bench / "configs" / "tiny-moe.json").write_text(json.dumps({**TINY, "program": _real()["program"]}))
    (bench / "traffic" / "tiny-closed.json").write_text(json.dumps({
        "loop": "closed", "clients": 6, "request_pool": 64, "endpoint": "/generate", "stream": False,
        "prompt_len": {"dist": "uniform", "min": 8, "max": 40}, "output_len": {"dist": "uniform", "min": 4, "max": 12},
        "ramp_s": 1, "shape_seed": 5, "client_timeout_s": 60, "drain_s": 30}))
    cell = "tiny-moe.tiny-closed"
    (bench / "cells" / f"{cell}.json").write_text(json.dumps({
        "name": cell, "config": "tiny-moe", "traffic": "tiny-closed", "chips": 1, "load": {}, "why": "rehearsal",
        "engine": {"kv_layout": "paged", "slots": 4, "max_len": 64, "page_size": 8, "prefill_buckets": [16, 32]}}))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for section in ("end_to_end", "per_layer"):
        for m in doc[section]:
            if "workloads" in m:
                m["workloads"] = [cell if any(w.endswith("deep-closed") for w in m["workloads"]) else "none"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    manifest = Manifest(str(bench), str(tmp_path / "BENCHMARK.json"))
    result = asyncio.run(run.run_cell(manifest, cell, seed=3_000_000_019, seconds=3.0, trace=False,
                                      workdir=str(tmp_path / "work")))
    notes = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith('{"note"')]
    checks = next(n for n in notes if n["note"] == "checks")
    assert result["correct"] is True, checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"out_tok_per_s", "setup_s"}
    assert checks["first_token_detail"]["sampled"] == 8 and checks["first_token_detail"]["miss"] == 0
    with open(tmp_path / "work" / "generator.json") as f:
        gen = json.load(f)
    ctx = {"metrics_before": gen["metrics_before"], "metrics_after": gen["metrics_after"]}
    for phase in ("prefill", "decode"):
        counted = moe_work.per_layer_step(ctx, phase)
        assert counted is not None and counted["assignments"] > 0 and 0 < counted["experts_hit"] <= 4, (phase, counted)
