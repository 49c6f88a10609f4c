"""``run.py``'s body rehearsed on the CPU at a tiny width: both loops, the
generator child included, against a tree of cell/config/traffic files written
to a temporary directory — which also shows that a new cell is files plus
``BENCHMARK.json`` entries and no edit to any ``.py``.

No time, rate or share from here is a measurement: the assertions are about
keys, counts and correctness only.

    python -m pytest benchmarks/tests -q      (by hand; tier-1 runs tests/ only)
"""

import asyncio
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

TINY = {
    "name": "tiny", "source": "none: a CPU rehearsal size", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "rope_theta": 10000.0, "max_position_embeddings": 128,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False, "torch_dtype": "float32",
    "weights_seed": 0, "reference": "rope_gqa_swiglu",
}
LENGTHS = {"prompt_len": {"dist": "uniform", "min": 8, "max": 32},
           "output_len": {"dist": "uniform", "min": 4, "max": 12},
           "ramp_s": 1, "shape_seed": 5, "client_timeout_s": 60, "drain_s": 30}
MIXES = {
    "tiny-open": {"loop": "open", "endpoint": "/generate/stream", "stream": True, **LENGTHS},
    "tiny-closed": {"loop": "closed", "clients": 6, "request_pool": 64,
                    "endpoint": "/generate", "stream": False, **LENGTHS},
}
ENGINE = {"kv_layout": "paged", "slots": 4, "max_len": 64, "page_size": 8, "prefill_buckets": [16, 32]}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A benchmark tree of its own: data files written here, the readers and
    the reference linked from the real one."""
    from benchmarks.harness.manifest import Manifest

    root = tmp_path_factory.mktemp("bench")
    bench = root / "benchmarks"
    for d in ("configs", "traffic", "cells"):
        (bench / d).mkdir(parents=True)
    for d in ("layer_metrics", "references"):
        os.symlink(os.path.join(BENCH, d), bench / d)
    with open(os.path.join(BENCH, "configs", "internlm2-1.8b.json")) as f:
        program = json.load(f)["program"]  # the real mapping onto the program's config
    (bench / "configs" / "tiny.json").write_text(json.dumps({**TINY, "program": program}))
    cells = {}
    for mix, spec in MIXES.items():
        (bench / "traffic" / f"{mix}.json").write_text(json.dumps(spec))
        cells[f"tiny.{mix}"] = {"name": f"tiny.{mix}", "config": "tiny", "traffic": mix, "chips": 1,
                                "engine": ENGINE, "load": {"rate_rps": 6.0} if spec["loop"] == "open" else {},
                                "why": "rehearsal"}
    for name, cell in cells.items():
        (bench / "cells" / f"{name}.json").write_text(json.dumps(cell))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for section in ("end_to_end", "per_layer"):  # the real metrics, listed for the tiny cells
        for m in doc[section]:
            if "workloads" in m:
                closed = any(w.endswith("backlog-closed") for w in m["workloads"])
                m["workloads"] = ["tiny.tiny-closed" if closed else "tiny.tiny-open"]
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return Manifest(str(bench), str(root / "BENCHMARK.json")), str(root / "work")


@pytest.mark.parametrize("mix", ["tiny-open", "tiny-closed"])
def test_body_on_cpu(tree, mix, capsys):
    from benchmarks import run

    manifest, work = tree
    result = asyncio.run(run.run_cell(manifest, f"tiny.{mix}", seed=3_000_000_019, seconds=3.0,
                                      trace=False, workdir=os.path.join(work, mix)))
    notes = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith('{"note"')]
    checks = next(n for n in notes if n["note"] == "checks")
    assert result["correct"] is True, checks
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["attempted"] > 0 and result["failed"] == 0
    # every end-to-end metric BENCHMARK.json lists for a cell of this loop, and no other
    assert set(result["metrics"]) == {m["name"] for m in manifest.metrics("end_to_end", f"tiny.{mix}")}
    assert len(result["metrics"]) >= 2 and "setup_s" in result["metrics"]
    assert all(set(m) == {"value", "unit"} for m in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"  # and so: not a measurement
    assert checks["first_token_detail"]["sampled"] == 8 and checks["first_token_detail"]["miss"] == 0
    assert checks["no_compile_in_window"] and checks["page_pool_consistent"]
    json.dumps(result)


def test_entry_refuses_a_non_tpu_platform_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "internlm2-1.8b.chat-open",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "There is no CPU mode" in proc.stderr
    assert '"correct"' not in proc.stdout
