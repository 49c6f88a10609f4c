"""``BENCHMARK.json`` against the files: every name resolves, every name and
unit is made of the allowed characters, the cell files say what the manifest
says, and no ``.py`` names a cell, a configuration or a mix."""

import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmarks.harness import peaks  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def test_keys_names_units(manifest):
    doc = manifest.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["benchmarks"] and 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(manifest.path) <= 64 * 1024
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in doc[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in doc[k]}) == len(doc[k])
    metrics = doc["end_to_end"] + doc["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") and m["source"] in SOURCES, m
    for m in doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1, m
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in doc["end_to_end"])
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and len(w["why"]) <= 200
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and len(c["why"]) <= 200


def test_every_name_resolves_to_a_file_and_the_files_agree(manifest):
    doc = manifest.doc
    configs = {c["name"]: c for c in doc["configs"]}
    used = set()
    for w in doc["workloads"]:
        cell = manifest.cell(w["name"])
        for key in ("name", "config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        entry = configs[w["config"]]
        used.add(w["config"])
        assert entry["file"] == f"benchmarks/configs/{w['config']}.json"
        spec = cell["config_spec"]
        assert spec["source"] == entry["source"] and spec["reduced"] == entry["reduced"]
        assert hasattr(manifest.reference(spec), "last_logits")
        # the traffic fits the engine the cell builds
        t, e = cell["traffic_spec"], cell["engine"]
        assert t["prompt_len"]["max"] <= max(e["prefill_buckets"])
        assert t["prompt_len"]["max"] + t["output_len"]["max"] <= e["max_len"]
        assert ("rate_rps" in cell["load"]) == (t["loop"] == "open")
    assert used == set(configs)  # every configuration is used by some cell


def test_metrics_cover_every_cell_and_every_layer_metric_has_a_reader(manifest):
    doc = manifest.doc
    declared = {name for r in manifest.layer_readers() for name in r.NAMES}
    assert {m["name"] for m in doc["per_layer"]} <= declared
    for w in doc["workloads"]:
        e2e = {m["name"] for m in manifest.metrics("end_to_end", w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = manifest.metrics("per_layer", w["name"])
        assert layer
        assert all(m["moves"] in e2e for m in layer), w["name"]


def test_widths_are_the_published_ones():
    with open(os.path.join(BENCH, "configs", "internlm2-1.8b.json")) as f:
        intern = json.load(f)
    with open(os.path.join(BENCH, "configs", "mistral-7b-v0.3-l16.json")) as f:
        mistral = json.load(f)
    assert peaks.param_count(intern) == 1_889_110_016 and peaks.kv_bytes_per_token(intern) == 98_304
    assert peaks.param_count(mistral) == 3_758_231_552 and peaks.kv_bytes_per_token(mistral) == 65_536
    assert peaks.param_count({**mistral, **mistral["reduced_from"]}) == 7_248_023_552
    with pytest.raises(KeyError):
        peaks.peaks("some other chip")
    # one decode step of 64 lanes at 320 live tokens each: weights (less the
    # embedding table, plus 64 gathered rows) + (64*320 + 64) KV rows
    want = (1_889_110_016 - 92_544 * 2048) * 2 + 64 * 2048 * 2 + (64 * 320 + 64) * 98_304
    assert peaks.decode_step_bytes(intern, live_tokens=64 * 320, lanes=64) == want


def test_no_python_file_names_a_cell_a_config_or_a_mix(manifest):
    """The data-driven rule: adding one of them never needs an edit to a .py."""
    words = {x["name"] for k in ("configs", "workloads") for x in manifest.doc[k]}
    words |= {w["traffic"] for w in manifest.doc["workloads"]}
    for folder, _, files in os.walk(BENCH):
        if os.path.basename(folder) in ("tests", "__pycache__"):
            continue
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(folder, f)) as fh:
                    text = fh.read()
                assert not [w for w in words if w in text], (f, [w for w in words if w in text])
