"""Helpers for booting the example app and checking what it served — COPIES of
``chip_smoke.py``'s (``_CompileCounter``, ``_free_port``, ``_load_example_app``,
``_metric``, ``_near_tie_tol``, ``_check_first_tokens``), kept here so a later
change to that script cannot move the yardstick. Plus the reading of
Prometheus histograms as a delta over the window."""

from __future__ import annotations

import importlib.util
import os
import socket

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class CompileCounter:
    """Counts what JAX itself reports: compile requests (each one an
    executable built, or fetched from the persistent cache — either way a
    program nobody had ready) and how many of them the cache answered."""

    def __init__(self):
        import jax

        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += seconds

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def load_example_app():
    spec = importlib.util.spec_from_file_location(
        "serving_llm_example", os.path.join(REPO, "examples", "serving-llm", "main.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.build_app


def metric(text: str, name: str, **labels: str) -> float:
    """Sum of the series of ``name`` in Prometheus text whose labels include
    ``labels`` (absent = 0)."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in (" ", "{"):
            if all(f'{k}="{v}"' in line for k, v in labels.items()):
                total += float(line.rsplit(" ", 1)[1])
    return total


def histogram_mean_delta(before: str, after: str, name: str, **labels: str) -> float | None:
    """Δsum / Δcount of a Prometheus histogram between two scrapes: the mean
    of what was observed in between. None when nothing was."""
    count = metric(after, name + "_count", **labels) - metric(before, name + "_count", **labels)
    if count <= 0:
        return None
    return (metric(after, name + "_sum", **labels) - metric(before, name + "_sum", **labels)) / count


def near_tie_tol(eps: float, logits: np.ndarray, ulps: float) -> float:
    """How far below the reference's best logit a served token may sit and
    still count as the same answer: ``ulps`` units in the last place of the
    served dtype (``eps``) at the logits' magnitude. On a random-weight model
    the top two logits are often that close."""
    return ulps * eps * float(np.max(np.abs(logits)))


def check_first_tokens(firsts: list[int], ref_logits: np.ndarray, tol: float) -> dict:
    """Each served first token against the reference's last-position logits.
    → counts of exact matches, near-ties inside ``tol``, and misses, and the
    largest deficit seen."""
    exact = near = miss = 0
    worst = 0.0
    for tok, row in zip(firsts, ref_logits):
        best = int(np.argmax(row))
        deficit = float(row[best] - row[tok])
        worst = max(worst, deficit)
        if tok == best:
            exact += 1
        elif deficit <= tol:
            near += 1
        else:
            miss += 1
    return {"exact": exact, "near_tie": near, "miss": miss, "worst_deficit": worst, "tol": tol}
