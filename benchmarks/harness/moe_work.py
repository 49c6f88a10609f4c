"""The least work an expert layer's step needs, from the configuration file's
keys alone (HF names), and the reading of the program's expert scopes and
counters. Everything counts the LEAST the work needs — each weight once, each
row once — so no share built on it can read over 100%.

A configuration of this kind holds a SHARE: ``num_experts`` is the experts
held here, ``router_num_experts`` the router's width, ``intermediate_size``
one expert's width (routed and shared alike), the embedding is tied.

COPIES of ``gofr_tpu.tracing.MOE_SCOPES`` and of the counters' names: the
yardstick keeps its own spelling, so renaming one in the program silences the
metric instead of moving it (a test compares the lists)."""

from __future__ import annotations

from benchmarks.harness import names, peaks, serving

MOE_SCOPES = ("moe_router", "moe_experts", "moe_shared")
ASSIGNMENTS = "app_tpu_moe_assignments_total"
ABSENT = "app_tpu_moe_assignments_absent_total"
EXPERTS_HIT = "app_tpu_moe_experts_hit_total"
LAYER_STEPS = "app_tpu_moe_layer_steps_total"

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def item(c: dict) -> int:
    return _ITEM[c["torch_dtype"]]


def expert_bytes(c: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * c["hidden_size"] * c["intermediate_size"] * item(c)


def assignment_flops(c: dict) -> int:
    """One token through one expert: three products, 2 FLOP a weight."""
    return 6 * c["hidden_size"] * c["intermediate_size"]


def experts_layer_bytes(c: dict, experts_hit: float, assignments: float) -> float:
    """Bytes the grouped product of ONE layer-step must move: each expert that
    was hit read once, and each assignment's input row read and output row
    written once."""
    return experts_hit * expert_bytes(c) + assignments * 2 * c["hidden_size"] * item(c)


def always_layer_bytes(c: dict) -> int:
    """The weights of one layer every step reads whatever the routing:
    attention, the shared experts, the router (float32), the norm."""
    e, d = c["hidden_size"], c["head_dim"]
    attention = 2 * e * c["num_attention_heads"] * d + 2 * e * c["num_key_value_heads"] * d
    shared = 3 * e * c["intermediate_size"] * c["num_shared_experts"]
    return (attention + shared + e) * item(c) + e * c["router_num_experts"] * 4


def decode_step_bytes(c: dict, live_tokens: float, lanes: float, experts_hit: float,
                      assignments: float) -> float:
    """Bytes ONE decode step must move: per layer what every step reads and the
    experts hit with their rows; the head's slice of the (tied) embedding once
    and ``lanes`` gathered rows of it; the final norm; the live KV of every
    lane read once and one new row a lane written."""
    e = c["hidden_size"]
    layers = c["num_hidden_layers"] * (always_layer_bytes(c) + experts_layer_bytes(c, experts_hit, assignments))
    head = (c["vocab_size"] * e + lanes * e + e) * item(c)
    return layers + head + (live_tokens + lanes) * peaks.kv_bytes_per_token(c)


def innermost_moe_scope(path: str) -> str | None:
    for part in reversed(path.rstrip(":").split("/")):
        if part in MOE_SCOPES:
            return part
    return None


def moe_scope_sums(ops: list, modules: list, pattern: str) -> tuple[dict, int]:
    """Leaf-operation time inside the whole runs of the programs matching
    ``pattern``, by innermost expert scope → ``({scope: ns}, runs)``; an
    operation under none of them is not counted."""
    runs = names.whole_modules(modules, pattern)
    spans = [(start, start + dur) for _, start, dur, _ in runs]
    sums = dict.fromkeys(MOE_SCOPES, 0)
    j = 0
    for path, start, dur in names.leaf_ops(ops):  # ordered by start, as the spans are
        while j < len(spans) and spans[j][1] <= start:
            j += 1
        scope = innermost_moe_scope(path)
        if scope and j < len(spans) and spans[j][0] <= start and start + dur <= spans[j][1]:
            sums[scope] += dur
    return sums, len(runs)


def counter_delta(ctx: dict, name: str, **labels: str) -> float:
    return (serving.metric(ctx["metrics_after"], name, **labels)
            - serving.metric(ctx["metrics_before"], name, **labels))


def per_layer_step(ctx: dict, phase: str) -> dict | None:
    """What the window's counters say one expert layer-step of ``phase``
    (``prefill`` | ``decode``) did, on average: held assignments, experts hit.
    None where the program has no such counters (or counted nothing)."""
    steps = counter_delta(ctx, LAYER_STEPS, phase=phase)
    if steps <= 0:
        return None
    return {"assignments": counter_delta(ctx, ASSIGNMENTS, phase=phase) / steps,
            "experts_hit": counter_delta(ctx, EXPERTS_HIT, phase=phase) / steps,
            "layer_steps": steps}


def lanes_and_context(ctx: dict) -> tuple[float, float] | None:
    """Decode lanes over the window (occupancy x slots) and a lane's mean live
    context, as ``layer_metrics/decode_hbm_share`` reckons them."""
    occ = serving.histogram_mean_delta(
        ctx["metrics_before"], ctx["metrics_after"], "app_tpu_batch_occupancy", kind="decode")
    done = [r for r in ctx["window"] if r["ok"]]
    if occ is None or not done:
        return None
    context = (sum(r["n_tokens"] * (r["prompt_len"] + r["n_tokens"] / 2) for r in done)
               / sum(r["n_tokens"] for r in done))
    return occ * ctx["engine"]["slots"], context
