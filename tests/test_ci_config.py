"""CI-config rehearsal (VERDICT r4 #9): a clean runner installs ONLY its
OWN job's pip lines, so every job that runs pytest must cover every
third-party module its collection can import — including the transitive
anchor (tests/conftest.py -> jaxpin -> jax, and gofr_tpu/__init__ ->
app -> aiohttp) that EVERY pytest job pays regardless of target files.
Checked PER JOB (a union across jobs would hide exactly the per-job gap
this exists to prevent). Grep/ast-generated so the pip lines can't drift
as imports are added.
"""

import pathlib
import sys

import pytest
import yaml

pytestmark = pytest.mark.quick

REPO = pathlib.Path(__file__).resolve().parents[1]

# import name -> pip distribution name, for the names that differ
DIST = {
    "jax": "jax", "flax": "flax", "optax": "optax", "chex": "chex",
    "einops": "einops", "numpy": "numpy", "aiohttp": "aiohttp",
    "httpx": "httpx", "pytest": "pytest", "transformers": "transformers",
    "orbax": "orbax-checkpoint", "grpc": "grpcio", "google": "protobuf",
    "kafka": "kafka-python", "paho": "paho-mqtt", "pymysql": "pymysql",
    "psycopg2": "psycopg2-binary", "yaml": "pyyaml",
    "cryptography": "cryptography",
}
IN_REPO = {"gofr_tpu", "jaxpin", "tests", "examples", "conftest"}

# imports that only exist inside function bodies but are REQUIRED at test
# runtime when the matching marker appears in the job's run lines (lazy
# imports the ast scan below skips): cryptography whenever the auth suite
# can run; kafka only when the job wires a real broker (the client import
# is env-gated behind REAL_KAFKA_BROKER)
RUNTIME_LAZY = (
    (lambda r: "test_auth_jwt" in r or " tests/ " in r or r.strip().endswith("tests/"),
     {"cryptography"}),
    (lambda r: "REAL_KAFKA_BROKER" in r, {"kafka"}),
)


def _top_level_imports(path: pathlib.Path) -> set:
    """Module-level (non-lazy) imports only: lazy client imports inside
    functions are config-gated and legitimately absent on a clean runner."""
    import ast

    out = set()
    try:
        tree = ast.parse(path.read_text(errors="ignore"))
    except SyntaxError:
        return out
    for node in tree.body:  # module level only — nested defs excluded
        if isinstance(node, ast.Import):
            out.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module and not node.level:
            out.add(node.module.split(".")[0])
    return out


def _repo_needed() -> set:
    """Every module a pytest collection can pull in transitively: any test
    file plus the whole package (conftest imports gofr_tpu before
    selection filters apply, and gofr_tpu/__init__ imports app/aiohttp)."""
    needed = set()
    for base in (REPO / "tests", REPO / "gofr_tpu"):
        for p in base.rglob("*.py"):
            needed.update(_top_level_imports(p))
    needed.update(_top_level_imports(REPO / "jaxpin.py"))
    needed -= set(sys.stdlib_module_names)
    needed -= IN_REPO
    return needed


# -- quick-tier marker coverage (VERDICT r5 #8) --------------------------------
#
# `-m quick` is the <2-minute smoke tier (docs/testing.md). Every test
# module must either carry at least one @pytest.mark.quick test or appear
# here with a reason — so a NEW test module cannot silently land in no
# tier. Grep-based on purpose (same philosophy as the pip-line check):
# the list can't drift from what's actually marked.
QUICK_EXEMPT = {
    # engine/model tiers: jit compiles dominate — minutes, not seconds
    "test_70b_scale.py", "test_engine.py", "test_engine_stress.py",
    "test_kv_quant.py", "test_matrix.py", "test_mesh_serving.py",
    "test_models.py", "test_moe.py", "test_ops.py", "test_paged.py",
    "test_pallas.py", "test_parallel.py", "test_pipeline.py",
    "test_prefix.py", "test_quant.py", "test_seq_parallel.py",
    "test_spec_decode.py", "test_tokenizer.py", "test_train.py",
    "test_tpu_device.py", "test_native.py",
    # multi-process spawns / real servers / whole-app integration
    "test_examples.py", "test_http_server.py", "test_lockstep.py",
    "test_multihost.py", "test_pubsub_clients.py", "test_real_brokers.py",
    "test_real_checkpoint.py", "test_serve_integration.py",
    "test_service_client.py", "test_datasource_plugins.py",
    # needs `cryptography`, absent from minimal local envs
    "test_auth_jwt.py",
}


def test_quick_tier_marker_coverage():
    tests_dir = REPO / "tests"
    modules = sorted(p.name for p in tests_dir.glob("test_*.py"))
    unmarked = [
        name for name in modules
        if name not in QUICK_EXEMPT
        and "mark.quick" not in (tests_dir / name).read_text(errors="ignore")
    ]
    assert not unmarked, (
        f"test modules in no tier: {unmarked} — add a @pytest.mark.quick "
        "test (or `pytestmark = pytest.mark.quick`) or list them in "
        "QUICK_EXEMPT with a reason"
    )
    stale = sorted(n for n in QUICK_EXEMPT if not (tests_dir / n).exists())
    assert not stale, f"QUICK_EXEMPT entries for deleted modules: {stale}"
    # the tier must stay meaningful: several modules actually in it
    marked = [n for n in modules if n not in QUICK_EXEMPT]
    assert len(marked) >= 5, f"quick tier shrank to {marked}"


def test_kernel_backend_suite_is_in_quick_tier():
    """ISSUE 6 satellite: the fused int8 paged-decode parity tests and the
    tests of the backend rule (tests/test_kernel_backend.py) must ride the
    `-m quick` CI job on every push — interpreter-mode parity and a rule on
    platform and op are CPU-safe by construction, so exemption would be a
    coverage hole."""
    path = REPO / "tests" / "test_kernel_backend.py"
    assert path.exists(), "tests/test_kernel_backend.py missing"
    text = path.read_text()
    assert "pytestmark = pytest.mark.quick" in text, (
        "test_kernel_backend.py must be quick-marked module-wide"
    )
    assert "test_kernel_backend.py" not in QUICK_EXEMPT, (
        "test_kernel_backend.py must not be exempted from the quick tier"
    )
    # both halves are present: kernel parity + the rule
    assert "paged_decode_q" in text and "resolve_backend" in text


def test_router_suite_is_in_quick_tier():
    """ISSUE 7 satellite: the router units — stable chain keys (subprocess
    PYTHONHASHSEED regression), ring, registry state machine, routing
    plans — are CPU-trivial and must ride the `-m quick` CI job; the
    multi-replica drills stay in the process tier (unmarked, tier-1)."""
    path = REPO / "tests" / "test_router.py"
    assert path.exists(), "tests/test_router.py missing"
    text = path.read_text()
    assert "pytest.mark.quick" in text, "router units must be quick-marked"
    assert "test_router.py" not in QUICK_EXEMPT, (
        "test_router.py must not be exempted from the quick tier"
    )
    # both halves are present: the stable-key regression and the drills
    assert "PYTHONHASHSEED" in text and "chain_key" in text
    assert "def test_two_replica" in text and "def test_replica_kill" in text


def test_slo_suite_is_in_quick_tier():
    """ISSUE 9 satellite: the SLO plane — window/burn arithmetic, the
    federation merge semantics (never average percentiles), the capture
    rate limit (fake clocks), and the two-replica federation drill — is
    pure bookkeeping over injectable clocks, CPU-trivial by construction,
    and must ride the `-m quick` CI job on every push."""
    path = REPO / "tests" / "test_slo.py"
    assert path.exists(), "tests/test_slo.py missing"
    text = path.read_text()
    assert "pytest.mark.quick" in text, "SLO units must be quick-marked"
    assert "test_slo.py" not in QUICK_EXEMPT, (
        "test_slo.py must not be exempted from the quick tier"
    )
    # the tentpole's three pieces are all covered: burn math + health,
    # router-side federation, and the rate-limited anomaly capture
    assert "burn" in text and "federation" in text
    assert "CaptureWatcher" in text and "def test_two_replica" in text


def test_resilience_suite_is_in_quick_tier():
    """ISSUE 10 satellite: the request-lifetime plane — deadline wire
    form + per-hop shrink, the Request future's constructed-deadline
    bound, retry-budget math (fake clock), Retry jitter/Retry-After/
    deadline interplay (stub transport), router deadline shed +
    budget-gated spill + hedged dispatch — is CPU-trivial by
    construction and must ride the `-m quick` CI job on every push;
    the paged-engine cancellation drills stay in tier-1 (unmarked)."""
    path = REPO / "tests" / "test_resilience.py"
    assert path.exists(), "tests/test_resilience.py missing"
    text = path.read_text()
    assert "pytest.mark.quick" in text, "resilience units must be quick-marked"
    assert "test_resilience.py" not in QUICK_EXEMPT, (
        "test_resilience.py must not be exempted from the quick tier"
    )
    # the tentpole's pieces are all covered: deadline propagation,
    # budgeted retries, hedging, and cooperative cancellation
    assert "RetryBudget" in text and "hedge" in text
    assert "assert_page_refs_consistent" in text
    assert "cancel_mid_decode" in text and "DEADLINE_HEADER" in text


def test_autoscaler_suite_is_in_quick_tier():
    """ISSUE 11 satellite: the elastic-fleet units — ScaleDecider
    hysteresis/cooldown/clamp on fake clocks, spawn-retry and drain-abort
    chaos handling, registry draining transitions, zero-drop requeue —
    are CPU-trivial and must ride the `-m quick` CI job on every push;
    the real-engine drain drills stay in tier-1 (unmarked)."""
    path = REPO / "tests" / "test_autoscaler.py"
    assert path.exists(), "tests/test_autoscaler.py missing"
    text = path.read_text()
    assert "pytest.mark.quick" in text, "autoscaler units must be quick-marked"
    assert "test_autoscaler.py" not in QUICK_EXEMPT, (
        "test_autoscaler.py must not be exempted from the quick tier"
    )
    # the tentpole's pieces are all covered: decision math, chaos drills,
    # draining membership, requeue, and the token-exact drain drill
    assert "ScaleDecider" in text and "autoscale.spawn" in text
    assert "replica.drain" in text and "draining" in text
    assert "requeue" in text and "assert_page_refs_consistent" in text


def test_ci_runs_the_diurnal_smoke():
    """ISSUE 11 satellite: CI must run the trace-driven diurnal harness
    (60s-compressed, autoscaler live) as an EXPLICIT CPU run and assert
    the elastic-vs-static verdict lands in extra.autoscale — otherwise
    the judging harness itself can rot between TPU bench rounds."""
    ci = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
    smoke_runs = [
        step.get("run", "")
        for job in ci["jobs"].values()
        for step in job.get("steps", [])
        if "GOFR_BENCH_DIURNAL=1" in step.get("run", "")
    ]
    assert smoke_runs, "ci.yml has no job running the GOFR_BENCH_DIURNAL smoke"
    joined = " ".join(smoke_runs)
    # explicit CPU label (the fail-loud guard rejects silent fallbacks)
    assert "GOFR_BENCH_PLATFORM=cpu" in joined
    assert "bench.py" in joined


def test_handoff_suite_is_in_quick_tier():
    """ISSUE 12 satellite: the disaggregated-serving suite — KV wire
    codec round trips, token-exact P→D handoff vs a colocated engine
    (bf16 AND int8 paged KV), the deadline-plane handoff shed, the
    chaos-severed zero-leak drill on both workers, and the router's
    stage-aware planning — runs on the CPU mesh in seconds and must ride
    the `-m quick` CI job on every push."""
    path = REPO / "tests" / "test_handoff.py"
    assert path.exists(), "tests/test_handoff.py missing"
    text = path.read_text()
    assert "pytestmark = pytest.mark.quick" in text, (
        "test_handoff.py must be quick-marked module-wide"
    )
    assert "test_handoff.py" not in QUICK_EXEMPT, (
        "test_handoff.py must not be exempted from the quick tier"
    )
    # the tentpole's acceptance pieces are all covered: token-exactness
    # on both KV dtypes, the deadline shed, the severed-transfer leak
    # check, and role-aware routing
    assert "token_exact_bf16" in text and "token_exact_int8" in text
    assert "kv.handoff" in text and "assert_page_refs_consistent" in text
    assert "deadline" in text and "stage" in text
    # ISSUE 18: the GOFR-HANDOFF2 streaming units ride the same quick
    # tier — chunk sequencing across streams, out-of-order reassembly,
    # the mid-stream deadline shed, the mixed-version (blob fallback)
    # pair, and the stream-granular chaos sever drills
    assert "ACK_OK_STREAM" in text, "v2 negotiation units missing"
    assert "test_out_of_order_multistream_reassembly" in text
    assert "test_deadline_expiry_mid_stream_sheds_504" in text
    assert "test_mixed_version_pair_token_exact" in text
    assert "kv.handoff.chunk" in text and "kv.handoff.midchunk" in text
    assert "kv.handoff.hello" in text


def test_ci_runs_the_disagg_smoke():
    """ISSUE 12 satellite: CI must run the prefill/decode A/B as an
    EXPLICIT CPU run and assert both arms archive TTFT/TPOT percentiles
    plus the role-split arm's handoff transfer stats in extra.disagg —
    otherwise the disaggregation harness can rot between TPU rounds."""
    ci = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
    smoke_runs = [
        step.get("run", "")
        for job in ci["jobs"].values()
        for step in job.get("steps", [])
        if "GOFR_BENCH_DISAGG=1" in step.get("run", "")
    ]
    assert smoke_runs, "ci.yml has no job running the GOFR_BENCH_DISAGG smoke"
    joined = " ".join(smoke_runs)
    assert "GOFR_BENCH_PLATFORM=cpu" in joined
    assert "bench.py" in joined
    # the verdict step must actually check the archived structure
    checks = " ".join(
        step.get("run", "")
        for job in ci["jobs"].values()
        for step in job.get("steps", [])
        if "disagg" in step.get("run", ""))
    assert "tpot" in checks and "handoff" in checks and "token_exact" in checks


def test_ci_runs_the_handoff_stream_smoke():
    """ISSUE 18 satellite: CI must run the blob-vs-streaming handoff A/B
    as an explicit CPU run and assert the tentpole perf claim from the
    archive — the streaming arm's decode-side TTFT slope strictly below
    the blob arm's, its longest/shortest flatness ratio bounded, a
    nonzero overlap ratio, and token-exact serving."""
    ci = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
    smoke_runs = [
        step.get("run", "")
        for job in ci["jobs"].values()
        for step in job.get("steps", [])
        if "GOFR_BENCH_HANDOFF_STREAM=1" in step.get("run", "")
    ]
    assert smoke_runs, (
        "ci.yml has no job running the GOFR_BENCH_HANDOFF_STREAM smoke")
    joined = " ".join(smoke_runs)
    assert "GOFR_BENCH_PLATFORM=cpu" in joined
    assert "bench.py" in joined
    # the verdict step must assert the flattening, not just presence
    checks = " ".join(
        step.get("run", "")
        for job in ci["jobs"].values()
        for step in job.get("steps", [])
        if "handoff_stream" in step.get("run", ""))
    assert "slope_s_per_page" in checks and "flatness_p50" in checks
    assert "overlap_ratio" in checks and "token_exact" in checks


def test_kv_int4_suite_is_in_quick_tier():
    """ISSUE 13 satellite: the packed-int4 KV suite — nibble pack/unpack
    round trips, pool write/append/gather, fused-kernel vs gathered-XLA
    parity under the interpreter, and int4 engine plausibility — runs on
    CPU in seconds and must ride the `-m quick` CI job on every push."""
    path = REPO / "tests" / "test_kv_int4.py"
    assert path.exists(), "tests/test_kv_int4.py missing"
    text = path.read_text()
    assert "pytestmark = pytest.mark.quick" in text, (
        "test_kv_int4.py must be quick-marked module-wide"
    )
    assert "test_kv_int4.py" not in QUICK_EXEMPT, (
        "test_kv_int4.py must not be exempted from the quick tier"
    )
    # the tentpole's acceptance pieces: lossless packing, kernel parity
    # against the gather reference, the ENGINE_KV_DTYPE config plane, and
    # clean page accounting on the int4 engine
    assert "pack_int4" in text and "unpack_int4" in text
    assert "paged_decode_attention_q4" in text and 'backend="xla"' in text
    assert "ENGINE_KV_DTYPE" in text
    assert "assert_paged_pool_consistent" in text


def test_spec_pipeline_suite_is_in_quick_tier():
    """ISSUE 13 satellite: the spec-in-the-pipeline suite — the queue-spy
    proof that paged spec rounds dispatch while older entries are still in
    flight, the depth-1 synchronous escape hatch, and the over-claim/trim
    page-lifecycle drills (cancel mid-round, tight-pool preemption) — is
    CPU-fast and must ride the `-m quick` CI job."""
    path = REPO / "tests" / "test_spec_pipeline.py"
    assert path.exists(), "tests/test_spec_pipeline.py missing"
    text = path.read_text()
    assert "pytestmark = pytest.mark.quick" in text, (
        "test_spec_pipeline.py must be quick-marked module-wide"
    )
    assert "test_spec_pipeline.py" not in QUICK_EXEMPT, (
        "test_spec_pipeline.py must not be exempted from the quick tier"
    )
    assert "_dq" in text and "spec" in text
    assert "cancel" in text and "assert_paged_pool_consistent" in text


def test_ci_runs_the_kvdtype_smoke():
    """ISSUE 13 satellite: CI must run the bf16/int8/int4 paged-pool A/B
    as an EXPLICIT CPU run and assert the archive carries all three arms
    with strictly decreasing pool bytes per decode token plus the
    token_exact/parity correctness fields — otherwise the decode-bandwidth
    harness can rot between TPU rounds."""
    ci = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
    smoke_runs = [
        step.get("run", "")
        for job in ci["jobs"].values()
        for step in job.get("steps", [])
        if "GOFR_BENCH_KVDTYPE=1" in step.get("run", "")
    ]
    assert smoke_runs, "ci.yml has no job running the GOFR_BENCH_KVDTYPE smoke"
    joined = " ".join(smoke_runs)
    assert "GOFR_BENCH_PLATFORM=cpu" in joined
    assert "bench.py" in joined
    # the verdict step must actually check the archived structure
    checks = " ".join(
        step.get("run", "")
        for job in ci["jobs"].values()
        for step in job.get("steps", [])
        if "kvdtype" in step.get("run", ""))
    assert "kv_bytes_per_decode_token" in checks
    assert "token_exact" in checks and "parity" in checks
    for arm in ("bf16", "int8", "int4"):
        assert arm in checks, f"verdict step never mentions the {arm} arm"


def test_perf_plane_suite_is_in_quick_tier():
    """ISSUE 14 satellite: the live-perf-plane suite — cost model vs
    hand-computed FLOPs/bytes for every step kind and all three KV dtype
    planes, fake-clock bubble accounting, GOFR_DEVICE_PEAKS resolution,
    sum-of-parts federation merges, and the capture/debug surfaces — is
    CPU-fast and must ride the `-m quick` CI job on every push."""
    path = REPO / "tests" / "test_perf_plane.py"
    assert path.exists(), "tests/test_perf_plane.py missing"
    text = path.read_text()
    assert "pytestmark = pytest.mark.quick" in text, (
        "test_perf_plane.py must be quick-marked module-wide"
    )
    assert "test_perf_plane.py" not in QUICK_EXEMPT, (
        "test_perf_plane.py must not be exempted from the quick tier"
    )
    # the tentpole's acceptance pieces: per-dtype plane widths, bubble
    # semantics, peak overrides, exact merges, and the joined surfaces
    assert "kv_plane_bytes_per_position" in text
    assert "mark_no_work" in text and "GOFR_DEVICE_PEAKS" in text
    assert "merge_totals" in text and "aggregate_perf" in text
    assert "_debug_perf_handler" in text and "CaptureWatcher" in text
    assert "app_tpu_mbu" in text


def test_ci_runs_the_perf_smoke():
    """ISSUE 14 satellite: CI must run a short CPU-labelled bench and
    assert the archive carries the per-kind roofline breakdown
    (extra.perf) AND that the headline mbu_decode_lb matches a bit-for-bit
    recomputation from the shared estimator — the one-estimator contract
    between bench and the live serving plane cannot rot silently."""
    ci = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
    job = ci["jobs"].get("bench-perf-smoke")
    assert job, "ci.yml has no bench-perf-smoke job"
    runs = " ".join(step.get("run", "") for step in job.get("steps", []))
    assert "GOFR_BENCH_PLATFORM=cpu" in runs
    assert "bench.py" in runs
    # the verdict step recomputes through the SHARED module and checks
    # the structure the round archives ride on
    assert "perf.mbu_decode_lb" in runs
    assert "mbu_decode_lb_params" in runs
    assert "peaks_nominal" in runs
    for kind in ("prefill", "decode"):
        assert kind in runs, f"verdict step never checks the {kind} kind"


def test_quality_suite_is_in_quick_tier():
    """ISSUE 17 satellite: the quality-plane suite — divergence-report
    math, teacher-forced determinism, the shadow-off/on token-identity
    contract on both KV layouts with spec on/off, metric label routing,
    sum-never-average federation, the chaos → burn → bundle → replay
    round trip, and the preemption/page-refs drill — is CPU-fast and must
    ride the `-m quick` CI job on every push."""
    path = REPO / "tests" / "test_quality.py"
    assert path.exists(), "tests/test_quality.py missing"
    text = path.read_text()
    assert "pytestmark = pytest.mark.quick" in text, (
        "test_quality.py must be quick-marked module-wide"
    )
    assert "test_quality.py" not in QUICK_EXEMPT, (
        "test_quality.py must not be exempted from the quick tier"
    )
    # the tentpole's acceptance pieces: deterministic scoring, the
    # off-is-free contract, the full anomaly loop, and pool hygiene
    assert "teacher_forced_rows" in text and "divergence_report" in text
    assert "quality_shadow_rate" in text and "_quality is None" in text
    assert "quality.corrupt" in text and "replay_bundle" in text
    assert "observe_quality" in text and "DIGEST_COUNTERS" in text
    assert "assert_page_refs_consistent" in text
    assert "app_tpu_spec_accept_ratio" in text


def test_ci_runs_the_quality_smoke():
    """ISSUE 17 satellite: CI must run the quality drill as an EXPLICIT
    CPU run and assert BOTH verdicts — clean arms at every KV dtype close
    breach-free, and the chaos-corrupted arm burns, bundles, and replays
    offline — otherwise the divergence harness can rot between TPU
    rounds."""
    ci = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
    job = ci["jobs"].get("bench-quality-smoke")
    assert job, "ci.yml has no bench-quality-smoke job"
    runs = " ".join(step.get("run", "") for step in job.get("steps", []))
    assert "GOFR_BENCH_PLATFORM=cpu" in runs
    assert "GOFR_BENCH_QUALITY=1" in runs
    assert "bench.py" in runs
    # the verdict step must check both halves of the drill
    assert "top1_agree_mean" in runs and "quality_breaches" in runs
    assert "replay_reproduced" in runs and "bundle" in runs
    for arm in ("bf16", "int8", "int4", "corrupt_int8"):
        assert arm in runs, f"verdict step never mentions the {arm} arm"


def test_tp_paged_suite_is_in_quick_tier():
    """ISSUE 19 satellite: the tensor-parallel paged-pool suite — token
    exactness sharded-vs-single-device on all three KV dtypes, spec rounds
    + preemption + host-tier swap-in on the sharded pool, per-device byte
    accounting, the sharding-preserved-after-serving check, and the
    ENGINE_KV_SHARD resolution gates — runs on the conftest-forced 8-CPU-
    device mesh and must ride the `-m quick` CI job on every push."""
    path = REPO / "tests" / "test_tp_paged.py"
    assert path.exists(), "tests/test_tp_paged.py missing"
    text = path.read_text()
    assert "pytestmark = pytest.mark.quick" in text, (
        "test_tp_paged.py must be quick-marked module-wide"
    )
    assert "test_tp_paged.py" not in QUICK_EXEMPT, (
        "test_tp_paged.py must not be exempted from the quick tier"
    )
    # the tentpole's acceptance pieces: exactness on every dtype, the
    # hard serving paths on the sharded pool, and honest accounting
    assert "int8" in text and "int4" in text
    assert "ENGINE_KV_SHARD" in text and "kv_shards" in text
    assert "spec_tokens" in text and "app_tpu_preemptions" in text
    assert "prefix_host_mb" in text and "swapin" in text
    assert "kv_plane_bytes_per_position" in text
    assert "pool_bytes_device" in text and "addressable_shards" in text
    assert "assert_page_refs_consistent" in text


def test_ci_runs_the_tp_smoke():
    """ISSUE 19 judge: CI must run the replicated-vs-sharded pool A/B on a
    forced 8-device host mesh and assert ALL THREE verdicts — token
    exactness on both arms, per-device pool bytes ≈ 1/tp, and strictly
    more pool pages at equal per-device HBM budget — otherwise the
    capacity claim can rot between TPU rounds."""
    ci = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
    job = ci["jobs"].get("bench-tp-smoke")
    assert job, "ci.yml has no bench-tp-smoke job"
    runs = " ".join(step.get("run", "") for step in job.get("steps", []))
    assert "GOFR_BENCH_PLATFORM=cpu" in runs
    assert "GOFR_BENCH_TP=1" in runs
    assert "xla_force_host_platform_device_count=8" in runs
    assert "bench.py" in runs
    # the verdict step must check all three halves of the claim
    assert "token_exact" in runs
    assert "device_bytes_shrink_ok" in runs
    assert "sharded_gt" in runs
    for arm in ("replicated", "sharded"):
        assert arm in runs, f"verdict step never mentions the {arm} arm"


def test_ci_has_py310_compat_gate():
    """A py3.10 interpreter must compile the whole tree in CI: 3.12-only
    syntax (same-quote nested f-strings) passes every 3.12 job silently and
    then breaks collection for anyone on the oldest supported interpreter
    (PR 1 lost most of the suite to exactly this)."""
    ci = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
    gates = [
        name for name, job in ci["jobs"].items()
        if any("compileall" in step.get("run", "") for step in job.get("steps", []))
        and any(str(step.get("with", {}).get("python-version", "")) == "3.10"
                for step in job.get("steps", []))
    ]
    assert gates, (
        "ci.yml has no job compiling the tree under python 3.10 "
        "(compileall on a setup-python 3.10 runner)"
    )
    # the gate must cover the package AND the test tree — a 3.12-only
    # f-string in tests/ is how the original regression landed
    for name in gates:
        runs = " ".join(s.get("run", "") for s in ci["jobs"][name]["steps"])
        assert "gofr_tpu" in runs and "tests" in runs


def test_ci_builds_the_serving_image():
    """The root Dockerfile (serving runtime; libtpu/jaxlib pinning docs live
    in its header) must exist and be built by a CI job — image breakage is
    deploy breakage and no pytest tier would catch it."""
    dockerfile = REPO / "Dockerfile"
    assert dockerfile.exists(), "root Dockerfile missing"
    text = dockerfile.read_text()
    # the pinning contract the satellite documents: jax version + libtpu
    # release index as build args, never floating installs
    assert "JAX_VERSION" in text and "libtpu" in text.lower()
    ci = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
    builds = [
        name for name, job in ci["jobs"].items()
        if any("docker build" in step.get("run", "") for step in job.get("steps", []))
    ]
    assert builds, "ci.yml has no job running `docker build` on the root Dockerfile"


def test_ci_runs_the_quick_tier():
    ci = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
    quick_runs = [
        step.get("run", "")
        for job in ci["jobs"].values()
        for step in job.get("steps", [])
        if "pytest" in step.get("run", "") and "-m quick" in step.get("run", "")
    ]
    assert quick_runs, "ci.yml has no job running `pytest -m quick`"


def test_every_pytest_job_installs_what_collection_imports():
    ci = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
    base_needed = _repo_needed()
    checked = 0
    for job_name, job in ci["jobs"].items():
        runs = [step.get("run", "") for step in job.get("steps", [])]
        if not any("pytest" in r for r in runs):
            continue
        checked += 1
        installed = set()
        for r in runs:
            if "pip install" in r:
                installed.update(r.replace("pip install", "").split())
        needed = set(base_needed)
        for r in runs:
            if "pytest" not in r:
                continue
            for match, extra in RUNTIME_LAZY:
                if match(r):
                    needed.update(extra)
        missing = sorted(m for m in needed if DIST.get(m, m) not in installed)
        assert not missing, (
            f"CI job {job_name!r} runs pytest but its pip lines lack "
            f"{missing} (map import->dist in tests/test_ci_config.py DIST)"
        )
    assert checked >= 3, f"expected >=3 pytest jobs in ci.yml, found {checked}"


def test_adapter_suite_is_in_quick_tier():
    """PR 16 satellite: the multi-LoRA multiplexing suite — registry/pool
    units, adapter_id=None token-exactness on both KV layouts with spec
    on and off, the mixed-adapter-batch-vs-isolation drill, per-adapter
    perf attribution, the zero-drop live hot-swap drill, and the
    adapter-cache eviction consistency check — runs on the CPU mesh and
    must ride the `-m quick` CI job on every push."""
    path = REPO / "tests" / "test_adapters.py"
    assert path.exists(), "tests/test_adapters.py missing"
    text = path.read_text()
    assert "pytestmark = pytest.mark.quick" in text, (
        "test_adapters.py must be quick-marked module-wide"
    )
    assert "test_adapters.py" not in QUICK_EXEMPT, (
        "test_adapters.py must not be exempted from the quick tier"
    )
    # the tentpole's acceptance pieces are all covered: base-lane
    # exactness, mixed-batch isolation equivalence, the hot-swap drill,
    # and the eviction-vs-page-pool consistency check
    assert "token_exact" in text and "isolation" in text
    assert "adopt_weights" in text and "zero_drop" in text
    assert "assert_page_refs_consistent" in text
    assert "epoch_of" in text  # the router-gossip epoch bump is asserted


def test_control_suite_is_in_quick_tier():
    """ISSUE 20 satellite: the online-controller suite — the extracted
    HysteresisGate units plus the ScaleDecider-delegates proof, the
    StepController trial loop on fake clocks (commit/revert/backoff,
    oscillation freeze, stand-down, starved-window accumulation, pin
    persistence + resume), the engine knob seams (boot-envelope clamps,
    per-g spec handle swap), the mid-stream token-exactness drill, and
    the metric-registration lint — is CPU-fast by construction and must
    ride the `-m quick` CI job on every push."""
    path = REPO / "tests" / "test_control.py"
    assert path.exists(), "tests/test_control.py missing"
    text = path.read_text()
    assert "pytestmark = pytest.mark.quick" in text, (
        "test_control.py must be quick-marked module-wide"
    )
    assert "test_control.py" not in QUICK_EXEMPT, (
        "test_control.py must not be exempted from the quick tier"
    )
    # the tentpole's acceptance pieces are all covered: the shared damping
    # core, the bounded trial loop with every failure edge, the safe-seam
    # actuation contract, and the never-change-tokens invariant
    assert "HysteresisGate" in text and "ScaleDecider" in text
    assert "oscillat" in text and "standdown" in text
    assert "no-evidence" in text and "resume" in text
    assert "request_knobs" in text and "_apply_pending_knobs" in text
    assert "token_exact" in text and "band_totals" in text
    assert "never_registered" in text or "is_registered" in text


def test_ci_runs_the_controller_smoke():
    """ISSUE 20 judge: CI must run the controller-vs-static A/B as an
    EXPLICIT CPU run and assert the closed-loop verdicts from the archive
    — the controller arm starting from a pessimal knob vector meets the
    best static arm within tolerance, its decision ring is non-empty, and
    serving stays token-exact across every arm AND with the controller
    off — otherwise the actuation harness can rot between TPU rounds."""
    ci = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
    job = ci["jobs"].get("bench-controller-smoke")
    assert job, "ci.yml has no bench-controller-smoke job"
    runs = " ".join(step.get("run", "") for step in job.get("steps", []))
    assert "GOFR_BENCH_PLATFORM=cpu" in runs
    assert "GOFR_BENCH_CONTROLLER=1" in runs
    assert "bench.py" in runs
    # the verdict step must check every half of the closed-loop claim
    assert "meets_statics" in runs
    assert "token_exact" in runs and "control_off_token_exact" in runs
    assert "decisions" in runs
    assert "bubble_ratio" in runs
