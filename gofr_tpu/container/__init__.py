"""Container: the dependency-injection hub (gofr `pkg/gofr/container/container.go`).

One Container per App. It materializes every infrastructure dependency from
config at boot — logger (with remote level polling), metrics registry, tracer,
datasources, inter-service HTTP clients — and exposes them through narrow
attributes. Everything is config-gated: an unset host/backend means the feature
is simply not wired (`container.go:91-122` semantics).

TPU-first: the device mesh is itself a datasource (``container.tpu``), exactly
parallel to how the reference wraps a Redis pool — created lazily, health-checked,
surfaced in metrics.
"""

from __future__ import annotations

import threading
from typing import Any

from gofr_tpu.config import DictConfig
from gofr_tpu.logging import Level, Logger, MockLogger, new_logger
from gofr_tpu.metrics import Registry, sample_runtime_metrics
from gofr_tpu.metrics.flight import FlightRecorder
from gofr_tpu.tracing import Tracer, tracer_from_config
from gofr_tpu import version


class Container:
    def __init__(self, config, logger: Logger | None = None):
        self.config = config
        self.app_name = config.get_or_default("APP_NAME", "gofr-tpu-app")
        self.app_version = config.get_or_default("APP_VERSION", "dev")

        self.logger: Logger = logger or new_logger(config.get_or_default("LOG_LEVEL", "INFO"))
        self.metrics: Registry = Registry(logger=self.logger)
        self.tracer: Tracer = Tracer()
        # always-on ring of recent request timelines + engine steps
        # (docs/observability.md; served at /debug/requests, /debug/engine)
        self.flight = FlightRecorder(
            max_requests=config.get_int("FLIGHT_REQUESTS", 256),
            max_steps=config.get_int("FLIGHT_STEPS", 512),
        )

        # datasource slots (None = not wired; config decides)
        self.sql = None
        self.redis = None
        self.mongo = None
        self.cassandra = None
        self.clickhouse = None
        self.kv = None
        self.file = None
        self.pubsub = None
        self._tpu = None
        self._tpu_lock = threading.Lock()
        self.services: dict[str, Any] = {}
        self._engines: dict[str, Any] = {}
        self.qos = None  # AdmissionController once App.enable_qos runs
        self.slo = None  # SLOEngine once _maybe_slo runs (SLO_ENABLED)
        self.slo_capture = None  # CaptureWatcher once SLO_CAPTURE opts in
        self._remote_level_poller = None
        self._pubsub_hdr_support: tuple[Any, bool] | None = None  # per-broker probe cache

    # -- boot ------------------------------------------------------------------

    @classmethod
    def create(cls, config) -> "Container":
        c = cls(config)
        c._register_framework_metrics()
        c.metrics.add_collect_hook(sample_runtime_metrics)
        c.metrics.add_collect_hook(c._sample_tpu_metrics)
        c.tracer = tracer_from_config(config, c.logger, c.app_name)
        c._maybe_remote_log_level()
        c._maybe_slo()
        c._maybe_sql()
        c._maybe_redis()
        c._maybe_pubsub()
        c._wire_file()
        c._maybe_kv()
        return c

    def _register_framework_metrics(self) -> None:
        m = self.metrics
        g = m.new_gauge("app_info", "application info")
        g.set(1, app=self.app_name, version=self.app_version, framework=f"gofr_tpu-{version.FRAMEWORK}")
        m.new_histogram("app_http_response", "HTTP handler latency (s)")
        m.new_histogram("app_http_service_response", "outbound HTTP client latency (s)")
        m.new_histogram("app_sql_stats", "SQL query latency (s)")
        m.new_histogram("app_redis_stats", "redis command latency (s)")
        m.new_histogram("app_kv_stats", "kv store op latency (s)")
        m.new_counter("app_pubsub_publish_total_count", "pubsub publish attempts")
        m.new_counter("app_pubsub_publish_success_count", "pubsub publish successes")
        m.new_counter("app_pubsub_subscribe_total_count", "pubsub messages received")
        m.new_counter("app_pubsub_subscribe_success_count", "pubsub messages handled ok")
        # TPU serving metrics (north-star observability: HBM + compile cache + batching)
        m.new_gauge("app_tpu_device_count", "visible TPU devices")
        m.new_gauge("app_tpu_hbm_used_bytes", "per-device HBM in use")
        m.new_gauge("app_tpu_hbm_limit_bytes", "per-device HBM capacity")
        # fed by JAX's own compile events (tpu/device.py), not by the engines
        m.new_counter("app_tpu_compile_total",
                      "backend compile requests: executables built or fetched from the persistent cache")
        m.new_counter("app_tpu_compile_seconds_total", "seconds spent in backend compile requests")
        m.new_counter("app_tpu_compile_cache_hits",
                      "compile requests the persistent compilation cache answered")
        # the device loop's host time by phase (tracing.LoopPhases; self time,
        # nested phases subtracted), flushed from the engines on every scrape
        m.new_counter("app_tpu_loop_phase_seconds_total", "device-loop host self time by phase (s)")
        m.new_counter("app_tpu_loop_phase_total", "device-loop phase entries by phase")
        m.new_counter("app_tpu_moe_assignments_total",
                      "token-to-expert assignments computed here, by held expert (expert)")
        m.new_counter("app_tpu_moe_assignments_absent_total",
                      "assignments to experts this rank does not hold (left out)")
        m.new_counter("app_tpu_moe_experts_hit_total",
                      "held experts with at least one assignment, summed over layer-steps")
        m.new_counter("app_tpu_moe_layer_steps_total",
                      "expert layers run (layers x program steps): the denominator")
        m.new_histogram("app_tpu_batch_occupancy", "occupied fraction of each device batch",
                        buckets=[0.1, 0.25, 0.5, 0.75, 0.9, 1.0])
        m.new_histogram("app_tpu_step_seconds", "device step wall time (s)")
        m.new_gauge("app_tpu_queue_depth", "requests waiting for a device step")
        m.new_counter("app_tpu_tokens_total", "tokens processed (prefill+decode)")
        m.new_gauge("app_tpu_kv_pages_free", "free pages in the paged KV pool")
        m.new_counter("app_tpu_preemptions", "slots preempted under KV pool pressure")
        m.new_counter("app_tpu_engine_restarts", "engine device-thread restarts")
        # hierarchical prefix cache (tpu/prefix.py, docs/serving.md): hit
        # tokens carry a tier label (hbm = pages already in the pool,
        # host = pages swapped back in from the host-DRAM spill tier)
        m.new_counter("app_tpu_prefix_hit_tokens", "prompt tokens served from the prefix cache (by tier)")
        m.new_counter("app_tpu_prefix_lookup_total", "prefix-cache lookups at admission")
        m.new_counter("app_tpu_prefix_miss_total", "prefix-cache lookups that hit nothing")
        m.new_gauge("app_tpu_prefix_cached_pages", "KV pages held by the prefix cache in HBM")
        m.new_gauge("app_tpu_prefix_host_pages", "KV pages held by the host-DRAM cache tier")
        m.new_gauge("app_tpu_prefix_host_bytes", "bytes held by the host-DRAM cache tier")
        m.new_counter("app_tpu_prefix_evicted_pages_total",
                      "prefix-cache pages evicted (tier: hbm = left the pool, host = dropped from host DRAM)")
        m.new_counter("app_tpu_prefix_swapin_pages_total",
                      "host-tier pages swapped back into the device pool")
        m.new_histogram("app_tpu_prefix_swapin_seconds",
                        "host->device page swap-in latency, dispatch to fold (s)")
        m.new_histogram("app_tpu_prefix_swapin_bytes",
                        "bytes uploaded per host->device swap-in",
                        buckets=[2 ** 14, 2 ** 17, 2 ** 20, 2 ** 23, 2 ** 26, 2 ** 29])
        # elastic fleet (gofr_tpu.fleet; docs/parallelism.md): epoch is the
        # membership generation — it only moves when the fleet changes
        m.new_gauge("app_fleet_epoch", "current fleet epoch (membership generation)")
        m.new_gauge("app_fleet_followers", "followers active on the fleet announce channel")
        m.new_counter("app_fleet_rejoins_total",
                      "followers admitted at an epoch bump (leader side) / successful "
                      "redials after leader loss (follower side)")
        m.new_counter("app_fleet_followers_lost_total",
                      "followers dropped from the announce fan-out mid-stream")
        m.new_counter("app_fleet_supervisor_restarts_total",
                      "fleet member processes restarted by fleet.Supervisor")
        # SLO-driven autoscaler (fleet/autoscaler.py, docs/resilience.md)
        m.new_gauge("app_fleet_replicas", "replicas the autoscaler's driver manages")
        m.new_counter("app_fleet_autoscale_decisions_total",
                      "autoscaler control-loop ticks (by decision: out/in/hold/freeze)")
        m.new_counter("app_fleet_autoscale_spawn_failures_total",
                      "warm-spare spawn attempts that failed (retried with backoff)")
        m.new_counter("app_fleet_autoscale_drain_aborts_total",
                      "scale-in drains aborted (victim re-admitted to the ring)")
        m.new_counter("app_fleet_requeued_total",
                      "requests moved from a draining replica onto a peer")
        m.new_gauge("app_tpu_draining", "1 while the engine is in its scale-in drain")
        m.new_counter("app_tpu_drain_shed_total",
                      "requests shed 503 because they arrived during a drain")
        # which implementation serves an engine's decode op (the rule in
        # ops/attention.resolve_backend, docs/kernels.md): info-style gauge,
        # set at warm-up — 1 on the (op, backend) pair that serves, 0 on the other
        m.new_gauge("app_tpu_kernel_backend",
                    "attention-kernel backend per decode op (1 = backend='auto' "
                    "resolves the op to this backend; labels: op, backend, kv_dtype)")
        # data-plane router (gofr_tpu.router, docs/routing.md): the
        # front-end tier's routing/spillover/shed accounting — affinity hit
        # ratio = routed_total{affinity="home"} / requests_total
        m.new_counter("app_router_requests_total",
                      "requests entering the router data plane (by qos_class)")
        m.new_counter("app_router_routed_total",
                      "requests proxied to a replica (replica; affinity = home|spill)")
        m.new_counter("app_router_spilled_total",
                      "requests that LANDED off their home replica (replica = home "
                      "it left; reason: shedding/restart/down = plan-time exclusion, "
                      "busy/error = the home's own 429/5xx/transport answer)")
        m.new_counter("app_router_shed_total",
                      "requests shed AT the router (qos_class; reason)")
        m.new_gauge("app_router_ring_size",
                    "replicas currently in the consistent-hash ring")
        m.new_gauge("app_router_replicas_known",
                    "replicas known to the router registry, any state")
        m.new_counter("app_tpu_spec_proposed", "draft tokens proposed by speculative decoding")
        m.new_counter("app_tpu_spec_accepted", "draft tokens accepted by target verification")
        # SLO latency family (docs/observability.md): recorded by the engine
        # device loop / completion path regardless of QoS or tracing state
        m.new_histogram("app_tpu_queue_wait_seconds",
                        "enqueue-to-admission wait before the device loop picked the request")
        m.new_histogram("app_tpu_ttft_seconds", "time to first token (s)")
        m.new_histogram("app_tpu_tpot_seconds",
                        "time per output token after the first (s)",
                        buckets=[0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0])
        m.new_histogram("app_tpu_e2e_seconds",
                        "end-to-end request latency, submit to completion (by qos_class)")
        m.new_gauge("app_tpu_inflight_requests", "requests submitted but not yet complete")
        # QoS / admission control (gofr_tpu.qos; all zero while QoS is off)
        m.new_counter("app_qos_admitted_total", "requests admitted by QoS")
        m.new_counter("app_qos_rejected_total",
                      "requests rejected by QoS (reason: rate/route_rate/key_rate/"
                      "tenant_rate/queue/deadline_exceeded/capacity/restart/slo_burn)")
        m.new_counter("app_qos_shed_total", "requests shed under overload (503s)")
        m.new_gauge("app_qos_queue_depth", "queued requests per priority class")
        m.new_gauge("app_qos_predicted_wait_seconds",
                    "estimated queue wait per engine (EWMA step x backlog)")
        m.new_histogram("app_qos_queue_wait_seconds",
                        "time requests spent queued before reaching the device loop")
        # SLO plane (metrics/slo.py, docs/observability.md): attainment and
        # Google-SRE error-budget burn per (class, objective); refreshed by
        # the SLOEngine collect hook on every scrape
        m.new_gauge("app_slo_attainment",
                    "fraction of samples meeting the objective (class, objective, window)")
        m.new_gauge("app_slo_burn_rate",
                    "error-budget burn rate; 1.0 = sustainable pace (class, objective, window)")
        m.new_gauge("app_slo_budget_remaining",
                    "slow-window error budget left, clamped to [0,1] (class, objective)")
        m.new_counter("app_slo_captures_total",
                      "anomaly bundles written by the burn-breach capture watcher")
        m.new_counter("app_slo_captures_suppressed_total",
                      "burn-breach captures suppressed by the token-bucket rate limit")
        # router decision metrics (ISSUE 9 satellite: the affinity hit ratio
        # used to live only in the /debug/router JSON view)
        m.new_counter("app_router_decisions_total",
                      "router routing decisions (replica; decision = home|spill|shed|error)")
        m.new_gauge("app_router_affinity_hit_ratio",
                    "home-replica hit fraction of routed requests since router start")
        # request-lifetime plane (ISSUE 10, docs/resilience.md): deadline
        # propagation, retry budgets, and hedged dispatch
        m.new_counter("app_request_deadline_exceeded_total",
                      "requests shed because their deadline could not be met "
                      "(where = edge|qos|engine|router)")
        m.new_counter("app_retry_budget_spent_total",
                      "retries granted by the shared Envoy-style retry budget")
        m.new_counter("app_retry_budget_exhausted_total",
                      "retries DENIED because the budget window was spent")
        m.new_counter("app_router_hedged_total",
                      "hedged dispatches fired by the router "
                      "(winner = primary|hedge|none)")
        # live performance plane (metrics/perf.py, docs/observability.md):
        # windowed roofline utilization per step kind, derived at scrape
        # time from the engines' exact numerator/denominator sums — never
        # set per engine (the _sample_tpu_metrics discipline)
        m.new_gauge("app_tpu_mfu",
                    "windowed model-FLOPs utilization vs device peak "
                    "(kind, kv_dtype; absent while peaks are unknown)")
        m.new_gauge("app_tpu_mbu",
                    "windowed HBM-bandwidth utilization vs device peak "
                    "(kind, kv_dtype; absent while peaks are unknown)")
        m.new_gauge("app_tpu_perf_flops_window",
                    "analytical FLOPs folded in the perf window (kind, kv_dtype)")
        m.new_gauge("app_tpu_perf_bytes_window",
                    "analytical HBM bytes folded in the perf window (kind, kv_dtype)")
        m.new_gauge("app_tpu_perf_device_seconds_window",
                    "device-queue residency folded in the perf window (kind, kv_dtype)")
        m.new_histogram("app_tpu_step_device_seconds",
                        "per-step device-queue residency, pipeline overlap "
                        "deduplicated (kind)",
                        buckets=[0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                                 0.05, 0.1, 0.25, 1.0])
        m.new_gauge("app_tpu_pipeline_bubble_ratio",
                    "device-idle-while-work-queued fraction of the perf window")
        # per-adapter attribution (multi-LoRA multiplexing; docs/serving.md):
        # proportional share of each mixed-adapter step's roofline terms,
        # an exact partition — summed over adapters they equal the step's
        m.new_gauge("app_tpu_adapter_mfu",
                    "windowed MFU share attributed to one adapter (adapter)")
        m.new_gauge("app_tpu_adapter_mbu",
                    "windowed MBU share attributed to one adapter (adapter)")
        m.new_gauge("app_tpu_adapter_device_seconds",
                    "windowed device-seconds attributed to one adapter "
                    "(adapter) — the per-tenant COGS meter")
        m.new_gauge("app_tpu_weights_epoch",
                    "live base-weight epoch (bumped by every hot-swap "
                    "adoption; engine.adopt_weights)")
        m.new_counter("app_tpu_weight_swaps_total",
                      "full-model live weight adoptions (zero-drop hot-swap)")
        m.new_gauge("app_tpu_adapters_registered",
                    "adapters resident in the host registry tier")
        m.new_counter("app_tpu_spec_pages_trimmed_total",
                      "KV pages claimed for spec over-claim and released at fold")
        m.new_counter("app_tpu_spec_tokens_rejected_total",
                      "spec draft tokens the target verification rejected")
        m.new_gauge("app_tpu_kv_pool_occupancy",
                    "allocated fraction of the paged KV pool (engine)")
        m.new_gauge("app_tpu_kv_pool_fragmentation",
                    "claimed-but-unwritten fraction of slot-held pages (engine)")
        m.new_gauge("app_tpu_kv_pool_device_bytes",
                    "shard-local paged-KV pool bytes resident per device "
                    "(engine, kv_shards) — fleet rollups sum, never average")
        # quality plane (metrics/quality.py; docs/observability.md): shadow
        # re-score divergence vs the reference configuration, keyed by what
        # the serving path actually used (kv_dtype, backend, adapter)
        m.new_histogram("app_tpu_quality_logprob_delta",
                        "mean |serving - reference| log-prob of the emitted "
                        "tokens, per shadow sample (kv_dtype, backend, adapter)",
                        buckets=[0.001, 0.005, 0.01, 0.05, 0.1, 0.5,
                                 1.0, 2.0, 5.0])
        m.new_histogram("app_tpu_quality_kl",
                        "mean per-token KL(serving || reference), per shadow "
                        "sample (kv_dtype, backend, adapter)",
                        buckets=[0.0001, 0.001, 0.01, 0.05, 0.1, 0.5,
                                 1.0, 5.0])
        m.new_gauge("app_tpu_quality_top1_agree",
                    "fraction of emitted tokens matching the reference "
                    "argmax, last shadow sample (kv_dtype, backend, adapter)")
        m.new_histogram("app_tpu_quality_first_divergence_token",
                        "token index of the first reference-argmax "
                        "disagreement (diverged samples only)",
                        buckets=[0, 1, 2, 4, 8, 16, 32, 64, 128])
        m.new_counter("app_tpu_quality_samples_total",
                      "shadow-scored requests (kv_dtype, backend, adapter) — "
                      "rides the gossip digest for exact fleet rollups")
        m.new_counter("app_tpu_quality_good_total",
                      "shadow samples within divergence thresholds "
                      "(kv_dtype, backend, adapter)")
        m.new_counter("app_tpu_quality_shadow_dropped_total",
                      "sampled requests evicted from the bounded shadow "
                      "queue before scoring (back-pressure, never blocking)")
        m.new_gauge("app_tpu_spec_accept_ratio",
                    "lifetime speculative-decode acceptance ratio (adapter) "
                    "— the cheapest always-on quality proxy")
        # online step controller (gofr_tpu.control; docs/serving.md): the
        # perf plane closed into actuation — decisions counted by verdict,
        # the live knob vector exported per knob so dashboards can overlay
        # knob moves on the MFU/bubble timelines they were judged by
        m.new_counter("app_tpu_control_decisions_total",
                      "step-controller decisions (verdict: try|commit|"
                      "revert|resume|standdown)")
        m.new_gauge("app_tpu_control_knob",
                    "live value of one engine tuning knob (engine, knob)")
        m.new_gauge("app_tpu_control_active",
                    "1 when the engine's step controller is constructed and "
                    "not stood down (engine)")

    def _sample_tpu_metrics(self, _registry=None) -> None:
        """Collect hook: live HBM gauges on every /metrics scrape (the
        reference pushes pool gauges on a ticker, sql.go:190-203). Only if
        the TPU datasource is already materialized — a scrape must never be
        the thing that initializes a device backend."""
        tpu = self._tpu
        if tpu is not None:
            try:
                tpu._push_memory_gauges()
            except Exception:  # noqa: BLE001 - scrape must not fail on device hiccup
                pass
        # summed HERE rather than set by each engine: a per-engine write to
        # the shared gauge would report whichever engine completed last
        self.metrics.set_gauge(
            "app_tpu_inflight_requests",
            sum(getattr(e, "_inflight_requests", 0) for e in self._engines.values()))
        for e in self._engines.values():
            phases = getattr(e, "_phases", None)
            if phases is not None:
                phases.flush(self.metrics)
            flush = getattr(e, "flush_step_counters", None)
            if callable(flush):
                flush(self.metrics)
        # spec-decode acceptance, divided at scrape time from raw
        # per-adapter (accepted, proposed) numerators summed across engines
        # — never an average of per-engine ratios
        spec: dict[str, list[float]] = {}
        for e in self._engines.values():
            totals_fn = getattr(e, "spec_accept_totals", None)
            if not callable(totals_fn):
                continue
            for adapter, (acc, prop) in totals_fn().items():
                tot = spec.setdefault(adapter, [0.0, 0.0])
                tot[0] += acc
                tot[1] += prop
        for adapter, (acc, prop) in spec.items():
            if prop > 0:
                self.metrics.set_gauge("app_tpu_spec_accept_ratio",
                                       acc / prop, adapter=adapter)
        # online-controller surface: knob vectors are engine attributes, so
        # sampling them at scrape time (like the pool gauges) keeps the
        # device loop free of metrics writes on the knob-apply path
        for name, e in self._engines.items():
            kv_fn = getattr(e, "knob_vector", None)
            if not callable(kv_fn):
                continue
            for knob, value in kv_fn().items():
                self.metrics.set_gauge("app_tpu_control_knob", value,
                                       engine=name, knob=knob)
            ctl = getattr(e, "_control", None)
            self.metrics.set_gauge(
                "app_tpu_control_active",
                1 if (ctl is not None and ctl.standdown is None) else 0,
                engine=name)
        self._sample_perf_metrics()

    def perf_totals(self) -> dict | None:
        """Exact sum-of-parts merge of every registered engine's perf
        window (metrics/perf.py payload shape) — the one rollup the
        scrape gauges, the gossip digest, and capture bundles all share.
        None when no engine carries a perf plane."""
        planes = [e.perf for e in self._engines.values()
                  if getattr(e, "perf", None) is not None]
        if not planes:
            return None
        import time

        from gofr_tpu.metrics import perf as perf_mod

        now = time.monotonic()
        return perf_mod.merge_totals(p.window_totals(now) for p in planes)

    def knob_vectors(self) -> dict | None:
        """Per-engine live tuning-knob vectors (engine.knob_vector), with a
        ``_controlled`` marker where an online controller is actually
        driving them — rides the gossip digest so /debug/fleet shows who
        runs which tuning. None when no engine exposes knobs."""
        out: dict = {}
        for name, e in self._engines.items():
            kv_fn = getattr(e, "knob_vector", None)
            if not callable(kv_fn):
                continue
            vec = kv_fn()
            ctl = getattr(e, "_control", None)
            if ctl is not None and ctl.standdown is None:
                vec["_controlled"] = 1
            out[name] = vec
        return out or None

    def _sample_perf_metrics(self) -> None:
        """Roofline gauges from the merged engine windows: numerators and
        capacity denominators are summed exactly across engines, the
        ratios derived once here (never averaged)."""
        totals = self.perf_totals()
        if totals is None:
            return
        from gofr_tpu.metrics import perf as perf_mod

        for key, rec in totals["kinds"].items():
            kind, _, dtype = key.partition("|")
            labels = {"kind": kind, "kv_dtype": dtype}
            self.metrics.set_gauge(
                "app_tpu_perf_flops_window", rec["flops"], **labels)
            self.metrics.set_gauge(
                "app_tpu_perf_bytes_window", rec["bytes"], **labels)
            self.metrics.set_gauge(
                "app_tpu_perf_device_seconds_window", rec["device_s"], **labels)
            if rec["flops_cap"]:
                self.metrics.set_gauge(
                    "app_tpu_mfu", rec["flops"] / rec["flops_cap"], **labels)
            if rec["bytes_cap"]:
                self.metrics.set_gauge(
                    "app_tpu_mbu", rec["bytes"] / rec["bytes_cap"], **labels)
        derived = perf_mod.derive(totals)
        for aid, rec in derived.get("adapters", {}).items():
            labels = {"adapter": aid}
            self.metrics.set_gauge(
                "app_tpu_adapter_device_seconds", rec["device_s"], **labels)
            if rec.get("mfu") is not None:
                self.metrics.set_gauge("app_tpu_adapter_mfu", rec["mfu"],
                                       **labels)
            if rec.get("mbu") is not None:
                self.metrics.set_gauge("app_tpu_adapter_mbu", rec["mbu"],
                                       **labels)
        ratio = derived["bubble_ratio"]
        if ratio is not None:
            self.metrics.set_gauge("app_tpu_pipeline_bubble_ratio", ratio)
        for name, e in self._engines.items():
            stats_fn = getattr(e, "page_pool_stats", None)
            stats = stats_fn() if callable(stats_fn) else None
            if stats:
                # occupancy/fragmentation are page-count ratios — identical
                # on every shard of a tp-sharded pool, so one gauge per
                # engine IS the shard-local reading; the byte gauge is the
                # per-DEVICE slice (engine.page_pool_stats), so a fleet
                # sum-of-parts rollup over devices stays exact
                self.metrics.set_gauge(
                    "app_tpu_kv_pool_occupancy", stats["occupancy"], engine=name)
                self.metrics.set_gauge(
                    "app_tpu_kv_pool_fragmentation", stats["fragmentation"],
                    engine=name)
                if "pool_bytes_device" in stats:
                    self.metrics.set_gauge(
                        "app_tpu_kv_pool_device_bytes",
                        stats["pool_bytes_device"], engine=name,
                        kv_shards=str(stats.get("kv_shards", 1)))

    def _maybe_remote_log_level(self) -> None:
        url = self.config.get("REMOTE_LOG_URL")
        if not url:
            return
        from gofr_tpu.logging.remote import RemoteLevelPoller

        interval = self.config.get_float("REMOTE_LOG_FETCH_INTERVAL", 15.0)
        self._remote_level_poller = RemoteLevelPoller(self.logger, url, interval)
        self._remote_level_poller.start()

    def _maybe_slo(self) -> None:
        """Wire the SLO engine (on by default — it is pure bookkeeping over
        samples the engines already record) and, only when the app opts in
        via SLO_CAPTURE, the burn-breach anomaly capture watcher."""
        if not self.config.get_bool("SLO_ENABLED", True):
            return
        from gofr_tpu.metrics.slo import CaptureWatcher, SLOEngine

        self.slo = SLOEngine.from_config(
            self.config, metrics=self.metrics, logger=self.logger)
        self.metrics.add_collect_hook(self.slo.sample_gauges)
        if self.config.get_bool("SLO_CAPTURE"):
            self.slo_capture = CaptureWatcher.from_config(
                self.config, self, self.slo)
            self.slo.add_breach_listener(self.slo_capture.on_breach)

    def _maybe_sql(self) -> None:
        dialect = (self.config.get("DB_DIALECT") or "").lower()
        host = self.config.get("DB_HOST")
        if not dialect and not host:
            return
        from gofr_tpu.datasource.sql import connect_sql

        self.sql = connect_sql(self.config, self.logger, self.metrics)

    def _maybe_redis(self) -> None:
        host = self.config.get("REDIS_HOST")
        if not host:
            return
        from gofr_tpu.datasource.redis import connect_redis

        self.redis = connect_redis(self.config, self.logger, self.metrics)

    def _maybe_pubsub(self) -> None:
        backend = (self.config.get("PUBSUB_BACKEND") or "").lower()
        if not backend:
            return
        from gofr_tpu.pubsub import connect_pubsub

        self.pubsub = connect_pubsub(backend, self.config, self.logger, self.metrics)

    def _wire_file(self) -> None:
        from gofr_tpu.datasource.file import LocalFileSystem

        self.file = LocalFileSystem()

    def _maybe_kv(self) -> None:
        path = self.config.get("KV_PATH")
        if not path:
            return
        from gofr_tpu.datasource.kv import KVStore

        self.kv = KVStore(path, self.logger, self.metrics)

    # -- external-plugin injection (gofr `external_db.go` pattern) -------------

    def add_mongo(self, client: Any) -> None:
        self.mongo = self._wire_plugin(client)

    def add_cassandra(self, client: Any) -> None:
        self.cassandra = self._wire_plugin(client)

    def add_clickhouse(self, client: Any) -> None:
        self.clickhouse = self._wire_plugin(client)

    def add_kv_store(self, client: Any) -> None:
        self.kv = self._wire_plugin(client)

    def add_file_store(self, client: Any) -> None:
        """Replace the default local filesystem with a remote-FS provider
        (datasource/file.py ``FileSystemProvider``; gofr `file.go:69-78`)."""
        self.file = self._wire_plugin(client)

    def _wire_plugin(self, client: Any) -> Any:
        if hasattr(client, "use_logger"):
            client.use_logger(self.logger)
        if hasattr(client, "use_metrics"):
            client.use_metrics(self.metrics)
        if hasattr(client, "connect"):
            client.connect()
        return client

    # -- TPU device datasource (lazy; a feature like any other) ----------------

    @property
    def tpu(self):
        if self._tpu is None:
            with self._tpu_lock:
                if self._tpu is None:
                    from gofr_tpu.tpu.device import TPUDevices

                    self._tpu = TPUDevices(self.config, self.logger, self.metrics)
        return self._tpu

    @property
    def tpu_wired(self) -> bool:
        return self._tpu is not None

    # -- QoS / admission control -----------------------------------------------

    def register_qos(self, controller: Any) -> None:
        """Install the app-wide AdmissionController (App.enable_qos): binds
        every already-served engine, exports the per-class gauges on each
        scrape, and joins health aggregation (DEGRADED while shedding).
        Re-registering (QOS_ENABLED auto-enable followed by a programmatic
        enable_qos) replaces the old controller entirely — its scrape hook
        included, so a stale sampler can't keep writing gauges."""
        if self.qos is not None:
            self.metrics.remove_collect_hook(self.qos.sample_gauges)
        self.qos = controller
        self.metrics.add_collect_hook(controller.sample_gauges)
        for name, engine in self._engines.items():
            controller.bind_engine(name, engine)

    # -- model engines ---------------------------------------------------------

    def register_engine(self, name: str, engine: Any) -> None:
        self._engines[name] = engine
        if self.qos is not None:
            self.qos.bind_engine(name, engine)

    def engine(self, name: str):
        try:
            return self._engines[name]
        except KeyError:
            raise KeyError(
                f"no model {name!r} served; registered: {sorted(self._engines)}"
            ) from None

    @property
    def engines(self) -> dict[str, Any]:
        return dict(self._engines)

    def infer(self, model: str, inputs: Any, **kw: Any):
        return self.engine(model).infer(inputs, **kw)

    def generate(self, model: str, prompt: Any, **kw: Any):
        return self.engine(model).generate(prompt, **kw)

    # -- inter-service HTTP clients -------------------------------------------

    def register_service(self, name: str, client: Any) -> None:
        self.services[name] = client

    def http_service(self, name: str):
        try:
            return self.services[name]
        except KeyError:
            raise KeyError(f"no HTTP service registered as {name!r}") from None

    # -- pubsub convenience ----------------------------------------------------

    def _pubsub_supports_headers(self) -> bool:
        """Signature-probed once per broker object (NOT try/except TypeError
        around the send — that would conflate 'no headers parameter' with a
        genuine TypeError inside a headers-capable broker and re-publish)."""
        ps = self.pubsub
        cached = self._pubsub_hdr_support
        if cached is not None and cached[0] is ps:
            return cached[1]
        import inspect

        try:
            ok = "headers" in inspect.signature(ps.publish).parameters
        except (TypeError, ValueError):  # builtins/C extensions: no signature
            ok = False
        self._pubsub_hdr_support = (ps, ok)
        return ok

    def publish(self, topic: str, payload: Any, headers: dict[str, str] | None = None) -> None:
        if self.pubsub is None:
            raise RuntimeError("no pubsub backend configured (set PUBSUB_BACKEND)")
        self.metrics.increment_counter("app_pubsub_publish_total_count", 1, topic=topic)
        if headers and self._pubsub_supports_headers():
            # trace context (W3C traceparent) rides as message headers so
            # subscribe handlers join the publisher's trace; an external
            # plugin broker without header support still gets the message
            self.pubsub.publish(topic, payload, headers=headers)
        else:
            self.pubsub.publish(topic, payload)
        self.metrics.increment_counter("app_pubsub_publish_success_count", 1, topic=topic)

    # -- health aggregation (gofr `container/health.go`) -----------------------

    def health(self) -> dict[str, Any]:
        services: dict[str, Any] = {}
        down = 0

        def check(name: str, obj: Any) -> None:
            nonlocal down
            if obj is None:
                return
            try:
                h = obj.health_check() if hasattr(obj, "health_check") else {"status": "UP"}
            except Exception as e:  # noqa: BLE001
                h = {"status": "DOWN", "details": {"error": str(e)}}
            services[name] = h
            if h.get("status") != "UP":
                down += 1

        check("sql", self.sql)
        check("redis", self.redis)
        check("pubsub", self.pubsub)
        check("kv", self.kv)
        check("file", self.file)
        check("mongo", self.mongo)
        check("cassandra", self.cassandra)
        check("clickhouse", self.clickhouse)
        check("tpu", self._tpu)
        check("qos", self.qos)
        check("slo", self.slo)
        for name, engine in self._engines.items():
            check(f"model:{name}", engine)
        for name, svc in self.services.items():
            check(f"service:{name}", svc)

        status = "UP" if down == 0 else ("DEGRADED" if down < max(len(services), 1) else "DOWN")
        return {
            "status": status,
            "name": self.app_name,
            "version": self.app_version,
            "services": services,
        }

    # -- shutdown --------------------------------------------------------------

    def close(self) -> None:
        if self._remote_level_poller is not None:
            self._remote_level_poller.stop()
        for engine in self._engines.values():
            if hasattr(engine, "stop"):
                engine.stop()
        # a closed container lets go of its engines, so their weights and KV
        # cache leave device memory even while something still holds the App
        # (aiohttp keeps a process-wide LRU of handlers, hence of their App)
        self._engines.clear()
        for ds in (self.sql, self.redis, self.pubsub, self.kv, self.mongo, self.cassandra, self.clickhouse):
            if ds is not None and hasattr(ds, "close"):
                try:
                    ds.close()
                except Exception:  # noqa: BLE001
                    pass
        self.tracer.shutdown()


def new_mock_container(config: dict[str, str] | None = None) -> Container:
    """Hermetic container for handler tests (gofr `NewMockContainer`): mock
    logger, real metrics registry, no datasources wired, in-memory pubsub."""
    from gofr_tpu.pubsub.inmemory import InMemoryBroker

    c = Container(DictConfig(config or {}), logger=MockLogger(level=Level.DEBUG))
    c._register_framework_metrics()
    c.metrics.add_collect_hook(c._sample_tpu_metrics)
    c._maybe_slo()  # mock containers skip create(); SLO must still wire
    c.pubsub = InMemoryBroker()
    return c
