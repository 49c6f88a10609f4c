"""TPU device datasource.

Wraps the visible accelerator devices plus the configured mesh the way the
reference wraps a connection pool (SQL: `datasource/sql/sql.go:37-89` —
lazy connect, pushed pool gauges, health check). Config keys:

    TPU_MESH            mesh topology, e.g. "dp:2,tp:4" (default: all on dp)
    TPU_DEVICES         cap the number of devices used (default: all)
    JAX_COORDINATOR     host:port of process 0 → multi-host (DCN) mode:
                        ``jax.distributed.initialize`` runs before any device
                        access and the mesh spans the GLOBAL device set
                        (SURVEY §5.8; the reference's backend-by-config
                        switch, container.go:95-122)
    JAX_NUM_PROCESSES   total processes in the job (with JAX_COORDINATOR)
    JAX_PROCESS_ID      this process's index (with JAX_COORDINATOR)

Everything degrades gracefully on CPU (the virtual test mesh) — memory
stats are best-effort because the CPU PJRT client doesn't report them.
"""

from __future__ import annotations

import os
import threading
import weakref
from typing import Any

import jax

from gofr_tpu.parallel import ShardingRules, mesh_from_config

# <checkout>/.cache/jax: the directory that holds the gofr_tpu package, so
# the path is the same for every process started from one checkout. The
# path is part of the cache key — a directory that moves never hits.
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".cache", "jax")


def ensure_compile_cache() -> str:
    """The ONE place that decides where JAX's persistent compilation cache
    lives; returns the directory in use. Called by ``TPUDevices`` (so every
    App and engine gets it), tests/conftest.py and bench.py.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets no other directory in code. Otherwise the cache goes to the fixed
    ``<checkout>/.cache/jax`` (gitignored) — never /tmp, a pid or a
    timestamp — so a second process from the same checkout starts warm."""
    # An executable carries its operations' names — the tracing.SCOPES path a
    # profiler shows. JAX leaves them out of the cache key by default, so a
    # cache filled by another build of this code would hand back executables
    # under the OLD names; with them in the key it compiles anew instead.
    if not jax.config.jax_compilation_cache_include_metadata_in_key:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    if jax.config.jax_compilation_cache_dir != _CHECKOUT_CACHE:
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE


# -- compiles, from JAX's own events -----------------------------------------------
#
# JAX reports every backend compile REQUEST (an executable built, or fetched
# from the persistent cache: either way a program nobody had ready) as a
# duration event, and each request the persistent cache answered as a plain
# event. One listener pair per process — jax.monitoring has no public
# unregister — fans them out to the TPUDevices objects alive at the time.

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_live: "weakref.WeakSet[TPUDevices]" = weakref.WeakSet()
_listening = threading.Lock()  # held forever once the listeners are registered


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        for tpu in list(_live):
            tpu._on_compile(seconds)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        for tpu in list(_live):
            tpu._on_cache_hit()


def _listen_for_compiles(tpu: "TPUDevices") -> None:
    _live.add(tpu)
    if _listening.acquire(blocking=False):
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)


def _maybe_init_distributed(config, logger) -> bool:
    """Config-gated multi-host bring-up. Unset coordinator ⇒ single-process
    (the 'unset host ⇒ feature off' rule every datasource follows). Must run
    before the first device touch in the process; `jax.distributed` raises
    if already initialized, which we treat as wired."""
    coordinator = config.get("JAX_COORDINATOR")
    if not coordinator:
        return False
    num_processes = config.get_int("JAX_NUM_PROCESSES", 1)
    process_id = config.get_int("JAX_PROCESS_ID", 0)
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
        logger.infof(
            "jax.distributed initialized: process %d/%d via %s",
            process_id, num_processes, coordinator,
        )
        return True
    except RuntimeError as e:
        if "already initialized" in str(e).lower():
            return True
        raise


class TPUDevices:
    def __init__(self, config, logger, metrics):
        self.config = config
        self.logger = logger
        self.metrics = metrics
        self._lock = threading.Lock()

        self.compile_cache_dir = ensure_compile_cache()
        self.distributed = _maybe_init_distributed(config, logger)
        limit = config.get_int("TPU_DEVICES", 0)
        # multi-host: the mesh MUST span the global device set so pjit
        # programs agree across processes; local-only work uses local_devices
        devices = jax.devices()
        self.local_devices = jax.local_devices() if self.distributed else devices
        self.devices = devices[:limit] if limit > 0 else devices
        self.platform = self.devices[0].platform if self.devices else "none"
        self.mesh = mesh_from_config(config, devices=self.devices)
        self.rules = ShardingRules()
        self._compiles = 0
        _listen_for_compiles(self)

        metrics.set_gauge("app_tpu_device_count", len(self.devices))
        self._push_memory_gauges()
        logger.infof(
            "TPU datasource: %d %s device(s), mesh %s, compile cache %s",
            len(self.devices), self.platform,
            dict(zip(self.mesh.axis_names, self.mesh.devices.shape)),
            self.compile_cache_dir,
        )

    # -- stats -----------------------------------------------------------------

    def memory_stats(self) -> dict[str, dict[str, int]]:
        """Per-device HBM stats (empty entries where the backend doesn't
        report them, e.g. CPU). Multi-host: only this process's devices are
        addressable, so gauges cover the local slice."""
        stats: dict[str, dict[str, int]] = {}
        local = [d for d in self.devices if d in self.local_devices] or self.devices
        for d in local:
            try:
                s = d.memory_stats() or {}
            except Exception:  # noqa: BLE001
                s = {}
            stats[str(d.id)] = {
                "bytes_in_use": int(s.get("bytes_in_use", 0)),
                "bytes_limit": int(s.get("bytes_limit", 0)),
            }
        return stats

    def _push_memory_gauges(self) -> None:
        for dev_id, s in self.memory_stats().items():
            self.metrics.set_gauge("app_tpu_hbm_used_bytes", s["bytes_in_use"], device=dev_id)
            self.metrics.set_gauge("app_tpu_hbm_limit_bytes", s["bytes_limit"], device=dev_id)

    def _on_compile(self, seconds: float) -> None:
        with self._lock:
            self._compiles += 1
        self.metrics.increment_counter("app_tpu_compile_total", 1)
        self.metrics.increment_counter("app_tpu_compile_seconds_total", seconds)

    def _on_cache_hit(self) -> None:
        self.metrics.increment_counter("app_tpu_compile_cache_hits", 1)

    @property
    def compile_count(self) -> int:
        """Backend compile requests JAX reported in this process since this
        object was built (whoever asked: engines, warm-up, a handler's own
        jit) — what ``app_tpu_compile_total`` exports."""
        return self._compiles

    # -- health (container/health.go parity) -----------------------------------

    def health_check(self) -> dict[str, Any]:
        try:
            n = len(self.devices)
            if n == 0:
                return {"status": "DOWN", "details": {"error": "no devices visible"}}
            self._push_memory_gauges()
            return {
                "status": "UP",
                "details": {
                    "platform": self.platform,
                    "devices": n,
                    "mesh": {k: int(v) for k, v in zip(self.mesh.axis_names, self.mesh.devices.shape)},
                    "memory": self.memory_stats(),
                },
            }
        except Exception as e:  # noqa: BLE001
            return {"status": "DOWN", "details": {"error": str(e)}}
