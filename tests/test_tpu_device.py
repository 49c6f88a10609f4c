"""TPU device datasource tests (container.tpu) on the virtual CPU mesh."""


from gofr_tpu.config import DictConfig
from gofr_tpu.container import new_mock_container
from gofr_tpu.logging import MockLogger
from gofr_tpu.metrics import Registry
from gofr_tpu.tpu.device import TPUDevices


def _registry() -> Registry:
    """Registry with the framework's app_tpu_* metrics registered (names
    unknown to the registry are silently ignored, gofr-style)."""
    return new_mock_container().metrics


def make(conf=None):
    return TPUDevices(DictConfig(conf or {}), MockLogger(), _registry())


def test_defaults_all_devices_on_dp():
    t = make()
    assert len(t.devices) == 8
    assert t.mesh.axis_names == ("dp",)


def test_mesh_from_config():
    t = make({"TPU_MESH": "dp:2,tp:4"})
    assert t.mesh.devices.shape == (2, 4)
    assert t.mesh.axis_names == ("dp", "tp")


def test_device_cap():
    t = make({"TPU_DEVICES": "4", "TPU_MESH": "tp:4"})
    assert len(t.devices) == 4


def test_health_check_up():
    t = make({"TPU_MESH": "tp:-1"})
    h = t.health_check()
    assert h["status"] == "UP"
    assert h["details"]["devices"] == 8
    assert h["details"]["mesh"] == {"tp": 8}
    assert set(h["details"]["memory"]) == {str(d.id) for d in t.devices}


def test_compile_counter():
    """``app_tpu_compile_total`` is fed by JAX's own backend-compile events:
    jitting a new shape grows it by exactly what JAX reports, a repeat by 0;
    the seconds ride ``app_tpu_compile_seconds_total`` and the persistent
    cache's hits ``app_tpu_compile_cache_hits``."""
    import jax
    import jax.numpy as jnp

    reg = _registry()
    t = TPUDevices(DictConfig({}), MockLogger(), reg)
    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_kw: seen.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    assert t.compile_count == 0 and reg.get("app_tpu_compile_total").value() == 0

    f = jax.jit(lambda x: x * 3 + 1)
    f(jnp.ones((7,))).block_until_ready()
    first = t.compile_count
    assert first == len(seen) >= 1
    assert reg.get("app_tpu_compile_total").value() == first
    assert reg.get("app_tpu_compile_seconds_total").value() > 0

    f(jnp.ones((7,))).block_until_ready()  # same shape: nothing compiles
    assert t.compile_count == first == len(seen)

    f(jnp.ones((9,))).block_until_ready()  # a new shape does
    assert t.compile_count == len(seen) > first
    assert reg.get("app_tpu_compile_total").value() == len(seen)

    hits = reg.get("app_tpu_compile_cache_hits").value()
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert reg.get("app_tpu_compile_cache_hits").value() == hits + 1


def test_compile_events_reach_every_live_device_object():
    """One listener pair per process fans out to the objects alive: a second
    container counts from its own zero, a dropped one is not kept alive."""
    import gc
    import weakref

    import jax
    import jax.numpy as jnp

    from gofr_tpu.tpu import device

    a, b = make(), make()
    jax.jit(lambda x: x - 5)(jnp.ones((11,))).block_until_ready()
    assert a.compile_count == b.compile_count >= 1
    assert a in device._live and b in device._live
    gone = weakref.ref(b)
    del b
    gc.collect()
    assert gone() is None and a in device._live


def test_container_lazily_wires_tpu():
    c = new_mock_container()
    assert not c.tpu_wired
    tpu = c.tpu
    assert c.tpu_wired
    assert tpu is c.tpu  # cached
    assert c.health()["services"]["tpu"]["status"] == "UP"


def test_device_count_gauge():
    reg = _registry()
    TPUDevices(DictConfig({}), MockLogger(), reg)
    assert reg.get("app_tpu_device_count").value() == 8
