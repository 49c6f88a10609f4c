"""Multi-host (DCN) scaffolding: JAX_COORDINATOR config →
``jax.distributed.initialize`` in the TPU datasource (SURVEY §5.8).

Two REAL processes coordinate over localhost, each contributing 2 virtual
CPU devices; each builds the container's TPU datasource from config alone,
constructs the GLOBAL dp mesh, and runs a jitted psum across the process
boundary. This is the CPU stand-in for a v5e multi-slice job — the same
config keys drive real DCN bring-up.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

# integration tier (CI `integration` job): multi-minute engine/process
# runs — excluded from the tier-1 gate via -m 'not slow' (docs/testing.md)
pytestmark = pytest.mark.slow

_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from gofr_tpu.container import new_mock_container

    pid = int(sys.argv[1])
    c = new_mock_container({{
        "JAX_COORDINATOR": "127.0.0.1:{port}",
        "JAX_NUM_PROCESSES": "2",
        "JAX_PROCESS_ID": str(pid),
        "TPU_MESH": "dp:4",
    }})
    tpu = c.tpu
    assert tpu.distributed, "distributed init did not run"
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 4, jax.devices()
    assert len(tpu.local_devices) == 2

    mesh = tpu.mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    @jax.jit
    def global_sum(x):
        return jax.lax.psum(x, "dp")

    from functools import partial
    @partial(jax.jit, out_shardings=NamedSharding(mesh, P()))
    def reduce_all(x):
        return jnp.sum(x)

    # a length-4 array sharded one element per global device; the jitted sum
    # crosses the process boundary
    x = jax.device_put(
        jnp.arange(4.0), NamedSharding(mesh, P("dp"))
    )
    total = reduce_all(x)
    assert float(total) == 6.0, float(total)
    health = tpu.health_check()
    assert health["status"] == "UP"
    print(f"MULTIHOST_OK pid={{pid}} devices={{len(jax.devices())}} total={{float(total)}}")
""")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_global_mesh():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = _free_port()
    src = _WORKER.format(repo=repo, port=port)
    env = dict(os.environ)  # JAX_PLATFORMS=cpu (conftest pin) is inherited
    env.pop("XLA_FLAGS", None)  # workers pin their own device count

    procs = [
        subprocess.Popen([sys.executable, "-c", src, str(pid)],
                         env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(f"multi-host workers hung; partial output: {outs}")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-3000:]}"
        assert "MULTIHOST_OK" in out, out[-3000:]
