"""Pin this process, and the children it starts, to the CPU backend.

Used by tests/conftest.py, bench.py (``GOFR_BENCH_PLATFORM=cpu``) and
__graft_entry__.py — import-light (no package imports) so it can run before
anything touches jax.
"""

from __future__ import annotations

import os
import re


def pin_cpu(n_devices: int = 1) -> None:
    """Force the CPU backend with >= n_devices virtual devices.

    Sets ``JAX_PLATFORMS=cpu`` and the host device-count flag in the
    environment — which child processes inherit — so call it before the
    first jax backend is initialized. An existing device-count flag is
    raised, never lowered.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    count = max(n_devices, int(m.group(1)) if m else 0)
    want = f"--xla_force_host_platform_device_count={count}"
    flags = flags.replace(m.group(0), want) if m else (flags + " " + want).strip()
    os.environ["XLA_FLAGS"] = flags

    import jax

    # a jax imported before this call read JAX_PLATFORMS at import time
    jax.config.update("jax_platforms", "cpu")
