"""Hand-written Pallas TPU kernels (flash attention, slot-cache decode).

Kernels target the TPU memory hierarchy (HBM→VMEM blocks, MXU-sized
tiles). On CPU they run only under the Pallas interpreter — set
``GOFR_PALLAS_INTERPRET=1``, as tests/test_pallas.py does for its parity
cases (the rest of the suite runs the XLA path). ``backend='auto'``
callers go through ``ops.attention.resolve_backend`` — the one rule that
says which op's kernel serves where — and resolve to XLA where no kernel
can lower, so the same model code runs on the test mesh and real chips; an
EXPLICIT request for a kernel that cannot be honoured raises
(:func:`require_kernel_platform`) instead of running something else.
"""

from __future__ import annotations

import contextlib
import contextvars
import os

import jax

# Where the computation being *traced* will actually run. jax.default_backend()
# lies when a TPU is attached but the target mesh is CPU (the multichip dryrun,
# CPU test meshes), so mesh-aware callers (make_train_step, engines) pin it.
_PLATFORM: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "gofr_pallas_platform", default=None
)


@contextlib.contextmanager
def platform_hint(platform: str | None):
    """Pin the target platform for backend resolution while tracing, e.g.
    ``with platform_hint(mesh.devices.flat[0].platform): step_fn(...)``."""
    tok = _PLATFORM.set(platform)
    try:
        yield
    finally:
        _PLATFORM.reset(tok)


def interpret_mode() -> bool:
    """True when kernels should run under the Pallas interpreter (CPU tests)."""
    return os.environ.get("GOFR_PALLAS_INTERPRET", "") == "1"


def kernel_platform() -> bool:
    """True when the traced computation targets hardware (or the
    interpreter) that can actually lower the Pallas kernels."""
    if interpret_mode():
        return True
    platform = _PLATFORM.get()
    if platform is None:
        platform = jax.default_backend()
    return platform == "tpu"


def require_kernel_platform(what: str) -> None:
    """Raise unless the Pallas kernels can lower here. Call sites that were
    asked for a kernel BY NAME (``backend='pallas'``, ``GOFR_KV_WRITE=pallas``)
    use this so the request is never quietly served by a different lowering."""
    if not kernel_platform():
        platform = _PLATFORM.get() or jax.default_backend()
        raise RuntimeError(
            f"{what} asks for a Pallas kernel, but the target platform is "
            f"{platform!r} (kernels lower on 'tpu' only; on CPU set "
            f"GOFR_PALLAS_INTERPRET=1 to run them under the interpreter)")


__all__ = [
    "interpret_mode", "kernel_platform", "platform_hint",
    "require_kernel_platform",
]
