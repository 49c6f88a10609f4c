"""Test harness: force JAX onto CPU with 8 virtual devices BEFORE jax use.

This is the analog of the reference's MockContainer strategy (SURVEY.md §4): unit
tests run hermetically against a fake 8-chip mesh so every sharding/collective
path is exercised without TPU hardware. The pin lives in one place — repo-root
``jaxpin.py`` — shared with bench.py and __graft_entry__.py; child processes
the tests spawn inherit it through the environment.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jaxpin import pin_cpu  # noqa: E402

pin_cpu(8)

import jax  # noqa: E402

from gofr_tpu.tpu.device import ensure_compile_cache  # noqa: E402

# Persistent XLA compile cache (placement decided by ensure_compile_cache):
# the suite builds dozens of engines whose tiny-config programs compile
# identically across test modules (and the fleet/lockstep drills recompile
# them again in subprocesses). Caching the compiled executables on disk
# dedups those repeats — including within a single cold run, since each
# GenerateEngine re-jits its own function objects — which is what keeps
# tier-1 inside its wall-clock budget on 1–2 vCPU CI hosts. Semantically
# neutral: a cache miss just compiles. The low threshold is the suite's own:
# its programs are tiny, and most compile in under JAX's 1 s default.
ensure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)

import pytest  # noqa: E402


@pytest.fixture
def mock_logger():
    from gofr_tpu.logging import MockLogger

    return MockLogger()
