"""Test config: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's "multi-node without a cluster" strategy (SURVEY.md
§4: Aeron-on-loopback / Spark local[*]) — sharding/collective tests execute
on `xla_force_host_platform_device_count=8` CPU devices; real-TPU paths are
exercised by `chip_smoke.py` and `benchmark/run.py`.
"""
import os

# Force CPU whatever the session's JAX_PLATFORMS says: the TPU has no
# float64, and the sharding tests need the 8-device virtual mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    xla_flags += " --xla_force_host_platform_device_count=8"
# The tests' programs are tiny, so compiling them costs more than running
# them: LLVM's optimisation passes are skipped (IEEE semantics are kept).
if "xla_backend_optimization_level" not in xla_flags:
    xla_flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = xla_flags.strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")  # gradient checks need f64

import jax  # noqa: E402

# jax may already be imported by a pytest plugin before this conftest runs,
# in which case the env var alone is too late — set the config directly.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture
def ring():
    """The monitor's ring of recorded host intervals, empty before the test
    and after it (`monitor/spans.py`; the ring is process-wide)."""
    from deeplearning4j_tpu.monitor import clear_recorded
    clear_recorded()
    yield
    clear_recorded()
