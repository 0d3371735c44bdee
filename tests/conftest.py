"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's local[2] Spark strategy (utils/.../test/
TestSparkContext.scala:50): all algorithms are shard-order-invariant, so a
small local mesh exercises the same code paths as real hardware.
"""
import os

# force CPU even if the session env points at the real chip — EXCEPT when
# explicitly running the on-device suites (TPTPU_TPU_TESTS=1)
_ON_DEVICE = os.environ.get("TPTPU_TPU_TESTS", "") == "1"
if not _ON_DEVICE:
    os.environ["JAX_PLATFORMS"] = "cpu"
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    # hermetic: Workflow.train / score_function turn JAX's persistent
    # compilation cache on (compiler/cache.py); tests neither read a
    # previous run's entries nor write into the checkout. Tests of the
    # cache itself re-enable it in a subprocess.
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from transmogrifai_tpu.utils import uid as uid_util  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_uids():
    uid_util.reset()
    yield


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def fault_plan():
    """An installed, empty FaultPlan — tests script faults onto it and the
    fixture guarantees uninstall (resilience.faults is process-global)."""
    from transmogrifai_tpu.resilience import faults

    plan = faults.FaultPlan()
    with faults.installed(plan):
        yield plan


TITANIC_CSV = "/root/reference/test-data/PassengerDataAllWithHeader.csv"


@pytest.fixture(scope="session")
def titanic_path():
    if not os.path.exists(TITANIC_CSV):
        pytest.skip("Titanic test data not available")
    return TITANIC_CSV
