"""Shared fixtures.

Parity target: reference python/ray/tests/conftest.py (ray_start_regular:580,
shutdown_only:497, ray_start_cluster:668). Sharding tests run on a virtual
8-device CPU mesh (xla_force_host_platform_device_count), the load-bearing
mechanism for testing multi-chip SPMD without TPU hardware.
"""

import os

# Must be set before jax import anywhere in the test process tree.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402

import ray_tpu  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """`perf`-marked tests (bench smoke) run only on request (RT_RUN_PERF=1):
    they time things, so they are useless under tier-1's parallel load and
    would eat its time budget."""
    if os.environ.get("RT_RUN_PERF"):
        return
    skip = pytest.mark.skip(
        reason="perf smoke; set RT_RUN_PERF=1 to run (not part of tier-1)")
    for item in items:
        if "perf" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def device_plane_cpu():
    """Guard for device-object-plane tests under the tier-1 CPU backend:
    cpu jax.Arrays exercise the exact same DeviceObjectTable / placeholder /
    refcount / free-fan-out paths as TPU-resident ones (only the
    device_put target differs), so the plane is fully testable here. Skips
    cleanly if jax is unavailable, and asserts the plane wasn't disabled
    by ambient env (RT_DEVICE_OBJECTS) — these tests are about the plane."""
    jax = pytest.importorskip("jax")
    if os.environ.get("RT_DEVICE_OBJECTS", "").lower() in ("0", "false", "no"):
        pytest.skip("device object plane disabled via RT_DEVICE_OBJECTS")
    yield jax


@pytest.fixture
def shutdown_only():
    yield None
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_2cpu(shutdown_only):
    ray_tpu.init(num_cpus=2)
    yield


@pytest.fixture
def ray_start_4cpu(shutdown_only):
    ray_tpu.init(num_cpus=4)
    yield


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_node_args={"num_cpus": 1})
    yield cluster
    ray_tpu.shutdown()
    cluster.shutdown()
