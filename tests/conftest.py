"""Test fixtures.

Forces an 8-device virtual CPU platform BEFORE jax initializes, so all mesh /
collective / sharding tests exercise real multi-device SPMD semantics on one
host (ref test strategy: cluster_utils.Cluster runs multi-node on one box;
here the analogue is a virtual 8-chip mesh).
"""

import os

# Force the virtual 8-device CPU platform whatever the machine exports (a
# TPU host sets JAX_PLATFORMS=tpu,cpu): the env var covers child processes,
# jax.config covers a jax that something imported before this file.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
assert jax.device_count() == 8, (
    f"tests need the virtual 8-device CPU mesh, got {jax.devices()}"
)

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: reference-scale envelope benchmarks (excluded from tier-1 "
        "runs via -m 'not slow')")


@pytest.fixture
def ray_start_regular():
    """(ref: python/ray/tests/conftest.py:532 ray_start_regular)"""
    import ray_tpu

    runtime = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield runtime
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-(virtual-)node cluster fixture (ref: conftest.py:613 ray_start_cluster)."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    yield cluster
    cluster.shutdown()
