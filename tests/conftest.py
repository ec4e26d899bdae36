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

import faulthandler
import sys

import pytest

#: seconds after which a test that is still running has every thread's stack
#: written to stderr (and so to the run's log): a run the clock cuts (exit
#: code 124) then names the test that was waiting and where.  The longest
#: tier-1 test takes about 80 s.
HANG_DUMP_S = 150
#: the process's stderr as ``pytest_configure`` finds it (an xdist worker's is
#: the run's own): while a test runs, pytest's capture holds descriptor 2
_STDERR = pytest.StashKey[int]()


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    faulthandler.dump_traceback_later(HANG_DUMP_S,
                                      file=item.config.stash[_STDERR])
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()


def pytest_collection_modifyitems(items):
    """``tests/test_families.py`` family-major: a family's cases side by
    side, in the order of their first appearance, so that the contiguous
    chunks xdist hands a worker under ``--dist load`` hold most of one
    family and what ``tests/families.py`` builds once a process is built
    once."""
    def family(item):
        return getattr(item, "callspec", None) and item.callspec.params.get(
            "family")

    slots = [i for i, item in enumerate(items)
             if item.path.name == "test_families.py" and family(item)]
    first = {}
    for i in slots:
        first.setdefault(family(items[i]), len(first))
    ordered = sorted((items[i] for i in slots),
                     key=lambda item: first[family(item)])
    for i, item in zip(slots, ordered):
        items[i] = item


def pytest_configure(config):
    config.stash[_STDERR] = os.dup(sys.__stderr__.fileno())
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
