"""Node-to-node object plane tests: two OS processes, ownership-routed pulls.

The child process (tests/_objxfer_child.py) is the owner node: it runs an
object server and holds the primary copies.  This process is the borrower
node: it resolves each ref's owner address (stamped at pickle time —
ownership-based directory) and pulls the object through the PullManager.
Ref: src/ray/object_manager/object_manager.h:117, pull_manager.h:52.
"""

import base64
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import object_transfer, serialization
from ray_tpu._private.ids import ObjectID
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu.exceptions import ObjectLostError

CHILD = os.path.join(os.path.dirname(__file__), "_objxfer_child.py")


@pytest.fixture(scope="module")
def owner_node():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_OBJECT_TRANSFER_PULL_TIMEOUT_S"] = "5"
    proc = subprocess.Popen(
        [sys.executable, CHILD], env=env, stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=False)
    line = proc.stdout.readline().decode()
    assert line.startswith("REFS "), (
        line + proc.stderr.read(4000).decode(errors="replace"))
    refs = serialization.loads(base64.b64decode(line.split()[1]))
    yield refs
    proc.stdin.close()
    proc.wait(timeout=30)


@pytest.fixture()
def borrower():
    ray_tpu.init(ignore_reinit_error=True)
    yield
    # Keep the runtime for the other tests in this module (module-scoped
    # child stays up); individual tests clean their own refs.


def test_pull_small_object(owner_node, borrower):
    val = ray_tpu.get(owner_node["small"], timeout=30)
    assert val == {"kind": "small", "payload": list(range(32))}


def test_pull_large_object_chunked(owner_node, borrower):
    big = ray_tpu.get(owner_node["big"], timeout=60)
    assert isinstance(big, np.ndarray) and big.shape == (6_000_000,)
    assert float(big.sum()) == owner_node["big_sum"]


def test_pull_task_return(owner_node, borrower):
    out = ray_tpu.get(owner_node["task"], timeout=30)
    np.testing.assert_array_equal(out, np.full(1000, 7, dtype=np.int32))


def test_pull_spilled_object_restores(owner_node, borrower):
    spilled = ray_tpu.get(owner_node["spill"], timeout=60)
    assert spilled.shape == (2_000_000,) and spilled[0] == 1.0


def test_remote_ref_as_task_dependency(owner_node, borrower):
    # A remote-owned ref passed as a task arg triggers a dependency pull
    # (the DependencyManager path), not just ray.get.
    @ray_tpu.remote
    def total(x):
        return float(np.sum(x))

    # Re-pickle the ref so the arg carries the owner address even though the
    # local store may already have it cached from earlier tests.
    ref = owner_node["task"]
    assert ray_tpu.get(total.remote(ref), timeout=30) == 7000.0


def test_concurrent_pulls_are_deduplicated(owner_node, borrower):
    from ray_tpu._private.runtime import get_runtime

    rt = get_runtime()
    rt.store.free(owner_node["big"].id)  # drop the cache to force a re-pull
    before = rt._pull_manager().stats["pulls"]
    results = [None] * 4

    def fetch(i):
        results[i] = ray_tpu.get(owner_node["big"], timeout=60)

    threads = [threading.Thread(target=fetch, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert all(r is not None and r.shape == (6_000_000,) for r in results)
    # One transfer served all four getters.
    assert rt._pull_manager().stats["pulls"] == before + 1


def test_wait_on_remote_ref(owner_node, borrower):
    from ray_tpu._private.runtime import get_runtime

    get_runtime().store.free(owner_node["small"].id)
    ready, pending = ray_tpu.wait([owner_node["small"]], timeout=30)
    assert len(ready) == 1 and not pending


def test_contains_and_push(owner_node, borrower):
    from ray_tpu._private.runtime import get_runtime

    rt = get_runtime()
    rt.start_object_server()
    addr = owner_node["addr"]
    ref = ray_tpu.put(np.arange(10))
    rt.store.get_serialized(ref.id)  # materialize wire form
    object_transfer.push(rt.store, ref.id, addr, owner="borrower")
    assert object_transfer.contains(addr, ref.id)
    # And the owner can be asked to drop the pushed cache copy.
    object_transfer.free_remote(addr, ref.id)
    assert not object_transfer.contains(addr, ref.id)


def test_free_remote_refuses_primary_with_live_refs(borrower):
    """ADVICE r2: OP_FREE drops CACHED copies only — a peer must not be able
    to evict a primary copy that still has live local references."""
    from ray_tpu._private.runtime import get_runtime

    rt = get_runtime()
    addr = rt.start_object_server()
    ref = ray_tpu.put(np.arange(5))
    rt.store.get_serialized(ref.id)  # materialize wire form
    object_transfer.free_remote(addr, ref.id)  # must be refused
    assert rt.store.contains(ref.id)
    assert list(ray_tpu.get(ref)) == list(range(5))


def test_pull_waits_for_slow_producer(owner_node, borrower):
    # The producing task sleeps past the owner's serve-wait slice, so the
    # borrower sees ST_PENDING and keeps retrying — a long-running producer
    # must not be misreported as object loss (it is merely pending).
    assert ray_tpu.get(owner_node["slow"], timeout=60) == "slow-done"


def test_remote_task_failure_propagates_original_error(owner_node, borrower):
    # The producing task raised ValueError on the owner node; a cross-node
    # get must surface THAT error (task-failure parity), not ObjectLost.
    with pytest.raises(Exception) as ei:
        ray_tpu.get(owner_node["fail"], timeout=30)
    assert "intentional producer failure" in str(ei.value)
    assert not isinstance(ei.value, ObjectLostError)


def test_pull_unknown_object_raises(owner_node, borrower):
    ghost = ObjectRef(ObjectID.from_random(), owner="ghost",
                      owner_addr=owner_node["addr"])
    with pytest.raises(ObjectLostError):
        ray_tpu.get(ghost, timeout=20)


def test_pull_unreachable_owner_raises(borrower):
    ghost = ObjectRef(ObjectID.from_random(), owner="ghost",
                      owner_addr="127.0.0.1:1")  # nothing listens here
    with pytest.raises(ObjectLostError):
        ray_tpu.get(ghost, timeout=10)


# --------------------------------------------------------------------------
# r5 zero-copy plane: same-host arena handoff, sendfile socket path, range
# streams, pooled connections (ref: object_buffer_pool.h zero-copy chunk
# reads, push_manager.h parallel chunked transfer).
# --------------------------------------------------------------------------
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu._private.object_store import ObjectStore
from ray_tpu._private.object_transfer import ObjectTransferServer, PullManager


@pytest.fixture()
def store_pair():
    owner = ObjectStore(capacity_bytes=256 << 20)
    puller = ObjectStore(capacity_bytes=256 << 20)
    server = ObjectTransferServer(lambda: owner)
    pm = PullManager(puller)
    yield owner, puller, server, pm
    server.stop()
    owner.shutdown()
    puller.shutdown()


def _roundtrip(owner, puller, pm, addr, key, value):
    oid = ObjectID(key)
    owner.put(oid, value)
    pm.pull_blocking(oid, addr, timeout=30)
    got = puller.get(oid, timeout=5)
    np.testing.assert_array_equal(got, value)
    return oid


def test_same_host_handoff_engages(store_pair):
    # Same host: the puller maps the owner's arena file and lands the
    # payload with one memcpy — no socket payload bytes at all.
    owner, puller, server, pm = store_pair
    _roundtrip(owner, puller, pm, server.addr, "h1",
               np.arange(1 << 18, dtype=np.float64))
    assert pm.stats["handoffs"] == 1
    assert pm.stats["handoff_bytes"] > (1 << 21)


def test_socket_path_with_handoff_disabled(store_pair):
    # Socket path: server sendfiles from the arena, client lands the bytes
    # straight into a pre-created arena buffer (create_for_receive).
    owner, puller, server, pm = store_pair
    prev = GLOBAL_CONFIG.same_host_handoff
    GLOBAL_CONFIG.same_host_handoff = False
    try:
        _roundtrip(owner, puller, pm, server.addr, "s1",
                   np.arange(1 << 18, dtype=np.float64))
        assert pm.stats["handoffs"] == 0
        assert pm.stats["pulls"] == 1
    finally:
        GLOBAL_CONFIG.same_host_handoff = prev


def test_parallel_range_pull_streams(store_pair):
    # A large object split across concurrent range streams arrives intact.
    owner, puller, server, pm = store_pair
    prev = (GLOBAL_CONFIG.same_host_handoff,
            GLOBAL_CONFIG.parallel_pull_streams,
            GLOBAL_CONFIG.parallel_pull_chunk_bytes)
    GLOBAL_CONFIG.same_host_handoff = False
    GLOBAL_CONFIG.parallel_pull_streams = 3
    GLOBAL_CONFIG.parallel_pull_chunk_bytes = 1 << 20
    try:
        value = np.random.default_rng(0).integers(
            0, 255, size=6 << 20, dtype=np.uint8)  # ~6 MiB -> 6 ranges
        _roundtrip(owner, puller, pm, server.addr, "r1", value)
    finally:
        (GLOBAL_CONFIG.same_host_handoff,
         GLOBAL_CONFIG.parallel_pull_streams,
         GLOBAL_CONFIG.parallel_pull_chunk_bytes) = prev


def test_pooled_connections_reused(store_pair):
    owner, puller, server, pm = store_pair
    for i in range(4):
        _roundtrip(owner, puller, pm, server.addr, f"p{i}",
                   np.full(1024, float(i)))
    # After the pulls, at least one idle connection is parked in the pool
    # and subsequent pulls keep working through it.
    assert any(pool for pool in pm._socks.values())
    _roundtrip(owner, puller, pm, server.addr, "p-again", np.zeros(8))


def test_push_lands_in_receiver_arena(store_pair):
    owner, puller, server, pm = store_pair
    receiver_srv = ObjectTransferServer(lambda: puller)
    try:
        oid = ObjectID("pushed1")
        value = np.arange(1 << 16, dtype=np.int64)
        owner.put(oid, value)
        object_transfer.push(owner, oid, receiver_srv.addr)
        np.testing.assert_array_equal(puller.get(oid, timeout=5), value)
    finally:
        receiver_srv.stop()


def test_push_large_object_partial_sendfile(store_pair):
    # Larger than the socket send buffer: the client socket has a timeout
    # (non-blocking under the hood), so sendfile hits EAGAIN mid-stream and
    # must wait-and-continue — never restart the payload (which would land
    # corrupt bytes).  Regression for the r5 review finding.
    owner, puller, server, pm = store_pair
    receiver_srv = ObjectTransferServer(lambda: puller)
    try:
        oid = ObjectID("pushed-big")
        value = np.random.default_rng(7).integers(
            0, 255, size=32 << 20, dtype=np.uint8)  # 32 MiB
        owner.put(oid, value)
        object_transfer.push(owner, oid, receiver_srv.addr)
        np.testing.assert_array_equal(puller.get(oid, timeout=10), value)
    finally:
        receiver_srv.stop()
