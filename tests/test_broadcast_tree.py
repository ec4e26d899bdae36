"""Broadcast fan-out tree tests (owner-coordinated pull redirection).

The tree protocol (OP_PULL_LOC / OP_ANNOUNCE) is exercised both at the
wire level (raw client sockets with explicit requester addresses — the
owner's grant/holder bookkeeping) and end-to-end through PullManager
instances backed by real stores + servers in this process.  Ref: the
reference's 1 GiB broadcast anchor — owner egress must stay O(fanout),
not O(N).
"""

import socket
import struct
import time

import numpy as np
import pytest

from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu._private.ids import ObjectID
from ray_tpu._private.object_store import ObjectStore
from ray_tpu._private.object_transfer import (
    OP_ANNOUNCE,
    OP_PULL_LOC,
    ST_NOT_FOUND,
    ST_OK,
    ST_PENDING,
    ObjectTransferServer,
    PullManager,
    _recv_exact,
    _req_header,
)


def _connect(addr):
    host, port = addr.rsplit(":", 1)
    sock = socket.create_connection((host, int(port)), timeout=5)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return sock


def _negotiate(addr, oid, requester):
    """One OP_PULL_LOC round trip: returns (status, tree, source)."""
    rb = requester.encode()
    with _connect(addr) as sock:
        sock.sendall(_req_header(OP_PULL_LOC, oid)
                     + struct.pack("<H", len(rb)) + rb)
        status = _recv_exact(sock, 1)[0]
        if status != ST_OK:
            return status, False, ""
        tree = _recv_exact(sock, 1)[0] != 0
        (alen,) = struct.unpack("<H", _recv_exact(sock, 2))
        src = _recv_exact(sock, alen).decode() if alen else ""
        return status, tree, src


def _announce(addr, oid, requester):
    rb = requester.encode()
    with _connect(addr) as sock:
        sock.sendall(_req_header(OP_ANNOUNCE, oid)
                     + struct.pack("<H", len(rb)) + rb)
        assert _recv_exact(sock, 1)[0] == ST_OK


@pytest.fixture()
def tree_cfg():
    prev = (GLOBAL_CONFIG.broadcast_tree_enabled,
            GLOBAL_CONFIG.broadcast_tree_min_bytes,
            GLOBAL_CONFIG.broadcast_tree_fanout)
    GLOBAL_CONFIG.broadcast_tree_enabled = True
    GLOBAL_CONFIG.broadcast_tree_min_bytes = 1 << 16
    GLOBAL_CONFIG.broadcast_tree_fanout = 1
    yield
    (GLOBAL_CONFIG.broadcast_tree_enabled,
     GLOBAL_CONFIG.broadcast_tree_min_bytes,
     GLOBAL_CONFIG.broadcast_tree_fanout) = prev


@pytest.fixture()
def owner_server(tree_cfg):
    store = ObjectStore(capacity_bytes=64 << 20)
    server = ObjectTransferServer(lambda: store)
    yield store, server
    server.stop()
    store.shutdown()


def _put_big(store, key="big", n=1 << 17):
    oid = ObjectID(key)
    store.put_serialized(oid, b"x" * n)
    return oid


def test_small_object_negotiates_direct_without_tree(owner_server):
    store, server = owner_server
    oid = ObjectID("small")
    store.put_serialized(oid, b"y" * 64)  # below broadcast_tree_min_bytes
    status, tree, src = _negotiate(server.addr, oid, "127.0.0.1:9001")
    assert (status, tree, src) == (ST_OK, False, "")


def test_unknown_object_negotiation_not_found(owner_server):
    _, server = owner_server
    status, _, _ = _negotiate(server.addr, ObjectID("nope"), "127.0.0.1:9001")
    assert status == ST_NOT_FOUND


def test_fanout_cap_parks_excess_pullers(owner_server):
    # fanout=1: first requester gets an owner-direct grant, the second is
    # told to retry (no complete holder exists yet).
    store, server = owner_server
    oid = _put_big(store)
    status, tree, src = _negotiate(server.addr, oid, "127.0.0.1:9001")
    assert (status, tree, src) == (ST_OK, True, "")
    status, _, _ = _negotiate(server.addr, oid, "127.0.0.1:9002")
    assert status == ST_PENDING


def test_announce_turns_holder_into_redirect_target(owner_server):
    store, server = owner_server
    oid = _put_big(store)
    assert _negotiate(server.addr, oid, "127.0.0.1:9001")[2] == ""
    _announce(server.addr, oid, "127.0.0.1:9001")
    # The grant slot freed AND the announcer became a source: the next
    # puller is redirected to it instead of the owner.
    status, tree, src = _negotiate(server.addr, oid, "127.0.0.1:9002")
    assert (status, tree, src) == (ST_OK, True, "127.0.0.1:9001")
    assert server.stats()["redirects"] == 1


def test_renegotiation_after_failed_peer_regrants_owner(owner_server):
    # A requester that re-negotiates (its peer pull failed) must get an
    # owner-direct grant — one bad peer can't wedge it.
    store, server = owner_server
    oid = _put_big(store)
    _negotiate(server.addr, oid, "127.0.0.1:9001")
    _announce(server.addr, oid, "127.0.0.1:9001")
    assert _negotiate(server.addr, oid, "127.0.0.1:9002")[2] \
        == "127.0.0.1:9001"
    status, tree, src = _negotiate(server.addr, oid, "127.0.0.1:9002")
    assert (status, tree, src) == (ST_OK, True, "")


def test_holder_is_never_redirected_to_itself(owner_server):
    store, server = owner_server
    oid = _put_big(store)
    _negotiate(server.addr, oid, "127.0.0.1:9001")
    _announce(server.addr, oid, "127.0.0.1:9001")
    # The holder itself re-negotiating (e.g. it freed its copy) must not
    # be told to pull from its own address.
    status, tree, src = _negotiate(server.addr, oid, "127.0.0.1:9001")
    assert src != "127.0.0.1:9001"


def test_value_tier_size_hint_gates_tree(owner_server):
    # A big value put() without serialization must still engage the tree:
    # size_hint probes nbytes/len without serializing.
    store, server = owner_server
    oid = ObjectID("val")
    store.put(oid, np.zeros(1 << 15, dtype=np.float64))  # 256 KiB nbytes
    status, tree, src = _negotiate(server.addr, oid, "127.0.0.1:9001")
    assert (status, tree, src) == (ST_OK, True, "")


def test_end_to_end_redirected_pull_and_egress(tree_cfg):
    # owner + peer B (a holder) + puller C: C is redirected to B, the
    # payload bytes leave B (not the owner), and C announces itself.
    owner = ObjectStore(capacity_bytes=64 << 20)
    b_store = ObjectStore(capacity_bytes=64 << 20)
    c_store = ObjectStore(capacity_bytes=64 << 20)
    owner_srv = ObjectTransferServer(lambda: owner)
    b_srv = ObjectTransferServer(lambda: b_store)
    c_srv = ObjectTransferServer(lambda: c_store)  # last: local addr = C
    pm_b = PullManager(b_store)
    pm_c = PullManager(c_store)
    try:
        payload = np.arange(1 << 16, dtype=np.float64)  # 512 KiB
        oid = ObjectID("bcast")
        owner.put(oid, payload)
        # B pulls owner-direct (no negotiation: B can't name itself while
        # the process-local server addr points at C) and announces.
        pm_b.pull_blocking(oid, owner_srv.addr, timeout=30)
        _announce(owner_srv.addr, oid, b_srv.addr)
        before = owner_srv.stats()["by_object"].get(str(oid), 0)
        pm_c.pull_blocking(oid, owner_srv.addr, timeout=30)
        np.testing.assert_array_equal(c_store.get(oid, timeout=5), payload)
        # C's bytes came from B, not the owner.  (B's serving thread books
        # the egress after its last send returns, which C's receive can
        # beat: wait for the booking, not for the bytes.)
        deadline = time.monotonic() + 5.0
        while b_srv.stats()["by_object"].get(str(oid), 0) < payload.nbytes \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert b_srv.stats()["by_object"].get(str(oid), 0) \
            >= payload.nbytes
        assert owner_srv.stats()["by_object"].get(str(oid), 0) == before
        assert pm_c.stats["sources"].get(b_srv.addr, 0) >= payload.nbytes
        # C announced: the owner now lists it as a redirect target.
        with owner_srv._bcast_lock:
            holders = list(owner_srv._bcast[oid]["holders"])
        assert c_srv.addr in holders
    finally:
        for srv in (owner_srv, b_srv, c_srv):
            srv.stop()
        for st in (owner, b_store, c_store):
            st.shutdown()
