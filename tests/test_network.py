"""Two in-process nodes over localhost TCP: handshake, inv/getdata/object
gossip, addr exchange.  The in-memory two-node harness the reference
lacks (SURVEY §4 takeaway)."""

import asyncio
import time

import pytest

from pybitmessage_tpu.models.objects import serialize_object
from pybitmessage_tpu.models.pow_math import pow_initial_hash, pow_target
from pybitmessage_tpu.network.dandelion import Dandelion
from pybitmessage_tpu.network.messages import (
    AddrEntry, VersionPayload, decode_addr, decode_host, decode_inv,
    encode_addr, encode_host, encode_inv, network_group,
)
from pybitmessage_tpu.network.pool import ConnectionPool, NodeContext
from pybitmessage_tpu.ops import solve
from pybitmessage_tpu.storage import Database, Inventory, KnownNodes, Peer
from pybitmessage_tpu.utils.hashes import inventory_hash


def _make_node(listen=True, dandelion_enabled=False):
    db = Database(":memory:")
    ctx = NodeContext(
        inventory=Inventory(db),
        knownnodes=KnownNodes(),
        dandelion=Dandelion(enabled=dandelion_enabled),
        port=0,
        allow_private_peers=True,  # loopback test topology
        announce_buckets=2,        # keep inv jitter inside test timeouts
    )
    pool = ConnectionPool(ctx, listen_host="127.0.0.1")
    return ctx, pool


async def _wait_for(predicate, timeout=10.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        await asyncio.sleep(interval)
    return False


# --- codec unit tests -------------------------------------------------------

def test_host_codec_round_trip():
    for host in ("127.0.0.1", "8.8.8.8", "2001:db8::1"):
        assert decode_host(encode_host(host)) == host


def test_version_payload_round_trip():
    v = VersionPayload(remote_host="10.1.2.3", remote_port=8445,
                       my_port=8446, nonce=b"12345678", streams=(1, 2))
    d = VersionPayload.decode(v.encode())
    assert d.protocol_version == 3
    assert d.remote_host == "10.1.2.3"
    assert d.remote_port == 8445  # how the sender addressed us (addrRecv)
    assert d.my_port == 8446      # the sender's own listening port (addrFrom)
    assert d.nonce == b"12345678"
    assert d.streams == (1, 2)


def test_addr_codec_round_trip():
    entries = [AddrEntry(int(time.time()), 1, 1, "9.9.9.9", 8444),
               AddrEntry(int(time.time()), 2, 3, "2001:db8::2", 8555)]
    out = decode_addr(encode_addr(entries))
    assert [(e.host, e.port, e.stream) for e in out] == \
        [("9.9.9.9", 8444, 1), ("2001:db8::2", 8555, 2)]


def test_inv_codec():
    hashes = [bytes([i]) * 32 for i in range(3)]
    assert decode_inv(encode_inv(hashes)) == hashes


def test_inv_codec_empty():
    assert decode_inv(encode_inv([])) == []
    assert encode_inv([]) == b"\x00"


def test_inv_codec_exactly_at_protocol_maximum():
    from pybitmessage_tpu.models.constants import MAX_INV_COUNT

    hashes = [i.to_bytes(32, "big") for i in range(MAX_INV_COUNT)]
    out = decode_inv(encode_inv(hashes))
    assert len(out) == MAX_INV_COUNT
    assert out[0] == hashes[0] and out[-1] == hashes[-1]
    # the encoder silently truncates one-past-maximum input rather
    # than emitting an overlong (peer-disconnecting) packet
    over = hashes + [b"\xff" * 32]
    assert len(decode_inv(encode_inv(over))) == MAX_INV_COUNT


def test_inv_codec_one_past_maximum_raises():
    from pybitmessage_tpu.models.constants import MAX_INV_COUNT
    from pybitmessage_tpu.network.messages import MessageError
    from pybitmessage_tpu.utils.varint import encode_varint

    # a hand-rolled count of MAX+1 must be refused BEFORE any length
    # check touches the (absent) hash bytes
    with pytest.raises(MessageError):
        decode_inv(encode_varint(MAX_INV_COUNT + 1))


def test_inv_codec_truncated_payload_raises():
    from pybitmessage_tpu.network.messages import MessageError
    from pybitmessage_tpu.utils.varint import encode_varint

    with pytest.raises(MessageError):
        decode_inv(encode_varint(2) + b"\x00" * 63)  # one byte short


def test_network_group_antisybil():
    assert network_group("1.2.3.4") == network_group("1.2.9.9")
    assert network_group("1.2.3.4") != network_group("1.3.3.4")
    assert network_group("2001:db8::1") == network_group("2001:db8::2")


# --- two-node integration ---------------------------------------------------

@pytest.mark.asyncio
async def test_two_nodes_sync_objects(trivial_pow):
    ctx_a, pool_a = _make_node()
    ctx_b, pool_b = _make_node()
    # this journey's subject is inv/getdata gossip, not PoW: trivial
    # deterministic difficulty (conftest) — at full difficulty the
    # test swung 60-125 s on nonce luck
    trivial_pow.apply(ctx_a)
    trivial_pow.apply(ctx_b)

    # node A owns an object before the nodes ever meet
    payload = trivial_pow.solved_object(b"pre-existing object body")
    h_pre = inventory_hash(payload)
    hdr_expires = int.from_bytes(payload[8:16], "big")
    ctx_a.inventory.add(h_pre, 2, 1, payload, hdr_expires)

    await pool_a.start()
    await pool_b.start(listen=False)
    try:
        conn = await pool_b.connect_to(Peer("127.0.0.1", pool_a.listen_port))
        assert conn is not None
        assert await _wait_for(lambda: conn.fully_established), \
            "handshake did not complete"

        # B learns of A's object via big inv and downloads it
        assert await _wait_for(lambda: h_pre in ctx_b.inventory), \
            "object did not sync via big inv"
        assert ctx_b.inventory[h_pre].payload == payload

        # now A generates a NEW object; B must receive it via inv gossip
        payload2 = trivial_pow.solved_object(b"fresh object")
        h2 = inventory_hash(payload2)
        ctx_a.inventory.add(h2, 2, 1, payload2,
                            int.from_bytes(payload2[8:16], "big"))
        pool_a.announce_object(h2, local=True)
        assert await _wait_for(lambda: h2 in ctx_b.inventory), \
            "gossip of fresh object failed"

        # B's received-object queue saw both
        assert ctx_b.object_queue.qsize() == 2
    finally:
        await pool_b.stop()
        await pool_a.stop()


@pytest.mark.asyncio
async def test_bad_pow_object_rejected_and_connection_dropped():
    ctx_a, pool_a = _make_node()
    ctx_b, pool_b = _make_node()
    await pool_a.start()
    await pool_b.start(listen=False)
    try:
        conn = await pool_b.connect_to(Peer("127.0.0.1", pool_a.listen_port))
        assert await _wait_for(lambda: conn.fully_established)

        expires = int(time.time()) + 600
        bogus = serialize_object(expires, 2, 1, 1, b"no pow done", nonce=7)
        await conn.send_packet("object", bogus)
        # A must reject it and drop the connection
        assert await _wait_for(lambda: not pool_a.established())
        assert inventory_hash(bogus) not in ctx_a.inventory
    finally:
        await pool_b.stop()
        await pool_a.stop()


@pytest.mark.asyncio
async def test_self_connection_detected():
    ctx_a, pool_a = _make_node()
    await pool_a.start()
    try:
        # same nonce on both ends -> "connection to self" detected
        pool_b = ConnectionPool(ctx_a, listen_host="127.0.0.1")
        conn = await pool_b.connect_to(Peer("127.0.0.1", pool_a.listen_port))
        assert conn is not None
        assert not await _wait_for(
            lambda: conn.fully_established, timeout=1.0)
    finally:
        await pool_a.stop()


@pytest.mark.asyncio
async def test_addr_gossip_populates_knownnodes():
    ctx_a, pool_a = _make_node()
    ctx_b, pool_b = _make_node()
    ctx_a.knownnodes.add(Peer("203.0.113.7", 8444))
    await pool_a.start()
    await pool_b.start(listen=False)
    try:
        conn = await pool_b.connect_to(Peer("127.0.0.1", pool_a.listen_port))
        assert await _wait_for(lambda: conn.fully_established)
        assert await _wait_for(
            lambda: Peer("203.0.113.7", 8444) in ctx_b.knownnodes.peers())
    finally:
        await pool_b.stop()
        await pool_a.stop()


@pytest.mark.asyncio
async def test_verack_before_version_is_rejected():
    """A bare verack as the first packet must not establish the
    connection — it would bypass every peerValidityChecks gate
    (nonce/self-connect, protocol floor, time offset, streams)."""
    from pybitmessage_tpu.models.packet import pack_packet

    ctx_a, pool_a = _make_node()
    await pool_a.start()
    try:
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", pool_a.listen_port)
        writer.write(pack_packet("verack"))
        await writer.drain()
        # server must drop us without ever sending its own verack or
        # any establishment traffic (addr sample / big inv)
        data = await asyncio.wait_for(reader.read(4096), timeout=5)
        while True:
            more = await asyncio.wait_for(reader.read(4096), timeout=5)
            if not more:
                break
            data += more
        assert b"verack" not in data
        assert b"addr" not in data
        assert not any(c.fully_established for c in pool_a.connections())
        writer.close()
    finally:
        await pool_a.stop()


@pytest.mark.asyncio
async def test_download_throttle_paces_before_buffering():
    """maxdownloadrate is enforced at recv granularity: tokens are
    consumed BEFORE each chunk is read, so a large object cannot be
    slurped in one burst and accounted afterwards (VERDICT r3 weak #4;
    reference asyncore_pollchoose.py:109-130)."""
    ctx_a, pool_a = _make_node()
    ctx_b, pool_b = _make_node()
    # test-mode difficulty: a 60 kB object at full difficulty would
    # take minutes of CPU PoW to construct
    ctx_a.pow_ntpb = ctx_a.pow_extra = 10
    ctx_b.pow_ntpb = ctx_b.pow_extra = 10
    body = b"x" * 60_000
    ttl = 600
    expires = int(time.time()) + ttl
    obj = serialize_object(expires, 2, 1, 1, body)
    # clamp=False: without it the 10/10 test params are silently
    # clamped up to the network minimum (1000) and the setup PoW
    # becomes a 100x harder, minutes-long CPU solve
    target = pow_target(len(obj), ttl, 10, 10, clamp=False)
    nonce, _ = solve(pow_initial_hash(obj[8:]), target,
                     lanes=8192, chunks_per_call=16)
    payload = nonce.to_bytes(8, "big") + obj[8:]
    h = inventory_hash(payload)
    ctx_a.inventory.add(h, 2, 1, payload, expires)
    # B may download at most 30 kB/s -> the 60 kB transfer must take
    # >= ~1 s net of the bucket's initial one-second burst allowance
    ctx_b.download_bucket.rate = 30 * 1024
    ctx_b.download_bucket._tokens = float(ctx_b.download_bucket.rate)
    await pool_a.start()
    await pool_b.start(listen=False)
    try:
        t0 = time.time()
        conn = await pool_b.connect_to(Peer("127.0.0.1",
                                            pool_a.listen_port))
        assert conn is not None
        # generous ceiling for suite-load slack (the minimum-elapsed
        # assertion below is the real check; nothing here compiles —
        # the bare NodeContext verifies PoW with pure hashlib)
        assert await _wait_for(lambda: h in ctx_b.inventory, timeout=120), \
            "throttled object never arrived"
        elapsed = time.time() - t0
        # 60 kB at 30 kB/s with a one-second initial burst: >= ~1 s;
        # unthrottled this completes in well under 0.5 s
        assert elapsed >= 0.9, f"transfer outran the bucket ({elapsed:.2f}s)"
    finally:
        await pool_b.stop()
        await pool_a.stop()


# --- a peer link carries what a fast sender publishes (ISSUE 37) -------------


def test_the_download_window_gates_a_hand_out_and_does_not_size_it():
    """Reference semantics (randomtrackingdict.py): below ``max_pending``
    keys out, a poll hands out its whole count; at or above, none, until
    arrivals take keys out of the window again.  Sized by the window a
    poll a second fetched ten objects a second from a peer."""
    from pybitmessage_tpu.utils.randomtracking import RandomTrackingDict
    d = RandomTrackingDict()
    for k in range(300):
        d[k] = True
    first = d.random_keys(100)
    assert len(first) == len(set(first)) == 100
    assert d.random_keys(100) == []         # 100 out: the window is shut
    for k in first[:90]:
        del d[k]
    assert d.random_keys(100) == []         # ten still out
    del d[first[90]]
    second = d.random_keys(1000)            # nine out: all the rest
    assert len(second) == 200 - 9 + 9 and not set(second) & set(first)
    assert sorted(second + first[91:]) == sorted(d)
    # a key handed out and never delivered is eligible again in time
    e = RandomTrackingDict()
    e.pending_timeout = 0.0
    e["k"] = True
    assert e.random_keys(5) == ["k"] and e.random_keys(5) == ["k"]


class _UploadConn:
    """A connection as ``_upload_loop`` sees it: a getdata backlog, and
    ``flush_uploads`` serving ten of it (none while ``armed``: the
    anti-intersection delay; never returning while ``wedged``: a peer
    that does not read, ``writer.drain()`` has no deadline)."""
    host = "peer"

    def __init__(self, backlog, armed=False, wedged=False):
        self.pending_upload = list(range(backlog))
        self.armed, self.wedged, self.rounds = armed, wedged, 0

    async def flush_uploads(self, limit=10):
        self.rounds += 1
        if self.wedged:
            await asyncio.Event().wait()
        served = 0 if self.armed else min(limit, len(self.pending_upload))
        del self.pending_upload[:served]
        return served


async def _run_upload_loop(monkeypatch, conns, until, interval=0.05):
    import pybitmessage_tpu.network.pool as pool_mod
    monkeypatch.setattr(pool_mod, "UPLOAD_INTERVAL", interval)

    class Pool:
        established = staticmethod(lambda: conns)
        _upload_round = ConnectionPool._upload_round

    task = asyncio.create_task(ConnectionPool._upload_loop(Pool()))
    try:
        t0 = time.monotonic()
        assert await _wait_for(until, timeout=5.0)
        return time.monotonic() - t0
    finally:
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)


@pytest.mark.asyncio
async def test_upload_backlogs_are_served_round_after_round(monkeypatch):
    """The upload loop keeps serving, ten a connection a round, while
    any connection was served, and rests only after a round that served
    nothing (reference uploadthread.py); a connection inside its
    anti-intersection delay serves nothing and does not hold it up."""
    conns = [_UploadConn(35), _UploadConn(4), _UploadConn(50, armed=True)]
    took = await _run_upload_loop(
        monkeypatch, conns, interval=0.5,
        until=lambda: not conns[0].pending_upload)
    assert took < 0.5                       # four rounds and not one rest
    assert [len(c.pending_upload) for c in conns] == [0, 0, 50]
    assert conns[0].rounds == 4             # 10, 10, 10, 5
    await asyncio.sleep(0.1)
    assert conns[2].rounds <= 6             # ... and then it rests


@pytest.mark.asyncio
async def test_a_peer_that_does_not_read_holds_up_nobody_else(monkeypatch):
    """A peer with a large backlog whose socket does not drain is given
    its ten once and no more until they are through; the others are
    still served a round an ``UPLOAD_INTERVAL``, the pace a link always
    had (REVIEW of PR 37: the loop must not wait for every backlog)."""
    wedged, other = _UploadConn(50000, wedged=True), _UploadConn(35)
    await _run_upload_loop(monkeypatch, [wedged, other],
                           until=lambda: not other.pending_upload)
    assert wedged.rounds == 1 and other.rounds == 4


@pytest.mark.asyncio
async def test_getdata_goes_out_while_an_upload_is_stuck(trivial_pow,
                                                         monkeypatch):
    """The download loop asks for objects on its own cadence whatever
    the upload loop is waiting for: with every ``flush_uploads`` of the
    serving node stuck, a hundred objects announced TO it are still
    requested and arrive."""
    from pybitmessage_tpu.network.connection import BMConnection
    ctx_a, pool_a = _make_node()
    ctx_b, pool_b = _make_node()
    trivial_pow.apply(ctx_a)
    trivial_pow.apply(ctx_b)
    hashes = []
    for i in range(100):
        payload = trivial_pow.solved_object(b"object %d of a hundred" % i)
        hashes.append(inventory_hash(payload))
        ctx_a.inventory.add(hashes[-1], 2, 1, payload,
                            int.from_bytes(payload[8:16], "big"))
    await pool_a.start()
    await pool_b.start(listen=False)
    flush = BMConnection.flush_uploads

    async def stuck_on_b(self, limit=10):
        if self.ctx is ctx_b:               # B's uploads never return
            await asyncio.Event().wait()
        return await flush(self, limit)

    monkeypatch.setattr(BMConnection, "flush_uploads", stuck_on_b)
    try:
        conn = await pool_b.connect_to(Peer("127.0.0.1", pool_a.listen_port))
        assert conn is not None
        assert await _wait_for(lambda: conn.fully_established)
        conn.pending_upload.append(hashes[0])   # B owes A one, for ever
        assert await _wait_for(
            lambda: all(h in ctx_b.inventory for h in hashes), timeout=7.0)
    finally:
        await pool_b.stop()
        await pool_a.stop()


@pytest.mark.asyncio
async def test_a_hundred_objects_cross_a_link_in_seconds(trivial_pow):
    """Before PR 37 a link carried ten objects a second in each
    direction (a download window of ten polled once a second, an upload
    round of ten a second): a hundred objects took ten seconds."""
    ctx_a, pool_a = _make_node()
    ctx_b, pool_b = _make_node()
    trivial_pow.apply(ctx_a)
    trivial_pow.apply(ctx_b)
    hashes = []
    for i in range(100):
        payload = trivial_pow.solved_object(b"object %d of a hundred" % i)
        hashes.append(inventory_hash(payload))
        ctx_a.inventory.add(hashes[-1], 2, 1, payload,
                            int.from_bytes(payload[8:16], "big"))
    await pool_a.start()
    await pool_b.start(listen=False)
    try:
        conn = await pool_b.connect_to(Peer("127.0.0.1", pool_a.listen_port))
        assert conn is not None
        assert await _wait_for(lambda: conn.fully_established)
        t0 = time.monotonic()
        assert await _wait_for(
            lambda: all(h in ctx_b.inventory for h in hashes), timeout=7.0), \
            "%d of 100 objects after 7 s" % sum(
                h in ctx_b.inventory for h in hashes)
        assert time.monotonic() - t0 < 7.0
    finally:
        await pool_b.stop()
        await pool_a.stop()
