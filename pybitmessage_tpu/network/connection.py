"""One peer connection: framed packets, handshake, command dispatch.

Replaces the reference's AdvancedDispatcher + BMProto state machine
(src/network/advanceddispatcher.py, bmproto.py) with a single asyncio
reader task per connection.  Wire behavior kept: 24-byte header with
magic resync (bmproto.py:85-104), sha512/4 checksum, version validity
checks (bmproto.py:563-643), big-inv sync on establishment
(tcp.py:210-253), addr sample exchange (tcp.py:175-208).
"""

from __future__ import annotations

import asyncio
import logging
import random
import struct
import time
from collections import deque
from typing import TYPE_CHECKING

from ..models.constants import (
    MAGIC, MAX_MESSAGE_SIZE, MAX_OBJECT_COUNT, MAX_TIME_OFFSET,
    NODE_DANDELION, NODE_SSL, NODE_SYNC, NODE_TRACE, PROTOCOL_VERSION,
)
from ..models.objects import (ObjectError, ObjectHeader, check_by_type,
                              extract_tag)
from ..models.packet import (
    HEADER_LEN, PacketError, pack_packet, unpack_header, verify_payload,
)
from ..models.pow_math import check_pow
from ..observability import REGISTRY
from ..observability.lifecycle import LIFECYCLE
from ..observability.tracing import (
    TRACE_CTX_INVALID, TRACE_CTX_LEN, TRACE_CTX_RECEIVED, TRACE_CTX_SENT,
    SkewEstimator, TraceContext,
)
from ..resilience import inject
from ..resilience.policy import ERRORS
from ..utils.hashes import inventory_hash
from ..utils.varint import VarintError
from .bufpool import COPIED_MATERIALIZE, RECV_POOL, PooledBuffer
from .messages import (
    AddrEntry, MessageError, VersionPayload, append_trace_ctx,
    decode_addr, decode_inv, encode_addr, encode_error, encode_host,
    encode_inv, split_trace_ctx,
)
from .tracker import ConnectionTracker

if TYPE_CHECKING:  # pragma: no cover
    from .pool import ConnectionPool

logger = logging.getLogger("pybitmessage_tpu.network")

#: maximum addr entries sent on establishment (tcp.py:175-208)
MAX_ADDR_SAMPLE = 500
#: inv chunking for the initial big inv (tcp.py:210-253)
BIG_INV_CHUNK = 50000
#: max objects per connection with PoW verification still in flight —
#: lets one peer's flood coalesce into device batches without letting
#: it queue unbounded payloads
VERIFY_WINDOW = 32

PACKETS = REGISTRY.counter(
    "network_packets_total", "Framed protocol packets by direction",
    ("direction",))
# children bound once — the per-packet path must not pay a family
# lock + label lookup per frame
PACKETS_RX = PACKETS.labels(direction="rx")
PACKETS_TX = PACKETS.labels(direction="tx")
PACKET_ERRORS = REGISTRY.counter(
    "network_packet_errors_total",
    "Frames dropped for bad checksum / oversize payload")
HANDSHAKE_TIMEOUTS = REGISTRY.counter(
    "network_handshake_timeout_total",
    "Connections closed because version/verack never completed — "
    "black-holed peers no longer pin a slot forever")


class ConnectionClosed(Exception):
    pass


class BMConnection:
    """A framed Bitmessage peer connection over asyncio streams."""

    def __init__(self, pool: "ConnectionPool", reader: asyncio.StreamReader,
                 writer: asyncio.StreamWriter, *, outbound: bool,
                 host: str, port: int):
        self.pool = pool
        self.ctx = pool.ctx
        self.reader = reader
        self.writer = writer
        self.outbound = outbound
        self.host = host
        self.port = port
        self.tracker = ConnectionTracker(
            buckets=getattr(self.ctx, "announce_buckets", None) or 10)
        self.services = 0
        self.streams: tuple[int, ...] = ()
        self.remote_protocol = 0
        self.user_agent = ""
        self.verack_received = False
        self.verack_sent = False
        self.fully_established = False
        self.tls_established = False
        self.last_activity = time.time()
        self._closed = False
        self.pending_upload: deque[bytes] = deque()
        #: getdata service suppressed until this time
        #: (antiIntersectionDelay, reference tcp.py:96-127)
        self.skip_until = 0.0
        self._connected_at = time.time()
        #: bounded per-connection clock-offset estimator, fed by the
        #: send timestamps of incoming wire trace contexts — what makes
        #: cross-node stage latencies meaningful (docs/observability.md)
        self.skew = SkewEstimator()
        #: bounded in-flight object-verification pipeline (per peer)
        self._verify_sem = asyncio.Semaphore(VERIFY_WINDOW)
        self._verify_tasks: set[asyncio.Task] = set()
        self._task: asyncio.Task | None = None
        self._handshake_task: asyncio.Task | None = None

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> asyncio.Task:
        self._task = asyncio.create_task(self._run())
        return self._task

    def arm_handshake_timeout(self, timeout: float) -> None:
        """Close the connection if version/verack has not completed
        within ``timeout`` seconds (``asyncio.wait_for`` semantics via
        a watchdog task so the read loop itself stays untouched) — a
        black-holed peer must not hang the slot forever."""
        if timeout and timeout > 0 and not self.fully_established:
            self._handshake_task = asyncio.create_task(
                self._handshake_watchdog(timeout))

    async def _handshake_watchdog(self, timeout: float) -> None:
        try:
            await asyncio.sleep(timeout)
        except asyncio.CancelledError:
            return
        if not self.fully_established and not self._closed:
            HANDSHAKE_TIMEOUTS.inc()
            logger.debug("connection %s:%s handshake timed out after "
                         "%.0fs; closing", self.host, self.port, timeout)
            await self.close()

    async def _run(self) -> None:
        try:
            if self.outbound:
                await self.send_version()
            while True:
                await self._read_packet()
        except (ConnectionClosed, PacketError, MessageError, VarintError,
                asyncio.IncompleteReadError, ConnectionError, OSError) as exc:
            logger.debug("connection %s:%s closed: %r",
                         self.host, self.port, exc)
        except asyncio.CancelledError:
            pass
        except Exception:
            from ..resilience.policy import ERRORS
            ERRORS.labels(site="net.parse").inc()
            logger.exception("connection %s:%s parser error",
                             self.host, self.port)
        finally:
            await self.close()

    async def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        # in-flight verifications are NOT cancelled: the payloads are
        # fully received, and cancelling would strand their hashes in
        # GlobalTracker.missing for an hour (no peer re-requests a
        # hash marked in flight).  They settle within one verifier
        # round; node shutdown settles them deterministically as
        # unverified (BatchVerifier.stop sets False, never cancels).
        if self._handshake_task is not None and \
                not self._handshake_task.done() and \
                self._handshake_task is not asyncio.current_task():
            self._handshake_task.cancel()
        if self._task is not None and not self._task.done() and \
                self._task is not asyncio.current_task():
            self._task.cancel()
        try:
            self.writer.close()
            # bounded: a mid-handshake TLS transport can wedge the
            # orderly-shutdown wait forever
            await asyncio.wait_for(self.writer.wait_closed(), 3.0)
        except Exception as exc:
            # a transport that fails to close cleanly is routine for a
            # dead peer — but never swallow it SILENTLY (lint-enforced,
            # tests/test_observability.py)
            ERRORS.labels(site="net.close").inc()
            logger.debug("transport close for %s:%s failed: %r",
                         self.host, self.port, exc)
        self.pool.connection_closed(self)

    # -- framing -------------------------------------------------------------

    async def _read_chunked(self, n: int, sink) -> None:
        """THE throttled read loop: consume download tokens BEFORE
        each 32 KiB chunk, so a burst cannot outrun
        ``maxdownloadrate`` (the reference throttles at recv
        granularity, asyncore_pollchoose.py:109-130; r3 consumed the
        bucket only after the payload was already buffered), and hand
        each chunk to ``sink(offset, chunk)``.  While this coroutine
        sits in the bucket, the stream's flow control back-pressures
        the peer once the read buffer fills.  Both read paths share
        this loop — the throttle/activity semantics cannot drift."""
        bucket = self.ctx.download_bucket
        offset = 0
        while offset < n:
            take = min(n - offset, 32768)
            await bucket.consume(take)
            sink(offset, await self.reader.readexactly(take))
            offset += take
            # a paced transfer IS activity: without this a low rate
            # limit lets the inactivity reaper close a connection
            # mid-payload while bytes are still flowing
            self.last_activity = time.time()

    async def _read_throttled(self, n: int) -> bytes:
        """Read ``n`` bytes as ``bytes`` (header/resync-sized only —
        payloads go through :meth:`_read_payload_into`)."""
        if n == 0:
            return b""
        chunks: list[bytes] = []
        await self._read_chunked(n, lambda off, chunk: chunks.append(chunk))
        return chunks[0] if len(chunks) == 1 else b"".join(chunks)

    async def _read_payload_into(self, buf: PooledBuffer, n: int) -> None:
        """Fill a pooled payload buffer ``readinto``-style: each socket
        chunk lands at its final offset (the ONE fill copy, counted
        into ``ingest_bytes_copied_total{stage="fill"}``) — no chunk
        list, no join, no per-packet ``bytes`` churn."""
        await self._read_chunked(n, buf.write_at)

    async def _read_packet(self) -> None:
        # ingest backpressure (docs/ingest.md): while the validated-
        # object queue sits above its high watermark, stop reading —
        # the kernel buffer fills and TCP flow control pushes the
        # flood back onto the peers instead of into our memory
        wait_resume = getattr(self.ctx.object_queue, "wait_resume", None)
        if wait_resume is not None:
            await wait_resume()
        header = await self._read_throttled(HEADER_LEN)
        # resync on bad magic: scan forward byte-at-a-time
        # (reference bmproto.py:85-98)
        while not header.startswith(struct.pack(">L", MAGIC)):
            nxt = header.find(struct.pack(">L", MAGIC)[0:1], 1)
            if nxt == -1:
                header = await self._read_throttled(HEADER_LEN)
                continue
            header = header[nxt:] + await self._read_throttled(nxt)
        command, length, checksum = unpack_header(header)
        if length > MAX_MESSAGE_SIZE:
            PACKET_ERRORS.inc()
            raise ConnectionClosed("oversize payload")
        # zero-copy framing (docs/ingest.md): the payload fills a
        # pooled buffer; checksum verify, object-header parse, PoW
        # check and duplicate detection all run over memoryviews of
        # it.  Only a NEW object (or a non-object command handler)
        # materializes stable bytes — duplicate floods cost the fill
        # copy alone.
        buf = RECV_POOL.acquire(length)
        try:
            await self._read_payload_into(buf, length)
            view = buf.view()
            if not verify_payload(view, checksum):
                PACKET_ERRORS.inc()
                raise ConnectionClosed("bad checksum")
            PACKETS_RX.inc()
            self.last_activity = time.time()
            if command == "object":
                await self.cmd_object(view, buf=buf)
                return
            if command == "tobject":
                await self.cmd_tobject(view, buf=buf)
                return
            handler = getattr(self, "cmd_" + command, None)
            if handler is None:
                logger.debug("unimplemented command %r", command)
                return
            await handler(buf.materialize())
        finally:
            buf.release()

    async def send_packet(self, command: str, payload: bytes = b"") -> None:
        inject("net.send")
        frame = pack_packet(command, payload)
        await self.ctx.upload_bucket.consume(len(frame))
        PACKETS_TX.inc()
        self.writer.write(frame)
        await self.writer.drain()

    # -- handshake -----------------------------------------------------------

    async def send_version(self) -> None:
        payload = VersionPayload(
            services=self.ctx.services,
            remote_host=self.host, remote_port=self.port,
            my_port=self.ctx.port, nonce=self.ctx.nonce,
            streams=tuple(self.ctx.streams)).encode()
        await self.send_packet("version", payload)

    async def cmd_version(self, payload: bytes) -> None:
        try:
            ver = VersionPayload.decode(payload)
        except (MessageError, Exception) as exc:
            raise ConnectionClosed(f"bad version: {exc}") from exc
        # peer validity checks (reference bmproto.py:563-643)
        if ver.nonce == self.ctx.nonce:
            raise ConnectionClosed("connection to self")
        if ver.protocol_version < 3:
            await self.send_packet("error", encode_error(
                2, 0, b"", "protocol version too old"))
            raise ConnectionClosed("ancient protocol")
        if abs(ver.timestamp - time.time()) > MAX_TIME_OFFSET:
            await self.send_packet("error", encode_error(
                2, 0, b"", "time offset too large"))
            raise ConnectionClosed("time offset")
        if not set(ver.streams) & set(self.ctx.streams):
            raise ConnectionClosed("no stream overlap")
        self.remote_protocol = ver.protocol_version
        self.services = ver.services
        self.streams = ver.streams
        self.user_agent = ver.user_agent
        if not self.outbound:
            # knownnodes/addr-gossip must use the peer's advertised
            # LISTENING port, not the ephemeral source port we accepted
            self.port = ver.my_port
        # Verack ordering carries the TLS upgrade barrier: the OUTBOUND
        # side veracks as soon as it has the peer's version, but the
        # INBOUND side defers its verack until the peer's verack has
        # arrived.  That makes the inbound verack the guaranteed-last
        # plaintext packet on the wire, so when the outbound side reads
        # it and fires its ClientHello, the inbound side has already
        # swapped its transport to TLS — no handshake bytes can strand
        # in the plaintext stream buffer.  (The reference upgrades on
        # the same verack boundary, bmproto.py:552-560, but relies on
        # its hand-rolled socket buffers to tolerate the race.)
        if self.outbound:
            await self.send_packet("verack")
            self.verack_sent = True
        else:
            await self.send_version()
        if self.verack_sent and self.verack_received:
            await self._establish()

    async def cmd_verack(self, payload: bytes) -> None:
        if not self.remote_protocol:
            # verack before version: establishment would skip every
            # peer-validity check (nonce/self-connect, protocol floor,
            # time offset, stream overlap)
            raise ConnectionClosed("verack before version")
        self.verack_received = True
        if not self.outbound and not self.verack_sent:
            await self.send_packet("verack")
            self.verack_sent = True
        if self.verack_sent:
            await self._establish()

    async def _upgrade_tls(self) -> None:
        """Mid-stream TLS after the verack exchange (reference
        tls.py:62-220; negotiated when both sides advertise NODE_SSL,
        bmproto.py:552-560).  The verack is the last plaintext packet
        each side sends before switching, so no framed data straddles
        the upgrade."""
        from .tls import make_client_context, make_server_context
        if self.outbound:
            tls_ctx = make_client_context()
        else:
            tls_ctx = make_server_context(*self.ctx.tls_files)
        await self.writer.start_tls(tls_ctx, ssl_handshake_timeout=10)
        self.tls_established = True
        logger.debug("TLS established with %s:%s (%s)", self.host,
                     self.port, self.writer.get_extra_info("cipher"))

    async def _establish(self) -> None:
        if self.fully_established:
            return
        if self.ctx.tls_files is not None and self.services & NODE_SSL \
                and self.ctx.services & NODE_SSL:
            await self._upgrade_tls()
        self.fully_established = True
        if self._handshake_task is not None:
            self._handshake_task.cancel()
            self._handshake_task = None
        self._anti_intersection_delay(initial=True)
        await self._send_addr_sample()
        if not await self._start_sync():
            await self._send_big_inv()
        self.pool.connection_established(self)

    async def _start_sync(self) -> bool:
        """Negotiate set-reconciliation sync (docs/sync.md): when both
        ends advertise NODE_SYNC and a reconciler is attached, register
        the session and replace the big-inv flood with a digest-sized
        IBLT catch-up.  The OUTBOUND end initiates (one exchange
        converges both directions).  Returns False when the classic
        big inv should be sent instead."""
        rec = getattr(self.pool, "reconciler", None)
        if rec is None or not self.services & NODE_SYNC \
                or not self.ctx.services & NODE_SYNC:
            return False
        rec.register(self)
        if not self.outbound:
            return True
        return await rec.start_catchup(self)

    async def _send_addr_sample(self) -> None:
        entries = []
        for stream in self.ctx.streams:
            peers = self.ctx.knownnodes.peers(stream)
            random.shuffle(peers)
            for p in peers[:MAX_ADDR_SAMPLE]:
                info = self.ctx.knownnodes.get(p, stream)
                if not info or info.get("self"):
                    continue
                try:
                    encode_host(p.host)
                except (OSError, ValueError):
                    # DNS bootstrap names / v3 onions aren't
                    # wire-encodable
                    continue
                entries.append(AddrEntry(
                    info["lastseen"], stream, 1, p.host, p.port))
        if entries:
            await self.send_packet("addr", encode_addr(entries))

    async def _send_big_inv(self) -> None:
        """Advertise our whole unexpired inventory per stream —
        excluding objects still in the dandelion stem phase, which must
        not be linkable to us (reference tcp.py:210-253 excludes the
        Dandelion hashMap)."""
        dand = self.ctx.dandelion
        for stream in self.ctx.streams:
            hashes = [
                h for h in self.ctx.inventory.unexpired_hashes_by_stream(
                    stream)
                if dand is None or not dand.in_stem_phase(h)]
            for i in range(0, len(hashes), BIG_INV_CHUNK):
                chunk = hashes[i:i + BIG_INV_CHUNK]
                await self.send_packet("inv", encode_inv(chunk))

    # -- gossip --------------------------------------------------------------

    async def cmd_inv(self, payload: bytes) -> None:
        self._require_established()
        for h in decode_inv(payload):
            self._handle_inventory_announcement(h)

    async def cmd_dinv(self, payload: bytes) -> None:
        """Dandelion stem announcement (reference bmproto.py:340-360)."""
        self._require_established()
        hashes = decode_inv(payload)
        if self.ctx.dandelion is not None:
            for h in hashes:
                self.ctx.dandelion.add_hash(h, stream=1, source=self)
        for h in hashes:
            self._handle_inventory_announcement(h)

    def _handle_inventory_announcement(self, h: bytes) -> None:
        rec = getattr(self.pool, "reconciler", None)
        if rec is not None:
            # the peer has this object: drop it from the sync pending
            # set so neither a sketch nor an inv echoes it back
            rec.peer_announced(self, h)
        if h in self.ctx.inventory:
            self.tracker.peer_announced(h)
            self.tracker.object_received(h)
            return
        self.tracker.peer_announced(h)
        # a peer advertising more un-fetched objects than the whole
        # protocol allows is attacking our memory (reference
        # MAX_OBJECT_COUNT disconnect)
        if len(self.tracker.objects_new_to_me) > MAX_OBJECT_COUNT:
            raise ConnectionClosed("peer advertised too many objects")

    async def cmd_getdata(self, payload: bytes) -> None:
        self._require_established()
        for h in decode_inv(payload):
            if len(self.pending_upload) >= MAX_OBJECT_COUNT:
                break  # bounded backlog: a getdata flood can't grow memory
            self.pending_upload.append(h)
        await self.flush_uploads()

    def _anti_intersection_delay(self, initial: bool = False) -> None:
        """Defense against intersection attacks (reference tcp.py:96-127):
        pause getdata service for roughly the time a small object needs
        to propagate network-wide, (a) right after establishment and
        (b) whenever the peer requests an object we don't have — so an
        attacker probing whether we originated an object gets one shot
        per IP and an answer indistinguishable from relay timing."""
        import math
        nodes = max(len(self.ctx.knownnodes.peers(s) or ())
                    for s in self.ctx.streams) if self.ctx.streams else 0
        pending = self.tracker.pending_announcements()
        delay = math.ceil(math.log(nodes + 2, 20)) * (0.2 + pending / 2.0)
        if delay <= 0:
            return
        base = self._connected_at if initial else time.time()
        self.skip_until = max(self.skip_until, base + delay)
        logger.debug("%s: skipping getdata service for %.2fs%s",
                     self.host, self.skip_until - time.time(),
                     " (initial)" if initial else " (missing object)")

    async def flush_uploads(self, limit: int = 10) -> int:
        """Serve up to ``limit`` queued getdata requests
        (reference uploadthread.py:15-69); returns how many it served.
        Objects still in the dandelion stem phase are withheld as if
        unknown."""
        if time.time() < self.skip_until:
            return 0  # antiIntersectionDelay window — serve nothing yet
        dand = self.ctx.dandelion
        served = 0
        while self.pending_upload and served < limit:
            h = self.pending_upload.popleft()
            if dand is not None and dand.in_stem_phase(h) and \
                    dand.child_for(h) is not self:
                # withhold stem objects from everyone EXCEPT the
                # designated stem child, or the stem could never relay
                continue
            try:
                item = self.ctx.inventory[h]
            except KeyError:
                # edge role (docs/roles.md): a hash we KNOW exists
                # relay-side but don't hold locally is fetched over
                # role IPC and re-served when the payload lands — not
                # treated as unknown (no intersection-probe penalty
                # for objects the shard genuinely has)
                fetcher = getattr(self.ctx, "payload_fetcher", None)
                if fetcher is not None and fetcher(h, self):
                    continue
                self._anti_intersection_delay()
                continue
            await self.send_object(h, item.payload)
            self.tracker.object_received(h)
            served += 1
        return served

    # -- wire trace context (docs/observability.md) --------------------------

    @property
    def trace_negotiated(self) -> bool:
        """Both ends advertised NODE_TRACE: sync payloads carry the
        32-byte trace trailer and object pushes travel as ``tobject``.
        Legacy peers (no bit) see the classic wire format, byte for
        byte."""
        return bool(self.services & NODE_TRACE
                    and self.ctx.services & NODE_TRACE)

    def attach_trace(self, command: str, payload: bytes) -> bytes:
        """Append the trace trailer for a sync-round payload when the
        peer negotiated NODE_TRACE (reconciler send hook; simulated
        connections simply lack this method)."""
        if not self.trace_negotiated:
            return payload
        ctx = TraceContext(self.ctx.nonce.ljust(16, b"\x00"), 0)
        TRACE_CTX_SENT.labels(command=command).inc()
        return append_trace_ctx(payload, ctx)

    def _strip_trace(self, command: str, payload: bytes) -> bytes:
        """Split and consume an incoming sync payload's trace trailer:
        feed the skew estimator, count it, hand back the bare payload.
        A malformed trailer is dropped (counted) without killing the
        round — telemetry must not break sync."""
        if not self.trace_negotiated:
            return payload
        try:
            payload, ctx = split_trace_ctx(payload)
        except MessageError:
            TRACE_CTX_INVALID.inc()
            return payload
        TRACE_CTX_RECEIVED.labels(command=command).inc()
        self.skew.observe(ctx.sent_at)
        return payload

    async def send_object(self, h: bytes, payload: bytes) -> None:
        """Push one object: a ``tobject`` frame (32-byte trace context
        + object payload) to NODE_TRACE peers so the receiver's
        lifecycle timeline joins this object's trace, the classic
        ``object`` frame otherwise."""
        if not self.trace_negotiated:
            await self.send_packet("object", payload)
            return
        ctx = LIFECYCLE.trace_ctx_for(h)
        if ctx is None:
            await self.send_packet("object", payload)
            return
        TRACE_CTX_SENT.labels(command="tobject").inc()
        await self.send_packet("tobject", ctx.encode() + payload)

    async def cmd_tobject(self, payload: bytes, *,
                          buf: PooledBuffer | None = None) -> None:
        """A trace-carrying object push.  Only trace-negotiated peers
        send these; from anyone else the command is ignored like any
        unknown command would be (the object will arrive again through
        normal paths)."""
        self._require_established()
        if not self.trace_negotiated or len(payload) <= TRACE_CTX_LEN:
            logger.debug("tobject from %s without negotiation; ignored",
                         self.host)
            return
        try:
            ctx = TraceContext.decode(bytes(payload[:TRACE_CTX_LEN]))
        except ValueError:
            TRACE_CTX_INVALID.inc()
            return
        TRACE_CTX_RECEIVED.labels(command="tobject").inc()
        self.skew.observe(ctx.sent_at)
        await self._handle_object(payload[TRACE_CTX_LEN:], trace_ctx=ctx,
                                  buf=buf)

    async def cmd_object(self, payload: bytes, *,
                         buf: PooledBuffer | None = None) -> None:
        self._require_established()
        await self._handle_object(payload, buf=buf)

    async def _handle_object(self, payload,
                             trace_ctx: TraceContext | None = None,
                             buf: PooledBuffer | None = None) -> None:
        """``payload`` is either stable ``bytes`` (legacy callers,
        tests) or a memoryview over ``buf`` — every check below runs
        on either without copying; only :meth:`_accept_object`
        materializes, and only for objects that are actually new."""
        try:
            header = ObjectHeader.parse(payload)
            check_by_type(header.object_type, header.version, len(payload))
            header.check_expiry()
        except ObjectError as exc:
            logger.debug("rejected object from %s: %s", self.host, exc)
            return
        if header.stream not in self.ctx.streams:
            return
        if self.ctx.pow_verifier is not None:
            # Bounded verification pipeline: the read loop keeps parsing
            # (up to VERIFY_WINDOW objects in flight) while the PoW
            # checks coalesce into fused device batches in the
            # verifier's drain task (SURVEY §7.7).  Awaiting the check
            # inline would cap ingest at one object per device
            # round-trip and starve the batching entirely.  The pooled
            # buffer rides along retained: the view stays valid until
            # the verify task settles and releases it.
            await self._verify_sem.acquire()
            if buf is not None:
                buf.retain()
            task = asyncio.create_task(
                self._verify_and_accept(header, payload, trace_ctx, buf))
            self._verify_tasks.add(task)
            task.add_done_callback(self._verify_task_done)
        else:
            ok = check_pow(payload, self.ctx.pow_ntpb, self.ctx.pow_extra,
                           clamp=False)
            if not ok:
                logger.debug("insufficient PoW from %s", self.host)
                raise ConnectionClosed("object with insufficient PoW")
            self._accept_object(header, payload, trace_ctx)

    def _verify_task_done(self, task: asyncio.Task) -> None:
        self._verify_tasks.discard(task)
        self._verify_sem.release()
        if task.cancelled():
            return
        exc = task.exception()
        if exc is not None:
            # the inline path would have logged a traceback and closed
            # the connection; keep that visibility for pipelined objects
            logger.error("object acceptance failed on %s:%s",
                         self.host, self.port, exc_info=exc)

    async def _verify_and_accept(self, header, payload,
                                 trace_ctx=None,
                                 buf: PooledBuffer | None = None) -> None:
        try:
            ok = await self.ctx.pow_verifier.check(payload)
            if not ok:
                logger.debug("insufficient PoW from %s", self.host)
                await self.close()
                return
            self._accept_object(header, payload, trace_ctx)
        finally:
            if buf is not None:
                buf.release()

    def _accept_object(self, header, payload,
                       trace_ctx=None) -> None:
        h = inventory_hash(payload)
        if trace_ctx is not None:
            # the object arrived inside another node's trace: this
            # node's lifecycle timeline joins it (stitching) instead of
            # opening a fresh one
            LIFECYCLE.adopt(h, trace_ctx.trace_id,
                            trace_ctx.parent_span)
        self.tracker.object_received(h)
        self.ctx.global_tracker.received(h)
        if h in self.ctx.inventory:
            return
        # new object: the ONE materialize copy past the buffer fill —
        # shared by the inventory row, the hot set and the processor
        # queue (duplicates above never reach this line)
        if not isinstance(payload, (bytes, bytearray)):
            COPIED_MATERIALIZE.inc(len(payload))
            payload = bytes(payload)
        tag = extract_tag(header, payload)
        self.ctx.inventory.add(
            h, header.object_type, header.stream, payload, header.expires,
            tag)
        self.pool.object_received(h, header, payload, source=self)

    # -- set-reconciliation sync (docs/sync.md) ------------------------------

    def _reconciler(self):
        rec = getattr(self.pool, "reconciler", None)
        if rec is None or not rec.negotiated(self):
            logger.debug("sync message from %s without a negotiated "
                         "session; ignored", self.host)
            return None
        return rec

    async def cmd_sketchreq(self, payload: bytes) -> None:
        self._require_established()
        payload = self._strip_trace("sketchreq", payload)
        rec = self._reconciler()
        if rec is not None:
            await rec.handle_sketchreq(self, payload)

    async def cmd_sketch(self, payload: bytes) -> None:
        self._require_established()
        payload = self._strip_trace("sketch", payload)
        rec = self._reconciler()
        if rec is not None:
            await rec.handle_sketch(self, payload)

    async def cmd_recondiff(self, payload: bytes) -> None:
        self._require_established()
        payload = self._strip_trace("recondiff", payload)
        rec = self._reconciler()
        if rec is not None:
            await rec.handle_recondiff(self, payload)

    async def cmd_addr(self, payload: bytes) -> None:
        self._require_established()
        for entry in decode_addr(payload):
            if entry.stream not in self.ctx.streams:
                continue
            if not (1 <= entry.port <= 65535):
                continue
            age = time.time() - entry.time
            if age > 10800 * 2:  # stale addr
                continue
            self.pool.peer_discovered(entry)

    # -- keepalive / errors --------------------------------------------------

    async def cmd_portcheck(self, payload: bytes) -> None:
        """Peer asks us to verify its advertised listen port is
        reachable (reference bmproto.py:477-479 -> portCheckerQueue,
        prioritized by connectionchooser.py:37-44): queue a dial back
        to its source address + advertised port."""
        from ..storage.knownnodes import Peer
        self.pool.portcheck_requested(Peer(self.host, self.port))

    async def cmd_ping(self, payload: bytes) -> None:
        await self.send_packet("pong")

    async def cmd_pong(self, payload: bytes) -> None:
        pass

    async def cmd_error(self, payload: bytes) -> None:
        from .messages import decode_error
        fatal, ban, iv, text = decode_error(payload)
        logger.info("peer %s error (fatal=%d): %s", self.host, fatal, text)
        if fatal >= 2:
            raise ConnectionClosed("fatal peer error")

    def _require_established(self) -> None:
        if not self.fully_established:
            raise ConnectionClosed("command before handshake complete")

    # -- outgoing gossip helpers --------------------------------------------

    async def announce(self, hashes: list[bytes], stem: bool = False) -> None:
        if hashes:
            await self.send_packet("dinv" if stem else "inv",
                                   encode_inv(hashes))

    async def request_objects(self) -> None:
        """Request a fair share of missing objects (downloadthread.py)."""
        n_conns = max(1, len(self.pool.established()))
        wanted = []
        for h in self.tracker.request_batch(1000 // n_conns):
            if h in self.ctx.inventory:
                # obtained through another connection meanwhile: stop
                # tracking so it doesn't pin a pending-window slot
                self.tracker.object_received(h)
            elif not self.ctx.global_tracker.was_requested(h):
                wanted.append(h)
        if wanted:
            self.ctx.global_tracker.mark_requested(wanted)
            await self.send_packet("getdata", encode_inv(wanted))
