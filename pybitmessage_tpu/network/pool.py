"""Connection pool: dialer, listener, gossip cadences.

Reference: src/network/connectionpool.py (dial loop with rating-weighted
choice + network-group diversity), invthread.py (1 s inv batching with
dandelion split), downloadthread.py / uploadthread.py cadences,
announcethread.py (not yet), knownnodes rating lifecycle on
connect/close (tcp.py:284-300).
"""

from __future__ import annotations

import asyncio
import logging
import random
import socket
import time
from collections import deque
from typing import Callable, Optional

from ..observability import REGISTRY
from ..observability.lifecycle import LIFECYCLE
from ..resilience import CircuitBreaker, inject
from ..resilience.policy import ERRORS
from ..storage.knownnodes import Peer
from .connection import BMConnection
from .messages import AddrEntry, is_private_host, network_group
from .ratelimit import TokenBucket
from .tracker import GlobalTracker

logger = logging.getLogger("pybitmessage_tpu.network")

CONNECTIONS = REGISTRY.gauge(
    "network_connections", "Open connections by direction",
    ("direction",))
DIALS = REGISTRY.counter(
    "network_dial_total", "Outbound dial attempts by outcome",
    ("result",))
OBJECTS_RECEIVED = REGISTRY.counter(
    "network_objects_received_total",
    "Valid objects accepted from the network")
ANNOUNCE_RETRIES = REGISTRY.counter(
    "network_announce_requeue_total",
    "Inv/addr announcements put back after a failed send — retried "
    "next tick instead of silently lost")


def _is_local_address(host: str) -> bool:
    """True when ``host`` is one of this machine's own addresses.

    Kernel routing trick, no interface enumeration: a UDP connect
    (no packets sent) to a local address always selects that same
    address as the source.
    """
    if host in ("127.0.0.1", "::1", "localhost"):
        return True
    try:
        family = socket.AF_INET6 if ":" in host else socket.AF_INET
        with socket.socket(family, socket.SOCK_DGRAM) as s:
            s.connect((host, 9))
            return s.getsockname()[0] == host
    except OSError:
        return False

DEFAULT_MAX_OUTBOUND = 8
DEFAULT_MAX_TOTAL = 200
PING_INTERVAL = 300
INV_INTERVAL = 1.0
DOWNLOAD_INTERVAL = 1.0
#: rest of the upload loop after a round that served nothing, and the
#: longest a round waits for a connection's ten objects
UPLOAD_INTERVAL = 1.0
#: TCP connect budget for one outbound dial (``connecttimeout``)
DEFAULT_DIAL_TIMEOUT = 10.0
#: version/verack must complete within this or the slot is reclaimed —
#: a black-holed peer must not pin a connection slot forever
DEFAULT_HANDSHAKE_TIMEOUT = 30.0
#: per-peer dial breakers kept at most (oldest dropped beyond this)
MAX_DIAL_BREAKERS = 512


class NodeContext:
    """Shared state every connection needs — the explicit replacement
    for the reference's global singletons (state.py, queues.py,
    BMConnectionPool(), Inventory(), Dandelion())."""

    def __init__(self, *, inventory, knownnodes, dandelion=None,
                 streams=(1,), port=8444, services=1 | 8,
                 nonce: bytes | None = None,
                 allow_private_peers: bool = False,
                 pow_ntpb: int = 1000, pow_extra: int = 1000,
                 announce_buckets: int | None = None,
                 ingest_high: int | None = None,
                 ingest_low: int | None = None):
        self.inventory = inventory
        self.knownnodes = knownnodes
        self.dandelion = dandelion
        self.streams = tuple(streams)
        self.port = port
        self.services = services
        self.nonce = nonce or random.getrandbits(64).to_bytes(8, "big")
        self.allow_private_peers = allow_private_peers
        #: network-minimum PoW params this node enforces; test mode
        #: divides the consensus 1000/1000 by 100 (reference
        #: bitmessagemain.py:167-172)
        self.pow_ntpb = pow_ntpb
        self.pow_extra = pow_extra
        #: inv/addr timing-decorrelation bucket count (MultiQueue role)
        from .tracker import ANNOUNCE_BUCKETS
        self.announce_buckets = announce_buckets or ANNOUNCE_BUCKETS
        #: kB/s-style global throttles (0 = unlimited), reference
        #: maxdownloadrate/maxuploadrate semantics
        self.download_bucket = TokenBucket(0, direction="rx")
        self.upload_bucket = TokenBucket(0, direction="tx")
        self.global_tracker = GlobalTracker()
        #: validated objects flow out here: (hash, header, payload).
        #: Watermarked (docs/ingest.md): crossing HIGH pauses every
        #: connection's read loop until the processor drains it back
        #: under LOW — a flood stalls sockets, not memory (the old
        #: plain Queue grew without bound)
        from ..utils.queues import DEFAULT_HIGH_WATERMARK, WatermarkQueue
        self.object_queue: asyncio.Queue = WatermarkQueue(
            high=DEFAULT_HIGH_WATERMARK if ingest_high is None
            else ingest_high,
            low=ingest_low)
        #: optional BatchVerifier — incoming objects' PoW checked in
        #: fused device batches instead of one host hash pair each
        self.pow_verifier = None
        #: opportunistic TLS (NODE_SSL): (certfile, keyfile) or None.
        #: Set via enable_tls(); adds NODE_SSL to our service flags.
        self.tls_files: tuple[str, str] | None = None
        #: SOCKS proxy for outbound dials (Tor support): None or a dict
        #: {type: "SOCKS5"|"SOCKS4a", host, port, username, password}
        self.proxy: dict | None = None
        #: edge role (docs/roles.md): async payload fetch for getdata
        #: hashes known relay-side but not cached locally — a callable
        #: ``(hash, conn) -> bool`` or None
        self.payload_fetcher = None

    def enable_tls(self, directory=None) -> None:
        # graceful degradation on minimal images: the ephemeral cert
        # needs the optional `cryptography` package; without it the
        # node simply doesn't advertise NODE_SSL (TLS is opportunistic
        # and negotiated, so plaintext peering still interoperates)
        try:
            from .tls import generate_self_signed_cert
            self.tls_files = generate_self_signed_cert(directory)
        except ImportError as exc:
            logger.warning(
                "TLS disabled: `cryptography` not installed (%s)", exc)
            return
        self.services |= 2  # NODE_SSL


class ConnectionPool:
    def __init__(self, ctx: NodeContext, *,
                 max_outbound: int = DEFAULT_MAX_OUTBOUND,
                 max_total: int = DEFAULT_MAX_TOTAL,
                 listen_host: str = "127.0.0.1",
                 trusted_peer: Optional[Peer] = None,
                 dial_timeout: float = DEFAULT_DIAL_TIMEOUT,
                 handshake_timeout: float = DEFAULT_HANDSHAKE_TIMEOUT):
        self.ctx = ctx
        self.max_outbound = max_outbound
        self.max_total = max_total
        self.listen_host = listen_host
        self.trusted_peer = trusted_peer
        self.dial_timeout = dial_timeout
        self.handshake_timeout = handshake_timeout
        #: per-peer dial breaker tuning (``breakerfailures`` /
        #: ``breakercooldown``, applied by __main__) — takes effect for
        #: breakers created after the change
        self.dial_breaker_threshold = 3
        self.dial_breaker_cooldown = 120.0
        #: per-peer dial circuit breakers: a repeatedly unreachable
        #: peer stops consuming dial-loop ticks until its cooldown.
        #: Unregistered + one shared metric label — peer addresses
        #: must not explode metric cardinality.
        self._dial_breakers: dict[str, CircuitBreaker] = {}
        self.inbound: dict[BMConnection, None] = {}
        self.outbound: dict[BMConnection, None] = {}
        self._server: asyncio.AbstractServer | None = None
        self._tasks: list[asyncio.Task] = []
        self.on_object: Callable | None = None  # hook for the processor
        #: relay role hook: called by announce_object for locally-
        #: originated objects so edges receive the full payload
        self.on_announce: Callable | None = None
        #: share the listen socket across processes (edge role: N edge
        #: processes accept on one port, kernel-balanced)
        self.reuse_port = False
        #: set-reconciliation subsystem (docs/sync.md); None keeps the
        #: classic flooding-only paths
        self.reconciler = None
        #: LAN peers heard over UDP discovery -> last-heard time
        self.lan_peers: dict[Peer, float] = {}
        #: (AddrEntry, due_time) queue for ongoing addr relay
        self._addr_gossip: list = []
        #: peers that asked us to verify their reachability
        #: (reference portCheckerQueue) — dialed before rating choice
        self._portcheck_queue: deque[Peer] = deque()

    # -- queries -------------------------------------------------------------

    def connections(self) -> list[BMConnection]:
        return list(self.outbound) + list(self.inbound)

    @staticmethod
    def _subscribes(conn, stream: int) -> bool:
        """Per-stream overlay membership: a connection hears stream k
        when its negotiated streams include k.  Connections that never
        advertised streams (test doubles, pre-handshake) always
        subscribe."""
        streams = getattr(conn, "streams", None)
        return not streams or stream in streams

    def established(self, stream: int | None = None) -> list[BMConnection]:
        """Fully-established connections, optionally only those whose
        negotiated streams overlay ``stream`` (docs/roles.md: the
        per-stream overlay — announcements for stream k only reach
        peers subscribed to k)."""
        conns = [c for c in self.connections() if c.fully_established]
        if stream is None:
            return conns
        return [c for c in conns if self._subscribes(c, stream)]

    def stream_overlay(self) -> dict[int, int]:
        """Established-peer count per subscribed stream (roleStatus)."""
        return {s: len(self.established(s)) for s in self.ctx.streams}

    def _used_groups(self) -> set[bytes]:
        return {network_group(c.host) for c in self.outbound}

    # -- lifecycle -----------------------------------------------------------

    async def start(self, listen: bool = True) -> None:
        CONNECTIONS.labels(direction="inbound").set(len(self.inbound))
        CONNECTIONS.labels(direction="outbound").set(len(self.outbound))
        if listen:
            self._server = await asyncio.start_server(
                self._accept, self.listen_host, self.ctx.port,
                reuse_port=True if self.reuse_port else None)
        self._tasks = [
            asyncio.create_task(self._dial_loop()),
            asyncio.create_task(self._inv_loop()),
            asyncio.create_task(self._download_loop()),
            asyncio.create_task(self._upload_loop()),
            asyncio.create_task(self._maintenance_loop()),
        ]

    async def stop(self) -> None:
        for t in self._tasks:
            t.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)
        if self._server:
            self._server.close()
        # Close connections BEFORE Server.wait_closed(): since Python
        # 3.12 wait_closed() blocks until every handler transport is
        # gone, so the old order deadlocks on any live connection.
        for conn in self.connections():
            await conn.close()
        if self._server:
            await self._server.wait_closed()

    @property
    def listen_port(self) -> int:
        if self._server and self._server.sockets:
            return self._server.sockets[0].getsockname()[1]
        return self.ctx.port

    # -- connection management ----------------------------------------------

    async def _accept(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        peer = writer.get_extra_info("peername") or ("?", 0)
        if len(self.connections()) >= self.max_total:
            writer.close()
            return
        conn = BMConnection(self, reader, writer, outbound=False,
                            host=peer[0], port=peer[1])
        self.inbound[conn] = None
        CONNECTIONS.labels(direction="inbound").set(len(self.inbound))
        conn.start()
        # a peer that never completes version/verack must not pin an
        # inbound slot forever (black-holed / port-scanning peers)
        conn.arm_handshake_timeout(self.handshake_timeout)

    def _dial_breaker(self, peer: Peer) -> CircuitBreaker:
        key = "%s:%d" % (peer.host, peer.port)
        br = self._dial_breakers.get(key)
        if br is None:
            while len(self._dial_breakers) >= MAX_DIAL_BREAKERS:
                self._dial_breakers.pop(next(iter(self._dial_breakers)))
            # hashed peer-bucket label (``net.dial/bNN``): per-bucket
            # visibility without per-peer label cardinality
            from ..observability.metrics import peer_bucket_label
            br = self._dial_breakers[key] = CircuitBreaker(
                "net.dial:%s" % key,
                threshold=self.dial_breaker_threshold,
                cooldown=self.dial_breaker_cooldown,
                label=peer_bucket_label("net.dial", key), register=False)
        return br

    async def connect_to(self, peer: Peer) -> BMConnection | None:
        breaker = self._dial_breaker(peer)
        if not breaker.allow():
            # repeatedly-dead peer: don't pay the connect timeout again
            # until the breaker's cooldown lets a probe through
            DIALS.labels(result="skipped").inc()
            return None
        try:
            inject("net.dial")
            if self.ctx.proxy is not None:
                from .socks import open_via_proxy
                p = self.ctx.proxy
                reader, writer = await asyncio.wait_for(
                    open_via_proxy(
                        p["type"], p["host"], p["port"], peer.host,
                        peer.port,
                        username=p.get("username", ""),
                        password=p.get("password", ""), timeout=30),
                    timeout=max(self.dial_timeout, 30))
            else:
                reader, writer = await asyncio.wait_for(
                    asyncio.open_connection(peer.host, peer.port),
                    timeout=self.dial_timeout)
        except (OSError, asyncio.TimeoutError) as exc:
            logger.debug("dial %s failed: %r", peer, exc)
            DIALS.labels(result="failed").inc()
            ERRORS.labels(site="net.dial").inc()
            breaker.record_failure()
            self.ctx.knownnodes.decrease_rating(peer)
            return None
        breaker.record_success()
        conn = BMConnection(self, reader, writer, outbound=True,
                            host=peer.host, port=peer.port)
        self.outbound[conn] = None
        DIALS.labels(result="connected").inc()
        CONNECTIONS.labels(direction="outbound").set(len(self.outbound))
        conn.start()
        conn.arm_handshake_timeout(self.handshake_timeout)
        return conn

    def connection_established(self, conn: BMConnection) -> None:
        peer = Peer(conn.host, conn.port)
        self.ctx.knownnodes.add(peer)
        self.ctx.knownnodes.increase_rating(peer)
        if self.ctx.dandelion and conn.services & 8:
            self.ctx.dandelion.maybe_add_stem(conn)

    def connection_closed(self, conn: BMConnection) -> None:
        self.inbound.pop(conn, None)
        self.outbound.pop(conn, None)
        CONNECTIONS.labels(direction="inbound").set(len(self.inbound))
        CONNECTIONS.labels(direction="outbound").set(len(self.outbound))
        if self.reconciler is not None:
            self.reconciler.unregister(conn)
        if self.ctx.dandelion:
            self.ctx.dandelion.remove_connection(conn)
        if conn.outbound and not conn.fully_established:
            self.ctx.knownnodes.decrease_rating(Peer(conn.host, conn.port))

    def portcheck_requested(self, peer: Peer) -> None:
        """Queue a reachability-verification dial (cmd_portcheck)."""
        if peer not in self._portcheck_queue:
            self._portcheck_queue.append(peer)

    def lan_peer_discovered(self, peer: Peer, stream: int = 1) -> None:
        """A peer announced itself via LAN UDP broadcast — trusted more
        than gossip (we heard it from its own source address) and
        preferred by the dialer 50% of the time (reference
        connectionchooser.py:57-62, state.discoveredPeers)."""
        if peer.port == self.listen_port and _is_local_address(peer.host):
            return  # our own broadcast echoed back from a local iface
        self.lan_peers[peer] = time.time()

    def peer_discovered(self, entry: AddrEntry) -> None:
        # Reject unroutable addresses from gossip — loopback/private/
        # reserved hosts would poison the dial loop (the reference's
        # addr handling only accepts private IPs from LAN UDP discovery).
        if is_private_host(entry.host) and not self.ctx.allow_private_peers:
            return
        self.ctx.knownnodes.add(
            Peer(entry.host, entry.port), entry.stream,
            lastseen=min(int(entry.time), int(time.time())))

    def _route_announcement(self, h: bytes, conns,
                            stream: int | None = None) -> None:
        """Fan one announcement out: stem-phase hashes always ride the
        classic trackers (dandelion routing decides who may see them —
        they must NEVER enter a reconciliation sketch), everything
        else goes through the reconciler's flood/pending split when
        sync is enabled.  With a known ``stream`` the fan-out honors
        the per-stream overlay: only peers subscribed to that stream
        hear it, and a stream outside this process's shard
        (``ctx.streams``) is never announced at all — the shard
        boundary (docs/roles.md, docs/sync.md)."""
        if stream is not None:
            if stream not in self.ctx.streams:
                return
            conns = [c for c in conns if self._subscribes(c, stream)]
        LIFECYCLE.record(h, "announced")
        dand = self.ctx.dandelion
        if self.reconciler is not None and \
                (dand is None or not dand.in_stem_phase(h)):
            self.reconciler.route_announcement(h, conns, stream=stream)
            return
        for conn in conns:
            conn.tracker.we_should_announce(h)

    def object_received(self, h: bytes, header, payload: bytes,
                        source) -> None:
        """A new valid object arrived: queue for processing + relay.
        The source connection is excluded — an inv must never echo
        back to the peer that delivered the object."""
        OBJECTS_RECEIVED.inc()
        LIFECYCLE.record(h, "received")
        self._route_announcement(
            h, [c for c in self.established() if c is not source],
            stream=getattr(header, "stream", None))
        self.ctx.object_queue.put_nowait((h, header, payload))
        if self.on_object is not None:
            self.on_object(h, header, payload, source)

    def announce_object(self, h: bytes, stream: int = 1,
                        local: bool = True) -> None:
        """Advertise a (locally generated or relayed) object.  Local
        objects may enter the dandelion stem phase."""
        dand = self.ctx.dandelion
        if local and dand and dand.enabled and \
                random.randrange(100) < dand.stem_probability:
            dand.add_hash(h, stream, source=None)
        self._route_announcement(h, self.established(), stream=stream)
        if self.on_announce is not None:
            self.on_announce(h, stream, local)

    # -- periodic tasks ------------------------------------------------------

    async def _dial_loop(self) -> None:
        while True:
            try:
                await self._dial_once()
            except asyncio.CancelledError:
                raise
            except Exception:
                ERRORS.labels(site="net.dial_loop").inc()
                logger.exception("dial loop error")
            await asyncio.sleep(2)

    async def _dial_once(self) -> None:
        if self.trusted_peer is not None:
            if not self.outbound:
                await self.connect_to(self.trusted_peer)
            return
        if len(self.outbound) >= self.max_outbound:
            return
        peer = None
        # portcheck requests first (connectionchooser.py:37-44)
        while self._portcheck_queue:
            candidate = self._portcheck_queue.popleft()
            if candidate not in [Peer(c.host, c.port)
                                 for c in self.outbound]:
                peer = candidate
                break
        # 50% preference for LAN-discovered peers (connectionchooser.py)
        fresh_lan = [p for p, ts in self.lan_peers.items()
                     if time.time() - ts < 10800]
        if peer is None and fresh_lan and random.random() < 0.5:
            peer = random.choice(fresh_lan)
        if peer is None:
            peer = self.ctx.knownnodes.choose()
        if peer is None:
            return
        if peer in [Peer(c.host, c.port) for c in self.outbound]:
            return
        # network-group diversity (anti-Sybil, connectionpool.py:303-317)
        if network_group(peer.host) in self._used_groups():
            return
        await self.connect_to(peer)

    async def _inv_loop(self) -> None:
        """Per-second inv/dinv announcement batching (invthread.py)."""
        while True:
            await asyncio.sleep(INV_INTERVAL)
            try:
                await self._inv_once()
            except asyncio.CancelledError:
                raise
            except Exception:
                ERRORS.labels(site="net.inv_loop").inc()
                logger.exception("inv loop error")

    async def _flush_addr_gossip(self) -> None:
        """Ongoing addr relay (reference addrthread.py:13-49): peers
        newly learned since the last tick are re-advertised to every
        established connection, each entry leaving after a random
        sub-tick delay (the MultiQueue decorrelation)."""
        from .messages import encode_addr, encode_host

        fresh = self.ctx.knownnodes.newly_added
        if fresh:
            self.ctx.knownnodes.newly_added = []
            now = time.time()
            jitter = getattr(self.ctx, "announce_buckets", 10)
            for peer, stream in fresh:
                info = self.ctx.knownnodes.get(peer, stream)
                if not info or info.get("self"):
                    continue
                try:
                    encode_host(peer.host)
                except (OSError, ValueError):
                    # DNS bootstrap names / v3 onions aren't
                    # wire-encodable
                    continue
                entry = AddrEntry(info["lastseen"], stream, 1,
                                  peer.host, peer.port)
                self._addr_gossip.append(
                    (entry, now + random.uniform(0, jitter)))
        if not self._addr_gossip:
            return
        now = time.time()
        due = [e for e, d in self._addr_gossip if d <= now]
        if not due:
            return
        self._addr_gossip = [(e, d) for e, d in self._addr_gossip
                             if d > now]
        packet = encode_addr(due)
        for conn in self.established():
            try:
                await conn.send_packet("addr", packet)
            except (ConnectionError, OSError) as exc:
                # ongoing addr gossip is best-effort (the entries
                # re-advertise through other peers), but the failed
                # send must be COUNTED, not silently swallowed
                ERRORS.labels(site="net.send").inc()
                logger.debug("addr gossip to %s failed: %r",
                             conn.host, exc)
                continue

    async def _inv_once(self) -> None:
        await self._flush_addr_gossip()
        dand = self.ctx.dandelion
        if dand:
            for h, stream in dand.expire_fluffed():
                # stem timer expired: the hash is now an ordinary
                # fluff announcement and may use the sync paths
                self._route_announcement(h, self.established(),
                                         stream=stream)
        if self.reconciler is not None:
            await self.reconciler.tick()
        for conn in self.established():
            chunk = conn.tracker.take_announcements()
            if not chunk:
                continue
            fluffs, stems = [], []
            for h in chunk:
                child = dand.child_for(h) if dand else None
                if child is None:
                    fluffs.append(h)
                elif child is conn:
                    stems.append(h)
                # else: in stem phase routed to another child — skip
            random.shuffle(fluffs)
            sends = [(hs, stem) for hs, stem in
                     ((fluffs, False), (stems, True)) if hs]
            for i, (hashes, stem) in enumerate(sends):
                try:
                    await conn.announce(hashes, stem=stem)
                except (ConnectionError, OSError) as exc:
                    # a failed send must not LOSE the announcements —
                    # requeue ONLY the unsent groups (re-inv'ing the
                    # delivered portion would duplicate traffic) so
                    # the next tick re-delivers; a gone peer's tracker
                    # is discarded by connection_closed anyway
                    unsent = [h for hs, _ in sends[i:] for h in hs]
                    ERRORS.labels(site="net.send").inc()
                    ANNOUNCE_RETRIES.inc(len(unsent))
                    logger.debug("announce to %s failed (%r); requeued "
                                 "%d hashes", conn.host, exc, len(unsent))
                    for h in unsent:
                        conn.tracker.we_should_announce(h)
                    break

    async def _download_loop(self) -> None:
        while True:
            await asyncio.sleep(DOWNLOAD_INTERVAL)
            try:
                for conn in self.established():
                    await conn.request_objects()
            except asyncio.CancelledError:
                raise
            except Exception:
                ERRORS.labels(site="net.download_loop").inc()
                logger.exception("download loop error")

    async def _upload_loop(self) -> None:
        """Serve the queued getdata backlogs in a task of their own, at
        the cadence of the reference's uploadthread
        (uploadthread.py:15-69): ten objects a connection a round, round
        after round while any was served, a rest of ``UPLOAD_INTERVAL``
        after a round that served nothing.  One round a second inside
        the download loop capped a peer at ten objects a second, which a
        sender on four chips outruns four times over (PERF.md section 6,
        PR 37).

        A connection's ten are sent in a task of that connection's and
        a round waits ``UPLOAD_INTERVAL`` for them at most
        (``send_object`` waits for the socket's buffer to drain, with
        no deadline): a peer that asks for much and reads slowly
        finishes its ten in its own time and is given no more until it
        has, the others are served at no less than the ten a second a
        link always carried, and ``request_objects`` waits for no
        upload at all."""
        sending: dict = {}      # connection -> its ten, on their way
        try:
            while True:
                try:
                    served, waiting = await self._upload_round(sending)
                except asyncio.CancelledError:
                    raise
                except Exception:
                    ERRORS.labels(site="net.upload_loop").inc()
                    logger.exception("upload loop error")
                    served = waiting = 0
                if not served and not waiting:
                    await asyncio.sleep(UPLOAD_INTERVAL)
        finally:
            for task in sending.values():
                task.cancel()

    async def _upload_round(self, sending: dict) -> tuple[int, int]:
        """One round of :meth:`_upload_loop`: how many objects it
        served, and how many connections are still sending theirs."""
        for conn in self.established():
            if conn.pending_upload and conn not in sending:
                sending[conn] = asyncio.create_task(conn.flush_uploads())
        if not sending:
            return 0, 0
        done, pending = await asyncio.wait(sending.values(),
                                           timeout=UPLOAD_INTERVAL)
        served = 0
        for conn in [c for c, t in sending.items() if t in done]:
            task = sending.pop(conn)
            if task.cancelled():
                continue
            if task.exception() is None:
                served += task.result()
            else:
                # a send that failed: the connection's reader sees the
                # same socket and closes it
                ERRORS.labels(site="net.send").inc()
                logger.debug("upload to %s failed (%r)", conn.host,
                             task.exception())
        return served, len(pending)

    async def _maintenance_loop(self) -> None:
        while True:
            await asyncio.sleep(30)
            try:
                now = time.time()
                self.ctx.global_tracker.expire()
                for conn in self.connections():
                    conn.tracker.clean()
                    if conn.fully_established and \
                            now - conn.last_activity > PING_INTERVAL:
                        await conn.send_packet("ping")
                    if now - conn.last_activity > PING_INTERVAL * 2:
                        await conn.close()
                if self.ctx.dandelion:
                    self.ctx.dandelion.maybe_reassign(self.established())
            except asyncio.CancelledError:
                raise
            except Exception:
                ERRORS.labels(site="net.maintenance_loop").inc()
                logger.exception("maintenance loop error")
