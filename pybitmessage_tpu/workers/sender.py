"""Send pipeline: the sent-message state machine + PoW dispatch.

Reference: class_singleWorker.py — sendMsg (717-1373), sendBroadcast
(532-715), sendOutOrStoreMyV4Pubkey (417-530), requestPubKey
(1375-1493).  States: msgqueued -> (doingpubkeypow -> awaitingpubkey)
-> doingmsgpow -> msgsent -> ackreceived, with retry backoff
TTL*2^retries at 1.1*TTL intervals.

The PoW runs through an injected solver (TPU ladder); every solve is
interruptible via the node's shutdown flag.

Admission is rolling: a command does not wait for the sweep before it
to end, so a send queued while others are in flight starts at once,
and at most :data:`MAX_IN_FLIGHT` sends run at a time — the sent table
holds a long outbox, not a thousand coroutines.
"""

from __future__ import annotations

import asyncio
import contextvars
import logging
import random
import struct
import time
from typing import Callable

from ..crypto import decrypt, encrypt, sign, verify
from ..crypto.ecies import DecryptionError
from ..models import msgcoding
from ..models.constants import (
    DEFAULT_EXTRA_BYTES, DEFAULT_NONCE_TRIALS_PER_BYTE, OBJECT_BROADCAST,
    OBJECT_GETPUBKEY, OBJECT_MSG, OBJECT_ONIONPEER, OBJECT_PUBKEY,
    RIDICULOUS_DIFFICULTY,
)
from ..models.payloads import (
    MsgPlaintext, BroadcastPlaintext, PayloadError, PubkeyData,
    ack_ttl_bucket, assemble_getpubkey, assemble_pubkey,
    broadcast_signed_data, double_hash_of_address_data, get_bitfield,
    bitfield_does_ack, object_shell, parse_pubkey_inner,
)
from ..models.pow_math import pow_target
from ..observability import REGISTRY, trace
from ..storage.messages import (
    ACKRECEIVED, AWAITINGPUBKEY, BROADCASTSENT, DOINGMSGPOW,
    DOINGPUBKEYPOW, MSGQUEUED, MSGSENT, MSGSENTNOACKEXPECTED, MessageStore,
)
from ..utils.addresses import decode_address
from ..utils.hashes import inventory_hash, sha512
from ..utils.varint import decode_varint, encode_varint
from .cryptopool import CryptoPool
from .keystore import KeyStore, OwnIdentity

logger = logging.getLogger("pybitmessage_tpu.worker")

#: re-request a pubkey after this long (class_singleWorker.py getpubkey)
GETPUBKEY_RETRY = 2.5 * 24 * 3600
#: sends (messages and broadcasts) in flight at once: the slots of one
#: solve (``pow.service.SOLVE_SLOTS``), so a send's message follows its
#: ack into the solve within a few launches; the rest of the outbox
#: waits in the sent table
MAX_IN_FLIGHT = 256
#: a sweep's command and the sent-table statuses it admits
_SWEEPS = {"message": (("sendmessage",), (MSGQUEUED, "forcepow")),
           "broadcast": (("sendbroadcast",), ("broadcastqueued",))}

POW_WAIT_SECONDS = REGISTRY.histogram(
    "worker_pow_wait_seconds",
    "End-to-end PoW wait in the send pipeline: coalescing queue + "
    "solve + host verify")
ADMIT_SECONDS = REGISTRY.histogram(
    "sender_admit_seconds",
    "One admission pass of a sweep over the sent table: the read of "
    "the queued rows and the filter against the sends in flight",
    ("kind",))
ADMIT_ROWS = REGISTRY.counter(
    "sender_admit_rows_total",
    "Sent-table rows an admission pass read, admitted or not",
    ("kind",))
OBJECTS_PUBLISHED = REGISTRY.counter(
    "worker_objects_published_total",
    "Locally generated objects entered into the inventory",
    ("type",))
_TYPE_NAMES = {OBJECT_GETPUBKEY: "getpubkey", OBJECT_MSG: "msg",
               OBJECT_PUBKEY: "pubkey", OBJECT_BROADCAST: "broadcast",
               OBJECT_ONIONPEER: "onionpeer"}


def _jitter_ttl(ttl: int) -> int:
    return max(300, int(ttl + random.randrange(-300, 300)))


#: sign and encrypt as ``_run_crypto`` hands them to the executor: each
#: call is one span on the thread that does the work
_sign = trace("sender.sign")(sign)
_encrypt = trace("sender.encrypt")(encrypt)


def _sign_and_encrypt(plain, signed: bytes, priv_signing: bytes,
                      pub_enc: bytes) -> bytes:
    """A send's signature and its encryption as ONE executor job: the
    sends of a sweep then leave the executor one by one from the start
    (two jobs a send, first in first out, put every signature of the
    sweep before its first encryption), so the first asks for its PoW
    while the others are still being sealed."""
    plain.signature = _sign(signed, priv_signing)
    return _encrypt(plain.encode(), pub_enc)


class SendWorker:
    """Consumes send commands; drives the sent table state machine."""

    #: where ``_run_crypto`` runs its jobs: the receive side's pool,
    #: sized to ONE thread.  A job here is mostly Python around short
    #: OpenSSL calls, so it holds the interpreter lock: fanned over the
    #: default executor's 17 threads a sweep of 256 is sealed no sooner
    #: (0.6-0.7 s either way, on the chip's host), every job takes ten
    #: times as long (20.7 ms against 1.9), and the event loop, one
    #: waiter among 18, sees the first member sealed 658 ms after the
    #: sweep began, not 20 ms: the sweep's solve then starts as late as
    #: at the parent and the device idles 6.95 % against 1.08 (traced,
    #: PR 33: PERF.md section 6).  Members have to leave one by one for
    #: a solve to begin with the first.  How much of its time the one
    #: thread works is ``cryptopool_busy_seconds_total{pool="sender"}``.
    crypto = CryptoPool(1, name="sender")

    def __init__(self, *, keystore: KeyStore, store: MessageStore,
                 inventory, pool, solver: Callable,
                 pow_service=None,
                 shutdown: asyncio.Event | None = None,
                 min_ntpb: int = DEFAULT_NONCE_TRIALS_PER_BYTE,
                 min_extra: int = DEFAULT_EXTRA_BYTES,
                 ui_signal=None):
        #: UISignaler.emit-compatible callback (may be None)
        self.ui_signal = ui_signal or (lambda cmd, data=(): None)
        #: ``(h, type, stream, expires, tag, payload)`` hook for every
        #: locally published object — the light-client plane's feed for
        #: objects that never cross ctx.object_queue (roles/subscription)
        self.on_publish = None
        self.keystore = keystore
        self.store = store
        self.inventory = inventory
        self.pool = pool
        self.solver = solver  # solve(initial_hash, target) -> (nonce, trials)
        #: optional batching front-end (PowService) — when present, all
        #: concurrently pending sends coalesce into one pod-wide launch
        self.pow_service = pow_service
        self.min_ntpb = min_ntpb    # network-minimum PoW (test mode: /100)
        self.min_extra = min_extra
        self.shutdown = shutdown or asyncio.Event()
        self.queue: asyncio.Queue = asyncio.Queue()
        #: ackdata payloads we watch for (state.ackdataForWhichImWatching)
        self.watched_acks: set[bytes] = set()
        #: tag -> address for pubkeys we await (state.neededPubkeys analog)
        self.needed_pubkeys: dict[bytes, str] = {}
        #: (host, port) of our own onion endpoint; when set, start()
        #: publishes it as an ONIONPEER object (sendOnionPeerObj role)
        self.onion_peer: tuple[str, int] | None = None
        #: user-configurable ceilings on a recipient's demanded PoW
        #: (reference maxacceptablenoncetrialsperbyte /
        #: maxacceptablepayloadlengthextrabytes; 0 = unlimited, and the
        #: default matches the reference's ridiculousDifficulty x
        #: network-default sanity cap, helper_startup.py:225-240)
        self.max_acceptable_ntpb = \
            RIDICULOUS_DIFFICULTY * DEFAULT_NONCE_TRIALS_PER_BYTE
        self.max_acceptable_extra = \
            RIDICULOUS_DIFFICULTY * DEFAULT_EXTRA_BYTES
        self._task: asyncio.Task | None = None
        #: commands running beside the loop that took them
        self._commands: set[asyncio.Task] = set()
        #: ackdata of the sent rows whose send is in flight
        self._in_flight: set[bytes] = set()
        #: sweep commands that found rows and no room: put on the queue
        #: again when a send ends
        self._held: set[tuple] = set()

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> asyncio.Task:
        self.store.reset_interrupted_pow()
        self._rebuild_watchlists()
        # initial sweep: anything re-queued by reset_interrupted_pow (or
        # left queued at last shutdown) gets processed without waiting
        # for a new command (reference worker startup behavior)
        self.queue.put_nowait(("sendmessage",))
        self.queue.put_nowait(("sendbroadcast",))
        # announce our onion endpoint, if configured (the reference
        # enqueues 'sendOnionPeerObj' at worker startup the same way,
        # class_singleWorker.py:142-143)
        if self.onion_peer:
            self.queue.put_nowait(("sendonionpeer",))
        self._task = asyncio.create_task(self._run())
        return self._task

    async def stop(self) -> None:
        tasks = [t for t in (self._task, *self._commands) if t]
        for task in tasks:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        self._in_flight.clear()

    def _rebuild_watchlists(self) -> None:
        """Recover state from the sent table (class_singleWorker.py:72-117)."""
        for m in self.store.sent_by_status(MSGSENT, DOINGMSGPOW):
            self.watched_acks.add(m.ackdata)
        # (doingpubkeypow rows were already re-queued to msgqueued by
        # reset_interrupted_pow, which runs before this)
        for m in self.store.sent_by_status(AWAITINGPUBKEY):
            try:
                a = decode_address(m.toaddress)
            except Exception:
                logger.warning("sent row awaiting pubkey has "
                               "undecodable address %r", m.toaddress)
                continue
            tag = double_hash_of_address_data(a.version, a.stream, a.ripe)[32:]
            self.needed_pubkeys[tag] = m.toaddress

    async def _run(self) -> None:
        while not self.shutdown.is_set():
            cmd = await self.queue.get()
            # each command runs beside those before it: a send queued
            # while a sweep is in flight is not held for the sweep's end
            task = asyncio.create_task(self._dispatch(cmd))
            self._commands.add(task)
            task.add_done_callback(self._commands.discard)

    async def _dispatch(self, cmd: tuple) -> None:
        kind = cmd[0]
        try:
            if kind == "sendmessage":
                await self.process_queued_messages()
            elif kind == "sendbroadcast":
                await self.process_queued_broadcasts()
            elif kind == "sendpubkey":
                await self.send_my_pubkey(cmd[1])
            elif kind == "sendonionpeer":
                await self.send_onion_peer(*cmd[1:])
            else:
                logger.warning("unknown worker command %r", kind)
        except Exception:
            logger.exception("send worker command failed: %r", cmd[:1])

    # -- PoW helper ----------------------------------------------------------

    async def _run_crypto(self, fn, *args):
        """Run a scalar-mult-heavy crypto call (sign/encrypt) off the
        event loop — the send path's counterpart of the receive-side
        CryptoPool hop (keeps the loop-lag budget; lint-enforced).
        The context is copied across the hop, so the callable's span
        is a child of the sweep that asked for it."""
        return await self.crypto.run(
            contextvars.copy_context().run, fn, *args)

    async def _do_pow(self, payload_sans_nonce: bytes, ttl: int,
                      ntpb: int = 0, extra: int = 0) -> bytes:
        """Solve and prepend the nonce (class_singleWorker._doPOWDefaults)."""
        target = pow_target(len(payload_sans_nonce) + 8, ttl,
                            ntpb or self.min_ntpb, extra or self.min_extra,
                            clamp=False)
        initial = sha512(payload_sans_nonce)
        t0 = time.monotonic()
        with trace("worker.pow", bytes=len(payload_sans_nonce) + 8,
                   histogram=POW_WAIT_SECONDS):
            if self.pow_service is not None:
                nonce, trials = await self.pow_service.solve(initial, target)
            else:
                loop = asyncio.get_running_loop()
                nonce, trials = await loop.run_in_executor(
                    None,
                    lambda: self.solver(initial, target,
                                        should_stop=self.shutdown.is_set))
        dt = max(time.monotonic() - t0, 1e-9)
        logger.info("PoW done: %d trials in %.2fs (%.0f H/s)",
                    trials, dt, trials / dt)
        return struct.pack(">Q", nonce) + payload_sans_nonce

    @trace("sender.publish")
    def _publish(self, payload: bytes, object_type: int, stream: int,
                 tag: bytes = b"") -> bytes:
        h = inventory_hash(payload)
        expires = int.from_bytes(payload[8:16], "big")
        OBJECTS_PUBLISHED.labels(
            type=_TYPE_NAMES.get(object_type, str(object_type))).inc()
        self.inventory.add(h, object_type, stream, payload, expires, tag)
        if self.pool is not None:
            self.pool.announce_object(h, stream, local=True)
        if self.on_publish is not None:
            self.on_publish(h, object_type, stream, expires, tag, payload)
        return h

    # -- msg sending ---------------------------------------------------------

    async def process_queued_messages(self) -> None:
        # Send concurrently: each message's PoW request lands in the
        # PowService coalescing window or, while a solve is running,
        # in a slot that has come free.
        await self._sweep("message", self._send_one_msg)

    async def _sweep(self, kind: str, send_one) -> None:
        """Admit queued rows of ``kind``, oldest first, as far as
        :data:`MAX_IN_FLIGHT` allows, and run their sends; returns when
        those it admitted have ended.  Where rows are left waiting, the
        first send to end puts the command on the queue again, so the
        next is admitted then and not at this sweep's end."""
        command, statuses = _SWEEPS[kind]
        if self.shutdown.is_set():
            return
        room = MAX_IN_FLIGHT - len(self._in_flight)
        if room <= 0:
            self._held.add(command)
            return
        # a row in flight may still be in its queued status (a message
        # until its task first runs, a broadcast until it is published),
        # so one row more than can be in flight is read: whatever the
        # in-flight rows leave of that is ``room`` rows to admit and one
        # that says the outbox goes on
        t0 = time.perf_counter()
        read = self.store.sent_by_status(*statuses,
                                         limit=MAX_IN_FLIGHT + 1)
        rows = [m for m in read if m.ackdata not in self._in_flight]
        ADMIT_SECONDS.labels(kind=kind).observe(time.perf_counter() - t0)
        ADMIT_ROWS.labels(kind=kind).inc(len(read))
        msgs = rows[:room]
        if not msgs:
            return
        if len(rows) > room:
            self._held.add(command)

        async def tracked(m):
            try:
                await send_one(m)
            finally:
                self._in_flight.discard(m.ackdata)
            # a slot is free (a send that raised asks for nothing: its
            # row may still be queued, and would be admitted again)
            while self._held:
                self.queue.put_nowait(self._held.pop())

        self._in_flight.update(m.ackdata for m in msgs)
        with trace("sender.sweep", kind=kind, objects=len(msgs),
                   in_flight=len(self._in_flight)):
            results = await self._gather_sweep(tracked(m) for m in msgs)
        for m, r in zip(msgs, results):
            if isinstance(r, BaseException) and \
                    not isinstance(r, asyncio.CancelledError):
                logger.error("%s send failed, %s -> %s: %r", kind,
                             m.fromaddress, m.toaddress, r)

    async def _gather_sweep(self, sends) -> list:
        """Run a sweep's sends concurrently, announced to the PoW
        service before any of them runs: its coalescing window then
        closes when the last of them has asked for its PoW, and a lone
        send is not held for company that cannot come."""
        members = [asyncio.ensure_future(send) for send in sends]
        if self.pow_service is not None:
            self.pow_service.announce(members)
        return await asyncio.gather(*members, return_exceptions=True)

    async def _send_one_msg(self, m) -> None:
        to = decode_address(m.toaddress)
        sender = self.keystore.get(m.fromaddress)
        if sender is None:
            logger.error("own address %s missing from keystore",
                         m.fromaddress)
            self.store.update_sent_status(m.ackdata, "badkey")
            return

        if self.keystore.owns(m.toaddress):
            recipient = self.keystore.get(m.toaddress)
            pub_enc = recipient.pub_encryption_key
            their_ntpb = self.min_ntpb
            their_extra = self.min_extra
            their_bitfield_acks = False  # no ack to self/chan
        else:
            pubkey = self._lookup_pubkey(to, m.toaddress)
            if pubkey is None:
                await self._request_pubkey(to, m.toaddress, m.ackdata)
                return
            their_ntpb = max(pubkey.nonce_trials_per_byte, self.min_ntpb)
            their_extra = max(pubkey.extra_bytes, self.min_extra)
            # refuse recipients demanding more work than the user is
            # willing to do — 'forcepow' overrides, 0 means unlimited
            # (class_singleWorker.py:1060-1091)
            if m.status != "forcepow" and (
                    (self.max_acceptable_ntpb
                     and their_ntpb > self.max_acceptable_ntpb)
                    or (self.max_acceptable_extra
                        and their_extra > self.max_acceptable_extra)):
                self.store.update_sent_status(m.ackdata, "toodifficult")
                self.ui_signal("updateSentItemStatusByAckdata",
                               (m.ackdata, "toodifficult"))
                return
            pub_enc = pubkey.pub_encryption_key
            their_bitfield_acks = bitfield_does_ack(pubkey.bitfield)

        self.store.update_sent_status(m.ackdata, DOINGMSGPOW)
        ttl = _jitter_ttl(m.ttl or 4 * 24 * 3600)
        expires = int(time.time()) + ttl

        # optional pre-PoW'd ack packet embedded in the plaintext
        ack_packet = b""
        if not self.keystore.owns(m.toaddress) and their_bitfield_acks:
            ack_packet = await self._make_full_ack(m.ackdata, to.stream, ttl)

        body = msgcoding.encode_message(m.subject, m.message,
                                        m.encodingtype or 2)
        plain = MsgPlaintext(
            sender_version=sender.version, sender_stream=sender.stream,
            bitfield=get_bitfield(True),
            pub_signing_key=sender.pub_signing_key,
            pub_encryption_key=sender.pub_encryption_key,
            nonce_trials_per_byte=sender.nonce_trials_per_byte,
            extra_bytes=sender.extra_bytes,
            dest_ripe=to.ripe, encoding=m.encodingtype or 2,
            message=body, ack_data=ack_packet)
        unsigned = plain.encode_unsigned()
        # msg object shell: expires + type(2) + msgver(1) + stream; the
        # signature covers shell-sans-nonce + plaintext through ackdata
        # (class_singleWorker.py:1224-1228)
        shell = object_shell(expires, OBJECT_MSG, 1, to.stream)
        encrypted = await self._run_crypto(
            _sign_and_encrypt, plain, shell + unsigned,
            sender.priv_signing, pub_enc)
        payload = shell + encrypted
        payload = await self._do_pow(payload, ttl, their_ntpb, their_extra)
        h = self._publish(payload, OBJECT_MSG, to.stream)
        logger.info("msg sent, inventory hash %s", h.hex())

        if self.keystore.owns(m.toaddress):
            # loopback: deliver straight to our inbox
            # (class_singleWorker.py:1350-1373)
            sighash = sha512(plain.signature)
            self.store.deliver_inbox(
                msgid=h, toaddress=m.toaddress, fromaddress=m.fromaddress,
                subject=m.subject, message=m.message,
                encoding=m.encodingtype or 2, sighash=sighash)
            self.store.update_sent_status(m.ackdata, ACKRECEIVED)
            self.ui_signal("displayNewInboxMessage",
                           (h, m.toaddress, m.fromaddress, m.subject,
                            m.message))
        elif ack_packet:
            self.watched_acks.add(m.ackdata)
            self.store.update_sent_status(
                m.ackdata, MSGSENT,
                sleeptill=int(time.time() + 1.1 * ttl))
        else:
            self.store.update_sent_status(m.ackdata, MSGSENTNOACKEXPECTED)

    async def _make_full_ack(self, ackdata: bytes, stream: int,
                             ttl: int) -> bytes:
        """Pre-PoW'd ack the recipient floods back verbatim
        (generateFullAckMessage, class_singleWorker.py:1495-1519)."""
        ack_ttl = _jitter_ttl(ack_ttl_bucket(ttl))
        expires = int(time.time()) + ack_ttl
        payload = struct.pack(">Q", expires) + ackdata
        payload = await self._do_pow(payload, ack_ttl)
        from ..models.packet import pack_packet
        return pack_packet("object", payload)

    # -- pubkey lookup / request ---------------------------------------------

    def _lookup_pubkey(self, to, toaddress: str) -> PubkeyData | None:
        raw = self.store.get_pubkey(toaddress)
        if raw is not None:
            return parse_pubkey_inner(raw, to.version, to.stream)
        if to.version >= 4:
            # look in the inventory for tagged pubkey objects we can
            # decrypt (protocol.py:401-529 decryptAndCheckPubkeyPayload)
            tag = double_hash_of_address_data(
                to.version, to.stream, to.ripe)[32:]
            for item in self.inventory.by_type_and_tag(OBJECT_PUBKEY, tag):
                data = self._decrypt_pubkey_object(item.payload, to)
                if data is not None:
                    self.store.store_pubkey(
                        toaddress, to.version,
                        _pubkey_inner_bytes(data), used_personally=True)
                    return data
        return None

    def _decrypt_pubkey_object(self, payload: bytes, to) -> PubkeyData | None:
        try:
            from ..models.objects import ObjectHeader
            hdr = ObjectHeader.parse(payload)
            if hdr.version != to.version:
                return None
            dh = double_hash_of_address_data(to.version, to.stream, to.ripe)
            blob = payload[hdr.header_length + 32:]
            inner = decrypt(blob, dh[:32])
            data = parse_pubkey_inner(inner, to.version, to.stream)
            # verify: sig covers payload-through-tag + inner-through-extra
            span = 4 + 64 + 64
            i = span
            _, n = decode_varint(inner, i)
            i += n
            _, n = decode_varint(inner, i)
            i += n
            signed = payload[8:hdr.header_length + 32] + inner[:i]
            if not verify(signed, data.signature, data.pub_signing_key):
                return None
            from ..utils.hashes import address_ripe
            if address_ripe(data.pub_signing_key,
                            data.pub_encryption_key) != to.ripe:
                return None
            return data
        except (DecryptionError, PayloadError, ValueError):
            return None
        except Exception:
            logger.exception("unexpected error verifying v4 pubkey object")
            return None

    async def _request_pubkey(self, to, toaddress: str,
                              ackdata: bytes) -> None:
        tag = double_hash_of_address_data(to.version, to.stream, to.ripe)[32:]
        if tag in self.needed_pubkeys:
            # already requested: park until the normal retry horizon so
            # the resend sweep doesn't immediately re-fire it
            self.store.update_sent_status(
                ackdata, AWAITINGPUBKEY,
                sleeptill=int(time.time() + GETPUBKEY_RETRY))
            return
        self.needed_pubkeys[tag] = toaddress
        ttl = _jitter_ttl(int(GETPUBKEY_RETRY / 2.5))
        expires = int(time.time()) + ttl
        payload = assemble_getpubkey(expires, to.version, to.stream, to.ripe)
        # visible while the getpubkey PoW runs; a crash here is
        # re-queued by reset_interrupted_pow at next startup
        # (class_singleWorker.py:874-895 doingpubkeypow stage)
        self.store.update_sent_status(ackdata, DOINGPUBKEYPOW)
        payload = await self._do_pow(payload, ttl)
        self._publish(payload, OBJECT_GETPUBKEY, to.stream)
        self.store.update_sent_status(
            ackdata, AWAITINGPUBKEY,
            sleeptill=int(time.time() + GETPUBKEY_RETRY))
        logger.info("requested pubkey for %s", toaddress)

    # -- own pubkey publication ----------------------------------------------

    async def send_my_pubkey(self, address: str) -> None:
        ident = self.keystore.get(address)
        if ident is None:
            return
        ttl = _jitter_ttl(28 * 24 * 3600)
        expires = int(time.time()) + ttl
        data = PubkeyData(
            ident.version, ident.stream, get_bitfield(True),
            ident.pub_signing_key, ident.pub_encryption_key,
            ident.nonce_trials_per_byte, ident.extra_bytes)
        payload = assemble_pubkey(
            expires, data, ident.ripe,
            sign_fn=lambda d: sign(d, ident.priv_signing))
        payload = await self._do_pow(payload, ttl)
        tag = ident.tag if ident.version >= 4 else b""
        self._publish(payload, OBJECT_PUBKEY, ident.stream, tag)
        self.keystore.touch_pubkey_sent(address)
        logger.info("published pubkey for %s", address)

    def queue_broadcast(self, fromaddress: str, subject: str,
                        message: str, *, ttl: int = 4 * 24 * 3600,
                        encoding: int = 2, stream: int = 1,
                        toaddress: str = "[Broadcast]") -> bytes:
        """Enqueue a broadcast row and nudge the worker; the single
        owner of the queued-broadcast contract (helper_sent.insert with
        status='broadcastqueued') for Node.send_broadcast and the
        mailing-list rebroadcast path alike."""
        import os
        from ..models.payloads import gen_ack_payload
        ack = gen_ack_payload(stream, 0)
        self.store.queue_sent(
            msgid=os.urandom(16), toaddress=toaddress, toripe=b"",
            fromaddress=fromaddress, subject=subject, message=message,
            ackdata=ack, ttl=ttl, encoding=encoding,
            status="broadcastqueued")
        self.queue.put_nowait(("sendbroadcast",))
        return ack

    # -- onionpeer announcement ----------------------------------------------

    async def send_onion_peer(self, peer: tuple[str, int] | None = None,
                              stream: int = 1) -> None:
        """Flood an ONIONPEER object naming an onion endpoint — ours by
        default (reference sendOnionPeerObj,
        class_singleWorker.py:494-530).  Body: varint port + 16-byte
        encoded host; dedup by tag so an unexpired copy isn't redone."""
        peer = peer or self.onion_peer
        if not peer:
            return
        host, port = peer
        from ..network.messages import encode_host
        try:
            body = encode_varint(port) + encode_host(host)
        except Exception:
            # expected for v3 onions (56 chars > the 16-byte addr
            # field): the service still serves inbound Tor dials, it
            # just can't be flooded — debug, not a per-start warning
            logger.debug("onion endpoint %r not wire-encodable; "
                         "skipping ONIONPEER announcement", host)
            return
        tag = inventory_hash(body)
        if any(item.expires > time.time() for item in
               self.inventory.by_type_and_tag(OBJECT_ONIONPEER, tag)):
            return          # an unexpired announcement is circulating
        ttl = _jitter_ttl(7 * 24 * 3600)
        expires = int(time.time()) + ttl
        # object version 2 for v2 onions (22-char hostname), else 3
        # (matches the reference's wire choice)
        version = 2 if len(host) == 22 else 3
        payload = object_shell(expires, OBJECT_ONIONPEER, version,
                               stream) + body
        payload = await self._do_pow(payload, ttl)
        self._publish(payload, OBJECT_ONIONPEER, stream, tag)
        logger.info("published onionpeer object for %s:%d", host, port)

    # -- broadcast sending ---------------------------------------------------

    async def process_queued_broadcasts(self) -> None:
        await self._sweep("broadcast", self._send_one_broadcast)

    async def _send_one_broadcast(self, m) -> None:
        sender = self.keystore.get(m.fromaddress)
        if sender is None:
            self.store.update_sent_status(m.ackdata, "badkey")
            return
        ttl = _jitter_ttl(min(max(m.ttl or 4 * 24 * 3600, 3600),
                              28 * 24 * 3600))
        expires = int(time.time()) + ttl
        obj_version = 4 if sender.version <= 3 else 5
        shell = (struct.pack(">Q", expires) + b"\x00\x00\x00\x03"
                 + encode_varint(obj_version)
                 + encode_varint(sender.stream))
        dh = double_hash_of_address_data(
            sender.version, sender.stream, sender.ripe)
        tag = b""
        if sender.version >= 4:
            tag = dh[32:]
            shell += tag

        body = msgcoding.encode_message(m.subject, m.message,
                                        m.encodingtype or 2)
        plain = BroadcastPlaintext(
            sender.version, sender.stream, get_bitfield(True),
            sender.pub_signing_key, sender.pub_encryption_key,
            sender.nonce_trials_per_byte, sender.extra_bytes,
            m.encodingtype or 2, body)
        unsigned = plain.encode_unsigned()
        if sender.version <= 3:
            from ..models.payloads import broadcast_v4_key
            key = broadcast_v4_key(sender.version, sender.stream, sender.ripe)
        else:
            key = dh[:32]
        from ..crypto import priv_to_pub
        payload = shell + await self._run_crypto(
            _sign_and_encrypt, plain,
            broadcast_signed_data(shell, unsigned), sender.priv_signing,
            priv_to_pub(key))
        payload = await self._do_pow(payload, ttl)
        h = self._publish(payload, OBJECT_BROADCAST, sender.stream, tag)
        self.store.update_sent_status(m.ackdata, BROADCASTSENT)
        logger.info("broadcast sent, hash %s", h.hex())

    # -- resend (cleaner hook) ----------------------------------------------

    async def resend_stale(self) -> None:
        """Re-queue messages whose sleeptill passed, doubling TTL
        (class_singleCleaner.py:92-106, singleWorker.py:900-904)."""
        for m in self.store.due_for_resend():
            new_ttl = min(m.ttl * 2, 28 * 24 * 3600)
            self.store.bump_retry(m.ackdata, new_ttl, 0)
            if m.status == AWAITINGPUBKEY:
                try:
                    to = decode_address(m.toaddress)
                except Exception:
                    logger.warning("resend row has undecodable "
                                   "address %r", m.toaddress)
                    continue
                tag = double_hash_of_address_data(
                    to.version, to.stream, to.ripe)[32:]
                self.needed_pubkeys.pop(tag, None)
                self.store.update_sent_status(m.ackdata, MSGQUEUED)
            else:
                self.watched_acks.discard(m.ackdata)
                self.store.update_sent_status(m.ackdata, MSGQUEUED)
            await self.queue.put(("sendmessage",))



def _pubkey_inner_bytes(data: PubkeyData) -> bytes:
    """Serialize the pubkey body the way the pubkeys table stores it."""
    out = data.bitfield + data.pub_signing_key[1:] + \
        data.pub_encryption_key[1:]
    if data.address_version >= 3:
        out += encode_varint(data.nonce_trials_per_byte)
        out += encode_varint(data.extra_bytes)
        out += encode_varint(len(data.signature)) + data.signature
    return out
