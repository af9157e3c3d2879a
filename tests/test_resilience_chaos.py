"""Seeded chaos suite (ISSUE 3 acceptance criteria; ``make chaos``).

Deterministic fault injection at the named sites proves the
no-object-loss property: with faults at device launch, readback, db
write, and socket send, every queued PoW object is either solved (and
host-verified) or journaled/requeued — and a killed-and-restarted
solve resumes from its checkpointed nonce offset rather than 0.

Every test arms the process-wide CHAOS registry and disarms it in a
finally block; the suite runs on the CPU mesh inside the tier-1
``not slow`` budget.
"""

import asyncio
import hashlib
import time
from types import SimpleNamespace

import pytest

from pybitmessage_tpu.observability import REGISTRY
from pybitmessage_tpu.pow import PowDispatcher
from pybitmessage_tpu.pow.dispatcher import host_trial
from pybitmessage_tpu.pow.service import PowService
from pybitmessage_tpu.resilience import CHAOS, ChaosError, PowJournal

SEED = 1234
EASY = 2**58


def _ih(tag):
    return hashlib.sha512(b"chaos %r" % tag).digest()


def setup_function(_fn):
    CHAOS.disarm()
    CHAOS.seed(SEED)


def teardown_function(_fn):
    CHAOS.disarm()


# ---------------------------------------------------------------------------
# device-launch faults: the ladder + breaker rescue every object
# ---------------------------------------------------------------------------


def test_no_object_loss_under_device_launch_faults():
    d = PowDispatcher(use_native=False,
                      tpu_kwargs={"lanes": 256, "chunks_per_call": 8})
    CHAOS.arm("pow.device_launch", probability=1.0, count=3)
    items = [(_ih(i), EASY) for i in range(4)]
    before = REGISTRY.sample("chaos_injected_total",
                             {"site": "pow.device_launch"})
    results = d.solve_batch(items)
    assert REGISTRY.sample("chaos_injected_total",
                           {"site": "pow.device_launch"}) > before
    # every object solved, every nonce host-verified — faults only
    # moved the work to a lower tier
    assert len(results) == len(items)
    for (ih, target), (nonce, _) in zip(items, results):
        assert host_trial(nonce, ih) <= target
    assert d.last_backend == "python"
    assert d.breakers["tpu"].state == "open", \
        "repeated launch faults must open the tier breaker"


# ---------------------------------------------------------------------------
# readback faults: the pipelined path loses no progress
# ---------------------------------------------------------------------------


def test_pipeline_readback_fault_then_resume_from_checkpoint():
    """A readback fault kills the pipelined solve mid-search; the
    checkpoints its harvests already recorded let the retry resume
    from the last proven-miss-free offset instead of nonce 0 — the
    same (start_nonces, progress) contract PowService drives."""
    from pybitmessage_tpu.pow.pipeline import (BatchPlan,
                                               solve_batch_pipelined)

    items = [(_ih("rb0"), 2**49), (_ih("rb1"), 2**49)]
    checkpoints = {}

    def progress(i, nxt):
        checkpoints[i] = max(checkpoints.get(i, 0), nxt)

    # tiny explicit plan (the bench-smoke trick): the XLA stand-in has
    # no early exit, so small slabs keep the test fast on CPU
    plan = BatchPlan("packed", 2, 8, [0, 1])
    # fire once, after a couple of clean harvests
    CHAOS.arm("pow.readback", probability=0.34, count=1)
    attempts = 0
    results = None
    while results is None:
        attempts += 1
        assert attempts <= 40, "fault storm never converged"
        starts = [checkpoints.get(i, 0) for i in range(len(items))]
        try:
            results = solve_batch_pipelined(
                items, impl="xla", rows=32, plan=plan,
                start_nonces=starts, progress=progress)
        except ChaosError:
            continue
    for (ih, target), (nonce, _) in zip(items, results):
        check = hashlib.sha512(hashlib.sha512(
            nonce.to_bytes(8, "big") + ih).digest()).digest()
        assert int.from_bytes(check[:8], "big") <= target
    if max(checkpoints.values(), default=0) > 0 and attempts > 1:
        # when the fault did interrupt the search, the retry resumed
        # from a non-zero offset (the point of the checkpoint)
        assert any(s > 0 for s in starts)


@pytest.mark.parametrize("mode, n", [("packed", 2), ("slab", 1)])
def test_pipeline_stall_watchdog_abandons_wedged_readback(mode, n):
    """A wedged device->host transfer (simulated by an injected delay)
    trips the slab-stall watchdog instead of hanging the pipeline —
    for a queue and, since the lone-object search runs in the same
    loop, for one object alone."""
    from pybitmessage_tpu.ops.pow_search import PowInterrupted
    from pybitmessage_tpu.pow.pipeline import (BatchPlan,
                                               solve_batch_pipelined)
    from pybitmessage_tpu.resilience import SlabStallError

    items = [(_ih("stall%d" % i), EASY) for i in range(n)]
    plan = BatchPlan(mode, n, 8, list(range(n)))
    before = REGISTRY.sample("pow_stall_total", {"site": "pow.slab"})
    CHAOS.arm("pow.readback", delay=1.0, count=1)
    with pytest.raises((SlabStallError, PowInterrupted)):
        solve_batch_pipelined(items, impl="xla", rows=32, plan=plan,
                              stall_timeout=0.05)
    assert REGISTRY.sample("pow_stall_total",
                           {"site": "pow.slab"}) == before + 1
    CHAOS.disarm()
    # the rescued retry completes normally
    results = solve_batch_pipelined(items, impl="xla", rows=32,
                                    plan=plan)
    assert all(r is not None for r in results)


# ---------------------------------------------------------------------------
# db-write faults: journal + store writes absorb transient failures
# ---------------------------------------------------------------------------


@pytest.mark.asyncio
async def test_no_object_loss_under_db_write_faults():
    class InstantDispatcher:
        last_backend = "instant"

        def solve_batch(self, items, should_stop=None, start_nonces=None,
                        progress=None):
            return [(11, 1)] * len(items)

    journal = PowJournal()
    CHAOS.arm("db.write", probability=0.5)
    svc = PowService(InstantDispatcher(), window=0.01, journal=journal)
    svc.start()
    try:
        results = await asyncio.wait_for(
            asyncio.gather(*(svc.solve(_ih(i), 2**60) for i in range(8))),
            timeout=30)
        assert results == [(11, 1)] * 8, \
            "journal write faults must never fail a solve"
    finally:
        await svc.stop()
        CHAOS.disarm()
        journal.close()


def test_database_write_retry_absorbs_transient_faults():
    from pybitmessage_tpu.storage.db import Database

    db = Database()
    # p=0.5 with 3 attempts: most writes succeed through the retry;
    # run enough writes that at least one needed a retry (seeded)
    CHAOS.arm("db.write", probability=0.5)
    before = REGISTRY.sample("resilience_retry_total",
                             {"site": "db.write", "outcome": "retried"})
    ok = failed = 0
    for i in range(24):
        try:
            db.set_setting("chaos-%d" % i, str(i))
            ok += 1
        except ChaosError:
            failed += 1
    CHAOS.disarm()
    assert ok > 0
    assert REGISTRY.sample(
        "resilience_retry_total",
        {"site": "db.write", "outcome": "retried"}) > before
    # every write that reported success is durably visible
    for i in range(24):
        val = db.get_setting("chaos-%d" % i)
        if val is not None:
            assert val == str(i)
    db.close()


# ---------------------------------------------------------------------------
# socket-send faults: announcements requeue instead of vanishing
# ---------------------------------------------------------------------------


@pytest.mark.asyncio
async def test_inv_announcements_requeue_on_send_failure():
    from pybitmessage_tpu.network.pool import ConnectionPool, NodeContext
    from pybitmessage_tpu.network.tracker import ConnectionTracker
    from pybitmessage_tpu.storage.db import Database
    from pybitmessage_tpu.storage.inventory import Inventory
    from pybitmessage_tpu.storage.knownnodes import KnownNodes

    ctx = NodeContext(inventory=Inventory(Database()),
                      knownnodes=KnownNodes(None), dandelion=None)
    pool = ConnectionPool(ctx)

    sent = []

    class StubConn:
        fully_established = True
        host, port = "203.0.113.9", 8444

        def __init__(self):
            self.tracker = ConnectionTracker(buckets=1)

        async def announce(self, hashes, stem=False):
            # chaos net.send defaults to ConnectionError — the same
            # handler path a dead peer exercises
            CHAOS.inject("net.send")
            sent.extend(hashes)

    conn = StubConn()
    pool.inbound[conn] = None
    h = b"\xab" * 32
    conn.tracker.we_should_announce(h)

    CHAOS.arm("net.send", probability=1.0, count=2)
    before = REGISTRY.sample("network_announce_requeue_total")
    for _ in range(40):             # ticks until the fault budget burns
        await pool._inv_once()
        if sent:
            break
        await asyncio.sleep(0.05)
    assert sent == [h], \
        "the announcement must survive failed sends and go out"
    assert REGISTRY.sample("network_announce_requeue_total") > before


# ---------------------------------------------------------------------------
# crash + restart: the journaled solve resumes from its checkpoint
# ---------------------------------------------------------------------------


@pytest.mark.asyncio
async def test_killed_and_restarted_solve_resumes_from_checkpoint(tmp_path):
    path = str(tmp_path / "powjournal.dat")
    ih = _ih("resume")
    impossible = 1                  # never solves: forces checkpoints

    # -- process 1: solve until checkpoints land, then "crash" ----------
    journal = PowJournal(path)
    shutdown = asyncio.Event()
    svc = PowService(PowDispatcher(use_tpu=False, use_native=False),
                     window=0.0, shutdown=shutdown, journal=journal)
    svc.start()
    solve_task = asyncio.ensure_future(svc.solve(ih, impossible))
    job_checkpoint = 0
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        jobs = journal.pending()
        if jobs and jobs[0].start_nonce > 0:
            job_checkpoint = jobs[0].start_nonce
            break
        await asyncio.sleep(0.05)
    assert job_checkpoint > 0, "the python tier must checkpoint progress"
    shutdown.set()                  # interrupt mid-solve
    with pytest.raises(asyncio.CancelledError):
        await asyncio.wait_for(solve_task, timeout=30)
    await svc.stop()
    journal.close()                 # crash boundary

    # -- process 2: same payload re-queued after restart ----------------
    journal2 = PowJournal(path)
    recovered = journal2.pending()
    assert len(recovered) == 1 and recovered[0].status == "queued"
    assert recovered[0].start_nonce >= job_checkpoint

    class SpyDispatcher:
        last_backend = "spy"
        seen_starts = None

        def solve_batch(self, items, should_stop=None, start_nonces=None,
                        progress=None):
            SpyDispatcher.seen_starts = list(start_nonces)
            return [(start_nonces[0], 1)]

    svc2 = PowService(SpyDispatcher(), window=0.0, journal=journal2)
    svc2.start()
    try:
        await asyncio.wait_for(svc2.solve(ih, impossible), timeout=10)
        assert SpyDispatcher.seen_starts[0] >= job_checkpoint > 0, \
            "restarted solve must resume from the checkpoint, not 0"
    finally:
        await svc2.stop()
        journal2.close()


# ---------------------------------------------------------------------------
# observability: breaker/stall/journal state is exported
# ---------------------------------------------------------------------------


def test_breaker_and_stall_state_visible_in_metrics_and_clientstatus():
    from pybitmessage_tpu.api.commands import CommandHandler

    # a dispatcher construction registers the pow tier breakers
    PowDispatcher(use_tpu=False, use_native=False)
    text = REGISTRY.render()
    for family in ("resilience_breaker_state",
                   "resilience_breaker_transitions_total",
                   "pow_stall_total", "pow_requeue_total",
                   "pow_journal_jobs", "chaos_injected_total"):
        assert "# TYPE %s " % family in text, family

    handler = CommandHandler(SimpleNamespace(pow_journal=None))
    stats = handler._resilience_stats()
    assert "pow.tier.tpu" in stats["breakers"]
    assert stats["breakers"]["pow.tier.tpu"]["state"] in (
        "closed", "half-open", "open")
    for key in ("stallEvents", "powRequeues", "journal", "chaos",
                "handshakeTimeouts"):
        assert key in stats


def test_seeded_chaos_run_lands_in_flight_recorder_dump():
    """ISSUE 6 acceptance: a seeded chaos run that trips a breaker
    leaves the triggering events (chaos fire + breaker transition) in
    the flight-recorder ring, and a dump contains them."""
    from pybitmessage_tpu.observability import FLIGHT_RECORDER

    d = PowDispatcher(use_native=False,
                      tpu_kwargs={"lanes": 256, "chunks_per_call": 8})
    CHAOS.arm("pow.device_launch", probability=1.0, count=3)
    d.solve_batch([(_ih("flightrec"), EASY)])

    before = REGISTRY.sample("flightrec_dumps_total", {"trigger": "api"})
    events = FLIGHT_RECORDER.dump("api")
    assert REGISTRY.sample("flightrec_dumps_total",
                           {"trigger": "api"}) == before + 1
    chaos_events = [e for e in events if e.get("kind") == "chaos"
                    and e.get("site") == "pow.device_launch"]
    assert chaos_events, "chaos injection missing from the dump"
    breaker_events = [e for e in events if e.get("kind") == "breaker"]
    assert breaker_events, "breaker transition missing from the dump"
    # the dump orders by sequence: the post-mortem can reconstruct
    # what fired in the run-up
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)


@pytest.mark.asyncio
@pytest.mark.parametrize("sites", [
    ("crypto.native",),
    ("crypto.tpu", "crypto.native"),
], ids=["native", "tpu_and_native"])
async def test_no_object_loss_under_crypto_faults(sites):
    """ISSUE 7 + ISSUE 13 acceptance: with the ``crypto.native`` (and,
    in the second variant, also the ``crypto.tpu``) chaos site at
    100%% fire rate, every msg object still decrypts, verifies and
    delivers — the drain walks the WHOLE ladder tpu -> native -> pure
    end to end with zero objects lost — and the per-rung fallback
    counters increment."""
    from pybitmessage_tpu.crypto import encrypt, sign
    from pybitmessage_tpu.models import msgcoding
    from pybitmessage_tpu.models.constants import OBJECT_MSG
    from pybitmessage_tpu.models.payloads import (MsgPlaintext,
                                                  get_bitfield,
                                                  object_shell)
    from pybitmessage_tpu.storage.db import Database
    from pybitmessage_tpu.storage.messages import MessageStore
    from pybitmessage_tpu.workers.keystore import KeyStore
    from pybitmessage_tpu.workers.processor import ObjectProcessor

    ks = KeyStore()
    idents = [ks.create_random("chaos %d" % i) for i in range(3)]
    for ident in idents:
        ident.nonce_trials_per_byte = 1
        ident.extra_bytes = 1
    sender = idents[0]
    ttl = 3600
    expires = int(time.time()) + ttl
    shell = object_shell(expires, OBJECT_MSG, 1, 1)

    def build(i: int) -> bytes:
        from pybitmessage_tpu.models.pow_math import pow_target
        from pybitmessage_tpu.pow.dispatcher import python_solve
        from pybitmessage_tpu.utils.hashes import sha512

        r = idents[i % 3]
        body = msgcoding.encode_message("chaos %d" % i, "body %d" % i)
        plain = MsgPlaintext(
            sender_version=sender.version, sender_stream=1,
            bitfield=get_bitfield(False),
            pub_signing_key=sender.pub_signing_key,
            pub_encryption_key=sender.pub_encryption_key,
            nonce_trials_per_byte=1, extra_bytes=1,
            dest_ripe=r.ripe, encoding=2, message=body, ack_data=b"")
        plain.signature = sign(shell + plain.encode_unsigned(),
                               sender.priv_signing)
        sans_nonce = shell + encrypt(plain.encode(), r.pub_encryption_key)
        target = pow_target(len(sans_nonce) + 8, ttl, 1, 1, clamp=False)
        nonce, _ = python_solve(sha512(sans_nonce), target)
        return nonce.to_bytes(8, "big") + sans_nonce

    payloads = [build(i) for i in range(9)]
    db = Database()
    store = MessageStore(db)
    proc = ObjectProcessor(
        keystore=ks, store=store, inventory=None,
        sender=SimpleNamespace(watched_acks=set(), needed_pubkeys={},
                               queue=asyncio.Queue()),
        min_ntpb=1, min_extra=1, write_behind=False)
    from pybitmessage_tpu.crypto import tpu as crypto_tpu
    tpu_armed = "crypto.tpu" in sites
    if tpu_armed:
        # force the rung into the walk (auto = idle on the CPU mesh);
        # the chaos fault fires before any device work is attempted
        crypto_tpu.configure("on")
        crypto_tpu.reset_tpu()
        proc.crypto.batch.tpu_batch_min = 1
    before = REGISTRY.sample("crypto_native_fallback_total") or 0
    before_tpu = REGISTRY.sample("crypto_tpu_fallback_total") or 0
    CHAOS.seed(SEED)
    for site in sites:
        CHAOS.arm(site, probability=1.0)
    try:
        proc.start()
        for p in payloads:
            await proc.queue.put(p)
        while proc.pending():
            await asyncio.sleep(0.01)
        await proc.stop()
    finally:
        CHAOS.disarm()
        if tpu_armed:
            crypto_tpu.configure("auto")
            crypto_tpu.reset_tpu()
    assert len(store.inbox()) == len(payloads), "objects lost"
    from pybitmessage_tpu.crypto.native import get_native
    if get_native().available:
        assert REGISTRY.sample("crypto_native_fallback_total") > before
    if tpu_armed:
        assert REGISTRY.sample("crypto_tpu_fallback_total") > before_tpu
    db.close()


# ---------------------------------------------------------------------------
# role.ipc faults: the edge->relay hand-off never loses accepted objects
# ---------------------------------------------------------------------------


async def test_no_object_loss_under_role_ipc_faults():
    """100% seeded failure injection on the edge->relay hand-off
    (ISSUE 14 satellite): every accepted object survives in the
    edge's outbox and is redelivered once the site stops firing —
    zero loss, visible in the resend counter; a relay KILLED and
    RESTARTED mid-flood loses nothing either (at-least-once delivery
    + hash-idempotent ingest)."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    from test_roles import build_msg_objects, make_edge, make_relay, \
        wait_for

    payloads = build_msg_objects(18)
    relay = make_relay()
    await relay.start()
    ipc_port = relay.role_runtime.listen_port
    edge = make_edge([ipc_port])
    await edge.start()
    try:
        await wait_for(lambda: edge.role_runtime.links[0].connected,
                       what="edge link")
        link = edge.role_runtime.links[0]
        link.breaker.cooldown = 0.2
        link.reconnect_max = 0.3
        before_resends = REGISTRY.sample("role_edge_resend_total") or 0
        before_chaos = REGISTRY.sample("chaos_injected_total",
                                       {"site": "role.ipc"}) or 0
        CHAOS.seed(SEED)
        # every hand-off frame send fails for the first 10 fires —
        # including relay-side ack/hello sends (both hops share the
        # site), so the link churns through several reconnects
        CHAOS.arm("role.ipc", probability=1.0, count=10)
        try:
            # feed through the pool exactly as the framing loop would
            from types import SimpleNamespace as _NS

            from pybitmessage_tpu.models.objects import ObjectHeader
            from pybitmessage_tpu.utils.hashes import inventory_hash
            for p in payloads[:9]:
                hdr = ObjectHeader.parse(p)
                h = inventory_hash(p)
                edge.inventory.add(h, hdr.object_type, hdr.stream, p,
                                   hdr.expires, b"")
                edge.pool.object_received(h, hdr, p, source=_NS())
            await wait_for(
                lambda: len(relay.inventory) == 9, timeout=30.0,
                what="redelivery after chaos")
        finally:
            CHAOS.disarm()
        assert REGISTRY.sample("chaos_injected_total",
                               {"site": "role.ipc"}) > before_chaos
        assert REGISTRY.sample("role_edge_resend_total") > \
            before_resends, "faults never forced a resend"
        assert relay.role_runtime.snapshot()["rejected"] == 0

        # relay killed mid-flood: objects pool in the edge outbox and
        # drain after a restart on the same port
        await relay.stop()
        for p in payloads[9:]:
            hdr = ObjectHeader.parse(p)
            h = inventory_hash(p)
            edge.inventory.add(h, hdr.object_type, hdr.stream, p,
                               hdr.expires, b"")
            edge.pool.object_received(h, hdr, p, source=_NS())
        await asyncio.sleep(0.5)
        assert link.depth() > 0, "outbox should hold the stranded objects"
        relay2 = make_relay()
        relay2.role_runtime.port = ipc_port
        await relay2.start()
        try:
            await wait_for(lambda: len(relay2.inventory) == 9,
                           timeout=30.0, what="drain into restarted relay")
            assert link.depth() == 0
        finally:
            await relay2.stop()
    finally:
        await edge.stop()


# ---------------------------------------------------------------------------
# role.handoff faults: a live shard split survives mid-handoff failures
# AND a receiver kill/restart with zero objects lost
# ---------------------------------------------------------------------------


async def test_shard_handoff_chaos_and_receiver_restart_zero_loss():
    """Seeded 100%-armed ``role.ipc`` + seeded ``role.handoff`` faults
    against a live shard shed (ISSUE 18 acceptance): attempt 1 dies on
    the receiver's faulted HELLO_ACK, attempt 2 drains every record
    and dies on the faulted END control frame — in both cases the
    sender keeps ownership (the shed only commits on the END ack).
    The receiver is then KILLED and RESTARTED empty on the same port;
    re-invoking resumes (BEGIN is idempotent, re-drained records
    dedupe) and the restarted receiver ends holding every object —
    zero loss across two faults and a crash."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    from test_roles import make_relay

    from pybitmessage_tpu.roles import ipc as _ipc  # noqa: F401

    relay_a = make_relay(streams=(1, 2))
    relay_b = make_relay(streams=(3,))
    await relay_a.start()
    await relay_b.start()
    b_port = relay_b.role_runtime.listen_port
    target = "127.0.0.1:%d" % b_port
    expires = int(time.time()) + 1200
    hashes = []
    for i in range(40):
        h = hashlib.sha512(b"handoff %d" % i).digest()[:32]
        # same expiry -> one slab bucket -> exactly one OBJECTS frame,
        # pinning the seeded draw sequence asserted below
        relay_a.inventory.add(h, 2, 2, b"handoff payload %d" % i,
                              expires, b"")
        hashes.append(h)

    # the draw sequence this test relies on (seed 11, p=0.3): the
    # sender's role.handoff site passes hello on attempt 1, passes
    # hello/BEGIN/OBJECTS on attempt 2, then FIRES on the END frame —
    # a fault landing only after the receiver holds every record
    import random as _random
    rng = _random.Random("11:role.handoff")
    draws = [rng.random() for _ in range(5)]
    assert all(d >= 0.3 for d in draws[:4]) and draws[4] < 0.3, \
        "seeded RNG sequence changed; re-pick the seed"

    relay_b2 = None
    b_stopped = False
    try:
        before_ho = REGISTRY.sample("chaos_injected_total",
                                    {"site": "role.handoff"}) or 0
        before_ipc = REGISTRY.sample("chaos_injected_total",
                                    {"site": "role.ipc"}) or 0
        CHAOS.seed(11)
        CHAOS.arm("role.handoff", probability=0.3)
        CHAOS.arm("role.ipc", probability=1.0, count=1)

        # attempt 1: the receiver's HELLO_ACK send faults (role.ipc at
        # 100%) -> the dial dies before any drain; ownership unchanged
        with pytest.raises((OSError, ConnectionError,
                            asyncio.IncompleteReadError)):
            await relay_a.role_runtime.shed_stream(2, target)
        assert tuple(relay_a.ctx.streams) == (1, 2)
        assert relay_a.role_runtime.epoch == 0
        assert relay_a.role_runtime.forwarding == {}

        # attempt 2: the full drain lands (receiver acquires the
        # stream and holds all 40 records) but END faults -> the
        # sender STILL does not shed
        with pytest.raises(ConnectionError):
            await relay_a.role_runtime.shed_stream(2, target)
        assert tuple(relay_a.ctx.streams) == (1, 2)
        assert relay_a.role_runtime.epoch == 0
        assert 2 in relay_b.ctx.streams
        assert relay_b.role_runtime.epoch == 1
        assert all(h in relay_b.inventory for h in hashes)
        assert REGISTRY.sample("chaos_injected_total",
                               {"site": "role.handoff"}) > before_ho
        assert REGISTRY.sample("chaos_injected_total",
                               {"site": "role.ipc"}) > before_ipc

        # receiver killed and restarted EMPTY on the same port: the
        # resumed shed re-begins and re-drains everything into it
        await relay_b.stop()
        b_stopped = True
        relay_b2 = make_relay(streams=(3,))
        relay_b2.role_runtime.port = b_port
        await relay_b2.start()
        CHAOS.disarm()
        res = await relay_a.role_runtime.shed_stream(2, target)
        assert res["objectsDrained"] == len(hashes)
        assert all(h in relay_b2.inventory for h in hashes), \
            "objects lost across the receiver restart"
        assert 2 in relay_b2.ctx.streams
        # the shed finally committed: A flipped into forwarding mode
        assert tuple(relay_a.ctx.streams) == (1,)
        assert relay_a.role_runtime.epoch == 1
        assert relay_a.role_runtime.forwarding == {2: target}
    finally:
        CHAOS.disarm()
        await relay_a.stop()
        if not b_stopped:
            await relay_b.stop()
        if relay_b2 is not None:
            await relay_b2.stop()
