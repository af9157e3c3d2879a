"""Decides ``correct`` for one measured window, after it has closed.

Three things are held against the configuration's guarantees, each a
count with the limit 0 (exact comparisons):

``invalid_nonces``   objects published in the window whose nonce the
                     plain reference (``reference.py``) refuses for the
                     object's length, remaining TTL and the
                     configuration's ntpb/extra; objects that should
                     exist and do not count too
``undelivered``      sends of the window that did not arrive intact:
                     in the recipient's inbox, or for a broadcast in
                     the inventory and decrypting with the chan's key
                     to the body that was sent
``off_tier``         solves that did not run on a backend the
                     configuration names, plus counted fall-throughs,
                     device-tier errors and breakers that are not closed
"""

from __future__ import annotations

import asyncio
import re
import time

from . import reference

TIER_ERROR_SITE = re.compile(r"^pow\.tier\.")
#: seconds the check waits for the window's last sends to arrive
DELIVERY_TIMEOUT = 120.0
#: an announcement leaves up to ten seconds after it was queued, so
#: nothing new for this long means nothing more is coming
STALL_SECONDS = 30.0
#: type(4) + version and stream varints + 32 random bytes
ACKDATA_BYTES = 38
OBJECT_BROADCAST = 3


def inventory_hashes(dep) -> list[set]:
    return [set(node.inventory.hashes()) for node in dep.nodes]


def new_objects(dep, before: list[set], sent) -> dict[bytes, bytes]:
    """hash -> payload of the objects the window's sends produced: what
    entered the sender's inventory since ``before`` (its own messages
    or broadcasts), and each send's ack object wherever it has been
    published.  Acks of earlier (warm-up) sends may still be coming
    back: an ack object is ``nonce, expires, ackdata``, and only those
    whose ackdata is a window send's handle belong to the window."""
    handles = {s.handle for s in sent}
    out = {}
    for node, old in zip(dep.nodes, before):
        for h in node.inventory.hashes():
            if h in old or h in out:
                continue
            payload = bytes(node.inventory[h].payload)
            is_ack = len(payload) == 16 + ACKDATA_BYTES
            if (is_ack and payload[16:] in handles) or (
                    not is_ack and node is dep.sender):
                out[h] = payload
    return out


async def _wait_all(count, total: int) -> None:
    """Wait until ``count()`` reaches ``total``, or has stood still
    for STALL_SECONDS (an object the recipient refused never arrives),
    or DELIVERY_TIMEOUT has passed."""
    start = last_change = time.monotonic()
    seen = -1
    while True:
        now = time.monotonic()
        n = count()
        if n != seen:
            seen, last_change = n, now
        if (n >= total or now - last_change > STALL_SECONDS
                or now - start > DELIVERY_TIMEOUT):
            return
        await asyncio.sleep(0.2)


async def wait_delivered(dep, sent) -> None:
    """Let the window's last sends arrive (messages: in the inbox and
    acknowledged, so that their ack objects have been published)."""
    if dep.recipient is None or not sent:
        return
    want = {s.subject for s in sent}
    await _wait_all(lambda: len(want & {
        m.subject for m in dep.recipient.store.inbox()}), len(want))
    if dep.config.get("acks"):
        await _wait_all(lambda: sum(
            dep.sender.message_status(s.handle) == "ackreceived"
            for s in sent), len(sent))


def _undelivered_messages(dep, sent) -> list[str]:
    inbox = {m.subject: m for m in dep.recipient.store.inbox()}
    bad = []
    for s in sent:
        m = inbox.get(s.subject)
        if (m is None or m.message != s.body
                or m.fromaddress != dep.from_address
                or m.toaddress != dep.to_address):
            bad.append(s.subject)
    return bad


def _undelivered_broadcasts(dep, sent, objects) -> list[str]:
    """Each broadcast must be an inventory object that decrypts with
    the chan's key to the subject and body that were sent."""
    from pybitmessage_tpu.crypto import decrypt
    from pybitmessage_tpu.models import msgcoding
    from pybitmessage_tpu.models.objects import ObjectHeader
    from pybitmessage_tpu.models.payloads import (
        BroadcastPlaintext, double_hash_of_address_data)
    ident = dep.identity
    dh = double_hash_of_address_data(ident.version, ident.stream,
                                     ident.ripe)
    found = {}
    for payload in objects.values():
        try:
            hdr = ObjectHeader.parse(payload)
            if hdr.object_type != OBJECT_BROADCAST:
                continue
            skip = 32 if hdr.version >= 5 else 0
            plain = BroadcastPlaintext.decode(
                decrypt(payload[hdr.header_length + skip:], dh[:32]))
            body = msgcoding.decode_message(plain.message,
                                            plain.encoding)
            found[body.subject] = body.body
        except Exception:       # an object that does not decrypt is
            continue            # simply not one of ours
    return [s.subject for s in sent if found.get(s.subject) != s.body]


def verify(dep, sent, objects: dict, counters, now: float | None = None,
           ) -> dict:
    """The three counts, the lists behind them and the trials the
    published nonces needed."""
    cfg = dep.config
    now = time.time() if now is None else now
    invalid = 0
    worst = 0.0
    needed = 0
    for payload in objects.values():
        value, limit = reference.object_value_and_target(
            payload, cfg["ntpb"], cfg["extra"], now)
        worst = max(worst, value / limit)
        if value > limit:
            invalid += 1
        needed += reference.nonce_of(payload) + 1
    published = [s for s in sent if s.t_done is not None]
    per_send = 2 if (cfg.get("acks") and dep.recipient is not None) else 1
    missing = max(0, per_send * len(published) - len(objects))

    if dep.recipient is not None:
        undelivered = _undelivered_messages(dep, sent)
    else:
        undelivered = _undelivered_broadcasts(dep, sent, objects)

    allowed = tuple(cfg["solve_backends"])
    attempts = {k[0]: int(v) for k, v in
                counters.delta("pow_attempts_total").items()}
    off_device = sum(n for backend, n in attempts.items()
                     if not backend.startswith(allowed))
    fallbacks = int(counters.total("pow_fallback_total"))
    tier_errors = int(sum(
        v for k, v in counters.delta("resilience_errors_total").items()
        if TIER_ERROR_SITE.match(k[0])))
    breakers = getattr(dep.sender.solver, "breakers", {})
    open_breakers = sorted(name for name, b in breakers.items()
                           if b.state != "closed")
    off_tier = (off_device + fallbacks + tier_errors + len(open_breakers)
                + (0 if attempts else 1))

    failed = {s.subject for s in sent if s.t_done is None}
    failed.update(undelivered)
    return {
        "compared": {
            "invalid_nonces": {"value": invalid + missing, "limit": 0},
            "undelivered": {"value": len(undelivered), "limit": 0},
            "off_tier": {"value": off_tier, "limit": 0},
        },
        "correct": (invalid + missing == 0 and not undelivered
                    and off_tier == 0 and not failed),
        "attempted": len(sent),
        "failed": len(failed),
        "objects": len(objects),
        "missing_objects": missing,
        "worst_value_over_target": worst,
        "needed_trials": needed,
        "attempts_by_backend": attempts,
        "off_device_solves": off_device,
        "fallbacks": fallbacks,
        "tier_errors": tier_errors,
        "open_breakers": open_breakers,
    }
