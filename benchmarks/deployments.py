"""Builds the deployment a configuration file describes, out of the
program's own objects: ``Node`` and nothing below it.

``topology`` in the file is one of

``pair``
    a sender under test and a recipient in the same process, joined by
    loopback TCP.  The recipient stands for a remote machine, so with
    ``recipient_on_host`` it keeps all of its own work — the one PoW of
    its PoW checks, its crypto — off the chip the sender is measured
    on.  The recipient is a contact the sender already knows: its
    public keys are in the sender's ``pubkeys`` table from the start,
    as after any earlier exchange, so no run times (or waits for) a
    key request.
``single``
    one node with a chan identity it broadcasts from.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field


def known_contact(ident, config: dict) -> bytes:
    """A row of the ``pubkeys`` table for ``ident``: behaviour bitfield
    (bit 0: sends acks), both public keys without their 0x04 prefix, the
    difficulty it demands, an empty signature (checked when a key
    arrives, not when it is read back)."""
    from pybitmessage_tpu.utils.varint import encode_varint
    bitfield = (1 if config.get("acks") else 0).to_bytes(4, "big")
    return (bitfield + ident.pub_signing_key[1:]
            + ident.pub_encryption_key[1:]
            + encode_varint(config["ntpb"])
            + encode_varint(config["extra"]) + encode_varint(0))


async def wait_for(predicate, timeout: float, interval: float = 0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        await asyncio.sleep(interval)
    return bool(predicate())


@dataclass
class Deployment:
    config: dict
    sender: object                      # the Node under test
    from_address: str
    to_address: str | None = None
    recipient: object | None = None
    identity: object | None = None      # the sending identity
    nodes: list = field(default_factory=list)

    async def stop(self) -> None:
        for node in reversed(self.nodes):
            await node.stop()


def _demand(ident, config: dict) -> None:
    """An identity demands the configuration's difficulty of those who
    write to it (the network default unless the file says otherwise)."""
    ident.nonce_trials_per_byte = config["ntpb"]
    ident.extra_bytes = config["extra"]


async def build(config: dict, solver=None) -> Deployment:
    """Start the nodes of ``config``.  ``solver`` replaces the sender's
    solver ladder (the control and the broken-path test pass one)."""
    from pybitmessage_tpu.core import Node
    from pybitmessage_tpu.storage import Peer

    common = dict(port=0, test_mode=bool(config["test_mode"]),
                  allow_private_peers=True, dandelion_enabled=False)
    sender = Node(None, solver=solver, **common)
    nodes = [sender]
    if config["topology"] == "single":
        await sender.start()
        chan = sender.create_identity(
            "chan", deterministic=config["chan_passphrase"].encode(),
            chan=True)
        _demand(chan, config)
        return Deployment(config, sender, chan.address, identity=chan,
                          nodes=nodes)
    if config["topology"] != "pair":
        raise ValueError("unknown topology %r" % config["topology"])

    on_host = bool(config.get("recipient_on_host"))
    their_solver = None
    if on_host:
        from pybitmessage_tpu.pow import PowDispatcher
        their_solver = PowDispatcher(use_tpu=False)
    recipient = Node(None, solver=their_solver, **common)
    nodes.append(recipient)
    if on_host:
        recipient.pow_verifier.use_device = False
        recipient.processor.crypto.batch.use_tpu = False
    await sender.start()
    await recipient.start()
    alice = sender.create_identity("alice")
    bob = recipient.create_identity("bob")
    _demand(alice, config)
    _demand(bob, config)
    conn = await recipient.pool.connect_to(
        Peer("127.0.0.1", sender.pool.listen_port))
    if conn is None or not await wait_for(
            lambda: conn.fully_established, 30.0):
        await recipient.stop()
        await sender.stop()
        raise RuntimeError("the recipient could not connect to the sender")
    # the sender's own PoW-verify probe runs on a thread; until it has
    # landed, incoming batches would take the host path unnoticed
    await wait_for(lambda: sender.pow_verifier._device_ok is not None,
                   120.0)
    sender.store.store_pubkey(bob.address, bob.version,
                              known_contact(bob, config),
                              used_personally=True)
    return Deployment(config, sender, alice.address, bob.address,
                      recipient, identity=alice, nodes=nodes)
