#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the node still starts on the chip.

One process, one TPU chip: two in-process nodes over localhost TCP send
and receive at NETWORK difficulty through the product's own objects
(SendWorker -> PowService -> PowDispatcher on send; connection ->
BatchVerifier -> ObjectProcessor -> BatchCryptoEngine on receive), then
the two ``auto`` device rungs are exercised directly, and every way the
device can be silently given up is turned into a failure: a solve that
did not run on ``tpu-pallas*``, a counted fall-through, an open breaker,
a PoW-verify batch that slipped to the host, an interpret-mode launch.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the pod path only, on four chips

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``;
everything else (versions, per-phase wall time, the compile table, smoke
solve rates) is printed on earlier lines.  The timings are SMOKE timings
— a cold run is mostly compiling — not benchmark results.  On anything
but a TPU the script fails at its first check.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import hashlib
import json
import os
import random
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the 64-message queue (production batch grid: 64 objects per launch)
QUEUE_MESSAGES = 64
#: body bytes of the queued messages, mixed sizes
MESSAGE_BYTES = (200, 16000)
#: concurrent pow_verifier.check() calls, plus spoiled-nonce objects
VERIFY_BURST = 64
SPOILED = 4
#: signature checks and ECDH pairs of the crypto drain
CRYPTO_DRAIN = 256
#: --chips 4: harder single objects, so winners spread over the devices
POD_SINGLE_OBJECTS = 6
POD_SINGLE_BYTES = 16000
#: seconds one phase may take (a cold Mosaic compile is minutes)
PHASE_TIMEOUT = 900.0
TTL = 4 * 24 * 3600
#: the network's difficulty (what Node(test_mode=False) demands)
NETWORK_NTPB = NETWORK_EXTRA = 1000
SEED = 22

#: most shapes each Mosaic program may compile or load: one per shape
#: it was meant to have (network difficulty never plans a packed launch)
EXPECTED_COMPILES = {"pallas_slab": 1, "batch_search": 1,
                     "packed_search": 0, "secp_verify": 1, "secp_ecdh": 1}

#: resilience ERRORS sites that mean "the device was given up quietly"
_HIDDEN_SITES = re.compile(
    r"^(pow\.tier\..*|pow\.verify_device|crypto\.tpu.*|pow\..*_probe)$")
#: index of ``interpret`` in each Pallas program's telemetry key
_INTERPRET_AT = {"pallas_slab": 3, "batch_search": 3, "packed_search": 4,
                 "pod_slab": 4, "pod_batch": 4}


#: counter families the verdict reads, as deltas over the run
_WATCHED = ("pow_attempts_total", "pow_fallback_total",
            "crypto_tpu_fallback_total", "crypto_native_fallback_total",
            "resilience_errors_total")


class Report:
    """Prints as it goes and remembers what failed."""

    def __init__(self):
        self.failures: list[str] = []
        self.t0 = time.monotonic()
        self.base = {fam: _family(fam) for fam in _WATCHED}

    def since_start(self, fam: str) -> dict[tuple, int]:
        """A watched counter family's non-zero growth over this run."""
        grown = {k: int(v - self.base[fam].get(k, 0))
                 for k, v in _family(fam).items()}
        return {k: v for k, v in grown.items() if v}

    def say(self, text: str) -> None:
        print("[%7.1fs] %s" % (time.monotonic() - self.t0, text),
              flush=True)

    def check(self, ok: bool, what: str) -> bool:
        self.say(("ok    " if ok else "FAIL  ") + what)
        if not ok:
            self.failures.append(what)
        return bool(ok)


def device_block(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def platform_ok(devices, chips: int) -> str | None:
    """The first check: None when this is the machine the smoke is
    for, else why not."""
    if devices[0].platform != "tpu":
        return "no accelerator: JAX reports platform %r" \
            % devices[0].platform
    if len(devices) != chips:
        return "%d device(s) visible, this run needs %d" \
            % (len(devices), chips)
    return None


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _family(name: str) -> dict[tuple, float]:
    from pybitmessage_tpu.observability import REGISTRY
    fam = REGISTRY.get(name)
    if fam is None:
        return {}
    return {values: float(child.value) for values, child in fam.children()}


def _grown_by_label(name: str, before: dict) -> dict[str, int]:
    """What each series of a one-label counter family grew by since
    ``before`` (``_family``'s answer then), by that label."""
    return {k[0]: int(v - before.get(k, 0))
            for k, v in sorted(_family(name).items())
            if v != before.get(k, 0)}


def _hist(name: str, *labels: str) -> tuple[float, int]:
    """(sum, count) of one histogram series."""
    from pybitmessage_tpu.observability import REGISTRY
    fam = REGISTRY.get(name)
    for values, child in (fam.children() if fam is not None else ()):
        if values == labels:
            _, total, count = child.snapshot()
            return float(total), int(count)
    return 0.0, 0


def hashlib_trial(nonce: bytes, initial_hash: bytes) -> int:
    """One PoW trial value, by hashlib alone."""
    return int.from_bytes(hashlib.sha512(hashlib.sha512(
        nonce + initial_hash).digest()).digest()[:8], "big")


def hashlib_pow_ok(obj: bytes, ntpb: int, extra: int) -> bool:
    """The object's embedded nonce against its target, by hashlib."""
    from pybitmessage_tpu.models.pow_math import pow_target
    ttl = max(int.from_bytes(obj[8:16], "big") - int(time.time()), 300)
    target = pow_target(len(obj), ttl, ntpb, extra, clamp=False)
    return hashlib_trial(obj[:8], hashlib.sha512(obj[8:]).digest()) \
        <= target


async def _wait_for(predicate, timeout: float, interval: float = 0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        await asyncio.sleep(interval)
    return bool(predicate())


# ---------------------------------------------------------------------------
# one chip: the node's send and receive paths
# ---------------------------------------------------------------------------


async def setup_pair():
    """Two nodes at network difficulty, B connected to A."""
    from pybitmessage_tpu.core import Node
    from pybitmessage_tpu.storage import Peer

    node_a = Node(None, port=0, test_mode=False, allow_private_peers=True,
                  dandelion_enabled=False)
    node_b = Node(None, port=0, test_mode=False, allow_private_peers=True,
                  dandelion_enabled=False)
    await node_a.start()
    await node_b.start()
    alice = node_a.create_identity("alice")
    bob = node_b.create_identity("bob")
    conn = await node_b.pool.connect_to(
        Peer("127.0.0.1", node_a.pool.listen_port))
    if conn is None or not await _wait_for(
            lambda: conn.fully_established, 30.0):
        raise RuntimeError("node B could not connect to node A")
    return node_a, node_b, alice, bob


async def phase_single(rep: Report, pair) -> None:
    """A sends B one message; B's inbox holds it and A sees the ack."""
    from pybitmessage_tpu.storage.messages import ACKRECEIVED
    node_a, node_b, alice, bob = pair
    # the ``auto`` probe of the PoW verifier runs on a thread: wait for
    # it, so no later batch slips to the host unnoticed
    rep.check(await _wait_for(
        lambda: node_b.pow_verifier._device_ok is not None, 120.0),
        "PoW-verify probe resolved (device=%s)"
        % node_b.pow_verifier._device_ok)
    body = "smoke body " + "x" * 1000
    ack = await node_a.send_message(bob.address, alice.address,
                                    "smoke single", body, ttl=TTL)
    got = await _wait_for(lambda: len(node_b.store.inbox()) >= 1,
                          PHASE_TIMEOUT)
    rep.check(got, "single message reached B's inbox")
    if got:
        m = node_b.store.inbox()[0]
        rep.check(m.subject == "smoke single" and m.message == body
                  and m.fromaddress == alice.address,
                  "single message arrived intact")
    rep.check(await _wait_for(
        lambda: node_a.message_status(ack) == ACKRECEIVED, 120.0),
        "A saw the ack of the single message")
    modes = {k[0]: int(v) for k, v in
             _family("pow_pipeline_mode_total").items() if v}
    rep.say("single-object path: %s (pipeline modes so far: %s)"
            % (node_a.solver.last_backend, modes or "none"))
    # trials are credited by the grid steps the search ran (81,920 a
    # step), over a wall that holds the launch and the read-back too:
    # the dispatcher's own figure, a lower bound on the device rate
    rep.say("smoke timing, single: last solve %.0f credited trials/s "
            "(%.2fs solve-only, step-granular credit)"
            % (node_a.solver.last_solve_rate,
               node_a.solver.last_solve_seconds))


async def phase_queue(rep: Report, pair) -> None:
    """A queue of messages submitted together on A; B receives all."""
    from pybitmessage_tpu.storage.messages import ACKRECEIVED
    node_a, node_b, alice, bob = pair
    rng = random.Random(SEED)
    before = len(node_b.store.inbox())
    trials0 = _family("pow_trials_total").get(("tpu-pallas-batch",), 0.0)
    secs0 = _hist("pow_solve_seconds", "tpu-pallas-batch")[0]
    acks = await asyncio.gather(*(
        node_a.send_message(
            bob.address, alice.address, "smoke queue %d" % i,
            "q" * rng.randint(*MESSAGE_BYTES), ttl=TTL)
        for i in range(QUEUE_MESSAGES)))
    rep.check(await _wait_for(
        lambda: len(node_b.store.inbox()) >= before + QUEUE_MESSAGES,
        PHASE_TIMEOUT),
        "B received all %d queued messages (inbox %d)"
        % (QUEUE_MESSAGES, len(node_b.store.inbox())))
    rep.check(await _wait_for(
        lambda: all(node_a.message_status(a) == ACKRECEIVED
                    for a in acks), 300.0),
        "A saw the acks of all %d queued messages" % QUEUE_MESSAGES)
    rep.say("PowService so far: %d objects in %d solve_batch launches"
            % _hist("pow_batch_size"))
    trials = _family("pow_trials_total").get(
        ("tpu-pallas-batch",), 0.0) - trials0
    secs = _hist("pow_solve_seconds", "tpu-pallas-batch")[0] - secs0
    rep.check(trials > 0, "the queue solved through the batch pipeline "
              "(tpu-pallas-batch trials %d)" % trials)
    if secs > 0:
        rep.say("smoke timing, batch: %.0f trials/s over %.1fs of "
                "solve_batch wall (first-launch compile included)"
                % (trials / secs, secs))
    # every nonce that went over the wire, re-checked by hashlib
    bad = total = 0
    for node in (node_a, node_b):
        for h in node.inventory.hashes():
            total += 1
            if not hashlib_pow_ok(node.inventory[h].payload,
                                  node.ctx.pow_ntpb, node.ctx.pow_extra):
                bad += 1
    rep.check(bad == 0 and total >= 2 * QUEUE_MESSAGES,
              "every nonce valid by hashlib (%d objects in the two "
              "inventories, %d bad)" % (total, bad))


async def phase_verify_burst(rep: Report, pair) -> None:
    """One burst of concurrent check() calls on B's PoW verifier."""
    node_b = pair[1]
    verifier = node_b.pow_verifier
    objs = [node_b.inventory[h].payload
            for h in node_b.inventory.hashes()]
    if not objs:
        rep.check(False, "B holds objects to re-verify")
        return
    good = [objs[i % len(objs)] for i in range(VERIFY_BURST)]
    spoiled = [((int.from_bytes(o[:8], "big") + 1) % (1 << 64))
               .to_bytes(8, "big") + o[8:] for o in good[:SPOILED]]
    batches0, checked0 = verifier.device_batches, verifier.device_checked
    answers = await asyncio.wait_for(asyncio.gather(
        *(verifier.check(o) for o in good + spoiled)), PHASE_TIMEOUT)
    rep.check(all(answers[:len(good)]),
              "%d received objects pass the PoW verifier" % len(good))
    rep.check(not any(answers[len(good):]),
              "%d spoiled nonces come back False" % len(spoiled))
    rep.check(verifier.device_batches > batches0
              and verifier.device_checked > checked0,
              "the burst ran on the device (device_batches %d, "
              "device_checked %d, host_checked %d)"
              % (verifier.device_batches, verifier.device_checked,
                 verifier.host_checked))


async def phase_crypto_drain(rep: Report, pair) -> None:
    """One drain of signature checks and ECDH pairs through B's crypto
    batch engine, every answer compared with crypto/fallback.py."""
    from pybitmessage_tpu.crypto import ecies, encrypt, fallback, sign
    from pybitmessage_tpu.crypto import tpu as crypto_tpu

    engine = pair[1].processor.crypto.batch
    rng = random.Random(SEED + 1)

    def priv() -> bytes:
        return rng.randrange(1, fallback.N).to_bytes(32, "big")

    verifies, expect_ok = [], []
    for i in range(CRYPTO_DRAIN):
        key = priv()
        data = b"chip smoke %d" % i
        sig = sign(data, key)
        if i % 7 == 6:                      # ~14% must be refused
            data += b"!"
        digest = hashlib.sha256(data).digest()
        r, s = fallback.der_decode_sig(sig)
        pub = fallback.priv_to_pub(key)
        expect_ok.append(fallback.ecdsa_verify_scalars(
            fallback.digest_to_scalar(digest), r, s,
            fallback.decode_point(pub)))
        verifies.append((data, sig, pub))

    # 4 candidate keys per object: CRYPTO_DRAIN ECDH pairs in all; one
    # object in five is for nobody here
    keys = [priv() for _ in range(4)]
    candidates = [(k, i) for i, k in enumerate(keys)]
    stranger = fallback.priv_to_pub(priv())
    decrypts, expect_match = [], []
    for i in range(CRYPTO_DRAIN // 4):
        owner = None if i % 5 == 4 else i % 4
        plain = b"drain object %d" % i
        payload = encrypt(plain, stranger if owner is None
                          else fallback.priv_to_pub(keys[owner]))
        parsed = ecies.parse_payload(payload)
        expect = []
        for k, handle in candidates:        # the plain reference sweep
            aes_key, mac_key = ecies.kdf(
                fallback.ecdh_x(k, parsed.ephem_pub))
            if ecies.mac_ok(mac_key, parsed.macdata, parsed.tag):
                expect.append((ecies.finish_decrypt(aes_key, parsed),
                               handle))
                break
        expect_match.append(expect)
        decrypts.append(payload)

    items0 = engine.tpu_items
    results = await asyncio.wait_for(asyncio.gather(
        *(engine.verify(*v) for v in verifies),
        *(engine.try_decrypt(p, candidates) for p in decrypts)),
        PHASE_TIMEOUT)
    got_ok = [bool(x) for x in results[:len(verifies)]]
    got_match = [list(x) for x in results[len(verifies):]]
    rep.check(got_ok == expect_ok,
              "%d signature checks agree with crypto/fallback.py "
              "(%d accepted)" % (len(got_ok), sum(got_ok)))
    rep.check(got_match == expect_match,
              "%d ECDH pairs agree with crypto/fallback.py (%d of %d "
              "objects matched)" % (4 * len(decrypts),
                                    sum(1 for m in got_match if m),
                                    len(decrypts)))
    tpu = crypto_tpu.get_tpu()
    rung = engine.last_path
    rep.say("crypto rung: %s (cryptotpu=%s, probe: %s)"
            % (rung, crypto_tpu.mode(), tpu.snapshot()))
    if tpu.available:
        rep.check(rung == "tpu" and engine.tpu_items > items0,
                  "the drain ran on the tpu rung the probe offered")


def check_no_hidden_fallback(rep: Report, pair) -> None:
    """What would hide a failure is a failure."""
    from pybitmessage_tpu.observability.devicetelemetry import (
        DEVICE_TELEMETRY, device_status)
    node_a, node_b = pair[0], pair[1]
    attempts = {k[0]: v for k, v in
                rep.since_start("pow_attempts_total").items()}
    rep.check(bool(attempts) and all(
        b.startswith("tpu-pallas") for b in attempts),
        "every solve ran on tpu-pallas* (attempts by backend: %s)"
        % attempts)
    for node, name in ((node_a, "A"), (node_b, "B")):
        rep.check(node.solver.last_backend.startswith("tpu-pallas"),
                  "node %s last_backend %r" % (name,
                                               node.solver.last_backend))
    for fam in ("pow_fallback_total", "crypto_tpu_fallback_total",
                "crypto_native_fallback_total"):
        fired = rep.since_start(fam)
        rep.check(not fired, "%s is zero %s" % (fam, fired or ""))
    errors = {k[0]: v for k, v in
              rep.since_start("resilience_errors_total").items()
              if _HIDDEN_SITES.match(k[0])}
    rep.check(not errors, "no device-tier error was swallowed %s"
              % (errors or ""))
    breakers = {}
    for node, name in ((node_a, "A:"), (node_b, "B:")):
        engine = node.processor.crypto.batch
        for b in (*node.solver.breakers.values(), engine.tpu_breaker,
                  engine.breaker):
            breakers[name + b.name] = b.state
    rep.check(all(s == "closed" for s in breakers.values()),
              "every breaker closed %s" % breakers)
    v = node_b.pow_verifier
    rep.check(v.device_batches > 0 and v.device_checked > 0,
              "B's PoW verifier ran device batches (%d batches, %d "
              "objects; %d on the host)"
              % (v.device_batches, v.device_checked, v.host_checked))
    programs = device_status()["programs"]
    if node_b.processor.crypto.batch.last_path == "tpu":
        rep.check(all(programs.get(p, {}).get("launches", 0) > 0
                      for p in ("secp_verify", "secp_ecdh")),
                  "secp_verify/secp_ecdh launched on the device")
    interp = [(p, k) for p, k in DEVICE_TELEMETRY.launched_keys()
              if p in _INTERPRET_AT and k[_INTERPRET_AT[p]]]
    rep.check(not interp, "no program ran with interpret=True %s"
              % (interp or ""))
    for prog, most in EXPECTED_COMPILES.items():
        row = programs.get(prog, {})
        seen = row.get("compiles", 0) + row.get("cacheHits", 0)
        rep.check(seen <= most,
                  "%s compiled once per shape it was meant to have "
                  "(%d compiled or loaded from the cache, at most %d)"
                  % (prog, seen, most))


def print_compile_table(rep: Report, cache_events: dict) -> None:
    from pybitmessage_tpu.observability.devicetelemetry import (
        DEVICE_TELEMETRY, device_status)
    rep.say("program            launches  compiles  cache-hits  "
            "compile-s  busy-s")
    for name, row in device_status()["programs"].items():
        if row["launches"]:
            rep.say("%-18s %8d  %8d  %10d  %9.1f  %6.1f"
                    % (name, row["launches"], row["compiles"],
                       row["cacheHits"], row["compileSeconds"],
                       row["busySeconds"]))
    rep.say("(compile seconds = trace + lowering + backend compile, or "
            "the load from the persistent cache; from JAX's events)")
    for prog, key in DEVICE_TELEMETRY.launched_keys():
        rep.say("  shape key: %s %r" % (prog, key))
    rep.say("persistent compile cache: %s"
            % (dict(sorted(cache_events.items())) or "no events"))


def native_libraries(prebuilt: dict) -> str:
    from pybitmessage_tpu.crypto.native import get_native
    from pybitmessage_tpu.pow.native import NativeSolver
    loaded = {"native/pow/libbitmsgpow.so": NativeSolver().available,
              "native/secp256k1/libbmsecp256k1.so": get_native().available}
    return ", ".join(
        "%s: %s, %s" % (path, "was in the tree" if prebuilt[path]
                        else "built here from source",
                        "loaded" if ok else "NOT loaded")
        for path, ok in loaded.items())


async def run_one_chip(rep: Report, pair_factory=None) -> None:
    pair = None
    try:
        t0 = time.monotonic()
        pair = await (pair_factory or setup_pair)()
        rep.say("two nodes up and connected (%.1fs)"
                % (time.monotonic() - t0))
        for phase in (phase_single, phase_queue, phase_verify_burst,
                      phase_crypto_drain):
            t0 = time.monotonic()
            try:
                await phase(rep, pair)
            except Exception as exc:
                rep.check(False, "%s raised %r" % (phase.__name__, exc))
            rep.say("%s: %.1fs wall" % (phase.__name__,
                                        time.monotonic() - t0))
        check_no_hidden_fallback(rep, pair)
    finally:
        if pair is not None:
            await pair[1].stop()
            await pair[0].stop()


# ---------------------------------------------------------------------------
# four chips: the pod path and what it is compared with
# ---------------------------------------------------------------------------


def _default_item(tag: bytes, length: int):
    from pybitmessage_tpu.models.pow_math import pow_target
    return (hashlib.sha512(tag).digest(),
            pow_target(length, TTL, NETWORK_NTPB, NETWORK_EXTRA,
                       clamp=False))


def _valid(item, result) -> bool:
    ih, target = item
    return hashlib_trial(result[0].to_bytes(8, "big"), ih) <= target


def run_pod(rep: Report) -> None:
    """Through PowDispatcher on every chip: default-difficulty objects
    one at a time, each object's nonce space shared out over the
    pipeline's lanes (a chip each, first hit wins), and a 64-object
    queue, its launch groups placed over them; and the same objects on
    one device to compare with.  No ``shard_map`` program is lowered."""
    import jax

    from pybitmessage_tpu.pow import pipeline
    from pybitmessage_tpu.pow.dispatcher import PowDispatcher
    from pybitmessage_tpu.pow.pipeline import solve_batch_pipelined

    ndev = len(jax.devices())
    rng = random.Random(SEED)
    singles = [_default_item(b"pod single", 1016)] + [
        _default_item(b"pod hard %d" % i, POD_SINGLE_BYTES)
        for i in range(POD_SINGLE_OBJECTS)]
    batch = [_default_item(b"pod batch %d" % i,
                           rng.randint(*MESSAGE_BYTES))
             for i in range(QUEUE_MESSAGES)]
    d = PowDispatcher()

    def timed(label, fn):
        t0 = time.monotonic()
        out = fn()
        dt = time.monotonic() - t0
        rep.say("%s: %.1fs wall" % (label, dt))
        return out, dt

    def alone(item):
        # one chip's lone object, as ``single_send`` solves it
        return solve_batch_pipelined([item])[0]

    # a lone object is the pipeline's too: the rung of one chip, given
    # every chip; lane k searches from the object's start plus k shares
    # of the nonce space, and the first harvest with a hit wins
    wins, share = "pow_pipeline_lone_wins_total", (1 << 64) // ndev
    lanes, lag = ("pow_pipeline_lone_lanes_total",
                  "pow_pipeline_lone_cancel_lag_steps")
    warm = _default_item(b"pod warm", 1016)
    timed("first solve, single, %d devices" % ndev, lambda: d(*warm))
    rep.check(d.last_backend == "tpu-pallas",
              "single solve backend %r" % d.last_backend)
    timed("first solve, single, 1 device", lambda: alone(warm))
    before, left_before, lag_before = (_family(wins), _family(lanes),
                                       _hist(lag))
    res_n, dt_n = timed("single x%d, %d devices" % (len(singles), ndev),
                        lambda: [d(*it) for it in singles])
    left, won = _grown_by_label(lanes, left_before), _grown_by_label(
        wins, before)
    res_1, dt_1 = timed("single x%d, 1 device" % len(singles),
                        lambda: [alone(it) for it in singles])
    rep.check(all(_valid(it, r) for it, r in zip(singles, res_n))
              and all(_valid(it, r) for it, r in zip(singles, res_1)),
              "single nonces valid by hashlib on %d devices and on one"
              % ndev)
    rep.check(sum(won.values()) == len(singles)
              and won == dict(collections.Counter(
                  "%d" % (r[0] // share) for r in res_n)),
              "every single solve was laid out over the %d lanes and "
              "credited the lane whose share its nonce lies in: %s"
              % (ndev, won))
    rep.check(len(won) > 1,
              "winners came from more than one lane: %s" % sorted(won))
    if pipeline._one_program(pipeline.default_impl(), jax.devices()):
        # the chips of an accelerator: each of those solves was ONE
        # program over them (``ops/sha512_ici.py``), whose kernels stop
        # at the winner's flag, and every lane said how it left
        steps, late = (a - b for a, b in zip(_hist(lag), lag_before))
        rep.check(left.get("won") == len(singles)
                  and sum(left.values()) % ndev == 0,
                  "every lane of every launch of the one program "
                  "reported how it left: %s" % left)
        rep.check(left.get("cancelled", 0) > 0
                  and late == left.get("cancelled"),
                  "losers left on the winner's flag over ICI, %.2f "
                  "grid steps past its hit on average"
                  % (steps / max(late, 1)))
    rep.say("smoke timing, single x%d: %d lanes %.2fs wall, 1 device "
            "%.2fs wall" % (len(singles), ndev, dt_n, dt_1))

    # a queue is the pipeline's on any number of chips: its launch
    # groups dealt over them, an object's own range on one chip and a
    # copy of a straggler's on a chip that has run out
    by_device = "pow_pipeline_device_launches_total"
    timed("first batch, %d devices" % ndev,
          lambda: d.solve_batch(batch))
    rep.check(d.last_backend == "tpu-pallas-batch",
              "batch solve backend %r" % d.last_backend)
    timed("first batch, 1 device", lambda: solve_batch_pipelined(batch))
    before = _family(by_device)
    bres_n, bdt_n = timed("batch of %d, %d devices" % (len(batch), ndev),
                          lambda: d.solve_batch(batch))
    took = {k[0]: int(v - before.get(k, 0))
            for k, v in sorted(_family(by_device).items())}
    bres_1, bdt_1 = timed("batch of %d, 1 device" % len(batch),
                          lambda: solve_batch_pipelined(batch))
    rep.check(all(_valid(it, r) for it, r in zip(batch, bres_n))
              and all(_valid(it, r) for it, r in zip(batch, bres_1)),
              "batch nonces valid by hashlib on %d devices and on one"
              % ndev)
    share = (1 << 64) // ndev
    rep.check(all(placed[1] >= alone[1] if placed[0] == alone[0]
                  else placed[0] >= share
                  for placed, alone in zip(bres_n, bres_1)),
              "the placed batch found the nonces of the one-device batch, "
              "or a copy's from its own share of the nonce space")
    rep.check(len(took) == ndev and all(took.values()),
              "every device took launches of the batch: %s" % took)
    for label, res, dt in (("%d devices" % ndev, bres_n, bdt_n),
                           ("1 device", bres_1, bdt_1)):
        rep.say("smoke timing, batch, %s: %.0f trials/s (%.1fs)"
                % (label, sum(r[1] for r in res) / dt, dt))
    fell = rep.since_start("pow_fallback_total")
    rep.check(not fell, "no fall to the XLA sharded tier %s"
              % (fell or ""))
    rep.check(all(b.state == "closed" for b in d.breakers.values()),
              "every breaker closed")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the pod path only (default: 1, the "
                         "node's send and receive paths)")
    args = ap.parse_args(argv)
    rep = Report()
    prebuilt = {p: os.path.exists(os.path.join(ROOT, p)) for p in (
        "native/pow/libbitmsgpow.so",
        "native/secp256k1/libbmsecp256k1.so")}
    try:
        from pybitmessage_tpu.core.jaxsetup import setup_jax
        cache_dir = setup_jax()
        import jax
        devices = jax.devices()
    except Exception as exc:
        print(json.dumps({"ok": False, "error": repr(exc)}))
        return 2
    device = device_block(devices)
    why = platform_ok(devices, args.chips)
    if why is not None:
        rep.say("chip_smoke cannot run here: %s" % why)
        print(json.dumps({"ok": False, "device": device}))
        return 1

    from pybitmessage_tpu.observability.devicetelemetry import \
        env_fingerprint
    cache_events: dict[str, int] = {}

    def on_event(event, **_kw):
        if "compilation_cache" in event:
            cache_events[event] = cache_events.get(event, 0) + 1
    jax.monitoring.register_event_listener(on_event)
    rep.say("env: %s" % json.dumps(env_fingerprint()))
    rep.say("compile cache: %s (%s)" % (
        cache_dir, "JAX_COMPILATION_CACHE_DIR" if os.environ.get(
            "JAX_COMPILATION_CACHE_DIR") else "fixed path, set in code"))
    try:
        if args.chips == 1:
            asyncio.run(run_one_chip(rep))
        else:
            run_pod(rep)
    except Exception as exc:
        import traceback
        traceback.print_exc()
        rep.check(False, "run raised %r" % exc)
    print_compile_table(rep, cache_events)
    rep.say("native libraries: %s" % native_libraries(prebuilt))
    rep.say("total wall %.1fs; %d failure(s)%s"
            % (time.monotonic() - rep.t0, len(rep.failures),
               "".join("\n    - " + f for f in rep.failures)))
    ok = not rep.failures
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
