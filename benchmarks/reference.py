"""The plain reference the benchmark's ``correct`` rests on.

Bitmessage proof of work, written out from the protocol description
with hashlib alone.  It imports nothing of the program: a later change
to ``pybitmessage_tpu.models.pow_math`` cannot move this yardstick.

An object ``nonce(8) || expires(8) || type(4) || ...`` is valid when

    u64_be(SHA512(SHA512(nonce || SHA512(rest)))[:8])
        <= 2**64 // (ntpb * (len + extra + (ttl * (len + extra)) // 2**16))

with ``len`` the whole object's length (nonce included) and ``ttl`` the
seconds the object still has to live when it is checked, at least 300.
"""

from __future__ import annotations

import hashlib

#: the least time to live a verifier assumes (protocol: stale objects
#: still verify at this floor)
MIN_TTL = 300


def trial_value(nonce: bytes, initial_hash: bytes) -> int:
    """One trial: the first eight bytes of the double hash, big-endian."""
    inner = hashlib.sha512(nonce + initial_hash).digest()
    return int.from_bytes(hashlib.sha512(inner).digest()[:8], "big")


def target(length: int, ttl: int, ntpb: int, extra: int) -> int:
    """The largest trial value an object of ``length`` bytes living
    ``ttl`` more seconds may show."""
    if ntpb <= 0 or extra < 0 or length <= 0:
        raise ValueError("difficulty and length must be positive")
    weight = length + extra
    return 2 ** 64 // (ntpb * (weight + (ttl * weight) // 2 ** 16))


def object_value_and_target(obj: bytes, ntpb: int, extra: int,
                            now: float) -> tuple[int, int]:
    """(trial value, target) of a whole object as a peer receiving it
    at ``now`` would compute them."""
    if len(obj) < 20:
        raise ValueError("object shorter than its header")
    expires = int.from_bytes(obj[8:16], "big")
    ttl = max(expires - int(now), MIN_TTL)
    value = trial_value(bytes(obj[:8]), hashlib.sha512(obj[8:]).digest())
    return value, target(len(obj), ttl, ntpb, extra)


def object_ok(obj: bytes, ntpb: int, extra: int, now: float) -> bool:
    value, limit = object_value_and_target(obj, ntpb, extra, now)
    return value <= limit


def nonce_of(obj: bytes) -> int:
    """The winning nonce an object carries."""
    return int.from_bytes(obj[:8], "big")
