"""Single-device PoW nonce search and batched verification (JAX).

Search strategy (reference semantics: src/proofofwork.py:288-325, nonce
strided over workers; src/openclpow.py:96-107, host loop over batches):
a jitted ``lax.while_loop`` evaluates ``lanes`` double-SHA512 trials per
iteration and exits as soon as any lane beats the target.  The host
wrapper re-invokes the jitted search in slabs so a Python-level shutdown
flag can interrupt arbitrarily long searches (reference aborts via
``state.shutdown`` checks inside every solver, proofofwork.py:104-191).

Verification of flooded incoming objects is a pure batch computation —
one fused launch checks a whole batch of (nonce, initialHash, target)
triples (reference verifies one at a time on the host,
src/protocol.py:258-286; batching is the TPU-native win).
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from ..observability.devicetelemetry import (POW_FLOPS_PER_HASH,
                                             record_launch,
                                             register_program)
from ..utils.hashes import double_sha512
from .sha512_jax import (DEFAULT_VARIANT, double_sha512_trial,
    initial_hash_words, trial_values)
from .u64 import le64, u64_from_int, u64_to_int, U32

#: lanes per while_loop iteration; multiple of 8*128 VPU tiles.
#: 2^19 x 64 chunks (33.5M trials/slab) is the measured single-chip
#: sweet spot: 25.5 MH/s honest vs 7 MH/s at 2^17 x 8, where per-call
#: dispatch latency dominates (see BASELINE.md "Measured").
DEFAULT_LANES = 1 << 19
#: while_loop iterations per jitted call (between shutdown checks);
#: one slab is ~1.3 s on a v5e chip — the shutdown-poll granularity.
DEFAULT_CHUNKS_PER_CALL = 64


class PowInterrupted(Exception):
    """Nonce search aborted by the shutdown callback.

    A dedicated type (not StopIteration, which the iterator protocol
    swallows) carrying no result; the pending object stays queued and
    is retried on restart — checkpoint/resume semantics of the
    reference's sent-state machine (class_singleWorker.py:720-724).
    """


def _run_host_driver(search_once, initial_hash: bytes, target: int, *,
                     start_nonce: int, trials_per_call_step: int,
                     should_stop: Callable[[], bool] | None,
                     on_slab: Callable[[int, float], None] | None = None,
                     progress: Callable[[int], None] | None = None,
                     program: str = "pow_slab", program_key=None,
                     devices: int = 1):
    """Shared host loop over a jitted search slab.

    ``search_once(b_hi, b_lo) -> (found, n_hi, n_lo, chunks)``;
    ``trials_per_call_step`` = trials represented by one chunk across
    all participating devices.  ``on_slab`` (if given) receives the
    chunks each slab really ran (it leaves at its first hit) and its
    measured wall seconds — the autotuner's latency feedback.
    ``progress`` (if given) receives the next base after every
    miss-free slab — the resumable-PoW checkpoint hook.  Re-verifies
    the winning nonce with hashlib before returning, guarding against
    accelerator miscompute (the reference re-checks OpenCL results,
    proofofwork.py:302-313).

    Every slab is attributed to the device-telemetry ``program``
    (dispatch vs the ``int(chunks)`` completion pull, compile-vs-
    cache on ``program_key``, hashes from the chunk count).
    """
    import time as _time

    base = start_nonce
    trials = 0
    while True:
        if should_stop is not None and should_stop():
            raise PowInterrupted("PoW interrupted by shutdown")
        b_hi, b_lo = u64_from_int(base)
        t0 = _time.monotonic()
        found, n_hi, n_lo, chunks = search_once(b_hi, b_lo)
        t1 = _time.monotonic()
        chunks = int(chunks)          # host pull — forces completion
        t2 = _time.monotonic()
        record_launch(program, key=program_key,
                      dispatch_seconds=t1 - t0, wait_seconds=t2 - t1,
                      span=(t0, t2),
                      items=chunks * trials_per_call_step,
                      bytes_out=16, devices=devices)
        if on_slab is not None:
            on_slab(chunks, t2 - t0)
        trials += chunks * trials_per_call_step
        if bool(found):
            nonce = u64_to_int(n_hi, n_lo)
            check = double_sha512(nonce.to_bytes(8, "big") + initial_hash)
            if int.from_bytes(check[:8], "big") > target:  # pragma: no cover
                raise ArithmeticError(
                    "accelerator returned an invalid PoW nonce")
            return nonce, trials
        base += chunks * trials_per_call_step
        if progress is not None:
            progress(base)


@functools.partial(jax.jit,
                   static_argnames=("lanes", "max_chunks", "variant"))
def pow_search_jit(ih_hi, ih_lo, target_hi, target_lo, start_hi, start_lo,
                   lanes: int = DEFAULT_LANES,
                   max_chunks: int = DEFAULT_CHUNKS_PER_CALL,
                   variant: str = DEFAULT_VARIANT):
    """Search nonces [start, start + lanes*max_chunks) for value <= target.

    Returns (found: bool, nonce_hi, nonce_lo, chunks_done: int32).
    Exits the loop at the first chunk containing a hit.  ``variant``
    selects the SHA-512 kernel (see ``sha512_jax.DEFAULT_VARIANT`` for
    why "windowed" is the production default); dispatching the fastest
    *usable* backend matches the reference wiring
    (src/openclpow.py:96-107 + proofofwork.py:288-325).
    """
    lanes_pair = u64_from_int(lanes)

    def cond(carry):
        found, chunk = carry[0], carry[1]
        return jnp.logical_and(jnp.logical_not(found), chunk < max_chunks)

    def body(carry):
        found, chunk, base_hi, base_lo, nonce_hi, nonce_lo = carry
        (v_hi, v_lo), (n_hi, n_lo) = trial_values(
            base_hi, base_lo, ih_hi, ih_lo, lanes, variant)
        ok = le64((v_hi, v_lo), (target_hi, target_lo))
        hit = jnp.any(ok)
        idx = jnp.argmax(ok)  # first winning lane
        nonce_hi = jnp.where(hit, n_hi[idx], nonce_hi)
        nonce_lo = jnp.where(hit, n_lo[idx], nonce_lo)
        lo = base_lo + lanes_pair[1]
        hi = base_hi + lanes_pair[0] + (lo < base_lo).astype(U32)
        return (jnp.logical_or(found, hit), chunk + 1, hi, lo,
                nonce_hi, nonce_lo)

    carry = (jnp.bool_(False), jnp.int32(0), start_hi, start_lo,
             jnp.uint32(0), jnp.uint32(0))
    found, chunks, _, _, nonce_hi, nonce_lo = jax.lax.while_loop(
        cond, body, carry)
    return found, nonce_hi, nonce_lo, chunks


def solve(initial_hash: bytes, target: int, *,
          start_nonce: int = 0,
          lanes: int = DEFAULT_LANES,
          chunks_per_call: int = DEFAULT_CHUNKS_PER_CALL,
          variant: str = DEFAULT_VARIANT,
          should_stop: Callable[[], bool] | None = None,
          tuner=None, tuner_kind: str = "xla",
          progress: Callable[[int], None] | None = None):
    """Find a nonce whose trial value is <= target.

    Host driver over :func:`pow_search_jit`; between jitted slabs the
    optional ``should_stop`` callback is polled (shutdown semantics of
    reference proofofwork.py:104-191).  ``tuner`` (a
    ``pow.pipeline.SlabAutotuner``-shaped object) replaces the
    hardcoded chunk constant with a measured-latency-derived slab
    size; the winning nonce is slab-shape invariant (consecutive
    ranges — regression-tested), so autotuning never changes results.
    Returns (nonce, trials_done) or raises :class:`PowInterrupted`
    when interrupted.
    """
    ih_hi, ih_lo = initial_hash_words(initial_hash)
    t_hi, t_lo = u64_from_int(target)
    chunks = chunks_per_call
    if tuner is not None:
        # one octave around the default: keeps the compiled-shape
        # ladder short and stops compile-contaminated observations
        # from swinging the slab size between extremes
        chunks = tuner.suggest(tuner_kind, chunks_per_call,
                               lo=max(1, chunks_per_call // 2),
                               hi=chunks_per_call * 2)

    def search_once(b_hi, b_lo):
        return pow_search_jit(ih_hi, ih_lo, t_hi, t_lo, b_hi, b_lo,
                              lanes, chunks, variant)

    on_slab = None
    if tuner is not None:
        on_slab = functools.partial(tuner.record, tuner_kind)

    return _run_host_driver(
        search_once, initial_hash, target, start_nonce=start_nonce,
        trials_per_call_step=lanes, should_stop=should_stop,
        on_slab=on_slab, progress=progress, program="pow_slab",
        program_key=(lanes, chunks, variant))


@jax.jit
def pow_verify_batch(nonce_hi, nonce_lo, ih_hi, ih_lo, target_hi, target_lo):
    """Vector PoW check: (B,) nonces, (8, B) initial-hash words, (B,) targets.

    Returns a (B,) bool array — True where the object's PoW is valid.
    """
    v = double_sha512_trial(nonce_hi, nonce_lo, ih_hi, ih_lo)
    return le64(v, (target_hi, target_lo))


def verify(items: Sequence[tuple[int, bytes, int]]) -> list[bool]:
    """Batch-verify (nonce, initial_hash, target) triples on device.

    Pads to the next power of two to bound recompilations.
    """
    if not items:
        return []
    n = len(items)
    size = 1
    while size < n:
        size *= 2
    nh_l, nl_l, th_l, tl_l = [], [], [], []
    ih_hi_l, ih_lo_l = [], []
    for nonce, ih, target in items:
        nonce &= (1 << 64) - 1
        nh_l.append(nonce >> 32)
        nl_l.append(nonce & 0xFFFFFFFF)
        th_l.append((target >> 32) & 0xFFFFFFFF)
        tl_l.append(target & 0xFFFFFFFF)
        words = [int.from_bytes(ih[i:i + 8], "big") for i in range(0, 64, 8)]
        ih_hi_l.append([w >> 32 for w in words])
        ih_lo_l.append([w & 0xFFFFFFFF for w in words])
    pad = size - n
    nh = jnp.array(nh_l + [0] * pad, dtype=U32)
    nl = jnp.array(nl_l + [0] * pad, dtype=U32)
    th = jnp.array(th_l + [0] * pad, dtype=U32)
    tl = jnp.array(tl_l + [0] * pad, dtype=U32)
    ih_hi = jnp.array(ih_hi_l + [[0] * 8] * pad, dtype=U32).T
    ih_lo = jnp.array(ih_lo_l + [[0] * 8] * pad, dtype=U32).T
    import time as _time

    import numpy as np
    bytes_in = sum(int(a.nbytes) for a in
                   (nh, nl, th, tl, ih_hi, ih_lo))
    t0 = _time.monotonic()
    ok = pow_verify_batch(nh, nl, ih_hi, ih_lo, th, tl)
    t1 = _time.monotonic()
    ok = np.asarray(ok)               # the blocking completion pull
    t2 = _time.monotonic()
    record_launch("pow_verify", key=size, dispatch_seconds=t1 - t0,
                  wait_seconds=t2 - t1, span=(t0, t2), items=size,
                  bytes_in=bytes_in, bytes_out=int(ok.nbytes))
    return [bool(b) for b in ok[:n]]


register_program("pow_slab", flops_per_item=POW_FLOPS_PER_HASH,
                 module="ops/pow_search.py", jit_names=("pow_search_jit",))
register_program("pow_verify", flops_per_item=POW_FLOPS_PER_HASH,
                 module="ops/pow_search.py",
                 jit_names=("pow_verify_batch",))
