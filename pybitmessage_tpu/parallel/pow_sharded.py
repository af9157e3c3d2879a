"""Pod-wide PoW nonce search: shard_map over a device mesh.

Partitioning: device *d* of *D* searches nonces
``start + d*lanes + chunk*D*lanes + lane`` — contiguous per-chunk blocks
interleaved across the mesh, the multi-chip generalization of the
reference's per-thread striding (src/bitmsghash/bitmsghash.cpp:40-74).

Early exit: each while_loop iteration all-reduces a "found" flag over
the mesh axis (``psum`` rides ICI), so the whole pod stops within one
chunk of the first hit.  The winning (device, nonce) is resolved with a
tiny all_gather; every device returns the same replicated result.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..observability.devicetelemetry import (POW_FLOPS_PER_HASH,
                                             record_launch,
                                             register_program)
from ..ops.pow_search import PowInterrupted, _run_host_driver
from ..ops.sha512_jax import (DEFAULT_VARIANT, initial_hash_words,
    trial_values)
from ..ops.u64 import add64, le64, u64_from_int, U32

_MASK64 = (1 << 64) - 1

register_program("sharded_search", flops_per_item=POW_FLOPS_PER_HASH,
                 module="parallel/pow_sharded.py")
register_program("sharded_batch", flops_per_item=POW_FLOPS_PER_HASH,
                 module="parallel/pow_sharded.py")


def _device_search(ih_hi, ih_lo, t_hi, t_lo, s_hi, s_lo,
                   *, lanes: int, max_chunks: int, axis: str,
                   variant: str = DEFAULT_VARIANT):
    """Per-device body run under shard_map. All inputs replicated."""
    dev = jax.lax.axis_index(axis)
    ndev = jax.lax.psum(jnp.int32(1), axis)

    # local start = start + dev * lanes
    off = (jnp.uint32(0), dev.astype(U32) * jnp.uint32(lanes))
    base = add64((s_hi, s_lo), off)
    # per-chunk stride = ndev * lanes (lanes is static, ndev tiny)
    stride_lo = ndev.astype(U32) * jnp.uint32(lanes)
    stride = (jnp.uint32(0), stride_lo)

    def cond(carry):
        return jnp.logical_and(jnp.logical_not(carry[0]), carry[1] < max_chunks)

    def body(carry):
        _, chunk, b_hi, b_lo, n_hi, n_lo, local = carry
        (v_hi, v_lo), (c_hi, c_lo) = trial_values(
            b_hi, b_lo, ih_hi, ih_lo, lanes, variant)
        ok = le64((v_hi, v_lo), (t_hi, t_lo))
        hit = jnp.any(ok)
        idx = jnp.argmax(ok)
        n_hi = jnp.where(hit & ~local, c_hi[idx], n_hi)
        n_lo = jnp.where(hit & ~local, c_lo[idx], n_lo)
        local = jnp.logical_or(local, hit)
        # pod-wide OR over ICI — the early-exit collective
        global_found = jax.lax.psum(local.astype(jnp.int32), axis) > 0
        b_hi, b_lo = add64((b_hi, b_lo), stride)
        return (global_found, chunk + 1, b_hi, b_lo, n_hi, n_lo, local)

    carry = (jnp.bool_(False), jnp.int32(0), base[0], base[1],
             jnp.uint32(0), jnp.uint32(0), jnp.bool_(False))
    _, chunks, _, _, n_hi, n_lo, local = jax.lax.while_loop(cond, body, carry)

    # Resolve the pod-wide winner: gather every device's (found, nonce).
    founds = jax.lax.all_gather(local, axis)          # (D,)
    nonces_hi = jax.lax.all_gather(n_hi, axis)
    nonces_lo = jax.lax.all_gather(n_lo, axis)
    any_found = jnp.any(founds)
    win = jnp.argmax(founds)
    return (any_found, nonces_hi[win], nonces_lo[win], chunks)


def make_sharded_search(mesh: Mesh, *, lanes: int = 1 << 13,
                        max_chunks: int = 64, axis: str | None = None,
                        variant: str = DEFAULT_VARIANT):
    """Build a jitted pod-wide search fn over ``mesh``.

    Returns ``fn(ih_hi, ih_lo, t_hi, t_lo, s_hi, s_lo) ->
    (found, nonce_hi, nonce_lo, chunks)`` with all inputs/outputs
    replicated; internally the nonce range is partitioned across the
    mesh axis.
    """
    if axis is None:
        axis = mesh.axis_names[-1]
    body = functools.partial(_device_search, lanes=lanes,
                             max_chunks=max_chunks, axis=axis,
                             variant=variant)
    reps = P()  # replicated in and out; partitioning is by axis_index
    fn = shard_map(body, mesh=mesh,
                   in_specs=(reps,) * 6, out_specs=(reps,) * 4,
                   check_vma=False)
    return jax.jit(fn)


def make_sharded_batch_search(mesh: Mesh, *, lanes: int = 1 << 13,
                              max_chunks: int = 64,
                              obj_axis: str = "obj",
                              nonce_axis: str = "nonce",
                              variant: str = DEFAULT_VARIANT):
    """Pod-wide search over a BATCH of pending objects on a 2D mesh.

    Objects are data-parallel over ``obj_axis`` while each object's
    nonce range is partitioned over ``nonce_axis`` — the "batch all
    pending workerQueue objects into one grid" design.  Inputs:
    ``ih_hi, ih_lo``: (B, 8) initial-hash words; ``t_hi, t_lo, s_hi,
    s_lo``: (B,).  Outputs (found, nonce_hi, nonce_lo, chunks): (B,).
    The vmapped while_loop runs until every local object has a hit (or
    max_chunks), so per-object early exit is batch-granular.
    """
    def local(ih_hi, ih_lo, t_hi, t_lo, s_hi, s_lo):
        search_one = functools.partial(
            _device_search, lanes=lanes, max_chunks=max_chunks,
            axis=nonce_axis, variant=variant)
        return jax.vmap(search_one)(ih_hi, ih_lo, t_hi, t_lo, s_hi, s_lo)

    obj = P(obj_axis)
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(obj_axis, None), P(obj_axis, None), obj, obj, obj, obj),
        out_specs=(obj,) * 4,
        check_vma=False)
    return jax.jit(fn)


#: cache of jitted search fns keyed by (mesh, kind, lanes, max_chunks) —
#: re-wrapping shard_map produces a fresh fn object every call, which
#: would defeat jit's compile cache and recompile per solve.
_FN_CACHE: dict = {}


def get_sharded_search(mesh: Mesh, *, lanes: int, max_chunks: int,
                       variant: str = DEFAULT_VARIANT):
    key = (mesh, "single", lanes, max_chunks, variant)
    if key not in _FN_CACHE:
        _FN_CACHE[key] = make_sharded_search(
            mesh, lanes=lanes, max_chunks=max_chunks, variant=variant)
    return _FN_CACHE[key]


def get_sharded_batch_search(mesh: Mesh, *, lanes: int, max_chunks: int,
                             variant: str = DEFAULT_VARIANT):
    key = (mesh, "batch", lanes, max_chunks, variant)
    if key not in _FN_CACHE:
        _FN_CACHE[key] = make_sharded_batch_search(
            mesh, lanes=lanes, max_chunks=max_chunks,
            obj_axis=mesh.axis_names[0], nonce_axis=mesh.axis_names[-1],
            variant=variant)
    return _FN_CACHE[key]


def sharded_solve_batch(items, mesh: Mesh, *, lanes: int = 1 << 13,
                        chunks_per_call: int = 64,
                        variant: str = DEFAULT_VARIANT,
                        should_stop: Callable[[], bool] | None = None):
    """Solve a batch of pending objects in one pod-wide grid.

    ``items``: sequence of ``(initial_hash, target)``.  The 2D mesh's
    leading axis carries objects (data-parallel), the trailing axis
    partitions each object's nonce range.  The batch is padded to a
    multiple of the object-axis size; every returned nonce is
    re-verified host-side.  Returns ``[(nonce, trials), ...]`` aligned
    with ``items``.

    This is the production form of SURVEY §6's "grid = nonce-lanes x
    objects" design — all queued workerQueue sends become one launch
    (reference solves strictly one at a time,
    src/class_singleWorker.py:1274-1276).
    """
    import numpy as np

    from ..utils.hashes import double_sha512

    n = len(items)
    if n == 0:
        return []
    obj_size = mesh.shape[mesh.axis_names[0]] if len(mesh.axis_names) > 1 \
        else 1
    nonce_size = mesh.shape[mesh.axis_names[-1]]
    # pad with always-hit dummies: a duplicated real item would re-solve
    # its full difficulty and hold the vmapped while_loop open for it
    padded = list(items) + [(b"\x00" * 64, _MASK64)] * (-n % obj_size)
    total = len(padded)
    fn = get_sharded_batch_search(mesh, lanes=lanes,
                                  max_chunks=chunks_per_call,
                                  variant=variant) \
        if len(mesh.axis_names) > 1 else None
    if fn is None:
        # 1D mesh: no object axis — fall back to sequential pod solves
        return [sharded_solve(ih, t, mesh, lanes=lanes,
                              chunks_per_call=chunks_per_call,
                              variant=variant, should_stop=should_stop)
                for ih, t in items]

    words = [initial_hash_words(ih) for ih, _ in padded]
    ih_hi = jnp.stack([w[0] for w in words])
    ih_lo = jnp.stack([w[1] for w in words])
    targets = [t & _MASK64 for _, t in padded]
    t_hi = jnp.array([t >> 32 for t in targets], dtype=U32)
    t_lo = jnp.array([t & 0xFFFFFFFF for t in targets], dtype=U32)

    import time as _time

    step = lanes * nonce_size            # trials per object per chunk
    ndev = mesh.devices.size
    bases = [0] * total
    trials = [0] * total
    nonces: list[int | None] = [None] * total
    while any(x is None for x in nonces[:n]):
        if should_stop is not None and should_stop():
            raise PowInterrupted("batched PoW interrupted by shutdown")
        s_hi = jnp.array([(b >> 32) & 0xFFFFFFFF for b in bases], dtype=U32)
        s_lo = jnp.array([b & 0xFFFFFFFF for b in bases], dtype=U32)
        t0 = _time.monotonic()
        out_dev = fn(ih_hi, ih_lo, t_hi, t_lo, s_hi, s_lo)
        t1 = _time.monotonic()
        found, n_hi, n_lo, chunks = (np.asarray(x) for x in out_dev)
        t2 = _time.monotonic()
        record_launch("sharded_batch",
                      key=(lanes, chunks_per_call, total, variant),
                      dispatch_seconds=t1 - t0, wait_seconds=t2 - t1,
                      span=(t0, t2),
                      items=int(chunks.sum()) * step,
                      bytes_in=int(s_hi.nbytes + s_lo.nbytes),
                      bytes_out=16 * total, devices=ndev)
        for i in range(total):
            c = int(chunks[i])
            if nonces[i] is not None:
                continue
            trials[i] += c * step
            if found[i]:
                nonce = (int(n_hi[i]) << 32) | int(n_lo[i])
                ih = padded[i][0]
                check = double_sha512(nonce.to_bytes(8, "big") + ih)
                if int.from_bytes(check[:8], "big") > targets[i]:
                    raise ArithmeticError(
                        "accelerator returned an invalid PoW nonce")
                nonces[i] = nonce
                # mask the solved object: with an always-hit target its
                # vmapped while_loop lane exits on the first chunk of
                # any subsequent launch instead of re-solving
                t_hi = t_hi.at[i].set(jnp.uint32(0xFFFFFFFF))
                t_lo = t_lo.at[i].set(jnp.uint32(0xFFFFFFFF))
            else:
                bases[i] = (bases[i] + c * step) & _MASK64
    return [(nonces[i], trials[i]) for i in range(n)]


def sharded_solve(initial_hash: bytes, target: int, mesh: Mesh, *,
                  start_nonce: int = 0, lanes: int = 1 << 13,
                  chunks_per_call: int = 64,
                  variant: str = DEFAULT_VARIANT,
                  should_stop: Callable[[], bool] | None = None,
                  _search_fn=None):
    """Host driver for the pod-wide search (same contract as ops.solve)."""
    ndev = mesh.devices.size
    fn = _search_fn or get_sharded_search(
        mesh, lanes=lanes, max_chunks=chunks_per_call, variant=variant)
    ih_hi, ih_lo = initial_hash_words(initial_hash)
    t_hi, t_lo = u64_from_int(target)

    def search_once(b_hi, b_lo):
        return fn(ih_hi, ih_lo, t_hi, t_lo, b_hi, b_lo)

    return _run_host_driver(
        search_once, initial_hash, target, start_nonce=start_nonce,
        trials_per_call_step=lanes * ndev, should_stop=should_stop,
        program="sharded_search",
        program_key=(lanes, chunks_per_call, ndev, variant),
        devices=ndev)
