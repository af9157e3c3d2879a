"""Pallas TPU kernel: VMEM-resident double-SHA512 nonce search.

Differences from the XLA path (pow_search.py): the entire search slab
runs inside ONE kernel — the round state (24 uint32 tile pairs) lives
in VMEM/registers across all 160 rounds and all grid steps, instead of
being materialized to HBM at every fori_loop iteration boundary.  An
SMEM scratch "found" flag carried across the sequential grid gives
early exit: once a step hits, every later step's search body is skipped
via ``pl.when`` and only writes its zeroed output row.

Layout: grid = (chunks,); each grid step evaluates a (ROWS, 128) tile
of nonces = base + step*ROWS*128 + lane.  Outputs per step: hit flag
and winning (nonce_hi, nonce_lo); the host takes the first hit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.devicetelemetry import (POW_FLOPS_PER_HASH,
                                             record_launch,
                                             register_program)
from ..observability.tracing import trace
from .sha512_jax import _H0, _K
from .u64 import U32

LANE_COLS = 128

#: measured v5e sweet spot: FIVE independent 128-row tiles per grid
#: step — the 160-round chains are dependency-limited, so extra
#: instruction streams let the VPU multi-issue.  r3 same-day ladder
#: (rows=128, chunks=512): unroll=1: 77.8 MH/s, 2: 97.9, 3: 121.3,
#: 4: 136.4, 6: 143.3; 64-row streams lose (64x8: 133.5, 64x4: 90.2),
#: two 256-row streams thrash VMEM (77.2), rows=512 exceeds the 16 MB
#: scoped VMEM limit, chunks>=1024 fails to compile.  r4 same-day
#: ladder: 4: 138.0, 5: 149.2 (compile 170 s), 6: 151.0 (compile
#: 228 s) — 5 is the knee.  A carry-save restructure of _add_many
#: (hi parts summed as an independent tree off the carry chain)
#: measured NEGATIVE same-day: 134.7 vs the 138.0 control — the VPU is
#: issue-limited, not carry-latency-limited, so the only lever that
#: moves the number is more independent streams.
DEFAULT_ROWS = 128
DEFAULT_CHUNKS = 512
DEFAULT_UNROLL = 5


def _pair(value: int):
    return jnp.uint32(value >> 32), jnp.uint32(value & 0xFFFFFFFF)


def _rotr(x, n):
    hi, lo = x
    if n == 32:
        return lo, hi
    if n < 32:
        m = 32 - n
        return (hi >> n) | (lo << m), (lo >> n) | (hi << m)
    n -= 32
    m = 32 - n
    return (lo >> n) | (hi << m), (hi >> n) | (lo << m)


def _shr(x, n):
    hi, lo = x
    if n >= 32:
        return jnp.zeros_like(hi), hi >> (n - 32)
    return hi >> n, (lo >> n) | (hi << (32 - n))


def _xor3(a, b, c):
    return a[0] ^ b[0] ^ c[0], a[1] ^ b[1] ^ c[1]


def _add(a, b):
    lo = a[1] + b[1]
    carry = (lo < a[1]).astype(U32)
    return a[0] + b[0] + carry, lo


def _add_many(*terms):
    acc = terms[0]
    for t in terms[1:]:
        acc = _add(acc, t)
    return acc


def _compress(w):
    """80 rounds over a 16-entry python-list window of tile pairs."""
    a, b, c, d, e, f, g, h = [_broadcast_pair(_pair(x), w[0][0].shape)
                              for x in _H0]
    for t in range(80):
        if t < 16:
            wt = w[t]
        else:
            wt = _add_many(
                _xor3(_rotr(w[(t - 2) % 16], 19), _rotr(w[(t - 2) % 16], 61),
                      _shr(w[(t - 2) % 16], 6)),
                w[(t - 7) % 16],
                _xor3(_rotr(w[(t - 15) % 16], 1), _rotr(w[(t - 15) % 16], 8),
                      _shr(w[(t - 15) % 16], 7)),
                w[t % 16])
            w[t % 16] = wt
        ch = ((e[0] & f[0]) ^ (~e[0] & g[0]),
              (e[1] & f[1]) ^ (~e[1] & g[1]))
        maj = ((a[0] & b[0]) ^ (a[0] & c[0]) ^ (b[0] & c[0]),
               (a[1] & b[1]) ^ (a[1] & c[1]) ^ (b[1] & c[1]))
        s1e = _xor3(_rotr(e, 14), _rotr(e, 18), _rotr(e, 41))
        s0a = _xor3(_rotr(a, 28), _rotr(a, 34), _rotr(a, 39))
        t1 = _add_many(h, s1e, ch, _pair(_K[t]), wt)
        t2 = _add(s0a, maj)
        h, g, f, e = g, f, e, _add(d, t1)
        d, c, b, a = c, b, a, _add(t1, t2)
    return [_add(_broadcast_pair(_pair(_H0[i]), a[0].shape), v)
            for i, v in enumerate([a, b, c, d, e, f, g, h])]


def _broadcast_pair(pair, shape):
    return (jnp.broadcast_to(pair[0], shape), jnp.broadcast_to(pair[1], shape))


def _double_sha512_tile(ih_pair, n_hi, n_lo):
    """Double-SHA512 trial values for a tile of nonces.

    ``ih_pair(i) -> (hi, lo)`` may return shape-() scalars (the single
    and per-object batch kernels read them straight from SMEM) or
    full-tile arrays (the packed kernel's per-lane object identity).
    Scalar initial-hash words are NOT broadcast to the lane shape here:
    every message-schedule word whose inputs are all uniform across the
    lane axis (w17/w19/w21 outright, plus the sigma contributions of
    w1..w15 feeding later extensions) then stays a shape-() value the
    compiler evaluates once per object on the scalar core, instead of
    redundantly per lane on the VPU — the schedule-hoisting lever.
    Mixed scalar/tile pairs combine through ordinary broadcasting in
    ``_add``/``_xor3``.
    """
    zero = jnp.uint32(0)
    w = [(n_hi, n_lo)]
    w += [ih_pair(i) for i in range(8)]
    w.append((jnp.uint32(0x80000000), zero))
    w += [(zero, zero)] * 5
    w.append((zero, jnp.uint32(576)))
    h1 = _compress(w)

    w2 = list(h1)
    w2.append((jnp.uint32(0x80000000), zero))
    w2 += [(zero, zero)] * 6
    w2.append((zero, jnp.uint32(512)))
    h2 = _compress(w2)
    return h2[0]


def _search_step(ih_pair, base_hi, base_lo, target_hi, target_lo,
                 step, rows: int):
    """One grid step's search over a (rows, 128) nonce tile.

    ``ih_pair(i) -> (hi, lo)`` abstracts the initial-hash indexing so
    the single-object and batched kernels share this body exactly.
    Returns (hit int32, nonce_hi, nonce_lo).
    """
    shape = (rows, LANE_COLS)
    lane = (jax.lax.broadcasted_iota(U32, shape, 0)
            * jnp.uint32(LANE_COLS)
            + jax.lax.broadcasted_iota(U32, shape, 1))
    offset = jnp.uint32(step) * jnp.uint32(rows * LANE_COLS)
    lo = base_lo + offset + lane
    carry = (lo < base_lo).astype(U32)  # offset+lane < 2^32 per slab
    hi = jnp.broadcast_to(base_hi, shape) + carry

    v_hi, v_lo = _double_sha512_tile(ih_pair, hi, lo)

    ok = (v_hi < target_hi) | ((v_hi == target_hi) & (v_lo <= target_lo))
    # winner = smallest lane index with a hit.  Mosaic has no unsigned
    # reductions; lane < 2^31 so int32 min is safe.
    big = jnp.int32(0x7FFFFFFF)
    win_i = jnp.min(jnp.where(ok, lane.astype(jnp.int32), big))
    hit = (win_i != big).astype(jnp.int32)
    win = win_i.astype(U32)
    wl = base_lo + offset + win
    wc = (wl < base_lo).astype(U32)
    return hit, base_hi + wc, wl


def _unrolled_search(ih_pair, base_hi, base_lo, t_hi, t_lo, step,
                     rows: int, unroll: int):
    """``unroll`` independent (rows, 128) tiles for one grid step.

    The 160-round chains are dependency-limited, so interleaving
    independent instruction streams lets the VPU multi-issue (the MFU
    lever, BASELINE.md "Arithmetic utilization").  Keeps the FIRST
    sub-tile's winner (lowest nonce range).  Shared by the single and
    batch kernels."""
    hit, n_hi, n_lo = _search_step(ih_pair, base_hi, base_lo, t_hi, t_lo,
                                   step * unroll, rows)
    for u in range(1, unroll):
        h2, nh2, nl2 = _search_step(ih_pair, base_hi, base_lo, t_hi, t_lo,
                                    step * unroll + u, rows)
        n_hi = jnp.where(hit == 1, n_hi, nh2)
        n_lo = jnp.where(hit == 1, n_lo, nl2)
        hit = jnp.maximum(hit, h2)
    return hit, n_hi, n_lo


def _kernel(ih_ref, base_ref, target_ref, found_ref, nonce_ref, flag_ref, *,
            rows: int, unroll: int = 1):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init_flag():
        flag_ref[0] = jnp.int32(0)

    # Every step owns one output row; default it so skipped steps don't
    # leave garbage in the (uninitialized) SMEM output buffer.
    found_ref[step, 0] = jnp.int32(0)
    nonce_ref[step, 0] = jnp.uint32(0)
    nonce_ref[step, 1] = jnp.uint32(0)

    @pl.when(flag_ref[0] == 0)
    def do_search():
        hit, n_hi, n_lo = _unrolled_search(
            lambda i: (ih_ref[i, 0], ih_ref[i, 1]),
            base_ref[0], base_ref[1], target_ref[0], target_ref[1],
            step, rows, unroll)
        found_ref[step, 0] = hit
        flag_ref[0] = hit
        nonce_ref[step, 0] = n_hi
        nonce_ref[step, 1] = n_lo


def _batch_kernel(ih_ref, base_ref, target_ref, out_ref, flag_ref,
                  *, rows: int, unroll: int = 1):
    """2D grid (objects, chunks): each object owns a per-object early-
    exit flag, so easy objects stop costing compute while hard ones
    keep searching — the single-chip form of the (objects x
    nonce-lanes) batch design (SURVEY §6).  The search body is shared
    with the single-object kernel (_search_step), including its
    ``unroll`` independent instruction streams per grid step (the ILP
    lever that lifted the single kernel 1.75x — BASELINE.md).

    Output is written ONCE per object, on its hit step: a (B, 3) u32
    row ``[hit_step + 1, nonce_hi, nonce_lo]`` (0 = not found).  r3's
    (B, chunks)-shaped outputs made SMEM scale with the chunk count
    and capped the batch at 16 objects (VERDICT r3 #2); the write-once
    row is chunk-count-independent — 64 objects compile comfortably —
    and the harvest is ONE small device->host fetch."""
    obj = pl.program_id(0)
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        flag_ref[obj] = jnp.int32(0)
        out_ref[obj, 0] = jnp.uint32(0)
        out_ref[obj, 1] = jnp.uint32(0)
        out_ref[obj, 2] = jnp.uint32(0)

    @pl.when(flag_ref[obj] == 0)
    def do_search():
        hit, n_hi, n_lo = _unrolled_search(
            lambda i: (ih_ref[obj, i, 0], ih_ref[obj, i, 1]),
            base_ref[obj, 0], base_ref[obj, 1],
            target_ref[obj, 0], target_ref[obj, 1], step, rows, unroll)
        flag_ref[obj] = hit

        @pl.when(hit == 1)
        def _record():
            out_ref[obj, 0] = jnp.uint32(step + 1)
            out_ref[obj, 1] = n_hi
            out_ref[obj, 2] = n_lo


def _packed_kernel(ih_hi_ref, ih_lo_ref, t_hi_ref, t_lo_ref,
                   b_hi_ref, b_lo_ref, base_ref, out_ref, flag_ref,
                   *, rows: int, pack: int, unroll: int = 1):
    """Multi-object SLAB PACKING: grid = (groups, chunks).  Each grid
    step evaluates ONE (rows, 128) tile shared by ``pack`` objects
    (``rows // pack`` rows each), and the leading grid axis carries
    independent groups — one launch covers ``groups * pack`` pending
    objects, so a broadcast storm of tiny objects fills the whole grid
    instead of paying a launch + host sync per object (the ISSUE 2
    tentpole: BENCH_r05 measured the storm at 35.7M H/s, 5.7x below
    kernel peak, dominated by per-launch overhead).

    Per-lane object identity (initial-hash words, targets, nonce
    bases) is baked into pre-gathered VMEM tiles streamed per group;
    ``base_ref`` (SMEM (groups, pack, 2)) carries scalar nonce bases
    for winner recovery.  Winners resolve per object via a masked min
    over the object's rows; per-object SMEM flags keep the first
    winner and a per-group counter skips the group's remaining steps
    once every member has hit (storm groups usually exit within a few
    steps).  Solved objects' rows keep hashing until their group
    finishes — waste bounded by the group, which the planner keeps
    difficulty-homogeneous by sorting.
    """
    grp = pl.program_id(0)
    step = pl.program_id(1)
    rpo = rows // pack
    shape = (rows, LANE_COLS)

    @pl.when(step == 0)
    def _init():
        flag_ref[grp, pack] = jnp.int32(0)
        for k in range(pack):
            flag_ref[grp, k] = jnp.int32(0)
            out_ref[grp, k, 0] = jnp.uint32(0)
            out_ref[grp, k, 1] = jnp.uint32(0)
            out_ref[grp, k, 2] = jnp.uint32(0)

    @pl.when(flag_ref[grp, pack] < pack)
    def do_search():
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        # lane index WITHIN the owning object: (r % rpo)*128 + c
        local = ((jax.lax.broadcasted_iota(U32, shape, 0)
                  % jnp.uint32(rpo)) * jnp.uint32(LANE_COLS)
                 + jax.lax.broadcasted_iota(U32, shape, 1))
        local_i = local.astype(jnp.int32)
        big = jnp.int32(0x7FFFFFFF)
        b_hi = b_hi_ref[0]
        b_lo = b_lo_ref[0]
        t_hi = t_hi_ref[0]
        t_lo = t_lo_ref[0]
        for u in range(unroll):
            offset = (jnp.uint32(step) * jnp.uint32(unroll)
                      + jnp.uint32(u)) * jnp.uint32(rpo * LANE_COLS)
            lo = b_lo + offset
            carry = (lo < b_lo).astype(U32)
            hi = b_hi + carry
            v_hi, v_lo = _double_sha512_tile(
                lambda i: (ih_hi_ref[0, i], ih_lo_ref[0, i]), hi, lo)
            ok = (v_hi < t_hi) | ((v_hi == t_hi) & (v_lo <= t_lo))
            cand = jnp.where(ok, local_i, big)
            for k in range(pack):
                @pl.when(flag_ref[grp, k] == 0)
                def _check(k=k, cand=cand, offset=offset):
                    mask = ((row >= k * rpo) & (row < (k + 1) * rpo))
                    win = jnp.min(jnp.where(mask, cand, big))

                    @pl.when(win != big)
                    def _record():
                        wl = (base_ref[grp, k, 1] + offset
                              + win.astype(U32))
                        wc = (wl < base_ref[grp, k, 1]).astype(U32)
                        out_ref[grp, k, 0] = jnp.uint32(step + 1)
                        out_ref[grp, k, 1] = base_ref[grp, k, 0] + wc
                        out_ref[grp, k, 2] = wl
                        flag_ref[grp, k] = jnp.int32(1)
                        flag_ref[grp, pack] = flag_ref[grp, pack] + 1


@functools.partial(jax.jit, static_argnames=("rows", "chunks", "pack",
                                             "unroll", "interpret"),
                   donate_argnums=(1, 2))
def pallas_packed_search(ih_words, bases, targets, rows: int = DEFAULT_ROWS,
                         chunks: int = 16, pack: int = 16,
                         unroll: int = 1, interpret: bool = False):
    """Search B = groups*pack objects' nonce ranges in ONE launch.

    ``bases``/``targets`` are DONATED: the pipeline uploads fresh
    per-launch arrays (they change every dispatch), so XLA recycles
    the previous launch's buffers instead of allocating — callers must
    not reuse the arrays they pass in.

    ``ih_words``: (B, 8, 2) uint32; ``bases``/``targets``: (B, 2),
    with B a multiple of ``pack``.  Objects are tiled ``pack`` per
    (rows, 128) grid-step tile (object k of a group owns rows
    [k*rows/pack, (k+1)*rows/pack)) and groups ride the leading grid
    axis; object b searches nonces ``bases[b] + step*unroll*rpo*128 +
    local_lane``.  Returns a (B, 3) uint32 array of ``[hit_step + 1,
    nonce_hi, nonce_lo]`` rows (first column 0 = no hit this launch).

    The per-lane gathers (object id -> ih words / target / base) run
    in XLA *outside* the kernel, once per launch — Mosaic only ever
    sees dense elementwise tiles, DMA-streamed per group.
    """
    if rows % pack:
        raise ValueError("rows %d not divisible by pack %d" % (rows, pack))
    n_obj = ih_words.shape[0]
    if n_obj % pack:
        raise ValueError("batch %d not divisible by pack %d"
                         % (n_obj, pack))
    groups = n_obj // pack
    rpo = rows // pack
    shape = (rows, LANE_COLS)

    def tile(col):          # (G, rows) -> (G, rows, 128)
        return jnp.broadcast_to(col[:, :, None], (groups,) + shape)

    # (G, pack, 8, 2) -> per-row object identity (G, rows, 8, 2)
    ihw = jnp.repeat(ih_words.reshape(groups, pack, 8, 2), rpo, axis=1)
    ih_hi_t = jnp.broadcast_to(
        ihw[..., 0].transpose(0, 2, 1)[:, :, :, None],
        (groups, 8) + shape)
    ih_lo_t = jnp.broadcast_to(
        ihw[..., 1].transpose(0, 2, 1)[:, :, :, None],
        (groups, 8) + shape)
    tg = jnp.repeat(targets.reshape(groups, pack, 2), rpo, axis=1)
    t_hi_t = tile(tg[..., 0])
    t_lo_t = tile(tg[..., 1])
    local = ((jax.lax.broadcasted_iota(U32, shape, 0) % jnp.uint32(rpo))
             * jnp.uint32(LANE_COLS)
             + jax.lax.broadcasted_iota(U32, shape, 1))
    bg = jnp.repeat(bases.reshape(groups, pack, 2), rpo, axis=1)
    b_lo_obj = tile(bg[..., 1])
    b_lo_t = b_lo_obj + local
    b_hi_t = tile(bg[..., 0]) + (b_lo_t < b_lo_obj).astype(U32)

    kernel = functools.partial(_packed_kernel, rows=rows, pack=pack,
                               unroll=unroll)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups, pack, 3), U32),
        grid=(groups, chunks),
        in_specs=[
            pl.BlockSpec((1, 8) + shape, lambda g, s: (g, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8) + shape, lambda g, s: (g, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1,) + shape, lambda g, s: (g, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1,) + shape, lambda g, s: (g, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1,) + shape, lambda g, s: (g, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1,) + shape, lambda g, s: (g, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        scratch_shapes=[pltpu.SMEM((groups, pack + 1), jnp.int32)],
        interpret=interpret,
    )(ih_hi_t, ih_lo_t, t_hi_t, t_lo_t, b_hi_t, b_lo_t,
      bases.reshape(groups, pack, 2))
    return out.reshape(n_obj, 3)


@functools.partial(jax.jit, static_argnames=("rows", "chunks", "interpret",
                                             "unroll"))
def pallas_batch_search(ih_words, bases, targets, rows: int = 256,
                        chunks: int = 128, interpret: bool = False,
                        unroll: int = 1):
    """Search B objects' nonce ranges in ONE kernel launch.

    ``ih_words``: (B, 8, 2) uint32; ``bases``/``targets``: (B, 2).
    Returns a (B, 3) uint32 array of ``[hit_step + 1, nonce_hi,
    nonce_lo]`` rows (first column 0 = no hit in this launch); each
    grid step covers ``unroll`` consecutive (rows, 128) tiles.
    """
    n_obj = ih_words.shape[0]
    kernel = functools.partial(_batch_kernel, rows=rows, unroll=unroll)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_obj, 3), U32),
        grid=(n_obj, chunks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        scratch_shapes=[pltpu.SMEM((n_obj,), jnp.int32)],
        interpret=interpret,
    )(ih_words, bases, targets)
    return out


#: pad batches to this many objects per launch — one compiled program
#: serves any batch size; always-hit targets make pad slots skip after
#: their first chunk via the per-object flag.  r4 on-chip measurements
#: (the r3 16-object SMEM cap is gone with the write-once output row):
#: launch wall is fixed-overhead dominated at low difficulty, so wider
#: launches win the storm — 256-object test-difficulty storm ~300
#: obj/s at 32-wide (8 launches) vs ~500 obj/s at 64-wide (4 launches,
#: ~0.12 s each); at ~2^44 difficulty (every object searching ~1M
#: trials) a 64-wide launch runs 0.45 s warm.  Mosaic compile for the
#: 64-wide grid takes minutes (CHANGES.md PR 22 has the measured
#: times); core/jaxsetup.py places the persistent cache that keeps it
#: a once-per-machine cost.
BATCH_OBJS = 64
BATCH_CHUNKS = 64
#: the batch grid keeps the unroll-4 configuration (64 objects x 64
#: chunks x 4 streams compiled + solve-verified on-chip r4); the storm
#: is launch-overhead-bound, not VPU-bound, so the single kernel's
#: unroll-5 knee doesn't transfer.  r5 measured the u5 batch grid
#: anyway: storm 541 vs 531 obj/s (noise) and ~+5% on the
#: real-difficulty batch, for +70 s Mosaic compile (142 -> 213 s) —
#: below the knee, not worth the driver-bench wall time
BATCH_UNROLL = 4


class _BatchGroup:
    """Host state for one ``BATCH_OBJS``-wide launch group."""

    __slots__ = ("idx", "ih_words", "t_np", "t_dev", "t_dirty", "targets",
                 "bases", "trials", "done", "harvested")

    def __init__(self, items, idx, mask64):
        import numpy as np

        pad = BATCH_OBJS - len(idx)
        ihs = [items[i][0] for i in idx] + [b"\x00" * 64] * pad
        self.targets = ([items[i][1] & mask64 for i in idx]
                        + [mask64] * pad)
        words = [[int.from_bytes(ih[j:j + 8], "big")
                  for j in range(0, 64, 8)] for ih in ihs]
        self.ih_words = jnp.array(
            [[[w >> 32, w & 0xFFFFFFFF] for w in ws] for ws in words],
            dtype=U32)
        # all per-launch mutation is staged in NUMPY and converted once
        # per launch: a tiny device op per solved object (an
        # .at[].set) is a dispatch each
        self.t_np = np.array(
            [[t >> 32, t & 0xFFFFFFFF] for t in self.targets],
            dtype=np.uint32)
        self.idx = idx
        self.t_dev = None       # device-resident targets (lazy upload)
        self.t_dirty = True     # re-upload only after a target flips
        self.bases = [0] * BATCH_OBJS
        self.trials = [0] * BATCH_OBJS
        self.done = [i >= len(idx) for i in range(BATCH_OBJS)]
        self.harvested = 0

    @property
    def finished(self) -> bool:
        return all(self.done)


def solve_batch(items, *, rows: int = DEFAULT_ROWS,
                chunks_per_call: int = BATCH_CHUNKS,
                unroll: int = BATCH_UNROLL, should_stop=None,
                interpret: bool = False):
    """Solve ``[(initial_hash, target), ...]`` in batched launches.

    The single-chip production form of the pod-wide batch grid: up to
    ``BATCH_OBJS`` objects share each kernel launch; solved (and pad)
    objects flip their per-object flag and stop consuming grid steps.
    Returns ``[(nonce, trials), ...]`` aligned with ``items``.

    The host loop keeps ONE launch in flight ahead of the one being
    harvested (the same pipeline as the single-object :func:`solve`):
    bases advance optimistically at dispatch, and a launch is dispatched
    for the NEXT group (or, for a group that has already proven it needs
    more than one slab, the next slab of the same group) before the
    pending launch's results are pulled, so the transfer and the
    per-object host bookkeeping hide behind device compute.  A
    speculative tail launch dispatched for a group whose pending launch
    turns out to have finished it is abandoned unfetched; since every
    finished object's target is flipped to always-hit, such a launch
    exits after one chunk per object and costs almost nothing.
    """
    from ..utils.hashes import double_sha512
    from .pow_search import PowInterrupted

    n = len(items)
    if n == 0:
        return []
    results: list = [None] * n
    mask64 = (1 << 64) - 1
    trials_per_slab = rows * LANE_COLS * chunks_per_call * unroll
    step_trials = rows * LANE_COLS * unroll

    groups = [
        _BatchGroup(items,
                    list(range(s, min(s + BATCH_OBJS, n))), mask64)
        for s in range(0, n, BATCH_OBJS)
    ]

    def dispatch(g: _BatchGroup):
        import time as _time

        import numpy as np

        b_arr = np.array(
            [[(b >> 32) & 0xFFFFFFFF, b & 0xFFFFFFFF] for b in g.bases],
            dtype=np.uint32)
        live = sum(1 for d in g.done if not d)
        uploaded = int(b_arr.nbytes)
        t0 = _time.monotonic()
        # targets change only when an object solves; keeping the device
        # copy across launches saves one host->device transfer on
        # every steady-state launch
        if g.t_dirty:
            g.t_dev = jnp.asarray(g.t_np.copy())
            g.t_dirty = False
            uploaded += int(g.t_np.nbytes)
        out = pallas_batch_search(
            g.ih_words, b_arr, g.t_dev, rows=rows,
            chunks=chunks_per_call, unroll=unroll, interpret=interpret)
        t1 = _time.monotonic()
        for k in range(BATCH_OBJS):
            if not g.done[k]:
                g.bases[k] = (g.bases[k] + trials_per_slab) & mask64
        return out, live, uploaded, t0, t1

    def harvest(g: _BatchGroup, out_dev, live, uploaded, t0, t1):
        import time as _time

        import numpy as np

        t2 = _time.monotonic()
        out = np.asarray(out_dev)
        t3 = _time.monotonic()
        record_launch("batch_search",
                      key=(rows, chunks_per_call, unroll, interpret),
                      dispatch_seconds=t1 - t0, wait_seconds=t3 - t2,
                      span=(t0, t3), items=live * trials_per_slab,
                      bytes_in=uploaded, bytes_out=int(out.nbytes))
        for k in range(BATCH_OBJS):
            if g.done[k]:
                continue
            step1 = int(out[k, 0])
            if step1:
                # trials credited up to the hit step, not the slab
                g.trials[k] += step1 * step_trials
                val = (int(out[k, 1]) << 32) | int(out[k, 2])
                ih = items[g.idx[k]][0]
                check = double_sha512(val.to_bytes(8, "big") + ih)
                if int.from_bytes(check[:8], "big") > g.targets[k]:
                    raise ArithmeticError(
                        "accelerator returned an invalid nonce")
                results[g.idx[k]] = (val, g.trials[k])
                g.done[k] = True
                # pad semantics: hit instantly next launch, then skip
                g.t_np[k] = (0xFFFFFFFF, 0xFFFFFFFF)
                g.t_dirty = True
            else:
                g.trials[k] += trials_per_slab
        g.harvested += 1

    pending = None  # (group, in-flight device output)
    rr = 0          # round-robin dispatch cursor over groups
    while True:
        if should_stop is not None and should_stop():
            raise PowInterrupted("batched Pallas PoW interrupted")
        live = [g for g in groups if not g.finished]
        if not live and pending is None:
            return results
        pending_g = pending[0] if pending is not None else None
        # round-robin over unfinished groups, never the pending one
        # (its next slab would be speculative while fresh work exists);
        # otherwise speculate one slab ahead on a group that has
        # already needed >=1 full slab without finishing
        cand = None
        for off in range(len(groups)):
            g = groups[(rr + off) % len(groups)]
            if not g.finished and g is not pending_g:
                cand = g
                rr = (rr + off + 1) % len(groups)
                break
        if cand is None and pending_g is not None \
                and pending_g.harvested >= 1 and not pending_g.finished:
            cand = pending_g
        cur = (cand,) + dispatch(cand) if cand is not None else None
        if pending is not None and not pending[0].finished:
            harvest(*pending)
        pending = cur


@functools.partial(jax.jit, static_argnames=("rows", "chunks", "interpret",
                                             "unroll"))
def pallas_search(ih_words, base, target, rows: int = 256,
                  chunks: int = 16, interpret: bool = False,
                  unroll: int = 1):
    """Search nonces [base, base + chunks*unroll*rows*128) for value
    <= target.

    ``ih_words``: (8, 2) uint32 — initial-hash words as (hi, lo);
    ``base``/``target``: (2,) uint32 pairs.  Returns (found (chunks,),
    nonce (chunks, 2)) per grid step; each grid step covers ``unroll``
    consecutive (rows, 128) tiles.
    """
    grid = (chunks,)
    kernel = functools.partial(_kernel, rows=rows, unroll=unroll)
    found, nonce = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((chunks, 1), jnp.int32),
                   jax.ShapeDtypeStruct((chunks, 2), U32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(ih_words, base, target)
    return found[:, 0], nonce




def solve(initial_hash: bytes, target: int, *,
          start_nonce: int = 0, rows: int = DEFAULT_ROWS,
          chunks_per_call: int = DEFAULT_CHUNKS,
          unroll: int = DEFAULT_UNROLL, should_stop=None,
          interpret: bool = False, tuner=None,
          tuner_kind: str = "pallas_single", progress=None):
    """Find a nonce whose trial value is <= target (Pallas backend).

    Same contract as :func:`pow_search.solve`: returns
    ``(nonce, trials_done)`` or raises ``PowInterrupted``.  The host
    re-invokes the kernel in slabs of ``chunks_per_call * rows * 128 *
    unroll`` trials so the shutdown callback stays responsive
    (reference host loop: src/openclpow.py:96-107), and keeps one slab
    in flight ahead of the one being harvested so dispatch and
    host-transfer gaps hide behind device compute.  The r3 production
    slab (128 x 512 x 4) measures 136.4 MH/s — see BASELINE.md
    "Arithmetic utilization" for the unroll ladder.  Trials are
    accounted at slab granularity.
    """
    import numpy as np

    # the host loops' counters live with the pipeline, which imports
    # this module at its top
    from ..pow.pipeline import (ABANDONED_LAUNCHES, EXECUTED_TRIALS,
                                LAUNCHES)
    from ..utils.hashes import double_sha512
    from .pow_search import PowInterrupted

    words = [int.from_bytes(initial_hash[i:i + 8], "big")
             for i in range(0, 64, 8)]
    ih_words = jnp.array([[w >> 32, w & 0xFFFFFFFF] for w in words],
                         dtype=U32)
    target &= (1 << 64) - 1
    target_arr = jnp.array([target >> 32, target & 0xFFFFFFFF], dtype=U32)

    chunks = chunks_per_call
    if tuner is not None:
        # measured-latency slab sizing; the octave bound keeps Mosaic
        # recompiles (one per distinct chunk count) rare
        chunks = tuner.suggest(tuner_kind, chunks_per_call,
                               lo=chunks_per_call // 2,
                               hi=chunks_per_call * 2)
    trials_per_slab = rows * LANE_COLS * chunks * unroll
    mask64 = (1 << 64) - 1

    def launch(base_int: int):
        import numpy as np

        # numpy arg: the transfer rides the jit call itself instead of
        # a separate explicit device-put
        base = np.array([(base_int >> 32) & 0xFFFFFFFF,
                         base_int & 0xFFFFFFFF], dtype=np.uint32)
        with trace("pow.launch", program="pallas_slab", chunks=chunks,
                   live=1) as span:
            out = pallas_search(ih_words, base, target_arr, rows=rows,
                                chunks=chunks, unroll=unroll,
                                interpret=interpret)
        LAUNCHES.labels(kind="slab").inc()
        return out, span.start, span.end

    def harvest(found_dev, nonce_dev, t_disp, t_disp_end):
        """Sync one slab's results; returns the winning nonce or None."""
        with trace("pow.fetch") as fetch:
            f = np.asarray(found_dev)
        record_launch("pallas_slab",
                      key=(rows, chunks, unroll, interpret),
                      dispatch_seconds=t_disp_end - t_disp,
                      wait_seconds=fetch.duration,
                      span=(t_disp, fetch.end),
                      items=trials_per_slab, bytes_in=8,
                      bytes_out=int(f.nbytes))
        idx = int(f.argmax())
        # the grid leaves at its first hit: steps up to it really ran
        EXECUTED_TRIALS.labels(kind="slab").inc(
            (idx + 1 if f[idx] else chunks) * rows * LANE_COLS * unroll)
        if not f[idx]:
            return None
        n = np.asarray(nonce_dev)
        offset = (int(n[idx, 0]) << 32) | int(n[idx, 1])
        check = double_sha512(offset.to_bytes(8, "big") + initial_hash)
        if int.from_bytes(check[:8], "big") > target:  # pragma: no cover
            raise ArithmeticError("accelerator returned an invalid nonce")
        return offset

    # Double-buffered host loop: slab N+1 is dispatched BEFORE slab N's
    # results are pulled, so the host-side transfer/bookkeeping gap
    # hides behind device compute on long (multi-slab) searches.
    import time as _time

    base = start_nonce & mask64
    trials = 0
    # ((found_dev, nonce_dev), dispatch_start, dispatch_end, end_base)
    pending = None
    while True:
        if should_stop is not None and should_stop():
            # the in-flight slab may already hold the answer — check
            # before discarding ~16.7M trials of completed device work
            if pending is not None:
                trials += trials_per_slab
                nonce = harvest(*pending[0], pending[1], pending[2])
                if nonce is not None:
                    return nonce, trials
                if progress is not None:
                    progress(pending[3])
            raise PowInterrupted("Pallas PoW interrupted by shutdown")
        end_base = (base + trials_per_slab) & mask64
        current = launch(base) + (end_base,)
        base = end_base
        if pending is not None:
            trials += trials_per_slab
            nonce = harvest(*pending[0], pending[1], pending[2])
            if tuner is not None:
                # dispatch -> harvested wall of the pending slab: the
                # cadence the autotuner steers toward target_seconds
                tuner.record(tuner_kind, chunks,
                             _time.monotonic() - pending[2])
            if nonce is not None:
                # the slab just dispatched is left behind unfetched
                ABANDONED_LAUNCHES.labels(kind="slab").inc()
                return nonce, trials
            if progress is not None:
                # the pending slab harvested miss-free: its end is the
                # resumable-PoW checkpoint (resilience/journal.py)
                progress(pending[3])
        pending = current


register_program("pallas_slab", flops_per_item=POW_FLOPS_PER_HASH,
                 module="ops/sha512_pallas.py",
                 jit_names=("pallas_search",))
register_program("batch_search", flops_per_item=POW_FLOPS_PER_HASH,
                 module="ops/sha512_pallas.py",
                 jit_names=("pallas_batch_search",))
register_program("packed_search", flops_per_item=POW_FLOPS_PER_HASH,
                 module="ops/sha512_pallas.py",
                 jit_names=("pallas_packed_search",))
