"""Pallas TPU kernel: VMEM-resident double-SHA512 nonce search.

Differences from the XLA path (pow_search.py): the entire search slab
runs inside ONE kernel — the round state (24 uint32 tile pairs) lives
in VMEM/registers across all 160 rounds and all grid steps, instead of
being materialized to HBM at every fori_loop iteration boundary.  An
SMEM scratch "found" flag carried across the sequential grid gives
early exit: once a step hits, every later step's search body is skipped
via ``pl.when`` and only writes its zeroed output row.

Layout: grid = (chunks,); each grid step evaluates a (ROWS, 128) tile
of nonces = base + step*ROWS*128 + lane, one (8, 128) slice at a time.
Outputs per step: hit flag and winning (nonce_hi, nonce_lo); the host
takes the first hit.

This module holds kernels only: the three jitted entry points
(``pallas_search``, ``pallas_batch_search``, ``pallas_packed_search``),
their shape constants and their ``register_program`` lines.  The
entries are ``persisted_jit``s (``core/programcache.py``): tracing one
walks 160 unrolled rounds of Python and lowering the result to Mosaic
takes as long again, 4-8 s a shape together, so on an accelerator a
shape's lowered program is exported by a machine's first start, kept
beside the compile cache and loaded by every later start, which runs
the same Mosaic bytes without walking this file (an ``interpret=True``
call, the ``cpu`` backend and a call under a trace go to the jitted
function as before; an edit of this file, ``sha512_jax.py`` or
``u64.py`` is another program).  Which of
them serves an input, at which shape, and the host loop that launches
it live one layer up, in ``pow/pipeline.py`` (``plan_batch``,
``_PipelineDriver``), which also places a queue over the chips of a
host; the nonce-range partition of a lone object over several chips
is in ``parallel/pow_pallas_sharded.py``.  Nothing here imports from
``pybitmessage_tpu.pow``.
"""

from __future__ import annotations

import functools
import math
import operator
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.programcache import persisted_jit
from ..observability.devicetelemetry import (POW_FLOPS_PER_HASH,
                                             register_program)
from .sha512_jax import _H0, _K
from .u64 import U32

#: the files the three programs are traced from: the key of a
#: persisted program holds their digest (core/programcache.py)
_SOURCES = tuple(str(Path(__file__).with_name(name)) for name in (
    "sha512_pallas.py", "sha512_jax.py", "u64.py"))

LANE_COLS = 128
#: sublanes of one 32-bit vreg: the slice of a tile hashed at a time
SLICE_ROWS = 8

#: Tiles a step and steps a launch.  Since PR 26 a tile is hashed one
#: (SLICE_ROWS, 128) slice at a time, so rows and unroll only say how
#: many slices a step loops over (a grid step's fixed cost is about
#: 0.35 us) and how often a search can leave at a hit; they no longer
#: shape the instruction streams, and the r3/r4 unroll ladders that
#: stood here (77.8 MH/s at unroll 1 to 151.0 at 6) measured a kernel
#: that is gone.  rows=512 used to exceed the 16 MB scoped VMEM limit
#: and chunks>=1024 does not compile for ``pallas_search`` (SMEM).
#: Measured on a v5e, the batch kernel at 64 objects x 128 chunks x 4
#: tiles of 128 rows, traced `chan_storm_256` runs (my chip runs, PR
#: 26), with the compiler's final bundles of one grid step:
#:   textbook body            23,220 vector ops a vreg of trials (the
#:                            jaxpr shows 21,979: an unsigned compare is
#:                            two xors more), 459,233 bundles, 351,067
#:                            of them with a spill store   199.76 MH/s
#:   this body, whole tiles   20,593 ops (jaxpr 20,600), 432,313
#:                            bundles, 319,946 spill stores 211.63 MH/s
#:   this body, slice loop    5,396 bundles a slice, 201 spill stores,
#:                            64 slices a grid step         289.3 MH/s
#: 289.3 MH/s x 20,600 = 5.96e12 ops/s, 97 % of the 6.1e12 ESTIMATE of
#: the VPU's peak (8x128 lanes x 4 ALUs x 1.5 GHz): what is left is the
#: number of operations a trial.  An earlier carry-save _add_many (same
#: count, shorter chains) had measured no gain: issue-limited then too.
#: Since PR 40 the batch kernel's step is ONE tile of 64 rows (8
#: slices) and its steps run in a loop inside the kernel
#: (``BATCH_ROWS``, ``BATCH_INNER`` below): the same slice body (the
#: whole kernel 5,377 -> 5,387 final bundles, 205 spill stores both),
#: an eighth of the trials thrown away at a hit or by a slot with
#: nothing to search.
DEFAULT_ROWS = 128
DEFAULT_CHUNKS = 512
DEFAULT_UNROLL = 5


_M32 = 0xFFFFFFFF
_M64 = (1 << 64) - 1
_TOP = 0x80000000
I32 = jnp.int32


# A 64-bit word is a (hi, lo) pair of 32-bit halves.  A half is a Python
# int in [0, 2^32) where its value is known while tracing (the IV, K,
# the padding and length words and all that follows from them alone),
# else a traced int32: shape () where it is the same in every lane (the
# single and batch kernels' initial-hash words, read from SMEM) or a
# full tile.  The helpers do with Python ints what can be done while
# tracing and emit an operation only where a traced value is involved,
# so one _compress serves scalar and tile-shaped initial-hash words
# alike.  The halves are SIGNED because the v5e's VPU compares signed
# only: an unsigned ``lo < x`` costs two more xors a carry (PR 26 read
# them in the compiler's final bundles).

def _word(value: int):
    return value >> 32, value & _M32


def _known(x) -> bool:
    return isinstance(x, int)


def _i32(x):
    return I32(x - ((x & _TOP) << 1)) if _known(x) else x


def _rank(word) -> int:
    """0 for a word known while tracing, 1 for one uniform across the
    lanes (shape ()), 2 for a tile."""
    return max(0 if _known(x) else 1 if x.shape == () else 2 for x in word)


def _shl32(x, n):
    return (x << n) & _M32 if _known(x) else x << n


def _shr32(x, n):
    return x >> n if _known(x) else jax.lax.shift_right_logical(x, I32(n))


def _or32(x, y):
    if _known(x) and _known(y):
        return x | y
    return _i32(x) | _i32(y)


def _xor32(*xs):
    const = functools.reduce(operator.xor, filter(_known, xs), 0)
    traced = [x for x in xs if not _known(x)]
    if not traced:
        return const
    if const:
        traced.append(_i32(const))
    return functools.reduce(operator.xor, traced)


def _and32(x, y):
    if _known(x) and _known(y):
        return x & y
    return _i32(x) & _i32(y)


def _add32(x, y):
    if _known(x) and _known(y):
        return (x + y) & _M32
    if _known(y):
        x, y = y, x
    return y if _known(x) and x == 0 else _i32(x) + y


def _rotr(x, n):
    hi, lo = x
    if n == 32:
        return lo, hi
    if n > 32:
        hi, lo, n = lo, hi, n - 32
    m = 32 - n
    return (_or32(_shr32(hi, n), _shl32(lo, m)),
            _or32(_shr32(lo, n), _shl32(hi, m)))


def _shr(x, n):
    hi, lo = x
    return _shr32(hi, n), _or32(_shr32(lo, n), _shl32(hi, 32 - n))


def _small_sigma0(x):
    """rotr 1 ^ rotr 8 ^ shr 7, as rotr 1 of (x ^ rotr 7) ^ shr 7: the
    rotation is the shift and one more piece, four operations less."""
    top, low = _shr(x, 7)
    rot7 = _or32(top, _shl32(x[1], 25)), low
    return _xor(_rotr(_xor(x, rot7), 1), (top, low))


def _small_sigma1(x):
    return _xor(_rotr(x, 19), _rotr(x, 61), _shr(x, 6))


def _xor(*words):
    return (_xor32(*[w[0] for w in words]), _xor32(*[w[1] for w in words]))


def _and(a, b):
    return _and32(a[0], b[0]), _and32(a[1], b[1])


class _Open(tuple):
    """A sum still open to further terms: (hi, lo ^ _TOP).

    With the top bit of the low half flipped, signed order is the
    unsigned order of the true values, so every further term's carry is
    one signed compare of the new low half with the old.  ``_close``
    flips the bit back; a sum that feeds two others (t1) is never
    closed."""


def _sum(*terms):
    """Sum mod 2^64, as an :class:`_Open`.

    At most one term is itself open, and the sum goes on from it.  The
    others are taken known words first (folded here), then uniform
    ones, then tiles: like sums with like, and a known or uniform start
    is opened on the scalar core or not at all."""
    start = [t for t in terms if isinstance(t, _Open)]
    rest = sorted((t for t in terms if not isinstance(t, _Open)), key=_rank)
    n_known = sum(_rank(t) == 0 for t in rest)
    const = sum((hi << 32) | lo for hi, lo in rest[:n_known]) & _M64
    rest = rest[n_known:]
    if start:
        (hi, lo), = start
        if const:
            rest.insert(0, _word(const))
    elif n_known or not rest:
        hi, lo = const >> 32, (const & _M32) ^ _TOP
    else:
        (hi, lo), rest = rest[0], rest[1:]
        lo = _xor32(lo, _TOP)
    for x_hi, x_lo in rest:
        hi = _add32(hi, x_hi)
        if _known(x_lo) and x_lo == 0:
            continue                    # nothing to carry
        if _known(lo) and lo == _TOP:   # nor into a low half of zero
            lo = _xor32(x_lo, _TOP)
            continue
        was, lo = lo, _add32(lo, x_lo)
        hi = _add32(hi, (lo < _i32(was)).astype(I32))
    return _Open((hi, lo))


def _close(open_sum):
    hi, lo = open_sum
    return hi, _xor32(lo, _TOP)


def _compress(w):
    """80 rounds over a 16-entry python-list window of words."""
    iv = [_word(x) for x in _H0]
    a, b, c, d, e, f, g, h = iv
    bc = _xor(b, c)
    for t in range(80):
        if t < 16:
            wt = w[t]
        else:
            wt = w[t % 16] = _close(_sum(
                _small_sigma1(w[(t - 2) % 16]), w[(t - 7) % 16],
                _small_sigma0(w[(t - 15) % 16]), w[t % 16]))
        # Ch and Maj in three operations a half; this round's a ^ b is
        # the next round's b ^ c
        ch = _xor(g, _and(e, _xor(f, g)))
        ab = _xor(a, b)
        maj = _xor(b, _and(ab, bc))
        s1e = _xor(_rotr(e, 14), _rotr(e, 18), _rotr(e, 41))
        s0a = _xor(_rotr(a, 28), _rotr(a, 34), _rotr(a, 39))
        t1 = _sum(h, s1e, ch, _word(_K[t]), wt)
        h, g, f, e = g, f, e, _close(_sum(t1, d))
        d, c, b, a, bc = c, b, a, _close(_sum(t1, s0a, maj)), ab
    return [_close(_sum(x, v)) for x, v in zip(iv, (a, b, c, d, e, f, g, h))]


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
    redundantly per lane on the VPU -- the schedule-hoisting lever.
    The padding and length words stay Python ints, so what follows from
    them alone (``K[t] + W[t]`` of rounds 9-15, the sigmas of the zero
    words) is folded while tracing and never emitted.
    """
    def signed(word):
        return tuple(x.astype(I32) for x in word)

    w = [signed((n_hi, n_lo))] + [signed(ih_pair(i)) for i in range(8)]
    w += [_word(1 << 63)] + [_word(0)] * 5 + [_word(576)]
    h1 = _compress(w)
    w2 = h1 + [_word(1 << 63)] + [_word(0)] * 6 + [_word(512)]
    return tuple(x.astype(U32) for x in _compress(w2)[0])


def _search_step(ih_pair, base_hi, base_lo, target_hi, target_lo,
                 step, rows: int):
    """One grid step's search over a (rows, 128) nonce tile.

    ``ih_pair(i) -> (hi, lo)`` abstracts the initial-hash indexing so
    the single-object and batched kernels share this body exactly; both
    pass their ``unroll`` consecutive tiles as one of ``unroll`` times
    the rows, whose lowest winning lane is the first tile's winner if it
    has one.  Returns (hit int32, nonce_hi, nonce_lo).

    The tile is hashed one (8, 128) slice at a time -- one vreg a value
    -- in a loop: the 48 live halves of one double-SHA-512 then stay in
    the 64 vregs, where a whole (128, 128) tile a value made the
    compiler spill a quarter of all it computed (PR 26, final bundles
    of the batch kernel: 351,067 spill stores beside 1,541,974 vector
    operations a grid step, one store slot a bundle).
    """
    slice_rows = math.gcd(rows, SLICE_ROWS)
    shape = (slice_rows, LANE_COLS)
    lane0 = (jax.lax.broadcasted_iota(U32, shape, 0)
             * jnp.uint32(LANE_COLS)
             + jax.lax.broadcasted_iota(U32, shape, 1))
    offset = jnp.uint32(step) * jnp.uint32(rows * LANE_COLS)
    big = jnp.int32(0x7FFFFFFF)

    def hash_slice(i, best):
        lane = lane0 + jnp.uint32(i) * jnp.uint32(slice_rows * LANE_COLS)
        lo = base_lo + offset + lane
        carry = (lo < base_lo).astype(U32)  # offset+lane < 2^32 per slab
        hi = jnp.broadcast_to(base_hi, shape) + carry
        v_hi, v_lo = _double_sha512_tile(ih_pair, hi, lo)
        ok = (v_hi < target_hi) | ((v_hi == target_hi)
                                   & (v_lo <= target_lo))
        return jnp.minimum(best, jnp.where(ok, lane.astype(jnp.int32), big))

    # winner = smallest lane index with a hit.  Mosaic has no unsigned
    # reductions; lane < 2^31 so int32 min is safe.
    win_i = jnp.min(jax.lax.fori_loop(0, rows // slice_rows, hash_slice,
                                      jnp.full(shape, big, jnp.int32)))
    hit = (win_i != big).astype(jnp.int32)
    win = win_i.astype(U32)
    wl = base_lo + offset + win
    wc = (wl < base_lo).astype(U32)
    return hit, base_hi + wc, wl


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
        hit, n_hi, n_lo = _search_step(
            lambda i: (ih_ref[i, 0], ih_ref[i, 1]),
            base_ref[0], base_ref[1], target_ref[0], target_ref[1],
            step, rows * unroll)
        found_ref[step, 0] = hit
        flag_ref[0] = hit
        nonce_ref[step, 0] = n_hi
        nonce_ref[step, 1] = n_lo


def _batch_kernel(ih_ref, base_ref, target_ref, out_ref, flag_ref,
                  *, rows: int, unroll: int = 1, inner: int = 1):
    """2D grid (objects, chunks // inner): each object owns a per-object
    early-exit flag, so easy objects stop costing compute while hard
    ones keep searching — the single-chip form of the (objects x
    nonce-lanes) batch design (SURVEY §6).  The search body is shared
    with the single-object kernel (_search_step), ``unroll`` tiles to a
    step.

    A grid step runs ``inner`` of the object's steps in a loop that
    leaves at the first hit, so an object that is done (or a pad slot,
    done after its first step) skips ``chunks // inner`` grid steps and
    not ``chunks``: a skipped grid step costs 0.06 us, and with one step
    a grid step a dead slot's 1,023 of them would cost more than the one
    tile it hashes (PERF.md section 6, PR 40: at 512 steps a launch of
    64 dead slots took 6.38 ms without the loop, 4.29 with it).

    Output is written ONCE per object, on its hit step: a (B, 3) u32
    row ``[hit_step + 1, nonce_hi, nonce_lo]`` (0 = not found).  r3's
    (B, chunks)-shaped outputs made SMEM scale with the chunk count
    and capped the batch at 16 objects (VERDICT r3 #2); the write-once
    row is chunk-count-independent — 64 objects compile comfortably —
    and the harvest is ONE small device->host fetch."""
    obj = pl.program_id(0)
    outer = pl.program_id(1)

    @pl.when(outer == 0)
    def _init():
        flag_ref[obj] = jnp.int32(0)
        out_ref[obj, 0] = jnp.uint32(0)
        out_ref[obj, 1] = jnp.uint32(0)
        out_ref[obj, 2] = jnp.uint32(0)

    @pl.when(flag_ref[obj] == 0)
    def do_search():
        def searching(carry):
            i, hit = carry
            return (i < inner) & (hit == 0)

        def one_step(carry):
            step = outer * inner + carry[0]
            hit, n_hi, n_lo = _search_step(
                lambda i: (ih_ref[obj, i, 0], ih_ref[obj, i, 1]),
                base_ref[obj, 0], base_ref[obj, 1],
                target_ref[obj, 0], target_ref[obj, 1], step,
                rows * unroll)

            @pl.when(hit == 1)
            def _record():
                flag_ref[obj] = jnp.int32(1)
                out_ref[obj, 0] = jnp.uint32(step + 1)
                out_ref[obj, 1] = n_hi
                out_ref[obj, 2] = n_lo

            return carry[0] + 1, hit

        jax.lax.while_loop(searching, one_step,
                           (jnp.int32(0), jnp.int32(0)))


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


@persisted_jit(sources=_SOURCES,
               static_argnames=("rows", "chunks", "pack", "unroll",
                                "interpret"),
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


def _batch_search(ih_words, bases, targets, *, rows, chunks, interpret,
                  unroll, inner):
    """``pallas_batch_search`` with the steps a grid step loops over
    as an argument (the tests' handle on the loop)."""
    n_obj = ih_words.shape[0]
    kernel = functools.partial(_batch_kernel, rows=rows, unroll=unroll,
                               inner=inner)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_obj, 3), U32),
        grid=(n_obj, chunks // inner),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        scratch_shapes=[pltpu.SMEM((n_obj,), jnp.int32)],
        interpret=interpret,
    )(ih_words, bases, targets)


@persisted_jit(sources=_SOURCES,
               static_argnames=("rows", "chunks", "interpret", "unroll"))
def pallas_batch_search(ih_words, bases, targets, rows: int = 256,
                        chunks: int = 128, interpret: bool = False,
                        unroll: int = 1):
    """Search B objects' nonce ranges in ONE kernel launch.

    ``ih_words``: (B, 8, 2) uint32; ``bases``/``targets``: (B, 2).
    Returns a (B, 3) uint32 array of ``[hit_step + 1, nonce_hi,
    nonce_lo]`` rows (first column 0 = no hit in this launch); an
    object runs up to ``chunks`` steps of ``unroll`` consecutive
    (rows, 128) tiles, :data:`BATCH_INNER` of them (or as many as
    divide ``chunks``) to a grid step.
    """
    return _batch_search(ih_words, bases, targets, rows=rows, chunks=chunks,
                         interpret=interpret, unroll=unroll,
                         inner=math.gcd(chunks, BATCH_INNER))


#: pad batches to this many objects per launch — one compiled program
#: serves any batch size; always-hit targets make pad slots skip after
#: their first chunk via the per-object flag.  r4 on-chip measurements
#: (the r3 16-object SMEM cap is gone with the write-once output row):
#: launch wall is fixed-overhead dominated at low difficulty, so wider
#: launches win the storm — 256-object test-difficulty storm ~300
#: obj/s at 32-wide (8 launches) vs ~500 obj/s at 64-wide (4 launches,
#: ~0.12 s each); at ~2^44 difficulty (every object searching ~1M
#: trials) a 64-wide launch runs 0.45 s warm.  Mosaic compiled the
#: 64-wide grid in minutes until PR 26 (CHANGES.md PR 22 has those
#: times) and in seconds since; core/jaxsetup.py places the persistent
#: cache that keeps the compile a once-per-machine cost, and
#: core/programcache.py keeps the trace and the lowering one too.
BATCH_OBJS = 64
#: grid steps an object of the pod's batch launches
#: (parallel/pow_pallas_sharded.py, four tiles to a step there); the
#: pipeline has its own, ``pow.pipeline.DEFAULT_BATCH_CHUNKS``
BATCH_CHUNKS = 64
#: the step of the batch kernel: ONE tile of 64 rows, 8,192 trials (8
#: slices, 28 us), and that is all a hit or a dead slot throws away.
#: Four tiles of 128 rows to a step (PR 24-39) cost every solved or pad
#: slot of a launch 65,536 trials: 15.4 % of all the trials of
#: ``pod4_burst_64``, where 60 slots of 64 are dead.  Settled on the
#: chip (PERF.md section 6, PR 40: launches of 64 dead slots 15.66 ms
#: at 65,536, 4.29 at 16,384, 2.55 at 8,192; of 64 live ones 290.55,
#: 289.66, 288.47 MH/s): the smallest step that keeps the rate of a
#: launch of live slots within 1 % of what it was.  Since PR 26 these
#: set only how often an object can leave, not the kernel's streams or
#: its compile time.  The pipeline takes ``min(rows, BATCH_ROWS)``
BATCH_ROWS = 64
BATCH_UNROLL = 1
#: steps of one object a grid step loops over (``_batch_kernel``): a
#: launch of 1,024 steps is 16 grid steps an object, which is what a
#: done slot skips (0.06 us each; 64 or 128 to a grid step, 8 or 512 at
#: 16,384 trials a step, read the same on the chip)
BATCH_INNER = 64


@persisted_jit(sources=_SOURCES,
               static_argnames=("rows", "chunks", "interpret", "unroll"))
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


register_program("pallas_slab", flops_per_item=POW_FLOPS_PER_HASH,
                 module="ops/sha512_pallas.py",
                 jit_names=("pallas_search",))
register_program("batch_search", flops_per_item=POW_FLOPS_PER_HASH,
                 module="ops/sha512_pallas.py",
                 jit_names=("pallas_batch_search",))
register_program("packed_search", flops_per_item=POW_FLOPS_PER_HASH,
                 module="ops/sha512_pallas.py",
                 jit_names=("pallas_packed_search",))
