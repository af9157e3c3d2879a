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
"""

from __future__ import annotations

import functools
import math
import operator

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
#: sublanes of one 32-bit vreg: the slice of a tile hashed at a time
SLICE_ROWS = 8

#: Tiles a grid step and steps a launch.  Since PR 26 a tile is hashed
#: one (SLICE_ROWS, 128) slice at a time, so rows and unroll only say
#: how many slices a grid step loops over (its fixed cost is about
#: 0.35 us) and how often a search can leave at a hit; they no longer
#: shape the instruction streams, and the r3/r4 unroll ladders that
#: stood here (77.8 MH/s at unroll 1 to 151.0 at 6) measured a kernel
#: that is gone.  rows=512 used to exceed the 16 MB scoped VMEM limit
#: and chunks>=1024 does not compile (SMEM).  Measured on a v5e, batch
#: kernel 64 x 64 x 4 x 128 rows, traced `chan_storm_256` runs (my chip
#: runs, PR 26), with the compiler's final bundles of one grid step:
#:   textbook body            23,220 vector ops a vreg of trials (the
#:                            jaxpr shows 21,979: an unsigned compare is
#:                            two xors more), 459,233 bundles, 351,067
#:                            of them with a spill store   199.76 MH/s
#:   this body, whole tiles   20,593 ops (jaxpr 20,600), 432,313
#:                            bundles, 319,946 spill stores 211.63 MH/s
#:   this body, slice loop    64 x 5,396 bundles, 201 spill stores a
#:                            slice                         289.3 MH/s
#: 289.3 MH/s x 20,600 = 5.96e12 ops/s, 97 % of the 6.1e12 ESTIMATE of
#: the VPU's peak (8x128 lanes x 4 ALUs x 1.5 GHz): what is left is the
#: number of operations a trial.  An earlier carry-save _add_many (same
#: count, shorter chains) had measured no gain: issue-limited then too.
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
                  *, rows: int, unroll: int = 1):
    """2D grid (objects, chunks): each object owns a per-object early-
    exit flag, so easy objects stop costing compute while hard ones
    keep searching — the single-chip form of the (objects x
    nonce-lanes) batch design (SURVEY §6).  The search body is shared
    with the single-object kernel (_search_step), ``unroll`` tiles to a
    grid step.

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
        hit, n_hi, n_lo = _search_step(
            lambda i: (ih_ref[obj, i, 0], ih_ref[obj, i, 1]),
            base_ref[obj, 0], base_ref[obj, 1],
            target_ref[obj, 0], target_ref[obj, 1], step, rows * unroll)
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
#: trials) a 64-wide launch runs 0.45 s warm.  Mosaic compiled the
#: 64-wide grid in minutes until PR 26 (CHANGES.md PR 22 has those
#: times) and in seconds since; core/jaxsetup.py places the persistent
#: cache that keeps it a once-per-machine cost.
BATCH_OBJS = 64
BATCH_CHUNKS = 64
#: four tiles to a grid step of the batch grid (64 objects x 64 chunks
#: x 4, solve-verified on-chip since r4).  Since PR 26 this sets only
#: how often an object can leave at its hit, not the kernel's streams
#: or its compile time (8 s for what took 190 s)
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
          interpret: bool = False, progress=None):
    """Find a nonce whose trial value is <= target (Pallas backend).

    Same contract as :func:`pow_search.solve`: returns
    ``(nonce, trials_done)`` or raises ``PowInterrupted``.  The host
    re-invokes the kernel in slabs of ``chunks_per_call * rows * 128 *
    unroll`` trials so the shutdown callback stays responsive
    (reference host loop: src/openclpow.py:96-107), and keeps one slab
    in flight ahead of the one being harvested so dispatch and
    host-transfer gaps hide behind device compute.  Trials are
    accounted at slab granularity.  ``chunks_per_call`` is a static
    argument of the kernel and every caller on a node passes the same
    one: 512 is the largest power of two a v5e compiles (1024 asks for
    1.01M of its 1.00M of SMEM; tests/test_tpu_compile.py), and the
    grid leaves at its first hit, so a long slab costs a short solve
    nothing.
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
