"""Vectorized secp256k1 for the accelerator: batch point multiplication,
ECDSA verification and ECDH as one SIMD program (ISSUE 13).

The receive-side crypto drains (crypto/batch.py) are thousands of
*independent* scalar multiplications per call — the same embarrassingly
parallel integer workload the PoW kernel exploits (BENCH_r05 measures
~4.1e12 u32 ops/s/chip in ops/sha512_pallas.py).  This module lays one
drain across the vector lanes: every lane runs the same branchless
field/group program on its own operand, exactly like a nonce lane runs
the same SHA512 rounds on its own counter.

Field representation — 20 x 13-bit unsigned limbs ("lazy carries"):

The VPU has no 64-bit multiply (u64.py emulates u64 *adds* with u32
pairs, but 32x32->64 products would need 4 half-word multiplies each).
With 13-bit limbs a partial product fits u32 natively (26 bits) and a
whole schoolbook row of 20 partials still fits (< 2^31), so the 400
partial products of a field multiplication are plain u32 FMAs with NO
carry handling inside the row loop.  Carrying is *lazy* and parallel:
two data-parallel passes of ``(d & MASK) + shift(d >> 13)`` bound every
limb to <= 8223 — a quasi-carried form that is closed under the whole
op set — instead of a 40-step sequential ripple.  Reduction mod p uses
p = 2^256 - 2^32 - 977: limb 20+k folds back in as ``15632*L^k +
1024*L^(k+2)`` (L = 2^13, since L^20 = 2^4 * 2^256).  The 4x64
schoolbook in native/secp256k1/bmsecp256k1.cpp is the reference oracle
these exact bounds were cross-checked against (tests/test_crypto_tpu.py
proves bit-identical results vs crypto/fallback.py over random and
adversarial vectors).

Working forms:

- R*: value < 2^256 + 2^38 (so < 2p), limbs <= 8223, top limb <= 520.
  Every public field op returns R*; ``f_canon`` makes a value canonical
  (< p, fully carried) for equality tests and output packing.
- products/sums between ops may exceed R* freely as long as each limb
  stays < 2^32; ``f_reduce`` restores R*.

Group law: branchless Jacobian coordinates with explicit infinity
flags (secp256k1 has odd prime order, so Y = 0 never occurs on-curve
and doubling is total).  ``jac_add`` computes the generic sum AND the
doubling in parallel and lane-selects between them, so equal/inverse/
infinity operands cost selects, not branches.  ECDSA verification uses
the Strauss–Shamir dual ladder (one shared double chain for u1*G +
u2*Q, per-bit addend from the {inf, G, Q, G+Q} table).

Execution paths share one code body:

- ``xla_*``: ``jax.jit`` over the core functions — the CPU-CI path
  (JAX_PLATFORMS=cpu) and the fallback on hosts where Mosaic is
  unavailable.  Lanes are padded to fixed buckets so jit caches a
  handful of programs instead of one per drain size.
- ``pallas_*``: the same core functions called from inside a
  ``pl.pallas_call`` kernel over (8, 128) lane tiles resident in VMEM
  (the sha512_pallas layout), with ``interpret=True`` supported for
  parity tests.  ``nbits`` is static so interpret-mode tests can run a
  truncated ladder at tractable cost while exercising every code path.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .u64 import U32

# --- curve constants ---------------------------------------------------------

P = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEFFFFFC2F
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8

LIMB_BITS = 13
LIMBS = 20
MASK = (1 << LIMB_BITS) - 1

#: L^20 = 2^260 == 2^36 + 15632 (mod p); 2^36 = 2^10 * L^2
FOLD0, FOLD2 = 15632, 1024
#: 2^256 == 2^32 + 977 (mod p); 2^32 = 2^6 * L^2
TOP0, TOP2 = 977, 64

LANE_COLS = 128
LANE_ROWS = 8
TILE = LANE_ROWS * LANE_COLS

#: XLA-path lane buckets: drains pad up to one of these so the jit
#: cache holds a handful of programs, not one per drain size
BUCKETS = (64, 256, 1024)


def _int_limbs(v: int, n: int = LIMBS) -> list[int]:
    return [(v >> (LIMB_BITS * i)) & MASK for i in range(n - 1)] \
        + [v >> (LIMB_BITS * (n - 1))]


P_LIMBS = _int_limbs(P)
N_LIMBS = _int_limbs(N)
GX_LIMBS = _int_limbs(GX)
GY_LIMBS = _int_limbs(GY)

# Subtraction bias: 4p in a "borrow-lent" expansion whose limbs all
# dominate an R* subtrahend (middle limbs >= 16382 >= 8223, top limb
# >= 520), so ``a + SUB_C - b`` never goes negative per-limb while the
# value shifts by exactly 4p (== 0 mod p).
_4P = 4 * P
_B4 = _int_limbs(_4P)
SUB_C = ([_B4[0] + 2 * (MASK + 1)]
         + [_B4[i] + 2 * (MASK + 1) - 2 for i in range(1, 19)]
         + [_B4[19] - 2])
assert sum(c << (LIMB_BITS * i) for i, c in enumerate(SUB_C)) == _4P
assert min(SUB_C[:19]) >= 16382 and SUB_C[19] >= 520


# --- field arithmetic (stacked (LIMBS, *lanes) uint32 arrays) ---------------

def _const(limbs: list[int], lane_shape) -> jnp.ndarray:
    """Broadcast an integer-limb constant across the lane shape.

    Built from SCALAR constants (stacked broadcasts), not a
    materialized array — Pallas kernels may not capture constant
    arrays, while scalar constants inline fine in both paths."""
    return jnp.stack([jnp.full(lane_shape, c, dtype=U32)
                      for c in limbs])


def _carry2(d: jnp.ndarray) -> jnp.ndarray:
    """Two parallel lazy-carry passes: limbs < 2^31 in -> limbs <= 8223
    out, value unchanged.  One extra limb absorbs the top carry (zero
    by the callers' value bounds, kept for shape honesty)."""
    d = jnp.concatenate([d, jnp.zeros((1,) + d.shape[1:], dtype=U32)])
    for _ in range(2):
        c = d >> LIMB_BITS
        d = (d & MASK) + jnp.concatenate(
            [jnp.zeros((1,) + d.shape[1:], dtype=U32), c[:-1]])
    return d


def _place(x: jnp.ndarray, start: int, rows: int) -> jnp.ndarray:
    """``x`` at rows [start, start + len(x)) of a zero ``rows``-row
    stack.  Limb stacks are only ever shifted by concatenation on the
    leading axis: Mosaic has no scatter, and leading-axis concatenation
    of whole (8, 128) tiles moves no data."""
    parts = []
    if start:
        parts.append(jnp.zeros((start,) + x.shape[1:], dtype=U32))
    parts.append(x)
    tail = rows - start - x.shape[0]
    if tail:
        parts.append(jnp.zeros((tail,) + x.shape[1:], dtype=U32))
    return jnp.concatenate(parts) if len(parts) > 1 else x


def f_reduce(d: jnp.ndarray) -> jnp.ndarray:
    """Arbitrary limb stack (rows <= 2*LIMBS, limbs < 2^31) -> R*."""
    d = _carry2(d)
    if d.shape[0] > 21:
        for _ in range(2):
            # fold rows >= 20 down: h*L^(20+k) == h*(FOLD0 + FOLD2*L^2)*L^k
            hi = d[LIMBS:]
            r = (_place(d[:LIMBS], 0, LIMBS + 2)
                 + _place(hi * FOLD0, 0, LIMBS + 2)
                 + _place(hi * FOLD2, 2, LIMBS + 2))
            d = _carry2(r)
        # two passes leave value < 2^260 + 2^66: rows > 20 are
        # structurally zero (a nonzero row 21 implies >= 2^273)
        d = d[:21]
    else:
        d = d[:21]
    # fold bits >= 2^256 (rows 19..20): t = value div 2^256 bits
    t = (d[20] << 4) + (d[19] >> 9)
    r = jnp.concatenate([(d[0] + t * TOP0)[None], d[1:2],
                         (d[2] + t * TOP2)[None], d[3:19],
                         (d[19] & 511)[None]])
    return _carry2(r)[:LIMBS]


def f_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook 20x20 with u32-native partial products (R* inputs)."""
    d = _place(a[0] * b, 0, 2 * LIMBS - 1)
    for i in range(1, LIMBS):
        d = d + _place(a[i] * b, i, 2 * LIMBS - 1)
    return f_reduce(d)


def f_sqr(a: jnp.ndarray) -> jnp.ndarray:
    return f_mul(a, a)


def f_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return f_reduce(a + b)


def f_sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    c = _const(SUB_C, a.shape[1:])
    return f_reduce(a + c - b)


def f_scale(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """Multiply by a small constant (k <= 8: limbs stay < 2^17)."""
    return f_reduce(a * jnp.uint32(k))


def f_canon(a: jnp.ndarray) -> jnp.ndarray:
    """R* -> canonical (< p, fully carried): one sequential ripple plus
    one conditional subtract of p (R* < 2p makes one enough)."""
    limbs = []
    c = jnp.zeros_like(a[0])
    for i in range(LIMBS):
        t = a[i] + c
        limbs.append(t & MASK if i < LIMBS - 1 else t)
        c = t >> LIMB_BITS
    a = jnp.stack(limbs)
    return _cond_sub(a, P_LIMBS)


def _cond_sub(a: jnp.ndarray, mod_limbs: list[int]) -> jnp.ndarray:
    """Subtract ``mod_limbs`` when a >= mod (a fully carried, < 2*mod)."""
    borrow = jnp.zeros_like(a[0])
    subbed = []
    for i in range(LIMBS):
        t = a[i] + jnp.uint32(MASK + 1) - mod_limbs[i] - borrow
        subbed.append(t & MASK)
        borrow = 1 - (t >> LIMB_BITS)
    ge = borrow == 0
    return jnp.where(ge[None], jnp.stack(subbed), a)


def f_is_zero(a: jnp.ndarray) -> jnp.ndarray:
    """Lane mask: a == 0 (mod p), for R*/intermediate inputs."""
    return jnp.all(f_canon(f_reduce(a)) == 0, axis=0)


def f_eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(f_canon(a) == f_canon(b), axis=0)


#: p - 2 exponent bits, MSB first
_INV_BITS = tuple(int(b) for b in bin(P - 2)[2:].zfill(256))


def f_inv(a: jnp.ndarray, *, unrolled: bool = False) -> jnp.ndarray:
    """a^(p-2) (Fermat); maps 0 -> 0, which the group layer masks via
    infinity flags.

    Two spellings of the same exponentiation: the default ROLLED
    square-and-multiply (``fori_loop`` over a constant bits array —
    an unrolled chain measured 90 s of XLA compile per lane bucket)
    for the XLA path, and the standard secp256k1 addition chain (258
    squarings in ``fori_loop`` runs + 14 inline multiplies, no captured
    constant array) for Pallas kernel bodies, which may not close over
    array constants.
    """
    if unrolled:
        def sqn(x, n):
            # rolled: 258 unrolled squarings were most of the kernel's
            # Mosaic compile time and code size
            return jax.lax.fori_loop(0, n, lambda _, v: f_sqr(v), x)

        x2 = f_mul(f_sqr(a), a)
        x3 = f_mul(f_sqr(x2), a)
        x6 = f_mul(sqn(x3, 3), x3)
        x9 = f_mul(sqn(x6, 3), x3)
        x11 = f_mul(sqn(x9, 2), x2)
        x22 = f_mul(sqn(x11, 11), x11)
        x44 = f_mul(sqn(x22, 22), x22)
        x88 = f_mul(sqn(x44, 44), x44)
        x176 = f_mul(sqn(x88, 88), x88)
        x220 = f_mul(sqn(x176, 44), x44)
        x223 = f_mul(sqn(x220, 3), x3)
        t = f_mul(sqn(x223, 23), x22)
        t = f_mul(sqn(t, 5), a)
        t = f_mul(sqn(t, 3), x2)
        return f_mul(sqn(t, 2), a)

    bits = jnp.array(_INV_BITS, dtype=U32)

    def body(k, acc):
        acc = f_sqr(acc)
        bit = jax.lax.dynamic_index_in_dim(bits, k, keepdims=False)
        return jnp.where(bit == 1, f_mul(acc, a), acc)

    one = _const([1] + [0] * (LIMBS - 1), a.shape[1:])
    return jax.lax.fori_loop(0, 256, body, one)


# --- Jacobian group law (branchless, infinity-flagged) ----------------------
# A point is (X, Y, Z, inf): limb stacks plus a lane bool; (x, y) maps
# to (x, y, 1, False).  No on-curve point has Y == 0 (odd prime group
# order), so doubling needs no special case beyond infinity.

def _bool_where(mask, a, b):
    """``jnp.where`` over lane BOOLS as plain logic: Mosaic has no
    select between i1 vectors."""
    return (mask & a) | (~mask & b)


def _pt_where(mask, a, b):
    """Lane-select between two (X, Y, Z, inf) points."""
    m = mask[None]
    return (jnp.where(m, a[0], b[0]), jnp.where(m, a[1], b[1]),
            jnp.where(m, a[2], b[2]), _bool_where(mask, a[3], b[3]))


def jac_double(pt):
    X, Y, Z, inf = pt
    ysq = f_sqr(Y)
    s = f_scale(f_mul(X, ysq), 4)
    m = f_scale(f_sqr(X), 3)
    x3 = f_sub(f_sqr(m), f_scale(s, 2))
    y3 = f_sub(f_mul(m, f_sub(s, x3)), f_scale(f_sqr(ysq), 8))
    z3 = f_scale(f_mul(Y, Z), 2)
    return (x3, y3, z3, inf)


def jac_add(a, b):
    """Generic complete addition: handles either operand at infinity,
    equal operands (falls into doubling) and inverse operands (falls
    into infinity) via lane selects."""
    X1, Y1, Z1, inf1 = a
    X2, Y2, Z2, inf2 = b
    z1z1 = f_sqr(Z1)
    z2z2 = f_sqr(Z2)
    u1 = f_mul(X1, z2z2)
    u2 = f_mul(X2, z1z1)
    s1 = f_mul(f_mul(Y1, z2z2), Z2)
    s2 = f_mul(f_mul(Y2, z1z1), Z1)
    h = f_sub(u2, u1)
    rr = f_sub(s2, s1)
    h_zero = f_is_zero(h)
    r_zero = f_is_zero(rr)
    hh = f_sqr(h)
    hhh = f_mul(hh, h)
    u1hh = f_mul(u1, hh)
    x3 = f_sub(f_sub(f_sqr(rr), hhh), f_scale(u1hh, 2))
    y3 = f_sub(f_mul(rr, f_sub(u1hh, x3)), f_mul(s1, hhh))
    z3 = f_mul(f_mul(Z1, Z2), h)
    added = (x3, y3, z3, jnp.zeros_like(inf1))
    dbl = jac_double(a)
    out = _pt_where(h_zero & r_zero, dbl, added)
    out = (out[0], out[1], out[2], out[3] | (h_zero & ~r_zero))
    out = _pt_where(inf2, a, out)
    return _pt_where(inf1, b, out)


def jac_infinity(lane_shape):
    one = _const([1] + [0] * (LIMBS - 1), lane_shape)
    return (one, one, one, jnp.ones(lane_shape, dtype=bool))


def jac_to_affine(pt, *, unrolled_inv: bool = False):
    """(x, y) canonical affine coordinates; infinity lanes yield
    garbage the caller masks with the returned flag."""
    X, Y, Z, inf = pt
    zi = f_inv(Z, unrolled=unrolled_inv)
    zi2 = f_sqr(zi)
    return (f_canon(f_mul(X, zi2)), f_canon(f_mul(f_mul(Y, zi2), zi)),
            inf)


def _scalar_bit(words: jnp.ndarray, i) -> jnp.ndarray:
    """Bit ``i`` (0 = MSB) of each lane's 256-bit scalar, given as a
    (8, *lanes) stack of big-endian u32 words.  ``i`` may be traced:
    the word is a select over all eight (Mosaic cannot index a value
    by a loop counter)."""
    idx = i >> 5
    w = words[0]
    for j in range(1, 8):
        w = jnp.where(idx == j, words[j], w)
    sh = (31 - (i & 31)).astype(U32)
    return (w >> sh) & 1


# --- ladders -----------------------------------------------------------------

def _ladder_loop(nbits: int, step, lane_shape):
    """``fori_loop`` of ``step(k, point) -> point`` from infinity.  The
    infinity flag rides the loop as u32: Mosaic cannot carry an i1
    vector through ``scf.for``."""
    def body(k, carry):
        x, y, z, inf = step(k, carry[:3] + (carry[3] != 0,))
        return (x, y, z, inf.astype(U32))

    x, y, z, inf = jac_infinity(lane_shape)
    x, y, z, inf = jax.lax.fori_loop(0, nbits, body,
                                     (x, y, z, inf.astype(U32)))
    return (x, y, z, inf != 0)


def shamir_ladder(u1w, u2w, q, nbits: int = 256,
                  unrolled_inv: bool = False):
    """u1*G + u2*Q per lane via the Strauss–Shamir dual ladder: one
    shared doubling chain, per-bit addend selected from
    {inf, G, Q, G+Q}.  ``q`` is (qx, qy) limb stacks.  When
    ``nbits < 256`` only the LOW nbits of the scalars are walked
    (interpret-mode tests)."""
    lane_shape = u1w.shape[1:]
    qx, qy = q
    gx = _const(GX_LIMBS, lane_shape)
    gy = _const(GY_LIMBS, lane_shape)
    one = _const([1] + [0] * (LIMBS - 1), lane_shape)
    no = jnp.zeros(lane_shape, dtype=bool)
    g_pt = (gx, gy, one, no)
    q_pt = (qx, qy, one, no)
    gq_x, gq_y, gq_inf = jac_to_affine(jac_add(g_pt, q_pt),
                                       unrolled_inv=unrolled_inv)

    def body(k, acc):
        i = jnp.int32(256 - nbits) + k
        acc = jac_double(acc)
        b1 = _scalar_bit(u1w, i)
        b2 = _scalar_bit(u2w, i)
        ax = jnp.where(b1[None] == 1,
                       jnp.where(b2[None] == 1, gq_x, gx), qx)
        ay = jnp.where(b1[None] == 1,
                       jnp.where(b2[None] == 1, gq_y, gy), qy)
        a_inf = _bool_where(b1 == 1, (b2 == 1) & gq_inf, b2 == 0)
        return jac_add(acc, (ax, ay, one, a_inf))

    return _ladder_loop(nbits, body, lane_shape)


def point_ladder(kw, p, p_inf=None, nbits: int = 256):
    """k*P per lane: plain double-and-add over ``nbits`` low bits."""
    lane_shape = kw.shape[1:]
    px, py = p
    one = _const([1] + [0] * (LIMBS - 1), lane_shape)
    if p_inf is None:
        p_inf = jnp.zeros(lane_shape, dtype=bool)

    def body(k, acc):
        i = jnp.int32(256 - nbits) + k
        acc = jac_double(acc)
        bit = _scalar_bit(kw, i)
        return jac_add(acc, (px, py, one, p_inf | (bit == 0)))

    return _ladder_loop(nbits, body, lane_shape)


# --- core drain programs (shared by the XLA and Pallas paths) ---------------

def _on_curve(x, y):
    """y^2 == x^3 + 7 per lane (coordinates already < p)."""
    seven = _const([7] + [0] * (LIMBS - 1), x.shape[1:])
    return f_eq(f_sqr(y), f_add(f_mul(f_sqr(x), x), seven))


def verify_core(u1w, u2w, qx, qy, r_limbs, nbits: int = 256,
                unrolled_inv: bool = False):
    """ECDSA acceptance per lane: (u1*G + u2*Q).x mod n == r.

    Scalars are pre-reduced mod n by the host (crypto/batch.py's
    Montgomery-batched s^-1 prep); r is canonical < n.  Off-curve
    points and a point-at-infinity result are False, matching the
    native and pure tiers' never-raise contract.
    """
    ok_curve = _on_curve(qx, qy)
    acc = shamir_ladder(u1w, u2w, (qx, qy), nbits=nbits,
                        unrolled_inv=unrolled_inv)
    x_aff, _, inf = jac_to_affine(acc, unrolled_inv=unrolled_inv)
    # x < p < 2n: one conditional subtract is a full reduction mod n
    x_mod_n = _cond_sub(x_aff, N_LIMBS)
    ok = jnp.all(x_mod_n == r_limbs, axis=0)
    return (ok & ok_curve & ~inf).astype(U32)


def ecdh_core(kw, px, py, nbits: int = 256,
              unrolled_inv: bool = False):
    """Scalar mult per lane: canonical affine (x, y) of k*P plus a
    validity mask (off-curve point or infinity result -> 0).

    One program serves BOTH drain shapes: ECDH (the wavefront round —
    callers read x only) and fixed-base mult (P = G broadcast; callers
    read x||y).  ``jac_to_affine`` computes y regardless, so sharing
    costs nothing and halves the per-process compile count.
    """
    ok_curve = _on_curve(px, py)
    acc = point_ladder(kw, (px, py), nbits=nbits)
    x_aff, y_aff, inf = jac_to_affine(acc, unrolled_inv=unrolled_inv)
    ok = ok_curve & ~inf
    zero = jnp.zeros_like(x_aff)
    return (jnp.where(ok[None], x_aff, zero),
            jnp.where(ok[None], y_aff, zero), ok.astype(U32))


# --- XLA path (CPU CI + Mosaic-less hosts) ----------------------------------

@functools.partial(jax.jit, static_argnames=("nbits",))
def xla_verify(u1w, u2w, qx, qy, r_limbs, nbits: int = 256):
    return verify_core(u1w, u2w, qx, qy, r_limbs, nbits=nbits)


@functools.partial(jax.jit, static_argnames=("nbits",))
def xla_ecdh(kw, px, py, nbits: int = 256):
    return ecdh_core(kw, px, py, nbits=nbits)


# --- Pallas kernels ----------------------------------------------------------
# Lanes live as (tiles, 8, 128) VMEM blocks (the sha512_pallas tile
# shape); each grid step runs the full ladder for one tile.  The kernel
# bodies just load refs and call the same core functions the XLA path
# jits, so interpret-mode parity IS kernel-logic parity.

def _verify_kernel(u1_ref, u2_ref, qx_ref, qy_ref, r_ref, ok_ref,
                   *, nbits: int):
    ok = verify_core(u1_ref[0], u2_ref[0], qx_ref[0], qy_ref[0],
                     r_ref[0], nbits=nbits, unrolled_inv=True)
    ok_ref[0] = ok


def _ecdh_kernel(k_ref, px_ref, py_ref, x_ref, y_ref, ok_ref,
                 *, nbits: int):
    x, y, ok = ecdh_core(k_ref[0], px_ref[0], py_ref[0], nbits=nbits,
                         unrolled_inv=True)
    x_ref[0] = x
    y_ref[0] = y
    ok_ref[0] = ok


def _tile_specs(rows: list[int]):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return [pl.BlockSpec((1, r, LANE_ROWS, LANE_COLS),
                         lambda t: (t, 0, 0, 0),
                         memory_space=pltpu.VMEM) for r in rows]


@functools.partial(jax.jit,
                   static_argnames=("nbits", "interpret"))
def pallas_verify(u1w, u2w, qx, qy, r_limbs, nbits: int = 256,
                  interpret: bool = False):
    """Batch ECDSA verify; lane arrays are (rows, T, 8, 128)-shaped
    (limb/word stack leading, tiles next).  Returns ok (T, 8, 128)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    tiles = u1w.shape[1]
    args = [jnp.transpose(a, (1, 0, 2, 3))
            for a in (u1w, u2w, qx, qy, r_limbs)]
    kernel = functools.partial(_verify_kernel, nbits=nbits)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (tiles, LANE_ROWS, LANE_COLS), U32),
        grid=(tiles,),
        in_specs=_tile_specs([8, 8, LIMBS, LIMBS, LIMBS]),
        out_specs=pl.BlockSpec((1, LANE_ROWS, LANE_COLS),
                               lambda t: (t, 0, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(*args)
    return out


@functools.partial(jax.jit,
                   static_argnames=("nbits", "interpret"))
def pallas_ecdh(kw, px, py, nbits: int = 256, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    tiles = kw.shape[1]
    args = [jnp.transpose(a, (1, 0, 2, 3)) for a in (kw, px, py)]
    kernel = functools.partial(_ecdh_kernel, nbits=nbits)
    coord = pl.BlockSpec((1, LIMBS, LANE_ROWS, LANE_COLS),
                         lambda t: (t, 0, 0, 0),
                         memory_space=pltpu.VMEM)
    x, y, ok = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((tiles, LIMBS, LANE_ROWS, LANE_COLS),
                                 U32),
            jax.ShapeDtypeStruct((tiles, LIMBS, LANE_ROWS, LANE_COLS),
                                 U32),
            jax.ShapeDtypeStruct((tiles, LANE_ROWS, LANE_COLS), U32),
        ),
        grid=(tiles,),
        in_specs=_tile_specs([8, LIMBS, LIMBS]),
        out_specs=(
            coord, coord,
            pl.BlockSpec((1, LANE_ROWS, LANE_COLS), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
        ),
        interpret=interpret,
    )(*args)
    return (jnp.transpose(x, (1, 0, 2, 3)),
            jnp.transpose(y, (1, 0, 2, 3)), ok)


# --- host packing helpers (numpy, exact) ------------------------------------

_LIMB_W = (1 << np.arange(LIMB_BITS, dtype=np.uint32)).astype(np.uint32)


def bytes_to_limbs(buf: bytes, n: int) -> np.ndarray:
    """n 32-byte big-endian field elements -> (LIMBS, n) u32 stack."""
    raw = np.frombuffer(buf, dtype=np.uint8).reshape(n, 32)
    bits = np.unpackbits(raw[:, ::-1], axis=1,
                         bitorder="little")        # (n, 256) LSB-first
    bits = np.concatenate(
        [bits, np.zeros((n, LIMBS * LIMB_BITS - 256), dtype=np.uint8)],
        axis=1).reshape(n, LIMBS, LIMB_BITS)
    limbs = (bits.astype(np.uint32) * _LIMB_W).sum(axis=2,
                                                   dtype=np.uint32)
    return np.ascontiguousarray(limbs.T)


def limbs_to_bytes(limbs: np.ndarray) -> list[bytes]:
    """Canonical (LIMBS, n) u32 stack -> n 32-byte big-endian values."""
    n = limbs.shape[1]
    bits = ((limbs.T.astype(np.uint32)[:, :, None]
             >> np.arange(LIMB_BITS, dtype=np.uint32)) & 1)
    bits = bits.reshape(n, LIMBS * LIMB_BITS)[:, :256].astype(np.uint8)
    raw = np.packbits(bits, axis=1, bitorder="little")[:, ::-1]
    return [raw[i].tobytes() for i in range(n)]


def bytes_to_words(buf: bytes, n: int) -> np.ndarray:
    """n 32-byte big-endian scalars -> (8, n) u32 big-endian words."""
    w = np.frombuffer(buf, dtype=">u4").reshape(n, 8).astype(np.uint32)
    return np.ascontiguousarray(w.T)


def pad_lanes(arr: np.ndarray, lanes: int) -> np.ndarray:
    """Pad the trailing lane axis to ``lanes`` by repeating lane 0
    (valid data: padded lanes must not take abnormal code paths)."""
    n = arr.shape[-1]
    if n == lanes:
        return arr
    pad = np.repeat(arr[..., :1], lanes - n, axis=-1)
    return np.concatenate([arr, pad], axis=-1)


def bucket_for(n: int) -> int:
    """Smallest lane bucket holding ``n`` (largest bucket caps the
    call; bigger drains chunk into several calls)."""
    for b in BUCKETS:
        if n <= b:
            return b
    return BUCKETS[-1]


# device-telemetry catalog: the jitted programs above are launched
# (and blocked on) by crypto/tpu.TpuSecp's lane drains, which record
# per-launch attribution; the declarations live with the kernels
from ..observability.devicetelemetry import (SECP_ECDH_FLOPS,
                                             SECP_VERIFY_FLOPS,
                                             register_program)

register_program("secp_verify", flops_per_item=SECP_VERIFY_FLOPS,
                 module="ops/secp256k1_pallas.py",
                 jit_names=("pallas_verify", "xla_verify"))
register_program("secp_ecdh", flops_per_item=SECP_ECDH_FLOPS,
                 module="ops/secp256k1_pallas.py",
                 jit_names=("pallas_ecdh", "xla_ecdh"))
