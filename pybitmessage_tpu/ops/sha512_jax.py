"""Batched SHA-512 on TPU in uint32-pair arithmetic (pure JAX / XLA).

The PoW trial is ``SHA512(SHA512(nonce(8B) || initialHash(64B)))`` and
only the first 8 output bytes matter (reference:
src/bitmsghash/bitmsghash.cpp:54-68, src/proofofwork.py:104-107).  The
72-byte message fits a single 1024-bit SHA-512 block, and the second
pass over the 64-byte digest fits another, so one trial is exactly two
80-round compressions.  Both are implemented over a rolling 16-word
message-schedule window carried through ``lax.fori_loop``, every word a
(hi, lo) uint32 pair vectorized over an arbitrary batch of lanes.

FIPS 180-4 constants; no reference code involved.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .u64 import add64, add64_many, rotr64, shr64, U32

# --- FIPS 180-4 SHA-512 constants ------------------------------------------

_K = [
    0x428A2F98D728AE22, 0x7137449123EF65CD, 0xB5C0FBCFEC4D3B2F, 0xE9B5DBA58189DBBC,
    0x3956C25BF348B538, 0x59F111F1B605D019, 0x923F82A4AF194F9B, 0xAB1C5ED5DA6D8118,
    0xD807AA98A3030242, 0x12835B0145706FBE, 0x243185BE4EE4B28C, 0x550C7DC3D5FFB4E2,
    0x72BE5D74F27B896F, 0x80DEB1FE3B1696B1, 0x9BDC06A725C71235, 0xC19BF174CF692694,
    0xE49B69C19EF14AD2, 0xEFBE4786384F25E3, 0x0FC19DC68B8CD5B5, 0x240CA1CC77AC9C65,
    0x2DE92C6F592B0275, 0x4A7484AA6EA6E483, 0x5CB0A9DCBD41FBD4, 0x76F988DA831153B5,
    0x983E5152EE66DFAB, 0xA831C66D2DB43210, 0xB00327C898FB213F, 0xBF597FC7BEEF0EE4,
    0xC6E00BF33DA88FC2, 0xD5A79147930AA725, 0x06CA6351E003826F, 0x142929670A0E6E70,
    0x27B70A8546D22FFC, 0x2E1B21385C26C926, 0x4D2C6DFC5AC42AED, 0x53380D139D95B3DF,
    0x650A73548BAF63DE, 0x766A0ABB3C77B2A8, 0x81C2C92E47EDAEE6, 0x92722C851482353B,
    0xA2BFE8A14CF10364, 0xA81A664BBC423001, 0xC24B8B70D0F89791, 0xC76C51A30654BE30,
    0xD192E819D6EF5218, 0xD69906245565A910, 0xF40E35855771202A, 0x106AA07032BBD1B8,
    0x19A4C116B8D2D0C8, 0x1E376C085141AB53, 0x2748774CDF8EEB99, 0x34B0BCB5E19B48A8,
    0x391C0CB3C5C95A63, 0x4ED8AA4AE3418ACB, 0x5B9CCA4F7763E373, 0x682E6FF3D6B2B8A3,
    0x748F82EE5DEFB2FC, 0x78A5636F43172F60, 0x84C87814A1F0AB72, 0x8CC702081A6439EC,
    0x90BEFFFA23631E28, 0xA4506CEBDE82BDE9, 0xBEF9A3F7B2C67915, 0xC67178F2E372532B,
    0xCA273ECEEA26619C, 0xD186B8C721C0C207, 0xEADA7DD6CDE0EB1E, 0xF57D4F7FEE6ED178,
    0x06F067AA72176FBA, 0x0A637DC5A2C898A6, 0x113F9804BEF90DAE, 0x1B710B35131C471B,
    0x28DB77F523047D84, 0x32CAAB7B40C72493, 0x3C9EBE0A15C9BEBC, 0x431D67C49C100D4C,
    0x4CC5D4BECB3E42B6, 0x597F299CFC657E2A, 0x5FCB6FAB3AD6FAEC, 0x6C44198C4A475817,
]

_H0 = [
    0x6A09E667F3BCC908, 0xBB67AE8584CAA73B, 0x3C6EF372FE94F82B, 0xA54FF53A5F1D36F1,
    0x510E527FADE682D1, 0x9B05688C2B3E6C1F, 0x1F83D9ABFB41BD6B, 0x5BE0CD19137E2179,
]

# Constant tables as NUMPY arrays: jnp constants at module scope would
# initialize the accelerator backend for any process that merely
# imports the package (and grab the chip: one process per chip), and
# jnp constants created lazily inside a trace become tracers that must
# not be cached across traces.  numpy values embed as XLA constants at
# every trace with neither problem.
import numpy as _np


def _k_tables():
    # reshaped (5, 16): each 16-round chunk does one dynamic row lookup
    # instead of 80 scalar gathers
    k_hi = _np.array([k >> 32 for k in _K], dtype=_np.uint32)
    k_lo = _np.array([k & 0xFFFFFFFF for k in _K], dtype=_np.uint32)
    return k_hi.reshape(5, 16), k_lo.reshape(5, 16)


def _h0_pairs():
    hi = tuple(_np.uint32(h >> 32) for h in _H0)
    lo = tuple(_np.uint32(h & 0xFFFFFFFF) for h in _H0)
    return hi, lo


def _big_sigma0(x):
    a = rotr64(x, 28)
    b = rotr64(x, 34)
    c = rotr64(x, 39)
    return a[0] ^ b[0] ^ c[0], a[1] ^ b[1] ^ c[1]


def _big_sigma1(x):
    a = rotr64(x, 14)
    b = rotr64(x, 18)
    c = rotr64(x, 41)
    return a[0] ^ b[0] ^ c[0], a[1] ^ b[1] ^ c[1]


def _small_sigma0(x):
    a = rotr64(x, 1)
    b = rotr64(x, 8)
    c = shr64(x, 7)
    return a[0] ^ b[0] ^ c[0], a[1] ^ b[1] ^ c[1]


def _small_sigma1(x):
    a = rotr64(x, 19)
    b = rotr64(x, 61)
    c = shr64(x, 6)
    return a[0] ^ b[0] ^ c[0], a[1] ^ b[1] ^ c[1]


def sha512_block(w_hi, w_lo):
    """One SHA-512 compression over a single padded block.

    ``w_hi``/``w_lo``: arrays of shape (16, ...) — the 16 message words
    (hi/lo halves), batched over trailing dimensions.  Returns the eight
    output words as two (8, ...) arrays.

    Structure: ``fori_loop`` over 5 chunks of 16 statically-unrolled
    rounds.  Within a chunk the message-schedule window rotation is pure
    Python-list renaming — no dynamic gathers/scatters — which is what
    lets XLA keep the whole round state in vector registers (3x the
    throughput of a per-round loop with a dynamically indexed window,
    at ~1/5 the compile cost of fully unrolling all 80 rounds).
    """
    batch_shape = w_hi.shape[1:]
    k2_hi, k2_lo = _k_tables()
    h0_hi, h0_lo = _h0_pairs()

    def bc(x):
        return jnp.broadcast_to(x, batch_shape) if batch_shape else x

    def chunk_body(k, carry):
        a, b, c, d, e, f, g, h = carry[:8]
        w = [(carry[8][i], carry[9][i]) for i in range(16)]
        k_hi = jax.lax.dynamic_index_in_dim(k2_hi, k, keepdims=False)
        k_lo = jax.lax.dynamic_index_in_dim(k2_lo, k, keepdims=False)
        for j in range(16):
            wt = w[j]
            kt = (k_hi[j], k_lo[j])
            ch = ((e[0] & f[0]) ^ (~e[0] & g[0]),
                  (e[1] & f[1]) ^ (~e[1] & g[1]))
            maj = ((a[0] & b[0]) ^ (a[0] & c[0]) ^ (b[0] & c[0]),
                   (a[1] & b[1]) ^ (a[1] & c[1]) ^ (b[1] & c[1]))
            t1 = add64_many(h, _big_sigma1(e), ch, kt, wt)
            t2 = add64(_big_sigma0(a), maj)
            # extend the window in place: prepares word t+16 (the last
            # chunk's extension is dead work XLA can't drop, ~6% waste,
            # the price of a static rotation)
            w[j] = add64_many(
                wt, _small_sigma0(w[(j + 1) % 16]),
                w[(j + 9) % 16], _small_sigma1(w[(j + 14) % 16]))
            h, g, f, e = g, f, e, add64(d, t1)
            d, c, b, a = c, b, a, add64(t1, t2)
        wh = jnp.stack([x[0] for x in w])
        wl = jnp.stack([x[1] for x in w])
        return (a, b, c, d, e, f, g, h, wh, wl)

    state = tuple((bc(h0_hi[i]), bc(h0_lo[i])) for i in range(8))
    carry = (*state, w_hi, w_lo)
    carry = jax.lax.fori_loop(0, 5, chunk_body, carry)
    final = carry[:8]

    out = tuple(add64((h0_hi[i], h0_lo[i]), final[i]) for i in range(8))
    out_hi = jnp.stack([o[0] for o in out])
    out_lo = jnp.stack([o[1] for o in out])
    return out_hi, out_lo


def initial_hash_words(initial_hash: bytes):
    """Split the 64-byte initial hash into 8 big-endian u64 (hi, lo) arrays."""
    assert len(initial_hash) == 64
    words = [int.from_bytes(initial_hash[i:i + 8], "big") for i in range(0, 64, 8)]
    hi = jnp.array([w >> 32 for w in words], dtype=U32)
    lo = jnp.array([w & 0xFFFFFFFF for w in words], dtype=U32)
    return hi, lo


def double_sha512_trial(nonce_hi, nonce_lo, ih_hi, ih_lo):
    """PoW trial value for a batch of nonces against one initial hash.

    ``nonce_hi``/``nonce_lo``: (N,) uint32 — the candidate nonces.
    ``ih_hi``/``ih_lo``: (8,) uint32 — the object's initial hash words.
    Returns (value_hi, value_lo): the first 8 bytes of
    SHA512(SHA512(nonce || initialHash)) as a big-endian u64 pair, shape (N,).
    """
    n = nonce_hi.shape
    zeros = jnp.zeros(n, dtype=U32)

    def bc(scalar):
        return jnp.broadcast_to(scalar, n)

    # Block 1: 72 bytes of message + padding. 72 B = 576 bits.
    w_hi = [nonce_hi] + [bc(ih_hi[i]) for i in range(8)]
    w_lo = [nonce_lo] + [bc(ih_lo[i]) for i in range(8)]
    w_hi.append(bc(jnp.uint32(0x80000000)))  # 0x80 pad byte
    w_lo.append(zeros)
    for _ in range(5):                       # W[10..14] zero
        w_hi.append(zeros)
        w_lo.append(zeros)
    w_hi.append(zeros)                       # W[15] = bit length 576
    w_lo.append(bc(jnp.uint32(576)))
    h1_hi, h1_lo = sha512_block(jnp.stack(w_hi), jnp.stack(w_lo))

    # Block 2: the 64-byte digest + padding. 512 bits.
    w_hi = [h1_hi[i] for i in range(8)]
    w_lo = [h1_lo[i] for i in range(8)]
    w_hi.append(bc(jnp.uint32(0x80000000)))
    w_lo.append(zeros)
    for _ in range(6):                       # W[9..14] zero
        w_hi.append(zeros)
        w_lo.append(zeros)
    w_hi.append(zeros)                       # W[15] = 512
    w_lo.append(bc(jnp.uint32(512)))
    h2_hi, h2_lo = sha512_block(jnp.stack(w_hi), jnp.stack(w_lo))

    return h2_hi[0], h2_lo[0]


#: production SHA-512 kernel variant.  "windowed" (the fori_loop kernel
#: below) is the default: the fully-unrolled variant emits a ~3200-op
#: straight-line graph that the TPU toolchain takes prohibitively long
#: to compile (>9 min observed vs ~7 s for windowed), which no runtime
#: advantage can amortize for a daemon that compiles at startup.
DEFAULT_VARIANT = "windowed"


def trial_values(base_hi, base_lo, ih_hi, ih_lo, lanes: int,
                 variant: str = DEFAULT_VARIANT):
    """Trial values for nonces base .. base+lanes-1 (u64 pair base).

    ``variant``: "windowed" (the fori_loop kernel here — production
    default, see DEFAULT_VARIANT) or "unrolled" (sha512_unrolled —
    static schedule; faster per-step in interpret/CPU tests but its
    TPU compile time is prohibitive).
    """
    lane = jax.lax.broadcasted_iota(U32, (lanes, 1), 0).reshape(lanes)
    lo = base_lo + lane
    carry = (lo < base_lo).astype(U32)
    hi = jnp.broadcast_to(base_hi, (lanes,)) + carry
    if variant == "unrolled":
        from .sha512_unrolled import double_sha512_trial_unrolled
        return double_sha512_trial_unrolled(hi, lo, ih_hi, ih_lo), (hi, lo)
    return double_sha512_trial(hi, lo, ih_hi, ih_lo), (hi, lo)
