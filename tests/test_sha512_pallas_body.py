"""The Pallas kernels' shared double-SHA-512 body, on the CPU.

``_double_sha512_tile`` and ``_search_step`` are plain ``jnp`` code, so
they are evaluated here eagerly -- no ``jit``, no ``pallas_call``, a few
seconds a (8, 128) tile -- and held to ``hashlib``, for both forms of the
initial-hash words the kernels hand them: shape-() scalars (the single
and batch kernels) and tiles (the packed kernel).  The op count is the
guard that the body stays as cheap as it was made: what the VPU issues a
trial is the kernel's whole cost (PERF.md section 5).
"""

import collections
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pybitmessage_tpu.ops import sha512_pallas as sp

ROWS = sp.SLICE_ROWS     # the tile-shaped initial hashes serve every slice
SHAPE = (ROWS, sp.LANE_COLS)
LANES = ROWS * sp.LANE_COLS
U32 = jnp.uint32
#: the low word wraps past 2^32 at lane WRAP of the tile
WRAP = 100
BASE = (7 << 32) | (2 ** 32 - WRAP)
FORMS = ("scalar", "tile")


def _ih_for_row(form: str, row: int) -> bytes:
    """One initial hash for every lane, or one for each row of the tile."""
    tag = b"body" if form == "scalar" else b"body row %d" % row
    return hashlib.sha512(tag).digest()


def _ih_pair(form: str):
    """``ih_pair(i) -> (hi, lo)`` as a kernel would pass it."""
    words = np.array(
        [[int.from_bytes(_ih_for_row(form, r)[j:j + 8], "big")
          for j in range(0, 64, 8)] for r in range(ROWS)], dtype=np.uint64)
    hi, lo = (words >> 32).astype(np.uint32), words.astype(np.uint32)
    if form == "scalar":
        return lambda i: (U32(hi[0, i]), U32(lo[0, i]))
    return lambda i: (
        jnp.broadcast_to(jnp.asarray(hi[:, i])[:, None], SHAPE),
        jnp.broadcast_to(jnp.asarray(lo[:, i])[:, None], SHAPE))


def _expected(form: str, base: int, lanes: int = LANES) -> list[int]:
    """hashlib's trial value of every lane, in lane order."""
    out = []
    for lane in range(lanes):
        nonce = (base + lane) & (2 ** 64 - 1)
        ih = _ih_for_row(form, lane // sp.LANE_COLS % ROWS)
        digest = hashlib.sha512(
            hashlib.sha512(nonce.to_bytes(8, "big") + ih).digest()).digest()
        out.append(int.from_bytes(digest[:8], "big"))
    return out


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("base", [BASE, 0x0123456789ABCDEF],
                         ids=["wraps_2_32", "plain"])
def test_tile_values_equal_hashlib(form, base):
    nonces = base + np.arange(LANES, dtype=np.uint64).reshape(SHAPE)
    v_hi, v_lo = sp._double_sha512_tile(
        _ih_pair(form), jnp.asarray((nonces >> 32).astype(np.uint32)),
        jnp.asarray(nonces.astype(np.uint32)))
    assert v_hi.shape == v_lo.shape == SHAPE
    got = (np.asarray(v_hi).astype(np.uint64) << 32) | np.asarray(v_lo)
    assert got.reshape(-1).tolist() == _expected(form, base)


#: ``_search_step`` hashes its tile a slice at a time: two slices here
SEARCH_ROWS = 2 * sp.SLICE_ROWS
SEARCH_LANES = SEARCH_ROWS * sp.LANE_COLS


def _search(form: str, target: int):
    """``_search_step`` over the tile that starts at ``BASE``, as step 1
    of a slab that starts one tile earlier.  Its loop over the slices
    runs as the Python loop it is without ``jit``."""
    base = BASE - SEARCH_LANES
    with jax.disable_jit():
        hit, n_hi, n_lo = sp._search_step(
            _ih_pair(form), U32(base >> 32), U32(base & 0xFFFFFFFF),
            U32(target >> 32), U32(target & 0xFFFFFFFF), 1, SEARCH_ROWS)
    return int(hit), (int(n_hi) << 32) | int(n_lo)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("admits", ["one_lane", "two_lanes", "none"])
def test_search_step_winner(form, admits):
    values = _expected(form, BASE, SEARCH_LANES)
    by_value = sorted(range(SEARCH_LANES), key=values.__getitem__)
    if admits == "one_lane":
        target, want = values[by_value[0]], by_value[0]
    elif admits == "two_lanes":
        target, want = values[by_value[1]], min(by_value[:2])
    else:
        target, want = values[by_value[0]] - 1, None
    hit, nonce = _search(form, target)
    if want is None:
        assert hit == 0
    else:
        # past the wrap, so the winner's carry into the high word counts
        assert want >= WRAP
        assert (hit, nonce) == (1, BASE + want)


def _vector_ops(form: str) -> collections.Counter:
    """Equations of ``_double_sha512_tile`` whose result is a tile, by
    primitive: what the VPU is asked to issue for one trial."""
    ih = jax.ShapeDtypeStruct((8, 2) + (SHAPE if form == "tile" else ()), U32)
    tile = jax.ShapeDtypeStruct(SHAPE, U32)
    jaxpr = jax.make_jaxpr(
        lambda ih, hi, lo: sp._double_sha512_tile(
            lambda i: (ih[i, 0], ih[i, 1]), hi, lo))(ih, tile, tile)
    return collections.Counter(
        eqn.primitive.name for eqn in jaxpr.jaxpr.eqns
        if any(getattr(v.aval, "shape", None) == SHAPE for v in eqn.outvars))


@pytest.mark.parametrize("form,limit", [("scalar", 20700), ("tile", 21100)])
def test_vector_ops_a_trial(form, limit):
    """20,600 / 21,007 as written.  Before PR 26 the jaxpr read 21,979 /
    22,320 and left out two xors for every unsigned compare (the v5e
    compares signed only: its compiler's final bundles held 23,220).
    This body compares signed and its bias xors are equations, so the
    count is what the chip issues (20,593 in the bundles), and the
    limits stand that much above the 20,500 / 20,900 that ISSUE 26 set
    in the old unit.  devicetelemetry.POW_FLOPS_PER_HASH quotes the
    scalar count."""
    ops = _vector_ops(form)
    assert sum(ops.values()) <= limit, sorted(ops.items())
