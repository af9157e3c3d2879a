"""The main path's kernels, compiled for a v5e that is described and
not attached (ISSUE 22; `on-chip-measurement` guide, section 2).

Interpret mode cannot see what the chip's compiler refuses: the
secp256k1 kernels passed every interpret-mode test while Mosaic had no
lowering for their scatter-adds, value indexing by a loop counter,
selects between i1 vectors and an i1 loop carry.  These cases compile
the real kernels, at production rows and layout, for ``v5e:2x2`` — no
chip time, nothing runs.

The SHA-512 kernels compile at ``unroll=1`` with a short grid and at
their production shape (since PR 26 the body is one slice in a loop, so
the production shape costs seconds, not minutes); the secp256k1 cases
at production ``nbits`` stay ``slow`` and run by hand before a chip
call.

The topology is described inside a module-scoped fixture, in the test's
own process: only one process may hold the TPU library, and every xdist
worker imports this file.
"""

import os

import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %r" % e)
    # a compile for a described chip cannot be read back from the
    # persistent cache: keep these silent and out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def pod_mesh(topo):
    """(1D nonce mesh, 2D obj x nonce mesh) over the four chips."""
    import numpy as np
    from jax.sharding import Mesh
    devs = np.array(topo.devices)
    return Mesh(devs, ("nonce",)), Mesh(devs.reshape(2, 2),
                                        ("obj", "nonce"))


def _u32(shape, sharding):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


UNROLLS = [pytest.param(False, id="unroll1"),
           pytest.param(True, id="production-unroll")]


@pytest.mark.parametrize("production", UNROLLS)
def test_pallas_search_compiles(one_chip, production):
    from pybitmessage_tpu.ops import sha512_pallas as sp
    unroll = sp.DEFAULT_UNROLL if production else 1
    chunks = sp.DEFAULT_CHUNKS if production else 16
    compiled = sp.pallas_search.lower(
        _u32((8, 2), one_chip), _u32((2,), one_chip),
        _u32((2,), one_chip), rows=sp.DEFAULT_ROWS, chunks=chunks,
        unroll=unroll).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("production", UNROLLS)
def test_pallas_batch_search_compiles(one_chip, production):
    """The batch kernel with one grid step an object (a launch short
    enough for its loop to take whole), at one tile a step and at the
    four of the pod's launches."""
    from pybitmessage_tpu.ops import sha512_pallas as sp
    from pybitmessage_tpu.parallel import pow_pallas_sharded as pod
    n = sp.BATCH_OBJS
    unroll = pod.POD_BATCH_UNROLL if production else 1
    chunks = sp.BATCH_CHUNKS if production else 4
    compiled = sp.pallas_batch_search.lower(
        _u32((n, 8, 2), one_chip), _u32((n, 2), one_chip),
        _u32((n, 2), one_chip), rows=sp.DEFAULT_ROWS, chunks=chunks,
        unroll=unroll).compile()
    assert _has_kernel(compiled)


def test_the_shape_the_pipeline_launches_compiles(one_chip):
    """``plan_batch`` hands the batch kernel 1,024 steps of one tile of
    64 rows an object (pow/pipeline.py, ``DEFAULT_BATCH_CHUNKS``;
    ``BATCH_ROWS``), not ``BATCH_CHUNKS``: 16 grid steps of a
    ``lax.while_loop`` over 64 steps, which Mosaic has to take
    (PR 40)."""
    from pybitmessage_tpu.ops import sha512_pallas as sp
    from pybitmessage_tpu.pow.pipeline import DEFAULT_BATCH_CHUNKS
    n = sp.BATCH_OBJS
    assert DEFAULT_BATCH_CHUNKS // sp.BATCH_INNER > 1
    compiled = sp.pallas_batch_search.lower(
        _u32((n, 8, 2), one_chip), _u32((n, 2), one_chip),
        _u32((n, 2), one_chip), rows=sp.BATCH_ROWS,
        chunks=DEFAULT_BATCH_CHUNKS, unroll=sp.BATCH_UNROLL).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("lanes", [2, 4, 8])
def test_the_slab_a_lone_object_s_lanes_launch_compiles(one_chip, lanes):
    """``plan_batch`` hands each lane of a lone object on several chips
    ``pallas_search`` at ``LONE_LANES_CHUNKS / lanes`` steps (64 on the
    four chips of a v5e host; PR 43): a shape of its own to lower."""
    from pybitmessage_tpu.ops import sha512_pallas as sp
    from pybitmessage_tpu.pow.pipeline import plan_batch
    plan = plan_batch([(b"\x00" * 64, 2 ** 64 // 10 ** 7)], lanes=lanes)
    assert (plan.mode, plan.chunks) == ("slab", 256 // lanes)
    compiled = sp.pallas_search.lower(
        _u32((8, 2), one_chip), _u32((2,), one_chip),
        _u32((2,), one_chip), rows=sp.DEFAULT_ROWS, chunks=plan.chunks,
        unroll=sp.DEFAULT_UNROLL).compile()
    assert _has_kernel(compiled)


def test_the_one_program_of_a_lone_object_compiles_for_four_chips(topo):
    """``ici_search`` (ISSUE 49): the slab kernel at its production
    shape as ONE program over the four chips, with the semaphore each
    grid step reads, the remote signals of a hit and of leaving, and
    the barrier of its ``collective_id``; the rows gathered on every
    chip.  What the chip's compiler refuses of those, it refuses
    here."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pybitmessage_tpu.ops import sha512_ici as ici
    from pybitmessage_tpu.ops import sha512_pallas as sp
    from pybitmessage_tpu.pow.pipeline import plan_batch
    plan = plan_batch([(b"", 2 ** 64 // 10 ** 7)], lanes=4,
                      one_program=True)
    assert plan.chunks == sp.DEFAULT_CHUNKS
    entry = ici._entry(tuple(topo.devices))
    mesh = ici.make_mesh(devices=topo.devices, axis=ici.AXIS)
    compiled = entry.lower(
        _u32((4, ici.OPERAND_WORDS),
             NamedSharding(mesh, P(ici.AXIS, None))),
        rows=sp.DEFAULT_ROWS, chunks=plan.chunks,
        unroll=sp.DEFAULT_UNROLL).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text


def test_pallas_search_has_no_larger_shape_on_a_v5e(one_chip):
    """Twice ``DEFAULT_CHUNKS`` is what PR 24's autotuner asked for at
    a node's second single solve: the chip's compiler refuses it (its
    per-step outputs no longer fit SMEM), which is why ``solve`` has
    one shape and nothing may ask for another."""
    from pybitmessage_tpu.ops import sha512_pallas as sp
    with pytest.raises(Exception) as refused:
        sp.pallas_search.lower(
            _u32((8, 2), one_chip), _u32((2,), one_chip),
            _u32((2,), one_chip), rows=sp.DEFAULT_ROWS,
            chunks=2 * sp.DEFAULT_CHUNKS,
            unroll=sp.DEFAULT_UNROLL).compile()
    assert "smem" in str(refused.value).lower()


def test_pallas_packed_search_compiles(one_chip):
    from pybitmessage_tpu.ops import sha512_pallas as sp
    n = sp.BATCH_OBJS
    compiled = sp.pallas_packed_search.lower(
        _u32((n, 8, 2), one_chip), _u32((n, 2), one_chip),
        _u32((n, 2), one_chip), rows=sp.DEFAULT_ROWS, chunks=4, pack=16,
        unroll=1).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("production", UNROLLS)
def test_sharded_search_compiles_for_four_chips(pod_mesh, production):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pybitmessage_tpu.ops import sha512_pallas as sp
    from pybitmessage_tpu.parallel import make_pallas_sharded_search
    mesh, _ = pod_mesh
    rep = NamedSharding(mesh, P())
    fn = make_pallas_sharded_search(
        mesh, rows=sp.DEFAULT_ROWS,
        chunks=sp.DEFAULT_CHUNKS if production else 16,
        unroll=sp.DEFAULT_UNROLL if production else 1)
    compiled = fn.lower(_u32((8, 2), rep), _u32((2,), rep),
                        _u32((2,), rep)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the winner is resolved across the pod by a collective
    assert "all-gather" in text or "all-reduce" in text \
        or "collective-permute" in text


@pytest.mark.parametrize("production", UNROLLS)
def test_sharded_batch_search_compiles_for_four_chips(pod_mesh,
                                                      production):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pybitmessage_tpu.ops import sha512_pallas as sp
    from pybitmessage_tpu.parallel import \
        make_pallas_sharded_batch_search
    from pybitmessage_tpu.parallel import pow_pallas_sharded as pod
    _, mesh = pod_mesh
    n = sp.BATCH_OBJS * mesh.shape["obj"]
    fn = make_pallas_sharded_batch_search(
        mesh, rows=sp.DEFAULT_ROWS,
        chunks=sp.BATCH_CHUNKS if production else 4,
        unroll=pod.POD_BATCH_UNROLL if production else 1)
    compiled = fn.lower(
        _u32((n, 8, 2), NamedSharding(mesh, P("obj", None, None)),),
        _u32((n, 2), NamedSharding(mesh, P("obj", None))),
        _u32((n, 2), NamedSharding(mesh, P("obj", None)))).compile()
    assert _has_kernel(compiled)


NBITS = [pytest.param(8, id="nbits8"),
         pytest.param(256, id="production-nbits",
                      marks=pytest.mark.slow)]


def _secp_args(one_chip, rows):
    from pybitmessage_tpu.ops import secp256k1_pallas as secp
    return [_u32((r, 1, secp.LANE_ROWS, secp.LANE_COLS), one_chip)
            for r in rows]


@pytest.mark.parametrize("nbits", NBITS)
def test_secp_verify_kernel_compiles(one_chip, nbits):
    from pybitmessage_tpu.ops import secp256k1_pallas as secp
    compiled = secp.pallas_verify.lower(
        *_secp_args(one_chip, [8, 8, secp.LIMBS, secp.LIMBS,
                               secp.LIMBS]), nbits=nbits).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("nbits", NBITS)
def test_secp_ecdh_kernel_compiles(one_chip, nbits):
    from pybitmessage_tpu.ops import secp256k1_pallas as secp
    compiled = secp.pallas_ecdh.lower(
        *_secp_args(one_chip, [8, secp.LIMBS, secp.LIMBS]),
        nbits=nbits).compile()
    assert _has_kernel(compiled)


def test_pow_verify_batch_compiles(one_chip):
    from pybitmessage_tpu.ops.pow_search import pow_verify_batch
    b = 64
    compiled = pow_verify_batch.lower(
        _u32((b,), one_chip), _u32((b,), one_chip),
        _u32((8, b), one_chip), _u32((8, b), one_chip),
        _u32((b,), one_chip), _u32((b,), one_chip)).compile()
    assert compiled.memory_analysis() is not None
