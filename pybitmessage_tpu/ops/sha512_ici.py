"""Pallas TPU kernel: ONE object searched by several chips as one
program, the first hit stopping the others over ICI.

``ops/sha512_pallas.pallas_search`` on several chips is a launch a
chip, and nothing can stop a launch on another chip: a chip whose peer
has found the nonce searches on to its OWN hit or its launch's end, a
third of ``pod4_single_send``'s window (PERF.md section 6, PR 49).
Here the chips run one ``shard_map`` program, each the single-object
search (``_search_step``, imported unchanged) over its own nonce range,
with a stop flag between them:

* every grid step first reads a semaphore (``stop``); the chip whose
  step hits signals it on every other chip, so a loser leaves its grid
  at the next step it begins (0.28 ms a step of 128 x 128 x 5 trials);
* a chip that has left (by its own hit, by the flag or at its grid's
  end) writes into its output row how many steps it ran and why, tells
  every other chip that it is through (``done``, a semaphore a sender)
  and waits until every other has said so, so no chip is signalled
  after it has left the kernel;
* the semaphores are left as they were found, whatever order the
  signals come in: a winner's ``done`` counts 2 and a loser's 1, so a
  chip knows from each peer's ``done`` whether a ``stop`` of that peer
  is still to be taken off, and waits for it;
* the barrier semaphore of the kernel's ``collective_id`` holds every
  chip at its first step until all are inside this launch: no signal
  reaches a chip still inside the launch before.

Two chips that hit in the same step both report; the host takes one
(``pow/pipeline.py``) and re-verifies it as every nonce.

The operands of a launch are ONE array, a row a chip (the object's
initial-hash words, that chip's base, the target), so a launch costs
the host one transfer a chip; the rows that come back are gathered on
every chip, so the host reads one chip.

Like the entries of ``sha512_pallas.py`` this one is a
``persisted_jit`` (``core/programcache.py``), one a set of devices:
its lowered program, for as many devices as it spans, is exported at a
machine's first launch and loaded by every later start.  The TPU
interpreter has no rule for ``semaphore_read`` on the ``cpu`` backend
(jax 0.9.0), so the kernel's flag is proven on the chip
(``chip_smoke.py``, ``tools/lone_lanes_bench.py --ici``) and the host's
lay-out around it on the CPU, with the XLA equivalent that keeps this
module's output contract (``pow/pipeline._ici_search_xla``, what
``impl="xla"`` launches; ``tests/test_pow_lone_ici.py``).
"""

from __future__ import annotations

import functools
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.programcache import persisted_jit
from ..observability.devicetelemetry import (POW_FLOPS_PER_HASH,
                                             register_program)
from ..parallel.mesh import make_mesh
from .sha512_pallas import (DEFAULT_CHUNKS, DEFAULT_ROWS, DEFAULT_UNROLL,
                            _search_step)
from .u64 import U32

#: the files the program is traced from (the key of its persisted
#: program holds their digest): this one and the three it draws on
_SOURCES = tuple(str(Path(__file__).with_name(name)) for name in (
    "sha512_ici.py", "sha512_pallas.py", "sha512_jax.py", "u64.py"))

AXIS = "nonce"
#: words of a chip's operand row: eight initial-hash words as (hi, lo),
#: the chip's base (hi, lo), the target (hi, lo)
OPERAND_WORDS = 20
_BASE, _TARGET = 16, 18
#: words of a chip's output row: ``[hit_step + 1 (0: no hit), nonce_hi,
#: nonce_lo, steps run, why it left, 0, 0, 0]``
ROW_WORDS = 8
HIT, NONCE_HI, NONCE_LO, STEPS, WHY = range(5)
#: why a chip left its grid: its own step hit; it read the flag another
#: chip had raised; it ran every step of the launch
OWN_HIT, CANCELLED, RAN_OUT = 1, 2, 3


def _ici_kernel(op_ref, out_ref, state_ref, stop_sem, done_sem, *,
                rows: int, unroll: int, lanes: int):
    step = pl.program_id(0)
    me = jax.lax.axis_index(AXIS)
    #: the other chips, round the mesh from the next one on: whom a
    #: signal goes to, and ``done_sem[slot]`` on ``peers[slot]`` is this
    #: chip's
    peers = [(jax.lax.rem(me + off, lanes),) for off in range(1, lanes)]

    @pl.when(step == 0)
    def _enter():
        state_ref[0] = jnp.int32(0)         # why it left (0: searching)
        state_ref[1] = jnp.int32(0)         # steps run
        for w in range(ROW_WORDS):
            out_ref[0, w] = jnp.uint32(0)
        barrier = pltpu.get_barrier_semaphore()
        for peer in peers:
            pltpu.semaphore_signal(barrier, 1, device_id=peer)
        pltpu.semaphore_wait(barrier, lanes - 1)

    @pl.when((state_ref[0] == 0) & (pltpu.semaphore_read(stop_sem) > 0))
    def _cancelled():
        state_ref[0] = jnp.int32(CANCELLED)

    @pl.when(state_ref[0] == 0)
    def _search():
        hit, n_hi, n_lo = _search_step(
            lambda i: (op_ref[0, 2 * i], op_ref[0, 2 * i + 1]),
            op_ref[0, _BASE], op_ref[0, _BASE + 1],
            op_ref[0, _TARGET], op_ref[0, _TARGET + 1],
            step, rows * unroll)
        state_ref[1] = step + 1

        @pl.when(hit == 1)
        def _won():
            state_ref[0] = jnp.int32(OWN_HIT)
            out_ref[0, HIT] = (step + 1).astype(U32)
            out_ref[0, NONCE_HI] = n_hi
            out_ref[0, NONCE_LO] = n_lo
            for peer in peers:
                pltpu.semaphore_signal(stop_sem, 1, device_id=peer)

    @pl.when(step == pl.num_programs(0) - 1)
    def _leave():
        why = jnp.where(state_ref[0] == 0, jnp.int32(RAN_OUT),
                        state_ref[0])
        out_ref[0, STEPS] = state_ref[1].astype(U32)
        out_ref[0, WHY] = why.astype(U32)
        through = jnp.where(why == OWN_HIT, jnp.int32(2), jnp.int32(1))
        for slot, peer in enumerate(peers):
            pltpu.semaphore_signal(done_sem.at[slot], through,
                                   device_id=peer)
        for slot in range(lanes - 1):
            pltpu.semaphore_wait(done_sem.at[slot], 1)

            @pl.when(pltpu.semaphore_read(done_sem.at[slot]) > 0)
            def _a_winner_s(slot=slot):
                pltpu.semaphore_wait(done_sem.at[slot], 1)
                pltpu.semaphore_wait(stop_sem, 1)


def _search(operands, *, rows: int, chunks: int, unroll: int, lanes: int,
            interpret: bool):
    """One chip's part under the ``shard_map``: its operand row in, the
    rows of every chip out."""
    kernel = functools.partial(_ici_kernel, rows=rows, unroll=unroll,
                               lanes=lanes)
    # the device op is named after this, as the other kernels' are after
    # their entries: ``%ici_search.1`` in a trace
    row = pl.pallas_call(
        kernel, name="ici_search",
        out_shape=jax.ShapeDtypeStruct((1, ROW_WORDS), U32),
        grid=(chunks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        scratch_shapes=[pltpu.SMEM((2,), jnp.int32),
                        pltpu.SemaphoreType.REGULAR,
                        pltpu.SemaphoreType.REGULAR((lanes - 1,))],
        compiler_params=pltpu.CompilerParams(
            collective_id=0, has_side_effects=True,
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(operands)
    return jax.lax.all_gather(row, AXIS, tiled=True)


@functools.lru_cache(maxsize=None)
def _entry(devices: tuple):
    """The persisted entry over ``devices``: a program is made for a
    mesh, so there is one a set of devices a process places a lone
    object over (one, in a node)."""
    mesh = make_mesh(devices=devices, axis=AXIS)
    lanes = len(devices)

    @persisted_jit(sources=_SOURCES,
                   static_argnames=("rows", "chunks", "unroll",
                                    "interpret"),
                   shardings=((NamedSharding(mesh, P(AXIS, None)),),
                              NamedSharding(mesh, P())))
    def ici_search(operands, rows: int = DEFAULT_ROWS,
                   chunks: int = DEFAULT_CHUNKS,
                   unroll: int = DEFAULT_UNROLL, interpret: bool = False):
        return jax.shard_map(
            functools.partial(_search, rows=rows, chunks=chunks,
                              unroll=unroll, lanes=lanes,
                              interpret=interpret),
            mesh=mesh, in_specs=P(AXIS, None), out_specs=P(),
            check_vma=False)(operands)

    return ici_search


#: held while a launch is handed to the devices: two threads that
#: launched at once could reach the devices in different orders, and
#: each launch would wait at its barrier for a chip the other holds
_DISPATCH = threading.Lock()


def ici_search(operands, devices, rows: int = DEFAULT_ROWS,
               chunks: int = DEFAULT_CHUNKS, unroll: int = DEFAULT_UNROLL,
               interpret: bool = False):
    """Search one object on ``devices`` at once, first hit stops all.

    ``operands``: (lanes, :data:`OPERAND_WORDS`) uint32, a row a device
    in the order of ``devices``: the object's initial-hash words as
    (hi, lo) pairs, that device's base and the target.  Device ``k``
    searches nonces ``[base_k, base_k + chunks*unroll*rows*128)`` a
    grid step of ``unroll`` (rows, 128) tiles at a time, until its own
    step hits, it reads the flag a peer's hit raised, or the steps are
    run.  Returns (lanes, :data:`ROW_WORDS`) uint32, the same on every
    device: ``[hit_step + 1, nonce_hi, nonce_lo, steps run, why]`` a
    device (``why``: :data:`OWN_HIT`, :data:`CANCELLED`,
    :data:`RAN_OUT`).
    """
    with _DISPATCH:
        return _entry(tuple(devices))(operands, rows=rows, chunks=chunks,
                                      unroll=unroll, interpret=interpret)


register_program("ici_slab", flops_per_item=POW_FLOPS_PER_HASH,
                 module="ops/sha512_ici.py", jit_names=("ici_search",))
