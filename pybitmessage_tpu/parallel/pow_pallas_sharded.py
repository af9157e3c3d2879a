"""Pod-sharded PoW built on the production Pallas kernel.

Nothing in the node calls this module any more (ROADMAP D10).  On a
host of several chips everything goes through ``pow/pipeline.py``: a
queue's launch groups are dealt over the chips, an object's own nonce
range on one chip and copies of a straggler's on the chips that have
run out, and since PR 43 an object that is alone has its nonce space
shared out over the driver's lanes (docs/pow_pipeline.md, "One object
on several chips"), where it came to :func:`pallas_sharded_solve`
before.  That and :func:`pallas_sharded_solve_batch`, the 2D (objects
x nonce-range) loop, are left to ``tools/tpu_doctor.py``,
``__graft_entry__.py``, ``bench.py`` and the tests until a
``simplicity`` PR takes them out.

The per-chip slab is the SAME Mosaic kernel the single-chip tier runs
(``ops/sha512_pallas.py``): a ``pl.pallas_call`` per device under
``shard_map``, device *d* searching the contiguous slab
``[base + d*slab, base + (d+1)*slab)`` — the multi-chip generalization
of the reference's per-thread nonce striding
(src/bitmsghash/bitmsghash.cpp:76-125), with the OpenCL host-loop slab
granularity (src/openclpow.py:96-107) scaled to the whole pod.

Early exit happens at two granularities:
- WITHIN a device, the kernel's SMEM found-flag skips remaining grid
  steps after a hit (per-object in the batch kernel);
- ACROSS the pod, each jitted call ends with a tiny ``all_gather`` of
  per-device (hit, nonce) over the mesh axis (rides ICI), and the host
  loop stops dispatching slabs once any device reports a hit.

There is deliberately no per-chunk cross-chip collective here: Mosaic
kernels cannot issue ICI collectives mid-grid, and a slab is ~200 ms of
work, so the worst-case overshoot (one slab's tail on the other chips)
matches the reference OpenCL driver's batch-granular exit.

On hosts without a TPU (the virtual CPU meshes the test suite and the
driver's multi-chip dryrun use), ``impl="xla"`` swaps the per-device
slab for an equivalent ``lax.scan`` over the XLA windowed kernel —
identical partitioning, winner resolution and host loop, so the
sharding logic is fully exercised without Mosaic.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..observability.devicetelemetry import (POW_FLOPS_PER_HASH,
                                             record_launch,
                                             register_program)
from ..ops.sha512_jax import DEFAULT_VARIANT, trial_values
from ..ops.sha512_pallas import (BATCH_CHUNKS, BATCH_OBJS,
                                 LANE_COLS, DEFAULT_CHUNKS,
                                 DEFAULT_ROWS, DEFAULT_UNROLL,
                                 pallas_batch_search, pallas_search)
from ..ops.u64 import U32, add64, le64, mul_u32_const
from ..ops.pow_search import PowInterrupted

_MASK64 = (1 << 64) - 1

register_program("pod_slab", flops_per_item=POW_FLOPS_PER_HASH,
                 module="parallel/pow_pallas_sharded.py")
register_program("pod_batch", flops_per_item=POW_FLOPS_PER_HASH,
                 module="parallel/pow_pallas_sharded.py")

#: per-DEVICE object cap for the unrolled batch kernel — the same
#: 64-object geometry the single-chip ``solve_batch`` compiles and
#: verifies on real hardware (r4: the write-once (B, 3) output row
#: removed the r3 SMEM scaling that capped this at 16).  The host loop
#: groups the batch so each device's local share stays within this.
POD_BATCH_PER_DEVICE = BATCH_OBJS
#: tiles to a step of the pod's batch launches: the four that the
#: single-chip queue had until PR 40 (``sha512_pallas.BATCH_UNROLL`` is
#: 1 since), so a pod launch is the trials it was.  Nothing in the node
#: calls this path (ROADMAP D10)
POD_BATCH_UNROLL = 4


def default_impl() -> str:
    """"pallas" on an accelerator backend, "xla" on host CPU.  A JAX
    that fails to initialise raises into the caller's tier handler."""
    return "pallas" if jax.default_backend() != "cpu" else "xla"


def _xla_slab(ih_words, base, target, *, rows: int, chunks: int,
              variant: str = DEFAULT_VARIANT):
    """XLA stand-in for one device's Pallas slab (same output contract:
    found (chunks,) int32, nonce (chunks, 2) uint32)."""
    lanes = rows * LANE_COLS
    ih_hi, ih_lo = ih_words[:, 0], ih_words[:, 1]
    t = (target[0], target[1])

    def step(carry, _):
        b_hi, b_lo = carry
        (v_hi, v_lo), (c_hi, c_lo) = trial_values(
            b_hi, b_lo, ih_hi, ih_lo, lanes, variant)
        ok = le64((v_hi, v_lo), t)
        idx = jnp.argmax(ok)
        out = (jnp.any(ok).astype(jnp.int32),
               jnp.stack([c_hi[idx], c_lo[idx]]))
        nxt = add64((b_hi, b_lo), (jnp.uint32(0), jnp.uint32(lanes)))
        return nxt, out

    _, (found, nonce) = jax.lax.scan(
        step, (base[0], base[1]), None, length=chunks)
    return found, nonce


def _first_hit(found, nonce):
    """First hit in one device's slab -> (hit, nonce_hi, nonce_lo)."""
    idx = jnp.argmax(found > 0)
    return found[idx] > 0, nonce[idx, 0], nonce[idx, 1]


def _resolve_winner(hit, n_hi, n_lo, axis: str):
    """all_gather per-device results and replicate the first winner.

    Returned PACKED as one (3,) uint32 array [found, nonce_hi,
    nonce_lo]: every separate output array costs a device->host fetch
    per harvest."""
    hits = jax.lax.all_gather(hit, axis)
    nhs = jax.lax.all_gather(n_hi, axis)
    nls = jax.lax.all_gather(n_lo, axis)
    win = jnp.argmax(hits)
    return jnp.stack([jnp.any(hits).astype(U32), nhs[win], nls[win]])


def make_pallas_sharded_search(mesh: Mesh, *, rows: int = DEFAULT_ROWS,
                               chunks: int = DEFAULT_CHUNKS,
                               unroll: int = DEFAULT_UNROLL,
                               axis: str | None = None,
                               impl: str = "pallas",
                               interpret: bool = False,
                               variant: str = DEFAULT_VARIANT):
    """Jitted pod-wide single-object search over ``mesh``.

    ``fn(ih_words (8,2), base (2,), target (2,)) -> (3,) uint32
    [found, nonce_hi, nonce_lo]``, everything replicated; each device
    runs one Pallas slab on its share of the nonce range.
    """
    if axis is None:
        axis = mesh.axis_names[-1]
    slab = rows * LANE_COLS * chunks * unroll

    def body(ih_words, base, target):
        dev = jax.lax.axis_index(axis).astype(U32)
        b_hi, b_lo = add64((base[0], base[1]), mul_u32_const(dev, slab))
        local_base = jnp.stack([b_hi, b_lo])
        if impl == "pallas":
            found, nonce = pallas_search(ih_words, local_base, target,
                                         rows=rows, chunks=chunks,
                                         unroll=unroll,
                                         interpret=interpret)
        else:
            found, nonce = _xla_slab(ih_words, local_base, target,
                                     rows=rows, chunks=chunks * unroll,
                                     variant=variant)
        return _resolve_winner(*_first_hit(found, nonce), axis)

    reps = P()
    fn = shard_map(body, mesh=mesh, in_specs=(reps,) * 3,
                   out_specs=reps, check_vma=False)
    return jax.jit(fn)


def make_pallas_sharded_batch_search(mesh: Mesh, *,
                                     rows: int = DEFAULT_ROWS,
                                     chunks: int = DEFAULT_CHUNKS,
                                     unroll: int = 1,
                                     obj_axis: str | None = None,
                                     nonce_axis: str | None = None,
                                     impl: str = "pallas",
                                     interpret: bool = False,
                                     variant: str = DEFAULT_VARIANT):
    """Jitted pod-wide BATCH search over a 2D (obj x nonce) mesh.

    Objects are data-parallel over ``obj_axis`` (each device holds
    B/obj_size of them); each object's nonce range is partitioned over
    ``nonce_axis``.  One Pallas batch-kernel launch per device covers
    its local (objects x chunks) grid with per-object early exit.
    ``fn(ih_words (B,8,2), bases (B,2), targets (B,2)) -> (B, 3)
    uint32 rows of [found, nonce_hi, nonce_lo]``.
    """
    if obj_axis is None:
        obj_axis = mesh.axis_names[0]
    if nonce_axis is None:
        nonce_axis = mesh.axis_names[-1]
    slab = rows * LANE_COLS * chunks * unroll

    def body(ih_words, bases, targets):
        dev = jax.lax.axis_index(nonce_axis).astype(U32)
        off = mul_u32_const(dev, slab)

        def offset(b):
            h, lo = add64((b[0], b[1]), off)
            return jnp.stack([h, lo])

        local_bases = jax.vmap(offset)(bases)
        if impl == "pallas":
            # write-once (B, 3) rows: [hit_step+1, nonce_hi, nonce_lo]
            out = pallas_batch_search(
                ih_words, local_bases, targets, rows=rows, chunks=chunks,
                unroll=unroll, interpret=interpret)
            hit = (out[:, 0] > 0).astype(jnp.int32)
            step1 = out[:, 0]
            n_hi, n_lo = out[:, 1], out[:, 2]
        else:
            found, nonce = jax.vmap(
                lambda iw, b, t: _xla_slab(iw, b, t, rows=rows,
                                           chunks=chunks * unroll,
                                           variant=variant)
            )(ih_words, local_bases, targets)
            hit, n_hi, n_lo = jax.vmap(_first_hit)(found, nonce)
            # XLA slab reports the hit chunk index the same way
            step1 = jnp.where(hit > 0,
                              jnp.argmax(found > 0, axis=1) + 1,
                              0).astype(U32)
        hits = jax.lax.all_gather(hit, nonce_axis)        # (D, B_local)
        nhs = jax.lax.all_gather(n_hi, nonce_axis)
        nls = jax.lax.all_gather(n_lo, nonce_axis)
        steps = jax.lax.all_gather(step1, nonce_axis)
        win = jnp.argmax(hits, axis=0)
        lane = jnp.arange(hits.shape[1])
        # packed (B_local, 4): one device->host fetch per harvest;
        # column 3 = winner's hit step (trials accounting parity with
        # the single-chip solve_batch)
        return jnp.stack([jnp.any(hits, axis=0).astype(U32),
                          nhs[win, lane], nls[win, lane],
                          steps[win, lane]], axis=-1)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(obj_axis, None, None), P(obj_axis, None),
                  P(obj_axis, None)),
        out_specs=P(obj_axis, None), check_vma=False)
    return jax.jit(fn)


#: jitted-fn cache — re-wrapping shard_map would defeat jit's compile
#: cache and recompile on every solve
_FN_CACHE: dict = {}


def _get_fn(mesh: Mesh, kind: str, rows: int, chunks: int, unroll: int,
            impl: str, interpret: bool, variant: str):
    key = (mesh, kind, rows, chunks, unroll, impl, interpret, variant)
    if key not in _FN_CACHE:
        if kind == "single":
            _FN_CACHE[key] = make_pallas_sharded_search(
                mesh, rows=rows, chunks=chunks, unroll=unroll, impl=impl,
                interpret=interpret, variant=variant)
        else:
            _FN_CACHE[key] = make_pallas_sharded_batch_search(
                mesh, rows=rows, chunks=chunks, unroll=unroll,
                impl=impl, interpret=interpret, variant=variant)
    return _FN_CACHE[key]


def _ih_words_arr(initial_hash: bytes):
    words = [int.from_bytes(initial_hash[i:i + 8], "big")
             for i in range(0, 64, 8)]
    return jnp.array([[w >> 32, w & 0xFFFFFFFF] for w in words], dtype=U32)


def _pair_arr(value: int):
    value &= _MASK64
    return jnp.array([value >> 32, value & 0xFFFFFFFF], dtype=U32)


def pallas_sharded_solve(initial_hash: bytes, target: int, mesh: Mesh, *,
                         start_nonce: int = 0, rows: int = DEFAULT_ROWS,
                         chunks_per_call: int = DEFAULT_CHUNKS,
                         unroll: int = DEFAULT_UNROLL,
                         impl: str | None = None, interpret: bool = False,
                         variant: str = DEFAULT_VARIANT,
                         should_stop: Callable[[], bool] | None = None,
                         progress: Callable[[int], None] | None = None):
    """Pod-wide solve running the production Pallas kernel per chip.

    Same contract as ``ops.solve``: returns
    ``(nonce, trials)`` or raises ``PowInterrupted``.  Double-buffered
    host loop (one pod slab in flight ahead of the harvest) with
    stride ``ndev * rows*128*chunks`` per call.  ``progress(next)``
    checkpoints resumable search state whenever a pod slab harvests
    miss-free (same contract as ``pow.pipeline.solve_batch_pipelined``).
    """
    import time as _time

    import numpy as np

    from ..utils.hashes import double_sha512

    if impl is None:
        impl = default_impl()
    ndev = mesh.devices.size
    nonce_devs = mesh.shape[mesh.axis_names[-1]] if len(mesh.axis_names) > 1 \
        else ndev
    fn = _get_fn(mesh, "single", rows, chunks_per_call, unroll, impl,
                 interpret, variant)
    ih_words = _ih_words_arr(initial_hash)
    target &= _MASK64
    target_arr = _pair_arr(target)
    slab = rows * LANE_COLS * chunks_per_call * unroll
    stride = nonce_devs * slab

    def harvest(out, t0, t1):
        t2 = _time.monotonic()
        found, n_hi, n_lo = np.asarray(out)     # one packed fetch
        t3 = _time.monotonic()
        record_launch("pod_slab",
                      key=(rows, chunks_per_call, unroll, impl, interpret),
                      dispatch_seconds=t1 - t0, wait_seconds=t3 - t2,
                      span=(t0, t3), items=stride,
                      bytes_in=int(ih_words.nbytes) + 16, bytes_out=12,
                      devices=ndev)
        if not found:
            return None
        nonce = (int(n_hi) << 32) | int(n_lo)
        check = double_sha512(nonce.to_bytes(8, "big") + initial_hash)
        if int.from_bytes(check[:8], "big") > target:  # pragma: no cover
            raise ArithmeticError("accelerator returned an invalid nonce")
        return nonce

    base = start_nonce & _MASK64
    trials = 0
    pending = None      # (device_out, end_base, dispatch t0, t1)
    while True:
        if should_stop is not None and should_stop():
            if pending is not None:
                trials += stride
                nonce = harvest(pending[0], pending[2], pending[3])
                if nonce is not None:
                    return nonce, trials
                if progress is not None:
                    progress(pending[1])
            raise PowInterrupted("sharded Pallas PoW interrupted")
        end_base = (base + stride) & _MASK64
        t0 = _time.monotonic()
        out = fn(ih_words, _pair_arr(base), target_arr)
        current = (out, end_base, t0, _time.monotonic())
        base = end_base
        if pending is not None:
            trials += stride
            nonce = harvest(pending[0], pending[2], pending[3])
            if nonce is not None:
                return nonce, trials
            if progress is not None:
                progress(pending[1])
        pending = current


#: always-hit target: every trial value is <= 2^64-1, so pad/done slots
#: hit on their first chunk and the per-object kernel flag then skips
#: the rest of their grid (contrast reference openclpow which has no
#: batch concept at all)
_ALWAYS_HIT = _MASK64


def pallas_sharded_solve_batch(items, mesh: Mesh, *,
                               rows: int = DEFAULT_ROWS,
                               chunks_per_call: int = BATCH_CHUNKS,
                               unroll: int = POD_BATCH_UNROLL,
                               impl: str | None = None,
                               interpret: bool = False,
                               variant: str = DEFAULT_VARIANT,
                               should_stop: Callable[[], bool] | None = None,
                               start_nonces=None, progress=None):
    """Solve ``[(initial_hash, target), ...]`` pod-wide, Pallas per chip.

    2D (obj x nonce) mesh: objects data-parallel, nonce ranges
    partitioned.  Per-object early exit across slabs: once an object
    solves, its target flips to always-hit so its lanes stop after one
    chunk of the next launch, and its trials stop accruing; the batch
    is padded with always-hit dummies (never duplicated real work).
    Defaults mirror the single-chip batch geometry (32 objects x 64
    chunks x 4 tiles per device, ``POD_BATCH_UNROLL`` — pinned to the
    configuration compiled + verified on real hardware, independent of
    the single kernel's unroll knee).  Returns ``[(nonce, trials),
    ...]`` aligned with ``items``.

    Resumable-PoW hooks (resilience/journal.py): ``start_nonces``
    gives one journaled offset per item — each object's device-
    resident range partition starts THERE instead of 0, so a restarted
    pod solve no longer re-searches work a previous process already
    covered.  ``progress(i, next_nonce)`` fires as slabs harvest
    miss-free with the end of item ``i``'s fully-searched range (the
    same checkpoint contract as the single-chip pipeline: every nonce
    in ``[start_nonces[i], next_nonce)`` has been searched without a
    hit).
    """
    import numpy as np

    from ..utils.hashes import double_sha512

    n = len(items)
    if n == 0:
        return []
    if impl is None:
        impl = default_impl()
    starts = list(start_nonces) if start_nonces else [0] * n
    if len(mesh.axis_names) < 2:
        out = []
        for i, (ih, t) in enumerate(items):
            prog = None
            if progress is not None:
                prog = (lambda nxt, _i=i: progress(_i, nxt))
            out.append(pallas_sharded_solve(
                ih, t, mesh, rows=rows,
                chunks_per_call=chunks_per_call,
                unroll=unroll, impl=impl,
                interpret=interpret, variant=variant,
                start_nonce=starts[i], progress=prog,
                should_stop=should_stop))
        return out

    obj_size = mesh.shape[mesh.axis_names[0]]
    nonce_devs = mesh.shape[mesh.axis_names[-1]]
    fn = _get_fn(mesh, "batch", rows, chunks_per_call, unroll, impl,
                 interpret, variant)
    slab = rows * LANE_COLS * chunks_per_call * unroll
    stride = nonce_devs * slab
    # group so each device's local share stays inside the unrolled
    # kernel's SMEM budget; every group pads to the SAME width, so one
    # compiled program serves any batch size
    group_objs = POD_BATCH_PER_DEVICE * obj_size

    results: list = [None] * n
    for start in range(0, n, group_objs):
        group = items[start:start + group_objs]
        # slot -> item of the group (None = pad).  The obj axis shards
        # the slots in contiguous blocks of POD_BATCH_PER_DEVICE, so
        # items are dealt round-robin over the blocks: a batch smaller
        # than the pod's capacity still spreads over every obj-axis
        # device instead of filling the first and padding the rest
        item_at: list = [None] * group_objs
        for j in range(len(group)):
            item_at[(j % obj_size) * POD_BATCH_PER_DEVICE
                    + j // obj_size] = j
        ihs = [b"\x00" * 64 if j is None else group[j][0]
               for j in item_at]
        targets = [_ALWAYS_HIT if j is None else group[j][1] & _MASK64
                   for j in item_at]
        ih_words = jnp.stack([_ih_words_arr(ih) for ih in ihs])
        t_arr = jnp.stack([_pair_arr(t) for t in targets])

        # trials granularity of one reported hit step, per impl: a
        # pallas grid step covers `unroll` tiles, an XLA chunk covers
        # one
        step_trials = rows * LANE_COLS * (
            unroll if impl == "pallas" else 1)
        # journaled resume offsets (ISSUE 4 satellite, closing the
        # ROADMAP known gap): each object's device-resident range
        # partition starts at its checkpoint instead of 0
        bases = [0 if j is None else starts[start + j] & _MASK64
                 for j in item_at]
        trials = [0] * group_objs
        done = [j is None for j in item_at]

        def dispatch():
            """Launch one pod slab for the group's live objects.

            Bases advance optimistically at dispatch so the NEXT slab
            can be issued before this one's flags are read back
            (dispatch-ahead double buffering — host verification and
            bookkeeping overlap device compute, the same pipeline as
            the single-chip solve_batch)."""
            live = [i for i in range(group_objs) if not done[i]]
            b_arr = jnp.stack([_pair_arr(b) for b in bases])
            t0 = _time.monotonic()
            out = fn(ih_words, b_arr, t_arr)
            t1 = _time.monotonic()
            for i in live:
                bases[i] = (bases[i] + stride) & _MASK64
            # per-slab end bases: the checkpoint each live object may
            # report once THIS slab harvests miss-free (bases keeps
            # advancing under dispatch-ahead, so snapshot now)
            return (out, live, {i: bases[i] for i in live},
                    int(b_arr.nbytes), t0, t1)

        def harvest(out_dev, live, end_bases, up_bytes, t0, t1):
            nonlocal t_arr
            t2 = _time.monotonic()
            packed = np.asarray(out_dev)          # the blocking fetch
            t3 = _time.monotonic()
            _metrics.DEVICE_WAIT.observe(t3 - t2)
            record_launch("pod_batch",
                          key=(rows, chunks_per_call, unroll, impl,
                               interpret),
                          dispatch_seconds=t1 - t0, wait_seconds=t3 - t2,
                          span=(t0, t3), items=stride * len(live),
                          bytes_in=up_bytes,
                          bytes_out=int(packed.nbytes),
                          devices=mesh.devices.size)
            found, n_hi, n_lo = packed[:, 0], packed[:, 1], packed[:, 2]
            steps = packed[:, 3]
            for i in live:
                if done[i]:
                    continue
                if found[i]:
                    # parity with single-chip solve_batch: credit the
                    # winning device up to its hit step; the other
                    # devices ran their full slab concurrently
                    trials[i] += (int(steps[i]) * step_trials
                                  + (nonce_devs - 1) * slab)
                    nonce = (int(n_hi[i]) << 32) | int(n_lo[i])
                    check = double_sha512(
                        nonce.to_bytes(8, "big") + ihs[i])
                    if int.from_bytes(check[:8], "big") > targets[i]:
                        raise ArithmeticError(
                            "accelerator returned an invalid nonce")
                    results[start + item_at[i]] = (nonce, trials[i])
                    done[i] = True
                    # flip to always-hit: from the next launch this
                    # object's lanes flag out after their first chunk
                    t_arr = t_arr.at[i].set(
                        jnp.array([0xFFFFFFFF, 0xFFFFFFFF], dtype=U32))
                else:
                    trials[i] += stride
                    if progress is not None:
                        # this object's slab harvested miss-free —
                        # everything below its end base is searched
                        progress(start + item_at[i], end_bases[i])

        import time as _time

        from ..pow import pipeline as _metrics

        pending = None      # (device_out, live_snapshot)
        while not all(done):
            if should_stop is not None and should_stop():
                if pending is not None:
                    # the in-flight pod slab may hold answers — drain
                    # before deciding to abandon the group
                    harvest(*pending)
                    pending = None
                if all(done):
                    break   # the drained slab finished the group
                raise PowInterrupted(
                    "sharded batched Pallas PoW interrupted")
            current = dispatch()
            _metrics.PIPELINE_DEPTH.set(2 if pending else 1)
            if pending is not None:
                _metrics.DISPATCH_AHEAD.observe(2)
                harvest(*pending)
            pending = current
        # loop exits with every object done; a still-in-flight slab is
        # pure speculation for a finished group (targets all flipped
        # always-hit next launch) — abandoned unfetched
        _metrics.PIPELINE_DEPTH.set(0)
    return results
