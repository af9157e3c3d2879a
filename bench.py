"""PoW benchmark: double-SHA512 trial-hashes/sec on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Methodology (honest-timing rules):
- every timed run uses a DIFFERENT start nonce (no result reuse) with
  an unreachable target, so the search executes all chunks;
- completion is forced by pulling a scalar output to the host;
- median of repeated runs, not best-of;
- the production single-chip kernel is benched: the Pallas/Mosaic
  kernel at (128 rows x 512 chunks x unroll 4) = 33.5M trials/slab,
  136.4 MH/s measured (BASELINE.md "Arithmetic utilization"), with the
  XLA windowed kernel (2^19 lanes x 64 chunks, 25.8 MH/s) as fallback
  + secondary datapoint.  Small slabs are dispatch-latency bound.
- beyond the headline rate, the ONE output line carries a "configs"
  object covering BASELINE.json's config list (single default-
  difficulty object, mixed batch queue, ntpb x64 TTL=28d, broadcast
  storm, pod-sharded tier) — sampled sizes are labeled as such.

``vs_baseline`` follows the reference's safe-PoW analog: a single-core
hashlib double-SHA512 loop (src/proofofwork.py:157-171).  The JSON also
reports the in-repo multithreaded C++ solver rate
(native/pow/bitmsgpow.cpp) as the honest native baseline — the OpenCL
GPU north-star rate (BASELINE.md) cannot be measured here (no GPU).
"""

import hashlib
import json
import os
import statistics
import sys
import time
from contextlib import contextmanager

from pybitmessage_tpu.observability import (REGISTRY, env_fingerprint,
                                            snapshot, trace)

LANES = 1 << 19
CHUNKS = 64
REPS = 5

#: continuous profiling plane (docs/observability.md): ``--profile``
#: makes the attributed sections (ingest_storm, role_split, pow_farm)
#: write a speedscope JSON next to their metrics snapshot; the
#: attribution dicts ride the bench JSON either way
PROFILE = "--profile" in sys.argv[1:]
PROFILE_DIR = os.environ.get("BMTPU_PROFILE_DIR", ".")


@contextmanager
def _attributed(section: str, hz: float = 47.0):
    """CPU attribution window around one bench section: a dedicated
    sampling profiler measures the body and the yielded dict fills
    with subsystem/thread-class shares, the dominant subsystem, the
    sampler's own overhead fraction (perfguard-banded <2%), and —
    under ``--profile`` — the path of the emitted speedscope file."""
    from pybitmessage_tpu.observability.profiling import (
        SamplingProfiler, speedscope_doc)
    prof = SamplingProfiler(hz=hz)
    with prof.measure() as att:
        yield att
    att["crypto_share"] = att.get("by_subsystem", {}).get("crypto", 0.0)
    if PROFILE:
        path = os.path.join(PROFILE_DIR,
                            "profile_%s.speedscope.json" % section)
        with open(path, "w") as f:
            json.dump(speedscope_doc(prof.collapsed(),
                                     name=section), f)
        att["speedscope_file"] = path

#: device-side kernel time per production slab, fed from the profiler
#: trace in _measure_mfu — the histogram form of the quantity MFU is
#: derived from (ISSUE 1 satellite: no more ad-hoc locals)
SLAB_DEVICE_SECONDS = REGISTRY.histogram(
    "pow_slab_device_seconds",
    "Device-side kernel duration of one production Pallas slab "
    "(from the XLA profiler trace)")


def _host_rate(initial_hash: bytes, trials: int = 20000) -> float:
    """Single-core hashlib double-SHA512 trial rate (the safe-PoW analog)."""
    t0 = time.perf_counter()
    for nonce in range(trials):
        hashlib.sha512(hashlib.sha512(
            nonce.to_bytes(8, "big") + initial_hash).digest()).digest()
    return trials / (time.perf_counter() - t0)


def _native_rate(initial_hash: bytes) -> float:
    """Multithreaded C++ solver rate (all cores), median of 3 solves."""
    from pybitmessage_tpu.pow.native import NativeSolver
    solver = NativeSolver()
    if not solver.available:
        return 0.0
    rates = []
    for i in range(3):
        t0 = time.perf_counter()
        # mean ~2M trials at 2^43; start offset decorrelates runs
        _, trials = solver.solve(initial_hash, 2 ** 43,
                                 start_nonce=i * (1 << 40))
        dt = max(time.perf_counter() - t0, 1e-9)
        rates.append(trials / dt)
    return statistics.median(rates)


def _device_rate_xla(initial_hash: bytes) -> float:
    from pybitmessage_tpu.ops.pow_search import pow_search_jit
    from pybitmessage_tpu.ops.sha512_jax import initial_hash_words
    from pybitmessage_tpu.ops.u64 import u64_from_int

    ih_hi, ih_lo = initial_hash_words(initial_hash)
    t_hi, t_lo = u64_from_int(1)      # unreachable target: full chunks
    trials = LANES * CHUNKS

    def run(start: int) -> float:
        s_hi, s_lo = u64_from_int(start)
        t0 = time.perf_counter()
        out = pow_search_jit(ih_hi, ih_lo, t_hi, t_lo, s_hi, s_lo,
                             LANES, CHUNKS)
        chunks_done = int(out[3])     # host pull forces completion
        assert chunks_done == CHUNKS
        return trials / (time.perf_counter() - t0)

    run(0)                            # compile + warm
    return statistics.median(run((i + 1) * trials) for i in range(REPS))


def _device_rate_pallas(initial_hash: bytes) -> float:
    """Production single-chip tier: the Mosaic kernel at its measured
    sweet spot (sha512_pallas.DEFAULT_ROWS/DEFAULT_CHUNKS)."""
    import jax.numpy as jnp
    import numpy as np

    from pybitmessage_tpu.ops.sha512_pallas import (
        DEFAULT_CHUNKS, DEFAULT_ROWS, DEFAULT_UNROLL, LANE_COLS,
        pallas_search)

    words = [int.from_bytes(initial_hash[i:i + 8], "big")
             for i in range(0, 64, 8)]
    ih_words = jnp.array([[w >> 32, w & 0xFFFFFFFF] for w in words],
                         dtype=jnp.uint32)
    target = jnp.array([0, 1], dtype=jnp.uint32)   # unreachable
    trials = DEFAULT_ROWS * LANE_COLS * DEFAULT_CHUNKS * DEFAULT_UNROLL

    def run(start: int) -> float:
        base = jnp.array([(start >> 32) & 0xFFFFFFFF,
                          start & 0xFFFFFFFF], dtype=jnp.uint32)
        t0 = time.perf_counter()
        found, _ = pallas_search(ih_words, base, target,
                                 rows=DEFAULT_ROWS, chunks=DEFAULT_CHUNKS,
                                 unroll=DEFAULT_UNROLL)
        np.asarray(found)             # host pull forces completion
        return trials / (time.perf_counter() - t0)

    run(0)                            # compile + warm
    return statistics.median(run((i + 1) * trials) for i in range(REPS))


def _device_rate_effective(initial_hash: bytes) -> float:
    """Effective rate of the production double-buffered ``solve()``
    loop (one slab in flight ahead of harvest): trials completed per
    wall-second with an unreachable target and a fixed slab budget.
    This is what a caller actually gets; it exceeds the synchronous
    slab rate because dispatch/transfer gaps hide behind compute
    (BASELINE.md)."""
    from pybitmessage_tpu.ops.pow_search import PowInterrupted
    from pybitmessage_tpu.ops.sha512_pallas import (
        DEFAULT_CHUNKS, DEFAULT_ROWS, DEFAULT_UNROLL, LANE_COLS)
    from pybitmessage_tpu.pow.pipeline import solve_batch_pipelined

    slab = DEFAULT_ROWS * LANE_COLS * DEFAULT_CHUNKS * DEFAULT_UNROLL
    calls = {"n": 0}

    def run(budget: int, start: int) -> float:
        calls["n"] = 0

        def stop():
            calls["n"] += 1
            return calls["n"] > budget

        t0 = time.perf_counter()
        try:
            solve_batch_pipelined([(initial_hash, 1)],
                                  start_nonces=[start], should_stop=stop)
        except PowInterrupted:
            pass
        return budget * slab / (time.perf_counter() - t0)

    run(1, 0)                                 # warm
    return statistics.median(run(6, (i + 1) << 40) for i in range(3))


#: vector u32 ops per double-SHA512 trial, counted from the jaxpr of
#: the unrolled schedule the kernel executes (BASELINE.md)
OPS_PER_TRIAL = 21152
#: v5e VPU peak u32 issue rate (8x128 lanes x 4 ALUs x ~1.5 GHz);
#: documented estimate — see BASELINE.md "Arithmetic utilization"
VPU_PEAK_U32 = 6.1e12


def _measure_mfu(initial_hash: bytes) -> dict:
    """Profiler-trace MFU (VERDICT r4 #5): capture a jax profiler trace
    of the production kernel, read the DEVICE-side kernel duration from
    the Chrome trace (immune to dispatch latency, which is why it
    exceeds the wall-clock effective rate), and derive achieved u32
    issue rate vs the documented VPU peak."""
    import glob
    import gzip
    import tempfile
    from collections import defaultdict

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pybitmessage_tpu.ops.sha512_pallas import (
        DEFAULT_CHUNKS, DEFAULT_ROWS, DEFAULT_UNROLL, LANE_COLS,
        pallas_search)

    words = [int.from_bytes(initial_hash[i:i + 8], "big")
             for i in range(0, 64, 8)]
    ih_words = jnp.array([[w >> 32, w & 0xFFFFFFFF] for w in words],
                         dtype=jnp.uint32)
    target = jnp.array([0, 1], dtype=jnp.uint32)   # unreachable
    trials = DEFAULT_ROWS * LANE_COLS * DEFAULT_CHUNKS * DEFAULT_UNROLL

    def launch(start: int):
        base = jnp.array([(start >> 32) & 0xFFFFFFFF,
                          start & 0xFFFFFFFF], dtype=jnp.uint32)
        found, _ = pallas_search(ih_words, base, target,
                                 rows=DEFAULT_ROWS, chunks=DEFAULT_CHUNKS,
                                 unroll=DEFAULT_UNROLL)
        np.asarray(found)
    launch(0)                                      # already-warm no-op
    tmp = tempfile.mkdtemp(prefix="bm_mfu_trace_")
    try:
        with jax.profiler.trace(tmp):
            for i in range(3):
                # the span mirrors into a TraceAnnotation, so the slab
                # launch is named in the XLA trace
                with trace("bench.slab", slab=i):
                    launch((i + 7) * trials)
        latest = max(glob.glob(tmp + "/plugins/profile/*"))
        (trace_file,) = glob.glob(latest + "/*.trace.json.gz")
        with gzip.open(trace_file) as f:
            trace_json = json.load(f)
    finally:
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
    events = trace_json["traceEvents"]
    dev_pids = {e["pid"] for e in events
                if e.get("ph") == "M" and e.get("name") == "process_name"
                and "TPU" in (e["args"].get("name") or "")}
    groups = defaultdict(list)
    for e in events:
        if e.get("pid") in dev_pids and e.get("ph") == "X":
            groups[e["name"]].append(e["dur"])
    if not groups:
        raise RuntimeError("no device events in profiler trace")
    # the kernel dominates the trace by orders of magnitude
    _name, durs = max(groups.items(),
                      key=lambda kv: statistics.median(kv[1]))
    # per-slab device timings flow through the shared histogram — the
    # snapshot in the output JSON then carries percentile latencies
    for d in durs:
        SLAB_DEVICE_SECONDS.observe(d * 1e-6)
    device_s = statistics.median(durs) * 1e-6
    rate = trials / device_s
    return {
        "device_kernel_time_s_per_slab": round(device_s, 4),
        "device_kernel_hps": round(rate, 1),
        "u32_issue_rate": round(rate * OPS_PER_TRIAL, 0),
        "vpu_peak_u32": VPU_PEAK_U32,
        "mfu": round(rate * OPS_PER_TRIAL / VPU_PEAK_U32, 4),
        "basis": "jax profiler trace, median device duration of 3 "
                 "production-slab launches",
    }


def _device_rate(initial_hash: bytes) -> tuple[float, float, str]:
    """(pallas_rate, xla_rate, "pallas").  The headline metric is the
    production Mosaic kernel's rate: a failure there raises, it is
    never retried and never reported as the XLA rate under the same
    name."""
    xla = _device_rate_xla(initial_hash)
    return _device_rate_pallas(initial_hash), xla, "pallas"


# -- BASELINE.json config benchmarks -----------------------------------------
# The driver's config list (BASELINE.json "configs") beyond the raw
# single-object rate.  Sizes are sampled down so the whole bench stays
# minutes, and every scaled run is labeled with its sampling; the
# full-size figures are the measured per-object wall-clocks times the
# config's object count (PoW objects are independent).

def _default_target(length: int, ttl: int, ntpb: int = 1000,
                    extra: int = 1000) -> int:
    from pybitmessage_tpu.models.pow_math import pow_target
    return pow_target(length, ttl, ntpb, extra, clamp=False)


def _mean_trials(length: int, ttl: int, ntpb: int = 1000,
                 extra: int = 1000) -> float:
    return 2.0 ** 64 / _default_target(length, ttl, ntpb, extra)


def _bench_single_default(device_rate: float) -> dict:
    """Config 1: one 1 kB msg object at network default difficulty
    (nonceTrialsPerByte=1000, TTL=4 d) — REAL solves, plus the implied
    mean from the measured hash rate (solve time is exponentially
    distributed, so two samples + the implied mean tell more than
    either alone)."""
    from pybitmessage_tpu.pow.pipeline import solve_batch_pipelined

    def solve(ih, target):
        return solve_batch_pipelined([(ih, target)])[0]

    ttl = 4 * 24 * 3600
    length = 1008 + 8
    target = _default_target(length, ttl)
    solve(hashlib.sha512(b"bench warm").digest(), target)   # absorb
    times = []                    # compile on the warmup
    for i in range(3):
        ih = hashlib.sha512(b"bench single %d" % i).digest()
        t0 = time.perf_counter()
        solve(ih, target)
        times.append(time.perf_counter() - t0)
    return {
        "measured_solve_s": [round(t, 2) for t in times],
        "median_solve_s": round(statistics.median(times), 2),
        "implied_mean_s": round(_mean_trials(length, ttl) / device_rate, 2),
        "mean_trials": int(_mean_trials(length, ttl)),
    }


def _pipeline_stats() -> dict:
    """Pipeline-overlap numbers for the run so far: device-busy
    fraction, dispatch-ahead depth and pack-occupancy percentiles from
    the registry (the ISSUE 2 'pipeline_overlap' section)."""
    from pybitmessage_tpu.observability import REGISTRY

    out = {"device_busy_ratio": round(
        REGISTRY.sample("pow_pipeline_device_busy_ratio"), 4)}
    ahead = REGISTRY.get("pow_pipeline_dispatch_ahead_size")
    if ahead is not None and ahead.count:
        out["dispatch_ahead"] = {
            "harvests": ahead.count,
            "mean": round(ahead.sum / ahead.count, 2),
            "p90": round(ahead.percentile(0.90), 1),
        }
    pack = REGISTRY.get("pow_pack_size")
    if pack is not None and pack.count:
        out["pack_size"] = {
            "launches": pack.count,
            "mean": round(pack.sum / pack.count, 2),
            "p50": round(pack.percentile(0.50), 1),
            "p90": round(pack.percentile(0.90), 1),
        }
    out["pack_occupancy_last"] = round(
        REGISTRY.sample("pow_pack_occupancy_ratio"), 4)
    wait = REGISTRY.get("pow_pipeline_device_wait_seconds")
    if wait is not None and wait.count:
        out["device_wait_s"] = {
            "p50": round(wait.percentile(0.50), 5),
            "p90": round(wait.percentile(0.90), 5),
        }
    modes = REGISTRY.get("pow_pipeline_mode_total")
    if modes is not None:
        out["modes"] = {v[0]: int(c.value) for v, c in modes.children()}
    return out


def _bench_batch_queue(impl: str = "pallas", n: int = 64,
                       rows: int = 128) -> dict:
    """Config 2: batched workerQueue — mixed-size objects through the
    async pipelined solver (sampled: 64 of the 1k config, difficulty
    /100 = reference test mode so the sample completes in seconds;
    scheduling behavior, which is what this config exercises, is
    difficulty-independent)."""
    from pybitmessage_tpu.pow.pipeline import solve_batch_pipelined

    ttl = 4 * 24 * 3600
    sizes = [116, 1016, 10016, 216]       # mixed payloadLengthExtraBytes
    items = []
    for i in range(n):
        length = sizes[i % len(sizes)]
        ih = hashlib.sha512(b"bench queue %d" % i).digest()
        items.append((ih, _default_target(length, ttl, ntpb=10, extra=10)))
    solve_batch_pipelined(items[:8], impl=impl, rows=rows)   # warm
    stats = {}
    t0 = time.perf_counter()
    results = solve_batch_pipelined(items, impl=impl, rows=rows,
                                    stats=stats)
    dt = time.perf_counter() - t0
    return {
        "objects": len(items), "sampled_from": 1000,
        "difficulty": "defaults/100 (reference test mode)",
        "wall_s": round(dt, 2),
        "objects_per_s": round(len(items) / dt, 2),
        # device-executed basis (incl. straggler/pad waste) — the
        # figure comparable to pre-pipeline rounds, where credit ==
        # executed because every object owned a full tile
        "aggregate_hps": round(stats.get("executed_trials", 0) / dt, 1),
        "credited_hps": round(sum(r[1] for r in results) / dt, 1),
        "plan": {k: stats.get(k) for k in
                 ("mode", "pack", "width", "chunks", "launches")},
        "pipeline": _pipeline_stats(),
    }


def _bench_batch_real_difficulty(device_rate: float,
                                 impl: str = "pallas") -> dict:
    """Config 2b: one full 64-object batch launch group at REAL network
    default difficulty (nonceTrialsPerByte=1000, extra=1000, TTL=4 d,
    1 kB objects; mean ~12.7M trials/object) — the batch tier measured
    at production difficulty, not test mode (VERDICT r4 weak #2).
    Runs through the dispatch-ahead pipeline: this is the config the
    sync-slab penalty (136.6 vs 202.9M H/s) shows up in, and where the
    overlap must close it (ISSUE 2 acceptance: within 15% of the
    device-kernel rate)."""
    from pybitmessage_tpu.pow.pipeline import solve_batch_pipelined

    ttl = 4 * 24 * 3600
    length = 1016
    target = _default_target(length, ttl)
    items = [(hashlib.sha512(b"bench real batch %d" % i).digest(), target)
             for i in range(64)]
    stats = {}
    t0 = time.perf_counter()
    results = solve_batch_pipelined(items, impl=impl, stats=stats)
    dt = time.perf_counter() - t0
    total_trials = sum(r[1] for r in results)
    return {
        "objects": len(items),
        "difficulty": "network defaults (ntpb=1000, extra=1000, TTL=4d)",
        "mean_trials_per_object": int(_mean_trials(length, ttl)),
        "wall_s": round(dt, 2),
        "objects_per_s": round(len(items) / dt, 2),
        "aggregate_hps": round(total_trials / dt, 1),
        "implied_serial_single_s": round(
            len(items) * _mean_trials(length, ttl) / device_rate, 1),
        "plan": {k: stats.get(k) for k in
                 ("mode", "pack", "width", "chunks", "launches")},
        "device_busy_ratio": stats.get("device_busy_ratio"),
    }


def _bench_high_difficulty(device_rate: float, host_rate: float) -> dict:
    """Config 3: nonceTrialsPerByte x64, TTL=28 d.  Mean work is
    ~4.9e9 trials (~40 s/object on-chip) — reported as implied
    wall-clock from the measured rates, the same methodology the
    reference UI uses for its difficulty/10s estimate
    (proofofwork.py:197-201)."""
    ttl = 28 * 24 * 3600
    length = 1016
    trials = _mean_trials(length, ttl, ntpb=64 * 1000)
    return {
        "mean_trials": int(trials),
        "implied_mean_s_per_object": round(trials / device_rate, 1),
        "implied_cpu_hashlib_s": round(trials / host_rate, 0),
    }


def _bench_broadcast_storm(impl: str = "pallas", n: int = 1024,
                           rows: int = 128) -> dict:
    """Config 4: chan broadcast storm — many small objects (sampled:
    1024 of the 10k config at test-mode difficulty; widened from r05's
    256 so multiple pipelined launches actually overlap).

    Measured BOTH ways the planner can run it: packed (objects share
    slab lanes — max objects/s, minimal wasted hashing) and wide
    batched (full tile per object — max device hash rate).  The
    headline keys mirror whichever run moved more objects per second;
    ``aggregate_hps`` is on the device-executed basis, comparable to
    pre-pipeline rounds where credit == executed.
    """
    from pybitmessage_tpu.pow.pipeline import (BatchPlan,
                                               solve_batch_pipelined)

    ttl = 3600
    items = []
    for i in range(n):
        ih = hashlib.sha512(b"bench storm %d" % i).digest()
        items.append((ih, _default_target(116, ttl, ntpb=10, extra=10)))
    solve_batch_pipelined(items[:8], impl=impl, rows=rows)   # warm

    def run(plan):
        stats = {}
        t0 = time.perf_counter()
        results = solve_batch_pipelined(items, impl=impl, rows=rows,
                                        plan=plan, stats=stats)
        dt = time.perf_counter() - t0
        return {
            "wall_s": round(dt, 2),
            "objects_per_s": round(len(items) / dt, 2),
            "aggregate_hps": round(
                stats.get("executed_trials", 0) / dt, 1),
            "credited_hps": round(sum(r[1] for r in results) / dt, 1),
            "plan": {k: stats.get(k) for k in
                     ("mode", "pack", "width", "chunks", "launches")},
            "device_busy_ratio": stats.get("device_busy_ratio"),
        }

    packed = run(None)            # planner's choice (packed for tiny)
    batched = run(BatchPlan("batched", 1, 64, list(range(len(items)))))
    best = max((packed, batched), key=lambda r: r["objects_per_s"])
    return {
        "objects": len(items), "sampled_from": 10000,
        "difficulty": "defaults/100 (reference test mode)",
        **best,
        "modes": {"planned": packed, "wide_batched": batched},
        "pipeline": _pipeline_stats(),
    }


def _bench_vanity_grind() -> dict:
    """SURVEY hot-loop #3 (address vanity-ripe grind,
    class_addressGenerator.py:119-214): measure the cost split between
    EC point multiplication (host, OpenSSL via `cryptography`) and
    SHA512+RIPEMD160 (the only part a TPU could take).  The measured
    hash share bounds any accelerator speedup (Amdahl); this config
    documents why the grind ships host-side with no device tier —
    VERDICT r4 #8's 'measure it and close it honestly' path."""
    from pybitmessage_tpu.crypto.keys import (priv_to_pub,
                                              random_private_key)
    from pybitmessage_tpu.utils.hashes import address_ripe

    n = 500
    keys = [random_private_key() for _ in range(n)]
    t0 = time.perf_counter()
    pubs = [priv_to_pub(k) for k in keys]
    ec_rate = n / (time.perf_counter() - t0)
    anchor = pubs[0]
    t0 = time.perf_counter()
    for p in pubs:
        address_ripe(anchor, p)
    hash_rate = n / (time.perf_counter() - t0)
    hash_share = (1 / hash_rate) / (1 / ec_rate + 1 / hash_rate)
    return {
        "ec_pointmult_per_s": round(ec_rate, 0),
        "sha512_ripemd160_per_s": round(hash_rate, 0),
        "hash_share_of_grind": round(hash_share, 4),
        "max_tpu_speedup_amdahl": round(1 / (1 - hash_share), 4),
        "conclusion": "EC-bound on host; device hash tier closed as a"
                      " measured loser",
    }


def _bench_sharded_tier(initial_hash: bytes) -> dict:
    """Config 5: the pod tier on a 1-device mesh (only one real chip
    here) — per-chip rate of the production sharded path; multi-chip
    partitioning itself is validated on the virtual CPU mesh
    (tests/test_pow_pallas_sharded.py, dryrun_multichip)."""
    from pybitmessage_tpu.ops.pow_search import PowInterrupted
    from pybitmessage_tpu.ops.sha512_pallas import (DEFAULT_CHUNKS,
                                                    DEFAULT_ROWS,
                                                    DEFAULT_UNROLL,
                                                    LANE_COLS)
    from pybitmessage_tpu.parallel import make_mesh, pallas_sharded_solve

    mesh = make_mesh(1)
    # must match pallas_sharded_solve's own slab accounting (it runs
    # DEFAULT_UNROLL tiles per grid step)
    slab = DEFAULT_ROWS * LANE_COLS * DEFAULT_CHUNKS * DEFAULT_UNROLL
    calls = {"n": 0}

    def stop_after(n):
        calls["n"] += 1
        return calls["n"] > n

    def run(budget: int, start: int) -> float:
        calls["n"] = 0
        t0 = time.perf_counter()
        try:
            pallas_sharded_solve(
                initial_hash, 1, mesh, start_nonce=start,
                should_stop=lambda: stop_after(budget))
        except PowInterrupted:
            pass
        return budget * slab / (time.perf_counter() - t0)

    run(1, 0)                                # compile + warm
    rate = statistics.median(run(6, (i + 1) << 40) for i in range(3))
    return {"per_chip_hps_1dev_mesh": round(rate, 1)}


def _bench_degraded_fallback(n: int = 4, target_exp: int = 56) -> dict:
    """Degraded-mode section (ISSUE 3): inject persistent device-launch
    faults, solve a small queue through the ladder, and report what a
    node actually delivers while its fastest tier is dead — plus the
    breaker state proving fallbacks stop paying the failure latency
    after it opens."""
    import hashlib as _hl

    from pybitmessage_tpu.pow import PowDispatcher
    from pybitmessage_tpu.pow.dispatcher import host_trial
    from pybitmessage_tpu.resilience import CHAOS

    d = PowDispatcher(use_tpu=True,
                      tpu_kwargs={"lanes": 1 << 12, "chunks_per_call": 8})
    items = [(_hl.sha512(b"degraded %d" % i).digest(), 2 ** target_exp)
             for i in range(n)]
    CHAOS.arm("pow.device_launch", probability=1.0)
    try:
        t0 = time.perf_counter()
        results = d.solve_batch(items)
        dt = max(time.perf_counter() - t0, 1e-9)
    finally:
        CHAOS.disarm()
    assert all(host_trial(nonce, ih) <= t
               for (ih, t), (nonce, _) in zip(items, results))
    trials = sum(r[1] for r in results)
    return {
        "objects": n,
        "faults": "pow.device_launch p=1.0 (persistent)",
        "rescue_backend": d.last_backend,
        "tpu_breaker": d.breakers["tpu"].snapshot()["state"],
        "wall_s": round(dt, 2),
        "objects_per_s": round(n / dt, 2),
        "degraded_hps": round(trials / dt, 1),
        "no_object_loss": True,
    }


# -- ingest fast path (ISSUE 4) ----------------------------------------------

def _ingest_stage_stats() -> dict:
    """Per-stage ingest latency percentiles from the registry."""
    fam = REGISTRY.get("ingest_stage_seconds")
    out = {}
    if fam is None:
        return out
    for values, child in fam.children():
        _, _, count = child.snapshot()
        if count:
            out[values[0]] = {
                "count": count,
                "p50_us": round(child.percentile(0.50) * 1e6, 1),
                "p90_us": round(child.percentile(0.90) * 1e6, 1),
            }
    return out


def _crypto_work_sums() -> dict[str, float]:
    """Receive-side crypto WORK time so far: per-call stage seconds
    (inline path) and batch-drain execution seconds (engine path), by
    source.  Deltas around a run attribute work to that run."""
    out = {"stage_decrypt": 0.0, "stage_sig_verify": 0.0,
           "batch_decrypt": 0.0, "batch_verify": 0.0}
    fam = REGISTRY.get("ingest_stage_seconds")
    if fam is not None:
        for values, child in fam.children():
            if values[0] in ("decrypt", "sig_verify"):
                out["stage_" + values[0]] = child.snapshot()[1]
    fam = REGISTRY.get("crypto_batch_seconds")
    if fam is not None:
        for values, child in fam.children():
            out["batch_" + values[0]] = child.snapshot()[1]
    return out


def _bench_batch_crypto(verifies: int = 128, decrypt_objects: int = 16,
                        fanout: int = 8) -> dict:
    """Direct engine microbench (ISSUE 7): coalesced batch drains vs
    the per-call path, for ECDSA verify and ECIES trial-decrypt sweeps,
    on whatever backend ladder this host carries (native -> pure).
    """
    import asyncio

    from pybitmessage_tpu.crypto import encrypt, priv_to_pub, sign
    from pybitmessage_tpu.crypto.batch import BatchCryptoEngine
    from pybitmessage_tpu.crypto.keys import random_private_key
    from pybitmessage_tpu.crypto.native import get_native
    from pybitmessage_tpu.crypto.signing import verify as _verify
    from pybitmessage_tpu.crypto.ecies import DecryptionError, decrypt

    privs = [random_private_key() for _ in range(fanout)]
    pubs = [priv_to_pub(p) for p in privs]
    sigs = [(b"bench msg %d" % i, sign(b"bench msg %d" % i,
                                       privs[i % fanout]),
             pubs[i % fanout]) for i in range(verifies)]
    # half the trial-decrypt objects decrypt under the LAST candidate
    # (full sweep), half under none (full miss sweep) — worst cases
    payloads = [encrypt(b"payload %d" % i,
                        pubs[-1] if i % 2 else
                        priv_to_pub(random_private_key()))
                for i in range(decrypt_objects)]
    candidates = [(p, i) for i, p in enumerate(privs)]

    async def engine_run() -> float:
        eng = BatchCryptoEngine()
        eng.start()
        try:
            t0 = time.perf_counter()
            oks = await asyncio.gather(
                *[eng.verify(*item) for item in sigs],
                *[eng.try_decrypt(pl, candidates) for pl in payloads])
            dt = time.perf_counter() - t0
            assert all(bool(r) for r in oks[:verifies])
            assert sum(1 for m in oks[verifies:] if m) \
                == decrypt_objects // 2
            return dt
        finally:
            await eng.stop()

    def percall_run() -> float:
        t0 = time.perf_counter()
        for item in sigs:
            assert _verify(*item)
        hits = 0
        for pl in payloads:
            for priv, _h in candidates:
                try:
                    decrypt(pl, priv)
                    hits += 1
                    break
                except DecryptionError:
                    continue
        dt = time.perf_counter() - t0
        assert hits == decrypt_objects // 2
        return dt

    # interleave A/B reps and take the median of per-pair ratios —
    # shared-host load swings 2x minute to minute, but a ratio taken
    # from adjacent runs sees (nearly) the same machine
    asyncio.run(engine_run())        # warm (comb table, lru tables)
    percall_run()
    pairs = [(asyncio.run(engine_run()), percall_run())
             for _ in range(3)]
    ratios = sorted(pc / max(b, 1e-9) for b, pc in pairs)
    batched = statistics.median(b for b, _ in pairs)
    percall = statistics.median(pc for _, pc in pairs)
    return {
        "verifies": verifies,
        "decrypt_sweeps": "%d objects x %d candidates"
                          % (decrypt_objects, fanout),
        "backend": "native" if get_native().available else "pure",
        "batched_s": round(batched, 3),
        "percall_s": round(percall, 3),
        "batch_speedup": ratios[len(ratios) // 2],
        # ISSUE 13 satellite: the same drain shapes through the tpu
        # rung vs the native rung, host-verified sample
        "tpu_vs_native": _bench_tpu_vs_native(drain=max(verifies, 64)),
    }


def _bench_tpu_vs_native(drain: int = 256, sample: int = 8) -> dict:
    """tpu-rung vs native-rung drain throughput (ISSUE 13): the SAME
    prepared verify/ECDH drains through ``TpuSecp`` and ``NativeSecp``
    back to back, with a host-verified sample of the results.

    On CPU CI the tpu rung runs its XLA path — the honest figure there
    is PARITY and zero loss (perfguard floors ``parity_ok``/
    ``zero_loss``), not speed; ``target_speedup_v5e`` records the
    acceptance bar for the next hardware run in the JSON schema.
    """
    import hashlib
    import random

    from pybitmessage_tpu.crypto import fallback
    from pybitmessage_tpu.crypto import tpu as crypto_tpu
    from pybitmessage_tpu.crypto.native import get_native

    _N = fallback.N
    # force the rung on for the measurement (auto = off on CPU), and
    # restore afterwards so later sections see the configured mode
    prev_mode = crypto_tpu.mode()
    crypto_tpu.configure("on")
    crypto_tpu.reset_tpu()
    tpu = crypto_tpu.get_tpu()
    try:
        if not tpu.available:
            return {"skipped": "jax unavailable", "parity_ok": 1.0,
                    "zero_loss": 1.0}
        rng = random.Random(1337)
        u1s, u2s, pubs, rs, oracle = [], [], [], [], []
        for i in range(drain):
            priv = rng.randrange(1, _N)
            data = b"tpu bench %d" % i
            e = fallback.digest_to_scalar(hashlib.sha256(data).digest())
            sig = fallback.ecdsa_sign_digest(
                hashlib.sha256(data).digest(), priv.to_bytes(32, "big"))
            r, s = fallback.der_decode_sig(sig)
            if i % 7 == 6:          # corrupt ~14%: must fail on BOTH
                e = (e + 1) % _N
            w = pow(s, -1, _N)
            u1s.append(((e * w) % _N).to_bytes(32, "big"))
            u2s.append(((r * w) % _N).to_bytes(32, "big"))
            pub = fallback.priv_to_pub(priv.to_bytes(32, "big"))
            pubs.append(pub[1:])
            rs.append(r.to_bytes(32, "big"))
            px, py = fallback.decode_point(pub)
            oracle.append((e, r, s, (px, py)))
        points = b"".join(pubs)
        scalars = b"".join(
            rng.randrange(1, _N).to_bytes(32, "big")
            for _ in range(drain))
        args = (drain, b"".join(u1s), b"".join(u2s), points,
                b"".join(rs))

        def run_rung(backend):
            backend.verify_prepared(*args)          # warm/compile
            backend.ecdh_batch(drain, points, scalars)
            t0 = time.perf_counter()
            oks = backend.verify_prepared(*args)
            tv = time.perf_counter() - t0
            t0 = time.perf_counter()
            xs = backend.ecdh_batch(drain, points, scalars)
            te = time.perf_counter() - t0
            return oks, xs, tv, te

        tpu_ok, tpu_x, tpu_tv, tpu_te = run_rung(tpu)
        native = get_native()
        out: dict = {
            "drain_size": drain,
            "tpu_kernel": tpu.snapshot()["kernel"],
            "tpu_platform": tpu.platform,
            "tpu_verify_ops_s": round(drain / max(tpu_tv, 1e-9), 1),
            "tpu_ecdh_ops_s": round(drain / max(tpu_te, 1e-9), 1),
            # acceptance bar for the next v5e run, recorded in-schema
            "target_speedup_v5e": 10.0,
        }
        # host-verify a sample of the tpu results against the oracle
        idx = rng.sample(range(drain), min(sample, drain))
        parity = all(
            bool(tpu_ok[i]) == fallback.ecdsa_verify_scalars(
                *oracle[i][:3], oracle[i][3]) for i in idx)
        parity &= all(
            tpu_x[i] == fallback.ecdh_x(
                scalars[32 * i:32 * i + 32],
                b"\x04" + points[64 * i:64 * i + 64]) for i in idx)
        if native.available:
            nat_ok, nat_x, nat_tv, nat_te = run_rung(native)
            parity &= (tpu_ok == nat_ok and tpu_x == nat_x)
            out.update({
                "native_verify_ops_s": round(
                    drain / max(nat_tv, 1e-9), 1),
                "native_ecdh_ops_s": round(drain / max(nat_te, 1e-9),
                                           1),
                "verify_speedup": round(nat_tv / max(tpu_tv, 1e-9), 3),
                "ecdh_speedup": round(nat_te / max(tpu_te, 1e-9), 3),
            })
        # no assert here: a divergence must land in the JSON as
        # parity_ok=0.0 so the perfguard `atleast 1.0` floor is the
        # thing that fails (an assert would kill the run before the
        # JSON exists and the band could never fire)
        out["parity_ok"] = 1.0 if parity else 0.0
        out["zero_loss"] = 1.0 if (
            len(tpu_ok) == drain and len(tpu_x) == drain) else 0.0
        return out
    finally:
        crypto_tpu.configure(prev_mode)
        crypto_tpu.reset_tpu()


def _bench_device_telemetry(reps: int = 5, batch: int = 64) -> dict:
    """Device-telemetry plane cost + zero-loss (ISSUE 16).

    The PR 1 harness shape: repeated batched device launches (the
    ``pow_verify`` program) with the always-on telemetry recording
    each one.  ``overhead_frac`` is the measured per-``record_launch``
    cost (timed over a scratch program so the real counters stay
    honest) amortized over the harness wall — the same <2% budget the
    tracing and sampler planes are held to.  ``populated_zero_loss``
    is 1 only when every launch the harness issued landed in the
    registry and nothing fell into ``device_telemetry_dropped_total``.
    """
    from pybitmessage_tpu.observability.devicetelemetry import \
        record_launch
    from pybitmessage_tpu.ops import pow_search

    ih = hashlib.sha512(b"telemetry overhead harness").digest()
    items = [(i, ih, (1 << 64) - 1) for i in range(batch)]
    before = REGISTRY.sample("device_launches_total",
                             {"program": "pow_verify"})
    dropped0 = REGISTRY.sample("device_telemetry_dropped_total")
    t0 = time.perf_counter()
    for _ in range(reps):
        pow_search.verify(items)
    wall = max(time.perf_counter() - t0, 1e-9)
    launches = REGISTRY.sample("device_launches_total",
                               {"program": "pow_verify"}) - before
    dropped = REGISTRY.sample("device_telemetry_dropped_total") - dropped0
    # per-record cost, timed in isolation on a scratch program (its
    # series ride /metrics but stay out of deviceStatus, which walks
    # only registered programs)
    calls = 2000
    t0 = time.perf_counter()
    for i in range(calls):
        record_launch("bench_overhead_probe", key=batch,
                      dispatch_seconds=1e-4, wait_seconds=1e-4,
                      span=(float(i), float(i) + 1e-3), items=batch,
                      bytes_in=1024, bytes_out=64)
    per_record = (time.perf_counter() - t0) / calls
    return {
        "launches": int(launches),
        "dropped": int(dropped),
        "record_us": round(per_record * 1e6, 2),
        "overhead_frac": round(per_record * reps / wall, 6),
        "populated_zero_loss": int(launches >= reps and dropped == 0),
    }


def _bench_keyring_sweep(smoke: bool = False) -> dict:
    """Keyring-scaling sweep (ISSUE 17): warm-path objects/s as the
    keyring grows 100 -> 1k -> 10k keys (32/128/512 in smoke).

    Each keyring size gets a COLD pass (every object distinct: the
    transposed-wavefront ECDH sweep runs and the completed no-match
    sweeps populate the negative screen) and a WARM pass (the no-match
    objects re-arrive shuffled, several rounds — the gossip re-flood
    common case): warm throughput should be nearly flat in keyring
    size because re-arrivals are screened before any scalar
    multiplication.  ``flatness_ratio`` is
    warm_rate(largest)/warm_rate(smallest); full mode asserts the
    issue's >= 0.5 acceptance bar.

    Re-arrivals of REAL matches are never cached (a hit must
    re-decrypt every time), so they are timed apart as
    ``rematch_objects_per_s`` — the honest keyring-bound residual —
    and ``zero_false_negatives`` asserts every for-us object is still
    decrypted on EVERY warm round (a cached no-match can never eat a
    real match).

    Full mode adds a forced-tpu pass on a 1k keyring so DeviceTelemetry
    records the transposed ``secp_ecdh`` drains and asserts the mean
    drain width clears ``cryptotpubatchmin`` (64) — the "wide drains
    earn the launch" acceptance.
    """
    import asyncio
    import random as _random

    from pybitmessage_tpu.crypto.keys import (priv_to_pub,
                                              random_private_key)
    from pybitmessage_tpu.storage.db import Database
    from pybitmessage_tpu.storage.messages import MessageStore
    from pybitmessage_tpu.utils.addresses import encode_address
    from pybitmessage_tpu.utils.hashes import address_ripe
    from pybitmessage_tpu.workers.keystore import KeyStore, OwnIdentity
    from pybitmessage_tpu.workers.processor import ObjectProcessor

    def _s(name, labels=None):
        return REGISTRY.sample(name, labels) or 0.0

    sizes = (32, 128, 512) if smoke else (100, 1000, 10000)
    n_foreign, n_forus = (28, 2) if smoke else (60, 4)
    rounds = 5 if smoke else 3
    rng = _random.Random(20260807)
    # foreign (all-miss) objects are keyring-independent: build once
    foreign, _ = _build_wire_msgs(n_foreign, ntpb=1, extra=1)

    def fast_keyring(n: int) -> KeyStore:
        """n identities WITHOUT the vanity ripe-grind (the sweep only
        exercises the decrypt fan, not address aesthetics)."""
        ks = KeyStore()
        for i in range(n):
            sk, ek = random_private_key(), random_private_key()
            ripe = address_ripe(priv_to_pub(sk), priv_to_pub(ek))
            ks._index(OwnIdentity(
                "sweep %d" % i, encode_address(4, 1, ripe), 4, 1,
                ripe, sk, ek, nonce_trials_per_byte=1, extra_bytes=1))
        return ks

    class _Sender:
        def __init__(self):
            self.watched_acks = set()
            self.needed_pubkeys = {}
            self.queue = asyncio.Queue()

    async def run_size(n_keys: int) -> dict:
        ks = fast_keyring(n_keys)
        recipients = rng.sample(list(ks.identities.values()), n_forus)
        forus, _ = _build_wire_msgs(n_forus, ntpb=1, extra=1,
                                    recipients=recipients,
                                    foreign_frac=0.0)
        objects = foreign + forus
        db = Database()
        store = MessageStore(db)
        proc = ObjectProcessor(
            keystore=ks, store=store, inventory=None, sender=_Sender(),
            min_ntpb=1, min_extra=1, concurrency=8,
            write_behind=True, crypto_batch=True)
        engine, screen = proc.crypto.batch, proc.crypto.screen
        proc.start()

        async def push(batch) -> float:
            t0 = time.perf_counter()
            for p in batch:
                await proc.queue.put(p)
            while proc.pending():
                await asyncio.sleep(0.002)
            return max(time.perf_counter() - t0, 1e-9)

        cold = await push(objects)
        drains, pairs = engine.drains, engine.drain_pairs
        hits0 = _s("crypto_screen_hits_total")
        misses0 = _s("crypto_screen_misses_total")
        # warm re-flood of the NO-MATCH objects (the gossip common
        # case): screened before any scalar multiplication, so this
        # rate must be flat in keyring size
        warm_batch = []
        for _ in range(rounds):
            arrival = list(foreign)
            rng.shuffle(arrival)
            warm_batch.extend(arrival)
        warm = await push(warm_batch)
        hits = _s("crypto_screen_hits_total") - hits0
        probes = hits + _s("crypto_screen_misses_total") - misses0
        # re-arrivals of REAL matches are never cached (a hit must
        # re-decrypt every time): timed separately because this
        # residual legitimately still scales with the keyring
        match0 = _s("crypto_decrypt_total", {"result": "hit"})
        rematch = await push(forus * rounds)
        warm_matches = _s("crypto_decrypt_total",
                          {"result": "hit"}) - match0
        await proc.stop()
        delivered = len(store.inbox())
        db.close()
        return {
            "keys": n_keys,
            "objects": len(objects),
            "cold_objects_per_s": round(len(objects) / cold, 1),
            "warm_objects_per_s": round(len(warm_batch) / warm, 1),
            "rematch_objects_per_s": round(
                n_forus * rounds / rematch, 1),
            # drain shape of the cold sweep (clientStatus analog)
            "mean_drain_width": round(pairs / drains, 1) if drains
            else 0.0,
            "screen_entries": len(screen) if screen else 0,
            "screen_hit_rate": round(hits / probes, 4) if probes
            else 0.0,
            # every warm round must still decrypt every for-us object
            "zero_false_negatives": int(
                warm_matches == n_forus * rounds),
            "zero_objects_lost": int(delivered >= n_forus),
        }

    tiers = [asyncio.run(run_size(n)) for n in sizes]
    flatness = round(tiers[-1]["warm_objects_per_s"]
                     / max(tiers[0]["warm_objects_per_s"], 1e-9), 3)
    out = {
        "keyrings": tiers,
        "warm_rounds": rounds,
        # acceptance (ISSUE 17): 10k-key warm throughput >= 0.5x the
        # 100-key rate — the screen removes the keyring dimension from
        # the re-arrival path
        "flatness_ratio": flatness,
        "screen_hit_rate": round(
            min(t["screen_hit_rate"] for t in tiers), 4),
        "mean_drain_width": tiers[-1]["mean_drain_width"],
        "zero_false_negatives": int(
            all(t["zero_false_negatives"] for t in tiers)),
        "zero_objects_lost": int(
            all(t["zero_objects_lost"] for t in tiers)),
    }
    if not smoke:
        assert flatness >= 0.5, (
            "keyring sweep not flat: warm rate fell to %.3fx from "
            "%d to %d keys" % (flatness, sizes[0], sizes[-1]))
        assert out["zero_false_negatives"] == 1, (
            "negative screen ate a real match: %r" % (tiers,))
        out["tpu"] = _keyring_sweep_tpu_pass(fast_keyring(1000))
    return out


def _keyring_sweep_tpu_pass(ks) -> dict:
    """Forced-tpu drain shape on a 1k keyring: DeviceTelemetry must
    record the transposed ``secp_ecdh`` launches and the mean drain
    width must clear the tpu rung's launch-worthiness floor (64)."""
    import asyncio

    from pybitmessage_tpu.crypto import encrypt, priv_to_pub
    from pybitmessage_tpu.crypto import tpu as crypto_tpu
    from pybitmessage_tpu.crypto.batch import BatchCryptoEngine
    from pybitmessage_tpu.crypto.keys import random_private_key

    def _s(name, labels=None):
        return REGISTRY.sample(name, labels) or 0.0

    cands = [(i.priv_encryption, i.address)
             for i in ks.identities.values()]
    payloads = [encrypt(b"tpu sweep %d" % i,
                        priv_to_pub(random_private_key()))
                for i in range(4)]
    crypto_tpu.configure("on")
    crypto_tpu.set_tpu_enabled(True)
    crypto_tpu.reset_tpu()
    try:
        rung = crypto_tpu.get_tpu()
        if not rung.available:
            return {"skipped": "tpu rung unavailable: %r"
                    % rung.snapshot().get("reason")}
        launches0 = _s("device_launches_total",
                       {"program": "secp_ecdh"})
        eng = BatchCryptoEngine(use_tpu=True, tpu_batch_min=64)

        async def sweep():
            eng.start()
            try:
                return await asyncio.gather(
                    *[eng.try_decrypt(p, cands) for p in payloads])
            finally:
                await eng.stop()

        results = asyncio.run(sweep())
        assert all(r == [] for r in results)
        launches = _s("device_launches_total",
                      {"program": "secp_ecdh"}) - launches0
        width = eng.drain_pairs / max(eng.drains, 1)
        assert eng.last_path == "tpu" and launches > 0, (
            "forced-tpu sweep never launched (rung=%r, launches=%r)"
            % (eng.last_path, launches))
        assert width > 64, (
            "mean drain width %.1f does not clear cryptotpubatchmin"
            % width)
        return {
            "keys": len(cands),
            "secp_ecdh_launches": int(launches),
            "mean_drain_width": round(width, 1),
            "rung": eng.last_path,
        }
    finally:
        crypto_tpu.configure("auto")
        crypto_tpu.set_tpu_enabled(True)
        crypto_tpu.reset_tpu()


def _bench_ingest_storm(identities: int = 8, objects: int = 400,
                        smoke: bool = False) -> dict:
    """Ingest fast path end-to-end: a multi-identity flood mix (msgs
    for us spread over N identities, plus msgs for nobody that force
    the full trial-decrypt miss sweep) pushed through ObjectProcessor,
    socket-side to store.

    Measured BOTH ways:

    - ``pipelined``: the fast path — crypto-pool fan-out with
      first-match early-cancel, cached parsed keys, write-behind
      storage, 8 concurrent pipeline workers;
    - ``inline``: the pre-PR path — one worker, inline crypto on the
      event loop, per-row autocommit, parsed-key cache disabled.

    A 5 ms loop-lag probe rides along both runs; in full (non-smoke)
    mode the pipelined run asserts the event loop was never blocked
    > 50 ms by crypto or SQL.  The inline run's lag is reported as the
    contrast figure.
    """
    import asyncio

    from pybitmessage_tpu.crypto import encrypt, priv_to_pub, sign
    from pybitmessage_tpu.crypto.keys import (random_private_key,
                                              set_key_cache)
    from pybitmessage_tpu.models import msgcoding
    from pybitmessage_tpu.models.constants import OBJECT_MSG
    from pybitmessage_tpu.models.payloads import (MsgPlaintext,
                                                  get_bitfield,
                                                  object_shell)
    from pybitmessage_tpu.models.pow_math import pow_target
    from pybitmessage_tpu.pow.dispatcher import python_solve
    from pybitmessage_tpu.storage.db import Database
    from pybitmessage_tpu.storage.messages import MessageStore
    from pybitmessage_tpu.utils.hashes import sha512 as _sha512
    from pybitmessage_tpu.workers.cryptopool import CryptoPool
    from pybitmessage_tpu.workers.keystore import KeyStore
    from pybitmessage_tpu.workers.processor import ObjectProcessor

    ks = KeyStore()
    idents = [ks.create_random("flood %d" % i) for i in range(identities)]
    for ident in idents:
        # trivial demanded difficulty: the bench measures ingest, and
        # flood objects carry matching trivial PoW (test-mode analog)
        ident.nonce_trials_per_byte = 1
        ident.extra_bytes = 1
    sender_ident = idents[0]
    foreign_pub = priv_to_pub(random_private_key())
    ttl = 3600
    expires = int(time.time()) + ttl
    shell = object_shell(expires, OBJECT_MSG, 1, 1)

    def build(i: int, recipient_pub, dest_ripe: bytes) -> bytes:
        body = msgcoding.encode_message("storm %d" % i,
                                        "ingest bench body %d" % i)
        plain = MsgPlaintext(
            sender_version=sender_ident.version, sender_stream=1,
            bitfield=get_bitfield(False),
            pub_signing_key=sender_ident.pub_signing_key,
            pub_encryption_key=sender_ident.pub_encryption_key,
            nonce_trials_per_byte=1, extra_bytes=1,
            dest_ripe=dest_ripe, encoding=2, message=body, ack_data=b"")
        plain.signature = sign(shell + plain.encode_unsigned(),
                               sender_ident.priv_signing)
        sans_nonce = shell + encrypt(plain.encode(), recipient_pub)
        target = pow_target(len(sans_nonce) + 8, ttl, 1, 1, clamp=False)
        nonce, _ = python_solve(_sha512(sans_nonce), target)
        return nonce.to_bytes(8, "big") + sans_nonce

    payloads, for_us = [], 0
    for i in range(objects):
        if i % 4 == 3:          # 25% decrypt-all-miss traffic
            payloads.append(build(i, foreign_pub, b"\x00" * 20))
        else:
            r = idents[i % identities]
            payloads.append(build(i, r.pub_encryption_key, r.ripe))
            for_us += 1

    class _StubSender:
        def __init__(self):
            self.watched_acks = set()
            self.needed_pubkeys = {}
            self.queue = asyncio.Queue()

    async def run(pipelined: bool) -> dict:
        db = Database()
        store = MessageStore(db)
        proc = ObjectProcessor(
            keystore=ks, store=store, inventory=None,
            sender=_StubSender(), min_ntpb=1, min_extra=1,
            crypto=CryptoPool() if pipelined else CryptoPool(size=0),
            concurrency=8 if pipelined else 1,
            write_behind=pipelined,
            # the coalescing batch crypto engine (ISSUE 7) rides the
            # fast path only; the baseline stays the per-call path
            crypto_batch=pipelined)
        work0 = _crypto_work_sums()
        # the promoted always-on sampler (observability/health.py) at
        # the old probe's 5 ms cadence; it ALSO feeds the exported
        # event_loop_lag_seconds histogram
        from pybitmessage_tpu.observability import LoopLagProbe
        prober = LoopLagProbe(0.005)
        prober.start()
        proc.start()
        t0 = time.perf_counter()
        for p in payloads:
            await proc.queue.put(p)
        while proc.pending():
            await asyncio.sleep(0.002)
        await proc.stop()       # final write-behind drain is in-scope
        dt = max(time.perf_counter() - t0, 1e-9)
        await prober.stop()
        delivered = len(store.inbox())
        db.close()
        work1 = _crypto_work_sums()
        delta = {k: work1[k] - work0[k] for k in work1}
        # combined decrypt+sig_verify WORK time for this run: the batch
        # engine's drain-execution seconds on the fast path, the
        # per-call stage seconds on the baseline (coalesce wait and
        # queueing excluded from both)
        crypto_work = (delta["batch_decrypt"] + delta["batch_verify"]
                       if pipelined else
                       delta["stage_decrypt"] + delta["stage_sig_verify"])
        engine = proc.crypto.batch
        return {
            "wall_s": round(dt, 3),
            "objects_per_s": round(len(payloads) / dt, 1),
            "delivered": delivered,
            "crypto_work_s": round(crypto_work, 4),
            "max_loop_lag_ms": round(prober.max_lag * 1e3, 2),
            # which crypto rung actually served the drains (ISSUE 13):
            # tpu / native / pure, None when no drain ran
            "crypto_rung": engine.last_path if engine else "per-call",
        }

    async def run_e2e_slab() -> dict:
        """ROADMAP item 3 remnant (ISSUE 12 satellite): the END-TO-END
        path — real BMConnection framing over an in-memory stream
        (pooled zero-copy buffers) -> slab-store inventory add ->
        pipelined ObjectProcessor with the batch crypto engine ->
        message store.  The number reported is socket-to-store
        objects/s with the slab backend in the loop."""
        from pybitmessage_tpu.models.packet import pack_packet
        from pybitmessage_tpu.network.connection import BMConnection
        from pybitmessage_tpu.network.pool import NodeContext
        from pybitmessage_tpu.storage import SlabStore
        from pybitmessage_tpu.storage.knownnodes import KnownNodes

        class _NullWriter:
            def write(self, b):
                pass

            async def drain(self):
                pass

            def close(self):
                pass

            async def wait_closed(self):
                pass

            def get_extra_info(self, *a, **k):
                return None

        db = Database()
        store = MessageStore(db)
        proc = ObjectProcessor(
            keystore=ks, store=store, inventory=None,
            sender=_StubSender(), min_ntpb=1, min_extra=1,
            crypto=CryptoPool(), concurrency=8, write_behind=True,
            crypto_batch=True)

        class _ForwardPool:
            """Connection -> processor bridge (the Node._pump_objects
            role, minus the node)."""

            def __init__(self, ctx):
                self.ctx = ctx
                self.reconciler = None
                self.received = 0

            def object_received(self, h, header, payload, source):
                self.received += 1
                proc.queue.put_nowait(bytes(payload))

            def connection_closed(self, conn):
                pass

            def established(self):
                return []

        slab = SlabStore(None)
        ctx = NodeContext(inventory=slab, knownnodes=KnownNodes(None),
                          pow_ntpb=1, pow_extra=1, ingest_high=0)
        pool = _ForwardPool(ctx)
        reader = asyncio.StreamReader()
        conn = BMConnection(pool, reader, _NullWriter(), outbound=False,
                            host="bench", port=0)
        conn.fully_established = True
        conn.remote_protocol = 3
        frames = [pack_packet("object", p) for p in payloads]
        proc.start()
        t0 = time.perf_counter()
        for f in frames:
            reader.feed_data(f)
            await conn._read_packet()
        while proc.pending():
            await asyncio.sleep(0.002)
        await proc.stop()
        dt = max(time.perf_counter() - t0, 1e-9)
        delivered = len(store.inbox())
        db.close()
        assert pool.received == len(payloads), (
            "framing delivered %d of %d" % (pool.received,
                                            len(payloads)))
        assert len(slab) == len(payloads)
        assert delivered == for_us, (
            "slab e2e delivered %d of %d" % (delivered, for_us))
        return {
            "backend": "slab",
            "objects_per_s": round(len(payloads) / dt, 1),
            "wall_s": round(dt, 3),
            "delivered": delivered,
            "slab_objects": len(slab),
        }

    async def run_wide_host(n_idents: int, n_objects: int) -> dict:
        """ROADMAP item 3 remnant (ISSUE 14 satellite): the wide-host
        thousands-of-identities variant THROUGH THE ROLE-SPLIT PATH —
        a real edge Node (TCP listener, zero-copy framing, PoW
        verify) handing objects over role IPC to a real relay Node
        whose keystore holds ``n_idents`` identities, slab-backed,
        with the wavefront trial-decrypt fan-out sweeping every
        candidate key on the native thread pool.  The reported figure
        is socket-to-inbox objects/s with delivery complete."""
        from pybitmessage_tpu.core.node import Node

        relay = Node(None, port=0, listen=False, test_mode=True,
                     tls_enabled=False, udp_enabled=False,
                     role="relay", role_ipc_listen="127.0.0.1:0",
                     inventory_backend="slab")
        idents = [relay.keystore.create_random("wide %d" % i)
                  for i in range(n_idents)]
        for ident in idents:
            ident.nonce_trials_per_byte = 1
            ident.extra_bytes = 1
        # the wavefront ECDH sweep is the workload: fan it across the
        # hardware threads (cryptonativethreads analog)
        engine = relay.processor.crypto.batch
        if engine is not None:
            engine.num_threads = os.cpu_count() or 1
        payloads, wide_for_us = _build_wire_msgs(
            n_objects, recipients=idents, foreign_frac=0.1)
        await relay.start()
        edge = Node(None, port=0, listen=True, test_mode=True,
                    tls_enabled=False, udp_enabled=False, role="edge",
                    role_ipc_connect="127.0.0.1:%d"
                    % relay.role_runtime.listen_port)
        await edge.start()
        client = await _RoleWireClient().connect(edge.pool.listen_port)
        t0 = time.perf_counter()
        await client.send_objects(payloads)
        deadline = time.perf_counter() + (600 if not smoke else 120)
        delivered = 0
        while time.perf_counter() < deadline:
            delivered = len(relay.store.inbox())
            if delivered >= wide_for_us:
                break
            await asyncio.sleep(0.05)
        dt = max(time.perf_counter() - t0, 1e-9)
        stored = len(relay.inventory)
        await client.close()
        await edge.stop()
        await relay.stop()
        assert stored == len(payloads), (
            "wide host stored %d of %d" % (stored, len(payloads)))
        assert delivered == wide_for_us, (
            "wide host delivered %d of %d" % (delivered, wide_for_us))
        return {
            "identities": n_idents,
            "objects": n_objects,
            "for_us": wide_for_us,
            "delivered": delivered,
            "wall_s": round(dt, 2),
            "objects_per_s": round(n_objects / dt, 1),
            "zero_objects_lost": len(payloads) - stored,
            "crypto_rung": engine.last_path if engine else "per-call",
        }

    with _attributed("ingest_storm") as pipe_att:
        pipe = asyncio.run(run(True))
    pipe["attribution"] = pipe_att
    e2e_slab = asyncio.run(run_e2e_slab())
    # full mode: 1000 identities is the "wide host" bar; the measured
    # rate is ECDH-bound (a foreign msg costs one trial decrypt per
    # candidate key — linear in keyring size), which is the
    # quantified motivation for per-address filter digests / light
    # clients (ROADMAP item 4's remaining piece)
    with _attributed("ingest_storm_wide_host") as wh_att:
        wide_host = asyncio.run(run_wide_host(
            *((32, 96) if smoke else (1000, 1000))))
    # the continuous-attribution consistency check against the PR 14
    # bench finding: the wide-host run IS ECDH-bound, so the sampler
    # must name crypto as the dominant subsystem (full mode asserts;
    # the smoke band guards crypto_share in perfguard)
    wide_host["attribution"] = wh_att
    if not smoke:
        assert wh_att.get("dominant_subsystem") == "crypto", (
            "wide_host attribution names %r dominant, expected the "
            "ECDH-bound crypto subsystem (shares: %r)"
            % (wh_att.get("dominant_subsystem"),
               wh_att.get("by_subsystem")))
    # honest pre-PR baseline: no key cache, and no native batch engine
    # either — the inline path runs the exact per-call ladder the code
    # before this engine ran (`cryptography` EVP calls where installed,
    # the pure-Python tier otherwise)
    from pybitmessage_tpu.crypto.native import set_native_enabled
    set_key_cache(False)
    set_native_enabled(False)
    try:
        inline = asyncio.run(run(False))
    finally:
        set_key_cache(True)
        set_native_enabled(True)
    assert pipe["delivered"] == for_us, (
        "pipelined run delivered %d of %d" % (pipe["delivered"], for_us))
    assert inline["delivered"] == for_us, (
        "inline run delivered %d of %d" % (inline["delivered"], for_us))
    if not smoke:
        # acceptance: the event loop is never blocked > 50 ms by
        # crypto or SQL on the fast path
        assert pipe["max_loop_lag_ms"] < 50.0, (
            "event loop blocked %.1f ms" % pipe["max_loop_lag_ms"])
    from pybitmessage_tpu.crypto.keys import have_openssl
    from pybitmessage_tpu.crypto.native import get_native
    return {
        "objects": objects, "identities": identities,
        "mix": {"for_us": for_us, "foreign": objects - for_us},
        "pipelined": pipe, "inline_baseline": inline,
        # device-telemetry plane cost + zero-loss on the PR 1 harness
        # shape (ISSUE 16; perfguard-banded like the sampler above)
        "device_telemetry": _bench_device_telemetry(),
        # socket -> batch crypto -> slab store, end to end (ISSUE 12
        # satellite; ROADMAP item 3 remnant)
        "end_to_end_slab": e2e_slab,
        # the wide-host thousands-of-identities variant through the
        # role-split path (ISSUE 14 satellite; closes the item 3
        # remnant): edge Node -> role IPC -> relay Node with the full
        # wavefront trial-decrypt sweep per foreign object
        "wide_host": wide_host,
        # keyring-scaling sweep (ISSUE 17): warm-path flatness from
        # the negative screen + transposed drain shape as the keyring
        # grows two orders of magnitude
        "keyring_sweep": _bench_keyring_sweep(smoke),
        # continuous-profiler attribution over the pipelined run
        # (ISSUE 15): subsystem CPU shares + the sampler's own <2%
        # overhead fraction, perfguard-banded
        "attribution": pipe_att,
        "speedup_vs_inline": round(
            pipe["objects_per_s"] / max(inline["objects_per_s"], 1e-9), 2),
        # acceptance (ISSUE 7): the batch engine's combined
        # decrypt+sig_verify work time vs the per-call baseline's
        # (pre-engine ladder: openssl where installed, else pure)
        "crypto_backend": "native" if get_native().available else (
            "openssl" if have_openssl() else "pure"),
        "inline_backend": "openssl" if have_openssl() else "pure",
        # the ladder rung (tpu/native/pure) the pipelined run's drains
        # actually landed on (ISSUE 13; docs/crypto.md)
        "crypto_rung": pipe.get("crypto_rung"),
        "crypto_stage_speedup": round(
            inline["crypto_work_s"] / max(pipe["crypto_work_s"], 1e-9),
            2),
        "decrypt_fanout_p50": round(
            (REGISTRY.get("crypto_decrypt_fanout_size") or
             _NullHist()).percentile(0.5), 1),
        "stage_latency": _ingest_stage_stats(),
        "write_behind": {
            "flushes": int(REGISTRY.sample(
                "storage_write_behind_flushes_total", {"result": "ok"})),
            "rows_per_flush_p90": round(
                (REGISTRY.get("storage_write_behind_flush_size") or
                 _NullHist()).percentile(0.9), 1),
        },
    }


def _bench_zero_copy_framing(objects: int = 400, dup_factor: int = 3,
                             smoke: bool = False) -> dict:
    """Zero-copy packet path (ISSUE 11 tentpole a): a duplicate-heavy
    object flood through the REAL ``BMConnection`` framing loop over
    an in-memory stream — pooled-buffer fills, checksum/parse/PoW/
    duplicate checks over memoryviews, materialize only for new
    objects.

    The proof metric is ``copies_per_payload_byte``: bytes counted
    into ``ingest_bytes_copied_total`` divided by payload bytes
    received.  The pre-PR path joined chunk lists and allocated a
    ``bytes`` payload per packet — >= 2.0 by construction.  The pooled
    path pays 1.0 (fill) plus one materialize per *unique* object:
    ~1.33 at dup factor 3, machine-independent and perfguard-banded.
    """
    import asyncio

    from pybitmessage_tpu.models.objects import serialize_object
    from pybitmessage_tpu.models.packet import pack_packet
    from pybitmessage_tpu.models.pow_math import pow_target
    from pybitmessage_tpu.network.connection import BMConnection
    from pybitmessage_tpu.network.pool import NodeContext
    from pybitmessage_tpu.pow.dispatcher import python_solve
    from pybitmessage_tpu.storage import SlabStore
    from pybitmessage_tpu.storage.knownnodes import KnownNodes
    from pybitmessage_tpu.utils.hashes import sha512 as _sha512

    class _NullWriter:
        def write(self, b):
            pass

        async def drain(self):
            pass

        def close(self):
            pass

        async def wait_closed(self):
            pass

        def get_extra_info(self, *a, **k):
            return None

    class _SinkPool:
        def __init__(self, ctx):
            self.ctx = ctx
            self.reconciler = None
            self.received = 0

        def object_received(self, h, header, payload, source):
            self.received += 1

        def connection_closed(self, conn):
            pass

        def established(self):
            return []

    ttl = 3600
    expires = int(time.time()) + ttl

    def build(i: int) -> bytes:
        sans = serialize_object(expires, 2, 1, 1,
                                b"%06d" % i + b"Z" * 96)[8:]
        target = pow_target(len(sans) + 8, ttl, 1, 1, clamp=False)
        nonce, _ = python_solve(_sha512(sans), target)
        return nonce.to_bytes(8, "big") + sans

    payloads = [build(i) for i in range(objects)]
    frames = [pack_packet("object", p) for p in payloads]

    async def run() -> dict:
        ctx = NodeContext(inventory=SlabStore(None),
                          knownnodes=KnownNodes(None),
                          pow_ntpb=1, pow_extra=1, ingest_high=0)
        pool = _SinkPool(ctx)
        reader = asyncio.StreamReader()
        conn = BMConnection(pool, reader, _NullWriter(), outbound=False,
                            host="bench", port=0)
        conn.fully_established = True
        conn.remote_protocol = 3

        def copied_total() -> float:
            return sum(REGISTRY.sample("ingest_bytes_copied_total",
                                       {"stage": s}) or 0.0
                       for s in ("fill", "materialize"))

        copied0 = copied_total()
        payload_bytes = 0
        n_frames = 0
        t0 = time.perf_counter()
        # every object arrives dup_factor times, interleaved — the
        # flooding-overlay arrival pattern (one copy per ~sqrt(N)
        # peers); feed in batches so the reader buffer stays bounded
        for rep in range(dup_factor):
            for f, p in zip(frames, payloads):
                reader.feed_data(f)
                payload_bytes += len(p)
                n_frames += 1
                await conn._read_packet()
        dt = max(time.perf_counter() - t0, 1e-9)
        copied = copied_total() - copied0
        assert pool.received == objects, (
            "framing delivered %d of %d unique objects"
            % (pool.received, objects))
        assert len(ctx.inventory) == objects
        return {
            "objects": objects, "dup_factor": dup_factor,
            "frames": n_frames,
            "frames_per_s": round(n_frames / dt, 1),
            "payload_bytes": payload_bytes,
            "bytes_copied": int(copied),
            # THE band: >= 2.0 on the pre-PR join-and-allocate path,
            # 1 + 1/dup_factor (+ header noise) on the pooled path
            "copies_per_payload_byte": round(copied / payload_bytes, 4),
            "copies_per_object": round(copied / n_frames, 1),
        }

    return asyncio.run(run())


def _bench_slab_store(objects: int = 4000, smoke: bool = False,
                      root=None) -> dict:
    """Sharded slab store at retention scale (ISSUE 11 tentpole b/c):
    preload an N-object inventory (full mode: 10M — the never-run
    headline's store), then measure sustained mixed ingest
    (add + contains + hot/disk reads) THROUGH two TTL compaction
    cycles driven by an injected clock, sampling per-op latency.

    Full-mode acceptance: sustained >= 100k objects/s, p99 flat
    across the compaction cycles (whole-slab drops — no DELETE-scan
    stalls), the always-on loop-lag probe < 50 ms, zero objects lost.
    """
    import asyncio
    import shutil
    import tempfile

    from pybitmessage_tpu.storage import SlabStore

    bucket_seconds = 600
    # bucket-aligned base time so the two expiry waves land in exactly
    # the two buckets the compaction cycles drop
    now = (int(time.time()) // bucket_seconds) * bucket_seconds
    fake_now = [now]
    tmp = None
    if root is None and not smoke:
        tmp = root = tempfile.mkdtemp(prefix="bmtpu-slab-bench-")
    store = SlabStore(root, slab_max_bytes=4 << 20,
                      bucket_seconds=bucket_seconds,
                      clock=lambda: fake_now[0])

    def mkhash(i: int) -> bytes:
        return b"SLAB" + i.to_bytes(12, "big") + i.to_bytes(16, "little")

    payload = b"P" * 140            # a small msg-object's ballpark
    from pybitmessage_tpu.models.constants import EXPIRES_GRACE
    # preload: 1/4 of the store expires in each of the first two
    # bucket windows (feeding the compaction cycles), the rest lives on
    expiries = (now + bucket_seconds // 2,
                now + bucket_seconds + bucket_seconds // 2,
                now + 12 * bucket_seconds, now + 18 * bucket_seconds)

    try:
        t0 = time.perf_counter()
        for i in range(objects):
            store.add(mkhash(i), 2, 1, payload,
                      expiries[i & 3], b"")
        preload_dt = max(time.perf_counter() - t0, 1e-9)
        assert len(store) == objects

        ingest_n = max(objects // 50, 1000)
        lat_ms: dict[str, list[float]] = {}

        cold_ms: list[float] = []

        async def phase(name: str, base: int) -> float:
            """Mixed sustained ingest — the shape the loop-lag bar
            guards: add + dup-check + hot reads of just-relayed
            objects (the sync-push/getdata shape the pinned hot set
            exists for).  Latency-sampled every 32 ops; yields to the
            loop per slice so the lag probe sees storage stalls.
            Cold deep-history reads are measured separately below —
            they are the getdata-cold-serve path, not the ingest
            path, and a pread against a write-pressured disk
            legitimately costs tens of ms."""
            samples = lat_ms.setdefault(name, [])
            t0 = time.perf_counter()
            for i in range(base, base + ingest_n):
                if i % 32 == 0:
                    op0 = time.perf_counter()
                h = mkhash(1_000_000_000 + i)
                store.add(h, 2, 1, payload, fake_now[0] + 7200, b"")
                assert h in store
                if i % 7 == 0:      # hot read: a just-relayed object
                    store[mkhash(1_000_000_000 + max(base, i - 64))]
                if i % 32 == 0:
                    samples.append((time.perf_counter() - op0) * 1e3)
                if i % 512 == 0:
                    await asyncio.sleep(0)
            dt = max(time.perf_counter() - t0, 1e-9)

            def cold_reads():
                # deep history, evicted from the hot set: the disk
                # path stays honest, timed per read
                for j in range(base, base + ingest_n, ingest_n // 64):
                    r0 = time.perf_counter()
                    store[mkhash(1_000_000_000 + j)]
                    cold_ms.append((time.perf_counter() - r0) * 1e3)
            await asyncio.to_thread(cold_reads)
            return dt

        # at 10M retained objects cyclic-GC passes cost 400-900 ms of
        # stop-the-world (measured: worst single add 920 ms under
        # normal GC, 471 ms under gc.freeze, 35 ms with collection
        # disabled) — far over the 50 ms loop-lag bar.  Disable
        # collection through the measured window, exactly as a
        # latency-critical deployment at retention scale must
        # (docs/storage.md); restored below so later bench sections
        # see normal GC.  Reference cycles still free by refcount;
        # nothing here leaks.
        import gc
        gc.collect()
        gc.disable()
        # with storage I/O on background threads, the loop's residual
        # lag is GIL handoff: at the default 5 ms switch interval a
        # convoy of busy worker threads (drainer + seal finalizes +
        # off-loop clean) can starve the loop for several intervals
        # in a row.  1 ms bounds each handoff — the same tuning a
        # latency-critical asyncio+threads deployment ships with.
        import sys as _sys
        prev_switch = _sys.getswitchinterval()
        _sys.setswitchinterval(0.001)

        async def run() -> dict:
            from pybitmessage_tpu.observability import LoopLagProbe
            prober = LoopLagProbe(0.005)
            prober.start()
            dts = [await phase("pre_compaction", 0)]
            # cycle 1: the first expiry wave's bucket falls past grace
            # (cleans run off-loop exactly as the Cleaner worker does)
            fake_now[0] = now + bucket_seconds + EXPIRES_GRACE + 20
            await asyncio.to_thread(store.clean)
            dts.append(await phase("post_cycle1", ingest_n))
            # cycle 2: the second wave's bucket goes too
            fake_now[0] = now + 2 * bucket_seconds + EXPIRES_GRACE + 20
            await asyncio.to_thread(store.clean)
            dts.append(await phase("post_cycle2", 2 * ingest_n))
            await prober.stop()
            return {"dts": dts, "max_lag_ms": prober.max_lag * 1e3}

        try:
            r = asyncio.run(run())
        finally:
            gc.enable()
            _sys.setswitchinterval(prev_switch)
        live = len(store)
        # zero loss: every preloaded survivor + every ingested object
        # is still present and readable
        expected = objects - (objects + 3) // 4 - (objects + 2) // 4 \
            + 3 * ingest_n
        assert live == expected, (
            "slab store holds %d objects, expected %d" % (live, expected))
        spot = mkhash(1_000_000_000 + ingest_n + 5)
        assert store[spot].payload == payload

        def p99(xs: list[float]) -> float:
            xs = sorted(xs)
            return xs[min(int(len(xs) * 0.99), len(xs) - 1)]

        p99s = {k: round(p99(v), 4) for k, v in lat_ms.items()}
        cold_p99 = round(p99(cold_ms), 3) if cold_ms else None
        flat = max(p99s["post_cycle1"], p99s["post_cycle2"]) / max(
            p99s["pre_compaction"], 1e-9)
        sustained = 3 * ingest_n / sum(r["dts"])
        out = {
            "preloaded_objects": objects,
            "preload_objects_per_s": round(objects / preload_dt, 1),
            "sustained_objects_per_s": round(sustained, 1),
            "ingested_objects": 3 * ingest_n,
            "op_p99_ms": p99s,
            "cold_read_p99_ms": cold_p99,
            "p99_flat_ratio": round(flat, 3),
            "compaction_cycles": 2,
            "dropped_slabs": int(REGISTRY.sample(
                "slab_store_dropped_slabs_total") or 0),
            "max_loop_lag_ms": round(r["max_lag_ms"], 2),
            "zero_objects_lost": True,   # the len/readback asserts above
            "backing": "disk" if store.root is not None else "ram",
        }
        if not smoke:
            # acceptance (ISSUE 11): the headline numbers are asserted,
            # not just reported.  The 100k bar is calibrated for a wide
            # IDLE host (this store measured 119.5k on a 24-core shared
            # container); BMTPU_SLAB_RATE_FLOOR lowers it on loaded or
            # narrow hosts so the gate flags regressions, not host
            # contention.
            floor = float(os.environ.get("BMTPU_SLAB_RATE_FLOOR",
                                         "100000"))
            assert sustained >= floor, (
                "sustained %.0f objects/s < floor %.0f"
                % (sustained, floor))
            # the store does no event-loop I/O (drains/seals run on
            # background threads); the residual lag is GIL/scheduler
            # jitter plus the bench's own cold preads, which on a busy
            # shared host hovers around the bar — tunable like the
            # rate floor
            lag_ceil = float(os.environ.get("BMTPU_SLAB_LAG_CEIL_MS",
                                            "50"))
            assert r["max_lag_ms"] < lag_ceil, (
                "event loop blocked %.1f ms through compaction "
                "(ceiling %.0f)" % (r["max_lag_ms"], lag_ceil))
            assert flat < 5.0, (
                "p99 grew %.1fx across TTL compaction cycles" % flat)
        return out
    finally:
        # quiesce the background drain/seal threads (what node.stop's
        # inventory.flush() does) BEFORE tearing the tree down —
        # rmtree under live finalizes manufactures phantom I/O errors
        try:
            store.flush()
        except Exception:
            logger_ = __import__("logging").getLogger("bench")
            logger_.exception("slab store flush at teardown failed")
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


class _NullHist:
    count = 0

    def percentile(self, q):
        return 0.0


# -- set-reconciliation sync (ISSUE 5) ---------------------------------------

def _bench_pow_farm(tenants: int = 8, seconds: float = 6.0,
                    smoke: bool = False) -> dict:
    """PoW solver farm (ISSUE 12 tentpole): N tenants flooding one
    farm daemon at ~2x capacity overload through the REAL wire
    protocol, scheduler and journal (docs/pow_farm.md).

    Measured:

    - **fairness spread** — per-tenant goodput under WDRR with equal
      weights; acceptance: max/min ratio <= 1.5 (full mode asserts);
    - **lane latency split** — interactive-lane p99 queue wait vs
      bulk-lane p99 under overload; acceptance: >= 5x lower (full);
    - **admission behavior** — accepted vs rejected-with-retry-after
      counts while the queue stays bounded (reject-before-melt);
    - **zero job loss** — every submitted job is eventually solved and
      host-verified, across seeded ``farm.*`` chaos AND a farm-daemon
      kill/restart mid-load (journal adoption + restart dedupe), both
      full-mode only.

    Capacity is pinned by throttling the real dispatcher (a fixed
    per-job device cost), so overload and the latency split are
    machine-independent; solved nonces are real ``python_solve``
    output and every result is re-verified client-side.
    """
    import asyncio
    import tempfile
    import threading

    from pybitmessage_tpu.powfarm import (FarmClient, FarmError,
                                          FarmJournal, FarmRejected,
                                          FarmScheduler, FarmServer)
    from pybitmessage_tpu.powfarm.protocol import (LANE_BULK,
                                                   LANE_INTERACTIVE)
    from pybitmessage_tpu.pow.dispatcher import (PowDispatcher,
                                                 host_trial)
    from pybitmessage_tpu.resilience import CHAOS

    per_job = 0.001              # throttled device cost: 1 ms/job
    capacity = 1.0 / per_job     # ~1000 jobs/s
    batch_max = 8                # small batches keep interactive
                                 # inflight-wait low (the lane split)
    max_wait = 5.0               # global backlog ceiling — set ABOVE
                                 # the quota-bound working set so the
                                 # PER-TENANT quotas (not first-come
                                 # global admission) allocate capacity
                                 # under overload; that is what makes
                                 # goodput fair instead of race-lucky
    quota = 64                   # per-tenant queued-job cap — the
                                 # fair-share allocator under overload
    bulk_batch = 128             # jobs per client submission: each
                                 # tenant OFFERS 2x its quota, so
                                 # admission must reject-with-retry-
                                 # after half of every submission
                                 # sweep (the 2x overload behavior)
    easy = 1 << 62               # ~4 trials/job
    if smoke:
        seconds = 2.5

    class _Throttled:
        """The breaker-supervised ladder with a pinned per-job cost."""

        def __init__(self):
            self.inner = PowDispatcher(use_tpu=False, use_native=False)
            self.last_backend = "throttled-ladder"

        def solve_batch(self, items, **kw):
            time.sleep(per_job * len(items))
            return self.inner.solve_batch(items, **kw)

    def job_key(tenant: str, i: int) -> bytes:
        return hashlib.sha512(b"farm %s %d" % (tenant.encode(), i)
                              ).digest()

    adm0 = {o: REGISTRY.sample("farm_admission_total", {"outcome": o})
            for o in ("accepted", "backlog", "quota", "rate")}
    collisions0 = REGISTRY.sample("farm_adopt_collisions_total")
    wait_hist = REGISTRY.get("farm_queue_wait_seconds")
    tenant_names = ["tenant-%d" % t for t in range(tenants)]
    goodput0 = {t: REGISTRY.sample(
        "farm_tenant_solved_total", {"tenant": t, "lane": "bulk"})
        for t in tenant_names}

    tmp = None
    journal_path = ":memory:"
    if not smoke:
        tmp = tempfile.NamedTemporaryFile(
            prefix="bmtpu-farmjournal-", suffix=".dat", delete=False)
        tmp.close()
        os.unlink(tmp.name)
        journal_path = tmp.name

    async def run() -> dict:
        from pybitmessage_tpu.powfarm import TenantConfig
        tenant_policy = TenantConfig(quota=quota)
        journal = FarmJournal(journal_path)
        server = FarmServer(
            _Throttled(), journal=journal,
            scheduler=FarmScheduler(capacity_hint=capacity,
                                    max_wait=max_wait,
                                    default_config=tenant_policy),
            batch_max=batch_max, window=0.002)
        await server.start()
        port = server.listen_port
        stop_flag = threading.Event()
        solved = {}              # tenant -> verified results
        attempted = {"n": 0}
        lost = {"n": 0}
        lock = threading.Lock()

        def submit_until_done(client, items, lane, deadline_s) -> bool:
            """Retry one batch until every job lands (reject backoff,
            reconnect-after-restart, recent-cache recovery); the
            zero-loss accounting counts a job done only after a
            client-side host re-verify."""
            for _ in range(200):
                with lock:
                    attempted["n"] += len(items)
                try:
                    results = client.solve_batch(
                        items, lane=lane, deadline_s=deadline_s)
                except FarmRejected as exc:
                    # top up at HALF the hinted backoff: the tenant's
                    # queue refills before it runs dry, so the DRR
                    # share (not refill timing) sets goodput
                    time.sleep(min(max(exc.retry_after / 2, 0.05),
                                   2.0))
                    continue
                except FarmError:
                    time.sleep(0.05)   # farm restarting / chaos
                    continue
                for (ih, target), (nonce, _) in zip(items, results):
                    assert host_trial(nonce, ih) <= target
                return True
            return False

        def bulk_flooder(tenant: str) -> None:
            client = FarmClient("127.0.0.1", port, tenant=tenant,
                                timeout=20.0)
            done = 0
            i = 0
            while not stop_flag.is_set():
                items = [(job_key(tenant, i + k), easy)
                         for k in range(bulk_batch)]
                if submit_until_done(client, items, LANE_BULK, 20.0):
                    done += len(items)
                else:
                    with lock:
                        lost["n"] += len(items)
                i += bulk_batch
            client.close()
            solved[tenant] = done

        def interactive_user(name: str) -> None:
            client = FarmClient("127.0.0.1", port, tenant=name,
                                timeout=10.0)
            done = 0
            i = 0
            while not stop_flag.is_set():
                if submit_until_done(
                        client, [(job_key(name, i), easy)],
                        LANE_INTERACTIVE, 10.0):
                    done += 1
                else:
                    with lock:
                        lost["n"] += 1
                i += 1
                time.sleep(0.025)
            client.close()
            solved[name] = done

        threads = [threading.Thread(target=bulk_flooder,
                                    args=("tenant-%d" % t,))
                   for t in range(tenants)]
        threads += [threading.Thread(target=interactive_user,
                                     args=("iuser-%d" % u,))
                    for u in range(2)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()

        restart_info = None
        if smoke:
            await asyncio.sleep(seconds)
        else:
            # phase A: clean overload (fairness + lane-split window)
            await asyncio.sleep(seconds * 0.5)
            # phase B: seeded farm.* chaos riding the live load
            CHAOS.seed(1234)
            CHAOS.arm("farm.accept", probability=0.05)
            CHAOS.arm("farm.dispatch", probability=0.05)
            CHAOS.arm("farm.result", probability=0.02)
            await asyncio.sleep(seconds * 0.25)
            CHAOS.disarm("farm.accept")
            CHAOS.disarm("farm.dispatch")
            CHAOS.disarm("farm.result")
            # phase C: kill the farm daemon mid-load and restart it on
            # the same port with the same on-disk journal — clients
            # reconnect, journaled jobs are adopted, re-submissions
            # dedupe onto the recovered jobs
            await server.stop()
            journal.close()
            journal = FarmJournal(journal_path)
            recovered = journal.pending_count()
            server = FarmServer(
                _Throttled(), journal=journal,
                scheduler=FarmScheduler(capacity_hint=capacity,
                                        max_wait=max_wait,
                                        default_config=tenant_policy),
                port=port, batch_max=batch_max, window=0.002)
            await server.start()
            restart_info = {"journal_recovered": recovered}
            await asyncio.sleep(seconds * 0.25)

        stop_flag.set()
        while any(t.is_alive() for t in threads):
            await asyncio.sleep(0.05)
        wall = time.perf_counter() - t0
        # every accepted job completed -> the journal must drain
        for _ in range(100):
            if journal.pending_count() == 0:
                break
            await asyncio.sleep(0.05)
        pending_at_end = journal.pending_count()
        await server.stop()
        journal.close()
        if restart_info is not None:
            restart_info["journal_drained"] = pending_at_end == 0

        # fairness is measured SERVER-side (jobs the scheduler
        # actually drained per tenant over the common window) — the
        # client-side batch counts quantize goodput to whole batches
        bulk_counts = {t: int(REGISTRY.sample(
            "farm_tenant_solved_total", {"tenant": t, "lane": "bulk"})
            - goodput0[t]) for t in tenant_names}
        total = sum(solved.values())
        ratio = (max(bulk_counts.values())
                 / max(min(bulk_counts.values()), 1))
        p99 = {}
        for lane in (LANE_INTERACTIVE, LANE_BULK):
            child = wait_hist.labels(lane=lane)
            p99[lane] = child.percentile(0.99)
        split = p99[LANE_BULK] / max(p99[LANE_INTERACTIVE], 1e-6)
        adm = {o: int(REGISTRY.sample("farm_admission_total",
                                      {"outcome": o}) - adm0[o])
               for o in adm0}
        rejected = sum(adm[o] for o in ("backlog", "quota", "rate"))
        out = {
            "tenants": tenants,
            "seconds": round(wall, 2),
            "capacity_jobs_per_s": capacity,
            "client_verified_jobs": total,
            "server_solved_bulk": sum(bulk_counts.values()),
            "solved_per_s": round(
                (adm["accepted"]) / wall, 1),
            "attempted_per_s": round(attempted["n"] / wall, 1),
            # how hard admission had to push back: submissions the
            # farm refused per submission it accepted, plus one —
            # ~2.0 at a sustained 2x offered overload
            "overload_factor": round(
                (adm["accepted"] + rejected)
                / max(adm["accepted"], 1), 2),
            "fairness": {
                "per_tenant_bulk": dict(sorted(bulk_counts.items())),
                "max_min_ratio": round(ratio, 3),
            },
            "lane_wait_p99_ms": {
                "interactive": round(p99[LANE_INTERACTIVE] * 1e3, 2),
                "bulk": round(p99[LANE_BULK] * 1e3, 2),
            },
            "lane_p99_split": round(split, 2),
            "admission": adm,
            "adopt_collisions": int(REGISTRY.sample(
                "farm_adopt_collisions_total") - collisions0),
            "lost_jobs": lost["n"],
            "zero_job_loss": lost["n"] == 0,
        }
        if restart_info is not None:
            out["restart"] = restart_info
            out["chaos_fired"] = {
                s: int(REGISTRY.sample("chaos_injected_total",
                                       {"site": s}))
                for s in ("farm.accept", "farm.dispatch",
                          "farm.result")}
        return out

    try:
        from pybitmessage_tpu.observability.profiling import \
            farm_tenant_costs
        cpu0 = {t: v["value"]
                for t, v in farm_tenant_costs().items()}
        with _attributed("pow_farm") as farm_att:
            out = asyncio.run(run())
        # per-tenant CPU attribution over this run (ISSUE 15): the
        # farm splits each batch's solve seconds by tenant job share
        # (farm_tenant_cpu_seconds_total) — the deltas are the run's
        # own cost table
        tenant_cpu = {
            t: round(v["value"] - cpu0.get(t, 0.0), 4)
            for t, v in farm_tenant_costs().items()}
        accounted = sum(tenant_cpu.values())
        farm_att["tenant_cpu_s"] = dict(sorted(tenant_cpu.items()))
        farm_att["tenant_cpu_accounted_s"] = round(accounted, 3)
        out["attribution"] = farm_att
    finally:
        if tmp is not None and os.path.exists(tmp.name):
            os.unlink(tmp.name)
    # acceptance bars (ISSUE 12): asserted in full mode, perfguard
    # bands cover the smoke trend
    assert out["zero_job_loss"], (
        "%d farm job(s) lost" % out["lost_jobs"])
    if not smoke:
        assert out["fairness"]["max_min_ratio"] <= 1.5, (
            "tenant goodput spread %.2f > 1.5"
            % out["fairness"]["max_min_ratio"])
        assert out["lane_p99_split"] >= 5.0, (
            "interactive lane only %.1fx better than bulk"
            % out["lane_p99_split"])
        assert out["restart"]["journal_drained"], \
            "journal did not drain after restart"
    return out


def _build_wire_msgs(objects: int, *, ntpb: int = 10, extra: int = 10,
                     ttl: int = 900, stream: int = 1,
                     recipients=None, foreign_frac: float = 1.0,
                     solver=None):
    """Build distinct PoW-valid OBJECT_MSG wire payloads.  With
    ``recipients`` (OwnIdentity list), ``1 - foreign_frac`` of the
    objects address a random recipient (round-robin) and the rest a
    foreign key (trial-decrypt-miss traffic).  Returns
    ``(payloads, for_us)``."""
    from pybitmessage_tpu.crypto import encrypt, priv_to_pub, sign
    from pybitmessage_tpu.crypto.keys import random_private_key
    from pybitmessage_tpu.models import msgcoding
    from pybitmessage_tpu.models.constants import OBJECT_MSG
    from pybitmessage_tpu.models.payloads import (MsgPlaintext,
                                                  get_bitfield,
                                                  object_shell)
    from pybitmessage_tpu.models.pow_math import pow_target
    from pybitmessage_tpu.pow.dispatcher import python_solve
    from pybitmessage_tpu.utils.hashes import sha512 as _sha512
    from pybitmessage_tpu.workers.keystore import KeyStore

    sender = KeyStore().create_random("role bench sender")
    foreign_pub = priv_to_pub(random_private_key())
    expires = int(time.time()) + ttl
    shell = object_shell(expires, OBJECT_MSG, 1, stream)
    solve = solver or python_solve
    payloads, for_us = [], 0
    for i in range(objects):
        miss = (not recipients) or (i % 100) < foreign_frac * 100
        if miss:
            pub, ripe = foreign_pub, b"\x00" * 20
        else:
            r = recipients[i % len(recipients)]
            pub, ripe = r.pub_encryption_key, r.ripe
            for_us += 1
        body = msgcoding.encode_message("role %d" % i, "body %d" % i)
        plain = MsgPlaintext(
            sender_version=sender.version, sender_stream=stream,
            bitfield=get_bitfield(False),
            pub_signing_key=sender.pub_signing_key,
            pub_encryption_key=sender.pub_encryption_key,
            nonce_trials_per_byte=ntpb, extra_bytes=extra,
            dest_ripe=ripe, encoding=2, message=body, ack_data=b"")
        plain.signature = sign(shell + plain.encode_unsigned(),
                               sender.priv_signing)
        sans_nonce = shell + encrypt(plain.encode(), pub)
        target = pow_target(len(sans_nonce) + 8, ttl, ntpb, extra,
                            clamp=False)
        nonce, _ = solve(_sha512(sans_nonce), target)
        payloads.append(nonce.to_bytes(8, "big") + sans_nonce)
    return payloads, for_us


def _build_relay_objects(n: int, *, ntpb: int = 10, extra: int = 10,
                         ttl: int = 900, stream: int = 1,
                         type_: int = 42):
    """Distinct PoW-valid objects of an unknown type — the relay-tier
    bulk workload (a node stores and forwards plenty of objects it
    cannot parse); build cost is one PoW solve each, so floods can be
    large."""
    from pybitmessage_tpu.models.objects import serialize_object
    from pybitmessage_tpu.models.pow_math import pow_target
    from pybitmessage_tpu.pow.dispatcher import python_solve
    from pybitmessage_tpu.utils.hashes import sha512 as _sha512

    expires = int(time.time()) + ttl
    out = []
    for i in range(n):
        body = os.urandom(24) + i.to_bytes(8, "big")
        obj = serialize_object(expires, type_, 1, stream, body)
        target = pow_target(len(obj), ttl, ntpb, extra, clamp=False)
        nonce, _ = python_solve(_sha512(obj[8:]), target)
        out.append(nonce.to_bytes(8, "big") + obj[8:])
    return out


class _RoleWireClient:
    """Minimal raw-socket Bitmessage peer for the role benches:
    version/verack handshake, then object frames at line rate."""

    async def connect(self, port):
        import asyncio

        from pybitmessage_tpu.models.packet import (HEADER_LEN,
                                                    pack_packet,
                                                    unpack_header)
        from pybitmessage_tpu.network.messages import VersionPayload
        self._pack = pack_packet
        self.reader, self.writer = await asyncio.open_connection(
            "127.0.0.1", port)

        async def read_packet():
            header = await self.reader.readexactly(HEADER_LEN)
            command, length, _ = unpack_header(header)
            payload = await self.reader.readexactly(length)
            return command, payload

        self.writer.write(pack_packet("version", VersionPayload(
            remote_port=port, my_port=0, nonce=os.urandom(8),
            services=1).encode()))
        await self.writer.drain()
        got_version = got_verack = False
        while not (got_version and got_verack):
            cmd, _ = await read_packet()
            if cmd == "version":
                got_version = True
                self.writer.write(pack_packet("verack"))
                await self.writer.drain()
            elif cmd == "verack":
                got_verack = True

        async def drain_reads():
            import asyncio as _a
            try:
                while True:
                    await read_packet()
            except (_a.IncompleteReadError, ConnectionError, OSError):
                pass
        import asyncio as _a
        self._pump = _a.create_task(drain_reads())
        return self

    async def send_objects(self, payloads):
        for i, p in enumerate(payloads):
            self.writer.write(self._pack("object", p))
            if i % 64 == 63:
                await self.writer.drain()
        await self.writer.drain()

    async def close(self):
        self._pump.cancel()
        self.writer.close()


def _role_rpc(port, method, *params):
    import base64
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    auth = base64.b64encode(b"bench:bench").decode()
    conn.request("POST", "/", json.dumps(
        {"method": method, "params": list(params), "id": 1}),
        {"Authorization": "Basic " + auth,
         "Content-Type": "application/json"})
    resp = json.loads(conn.getresponse().read())
    conn.close()
    if resp.get("error"):
        raise RuntimeError(str(resp["error"]))
    return resp["result"]


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_role_deployment(payloads, *, edge_procs: int, clients: int,
                         timeout_s: float, relays: int = 1,
                         streams: int = 1) -> dict:
    """Spawn one deployment as REAL daemon subprocesses — fused
    (``edge_procs=0``: one ``role=all`` process subscribing every
    stream) or split (M stream-sharded ``role=relay`` + N
    ``role=edge`` sharing the P2P port via SO_REUSEPORT) — flood it
    over real TCP and measure end-to-end accepted objects/s (wire ->
    framing -> PoW verify -> [role IPC ->] slab inventory), polled
    through the roleStatus API (summed across relay shards)."""
    import asyncio
    import signal
    import subprocess

    p2p_port = _free_port()
    stream_spec = ",".join(str(s + 1) for s in range(streams))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    here = os.path.dirname(os.path.abspath(__file__))

    def spawn(args):
        return subprocess.Popen(
            [sys.executable, "-m", "pybitmessage_tpu", "-t", "--no-udp",
             "--api-user", "bench", "--api-password", "bench"] + args,
            env=env, cwd=here, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)

    procs, api_ports = [], []
    try:
        if edge_procs:
            ipc_ports = [_free_port() for _ in range(relays)]
            # relay i owns stream i+1 (round-robin for streams>relays)
            for i, ipc_port in enumerate(ipc_ports):
                owned = ",".join(str(s + 1) for s in range(streams)
                                 if s % relays == i)
                api_ports.append(_free_port())
                procs.append(spawn(
                    ["-p", "0", "--api-port", str(api_ports[-1]),
                     "--set", "role=relay",
                     "--set", "rolestreams=%s" % owned,
                     "--set", "roleipclisten=127.0.0.1:%d" % ipc_port,
                     "--set", "inventorystorage=slab"]))
            connect = ",".join("127.0.0.1:%d" % p for p in ipc_ports)
            for _ in range(edge_procs):
                procs.append(spawn(
                    ["-p", str(p2p_port), "--no-api",
                     "--set", "role=edge",
                     "--set", "rolestreams=%s" % stream_spec,
                     "--set", "edgeprocs=%d" % edge_procs,
                     "--set", "roleipcconnect=%s" % connect]))
        else:
            api_ports.append(_free_port())
            procs.append(spawn(
                ["-p", str(p2p_port), "--api-port", str(api_ports[0]),
                 "--set", "rolestreams=%s" % stream_spec,
                 "--set", "inventorystorage=slab"]))

        # readiness: every authority's API answers roleStatus, every
        # edge is linked to every relay shard over IPC
        deadline = time.time() + 120
        while True:
            if time.time() > deadline:
                raise RuntimeError("role deployment never became ready")
            for p in procs:
                if p.poll() is not None:
                    raise RuntimeError("role process died during start")
            try:
                ready = 0
                for port in api_ports:
                    status = json.loads(_role_rpc(port, "roleStatus"))
                    if not edge_procs or \
                            len(status["ipc"]["edges"]) == edge_procs:
                        ready += 1
                if ready == len(api_ports):
                    break
            except (OSError, RuntimeError, KeyError):
                pass
            time.sleep(0.2)

        async def drive():
            conns = [await _RoleWireClient().connect(p2p_port)
                     for _ in range(clients)]
            share = (len(payloads) + clients - 1) // clients
            t0 = time.perf_counter()
            await asyncio.gather(*(
                c.send_objects(payloads[i * share:(i + 1) * share])
                for i, c in enumerate(conns)))

            def count_accepted():
                total = 0
                for port in api_ports:
                    status = json.loads(_role_rpc(port, "roleStatus"))
                    total += status["inventoryObjects"]
                return total

            accepted, t_done = 0, None
            deadline = time.perf_counter() + timeout_s
            while time.perf_counter() < deadline:
                accepted = await asyncio.to_thread(count_accepted)
                if accepted >= len(payloads):
                    t_done = time.perf_counter()
                    break
                await asyncio.sleep(0.05)
            if t_done is None:
                t_done = time.perf_counter()
            for c in conns:
                await c.close()
            return accepted, t_done - t0

        accepted, wall = asyncio.run(drive())

        # continuous profiling plane (ISSUE 15): pull each authority
        # daemon's LIVE cost attribution over JSON-RPC — the per-role
        # subsystem CPU shares of the run just measured, plus a
        # profileDump sample proving the dump path end to end
        attribution = []
        for port in api_ports:
            try:
                cost = json.loads(_role_rpc(port, "costStatus"))
                prof = json.loads(_role_rpc(port, "profileDump",
                                            0, "collapsed"))
                attribution.append({
                    "role": cost.get("role"),
                    "samplerRunning": cost["sampler"]["running"],
                    "overheadFrac": cost["sampler"]["overheadFrac"],
                    "subsystems": {
                        k: v["share"]
                        for k, v in cost["cpu"]["subsystems"].items()},
                    "profileSamples": prof.get("samples", 0),
                })
            except (OSError, RuntimeError, KeyError, ValueError,
                    TypeError) as exc:
                # a daemon mid-shutdown can return torn JSON or a
                # partial doc — degrade to a per-port error, never
                # kill the whole role_split section
                attribution.append({"error": repr(exc)[:120]})

        clean = True
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                clean = (p.wait(timeout=30) == 0) and clean
            except subprocess.TimeoutExpired:
                clean = False
                p.kill()
                p.wait()
        return {
            "processes": (relays + edge_procs) if edge_procs else 1,
            "edges": edge_procs,
            "relays": relays if edge_procs else 0,
            "streams": streams,
            "accepted": accepted,
            "lost": len(payloads) - accepted,
            "wall_s": round(wall, 3),
            "objects_per_s": round(accepted / max(wall, 1e-9), 1),
            "clean_shutdown": clean,
            # per-authority-daemon cost attribution, served live over
            # JSON-RPC by the daemons' own continuous profilers
            "attribution": attribution,
        }
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def _bench_role_split(objects: int = 12000, edges: int = 4,
                      relays: int = 2, clients: int = 16,
                      smoke: bool = False) -> dict:
    """Role-split scaling (ISSUE 14 tentpole d; ROADMAP item 4): the
    SAME pre-built object flood through (a) one fused single-process
    node and (b) a stream-sharded multi-process deployment — N edge
    processes sharing one P2P port via SO_REUSEPORT, handing verified
    objects over role IPC to a relay — both through the REAL wire
    path (TCP -> zero-copy framing -> device-batched PoW verify ->
    slab store), end to end as real daemon subprocesses.

    Full mode asserts the headline: >= 2x end-to-end accepted obj/s
    with 4 edge processes vs the fused baseline (the single event
    loop is the documented post-PR-11 ceiling; accept/framing/verify
    parallelize across edge cores while the relay ingests batched IPC
    frames), zero objects lost in either deployment, clean SIGTERM
    shutdowns.  ``BMTPU_ROLE_RATE_FLOOR`` tunes the honest floor on
    loaded hosts (like ``BMTPU_SLAB_RATE_FLOOR``)."""
    if smoke:
        objects, edges, relays, clients = 400, 1, 1, 2
    streams = max(relays, 1)
    t0 = time.perf_counter()
    # per stream shard: 10% real encrypted msg objects (crypto-built)
    # + 90% relay-tier objects of an unknown type (PoW-only build) —
    # the measured path (framing, PoW verify, dedupe, store, IPC,
    # announce) is identical for both, and the mix keeps multi-minute
    # floods affordable.  Streams interleave so every client exercises
    # every shard concurrently (the edge's dynamic stream routing).
    per_stream = []
    for s in range(1, streams + 1):
        share = objects // streams
        msgs, _ = _build_wire_msgs(share // 10, stream=s)
        per_stream.append(
            msgs + _build_relay_objects(share - len(msgs), stream=s))
    payloads = [p for group in zip(*per_stream) for p in group]
    build_s = time.perf_counter() - t0
    timeout_s = 120.0 if smoke else 420.0
    reps = 1 if smoke else 3

    def deploy(**kw):
        """Median-of-reps (honest-timing rules: median, never
        best-of) — each rep is a fresh set of daemon processes."""
        runs = [_run_role_deployment(payloads, clients=clients,
                                     timeout_s=timeout_s,
                                     streams=streams, **kw)
                for _ in range(reps)]
        mid = sorted(runs, key=lambda r: r["objects_per_s"])[reps // 2]
        mid["reps"] = reps
        mid["lost"] = max(r["lost"] for r in runs)
        mid["clean_shutdown"] = all(r["clean_shutdown"] for r in runs)
        return mid

    fused = deploy(edge_procs=0)
    split = deploy(edge_procs=edges, relays=relays)
    ratio = round(split["objects_per_s"]
                  / max(fused["objects_per_s"], 1e-9), 2)
    out = {
        "objects": len(payloads),
        "clients": clients,
        "build_s": round(build_s, 2),
        "fused": fused,
        "split": split,
        "ratio_vs_fused": ratio,
        # lost objects across BOTH deployments — the zero-loss guard
        "zero_objects_lost": fused["lost"] + split["lost"],
    }
    assert fused["lost"] == 0, (
        "fused deployment lost %d objects" % fused["lost"])
    assert split["lost"] == 0, (
        "split deployment lost %d objects" % split["lost"])
    assert fused["clean_shutdown"] and split["clean_shutdown"], \
        "a role process did not exit cleanly on SIGTERM"
    # elastic shard fabric drill (ISSUE 18): replicas, live split
    # under load, kill-a-relay-under-load failover — same wire path,
    # one deployment, three measured phases
    out["rescale"] = _bench_role_rescale(smoke=smoke)
    if not smoke:
        floor = float(os.environ.get("BMTPU_ROLE_RATE_FLOOR", "2.0"))
        out["rate_floor"] = floor
        assert ratio >= floor, (
            "split/fused ratio %.2f below the %.1fx floor (%d edges)"
            % (ratio, floor, edges))
    return out


def _bench_role_rescale(smoke: bool = False) -> dict:
    """Rescale under load (ISSUE 18 tentpole): one deployment of real
    daemon subprocesses, three measured phases.

    Phase 1 (baseline) — relay A owns streams 1+2, relay A2 replicates
    stream 1 (edges fan stream-1 records to both, actively): flood,
    measure end-to-end accepted obj/s.  Phase 2 (split under load) —
    spawn relay B mid-run and ``shardShed`` stream 2 from A to B WHILE
    the flood is in flight: the bucket drain, the mid-drain
    shadow-forward, and the edges' SHARD_UPDATE re-route all race live
    traffic.  Phase 3 (kill a relay under load) — SIGKILL A mid-flood:
    stream 1 fails over to replica A2 (unacked frames requeue and
    reroute), stream 2 already lives on B.

    Zero loss is the hard bar: after phase 3 the SURVIVORS hold every
    flooded object (A2 all of stream 1, B all of stream 2).  Clean
    SIGTERM shutdown is asserted for every process except the
    deliberately murdered primary.  Full mode additionally asserts the
    post-split rate did not collapse (``BMTPU_RESCALE_STEP_FLOOR``,
    default 0.8x the replicated baseline — on a multi-core host the
    split halves A's ingest load, so well above 1x is expected; the
    smoke floor lives in tools/bench_compare.py)."""
    import asyncio
    import signal
    import subprocess

    # smoke phases are sized so each measured wall comfortably clears
    # the 50 ms convergence-poll quantum (rates stay band-guardable)
    n_phase, clients, edge_procs = (300, 2, 1) if smoke else (2500, 8, 2)
    timeout_s = 120.0 if smoke else 420.0
    half = n_phase // 2
    t0 = time.perf_counter()
    floods = []
    for _ in range(3):
        s1 = _build_relay_objects(half, stream=1)
        s2 = _build_relay_objects(half, stream=2)
        floods.append([p for pair in zip(s1, s2) for p in pair])
    build_s = time.perf_counter() - t0

    p2p_port = _free_port()
    ipc_a, ipc_a2, ipc_b = _free_port(), _free_port(), _free_port()
    api_a, api_a2, api_b = _free_port(), _free_port(), _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    here = os.path.dirname(os.path.abspath(__file__))

    def spawn(args):
        return subprocess.Popen(
            [sys.executable, "-m", "pybitmessage_tpu", "-t", "--no-udp",
             "--api-user", "bench", "--api-password", "bench"] + args,
            env=env, cwd=here, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)

    def spawn_relay(api_port, ipc_port, streams):
        return spawn(["-p", "0", "--api-port", str(api_port),
                      "--set", "role=relay",
                      "--set", "rolestreams=%s" % streams,
                      "--set", "roleipclisten=127.0.0.1:%d" % ipc_port,
                      "--set", "inventorystorage=slab"])

    def status(port):
        return json.loads(_role_rpc(port, "roleStatus"))

    procs = []
    proc_a = None
    try:
        proc_a = spawn_relay(api_a, ipc_a, "1,2")    # primary
        proc_a2 = spawn_relay(api_a2, ipc_a2, "1")   # stream-1 replica
        procs += [proc_a, proc_a2]
        # B sits in every edge's connect list from the start; its link
        # simply stays on the health ladder's bottom rung (and keeps
        # redialing) until phase 2 spawns it — adopting a new relay
        # needs no edge restart
        connect = ",".join("127.0.0.1:%d" % p
                           for p in (ipc_a, ipc_a2, ipc_b))
        for _ in range(edge_procs):
            procs.append(spawn(
                ["-p", str(p2p_port), "--no-api",
                 "--set", "role=edge",
                 "--set", "rolestreams=1,2",
                 "--set", "edgeprocs=%d" % edge_procs,
                 "--set", "roleipcconnect=%s" % connect]))

        def wait_ready(api_ports):
            deadline = time.time() + 120
            while True:
                if time.time() > deadline:
                    raise RuntimeError(
                        "rescale deployment never became ready")
                for p in procs:
                    if p.poll() is not None:
                        raise RuntimeError(
                            "rescale process died during start")
                try:
                    if all(len(status(p)["ipc"]["edges"]) == edge_procs
                           for p in api_ports):
                        return
                except (OSError, RuntimeError, KeyError):
                    pass
                time.sleep(0.2)

        wait_ready([api_a, api_a2])

        async def drive():
            conns = [await _RoleWireClient().connect(p2p_port)
                     for _ in range(clients)]

            async def flood(payloads):
                share = (len(payloads) + clients - 1) // clients
                await asyncio.gather(*(
                    c.send_objects(payloads[i * share:(i + 1) * share])
                    for i, c in enumerate(conns)))

            async def converge(expect, t_start):
                got = {}
                deadline = time.perf_counter() + timeout_s
                while time.perf_counter() < deadline:
                    got = await asyncio.to_thread(
                        lambda: {p: status(p)["inventoryObjects"]
                                 for p in expect})
                    if all(got[p] >= expect[p] for p in expect):
                        return time.perf_counter() - t_start
                    await asyncio.sleep(0.05)
                raise RuntimeError("rescale flood never converged: "
                                   "%r < %r" % (got, expect))

            def rate(n, wall):
                return {"objects": n, "wall_s": round(wall, 3),
                        "objects_per_s": round(n / max(wall, 1e-9), 1)}

            out = {}
            # phase 1 — replicated baseline: A ingests both streams,
            # A2 actively replicates stream 1
            t = time.perf_counter()
            await flood(floods[0])
            out["baseline"] = rate(n_phase, await converge(
                {api_a: n_phase, api_a2: half}, t))

            # phase 2 — live split UNDER LOAD: spawn B, then shed
            # stream 2 from A to B while the flood is in flight
            procs.append(spawn_relay(api_b, ipc_b, "3"))
            await asyncio.to_thread(wait_ready, [api_b])
            t = time.perf_counter()
            send = asyncio.ensure_future(flood(floods[1]))
            out["handoff"] = json.loads(await asyncio.to_thread(
                _role_rpc, api_a, "shardShed", 2,
                "127.0.0.1:%d" % ipc_b))
            await send
            out["split"] = rate(n_phase, await converge(
                {api_a2: 2 * half, api_b: 2 * half}, t))

            # phase 3 — kill the primary mid-flood: stream 1 fails
            # over to A2, stream 2 already lives on B
            t = time.perf_counter()
            send = asyncio.ensure_future(flood(floods[2]))
            await asyncio.sleep(0.05 if smoke else 0.5)
            proc_a.kill()
            await send
            out["failover"] = rate(n_phase, await converge(
                {api_a2: 3 * half, api_b: 3 * half}, t))
            for c in conns:
                await c.close()
            return out

        result = asyncio.run(drive())

        clean = True
        for p in procs:
            if p is not proc_a:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            if p is proc_a:
                p.wait(timeout=30)   # reap the SIGKILLed primary
                continue
            try:
                clean = (p.wait(timeout=30) == 0) and clean
            except subprocess.TimeoutExpired:
                clean = False
                p.kill()
                p.wait()

        ratio = round(result["split"]["objects_per_s"]
                      / max(result["baseline"]["objects_per_s"],
                            1e-9), 2)
        out = {
            "objects": 3 * n_phase,
            "clients": clients,
            "edges": edge_procs,
            "build_s": round(build_s, 2),
            "baseline": result["baseline"],
            "split": result["split"],
            "failover": result["failover"],
            "handoff": result["handoff"],
            "step_up_ratio": ratio,
            # converge() raises on any shortfall, so reaching here
            # means the survivors hold every flooded object
            "zero_objects_lost": 0,
            "clean_shutdown": clean,
        }
        assert clean, "a rescale process did not exit cleanly on SIGTERM"
        if not smoke:
            floor = float(os.environ.get("BMTPU_RESCALE_STEP_FLOOR",
                                         "0.8"))
            out["step_floor"] = floor
            assert ratio >= floor, (
                "post-split rate %.2fx the replicated baseline, below "
                "the %.1fx floor" % (ratio, floor))
        return out
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def _bench_sync_storm(peers: int = 8, objects: int = 10000,
                      smoke: bool = False) -> dict:
    """Bytes-on-wire per delivered object: sketch reconciliation vs
    classic inv flooding across a simulated peer mesh (sync/mesh.py —
    real Reconciler/codec state machines over an in-memory transport).

    The scenario is a REJOIN + STORM: nodes come up holding largely-
    overlapping inventories (each missing a random ~2% of the base
    set), connect one link per tick, then ride out a live-injection
    storm.  The flooding baseline does what the current stack does —
    full big-inv per direction at establishment plus per-object inv
    flooding; sync mode runs digest-sized IBLT catch-up plus periodic
    pending-set reconciliation with a sqrt-fanout flood hybrid.

    Acceptance (full mode): >=5x reduction in announcement-layer
    bytes per delivered object at 10k objects / 8 peers, with zero
    objects lost (every peer converges to the full inventory).
    """
    import asyncio
    import os
    import random as _random

    from pybitmessage_tpu.sync.mesh import Mesh

    live = max(objects // 8, 8)
    base_n = objects - live
    missing_frac = 0.02
    per_tick = max(live // 40, 1)

    async def run(sync: bool, fanout):
        mesh = Mesh(peers, sync=sync, fanout=fanout)
        rng = _random.Random(7)
        base = [hashlib.sha512(b"sync base %d" % i).digest()[:32]
                for i in range(base_n)]
        held0 = 0
        for i in range(peers):
            missing = set(rng.sample(range(base_n),
                                     int(base_n * missing_frac)))
            seed = [h for j, h in enumerate(base) if j not in missing]
            mesh.seed(i, seed)
            held0 += len(seed)
        await mesh.establish()
        estab_ann = mesh.stats.announce_bytes
        injected = 0
        while injected < live:
            for _ in range(min(per_tick, live - injected)):
                mesh.inject(rng.randrange(peers), os.urandom(32))
                injected += 1
            await mesh.tick()
        ticks = await mesh.run_until_converged()
        # zero-loss acceptance: every peer holds the full inventory
        for node in mesh.nodes:
            assert len(node.inventory) == objects, (
                "node %d converged to %d of %d objects"
                % (node.index, len(node.inventory), objects))
        delivered = peers * objects - held0
        return mesh, estab_ann, delivered, ticks

    flood, flood_estab, delivered, _ = asyncio.run(run(False, None))
    sync, sync_estab, _, extra_ticks = asyncio.run(run(True, 1))

    def per_mode(mesh, estab_ann):
        ann = mesh.stats.announce_bytes
        return {
            "announce_bytes": ann,
            "announce_bytes_establishment": estab_ann,
            "announce_bytes_storm": ann - estab_ann,
            "total_bytes": mesh.stats.total_bytes,
            "bytes_per_delivered_object": round(ann / delivered, 1),
            "by_command": dict(sorted(
                mesh.stats.bytes_by_command.items())),
        }

    ratio = flood.stats.announce_bytes / max(
        sync.stats.announce_bytes, 1)
    # cross-node propagation latency (ISSUE 6): per-mesh lifecycle
    # tracers stamp injection and observe every delivery at another
    # node; one mesh tick == one simulated second
    prop_sync = sync.lifecycle.propagation_percentiles()
    prop_flood = flood.lifecycle.propagation_percentiles()
    out = {
        "peers": peers, "objects": objects,
        "seeded_overlap": 1.0 - missing_frac, "live_injected": live,
        "delivered_objects": delivered,
        "flooding": per_mode(flood, flood_estab),
        "reconciliation": per_mode(sync, sync_estab),
        "announce_reduction_x": round(ratio, 2),
        "catchup_reduction_x": round(
            flood_estab / max(sync_estab, 1), 2),
        "storm_reduction_x": round(
            (flood.stats.announce_bytes - flood_estab)
            / max(sync.stats.announce_bytes - sync_estab, 1), 2),
        "zero_objects_lost": True,
        "sync_extra_convergence_ticks": extra_ticks,
        "diff_p90": round((REGISTRY.get("sync_diff_size") or
                           _NullHist()).percentile(0.9), 1),
        "propagation_ticks": {"reconciliation": prop_sync,
                              "flooding": prop_flood},
    }
    if not smoke:
        # acceptance (ISSUE 6): the propagation percentiles the
        # scenario lab is built on must actually be measured
        assert prop_sync is not None and prop_sync["count"] > 0, (
            "sync mesh recorded no propagation latencies")
        # acceptance: >=5x announcement-bandwidth reduction, no loss
        assert ratio >= 5.0, (
            "sync reduced announce bytes only %.2fx (need >=5x)" % ratio)
    # distributed observability plane (ISSUE 9): the same mesh
    # machinery at lab scale with the REAL federation path running
    # in-process — propagation percentiles and bytes-per-delivered
    # now come from MERGED per-node snapshots, not mesh-global
    # bookkeeping
    out["federation"] = _bench_federated_mesh(smoke=smoke)
    return out


def _bench_federated_mesh(smoke: bool = False) -> dict:
    """Mesh-scale federated telemetry (ISSUE 9 tentpole c): a sparse
    ≥200-node simulated mesh (ring + random chords, the scenario-lab
    topology — a 200-node FULL mesh would be 19900 links) where every
    node runs its own metrics registry and pushes delta-encoded
    snapshots through the real ``FederationPublisher``/``Aggregator``
    path every few ticks.  Reported propagation p50/p90/p99 and
    bytes-per-delivered-object are computed from the MERGED snapshots.

    Federation overhead is measured directly — wall seconds spent
    inside snapshot build + push + ingest over the whole run, divided
    by total run wall time — and guarded <2% by tools/bench_compare.py
    (a two-run wall-clock difference would drown the same signal in
    scheduler noise).  A federation-off run of the identical workload
    is still reported informationally.
    """
    import asyncio
    import os
    import random as _random
    import time as _time

    from pybitmessage_tpu.sync.mesh import Mesh

    if smoke:
        # the smoke mesh settles in under a second of wall time, so
        # the per-push cost is amortized over far less work than at
        # lab scale — push less often to keep the measured overhead
        # fraction representative rather than fixed-cost-dominated
        nodes, base_n, live, degree, fed_every = 24, 160, 48, 3, 16
    else:
        nodes, base_n, live, degree, fed_every = 200, 800, 200, 3, 8

    rng = _random.Random(11)
    edges = {tuple(sorted((i, (i + 1) % nodes))) for i in range(nodes)}
    while len(edges) < nodes * degree:
        a, b = rng.randrange(nodes), rng.randrange(nodes)
        if a != b:
            edges.add(tuple(sorted((a, b))))
    edges = sorted(edges)
    base = [hashlib.sha512(b"fed base %d" % i).digest()[:32]
            for i in range(base_n)]

    async def run(federation: bool):
        mesh = Mesh(nodes, edges=edges, sync=True, fanout=1,
                    federation=federation, federate_every=fed_every)
        seed_rng = _random.Random(13)
        for i in range(nodes):
            missing = set(seed_rng.sample(range(base_n),
                                          max(base_n // 50, 1)))
            mesh.seed(i, [h for j, h in enumerate(base)
                          if j not in missing])
        await mesh.establish(links_per_tick=max(len(edges) // 20, 1))
        injected = 0
        inj_rng = _random.Random(17)
        while injected < live:
            for _ in range(min(max(live // 40, 1), live - injected)):
                mesh.inject(inj_rng.randrange(nodes), os.urandom(32))
                injected += 1
            await mesh.tick()
        await mesh.run_until_converged(max_ticks=600)
        if federation:
            mesh.federate_once()   # final flush so merges are complete
        return mesh

    t0 = _time.perf_counter()
    fed = asyncio.run(run(True))
    wall_on = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    asyncio.run(run(False))
    wall_off = _time.perf_counter() - t0

    prop = fed.federated_propagation_percentiles()
    bpd = fed.federated_bytes_per_delivered()
    overhead_frac = fed.federation_seconds / max(wall_on, 1e-9)
    fleet = fed.aggregator.status()["fleet"]
    out = {
        "nodes": nodes, "edges": len(edges),
        "base_objects": base_n, "live_injected": live,
        "propagation_ticks": prop,
        "bytes_per_delivered_object": round(bpd, 1)
        if bpd is not None else None,
        "federation_seconds": round(fed.federation_seconds, 4),
        "overhead_frac": round(overhead_frac, 5),
        "wall_seconds_on": round(wall_on, 3),
        "wall_seconds_off": round(wall_off, 3),
        "fleet": fleet,
        "zero_objects_lost": True,   # run_until_converged asserted it
    }
    # acceptance (ISSUE 9): merged percentiles actually measured from
    # every node's pushed snapshots, at ≥200 nodes in full mode, with
    # the federation path costing <2% of the run
    assert prop is not None and prop["count"] > 0, (
        "federated mesh merged no propagation observations")
    assert fleet["nodes"] == nodes, (
        "aggregator saw %d of %d nodes" % (fleet["nodes"], nodes))
    if not smoke:
        assert nodes >= 200
        assert overhead_frac < 0.02, (
            "federation overhead %.4f >= 2%%" % overhead_frac)
    return out


def _build_tagged_broadcasts(n: int, tags, *, ntpb: int = 10,
                             extra: int = 10, ttl: int = 900,
                             stream: int = 1):
    """PoW-valid broadcast-v5-shaped objects carrying an address-
    derived tag from ``tags`` (round-robin) — the predictable-routing
    flood of the light-client bench.  The edge only reads the header
    shape (``extract_tag``: type 3 version 5 -> leading 32-byte tag);
    the body past the tag is junk, PoW is the only build cost."""
    from pybitmessage_tpu.models.constants import OBJECT_BROADCAST
    from pybitmessage_tpu.models.objects import serialize_object
    from pybitmessage_tpu.models.pow_math import pow_target
    from pybitmessage_tpu.pow.dispatcher import python_solve
    from pybitmessage_tpu.utils.hashes import sha512 as _sha512

    expires = int(time.time()) + ttl
    out = []
    for i in range(n):
        # tag + ciphertext-shaped junk; check_by_type wants >=180 bytes
        # total for a broadcast
        body = (bytes(tags[i % len(tags)]) + os.urandom(160)
                + i.to_bytes(8, "big"))
        obj = serialize_object(expires, OBJECT_BROADCAST, 5, stream,
                               body)
        target = pow_target(len(obj), ttl, ntpb, extra, clamp=False)
        nonce, _ = python_solve(_sha512(obj[8:]), target)
        out.append(nonce.to_bytes(8, "big") + obj[8:])
    return out


def _anonymity_set(tags, counts=(64, 256, 1024)) -> dict:
    """The privacy knob, measured (ROADMAP item 1; docs/sync.md
    "Bucket count vs anonymity set"): with this client-tag population,
    how many clients share a bucket at each bucket count — the
    anonymity set an observer of SUBSCRIBE frames must break.  More
    buckets mean less push bandwidth but fewer co-bucketed clients."""
    from pybitmessage_tpu.sync.digest import bucket_of
    out = {}
    for count in counts:
        hist = [0] * count
        for t in tags:
            hist[bucket_of(t, count)] += 1
        occupied = sorted(h for h in hist if h)
        out[str(count)] = {
            "median_clients_per_bucket": float(
                statistics.median(occupied)) if occupied else 0.0,
            "min_clients_per_bucket": occupied[0] if occupied else 0,
            "occupied_buckets": len(occupied),
        }
    return out


def _bench_light_clients(smoke: bool = False) -> dict:
    """Light-client tier (ISSUE 19 tentpole; ROADMAP item 1): flood
    one edge over the real wire path (TCP -> framing -> PoW verify ->
    role IPC to a relay) while the subscription plane's client count
    scales 1k -> 100k (smoke-scaled), and measure that accepted obj/s
    stays FLAT — per-object cost is one inverted-index probe +
    fan-out to the (fixed, small) matched set, independent of how
    many clients are connected.  A handful of REAL LightClient
    sessions subscribe the flood's tags and must converge to every
    subscribed object (push or DIGEST_DELTA+FETCH repair) — zero
    subscribed-object loss is asserted at every scale.  The scaling
    population enters the inverted index exactly as SUBSCRIBE frames
    would put it there (one membership set per client id), without
    paying 100k real sockets the bench host cannot hold.

    Asserted bands (perfguard-committed): ``flat_rate_ratio`` >= 0.8
    (slowest scale vs the smallest), ``subscribed_objects_lost`` ==
    0, and the ``anonymity_set`` medians monotonically shrinking as
    the bucket count grows (the privacy knob behaving as documented).
    Edge crypto CPU share rides the attribution dict: trial-decrypt
    lives on the clients, so the edge's share must be near zero."""
    import asyncio
    import random as _random

    from pybitmessage_tpu.core.node import Node
    from pybitmessage_tpu.roles.client import (LightClient,
                                               buckets_for_tags)
    from pybitmessage_tpu.utils.hashes import inventory_hash

    scales = [100, 1000] if smoke else [1000, 10000, 100000]
    n_matched = 48 if smoke else 400
    n_bulk = 16 if smoke else 100
    n_real = 4 if smoke else 8
    buckets = 64
    accept_s = 90.0 if smoke else 420.0

    rng = _random.Random(0x19)
    flood_tags = [bytes(rng.getrandbits(8) for _ in range(32))
                  for _ in range(4)]
    client_tags = [bytes(rng.getrandbits(8) for _ in range(32))
                   for _ in range(max(scales))]

    t0 = time.perf_counter()
    payloads = (_build_tagged_broadcasts(n_matched, flood_tags)
                + _build_relay_objects(n_bulk))
    build_s = time.perf_counter() - t0
    matched_hashes = {inventory_hash(p)
                      for p in payloads[:n_matched]}

    async def run_point(n_clients: int) -> dict:
        relay = Node(None, port=0, listen=False, test_mode=True,
                     tls_enabled=False, udp_enabled=False,
                     role="relay", role_ipc_listen="127.0.0.1:0",
                     inventory_backend="slab")
        await relay.start()
        edge = Node(None, port=0, listen=True, test_mode=True,
                    tls_enabled=False, udp_enabled=False, role="edge",
                    role_ipc_connect="127.0.0.1:%d"
                    % relay.role_runtime.listen_port,
                    client_listen="127.0.0.1:0",
                    client_buckets=buckets)
        await edge.start()
        plane = edge.client_plane
        # the scaling population: each simulated client holds exactly
        # the index state its SUBSCRIBE frame would install — its own
        # address's buckets, which (being random) rarely match the
        # flood's tags
        for i in range(n_clients):
            plane.index.replace(
                "sim-%d" % i,
                [(1, buckets_for_tags([client_tags[i]], buckets))])
        real = []
        for i in range(n_real):
            cli = LightClient(
                "127.0.0.1:%d" % plane.listen_port,
                client_id="real-%d" % i, tags=flood_tags,
                streams=(1,))
            await cli.start()
            await cli.wait_synced(15)
            real.append(cli)
        wire_client = await _RoleWireClient().connect(
            edge.pool.listen_port)
        t1 = time.perf_counter()
        await wire_client.send_objects(payloads)
        deadline = time.perf_counter() + accept_s
        accepted = 0
        while time.perf_counter() < deadline:
            accepted = len(edge.inventory)
            if accepted >= len(payloads):
                break
            await asyncio.sleep(0.02)
        dt = max(time.perf_counter() - t1, 1e-9)
        # convergence: every real client holds every matched object,
        # via push or digest repair — the zero-loss bar
        lost = len(matched_hashes) * len(real)
        while time.perf_counter() < deadline:
            lost = sum(len(matched_hashes.difference(c.objects))
                       for c in real)
            if lost == 0:
                break
            await asyncio.sleep(0.05)
        snap = plane.snapshot()
        for c in real:
            await c.stop()
        await wire_client.close()
        await edge.stop()
        await relay.stop()
        assert accepted >= len(payloads), (
            "light_clients@%d accepted %d of %d"
            % (n_clients, accepted, len(payloads)))
        return {
            "clients": n_clients,
            "objects": len(payloads),
            "accepted_objects_per_s": round(len(payloads) / dt, 1),
            "edge_wall_us_per_object": round(dt / len(payloads) * 1e6,
                                             1),
            "subscribed_lost": lost,
            "pushed": snap["pushed"],
            "overflowed": snap["overflowed"],
            "index_memberships": snap["index"]["memberships"],
        }

    points = []
    for n_clients in scales:
        with _attributed("light_clients_%d" % n_clients) as att:
            point = asyncio.run(run_point(n_clients))
        point["crypto_share"] = att.get("crypto_share", 0.0)
        point["attribution"] = {
            "dominant_subsystem": att.get("dominant_subsystem"),
            "by_subsystem": att.get("by_subsystem", {}),
        }
        points.append(point)

    base_rate = points[0]["accepted_objects_per_s"]
    flat_ratio = round(
        min(p["accepted_objects_per_s"] for p in points)
        / max(base_rate, 1e-9), 3)
    lost_total = sum(p["subscribed_lost"] for p in points)
    anonymity = _anonymity_set(client_tags)
    medians = [anonymity[str(c)]["median_clients_per_bucket"]
               for c in (64, 256, 1024)]
    monotonic = 1.0 if medians[0] >= medians[1] >= medians[2] else 0.0

    out = {
        "scales": scales,
        "flood_objects": len(payloads),
        "matched_objects": n_matched,
        "real_clients": n_real,
        "bucket_count": buckets,
        "build_s": round(build_s, 2),
        "points": points,
        "flat_rate_ratio": flat_ratio,
        "subscribed_objects_lost": lost_total,
        "anonymity_set": anonymity,
        "anonymity_monotonic": monotonic,
        "edge_crypto_share_max": max(p["crypto_share"]
                                     for p in points),
    }
    # the headline: per-object edge cost independent of client count
    assert lost_total == 0, (
        "light_clients lost %d subscribed objects" % lost_total)
    assert flat_ratio >= 0.8, (
        "light_clients obj/s NOT flat: ratio %.3f across scales %r "
        "(rates %r)" % (flat_ratio, scales,
                        [p["accepted_objects_per_s"] for p in points]))
    assert monotonic == 1.0, (
        "anonymity medians not monotonic across bucket counts: %r"
        % medians)
    if not smoke:
        # trial-decrypt lives on the clients: the edge's crypto CPU
        # share during the flood must be noise, not a keyring sweep
        assert out["edge_crypto_share_max"] < 0.15, (
            "edge crypto share %.3f — trial-decrypt leaked back onto "
            "the edge?" % out["edge_crypto_share_max"])
    return out


def _smoke_main() -> int:
    """Tiny CPU-only bench for CI (``make bench-smoke``): reduced
    slabs, reference test-mode difficulty, XLA impl — exercises the
    full pipelined path (packing, planning, dispatch-ahead, metrics)
    and emits the same one-line JSON shape in well under a minute."""
    from pybitmessage_tpu.ops.pow_search import pow_search_jit
    from pybitmessage_tpu.ops.sha512_jax import initial_hash_words
    from pybitmessage_tpu.ops.u64 import u64_from_int

    initial_hash = hashlib.sha512(b"pybitmessage-tpu bench").digest()
    lanes, chunks = 1 << 12, 4
    ih_hi, ih_lo = initial_hash_words(initial_hash)
    t_hi, t_lo = u64_from_int(1)
    trials = lanes * chunks

    def run(start: int) -> float:
        s_hi, s_lo = u64_from_int(start)
        t0 = time.perf_counter()
        out = pow_search_jit(ih_hi, ih_lo, t_hi, t_lo, s_hi, s_lo,
                             lanes, chunks)
        assert int(out[3]) == chunks
        return trials / (time.perf_counter() - t0)

    run(0)
    device = statistics.median(run((i + 1) * trials) for i in range(3))
    host = _host_rate(initial_hash, trials=5000)

    from pybitmessage_tpu.pow.pipeline import (BatchPlan,
                                               solve_batch_pipelined)

    def pipe(items, pack, chunks, rows):
        """One pipelined run under an explicit tiny plan (the XLA
        fallback has no early exit, so smoke slabs stay small)."""
        plan = BatchPlan("packed", pack, chunks, list(range(len(items))))
        stats = {}
        t0 = time.perf_counter()
        results = solve_batch_pipelined(items, impl="xla", rows=rows,
                                        plan=plan, stats=stats)
        dt = time.perf_counter() - t0
        for (ih, target), (nonce, _) in zip(items, results):
            check = hashlib.sha512(hashlib.sha512(
                nonce.to_bytes(8, "big") + ih).digest()).digest()
            assert int.from_bytes(check[:8], "big") <= target
        return {
            "objects": len(items),
            "difficulty": "defaults/100 (reference test mode)",
            "wall_s": round(dt, 2),
            "objects_per_s": round(len(items) / dt, 2),
            "aggregate_hps": round(
                stats.get("executed_trials", 0) / dt, 1),
            "plan": {k: stats.get(k) for k in
                     ("mode", "pack", "width", "chunks", "launches")},
        }

    sizes = [116, 216, 516]       # mixed sizes, CPU-feasible means
    queue_items = [
        (hashlib.sha512(b"smoke queue %d" % i).digest(),
         _default_target(sizes[i % len(sizes)], 3600, ntpb=10, extra=10))
        for i in range(12)]
    storm_items = [
        (hashlib.sha512(b"smoke storm %d" % i).digest(),
         _default_target(116, 3600, ntpb=10, extra=10))
        for i in range(24)]
    configs = {
        "batched_queue_mixed": pipe(queue_items, pack=4, chunks=16,
                                    rows=32),
        "broadcast_storm_small": pipe(storm_items, pack=8, chunks=8,
                                      rows=32),
        # the degenerate case: one tiny object -> latency-optimal sync
        "single_tiny_object": (lambda r: {"nonce_ok": True,
                                          "trials": r[0][1]})(
            solve_batch_pipelined(storm_items[:1], impl="xla", rows=32)),
    }
    configs["pipeline_overlap"] = _pipeline_stats()
    # degraded mode: dead device tier, ladder + breaker rescue
    try:
        configs["degraded_fallback"] = _bench_degraded_fallback()
    except Exception as exc:
        configs["degraded_fallback"] = {"error": repr(exc)[:200]}
    # ingest fast path: tiny flood mix through the pipelined
    # processor vs the inline path (no lag assertion in smoke mode)
    try:
        configs["ingest_storm"] = _bench_ingest_storm(
            identities=3, objects=36, smoke=True)
    except ImportError as exc:  # optional `cryptography` absent
        configs["ingest_storm"] = {"skipped": repr(exc)[:120]}
    except Exception as exc:
        configs["ingest_storm"] = {"error": repr(exc)[:200]}
    # batched native crypto (ISSUE 7), reduced sizes for CI
    try:
        configs["batch_crypto"] = _bench_batch_crypto(
            verifies=64, decrypt_objects=12, fanout=6)
    except Exception as exc:
        configs["batch_crypto"] = {"error": repr(exc)[:200]}
    # zero-copy packet path + slab store (ISSUE 11), reduced sizes —
    # the copies-per-byte band and the zero-loss invariants are
    # machine-independent, so an AssertionError must fail CI
    try:
        configs["zero_copy_framing"] = _bench_zero_copy_framing(
            objects=48, dup_factor=3, smoke=True)
    except AssertionError:
        raise
    except Exception as exc:
        configs["zero_copy_framing"] = {"error": repr(exc)[:200]}
    try:
        configs["slab_store"] = _bench_slab_store(objects=4000,
                                                  smoke=True)
    except AssertionError:
        raise
    except Exception as exc:
        configs["slab_store"] = {"error": repr(exc)[:200]}
    # set-reconciliation sync (ISSUE 5): tiny rejoin+storm mesh — the
    # zero-loss invariant holds in smoke too; an AssertionError (an
    # object lost) must fail CI, not hide in the JSON
    try:
        configs["sync_storm"] = _bench_sync_storm(
            peers=6, objects=600, smoke=True)
    except AssertionError:
        raise
    except Exception as exc:
        configs["sync_storm"] = {"error": repr(exc)[:200]}
    # PoW solver farm (ISSUE 12): 8 tenants at ~2x capacity overload
    # through the real wire protocol / scheduler / journal — the
    # fairness-spread and zero-job-loss invariants hold in smoke too
    try:
        configs["pow_farm"] = _bench_pow_farm(smoke=True)
    except AssertionError:
        raise
    except Exception as exc:
        configs["pow_farm"] = {"error": repr(exc)[:200]}
    # role-split deployment (ISSUE 14): 1 edge + 1 relay as REAL
    # daemon subprocesses vs one fused process, same flood over real
    # TCP — zero loss and clean SIGTERM are invariants in smoke too
    # (the >=2x 4-edge scaling bar is full-mode only)
    try:
        configs["role_split"] = _bench_role_split(smoke=True)
    except AssertionError:
        raise
    except Exception as exc:
        configs["role_split"] = {"error": repr(exc)[:200]}
    # light-client tier (ISSUE 19): flat accepted-obj/s while the
    # subscription plane's client count scales, zero subscribed-object
    # loss, anonymity-set sanity — all bands hold in smoke too
    try:
        configs["light_clients"] = _bench_light_clients(smoke=True)
    except AssertionError:
        raise
    except Exception as exc:
        configs["light_clients"] = {"error": repr(exc)[:200]}
    print(json.dumps({
        "metric": "double_sha512_trial_hashes_per_sec_per_chip",
        "value": round(device, 1),
        "unit": "H/s",
        "vs_baseline": round(device / host, 2),
        "kernel": "xla-smoke",
        "smoke": True,
        # self-describing run: jax/jaxlib/libtpu versions + device
        # identity, so a BENCH JSON is comparable across environments
        "env": env_fingerprint(),
        # host-speed stamp (ISSUE 17 satellite): perfguard scales its
        # wall-clock floors by the current/baseline ratio of these, so
        # a baseline recorded on a big box doesn't fail a small one
        "calibration": {
            "cpu_count": os.cpu_count() or 1,
            "single_thread_hps": round(host, 1),
        },
        "baselines": {"python_hashlib_1core_hps": round(host, 1)},
        "configs": configs,
        "metrics_snapshot": snapshot(),
    }))
    return 0


def main():
    # single-section dispatch: ``bench.py light_clients [--smoke]``
    # runs just the light-client tier and prints its JSON block
    if "light_clients" in sys.argv[1:]:
        print(json.dumps({"light_clients": _bench_light_clients(
            smoke="--smoke" in sys.argv[1:])}))
        return 0
    if "--smoke" in sys.argv[1:]:
        return _smoke_main()
    # the PoW part measures the chip: any other device, and any failed
    # PoW phase below, ends the run non-zero instead of hiding in the
    # JSON (the later host-side sections keep their own handling)
    from pybitmessage_tpu.core.jaxsetup import setup_jax
    setup_jax()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("bench.py measures a TPU; found %r (use --smoke for the "
              "host-side sections)" % jax.devices()[0].platform,
              file=sys.stderr)
        return 1
    initial_hash = hashlib.sha512(b"pybitmessage-tpu bench").digest()
    device, xla, kernel = _device_rate(initial_hash)
    slab_rate = device
    effective = _device_rate_effective(initial_hash)
    # headline = what a caller gets from the production solve();
    # the synchronous slab rate stays reported alongside
    device = max(device, effective)
    host = _host_rate(initial_hash)
    native = _native_rate(initial_hash)
    configs = {}
    for name, fn in (
            ("single_msg_default_difficulty",
             lambda: _bench_single_default(device)),
            ("batched_queue_mixed", _bench_batch_queue),
            ("batched_real_default_difficulty",
             lambda: _bench_batch_real_difficulty(device)),
            ("high_difficulty_ntpb_x64_ttl28d",
             lambda: _bench_high_difficulty(device, host)),
            ("broadcast_storm_small", _bench_broadcast_storm),
            ("vanity_grind_cost_split", _bench_vanity_grind),
            ("pod_sharded_tier",
             lambda: _bench_sharded_tier(initial_hash))):
        configs[name] = fn()
    # run-wide pipeline-overlap section (ISSUE 2): device-busy
    # fraction, dispatch-ahead depth, pack-occupancy percentiles
    # accumulated across the batched-queue and storm configs
    configs["pipeline_overlap"] = _pipeline_stats()
    # degraded-mode section (ISSUE 3): throughput with the device tier
    # chaos-killed — the rate a node still delivers mid-outage, and
    # the breaker state proving failures stop being paid per solve
    try:
        configs["degraded_fallback"] = _bench_degraded_fallback()
    except Exception as exc:
        configs["degraded_fallback"] = {"error": repr(exc)[:200]}
    # ingest fast path (ISSUE 4): host-side end-to-end objects/s on a
    # multi-identity flood mix vs the pre-PR inline path, with the
    # loop-lag acceptance probe (<50 ms) armed — an AssertionError
    # here must fail the bench, not hide in the JSON
    try:
        configs["ingest_storm"] = _bench_ingest_storm()
    except AssertionError:
        raise
    except ImportError as exc:  # optional `cryptography` absent
        configs["ingest_storm"] = {"skipped": repr(exc)[:120]}
    except Exception as exc:
        configs["ingest_storm"] = {"error": repr(exc)[:200]}
    # batched native crypto (ISSUE 7): coalesced engine drains vs the
    # per-call path for ECDSA verify + ECIES trial-decrypt sweeps
    try:
        configs["batch_crypto"] = _bench_batch_crypto(
            verifies=256, decrypt_objects=32)
    except Exception as exc:
        configs["batch_crypto"] = {"error": repr(exc)[:200]}
    # line-rate node (ISSUE 11): zero-copy framing through the real
    # connection loop + the slab store at 10M-object retention (scale
    # with BMTPU_BENCH_SLAB_OBJECTS for smaller hosts); both assert
    # their acceptance bars in full mode — failures must surface
    try:
        configs["zero_copy_framing"] = _bench_zero_copy_framing(
            objects=2000, dup_factor=3)
    except AssertionError:
        raise
    except Exception as exc:
        configs["zero_copy_framing"] = {"error": repr(exc)[:200]}
    try:
        configs["slab_store"] = _bench_slab_store(
            objects=int(os.environ.get("BMTPU_BENCH_SLAB_OBJECTS",
                                       "10000000")))
    except AssertionError:
        raise
    except Exception as exc:
        configs["slab_store"] = {"error": repr(exc)[:200]}
    # set-reconciliation sync (ISSUE 5): full 8-peer / 10k-object
    # rejoin+storm mesh — the >=5x announce-bandwidth acceptance and
    # the zero-loss invariant are asserted, and must fail the bench
    try:
        configs["sync_storm"] = _bench_sync_storm()
    except AssertionError:
        raise
    except Exception as exc:
        configs["sync_storm"] = {"error": repr(exc)[:200]}
    # PoW solver farm (ISSUE 12): fairness <=1.5 across 8 tenants at
    # 2x overload, interactive p99 >=5x better than bulk, zero job
    # loss under seeded farm.* chaos + a kill/restart mid-load — all
    # asserted inside the bench
    try:
        configs["pow_farm"] = _bench_pow_farm()
    except AssertionError:
        raise
    except Exception as exc:
        configs["pow_farm"] = {"error": repr(exc)[:200]}
    # role-split node (ISSUE 14; ROADMAP item 4): the same flood
    # through one fused process vs 4 SO_REUSEPORT edge processes +
    # 2 stream-sharded relays, real daemons, real TCP, real role IPC
    # — asserts >=2x end-to-end accepted obj/s (BMTPU_ROLE_RATE_FLOOR
    # tunes the floor on loaded hosts), zero objects lost in either
    # deployment, clean SIGTERM shutdowns
    try:
        configs["role_split"] = _bench_role_split()
    except AssertionError:
        raise
    except Exception as exc:
        configs["role_split"] = {"error": repr(exc)[:200]}
    # light-client tier (ISSUE 19; ROADMAP item 1): edge obj/s flat
    # from 1k to 100k connected clients, zero subscribed-object loss,
    # edge crypto share near zero (trial-decrypt lives on clients) —
    # asserted inside the bench, must fail loudly
    try:
        configs["light_clients"] = _bench_light_clients()
    except AssertionError:
        raise
    except Exception as exc:
        configs["light_clients"] = {"error": repr(exc)[:200]}
    # measured MFU from a profiler trace (device-side kernel time);
    # the wall-clock u32_ops_per_sec stays alongside for continuity
    mfu_info = _measure_mfu(initial_hash)
    print(json.dumps({
        "metric": "double_sha512_trial_hashes_per_sec_per_chip",
        "value": round(device, 1),
        "unit": "H/s",
        "vs_baseline": round(device / host, 2),
        "kernel": kernel,
        "u32_ops_per_sec": round(device * OPS_PER_TRIAL, 0),
        "mfu": mfu_info.get("mfu"),
        "mfu_detail": mfu_info,
        # self-describing run: jax/jaxlib/libtpu versions + device
        # identity, so BENCH/MULTICHIP JSONs are comparable across
        # environments (the doctor leads its report with the same)
        "env": env_fingerprint(),
        # host-speed stamp (ISSUE 17 satellite) — see _smoke_main
        "calibration": {
            "cpu_count": os.cpu_count() or 1,
            "single_thread_hps": round(host, 1),
        },
        "baselines": {
            "python_hashlib_1core_hps": round(host, 1),
            "cpp_pthreads_allcores_hps": round(native, 1),
            "xla_windowed_hps": round(xla, 1),
            "pallas_sync_slab_hps": round(slab_rate, 1),
            "pallas_effective_solve_hps": round(effective, 1),
            "vs_cpp": round(device / native, 2) if native else None,
        },
        "configs": configs,
        # full registry state at the end of the run: every solve/slab
        # histogram with count/sum/p50/p90/p99 (ISSUE 1 satellite —
        # BENCH_r*.json gains percentile latencies)
        "metrics_snapshot": snapshot(),
    }))


if __name__ == "__main__":
    sys.exit(main())
