#!/usr/bin/env python
"""What the host does at the head of a lone object's solve, and how long
a lane's slab should be: ``solve_batch_pipelined`` in mode ``slab`` on
the chips of this host (ROADMAP S16; PERF.md section 6, PR 43 and PR 44).

``--split``: N lone solves at ``pod4_single_send``'s two difficulties
(ack 1.08e7 and message 1.56e7 expected trials, alternating) with a
sleep between (the host's work between two solves), a clock on each side
of every call of ``sha512_pallas.pallas_search`` and of
``jax.device_put``, read against the solve's own spans: what a launch
costs inside and around its kernel call, when each lane's call has
returned, and the head (the solve's entry to the return of the last
lane's first launch).

``--chunks 64,32,128``: the same solves at each launch length (a lane's
bases do not depend on it, so the luck is the same): the measurement
beside ``pipeline.LONE_LANES_CHUNKS``.

``--micro``: the host's cost of one launch by how its operands cross
(numpy arguments riding the jit call, committed arrays, one
``jax.device_put`` for every lane), a launch at a time on an idle
device.

``--ici``: the precheck of the first-hit flag (ROADMAP S16; PERF.md
section 6, PR 49): ``ops/sha512_ici.ici_search`` ALONE on the chips of
this host, no pipeline around it, the same solves with the same sleep
between, one launch of 512 steps a chip a solve.  Three numbers decide:
what ONE dispatch over the chips costs the host (``dispatch``), how
long after the winner's hit the last chip is out (``lag_steps`` a
cancelled chip ran past the winner's step, in milliseconds by the
measured time of a step; ``over_search`` is everything a solve's wall
holds beyond the winner's own steps), and the pairs of solves a second
beside the lay-out of a launch a lane in the same call (``--chunks
64``: the tool's own plan holds those runs to that lay-out, which an
accelerator's chips no longer get from the pipeline's; a last run has
the one program under the pipeline, as the node launches it).

    chiprun --chips 4 -- python3 tools/lone_lanes_bench.py --split \
        --chunks 64,32,128 --micro
    chiprun --chips 4 -- python3 tools/lone_lanes_bench.py --ici \
        --chunks 64
    JAX_PLATFORMS=cpu python tools/lone_lanes_bench.py --tiny --split
    JAX_PLATFORMS=cpu python tools/lone_lanes_bench.py --tiny --ici

Times are the host's clock; ``--tiny`` (the CPU's virtual devices, the
XLA stand-in at eight rows) rehearses the script and measures nothing.
"""

import argparse
import hashlib
import json
import os
import statistics
import sys
import time
from pathlib import Path

#: the cell's two difficulties: an ack's and a message's expected trials
EXPECTED = (1.08e7, 1.56e7)
#: ``--ici``: a flag that never comes would hang a chip for the call's
#: whole time limit; after this long the tool leaves with what it knows
ICI_PATIENCE_S = 300.0


def _ms(values) -> dict:
    values = [v * 1e3 for v in values]
    if not values:
        return {}
    return {"mean": round(statistics.fmean(values), 4),
            "median": round(statistics.median(values), 4),
            "max": round(max(values), 4), "n": len(values)}


class Clocked:
    """Stands where a module's function is and keeps ``(t_in, t_out,
    device)`` of every call, on the spans' clock: the device of the
    first operand that is on one already."""

    def __init__(self, owner, name):
        self.calls, self.orig = [], getattr(owner, name)
        setattr(owner, name, self)

    def __call__(self, *args, **kwargs):
        t0 = time.monotonic()
        out = self.orig(*args, **kwargs)
        t1 = time.monotonic()
        on = [a.devices() for a in args if hasattr(a, "devices")]
        self.calls.append((t0, t1, next(iter(on[0])) if on else None))
        return out


def solves(args, devices, chunks, split, one_program=False):
    """``args.solves`` lone solves at ``chunks`` steps a launch; the
    rates, and with ``split`` the head's parts.  The plan is the
    tool's: a launch a lane (the lay-out ``LONE_LANES_CHUNKS`` is sized
    for), or with ``one_program`` the lanes as the one program that an
    accelerator's chips get from the pipeline's own plan."""
    import jax
    from pybitmessage_tpu.observability import REGISTRY, TRACER
    from pybitmessage_tpu.ops import sha512_pallas
    from pybitmessage_tpu.pow import pipeline
    from pybitmessage_tpu.pow.dispatcher import host_trial

    lanes = len(devices or [None])
    kw = dict(rows=8, impl="pallas") if args.tiny else {}
    expected = (3e4, 5e4) if args.tiny else EXPECTED
    objs = [(hashlib.sha512(b"lone lanes bench %d" % i).digest(),
             int(2 ** 64 / expected[i % 2])) for i in range(args.solves)]
    one_program = one_program and lanes > 1
    plan = pipeline.BatchPlan("slab", 1, chunks, [0],
                              one_program=one_program)

    def solve(item, stats=None):
        return pipeline.solve_batch_pipelined(
            [item], plan=plan, devices=devices, stats=stats,
            stall_timeout=120.0, **kw)[0]

    solve(objs[0])              # every device compiles its program
    time.sleep(0.3)
    kernel = Clocked(sha512_pallas, "pallas_search")
    put = Clocked(jax, "device_put")
    parts = {k: [] for k in (
        "solve", "groups", "head", "launch_span", "launch_call",
        "span_before_call", "span_after_call", "between_first_launches",
        "later_launch_span", "harvest", "device_put", "tail")}
    parts.update({"lane%d_searching" % k: [] for k in range(lanes)})
    puts_under_groups = launches = trials = 0
    t_start = time.monotonic()
    for item in objs:
        TRACER.clear()
        del kernel.calls[:], put.calls[:]
        stats = {}
        t_in = time.monotonic()
        nonce, n = solve(item, stats)
        t_out = time.monotonic()
        assert host_trial(nonce, item[0]) <= item[1]
        trials += n
        launches += stats["launches"]
        if split:
            spans = TRACER.recent(2048)
            by = {name: [s for s in spans if s.name == name] for name in (
                "pow.groups", "pow.launch", "pow.harvest")}
            (groups,) = by["pow.groups"]
            # a lane's first launch: its span and its call, which may
            # be made side by side with the other lanes'
            first = sorted(by["pow.launch"][:lanes],
                           key=lambda s: s.attrs["device"])
            calls = kernel.calls[:lanes]
            if devices:
                calls.sort(key=lambda c: devices.index(c[2]))
            parts["solve"].append(t_out - t_in)
            parts["groups"].append(groups.duration)
            parts["head"].append(max(c[1] for c in calls) - t_in)
            for k, (span, call) in enumerate(zip(first, calls)):
                parts["launch_span"].append(span.duration)
                parts["launch_call"].append(call[1] - call[0])
                parts["span_before_call"].append(call[0] - span.start)
                parts["span_after_call"].append(span.end - call[1])
                parts["lane%d_searching" % k].append(call[1] - t_in)
            parts["between_first_launches"].extend(
                b.start - a.end for a, b in zip(first, first[1:]))
            parts["later_launch_span"].extend(
                s.duration for s in by["pow.launch"][lanes:])
            parts["harvest"].extend(s.duration for s in by["pow.harvest"])
            parts["device_put"].extend(c[1] - c[0] for c in put.calls)
            puts_under_groups += sum(
                groups.start <= c[0] <= groups.end for c in put.calls)
            parts["tail"].append(t_out - by["pow.harvest"][-1].end)
        time.sleep(args.gap_ms / 1e3)
    wall = time.monotonic() - t_start
    sha512_pallas.pallas_search, jax.device_put = kernel.orig, put.orig
    rec = dict(chunks=chunks, lanes=lanes, solves=len(objs),
               lay_out="one program" if one_program else "a launch a lane",
               wall_s=round(wall, 3),
               pairs_per_s=round(len(objs) / 2 / wall, 3),
               launches_per_solve=round(launches / len(objs), 3),
               credited_trials=trials)
    if split:
        rec["ms"] = {k: _ms(v) for k, v in parts.items()}
        rec["device_puts_under_groups_per_solve"] = round(
            puts_under_groups / len(objs), 3)
        head = REGISTRY.get("pow_pipeline_lone_head_seconds")
        if head is not None:
            rec["lone_head_seconds"] = {
                "/".join(values): child.snapshot()[:0:-1]
                for values, child in head.children()}
    return rec


def ici(args, devices):
    """``args.solves`` lone solves through ``ici_search`` alone: a
    launch, a fetch, the hashlib check; a second launch only if every
    chip ran out."""
    import numpy as np
    from pybitmessage_tpu.ops import sha512_ici
    from pybitmessage_tpu.pow.dispatcher import host_trial
    from pybitmessage_tpu.pow.pipeline import (_copy_base, _hash_words,
                                               _split64)

    lanes = len(devices)
    shape = dict(rows=8 if args.tiny else 128,
                 chunks=4 if args.tiny else 512, unroll=5)
    step_trials = shape["rows"] * 128 * shape["unroll"]
    expected = (3e4, 5e4) if args.tiny else EXPECTED
    objs = [(hashlib.sha512(b"lone lanes bench %d" % i).digest(),
             int(2 ** 64 / expected[i % 2])) for i in range(args.solves)]

    def operands(item, starts):
        words = [w for pair in _hash_words(item[0]) for w in pair]
        return np.array([words + list(_split64(starts[k]))
                         + list(_split64(item[1])) for k in range(lanes)],
                        dtype=np.uint32)

    def launch(item, starts):
        t0 = time.perf_counter()
        out = sha512_ici.ici_search(operands(item, starts), devices,
                                    **shape)
        t1 = time.perf_counter()
        rows = np.asarray(out)
        return rows, t1 - t0, time.perf_counter() - t1

    def begin():
        return [_copy_base(0, k, lanes) for k in range(lanes)]

    import threading
    patience = threading.Timer(ICI_PATIENCE_S, lambda: (
        print(json.dumps({"ici": "no answer in %.0f s"
                          % ICI_PATIENCE_S}), flush=True),
        os._exit(70)))
    patience.daemon = True
    patience.start()
    t0 = time.perf_counter()
    launch(objs[0], begin())        # the program is loaded or exported
    first_s = time.perf_counter() - t0
    # a step's time: launches nobody can win, every chip runs out
    nobody = (objs[0][0], 0)
    full = []
    for _ in range(3):
        t0 = time.perf_counter()
        rows, _d, _f = launch(nobody, begin())
        full.append(time.perf_counter() - t0)
        assert all(int(r[sha512_ici.WHY]) == sha512_ici.RAN_OUT
                   and int(r[sha512_ici.STEPS]) == shape["chunks"]
                   for r in rows), rows
    step_s = min(full) / shape["chunks"]
    time.sleep(0.3)
    parts = {k: [] for k in ("solve", "dispatch", "fetch", "over_search",
                             "lag")}
    why = dict.fromkeys(("won", "own_hit", "cancelled", "ran_out"), 0)
    lag_steps, launches, executed, needed = [], 0, 0, 0
    t_start = time.monotonic()
    for item in objs:
        t_in = time.perf_counter()
        starts, dispatch, fetch = begin(), 0.0, 0.0
        while True:
            rows, d, f = launch(item, starts)
            launches += 1
            dispatch, fetch = dispatch + d, fetch + f
            ran = [int(r[sha512_ici.STEPS]) for r in rows]
            executed += sum(ran) * step_trials
            hits = [k for k in range(lanes) if rows[k][sha512_ici.HIT]]
            if hits:
                break
            needed += sum(ran) * step_trials
            why["ran_out"] += lanes
            starts = [s + n * step_trials for s, n in zip(starts, ran)]
        t_out = time.perf_counter()
        win = min(hits, key=lambda k: int(rows[k][sha512_ici.HIT]))
        hit = int(rows[win][sha512_ici.HIT])
        nonce = (int(rows[win][sha512_ici.NONCE_HI]) << 32) \
            | int(rows[win][sha512_ici.NONCE_LO])
        assert host_trial(nonce, item[0]) <= item[1]
        assert (nonce - starts[win]) // step_trials == hit - 1
        needed += sum(min(n, hit) for n in ran) * step_trials
        for k in range(lanes):
            code = int(rows[k][sha512_ici.WHY])
            if k == win:
                why["won"] += 1
            elif code == sha512_ici.OWN_HIT:
                why["own_hit"] += 1
            elif code == sha512_ici.CANCELLED:
                why["cancelled"] += 1
                lag_steps.append(max(ran[k] - hit, 0))
            else:
                why["ran_out"] += 1
        parts["solve"].append(t_out - t_in)
        parts["dispatch"].append(dispatch)
        parts["fetch"].append(fetch)
        parts["over_search"].append(t_out - t_in - hit * step_s)
        late = [max(ran[k] - hit, 0) for k in range(lanes) if k != win]
        parts["lag"].append(max(late) * step_s)
        time.sleep(args.gap_ms / 1e3)
    wall = time.monotonic() - t_start
    patience.cancel()
    return dict(
        ici=True, lanes=lanes, solves=len(objs), chunks=shape["chunks"],
        first_launch_s=round(first_s, 3), step_ms=round(step_s * 1e3, 5),
        wall_s=round(wall, 3), pairs_per_s=round(len(objs) / 2 / wall, 3),
        launches_per_solve=round(launches / len(objs), 3), lanes_by=why,
        cancel_lag_steps={
            "mean": round(statistics.fmean(lag_steps), 4),
            "max": max(lag_steps), "n": len(lag_steps)} if lag_steps
        else {},
        executed_useful_share=round(100.0 * needed / executed, 3),
        ms={k: _ms(v) for k, v in parts.items()})


def micro(args, devices):
    """The host's cost of one launch of a lane, by how its operands
    cross; a launch at a time, the device idle before each."""
    import jax
    import numpy as np
    from pybitmessage_tpu.ops import sha512_pallas
    from pybitmessage_tpu.pow.pipeline import (_copy_base, _hash_words,
                                               _split64)

    lanes = len(devices)
    shape = dict(rows=8 if args.tiny else 128, chunks=2 if args.tiny else 64,
                 unroll=5)
    search = sha512_pallas.pallas_search
    words = np.array(_hash_words(hashlib.sha512(b"micro").digest()),
                     dtype=np.uint32)
    target = np.array(_split64(int(2 ** 64 / 1e12)), dtype=np.uint32)
    bases = [np.array(_split64(_copy_base(0, k, lanes)), dtype=np.uint32)
             for k in range(lanes)]
    out = {}

    def timed(name, fn, rounds=args.micro_rounds):
        laps = []
        for r in range(rounds + 3):
            t0 = time.perf_counter()
            outs = fn()
            laps.append(time.perf_counter() - t0)
            jax.block_until_ready(outs)
        out[name] = _ms(laps[3:])

    resident = [jax.device_put(words, d) for d in devices]
    res_t = [jax.device_put(target, d) for d in devices]
    res_b = [jax.device_put(bases[k], d) for k, d in enumerate(devices)]
    jax.block_until_ready((resident, res_t, res_b))
    timed("put_words_one_lane", lambda: jax.device_put(words, devices[0]))
    timed("put_words_every_lane_one_call",
          lambda: jax.device_put([words] * lanes, list(devices)))
    timed("put_words_every_lane_a_call_each",
          lambda: [jax.device_put(words, d) for d in devices])
    timed("put_all_operands_every_lane_one_call",
          lambda: jax.device_put(
              [a for k in range(lanes) for a in (words, bases[k], target)],
              [d for d in devices for _ in range(3)]))
    timed("launch_one_lane_words_resident_base_target_numpy",
          lambda: search(resident[0], bases[0], target, **shape))
    timed("launch_one_lane_all_resident",
          lambda: search(resident[0], res_b[0], res_t[0], **shape))
    timed("launch_one_lane_base_numpy_alone",
          lambda: search(resident[0], bases[0], res_t[0], **shape))
    timed("launch_every_lane_words_resident_base_target_numpy",
          lambda: [search(resident[k], bases[k], target, **shape)
                   for k in range(lanes)])
    timed("launch_every_lane_all_resident",
          lambda: [search(resident[k], res_b[k], res_t[k], **shape)
                   for k in range(lanes)])
    # the same, a thread a lane (the calls release the interpreter lock)
    import concurrent.futures as cf
    pool = cf.ThreadPoolExecutor(lanes)
    list(pool.map(time.sleep, [0.01] * lanes))      # every worker is up

    def fan(fn, inline_first=False):
        def go():
            futs = [pool.submit(fn, k)
                    for k in range(int(inline_first), lanes)]
            first = [fn(0)] if inline_first else []
            return first + [f.result() for f in futs]
        return go

    def put_and_launch(k):
        return search(jax.device_put(words, devices[k]), bases[k], target,
                      **shape)

    timed("put_and_launch_every_lane",
          lambda: [put_and_launch(k) for k in range(lanes)])
    timed("threads_put_and_launch_every_lane", fan(put_and_launch))
    timed("threads_put_and_launch_lane0_inline",
          fan(put_and_launch, inline_first=True))
    timed("threads_launch_every_lane_words_resident_base_target_numpy",
          fan(lambda k: search(resident[k], bases[k], target, **shape)))
    timed("threads_launch_every_lane_all_resident",
          fan(lambda k: search(resident[k], res_b[k], res_t[k], **shape)))
    timed("threads_put_words_every_lane",
          fan(lambda k: jax.device_put(words, devices[k])))
    timed("threads_wake_and_join_alone", fan(lambda k: None))
    pool.shutdown()
    timed("split64_pairs_to_numpy",
          lambda: np.array([_split64(12345678901234567)], dtype=np.uint32))
    return out


def _xla_where_the_kernel_is() -> None:
    """``--tiny``: an XLA program of real hashes with ``pallas_search``'s
    output contract in its place, as the tests have it."""
    import jax
    from pybitmessage_tpu.ops import sha512_ici, sha512_pallas
    from pybitmessage_tpu.parallel.pow_pallas_sharded import _xla_slab
    from pybitmessage_tpu.pow import pipeline

    slab = jax.jit(_xla_slab, static_argnames=("rows", "chunks"))

    def search(ih_words, base, target, rows, chunks, unroll,
               interpret=False):
        return slab(ih_words, base, target, rows=rows * unroll,
                    chunks=chunks)

    sha512_pallas.pallas_search = search

    def ici_search(operands, devices, rows, chunks, unroll,
                   interpret=False):
        # the entry's XLA equivalent: a grid step is ``unroll`` tiles
        return pipeline._ici_search_xla(
            operands, lanes=rows * sha512_pallas.LANE_COLS * unroll,
            chunks=chunks)

    sha512_ici.ici_search = ici_search


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--solves", type=int, default=200)
    ap.add_argument("--gap-ms", type=float, default=7.0)
    ap.add_argument("--split", action="store_true")
    ap.add_argument("--chunks", default="")
    ap.add_argument("--micro", action="store_true")
    ap.add_argument("--ici", action="store_true")
    ap.add_argument("--micro-rounds", type=int, default=200)
    ap.add_argument("--lanes", type=int, default=0,
                    help="devices to lay the object over (0: all)")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if args.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    sys.path.insert(0, os.getcwd())     # the tree it is run from
    from pybitmessage_tpu.core.jaxsetup import setup_jax
    setup_jax()
    import jax
    from pybitmessage_tpu.pow import pipeline

    if args.tiny:
        _xla_where_the_kernel_is()
    devices = jax.devices()[:args.lanes or None]
    out = {"device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(devices)}, "runs": []}
    print(json.dumps(out["device"]), flush=True)
    placed = devices if len(devices) > 1 else None
    own = 2 if args.tiny else pipeline.plan_batch(
        [(b"", int(2 ** 64 / EXPECTED[0]))], lanes=len(devices)).chunks
    if args.split:
        out["runs"].append(solves(args, placed, own, True))
        print(json.dumps(out["runs"][-1]), flush=True)
    for chunks in [int(c) for c in args.chunks.split(",") if c]:
        # beside ``--ici``, what that is compared with: a launch a lane
        out["runs"].append(solves(args, placed, chunks, False))
        print(json.dumps(out["runs"][-1]), flush=True)
    if args.ici:
        out["runs"].append(ici(args, devices))
        print(json.dumps(out["runs"][-1]), flush=True)
        # and the one program where the node has it: under the pipeline
        out["runs"].append(solves(args, placed, pipeline.plan_batch(
            [(b"", int(2 ** 64 / EXPECTED[0]))], lanes=len(devices),
            one_program=True).chunks if not args.tiny else 4, False,
            one_program=True))
        print(json.dumps(out["runs"][-1]), flush=True)
    if args.micro:
        out["micro"] = micro(args, devices)
        print(json.dumps(out["micro"]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
