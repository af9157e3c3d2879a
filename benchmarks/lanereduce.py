#!/usr/bin/env python3
"""Why each chip idled inside a solve, by the pipeline driver's lanes.

While ``_PipelineDriver.run`` is under way every lane (one a device) is
in one of three states, and the program mirrors two of them into the
profiler trace as intervals that carry the device's id
(``lanes.json``): ``pow.lane.turn`` (the lane's queue is empty and the
host's loop has yet to come round to it) and ``pow.lane.starved``
(nothing left to search on that device).  This module reads them back
and splits every device plane's idle time inside a solve span by the
state of THAT plane's lane:

    starved  = |G_p ∩ pow.lane.starved(d)|
    turn     = |G_p ∩ pow.lane.turn(d)|
    inflight = |G_p ∩ S| - starved - turn

with ``G_p`` the idle gaps of plane ``p`` of device ``d``, computed as
``spanreduce`` computes them, and ``S`` the union of the solve spans:
``inflight`` is idle with a launch in flight that the host has not read
yet.  The three add up to ``spanreduce``'s ``idle_in_solve_s`` of the
same trace, and with its ``idle_between_solves_s`` to the idle time.
``spanreduce`` gives an idle instant to the shortest span open on the
host, whichever chip that span waits for; here a chip's idle goes to
its own lane's interval and to no other's.

A recorded trace is ``tracereduce``'s JSON object with one more list,
``"lanes": [[device, name, start_s, dur_s], ...]``, and the solve spans
in its ``host`` list.  From a checkout that has just made a traced run:

    python3 benchmarks/lanereduce.py --workload <name> [--record out.json
        --record-seconds 16]

prints the reduction of that run's trace as one JSON object.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):       # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    __package__ = "benchmarks"

from . import spanreduce, tracereduce           # noqa: E402
from .harness import OUT_DIR                    # noqa: E402
from .stats import gaps                         # noqa: E402

BETWEEN = "between_solves"


def load_lanes(root) -> dict:
    """``lanes.json`` of the benchmark under ``root``."""
    return json.loads((Path(root) / "benchmarks" / "lanes.json")
                      .read_text())


def read_xplane(path: str, spec: dict, planes: bool = True) -> dict:
    """ONE read of an ``.xplane.pb``: the lane intervals with their
    device, the solve spans and the harness's own spans, and (unless
    the caller holds them already) the device planes."""
    from jax.profiler import ProfileData
    names, solve = set(spec["intervals"]), set(spec["solve"])
    stat = spec["device_stat"]
    out: dict = {"device": {}, "host": [], "lanes": []}
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith("/device:")
        if is_device and not planes:
            continue
        for line in plane.lines:
            for ev in line.events:
                start, dur = ev.start_ns / 1e9, ev.duration_ns / 1e9
                if is_device:
                    out["device"].setdefault(plane.name, []).append(
                        [line.name, tracereduce.short_name(ev.name),
                         start, dur])
                elif ev.name in names:
                    device = dict(ev.stats).get(stat)
                    if device is not None:
                        out["lanes"].append(
                            [int(device), ev.name, start, dur])
                elif ev.name in solve or ev.name.startswith(
                        tracereduce.SPAN_PREFIX):
                    out["host"].append([line.name, ev.name, start, dur])
    return out


def device_of(plane: str):
    """The JAX id of the device whose plane this is
    (``/device:TPU:2`` -> 2), None for a name that ends in none."""
    tail = plane.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else None


def _merged(intervals) -> list:
    """``(start, end)`` intervals as a sorted list of disjoint ones."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a: list, b: list) -> float:
    """Seconds that two sorted lists of disjoint intervals share."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce_lanes(trace: dict, spec: dict) -> dict:
    """Idle seconds of the window by lane state, the planes' mean and
    chip by chip, and each interval's count and summed seconds inside
    the window."""
    w0, w1 = tracereduce.window_of(trace)
    states = {name: row["state"] for name, row in spec["intervals"].items()}
    solves = _merged((max(s, w0), min(s + d, w1))
                     for _t, name, s, d in trace["host"]
                     if name in spec["solve"])
    by_device: dict = {}
    lane_s = {name: 0.0 for name in states}
    lane_n = {name: 0 for name in states}
    for device, name, s, d in trace.get("lanes", ()):
        a, b = max(s, w0), min(s + d, w1)
        if b <= a or name not in states:
            continue
        lane_s[name] += b - a
        lane_n[name] += 1
        by_device.setdefault(device, {}).setdefault(
            states[name], []).append((a, b))
    planes = {p: ev for p, ev in trace["device"].items() if ev}
    if not planes:
        # devices with nothing in the trace (the CPU rehearsal) were
        # idle: one empty plane for each device a lane names
        planes = {"/device:none:%d" % d: [] for d in sorted(by_device)} \
            or {"/device:none": []}
    chips = {}
    for plane, events in planes.items():
        op_line = tracereduce._pick_line(events, tracereduce.OP_LINES)
        idle = _merged(gaps(
            [(s, s + d) for line, _n, s, d in events if line == op_line],
            (w0, w1)))
        device = device_of(plane)
        mine = by_device.get(device, {})
        row = {"device": device,
               "idle_s": sum(b - a for a, b in idle),
               "turn": _overlap(idle, _merged(mine.get("turn", ()))),
               "starved": _overlap(idle, _merged(mine.get("starved", ())))}
        in_solve = _overlap(idle, solves)
        row["inflight"] = in_solve - row["turn"] - row["starved"]
        row[BETWEEN] = row["idle_s"] - in_solve
        chips[plane] = row
    n = len(chips)

    def mean(key):
        return sum(row[key] for row in chips.values()) / n

    return {
        "window_s": w1 - w0,
        "idle_s": mean("idle_s"),
        "idle_between_solves_s": mean(BETWEEN),
        "idle_by_state": {state: mean(state) for state in spec["states"]},
        "chips": chips,
        "lane_s": lane_s,
        "lane_n": lane_n,
        "lane_intervals": sum(lane_n.values()),
    }


def newest(root, cell: str) -> str:
    return tracereduce.newest_xplane(
        str(Path(root) / OUT_DIR / "trace" / cell))


def table(red: dict) -> list:
    """The reduction as rows ``[chip, idle, between solves, inflight,
    turn, starved]`` in seconds, the planes' mean first."""
    states = list(red["idle_by_state"])
    rows = [["mean", red["idle_s"], red["idle_between_solves_s"]]
            + [red["idle_by_state"][s] for s in states]]
    for plane, row in sorted(red["chips"].items()):
        rows.append([plane, row["idle_s"], row[BETWEEN]]
                    + [row[s] for s in states])
    return [[r[0]] + [round(x, 4) for x in r[1:]] for r in rows]


def for_window(window):
    """The reduction of a traced window, computed once and kept on the
    window; None for an untraced run.  The device planes and the
    harness's spans are the window's own (``recorded_trace``); the
    lane intervals and the solve spans are read here, once."""
    if window.trace is None:
        return None
    if "lane_reduction" not in window.notes:
        root = window.bench.root
        spec = load_lanes(root)
        t0 = time.monotonic()
        read = read_xplane(newest(root, window.bench.cell["name"]), spec,
                           planes=False)
        read["device"] = window.notes["recorded_trace"]["device"]
        red = reduce_lanes(read, spec)
        window.notes["lane_reduction"] = red
        print("[lanes] idle seconds of the window by lane state, the "
              "planes' mean and chip by chip (chip, idle, between "
              "solves, %s; %d intervals; read in %.2fs): %s"
              % (", ".join(spec["states"]), red["lane_intervals"],
                 time.monotonic() - t0, json.dumps(table(red))),
              flush=True)
    return window.notes["lane_reduction"]


def clip(trace: dict, seconds: float, digits: int = 7,
         shortest: float = 1e-6) -> dict:
    """The first ``seconds`` of the window as ``spanreduce.clip`` cuts
    them, thinned to what the reduction reads and rounded, for a
    recorded trace small enough to keep: of each plane its operation
    line, less the operations under ``shortest`` seconds (eight copies
    of a few hundred nanoseconds ride every launch)."""
    planes = {}
    for p, ev in trace["device"].items():
        op_line = tracereduce._pick_line(ev, tracereduce.OP_LINES)
        planes[p] = [e for e in ev
                     if e[0] == op_line and e[3] >= shortest]
    # a lane event is laid out as a plane's are, so it is cut as one
    cut = spanreduce.clip({"device": dict(planes, lanes=trace["lanes"]),
                           "host": trace["host"]}, seconds)

    def short(events):
        return [list(e[:2]) + [round(e[2], digits), round(e[3], digits)]
                for e in events]
    lanes = short(cut["device"].pop("lanes"))
    return {"device": {p: short(ev) for p, ev in cut["device"].items()},
            "host": short(cut["host"]), "lanes": lanes}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--record", default="")
    ap.add_argument("--record-seconds", type=float, default=16.0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parent.parent
    spec = load_lanes(root)
    trace = read_xplane(newest(root, args.workload), spec)
    if args.record:
        out = root / args.record
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(clip(trace, args.record_seconds),
                                  separators=(",", ":")))
    red = reduce_lanes(trace, spec)
    print(json.dumps(dict(red, table=table(red))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
