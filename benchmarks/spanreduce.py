#!/usr/bin/env python3
"""The device's idle time, named by the program's own spans.

The program mirrors every span of ``spans.json`` into the profiler
trace.  This module reads them back beside the harness's ``bench.*``
spans and gives every instant of an idle gap to the SHORTEST span open
at that instant (``bench.window`` excluded), so 256 one-millisecond
``sender.sign`` spans are not swallowed by the ``sender.sweep`` that
covers them.  Host events are intervals here and never a stack: a span
that crosses an ``await`` overlaps its neighbours on one thread without
nesting in them.

``tracereduce`` names a whole gap by the ``bench.*`` span that covers
most of it and is left as it is; the gaps themselves (the union of the
device's operation intervals, per device plane, averaged) are computed
the same way here, so the idle seconds agree.

A recorded trace is the JSON object ``tracereduce`` describes, its
``host`` list holding the program's spans too.  From a checkout that
has just made a traced run:

    python3 benchmarks/spanreduce.py --workload <name> [--record out.json
        --record-seconds 16]

prints the reduction of that run's trace as one JSON object.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

if __package__ in (None, ""):       # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    __package__ = "benchmarks"

from . import tracereduce                       # noqa: E402
from .harness import OUT_DIR                    # noqa: E402
from .stats import gaps, union_seconds          # noqa: E402

NO_SPAN = "no span"


def load_spans(root) -> dict:
    """``spans.json`` of the benchmark under ``root``."""
    return json.loads((Path(root) / "benchmarks" / "spans.json")
                      .read_text())


def keep_host(spec: dict):
    """The host events a reduction needs: the harness's and the
    program's."""
    names = set(spec["spans"])
    return lambda name: (name in names
                         or name.startswith(tracereduce.SPAN_PREFIX))


def _innermost(spans, a: float, b: float, into: dict) -> None:
    """Add the seconds of ``[a, b)`` to ``into`` by the shortest of
    ``spans`` (``(start, end, name)``) open at each instant."""
    over = [s for s in spans if s[0] < b and s[1] > a]
    cuts = sorted({a, b} | {t for s in over for t in s[:2] if a < t < b})
    for x, y in zip(cuts, cuts[1:]):
        name, best = NO_SPAN, float("inf")
        for s, e, n in over:
            if s <= x and e >= y and e - s < best:
                name, best = n, e - s
        into[name] = into.get(name, 0.0) + (y - x)


def reduce_spans(trace: dict, spec: dict) -> dict:
    """Idle seconds of the window by innermost span and by whether a
    solve was running, and each program span's count and summed
    seconds inside the window."""
    w0, w1 = tracereduce.window_of(trace)
    named = set(spec["spans"])
    spans, solves = [], []
    span_s = {n: 0.0 for n in named}
    span_n = {n: 0 for n in named}
    for _thread, name, s, d in trace["host"]:
        a, b = max(s, w0), min(s + d, w1)
        if b <= a or name == tracereduce.WINDOW_SPAN:
            continue
        if name in named:
            span_s[name] += b - a
            span_n[name] += 1
            if name in spec["solve"]:
                solves.append((a, b))
        elif not name.startswith(tracereduce.SPAN_PREFIX):
            continue
        spans.append((a, b, name))
    # a device with nothing in the trace (the CPU rehearsal) was idle
    planes = [ev for ev in trace["device"].values() if ev] or [[]]
    by_span: dict[str, float] = {}
    idle = in_solve = 0.0
    for events in planes:
        op_line = tracereduce._pick_line(events, tracereduce.OP_LINES)
        busy = [(max(s, w0), min(s + d, w1))
                for line, _name, s, d in events if line == op_line]
        for a, b in gaps([iv for iv in busy if iv[1] > iv[0]], (w0, w1)):
            idle += b - a
            _innermost(spans, a, b, by_span)
            in_solve += union_seconds(
                [(max(s, a), min(e, b)) for s, e in solves
                 if s < b and e > a])
    n = len(planes)
    return {
        "window_s": w1 - w0,
        "idle_s": idle / n,
        "idle_in_solve_s": in_solve / n,
        "idle_between_solves_s": (idle - in_solve) / n,
        "idle_by_span": sorted(([name, secs / n]
                                for name, secs in by_span.items()),
                               key=lambda row: -row[1]),
        "span_s": span_s,
        "span_n": span_n,
        "program_spans": sum(span_n.values()),
    }


def read_trace(root, cell: str) -> dict:
    """The newest traced run of ``cell`` under ``root`` as a recorded
    trace, the program's spans included."""
    trace_dir = Path(root) / OUT_DIR / "trace" / cell
    return tracereduce.read_xplane(
        tracereduce.newest_xplane(str(trace_dir)),
        keep_host=keep_host(load_spans(root)))


def for_window(window):
    """The reduction of a traced window, computed once and kept on the
    window; None for an untraced run and for a program that opens no
    span of ``spans.json`` (one older than these spans)."""
    if window.trace is None:
        return None
    if "span_reduction" not in window.notes:
        root = window.bench.root
        red = reduce_spans(read_trace(root, window.bench.cell["name"]),
                           load_spans(root))
        window.notes["span_reduction"] = red if red["program_spans"] \
            else None
        if red["program_spans"]:
            print("[spans] idle seconds of the window by innermost span "
                  "(%.3f idle of %.3f): %s"
                  % (red["idle_s"], red["window_s"],
                     json.dumps(red["idle_by_span"])), flush=True)
    return window.notes["span_reduction"]


def clip(trace: dict, seconds: float) -> dict:
    """The first ``seconds`` of the window, times relative to its
    start, for a recorded trace small enough to keep under testdata/."""
    w0, _w1 = tracereduce.window_of(trace)
    end = w0 + seconds

    def cut(events, at):
        out = []
        for ev in events:
            s, d = ev[at], ev[at + 1]
            if s + d <= w0 or s >= end:
                continue
            a, b = max(s, w0), min(s + d, end)
            out.append(list(ev[:at]) + [a - w0, b - a])
        return out
    return {"device": {p: cut(ev, 2) for p, ev in trace["device"].items()},
            "host": cut(trace["host"], 2)}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--record", default="")
    ap.add_argument("--record-seconds", type=float, default=16.0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parent.parent
    trace = read_trace(root, args.workload)
    if args.record:
        out = root / args.record
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(clip(trace, args.record_seconds)))
    print(json.dumps(reduce_spans(trace, load_spans(root))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
