"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, so that the second can be tested on a small recorded trace
(``testdata/``): :func:`read_xplane` turns the profiler's ``.xplane.pb``
into plain lists of events, :func:`reduce_trace` turns those into the
device's busy and idle time, each kernel's device time, the device
operations that took most time and the idle gaps named by what the
host was doing.

A recorded trace is a JSON object ``{"device": {plane: [[line, name,
start_s, dur_s], ...]}, "host": [[thread, name, start_s, dur_s], ...]}``
with all times in seconds on the profiler's one clock.
"""

from __future__ import annotations

import glob
import os

from .stats import gaps, union_seconds

#: the harness's own annotation that spans the measured window
WINDOW_SPAN = "bench.window"
#: prefix of every annotation the harness writes
SPAN_PREFIX = "bench."
#: device lines that hold one event per executed operation, best first
OP_LINES = ("XLA Ops", "XLA Modules")
#: the line whose events are whole programs (kernel time is read here)
MODULE_LINES = ("XLA Modules", "XLA Ops")
TOP = 10
NAME_CHARS = 96


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return max(files, key=os.path.getmtime)


def short_name(name: str) -> str:
    """A device operation is named by its whole HLO text: keep what
    stands before `` = `` (``%pallas_batch_search.1``)."""
    return name.split(" = ", 1)[0][:NAME_CHARS]


def read_xplane(path: str, keep_host=lambda name: True) -> dict:
    """The events of one ``.xplane.pb`` as a recorded trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                start = ev.start_ns / 1e9
                dur = ev.duration_ns / 1e9
                if is_device:
                    device.setdefault(plane.name, []).append(
                        [line.name, short_name(ev.name), start, dur])
                elif keep_host(ev.name):
                    host.append([line.name, ev.name, start, dur])
    return {"device": device, "host": host}


def inventory(trace: dict) -> dict:
    """Which planes, lines and event names a trace holds, with counts
    and total seconds — for a first look at a new trace by hand."""
    out: dict = {"device": {}, "host": {}}
    for plane, events in trace["device"].items():
        for line, name, _start, dur in events:
            slot = out["device"].setdefault(plane, {}).setdefault(line, {})
            n, s = slot.get(name, (0, 0.0))
            slot[name] = (n + 1, s + dur)
    for thread, name, _start, dur in trace["host"]:
        slot = out["host"].setdefault(thread, {})
        n, s = slot.get(name, (0, 0.0))
        slot[name] = (n + 1, s + dur)
    return out


def _pick_line(events, wanted) -> str | None:
    present = {e[0] for e in events}
    for name in wanted:
        if name in present:
            return name
    return None


def window_of(trace: dict) -> tuple[float, float]:
    """The measured window: the harness's ``bench.window`` span."""
    spans = [(s, s + d) for _t, name, s, d in trace["host"]
             if name == WINDOW_SPAN]
    if not spans:
        raise ValueError("the trace holds no %r span" % WINDOW_SPAN)
    return max(spans, key=lambda w: w[1] - w[0])


def _host_label(trace: dict, start: float, end: float) -> str:
    """What the host was doing during ``[start, end)``: the innermost
    harness span that covers most of it."""
    best, best_cover, best_len = "outside any span", 0.0, float("inf")
    for _t, name, s, d in trace["host"]:
        if not name.startswith(SPAN_PREFIX) or name == WINDOW_SPAN:
            continue
        cover = min(end, s + d) - max(start, s)
        if cover <= 0:
            continue
        # most cover first, then the shorter (inner) span
        if cover > best_cover + 1e-9 or (
                abs(cover - best_cover) <= 1e-9 and d < best_len):
            best, best_cover, best_len = name, cover, d
    return best


def reduce_trace(trace: dict, kernel_match: dict[str, str]) -> dict:
    """Busy and idle time of the device over the window, averaged over
    the device planes; each kernel's device seconds (``kernel_match``
    maps a program to the substring that names it in the trace); the
    operations that took most time; the idle time by host activity."""
    w0, w1 = window_of(trace)
    window = w1 - w0
    busy_total = 0.0
    kernel_s = {k: 0.0 for k in kernel_match}
    kernel_n = {k: 0 for k in kernel_match}
    op_s: dict[str, float] = {}
    gap_s: dict[str, float] = {}
    planes = [p for p, ev in trace["device"].items() if ev]
    for plane in planes:
        events = trace["device"][plane]
        op_line = _pick_line(events, OP_LINES)
        mod_line = _pick_line(events, MODULE_LINES)
        clipped = []
        for line, name, s, d in events:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            if line == op_line:
                clipped.append((a, b))
                op_s[name] = op_s.get(name, 0.0) + (b - a)
            if line == mod_line:
                for prog, needle in kernel_match.items():
                    if needle in name:
                        kernel_s[prog] += b - a
                        kernel_n[prog] += 1
        busy_total += union_seconds(clipped)
        for a, b in gaps(clipped, (w0, w1)):
            label = _host_label(trace, a, b)
            gap_s[label] = gap_s.get(label, 0.0) + (b - a)
    n = max(len(planes), 1)

    def top(table):
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, secs / n] for name, secs in ranked]

    return {
        "window_s": window,
        "busy_s": busy_total / n,
        "device_planes": len(planes),
        "kernel_s": {k: v / n for k, v in kernel_s.items()},
        "kernel_events": kernel_n,
        "device_ops": top(op_s),
        "idle_gaps": top(gap_s),
    }
