#!/usr/bin/env python3
"""Several runs of one cell in one process, sound and control, for
setting and proving what ``correct`` compares (the benchmark's own
runs never use this).

    python3 benchmarks/prove.py --workload <name> --seconds <s> \
        --seeds 1,2,3 --control 4,5,6 [--trace-seeds 7]

Each run prints the same lines as ``run.py``; a control run has the
sender's solver replaced by ``controls.EasierTargets`` and must come
out ``correct: false``.  Exit code 0 when every sound run was correct
and every control run was not.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse     # noqa: E402
import asyncio      # noqa: E402
import json         # noqa: E402
import sys          # noqa: E402
from pathlib import Path    # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _ints(text: str) -> list[int]:
    return [int(x) for x in text.split(",") if x.strip()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control", type=_ints, default=[])
    ap.add_argument("--trace-seeds", type=_ints, default=[])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from benchmarks import controls, harness, run
    run.quiet_logging()
    bench = harness.load(ROOT, args.workload)
    from pybitmessage_tpu.core.jaxsetup import setup_jax
    setup_jax()
    run.require_chips(int(bench.cell["chips"]))

    plan = ([("sound", s, False) for s in args.seeds]
            + [("control", s, False) for s in args.control]
            + [("sound", s, True) for s in args.trace_seeds])
    ok = True
    lines = []
    said = []

    def say(text: str) -> None:
        run.say(text)
        said.append("[%7.1fs] %s" % (time.monotonic() - T_START, text))
    for kind, seed, trace in plan:
        t_run = time.monotonic()
        say("=== %s run, seed %d, trace %d" % (kind, seed, trace))
        result = asyncio.run(harness.run_cell(
            bench, seed, args.seconds, trace, say, t_start=t_run,
            wrap_solver=(controls.EasierTargets if kind == "control"
                         else None)))
        window = result.pop("window")
        result["run"] = {"kind": kind, "seed": seed,
                         "compared": window.verdict["compared"],
                         "seconds": window.seconds}
        if trace:
            result["run"]["trace_inventory"] = \
                window.notes["trace_inventory"]
            if args.out:
                rec = ROOT / (args.out + ".trace.%d.json" % seed)
                rec.parent.mkdir(parents=True, exist_ok=True)
                rec.write_text(json.dumps(window.notes["recorded_trace"]))
        expected = kind == "sound"
        if result["correct"] != expected:
            ok = False
            run.say("UNEXPECTED: %s run came out correct=%s"
                    % (kind, result["correct"]))
        line = json.dumps(result)
        lines.append(line)
        print(line, flush=True)
    if args.out:
        out = ROOT / args.out
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text("\n".join(lines) + "\n")
        (ROOT / (args.out + ".log")).write_text("\n".join(said) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
