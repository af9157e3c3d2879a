#!/usr/bin/env python3
"""Runs of one cell as the driver makes them — one new process per
run, ``run.py`` with its real arguments — and the spread of each
end-to-end metric as the benchmark's contract measures it: the distance
between the first and third quartile (``statistics.quantiles(n=4)``)
as a share of the median.  For setting bounds and for looking at a
change before the driver does; never part of a benchmark run.

    python3 benchmarks/sets.py --workload <name> --seconds <s> \
        --seeds 1,2,3,4,5,6 [--sets 2] [--trace 0] --out <file.jsonl>

This process never touches JAX: each child needs the chip to itself.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def spread(values) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s.strip()]
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    ok = True
    with out.open("a") as sink:
        for set_no in range(args.sets):
            rows = []
            for seed in seeds:
                t0 = time.monotonic()
                proc = subprocess.run(
                    [sys.executable, str(ROOT / "benchmarks" / "run.py"),
                     "--workload", args.workload, "--seed", str(seed),
                     "--seconds", str(args.seconds),
                     "--trace", str(args.trace)],
                    capture_output=True, text=True, cwd=ROOT)
                wall = time.monotonic() - t0
                lines = proc.stdout.strip().splitlines()
                try:
                    row = json.loads(lines[-1])
                except (IndexError, ValueError):
                    row = {"correct": False, "error": proc.stderr[-2000:]}
                row["run"] = {"set": set_no, "seed": seed, "rc":
                              proc.returncode, "wall_s": wall,
                              "log": [l for l in lines[:-1]
                                      if "warm-up" in l or "window" in l]}
                ok = ok and proc.returncode == 0 and row.get("correct")
                sink.write(json.dumps(row) + "\n")
                sink.flush()
                rows.append(row)
                print("set %d seed %d rc %d wall %.0fs correct %s %s"
                      % (set_no, seed, proc.returncode, wall,
                         row.get("correct"),
                         {k: round(v["value"], 4) for k, v in
                          row.get("metrics", {}).items()}), flush=True)
            names = sorted({k for r in rows for k in r.get("metrics", {})})
            for name in names:
                vals = [r["metrics"][name]["value"] for r in rows
                        if name in r.get("metrics", {})]
                if len(vals) >= 2:
                    print("set %d %s: median %.6g spread %.4f (n=%d)"
                          % (set_no, name, statistics.median(vals),
                             spread(vals), len(vals)), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
