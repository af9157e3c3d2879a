#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Earlier lines say what happened; the last line of standard output is
the result as one JSON object.  Without a TPU, or with fewer chips
than the cell asks for, it prints no result and exits non-zero.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse     # noqa: E402
import asyncio      # noqa: E402
import json         # noqa: E402
import logging      # noqa: E402
import sys          # noqa: E402
from pathlib import Path    # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def say(text: str) -> None:
    print("[%7.1fs] %s" % (time.monotonic() - T_START, text), flush=True)


class _Truncated(logging.Formatter):
    """The program logs a failed Mosaic compile with the whole kernel
    in the message; keep the head of each record."""

    def format(self, record):
        text = super().format(record)
        return text if len(text) <= 2000 else text[:2000] + " [...]"


def quiet_logging() -> None:
    handler = logging.StreamHandler(sys.stderr)
    handler.setFormatter(_Truncated("%(levelname)s %(name)s: %(message)s"))
    logging.basicConfig(level=logging.WARNING, handlers=[handler])


def require_chips(chips: int) -> dict:
    """The device block, or exit: no accelerator, too few chips, or a
    device the table of peaks does not know."""
    from benchmarks import harness
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("benchmark needs a TPU: JAX reports platform %r"
                 % devices[0].platform)
    if len(devices) < chips:
        sys.exit("cell needs %d chip(s), JAX sees %d"
                 % (chips, len(devices)))
    peaks = json.loads((ROOT / "benchmarks" / "peaks.json").read_text())
    if devices[0].device_kind not in peaks:
        sys.exit("device kind %r is not in benchmarks/peaks.json"
                 % devices[0].device_kind)
    return harness.device_block()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    quiet_logging()
    from benchmarks import harness
    bench = harness.load(ROOT, args.workload)
    try:
        from pybitmessage_tpu.core.jaxsetup import setup_jax
    except ImportError as exc:
        sys.exit("the program is not in this checkout: %r" % exc)
    cache_dir = setup_jax()
    device = require_chips(int(bench.cell["chips"]))

    from pybitmessage_tpu.observability.devicetelemetry import \
        env_fingerprint
    say("env: %s" % json.dumps(env_fingerprint()))
    say("device: %s; compile cache: %s" % (json.dumps(device), cache_dir))
    say("cell %s: config %s, traffic %s, seed %d, %.0fs, trace %d"
        % (bench.cell["name"], bench.cell["config"],
           bench.cell["traffic"], args.seed, args.seconds, args.trace))
    result = asyncio.run(harness.run_cell(
        bench, args.seed, args.seconds, bool(args.trace), say,
        t_start=T_START))
    result.pop("window")
    print(json.dumps(result), flush=True)
    # and as the last lines of standard error
    for line in harness.compared_lines(result["compared"]):
        print(line, file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
