#!/usr/bin/env python
"""Minor page faults of a process's first lone solve, off the chip.

CPython 3.12 keeps a thread's frames in 16 KiB chunks and frees and maps
one at every call that crosses a chunk's end.  A kernel trace whose hot
calls sit on such a boundary took 2.5 times as long on the chip's host
(PERF.md section 6, PR 29), and where the boundary falls depends on the
frames between the thread's start and the traced call.  This drives the
real path on the CPU — PowService -> PowDispatcher -> pipeline -> the
benchmark's launch log -> ``pallas_search`` — so the kernel is traced
with the frames a chip run has (its lowering then fails for the CPU and
the ladder falls to XLA), and prints the faults.  About 80,000 is quiet
(PR 29, this sandbox); several hundred thousand is a trace on a
boundary: run it before and after a change to that path.

    JAX_PLATFORMS=cpu python tools/first_solve_faults.py
"""

import asyncio
import hashlib
import logging
import os
import resource
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


async def main() -> None:
    from benchmarks import probes
    from pybitmessage_tpu.pow import pipeline
    from pybitmessage_tpu.pow.dispatcher import PowDispatcher
    from pybitmessage_tpu.pow.service import PowService

    logging.disable(logging.CRITICAL)       # the expected rung failure
    PowDispatcher._on_accelerator = lambda self: True
    PowDispatcher._device_count = lambda self: 1
    pipeline.solve_batch_pipelined.__kwdefaults__["impl"] = "pallas"
    probes.LaunchLog(ROOT).install()
    service = PowService(PowDispatcher(
        use_native=False, tpu_kwargs={"lanes": 4096, "chunks_per_call": 8}))
    service.start()
    before = resource.getrusage(resource.RUSAGE_SELF)
    t0 = time.monotonic()
    await service.solve(hashlib.sha512(b"first solve").digest(),
                        2 ** 64 // 200000)
    after = resource.getrusage(resource.RUSAGE_SELF)
    print("first solve %.2fs, minor page faults %d, system time %.2fs"
          % (time.monotonic() - t0, after.ru_minflt - before.ru_minflt,
             after.ru_stime - before.ru_stime))
    await service.stop()


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    asyncio.run(main())
