#!/usr/bin/env python
"""Minor page faults of a process's first lone solve, off the chip.

CPython 3.12 keeps a thread's frames in 16 KiB chunks and frees and maps
one at every call that crosses a chunk's end.  A kernel trace whose hot
calls sit on such a boundary took 2.5 times as long on the chip's host
(PERF.md section 6, PR 29), and where the boundary falls depends on the
frames between the thread's start and the traced call.  This drives the
real path on the CPU — PowService -> PowDispatcher -> pipeline -> the
benchmark's launch log -> ``pallas_search`` — so the kernel is traced
with the frames a chip run has, and prints the faults.  Since PR 48
that trace is the EXPORT of a machine's first start
(``core/programcache.py``: a later start loads the program and traces
nothing), so the tool says an accelerator of platform ``tpu`` is there
and gives the store an empty directory: the kernel is exported for the
TPU from this host, with the frames of a cold start, the stored
program then fails to lower for the CPU and the ladder falls to XLA.
About 80,000 is quiet (PR 29, this sandbox); several hundred thousand
is a trace on a boundary: run it before and after a change to that
path.

    JAX_PLATFORMS=cpu python tools/first_solve_faults.py
    JAX_PLATFORMS=cpu python tools/first_solve_faults.py --queue

``--queue`` sends two objects hard enough for plan mode ``batched``, so
the kernel exported is ``pallas_batch_search`` at the node's shape (the
XLA rung then searches some six million trials: a quarter of a minute).
PR 48 met the boundary there and not in the lone solve: 22.5 s of trace
on the chip where the parent's live trace took 8.6, 161,000 faults here
where the lone solve read 100,000; since then the export runs under a
frame with a chunk of its own (``programcache._roomy_frame``) and both
read about 100,000 at whatever depth they are called.
"""

import asyncio
import hashlib
import logging
import os
import resource
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


async def main(queue: bool) -> None:
    from benchmarks import probes
    from pybitmessage_tpu.core import programcache
    from pybitmessage_tpu.pow import pipeline
    from pybitmessage_tpu.pow.dispatcher import PowDispatcher
    from pybitmessage_tpu.pow.service import PowService

    logging.disable(logging.CRITICAL)       # the expected rung failure
    PowDispatcher._on_accelerator = lambda self: True
    PowDispatcher._device_count = lambda self: 1
    pipeline.solve_batch_pipelined.__kwdefaults__["impl"] = "pallas"
    programcache.accelerator = lambda: "tpu"
    probes.LaunchLog(ROOT).install()
    service = PowService(PowDispatcher(
        use_native=False, tpu_kwargs={"lanes": 4096, "chunks_per_call": 8}))
    service.start()
    before = resource.getrusage(resource.RUSAGE_SELF)
    t0 = time.monotonic()
    if queue:
        await asyncio.gather(*(
            service.solve(hashlib.sha512(b"first solve %d" % i).digest(),
                          2 ** 64 // 3000000) for i in range(2)))
    else:
        await service.solve(hashlib.sha512(b"first solve").digest(),
                            2 ** 64 // 200000)
    after = resource.getrusage(resource.RUSAGE_SELF)
    print("first solve %.2fs, minor page faults %d, system time %.2fs, "
          "programs exported %s"
          % (time.monotonic() - t0, after.ru_minflt - before.ru_minflt,
             after.ru_stime - before.ru_stime,
             sorted(p.name.split("-")[0] for p in Path(
                 os.environ["JAX_COMPILATION_CACHE_DIR"])
                    .glob("programs/*"))))
    await service.stop()


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    with tempfile.TemporaryDirectory() as store:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = store
        asyncio.run(main("--queue" in sys.argv[1:]))
