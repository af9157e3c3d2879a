"""Runs one cell once: builds the deployment, warms up with the cell's
own traffic until nothing compiles any more, measures a window, checks
what the window published, and reduces everything to the result line.

Driven by data.  ``BENCHMARK.json`` names a cell's configuration and
traffic mix; this module finds, under the same root,

- ``benchmarks/configs/<config>.json``    the deployment
- ``benchmarks/traffic/<traffic>.json``   the mix, naming its generator
- ``benchmarks/generators/<name>.py``     the generator (``make``)
- ``benchmarks/end_to_end/<metric>.py``   one reader per end-to-end metric
- ``benchmarks/layers/<metric>.py``       one reader per per-layer metric

so a new cell, deployment, mix or metric is new files plus entries in
``BENCHMARK.json``.  A reader is ``read(window) -> number | None``;
``None`` leaves the metric out of the line.
"""

from __future__ import annotations

import importlib.util
import json
import random
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

from . import check, probes, tracereduce
from .deployments import build
from .end_to_end._latency import latencies_ms

#: where a traced run keeps its profile, inside the checkout
OUT_DIR = ".bench_out"


@dataclass
class Bench:
    """One cell of ``BENCHMARK.json`` with the files it names."""
    root: Path
    spec: dict
    cell: dict
    config: dict
    traffic: dict

    def metrics(self, group: str) -> list[dict]:
        """The metrics of ``group`` that this cell reports."""
        name = self.cell["name"]
        return [m for m in self.spec[group]
                if "workloads" not in m or name in m["workloads"]]


@dataclass
class Window:
    """What a reader may read."""
    bench: Bench
    seconds: float                 # the measured window's length
    setup_s: float
    sent: list                     # generator records of the window
    counters: object               # probes.Counters over the window
    launches: list                 # resolved kernel launches of the window
    verdict: dict                  # check.verify's result
    trace: dict | None = None      # tracereduce.reduce_trace's result
    notes: dict = field(default_factory=dict)

    @property
    def published(self) -> list:
        return [s for s in self.sent if s.t_done is not None]


def _json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SystemExit("benchmark file missing: %s" % path)


def load(root, workload: str) -> Bench:
    root = Path(root)
    spec = _json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise SystemExit("BENCHMARK.json has no workload %r (it has %s)"
                         % (workload, ", ".join(sorted(cells))))
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _json(root / configs[cell["config"]]["file"])
    traffic = _json(root / "benchmarks" / "traffic"
                    / (cell["traffic"] + ".json"))
    return Bench(root, spec, cell, config, traffic)


def load_module(root: Path, folder: str, name: str):
    """The module ``benchmarks/<folder>/<name>.py`` under ``root``."""
    path = Path(root) / "benchmarks" / folder / (name + ".py")
    if not path.exists():
        raise SystemExit("benchmark file missing: %s" % path)
    spec = importlib.util.spec_from_file_location(
        "bench_%s_%s" % (folder, name.replace(".", "_")), path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module     # dataclasses look it up there
    spec.loader.exec_module(module)
    return module


def read_metrics(bench: Bench, group: str, folder: str,
                 window: Window) -> dict:
    out = {}
    for m in bench.metrics(group):
        value = load_module(bench.root, folder, m["name"]).read(window)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def compared_lines(compared: dict) -> list[str]:
    """Each number ``check.verify`` compared, beside its limit."""
    return ["compared: %s = %d (limit %d)" % (name, row["value"],
                                              row["limit"])
            for name, row in compared.items()]


def device_block() -> dict:
    import jax
    devices = jax.devices()
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def _tuner_state() -> dict:
    """What the program's autotuner currently asks for, per kind."""
    from pybitmessage_tpu.observability import REGISTRY
    fam = REGISTRY.get("pow_slab_autotune_chunks")
    return {} if fam is None else {
        values[0]: int(child.value) for values, child in fam.children()}


_WATCH = None


def compile_watch() -> probes.CompileWatch:
    """One listener for the process (JAX has no way to drop one)."""
    global _WATCH
    if _WATCH is None:
        _WATCH = probes.CompileWatch()
        _WATCH.install()
    return _WATCH


async def run_cell(bench: Bench, seed: int, seconds: float, trace: bool,
                   say, *, t_start: float, wrap_solver=None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``wrap_solver`` (the control and the broken-path test) takes the
    program's solver ladder and returns what the sender under test is
    given in its place.
    """
    import jax.profiler as prof

    watch = compile_watch()
    log = probes.LaunchLog(bench.root)
    rng = random.Random(seed)
    # the program draws its TTL jitter from the module-level generator
    random.seed(seed)
    gen = load_module(bench.root, "generators",
                      bench.traffic["generator"]).make(bench.traffic, rng)
    solver = None
    if wrap_solver is not None:
        from pybitmessage_tpu.pow import PowDispatcher
        solver = wrap_solver(PowDispatcher())
    log.install()
    dep = await build(bench.config, solver)
    trace_dir = bench.root / OUT_DIR / "trace" / bench.cell["name"]
    tracing = False
    try:
        # -- warm-up: the cell's own traffic until nothing compiles ----
        quiet = 0
        need = int(bench.traffic["warm_quiet_sweeps"])
        for i in range(int(bench.traffic["warm_max_sweeps"])):
            low0 = watch.snapshot()[0]
            t0 = time.monotonic()
            sent = await gen.sweep(dep, "w%d" % i)
            if any(s.t_done is None for s in sent):
                raise RuntimeError(
                    "warm-up sweep %d did not publish: %s" % (i, sorted(
                        {s.status for s in sent if s.t_done is None})))
            if i == 0:
                await gen.warm_receive_shapes(dep)
            log.wait_idle()
            log.resolve()
            lowered = watch.snapshot()[0] - low0
            quiet = quiet + 1 if lowered == 0 else 0
            say("warm-up sweep %d: %d send(s) in %.2fs, %d program(s) "
                "lowered, tuner %s" % (i, len(sent),
                                       time.monotonic() - t0, lowered,
                                       _tuner_state()))
            if quiet >= need:
                break
        else:
            say("warm-up ended at its cap with programs still compiling")
        say("shapes launched in warm-up: %s" % json.dumps(log.shapes()))
        log.resolved.clear()

        # -- the measured window ---------------------------------------
        inv0 = check.inventory_hashes(dep)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True, exist_ok=True)
            opts = prof.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            prof.start_trace(str(trace_dir), profiler_options=opts)
            tracing = True
        if hasattr(solver, "arm"):
            solver.arm()
        setup_s = time.monotonic() - t_start
        c0 = probes.registry_snapshot()
        low0 = watch.snapshot()
        sent = []
        t0 = time.monotonic()
        with prof.TraceAnnotation(tracereduce.WINDOW_SPAN):
            n = 0
            while time.monotonic() - t0 < seconds:
                sent.extend(await gen.sweep(dep, "m%d" % n))
                n += 1
            t1 = time.monotonic()
            if trace:
                # speculative launches leave the device inside the
                # traced span, so its kernel events match the launches
                log.wait_idle()
        c1 = probes.registry_snapshot()
        low1 = watch.snapshot()
        if tracing:
            prof.stop_trace()
            tracing = False
        log.wait_idle()
        launches = [r for r in log.resolve() if r["t"] >= t0]

        # -- the check, outside the window -----------------------------
        with prof.TraceAnnotation("bench.check"):
            await check.wait_delivered(dep, sent)
            objects = check.new_objects(dep, inv0, sent)
            counters = probes.Counters(c0, c1)
            verdict = check.verify(dep, sent, objects, counters)
    finally:
        if tracing:
            prof.stop_trace()
        log.uninstall()
        await dep.stop()

    window = Window(bench, t1 - t0, setup_s, sent, counters, launches,
                    verdict, notes={"lowerings": low1[0] - low0[0],
                           "backend_compiles": low1[1] - low0[1]})
    for line in compared_lines(verdict["compared"]):
        say(line)
    say("window %.3fs: %d sent, %d published, %d objects, worst "
        "value/target %.4f, backends %s, lowerings in window %d, "
        "tuner %s" % (window.seconds, len(sent), len(window.published),
                      verdict["objects"],
                      verdict["worst_value_over_target"],
                      verdict["attempts_by_backend"],
                      window.notes["lowerings"], _tuner_state()))
    times = latencies_ms(window)
    if times:
        # 1,000 over the mean is the rate of a sweep of one
        say("window's send latency over %d send(s): mean %.3f ms, "
            "least %.3f, most %.3f" % (len(times), sum(times)
                                       / len(times), min(times),
                                       max(times)))
    say("shapes launched in the window: %s" % json.dumps(log.shapes()))
    say("persistent compile cache events: %s"
        % json.dumps(dict(sorted(watch.cache_events.items()))))

    device = device_block()
    result = {"correct": bool(verdict["correct"]),
              "attempted": verdict["attempted"],
              "failed": verdict["failed"]}
    if trace:
        raw = tracereduce.read_xplane(
            tracereduce.newest_xplane(str(trace_dir)),
            keep_host=lambda name: name.startswith(
                tracereduce.SPAN_PREFIX))
        window.trace = tracereduce.reduce_trace(
            raw, {k: v["trace_match"] for k, v in log.kernels.items()})
        window.notes["trace_inventory"] = tracereduce.inventory(raw)
        window.notes["recorded_trace"] = raw
        device["busy_s"] = window.trace["busy_s"]
        device["window_s"] = window.trace["window_s"]
        result["metrics"] = read_metrics(bench, "per_layer", "layers",
                                         window)
        result["breakdown"] = {
            "device_ops": window.trace["device_ops"],
            "idle_gaps": window.trace["idle_gaps"]}
    else:
        result["metrics"] = read_metrics(bench, "end_to_end",
                                         "end_to_end", window)
    result["device"] = device
    # each number compared beside its limit, last in the line
    result["compared"] = verdict["compared"]
    result["window"] = window       # for callers; dropped before printing
    return result
