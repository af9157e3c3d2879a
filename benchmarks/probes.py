"""What the benchmark reads from the program while it runs: the
registry's counters and histograms, JAX's compile events, and every
launch of a search kernel with its output.

Nothing here changes what the program does.  The kernel probe wraps
the jitted entry points named in ``kernels.json`` with a function that
calls them and keeps a reference to what they return, so that after
the window the steps each launch really ran can be read from its
output (``kernel_work``), for abandoned speculative launches too.
"""

from __future__ import annotations

import importlib
import inspect
import json
import threading
import time
from pathlib import Path

from . import kernel_work

#: JAX monitoring event fired once for every program it lowers, cached
#: executable or not: each is a trace + lowering the window must not pay
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """Counts JAX lowerings and backend compiles as they happen."""

    def __init__(self):
        self.lowerings = 0
        self.backend_compiles = 0
        self.cache_events: dict[str, int] = {}
        self._lock = threading.Lock()

    def install(self) -> None:
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, _seconds, **_kw) -> None:
        with self._lock:
            if event == LOWERING_EVENT:
                self.lowerings += 1
            elif event == BACKEND_COMPILE_EVENT:
                self.backend_compiles += 1

    def _on_event(self, event, **_kw) -> None:
        if "compilation_cache" in event:
            with self._lock:
                self.cache_events[event] = \
                    self.cache_events.get(event, 0) + 1

    def snapshot(self) -> tuple[int, int]:
        with self._lock:
            return self.lowerings, self.backend_compiles


def registry_snapshot() -> dict:
    """Every series of the program's registry: counters and gauges as
    their value, histograms as ``(sum, count)``."""
    from pybitmessage_tpu.observability import REGISTRY
    out = {}
    for fam in REGISTRY.families():
        for values, child in fam.children():
            if hasattr(child, "snapshot"):
                _, total, count = child.snapshot()
                out[(fam.name, tuple(values))] = (float(total), int(count))
            else:
                out[(fam.name, tuple(values))] = float(child.value)
    return out


class Counters:
    """Two registry snapshots and what grew between them."""

    def __init__(self, before: dict, after: dict):
        self.before, self.after = before, after

    def delta(self, name: str) -> dict[tuple, float]:
        """Growth of each series of counter family ``name``."""
        out = {}
        for (fam, labels), value in self.after.items():
            if fam != name or isinstance(value, tuple):
                continue
            grown = value - self.before.get((fam, labels), 0.0)
            if grown:
                out[labels] = grown
        return out

    def total(self, name: str) -> float:
        return sum(self.delta(name).values())

    def hist(self, name: str) -> tuple[float, int]:
        """(sum, count) growth of histogram family ``name``, all
        series together."""
        total, count = 0.0, 0
        for (fam, labels), value in self.after.items():
            if fam != name or not isinstance(value, tuple):
                continue
            b = self.before.get((fam, labels), (0.0, 0))
            total += value[0] - b[0]
            count += value[1] - b[1]
        return total, count

    def gauge(self, name: str) -> dict[tuple, float]:
        """Latest value of each series of gauge family ``name``."""
        return {labels: value for (fam, labels), value
                in self.after.items()
                if fam == name and not isinstance(value, tuple)}


class LaunchLog:
    """Every launch of the wrapped kernel entry points."""

    def __init__(self, root: Path):
        self.kernels = json.loads(
            (Path(root) / "benchmarks" / "kernels.json").read_text())
        self._lock = threading.Lock()
        self._open: list[tuple] = []     # (program, static, t, output)
        self.resolved: list[dict] = []
        self._patched: list[tuple] = []

    def install(self) -> None:
        for program, spec in self.kernels.items():
            owner = importlib.import_module(spec["module"])
            orig = getattr(owner, spec["entry"])
            wrapped = self._wrap(program, spec, orig)
            for mod in [owner] + [importlib.import_module(m)
                                  for m in spec["also_patch"]]:
                self._patched.append((mod, spec["entry"],
                                      getattr(mod, spec["entry"])))
                setattr(mod, spec["entry"], wrapped)

    def uninstall(self) -> None:
        for mod, name, orig in reversed(self._patched):
            setattr(mod, name, orig)
        self._patched.clear()

    def _wrap(self, program: str, spec: dict, orig):
        sig = inspect.signature(orig)
        names = spec["static"]
        index = spec["output_index"]

        def launch(*args, **kwargs):
            bound = sig.bind(*args, **kwargs)
            bound.apply_defaults()
            static = {n: int(bound.arguments[n]) for n in names}
            t = time.monotonic()
            out = orig(*args, **kwargs)
            kept = out if index is None else out[index]
            with self._lock:
                self._open.append((program, static, t, kept))
            return out
        launch.__wrapped__ = orig
        return launch

    def wait_idle(self) -> None:
        """Block until every launch so far has left the device."""
        with self._lock:
            pending = [o[3] for o in self._open]
        for out in pending:
            out.block_until_ready()

    def resolve(self) -> list[dict]:
        """Fetch the outputs kept so far and turn each launch into
        ``{program, static, t, trials}``; returns the new records."""
        import numpy as np
        with self._lock:
            todo, self._open = self._open, []
        new = []
        for program, static, t, out in todo:
            trials = kernel_work.launch_trials(
                self.kernels[program]["counter"], np.asarray(out), static)
            new.append({"program": program, "static": static, "t": t,
                        "trials": trials})
        self.resolved.extend(new)
        return new

    def shapes(self) -> dict[str, list[dict]]:
        """Distinct static shapes launched so far, per program."""
        seen: dict[str, list[dict]] = {}
        for rec in self.resolved:
            if rec["static"] not in seen.setdefault(rec["program"], []):
                seen[rec["program"]].append(rec["static"])
        return seen
