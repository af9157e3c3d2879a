"""``setup_trace_lower_s`` (ISSUE 48), on the CPU.

The entry layer's reading of what a start pays before any backend
compile: ``jax_compile_seconds_total`` when the window began, phases
``trace`` and ``lower`` and nothing else.  Held here: the entry, found
by name, is as it was entered and lists all nine cells; the reader
sums the two phases' series as they stood BEFORE the window and leaves
out the backend compile, every other family and histograms; it gives
``None`` where the program has no such family or no such phase (a
program older than the counter); and the program's own listener feeds
the two series the reader reads.
"""

import json
import pathlib
import sys
import types

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks import harness, probes  # noqa: E402

NAME = "setup_trace_lower_s"
FAMILY = "jax_compile_seconds_total"
CELLS = ["chan_storm_256", "single_send", "burst_send_64", "queue_1k",
         "pod4_queue_1k", "storm_10k", "pod4_burst_64",
         "pod4_single_send", "pod4_storm_10k"]


def _read(before: dict, after: dict | None = None):
    window = types.SimpleNamespace(
        counters=probes.Counters(before, before if after is None
                                 else after))
    return harness.load_module(REPO, "layers", NAME).read(window)


def test_the_entry_is_the_entry_layer_s_and_lists_every_cell():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    listed = entry.pop("workloads")
    assert entry == {"name": NAME, "unit": "s", "better": "lower",
                     "source": "program_counter", "layer": "entry",
                     "moves": "setup_s"}
    # the nine it was entered with; a later cell may follow
    assert listed[:len(CELLS)] == CELLS
    assert set(listed) <= {c["name"] for c in spec["workloads"]}
    others = {m["name"]: m for m in spec["per_layer"]
              if m["layer"] == "entry"}
    assert set(others) >= {"setup_compile_s", "setup_backend_init_s",
                           NAME}


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_it_beside_setup_s(cell):
    bench = harness.load(REPO, cell)
    assert NAME in {m["name"] for m in bench.metrics("per_layer")}
    assert "setup_s" in {m["name"] for m in bench.metrics("end_to_end")}


def test_the_reader_sums_the_two_phases_and_nothing_else():
    before = {(FAMILY, ("trace",)): 4.25,
              (FAMILY, ("lower",)): 3.5,
              (FAMILY, ("backend_compile",)): 11.0,
              ("jax_compile_events_total", ("trace",)): 40.0,
              ("jax_compile_events_total", ("lower",)): 17.0,
              ("jax_backend_init_seconds", ()): 6.0,
              ("program_cache_total", ("pallas_slab", "hit")): 1.0,
              ("device_program_compile_seconds", ("pallas_slab",)):
                  (9.0, 1)}
    assert _read(before) == pytest.approx(7.75)
    # what the window itself adds is not set-up
    after = dict(before)
    after[(FAMILY, ("trace",))] = 9.0
    assert _read(before, after) == pytest.approx(7.75)
    # one phase alone is still a reading
    del before[(FAMILY, ("lower",))]
    assert _read(before) == pytest.approx(4.25)


@pytest.mark.parametrize("before", [
    {},
    {("jax_backend_init_seconds", ()): 6.0},
    {(FAMILY, ("backend_compile",)): 11.0},
    {(FAMILY, ()): 3.0},
    {(FAMILY, ("trace",)): (3.0, 2)},
], ids=["empty", "no_family", "no_such_phase", "no_label", "histogram"])
def test_the_reader_gives_none_where_there_is_nothing_to_read(before):
    assert _read(before) is None


def test_the_program_s_listener_feeds_the_series_the_reader_reads():
    """A fresh jit's trace and lowering grow the two phases by what
    the reader then reports."""
    import jax
    import jax.numpy as jnp
    from pybitmessage_tpu.observability.devicetelemetry import \
        install_compile_listener
    install_compile_listener()
    start = _read(probes.registry_snapshot()) or 0.0

    @jax.jit
    def fresh(x):
        for _ in range(64):
            x = jnp.sin(x) + 1.0
        return x
    fresh(jnp.ones((4,))).block_until_ready()
    snap = probes.registry_snapshot()
    assert (FAMILY, ("trace",)) in snap and (FAMILY, ("lower",)) in snap
    grown = _read(snap) - start
    phases = snap[(FAMILY, ("trace",))] + snap[(FAMILY, ("lower",))]
    assert 0 < grown <= phases
    assert _read(snap) == pytest.approx(phases)
