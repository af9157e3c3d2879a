"""The benchmark's own tests (CPU only; the chip runs are the driver's).

What is held here: the harness finds a configuration, a cell, a traffic
mix, a generator and a per-layer metric added as NEW files, and runs
that cell end to end on the CPU with ``correct`` true; the same run
with the timed path broken underneath (every nonce altered where it is
produced) and the control (targets twice as easy) come out not
correct; the plain reference agrees with hashlib and refuses a nonce
off by one; the trace reduction gives the expected busy, idle and
kernel time on the recorded trace; ``run.py`` exits non-zero off a TPU
before it builds anything; and the percentile, window and kernel-step
arithmetic on fixed samples.

The rehearsal changes difficulty through a configuration FILE of its
own in a temporary tree (test mode, ntpb 10), never through an option
of ``run.py``.
"""

import asyncio
import hashlib
import json
import pathlib
import shutil
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks import (check, controls, harness, kernel_work,  # noqa: E402
                        reference, stats, tracereduce)

NEW_LAYER = '''"""A metric added as a file: messages the window published."""


def read(window):
    return len(window.published)
'''

NEW_GENERATOR = '''"""A generator added as a file: the closed loop, renamed."""

from benchmarks.generators.closed_loop import Generator


def make(params, rng):
    return Generator(params, rng)
'''


def _add_cell(root: pathlib.Path, name: str, topology_from: str,
              traffic: dict, generator: str) -> None:
    """Add one configuration, traffic mix, cell and layer metric to the
    tree at ``root`` as new files and new BENCHMARK.json entries."""
    bdir = root / "benchmarks"
    cfg = json.loads((bdir / "configs" / (topology_from + ".json"))
                     .read_text())
    # on the CPU the solver ladder's first rung is the XLA tier "tpu*"
    cfg.update(name=name + "_cfg", test_mode=True, ntpb=10, extra=10,
               solve_backends=["tpu"])
    (bdir / "configs" / (name + "_cfg.json")).write_text(json.dumps(cfg))
    (bdir / "traffic" / (name + "_mix.json")).write_text(
        json.dumps(dict(traffic, generator=generator)))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": name + "_cfg", "source": "test", "reduced": [],
        "file": "benchmarks/configs/%s_cfg.json" % name, "why": "test"})
    spec["workloads"].append({
        "name": name, "config": name + "_cfg", "traffic": name + "_mix",
        "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "published_count", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "send queue",
        "moves": "sent_msgs_per_s", "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with two cells added as new files."""
    root = tmp_path_factory.mktemp("bench_tree")
    shutil.copytree(REPO / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    (root / "benchmarks" / "layers" / "published_count.py").write_text(
        NEW_LAYER)
    (root / "benchmarks" / "generators" / "renamed_loop.py").write_text(
        NEW_GENERATOR)
    quick = {"warm_verify_batches": [], "warm_quiet_sweeps": 1,
             "warm_max_sweeps": 4}
    _add_cell(root, "rehearse_pair", "sender_default",
              dict(quick, send="message", sweep=2,
                   body_bytes=[[1.0, 40, 300]]), "renamed_loop")
    _add_cell(root, "rehearse_chan", "chan_broadcaster",
              dict(quick, send="broadcast", sweep=3,
                   body_bytes=[[1.0, 40, 120]]), "closed_loop")
    after = {p: p.read_bytes() for p in before}
    assert after == before, "adding a cell edited an existing file"
    return root


def _run(tree, cell, *, trace=False, wrap=None, seed=2**31 + 11):
    lines = []
    bench = harness.load(tree, cell)
    result = asyncio.run(harness.run_cell(
        bench, seed, 1.0, trace, lines.append, t_start=time.monotonic(),
        wrap_solver=wrap))
    result["lines"] = lines
    return result


@pytest.fixture(autouse=True)
def _quick_stall(monkeypatch, tmp_path):
    # test mode announces within two seconds, so a refused message is
    # known to be lost much sooner than on the network's ten
    monkeypatch.setattr(check, "STALL_SECONDS", 4.0)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


@pytest.mark.parametrize("cell", ["rehearse_pair", "rehearse_chan"])
def test_cell_added_as_files_runs_and_is_correct(tree, cell):
    result = _run(tree, cell, trace=True)
    assert result["correct"] is True, result["lines"]
    assert result["failed"] == 0 and result["attempted"] >= 2
    metrics = result["metrics"]
    # the new metric's reader was found by its name alone
    assert metrics["published_count"]["value"] == result["attempted"]
    assert metrics["off_device_solves"]["value"] == 0
    assert metrics["compiles_in_window"]["value"] == 0
    assert "kernel_mhash_per_s.batch" not in metrics     # nothing to read
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes", "window_s"}
    assert any(l.startswith("compared: invalid_nonces = 0 (limit 0)")
               for l in result["lines"])
    # and in the line, each number beside its limit, as the last key
    # (``window`` is dropped before the line is printed)
    printed = [k for k in result if k not in ("window", "lines")]
    assert printed[-2:] == ["device", "compared"]
    assert result["compared"] == {
        name: {"value": 0, "limit": 0}
        for name in ("invalid_nonces", "undelivered", "off_tier")}
    window = result["window"]
    assert window.verdict["objects"] == (
        2 if cell == "rehearse_pair" else 1) * result["attempted"]


@pytest.mark.parametrize("wrap, cell, compared", [
    (controls.SpoiledNonces, "rehearse_pair", "invalid_nonces"),
    (controls.SpoiledNonces, "rehearse_chan", "invalid_nonces"),
    (controls.EasierTargets, "rehearse_chan", "invalid_nonces"),
])
def test_broken_path_and_control_are_not_correct(tree, wrap, cell,
                                                 compared):
    seeds = [2**31 + 12] if wrap is controls.SpoiledNonces \
        else [5, 6, 7, 8]
    results = [_run(tree, cell, wrap=wrap, seed=s) for s in seeds]
    # half of the control's nonces pass by luck: over four runs of
    # three broadcasts a sweep, at least one run must fail
    assert any(r["correct"] is False for r in results)
    bad = [r for r in results if not r["correct"]][0]
    assert bad["window"].verdict["compared"][compared]["value"] > 0
    if wrap is controls.SpoiledNonces:
        assert all(r["correct"] is False for r in results)
        if cell == "rehearse_pair":     # the recipient refuses them too
            assert bad["failed"] == bad["attempted"]


# -- the plain reference ------------------------------------------------


def _solve_by_hashlib(payload: bytes, ntpb: int, extra: int, ttl: int):
    limit = reference.target(len(payload) + 8, ttl, ntpb, extra)
    ih = hashlib.sha512(payload).digest()
    nonce = 0
    while reference.trial_value(nonce.to_bytes(8, "big"), ih) > limit:
        nonce += 1
    return nonce.to_bytes(8, "big") + payload


@pytest.mark.parametrize("length, ttl, ntpb, extra, expected", [
    # 2**64 // (1000 * (2008 + 345600 * 2008 // 65536)) = 2**64 // 12597000
    (1008, 345600, 1000, 1000, 1464375968382),
    (54, 604800, 1000, 1000, 2**64 // (1000 * (1054 + 9726))),
    (300, 300, 10, 10, 2**64 // (10 * (310 + 1))),
])
def test_reference_target_formula(length, ttl, ntpb, extra, expected):
    assert reference.target(length, ttl, ntpb, extra) == expected


def test_reference_agrees_with_hashlib_and_refuses_off_by_one():
    ih = hashlib.sha512(b"fixed vector").digest()
    nonce = (123456789).to_bytes(8, "big")
    by_hand = int.from_bytes(hashlib.sha512(hashlib.sha512(
        nonce + ih).digest()).digest()[:8], "big")
    assert reference.trial_value(nonce, ih) == by_hand
    now = 1_800_000_000
    payload = (now + 3600).to_bytes(8, "big") + b"\x00\x00\x00\x02" + \
        b"\x01\x01" + b"body" * 20
    obj = _solve_by_hashlib(payload, 10, 10, 3600)
    assert reference.object_ok(obj, 10, 10, now)
    value, limit = reference.object_value_and_target(obj, 10, 10, now)
    assert value <= limit
    # the first nonce that works is the one found, so the one before it
    # (and at this difficulty nearly every other) must be refused
    winner = reference.nonce_of(obj)
    refused = [n for n in (winner - 1, winner + 1, winner + 2, winner + 3)
               if n >= 0 and not reference.object_ok(
                   n.to_bytes(8, "big") + obj[8:], 10, 10, now)]
    assert (winner == 0 or winner - 1 in refused) and refused
    # the network's difficulty refuses what test difficulty accepted
    assert not reference.object_ok(obj, 1000, 1000, now)


# -- the trace reduction ------------------------------------------------


def test_trace_reduction_on_the_recorded_trace():
    trace = json.loads((REPO / "benchmarks" / "testdata"
                        / "recorded_trace.json").read_text())
    expect = json.loads((REPO / "benchmarks" / "testdata"
                         / "recorded_trace.expected.json").read_text())
    kernels = json.loads((REPO / "benchmarks" / "kernels.json").read_text())
    got = tracereduce.reduce_trace(
        trace, {k: v["trace_match"] for k, v in kernels.items()})
    assert got["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    for prog, secs in expect["kernel_s"].items():
        assert got["kernel_s"][prog] == pytest.approx(secs, rel=1e-9)
    assert got["kernel_events"] == expect["kernel_events"]
    assert [n for n, _ in got["idle_gaps"]] == expect["idle_gap_names"]
    assert got["device_ops"][0][0] == expect["top_device_op"]
    idle = sum(s for _, s in got["idle_gaps"])
    assert idle == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-6)


def test_trace_reduction_by_hand():
    trace = {
        "device": {"/device:TPU:0": [
            ["XLA Modules", "jit_pallas_search(1)", 10.0, 2.0],
            ["XLA Ops", "pallas_call", 10.0, 1.5],
            ["XLA Ops", "fusion", 11.0, 1.0],     # overlaps: 10..12
            ["XLA Modules", "jit_pallas_batch_search(2)", 13.0, 1.0],
            ["XLA Ops", "pallas_call", 13.0, 1.0],
            ["XLA Ops", "pallas_call", 19.5, 2.0],    # cut at 20
        ]},
        "host": [["python", "bench.window", 9.0, 11.0],
                 ["python", "bench.submit", 9.0, 0.5],
                 ["python", "bench.wait_published", 9.5, 10.0],
                 ["python", "bench.check", 30.0, 1.0]]}
    got = tracereduce.reduce_trace(
        trace, {"slab": "pallas_search", "batch": "pallas_batch_search"})
    assert got["window_s"] == pytest.approx(11.0)
    assert got["busy_s"] == pytest.approx(2.0 + 1.0 + 0.5)
    assert got["kernel_s"] == {"slab": pytest.approx(2.0),
                               "batch": pytest.approx(1.0)}
    gaps = dict(got["idle_gaps"])
    # 9..10 is half submit, half wait: the shorter (inner) span names it
    assert gaps["bench.submit"] == pytest.approx(1.0)
    assert gaps["bench.wait_published"] == pytest.approx(1.0 + 5.5)
    assert got["device_ops"][0] == ["pallas_call", pytest.approx(3.0)]


# -- run.py off a TPU ---------------------------------------------------


def test_run_exits_nonzero_off_a_tpu_before_building(monkeypatch, capsys):
    from benchmarks import deployments, run

    async def must_not_build(*_a, **_k):
        raise AssertionError("built a deployment without a TPU")
    monkeypatch.setattr(deployments, "build", must_not_build)
    monkeypatch.setattr(harness, "build", must_not_build)
    with pytest.raises(SystemExit) as exit_:
        run.main(["--workload", "chan_storm_256", "--seed", str(2**31 + 5),
                  "--seconds", "1", "--trace", "0"])
    assert exit_.value.code not in (0, None)
    assert "TPU" in str(exit_.value.code)
    out = capsys.readouterr().out
    assert "correct" not in out


def test_run_refuses_an_unknown_workload():
    with pytest.raises(SystemExit) as exit_:
        harness.load(REPO, "no_such_cell")
    assert "no_such_cell" in str(exit_.value.code)


# -- arithmetic ---------------------------------------------------------


@pytest.mark.parametrize("values, q, expected", [
    ([5, 1, 3, 2, 4], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 11)), 90, 9.1),
    ([7], 90, 7.0),
    ([10, 20], 0, 10.0),
    ([10, 20], 100, 20.0),
])
def test_percentile(values, q, expected):
    assert stats.percentile(values, q) == pytest.approx(expected)


def test_window_arithmetic():
    assert stats.rate(192, 48.0) == pytest.approx(4.0)
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    spans = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert stats.union_seconds(spans) == pytest.approx(3.0)
    assert stats.gaps(spans, (0.0, 5.0)) == [(2.0, 3.0), (4.0, 5.0)]
    assert stats.gaps([], (1.0, 2.0)) == [(1.0, 2.0)]


@pytest.mark.parametrize("counter, output, static, trials", [
    # slab: hit on the third of eight steps -> three steps ran
    ("slab_steps", [0, 0, 1, 0, 0, 0, 0, 0],
     {"rows": 128, "chunks": 8, "unroll": 5}, 3 * 128 * 128 * 5),
    ("slab_steps", [0] * 8,
     {"rows": 128, "chunks": 8, "unroll": 5}, 8 * 128 * 128 * 5),
    # batch: one object hit at step 2, one missed (all 4), one pad (1)
    ("batch_steps", [[2, 0, 9], [0, 0, 0], [1, 0, 0]],
     {"rows": 128, "chunks": 4, "unroll": 4}, 7 * 128 * 128 * 4),
    # packed: a group runs until its last member has hit
    ("packed_steps", [[1, 0, 0], [3, 0, 0], [0, 0, 0], [2, 0, 0]],
     {"rows": 128, "chunks": 4, "unroll": 1, "pack": 2},
     (3 + 4) * 128 * 128),
])
def test_kernel_work_counts_the_steps_that_ran(counter, output, static,
                                               trials):
    assert kernel_work.launch_trials(counter, output, static) == trials


def test_every_seed_sends_the_same_sizes_in_another_order():
    import random

    gen_mod = harness.load_module(REPO, "generators", "closed_loop")
    params = json.loads((REPO / "benchmarks" / "traffic"
                         / "burst_64.json").read_text())
    a = gen_mod.make(params, random.Random(1))._bodies()
    b = gen_mod.make(params, random.Random(2**31 + 7))._bodies()
    assert sorted(map(len, a)) == sorted(map(len, b))
    assert list(map(len, a)) != list(map(len, b))
    assert len(a) == 64 and 1100 < sum(map(len, a)) / 64 < 1350


def test_benchmark_json_names_files_that_exist():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in spec["workloads"]}
    ends = {m["name"] for m in spec["end_to_end"]}
    for w in spec["workloads"]:
        bench = harness.load(REPO, w["name"])
        assert bench.config["name"] == w["config"]
        assert (REPO / "benchmarks" / "generators"
                / (bench.traffic["generator"] + ".py")).exists()
        assert bench.metrics("end_to_end") and bench.metrics("per_layer")
    for group, folder in (("end_to_end", "end_to_end"),
                          ("per_layer", "layers")):
        for m in spec[group]:
            assert (REPO / "benchmarks" / folder
                    / (m["name"] + ".py")).exists(), m["name"]
            assert set(m.get("workloads", cells)) <= cells
    for m in spec["per_layer"]:
        assert m["moves"] in ends
