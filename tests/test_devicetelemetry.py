"""Device-telemetry plane tests (docs/observability.md "Device
telemetry").

Covers the ISSUE 16 checklist: the program catalog <-> registration
lockstep, the compile-vs-cache split keyed on static shapes, launch /
transfer / donation accounting, the double-buffer-aware busy union
(overlap credited once), the never-raises drop counter, live CPU-mesh
population through the real ``pow_slab`` / ``pow_verify`` /
``packed_search_xla`` paths, deviceStatus / costStatus.device /
clientStatus.device / ``GET /debug/device`` end to end, the
``profileDevice`` trace capture + validation, the tpu_doctor failure
diagnosis golden (device busy), the <2% record overhead budget, and
the bmlint ``devicelaunch`` checker.

This file IS the ``make device-smoke`` gate (tox env
``device-smoke``).
"""

import asyncio
import base64
import hashlib
import json
import pathlib
import sys
import time
from types import SimpleNamespace

import pytest

from pybitmessage_tpu.observability import (
    DEVICE_TELEMETRY, REGISTRY, capture_device_trace, device_cost_block,
    device_status, env_fingerprint, record_launch)

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

IH = hashlib.sha512(b"device telemetry smoke").digest()
#: every trial wins — a single slab finishes the solve immediately
ALWAYS = (1 << 64) - 1


def _sample(name, program):
    return REGISTRY.sample(name, {"program": program})


def _import_launch_modules():
    """Import every module that registers a catalog program (cheap:
    imports only, no compiles)."""
    from pybitmessage_tpu import crypto, ops, parallel, pow  # noqa: F401
    import pybitmessage_tpu.crypto.tpu  # noqa: F401
    import pybitmessage_tpu.ops.pow_search  # noqa: F401
    import pybitmessage_tpu.ops.secp256k1_pallas  # noqa: F401
    import pybitmessage_tpu.ops.sha512_pallas  # noqa: F401
    import pybitmessage_tpu.parallel.pow_pallas_sharded  # noqa: F401
    import pybitmessage_tpu.parallel.pow_sharded  # noqa: F401
    import pybitmessage_tpu.pow.pipeline  # noqa: F401


# ---------------------------------------------------------------------------
# catalog lockstep + registration
# ---------------------------------------------------------------------------


def test_catalog_registration_lockstep():
    """The docstring catalog, the live registry and the doctor's probe
    table must agree program-for-program (the drift the devicelaunch
    checker also guards statically)."""
    import re

    from pybitmessage_tpu.observability import devicetelemetry
    _import_launch_modules()
    catalog = set(re.findall(r"^``([a-z_][a-z0-9_.]*)``",
                             devicetelemetry.__doc__, re.MULTILINE))
    assert len(catalog) == 13
    registered = set(DEVICE_TELEMETRY.programs())
    assert catalog == registered, (
        "catalog rows and register_program() calls drifted: "
        "only-cataloged=%r only-registered=%r"
        % (catalog - registered, registered - catalog))
    import tools.tpu_doctor as doctor
    assert set(doctor._PROBES) == catalog


def test_registered_programs_carry_module_and_flops():
    _import_launch_modules()
    progs = DEVICE_TELEMETRY.programs()
    for name in ("pow_slab", "packed_search", "sharded_batch",
                 "secp_verify"):
        assert progs[name]["module"], name
        assert progs[name]["flops_per_item"] > 0, name


# ---------------------------------------------------------------------------
# record_launch unit semantics (scratch program names — no device)
# ---------------------------------------------------------------------------


def test_compile_vs_cache_split(monkeypatch):
    """Compiles come from JAX's own events, never from launches: a
    program's trace, lowering, cache lookup and backend compile arrive
    in that order on one thread; a lookup that missed is a compile, one
    that hit is a cache hit, and the seconds of the phases add up."""
    from pybitmessage_tpu.observability import devicetelemetry as dt
    prog = "t_split_unit"
    dt.register_program(prog, jit_names=("t_split_fn",))
    record_launch(prog, key=(128, 1), dispatch_seconds=0.5)
    record_launch(prog, key=(128, 1), dispatch_seconds=0.001)
    assert _sample("device_launches_total", prog) == 2
    assert _sample("device_program_compiles_total", prog) == 0
    assert (prog, (128, 1)) in DEVICE_TELEMETRY.launched_keys()

    # one phase ends where the next begins, as in a real compile
    from types import SimpleNamespace
    clock = [5000.0]
    monkeypatch.setattr(dt, "time", SimpleNamespace(
        monotonic=lambda: clock[0]))
    monkeypatch.setattr(dt, "_compiling", SimpleNamespace())

    def fire(event, seconds, fun_name):
        clock[0] += seconds + 0.001
        dt._on_compile_duration("/jax/core/compile/" + event, seconds,
                                fun_name=fun_name)

    def phases(result, backend_seconds):
        fire("jaxpr_trace_duration", 0.1, "t_split_fn")
        fire("jaxpr_to_mlir_module_duration", 0.3, "jit(t_split_fn)")
        dt._on_compile_event("/jax/compilation_cache/cache_" + result)
        fire("backend_compile_duration", backend_seconds,
             "jit(t_split_fn)")

    lowered0 = REGISTRY.sample("jax_compile_events_total",
                               {"phase": "lower"})
    seconds0 = REGISTRY.sample("jax_compile_seconds_total",
                               {"phase": "backend_compile"})
    hits0 = REGISTRY.sample("jax_compile_cache_total", {"result": "hit"})
    phases("misses", 0.5)
    phases("hits", 0.02)
    # an event of a function no program owns feeds the totals only,
    # and takes the lookup's result with it
    dt._on_compile_event("/jax/compilation_cache/cache_hits")
    fire("backend_compile_duration", 0.01, "jit(convert_element_type)")
    dt._on_compile_duration("/jax/some/other/event", 9.0)
    assert _sample("device_program_compiles_total", prog) == 1
    assert _sample("device_program_cache_hits_total", prog) == 1
    from pybitmessage_tpu.observability.devicetelemetry import _hist_stats
    count, total = _hist_stats("device_program_compile_seconds", prog)
    assert count == 2
    assert total == pytest.approx(0.9 + 0.42)
    assert REGISTRY.sample("jax_compile_events_total",
                           {"phase": "lower"}) == lowered0 + 2
    assert REGISTRY.sample(
        "jax_compile_seconds_total",
        {"phase": "backend_compile"}) == pytest.approx(seconds0 + 0.53)
    assert REGISTRY.sample("jax_compile_cache_total",
                           {"result": "hit"}) == hits0 + 2


def test_nested_compile_events_are_not_counted_twice(monkeypatch):
    """Tracing a program traces the jitted functions it calls, and each
    reports its own duration before the outer one does: the seconds by
    phase add up to the wall, while a program's lowering, which starts
    where its trace ended, is counted whole."""
    from types import SimpleNamespace

    from pybitmessage_tpu.observability import devicetelemetry as dt
    clock = [1000.0]
    monkeypatch.setattr(dt, "time", SimpleNamespace(
        monotonic=lambda: clock[0]))
    monkeypatch.setattr(dt, "_compiling", SimpleNamespace())

    def fire(event, at, seconds, name):
        clock[0] = at
        dt._on_compile_duration("/jax/core/compile/" + event, seconds,
                                fun_name=name)

    def seconds(phase):
        return REGISTRY.sample("jax_compile_seconds_total",
                               {"phase": phase})

    trace0, lower0 = seconds("trace"), seconds("lower")
    fire("jaxpr_trace_duration", 1001.0, 0.5, "inner_a")   # 1000.5..1001
    fire("jaxpr_trace_duration", 1002.0, 0.25, "inner_b")  # 1001.75..1002
    fire("jaxpr_trace_duration", 1003.0, 3.0, "outer")     # 1000..1003
    fire("jaxpr_to_mlir_module_duration", 1005.0, 2.0, "jit(outer)")
    fire("jaxpr_trace_duration", 1006.0, 0.5, "next")      # sequential
    assert seconds("trace") - trace0 == pytest.approx(3.0 + 0.5)
    assert seconds("lower") - lower0 == pytest.approx(2.0)
    assert len(dt._compiling.intervals) == 3       # outer, lower, next


def test_busy_union_overlap_credited_once():
    """Two overlapping double-buffered spans must credit their overlap
    once: (0,10) then (5,12) is 12 busy seconds, not 17."""
    prog = "t_busy_union"
    record_launch(prog, span=(100.0, 110.0))
    record_launch(prog, span=(105.0, 112.0))
    assert _sample("device_busy_seconds_total",
                   prog) == pytest.approx(12.0)
    # a span fully inside the watermark adds nothing
    record_launch(prog, span=(106.0, 111.0))
    assert _sample("device_busy_seconds_total",
                   prog) == pytest.approx(12.0)
    # and a disjoint later span adds exactly its own length
    record_launch(prog, span=(120.0, 121.5))
    assert _sample("device_busy_seconds_total",
                   prog) == pytest.approx(13.5)


def test_transfer_donation_and_rate_accounting(monkeypatch):
    from pybitmessage_tpu.observability import devicetelemetry
    monkeypatch.setattr(devicetelemetry, "device_peak_ops",
                        lambda: 6.1e12)
    prog = "t_transfer_unit"
    DEVICE_TELEMETRY.register_program(prog, flops_per_item=21152.0)
    record_launch(prog, span=(0.0, 2.0), items=1000,
                  bytes_in=4096, bytes_out=128, bytes_donated=2048)
    assert _sample("device_h2d_bytes_total", prog) == 4096
    assert _sample("device_d2h_bytes_total", prog) == 128
    assert _sample("device_donated_bytes_total", prog) == 2048
    assert _sample("device_work_items_total", prog) == 1000
    assert _sample("device_hashrate_hps", prog) == pytest.approx(500.0)
    mfu = _sample("device_mfu_ratio", prog)
    assert 0 < mfu <= 1.0
    row = device_status()["programs"][prog]
    assert row["donationRate"] == pytest.approx(0.5)
    assert row["hashrateHps"] == pytest.approx(500.0)


def test_mfu_only_for_a_listed_device_kind():
    """The peak is keyed by device kind: the CPU backend has no row,
    so a launch there sets a hashrate and NO mfu."""
    from pybitmessage_tpu.observability import devicetelemetry
    assert "TPU v5 lite" in devicetelemetry.DEVICE_PEAK_OPS
    import jax
    assert jax.devices()[0].device_kind not in \
        devicetelemetry.DEVICE_PEAK_OPS
    assert devicetelemetry.device_peak_ops() is None
    prog = "t_mfu_unlisted_unit"
    DEVICE_TELEMETRY.register_program(prog, flops_per_item=21152.0)
    record_launch(prog, span=(0.0, 2.0), items=1000)
    assert _sample("device_hashrate_hps", prog) == pytest.approx(500.0)
    assert _sample("device_mfu_ratio", prog) == 0


def test_record_launch_never_raises():
    """Telemetry must not fail the launch path it observes — garbage
    arguments count into the dropped counter instead of raising."""
    before = REGISTRY.sample("device_telemetry_dropped_total")
    record_launch("t_drop_unit", bytes_in="not-a-number")
    assert REGISTRY.sample("device_telemetry_dropped_total") == before + 1


# ---------------------------------------------------------------------------
# live CPU-backend population (the real launch paths)
# ---------------------------------------------------------------------------


def test_pow_slab_live_compile_cache_and_verify_bytes():
    """A real ``ops/pow_search`` solve on the CPU backend populates
    pow_slab, its compile counted from JAX's events once setup_jax has
    installed the listener, and verify() populates pow_verify with
    upload bytes."""
    from pybitmessage_tpu.core.jaxsetup import setup_jax
    from pybitmessage_tpu.observability.devicetelemetry import _hist_stats
    from pybitmessage_tpu.ops import pow_search
    setup_jax()
    setup_jax()         # again: the listener must not count twice
    assert REGISTRY.sample("jax_backend_init_seconds") >= 0

    def shapes_compiled():
        return (_sample("device_program_compiles_total", "pow_slab")
                + _sample("device_program_cache_hits_total", "pow_slab"))

    launches0 = _sample("device_launches_total", "pow_slab")
    compiles0 = shapes_compiled()
    lowered0 = REGISTRY.sample("jax_compile_events_total",
                               {"phase": "lower"})

    # a lane count no other test uses, so this is the shape's first use
    nonce, trials = pow_search.solve(IH, ALWAYS, lanes=384,
                                     chunks_per_call=1)
    assert trials > 0
    assert _sample("device_launches_total", "pow_slab") > launches0
    assert shapes_compiled() == compiles0 + 1
    assert REGISTRY.sample("jax_compile_events_total",
                           {"phase": "lower"}) > lowered0
    count, total = _hist_stats("device_program_compile_seconds",
                               "pow_slab")
    assert count >= 1 and total > 0
    assert _sample("device_busy_seconds_total", "pow_slab") > 0
    assert _sample("device_work_items_total", "pow_slab") > 0

    pow_search.solve(IH, ALWAYS, lanes=384, chunks_per_call=1)
    # same static shape: nothing was compiled or loaded again
    assert shapes_compiled() == compiles0 + 1

    vlaunch0 = _sample("device_launches_total", "pow_verify")
    vbytes0 = _sample("device_h2d_bytes_total", "pow_verify")
    assert pow_search.verify([(nonce, IH, ALWAYS)]) == [True]
    assert _sample("device_launches_total", "pow_verify") == vlaunch0 + 1
    assert _sample("device_h2d_bytes_total", "pow_verify") > vbytes0
    assert _sample("device_hashrate_hps", "pow_slab") > 0


def test_pipeline_packed_search_xla_records():
    """The async pipeline's XLA packed path attributes its launches
    (the CPU-CI storm path)."""
    from pybitmessage_tpu.pow import pipeline
    launches0 = _sample("device_launches_total", "packed_search_xla")
    items = [(IH, ALWAYS)] * 4
    plan = pipeline.BatchPlan("packed", 2, 1, list(range(4)))
    out = pipeline.solve_batch_pipelined(items, rows=8, impl="xla",
                                         plan=plan)
    assert len(out) == 4
    assert _sample("device_launches_total",
                   "packed_search_xla") > launches0
    assert _sample("device_d2h_bytes_total", "packed_search_xla") > 0


def test_update_device_gauges_and_env_fingerprint():
    import jax
    from pybitmessage_tpu.observability.devicetelemetry import (
        _device_label, update_device_gauges)
    table = update_device_gauges()
    assert len(table) == len(jax.devices())
    assert table[0]["label"] == "d00"
    assert _device_label(0) == "d00"
    assert _device_label(999) == "overflow"
    env = env_fingerprint()
    assert env["python"]
    assert env["jax"]
    assert env["backend"] == jax.default_backend()
    assert env["device_count"] == len(jax.devices())
    assert "libtpu" in env  # None on CPU hosts, but always present


# ---------------------------------------------------------------------------
# status documents + API surface
# ---------------------------------------------------------------------------


def test_device_status_document_shape():
    st = device_status()
    assert set(st) == {"devices", "env", "programs", "dropped"}
    row = st["programs"]["pow_slab"]
    for key in ("module", "flopsPerItem", "launches", "compiles",
                "cacheHits", "compileSeconds", "dispatchSeconds",
                "executeWaitSeconds", "busySeconds", "h2dBytes",
                "d2hBytes", "donatedBytes", "donationRate",
                "workItems", "hashrateHps", "mfu"):
        assert key in row, key
    assert row["module"] == "ops/pow_search.py"
    json.dumps(st)  # the whole document is JSON-able


def test_cost_status_device_block():
    from pybitmessage_tpu.observability.profiling import cost_status
    block = cost_status()["device"]
    assert set(block) == {"busySeconds", "byProgram", "compileSeconds",
                          "executeWaitSeconds", "launches"}
    assert block == device_cost_block()
    assert block["launches"] >= 1
    assert block["byProgram"].get("pow_slab", 0) > 0
    assert block["busySeconds"] >= block["byProgram"]["pow_slab"]


def test_device_status_api_command_and_client_block():
    from pybitmessage_tpu.api.commands import APIError, CommandHandler

    async def body():
        handler = CommandHandler(SimpleNamespace())
        doc = json.loads(await handler.dispatch("deviceStatus", []))
        assert doc["programs"]["pow_slab"]["launches"] >= 1

        compact = handler._device_stats()
        assert set(compact) == {"programs", "env", "dropped"}
        assert compact["programs"]["pow_slab"]["launches"] >= 1
        # never-launched programs are elided from the compact block
        assert all(row["launches"] for row in
                   compact["programs"].values())

        with pytest.raises(APIError):
            await handler.dispatch("profileDevice", ["not-a-number"])

    asyncio.run(body())


def test_debug_device_endpoint():
    """``GET /debug/device`` serves the attribution table behind the
    same basic auth as every debug surface."""
    from pybitmessage_tpu.api import APIServer

    async def _get(port, path, auth=None):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        headers = "GET %s HTTP/1.1\r\n" % path
        if auth:
            headers += "Authorization: Basic %s\r\n" % auth
        writer.write((headers + "\r\n").encode())
        await writer.drain()
        response = await reader.read()
        writer.close()
        head, _, body = response.partition(b"\r\n\r\n")
        return int(head.split()[1]), body

    async def body():
        server = APIServer(SimpleNamespace(), port=0,
                           username="user", password="pass")
        await server.start()
        try:
            auth = base64.b64encode(b"user:pass").decode()
            status, _ = await _get(server.listen_port, "/debug/device")
            assert status == 401
            status, _ = await _get(server.listen_port,
                                   "/debug/device?seconds=nope", auth)
            assert status == 400
            status, raw = await _get(server.listen_port,
                                     "/debug/device", auth)
            assert status == 200
            doc = json.loads(raw)
            assert doc["programs"]["pow_slab"]["launches"] >= 1
            assert "env" in doc
        finally:
            await server.stop()

    asyncio.run(body())


def test_capture_device_trace_bounds_and_capture(tmp_path):
    with pytest.raises(ValueError):
        capture_device_trace(0)
    with pytest.raises(ValueError):
        capture_device_trace(61)
    out = capture_device_trace(0.1, out_dir=str(tmp_path))
    assert out["ok"] is True
    assert out["traceDir"] == str(tmp_path)
    assert out["seconds"] >= 0.1


def test_an_operators_capture_holds_the_programs_spans(tmp_path):
    """``profileDevice`` / ``GET /debug/device`` (this function) taken
    while a batch solves: the capture names the pipeline's launches
    and fetches, with nobody switching a bridge on."""
    import glob
    import threading

    from jax.profiler import ProfileData

    from pybitmessage_tpu.pow.pipeline import (BatchPlan,
                                               solve_batch_pipelined)
    # every object hits in its group's first launch (2,048 trials): a
    # solve is two launches, so whole solves fit the capture on a busy
    # host too (``pow.groups`` is once a solve)
    items = [(hashlib.sha512(b"captured %d" % i).digest(), 2 ** 64 // 200)
             for i in range(4)]

    def solve():
        # one small pinned shape: the capture must not be spent
        # compiling, nor drowned in the CPU backend's own events
        solve_batch_pipelined(items, impl="xla", rows=8,
                              plan=BatchPlan("batched", 1, 2, [0, 1, 2, 3]))
        time.sleep(0.01)
    solve()                                     # compiled before
    stop = threading.Event()

    def keep_solving():
        while not stop.is_set():
            solve()

    worker = threading.Thread(target=keep_solving)
    worker.start()
    try:
        # under the profiler the CPU backend's stand-in kernel is slow
        # (a solve takes 0.1-0.3 s): long enough for whole solves
        out = capture_device_trace(3.0, out_dir=str(tmp_path))
    finally:
        stop.set()
        worker.join()
    assert out["ok"] is True
    (path,) = glob.glob(str(tmp_path) + "/plugins/profile/*/*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert {"pow.groups", "pow.launch", "pow.fetch", "pow.harvest"} <= names


# ---------------------------------------------------------------------------
# tpu_doctor: failure-signature diagnosis golden
# ---------------------------------------------------------------------------


def test_doctor_diagnoses_device_busy_tail(tmp_path, capsys):
    """A recorded failure tail — what a second process gets while
    another holds the chip — maps to the named tpu-device-busy
    diagnosis with a nonzero exit."""
    import tools.tpu_doctor as doctor
    tail = ("jax.errors.JaxRuntimeError: ABORTED: Internal error when "
            "accessing libtpu multi-process lockfile. Run \"$ sudo rm "
            "/tmp/libtpu_lockfile\".")
    diag = doctor.diagnose_text(tail)
    assert diag["name"] == "tpu-device-busy"
    assert "JAX_PLATFORMS=cpu" in diag["hint"]

    golden = tmp_path / "record.json"
    golden.write_text(json.dumps({"rc": 1, "tail": tail}))
    rc = doctor.main(["--diagnose", str(golden)])
    assert rc == 1
    out = json.loads(capsys.readouterr().out)
    assert out["diagnosis"]["name"] == "tpu-device-busy"


def test_doctor_report_says_when_it_probed_in_interpret_mode(
        monkeypatch, tmp_path):
    """Off a TPU the probes run in interpret mode — fine for CI, but the
    report says so at top level: it proves the plumbing, not the chip."""
    import tools.tpu_doctor as doctor
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    report = doctor.run_preflight(skip_probes=True)
    assert report["interpret"] is True
    assert report["env"]["backend"] == "cpu"


def test_doctor_clean_tail_exits_zero(tmp_path, capsys):
    import tools.tpu_doctor as doctor
    benign = tmp_path / "tail.txt"
    benign.write_text("solver converged, all replicas healthy\n")
    assert doctor.main(["--diagnose", str(benign)]) == 0
    assert json.loads(capsys.readouterr().out)["diagnosis"] is None


def test_doctor_known_signatures():
    import tools.tpu_doctor as doctor
    cases = {
        "RuntimeError: Unable to initialize backend 'tpu': "
        "No TPU devices found": "no-tpu-found",
        "The TPU is already in use by process 4242": "tpu-device-busy",
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "8589934592 bytes": "device-out-of-memory",
        "DEADLINE_EXCEEDED: waiting for coordination service":
            "device-deadline-exceeded",
    }
    for tail, name in cases.items():
        diag = doctor.diagnose_text(tail)
        assert diag is not None and diag["name"] == name, tail
    assert doctor.diagnose_text("everything is fine") is None


# ---------------------------------------------------------------------------
# overhead budget
# ---------------------------------------------------------------------------


def test_record_launch_overhead_budget():
    """Per-launch recording cost must stay far below any real slab's
    wall clock (the perfguard band holds <2% on the ingest path; here
    the raw per-call cost must be microseconds, not milliseconds)."""
    prog = "t_overhead_unit"
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        record_launch(prog, key=128, dispatch_seconds=1e-4,
                      wait_seconds=1e-4, span=(float(i), float(i) + 0.5),
                      items=100, bytes_in=64, bytes_out=16)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 250e-6, "record_launch costs %.1fus" % (
        per_call * 1e6)
    assert _sample("device_launches_total", prog) == n


# ---------------------------------------------------------------------------
# bmlint devicelaunch checker
# ---------------------------------------------------------------------------

from tools.bmlint import run_checkers  # noqa: E402

TELEMETRY_PATH = "pybitmessage_tpu/observability/devicetelemetry.py"
TELEMETRY_FIXTURE = (
    '"""Catalog:\n'
    "\n"
    "``alpha`` — a documented program.\n"
    "``beta`` — documented but never registered.\n"
    '"""\n'
)
PKG_ROOT = ("pybitmessage_tpu/__init__.py", "")


def _lint(files, rules):
    found = run_checkers(list(files))
    return [f for f in found.findings if f.rule in rules]


def test_devicelaunch_unrouted_launch_site():
    src = ("import jax\n"
           "fn = jax.jit(lambda x: x)\n")
    found = _lint([("pybitmessage_tpu/ops/fixture.py", src)],
                  rules=("device-launch-unrouted",))
    assert len(found) == 1
    assert "device-telemetry" in found[0].message


def test_devicelaunch_routed_module_is_clean():
    src = ("import jax\n"
           "from ..observability.devicetelemetry import (\n"
           "    record_launch, register_program)\n"
           "register_program('alpha')\n"
           "fn = jax.jit(lambda x: x)\n")
    found = _lint([("pybitmessage_tpu/ops/fixture.py", src),
                   (TELEMETRY_PATH, TELEMETRY_FIXTURE)],
                  rules=("device-launch-unrouted",))
    assert found == []


def test_devicelaunch_pallas_call_is_a_launch_site():
    src = ("from jax.experimental import pallas as pl\n"
           "def k():\n"
           "    return pl.pallas_call(None)\n")
    found = _lint([("pybitmessage_tpu/parallel/fixture.py", src)],
                  rules=("device-launch-unrouted",))
    assert len(found) == 1


def test_devicelaunch_catalog_lockstep():
    user = ("from ..observability.devicetelemetry import "
            "register_program\n"
            "register_program('alpha')\n"
            "register_program('gamma')\n")
    found = _lint([PKG_ROOT, (TELEMETRY_PATH, TELEMETRY_FIXTURE),
                   ("pybitmessage_tpu/pow/fixture.py", user)],
                  rules=("device-program-unregistered",
                         "device-program-undocumented"))
    by_rule = {f.rule: f for f in found}
    assert len(found) == 2
    assert "'beta'" in by_rule["device-program-unregistered"].message
    assert "'gamma'" in by_rule["device-program-undocumented"].message


def test_devicelaunch_lockstep_silent_on_subset_sweep():
    """Without the package root (a per-path run) the cross-file
    lockstep rules must not fire."""
    user = ("from ..observability.devicetelemetry import "
            "register_program\n"
            "register_program('gamma')\n")
    found = _lint([(TELEMETRY_PATH, TELEMETRY_FIXTURE),
                   ("pybitmessage_tpu/pow/fixture.py", user)],
                  rules=("device-program-unregistered",
                         "device-program-undocumented"))
    assert found == []
