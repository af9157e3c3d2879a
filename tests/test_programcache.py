"""Persisted programs (``core/programcache.py``), on the CPU.

What is held here: the two search kernels the node launches export for
platform ``tpu`` from this host, and the round trip through a file's
bytes keeps the ``tpu_custom_call`` as a live lowering makes it; a
program's key changes with each of its parts and with nothing else; a
first start exports and writes, a later one loads without tracing the
function, a truncated file is replaced, an unwritable directory is
said once and the call still answers, two writers leave one whole
file; and what must stay as it was: an ordinary launch on the CPU
writes nothing, a call under a trace passes through, and the three
entry points keep their signature, ``lower`` and ``__wrapped__``.

The kernels themselves cannot run here but in interpret mode, which
takes minutes a launch (the ``slow`` test at the end), so the store is
driven end to end with a small Pallas program of its own, and the test
says that an accelerator of platform ``cpu`` is there.
"""

import inspect
import logging
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from pybitmessage_tpu.core import programcache as pc
from pybitmessage_tpu.observability import REGISTRY, TRACER

U32 = np.dtype(np.uint32)
STATIC_NAMES = ("rows", "chunks", "interpret", "unroll")


def _kernel_shapes():
    from pybitmessage_tpu.ops import sha512_pallas as sp
    from pybitmessage_tpu.pow.pipeline import (DEFAULT_BATCH_CHUNKS,
                                               plan_batch)
    n = sp.BATCH_OBJS
    lanes = plan_batch([(b"\x00" * 64, 2 ** 64 // 10 ** 7)], lanes=4)
    return {
        "batch": (sp.pallas_batch_search,
                  (((n, 8, 2), U32), ((n, 2), U32), ((n, 2), U32)),
                  {"rows": sp.BATCH_ROWS, "chunks": DEFAULT_BATCH_CHUNKS,
                   "unroll": sp.BATCH_UNROLL}),
        "lanes": (sp.pallas_search,
                  (((8, 2), U32), ((2,), U32), ((2,), U32)),
                  {"rows": sp.DEFAULT_ROWS, "chunks": lanes.chunks,
                   "unroll": sp.DEFAULT_UNROLL}),
    }


def _custom_call(text: str):
    """(backend_config, kernel_name) of a module's one Mosaic call."""
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 1
    config = re.search(r'backend_config = "((?:[^"\\]|\\.)*)"', text)
    name = re.search(r'kernel_name = "([^"]*)"', text)
    return config.group(1), name.group(1)


@pytest.mark.parametrize("which", ["batch", "lanes"])
def test_the_round_trip_keeps_the_kernel_as_a_live_lowering_makes_it(
        which):
    """64 objects x 64 rows x 1,024 steps of the batch kernel, and the
    slab a lone object's lanes launch: exported for ``tpu`` from the
    CPU host, through bytes and back, lowered for ``tpu`` again."""
    entry, avals, static = _kernel_shapes()[which]
    jitted = jax.jit(entry.__wrapped__, static_argnames=STATIC_NAMES)
    exported = pc.export_program(jitted, "tpu", avals, static)
    assert exported.fun_name == entry.__name__
    assert tuple(exported.platforms) == ("tpu",)
    back = jax.export.deserialize(exported.serialize())
    assert pc._fits(back, "tpu", avals)
    specs = [jax.ShapeDtypeStruct(s, d) for s, d in avals]
    stored = jax.jit(back.call).trace(*specs).lower(
        lowering_platforms=("tpu",)).as_text()
    live = entry.trace(*specs, **static).lower(
        lowering_platforms=("tpu",)).as_text()
    assert _custom_call(stored) == _custom_call(live)
    assert _custom_call(live)[1] == {"batch": "_batch_kernel",
                                     "lanes": "_kernel"}[which]


# -- the key ------------------------------------------------------------

KEY = {"name": "pallas_search",
       "static": {"rows": 128, "chunks": 512, "unroll": 5,
                  "interpret": False},
       "avals": (((8, 2), U32), ((2,), U32), ((2,), U32)),
       "environment": ("0.9.0", "0.9.0", "tpu", "libtpu 1; built X",
                       "TPU v5 lite"),
       "sources": "ab" * 32}


def _key(**changed):
    return pc.program_key(**{**KEY, **changed})


@pytest.mark.parametrize("part, value", [
    ("name", "pallas_batch_search"),
    ("static", {**KEY["static"], "chunks": 64}),
    ("static", {**KEY["static"], "unroll": 1}),
    ("avals", (((8, 2), U32), ((2,), U32), ((4,), U32))),
    ("avals", (((8, 2), U32), ((2,), U32), ((2,), np.dtype(np.int32)))),
    ("environment", ("0.9.1",) + KEY["environment"][1:]),
    ("environment", KEY["environment"][:1] + ("0.9.1",)
     + KEY["environment"][2:]),
    ("environment", KEY["environment"][:3] + ("libtpu 2; built Y",)
     + KEY["environment"][4:]),
    ("environment", KEY["environment"][:4] + ("TPU v6 lite",)),
    ("sources", "ab" * 31 + "ac"),
])
def test_the_key_changes_with_each_of_its_parts(part, value):
    assert _key(**{part: value}) != _key()


def test_the_key_changes_with_nothing_else():
    """Not with the order the static arguments were given in, a list
    for a tuple or a dtype by another spelling."""
    again = _key(static=dict(reversed(list(KEY["static"].items()))),
                 avals=[([8, 2], np.uint32), ([2], "uint32"),
                        ((2,), jnp.uint32)],
                 environment=list(KEY["environment"]))
    assert again == _key()
    assert re.fullmatch(r"[0-9a-f]{40}", again)


def test_one_byte_of_a_source_file_is_another_program(tmp_path):
    from pybitmessage_tpu.ops import sha512_pallas as sp
    assert [p.rsplit("/", 1)[1] for p in sp._SOURCES] == [
        "sha512_pallas.py", "sha512_jax.py", "u64.py"]
    copies = []
    for source in sp._SOURCES:
        copy = tmp_path / source.rsplit("/", 1)[1]
        copy.write_bytes(open(source, "rb").read())
        copies.append(str(copy))
    copies = tuple(copies)
    assert pc.source_digest(copies) == pc.source_digest(sp._SOURCES)
    for path in copies:
        pc.source_digest.cache_clear()
        before = pc.source_digest(copies)
        text = open(path).read()
        assert "0" in text
        open(path, "w").write(text.replace("0", "1", 1))
        pc.source_digest.cache_clear()
        assert pc.source_digest(copies) != before
    pc.source_digest.cache_clear()


# -- the store, end to end, with a program of its own -------------------

def _add_kernel(x_ref, o_ref, *, step):
    o_ref[...] = x_ref[...] + jnp.uint32(step)


class Store:
    """One machine's cache directory and a source file, with as many
    'processes' (fresh decorations of one function) as a test asks
    for."""

    def __init__(self, tmp_path, monkeypatch):
        self.dir = tmp_path / "cache"
        self.source = tmp_path / "source.py"
        self.source.write_text("STEP = 1\n")
        self.traced = 0
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(self.dir))
        monkeypatch.setattr(pc, "accelerator", lambda: "cpu")
        pc.source_digest.cache_clear()

    def process(self):
        def add_step(x, step: int = 1, interpret: bool = True):
            self.traced += 1
            return pl.pallas_call(
                lambda x_ref, o_ref: _add_kernel(x_ref, o_ref, step=step),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=True)(x)
        pc.source_digest.cache_clear()
        return pc.persisted_jit(
            sources=(str(self.source),),
            static_argnames=("step", "interpret"))(add_step)

    def files(self):
        return sorted(p.name for p in self.dir.rglob("*") if p.is_file())

    @staticmethod
    def counted():
        fam = REGISTRY.get("program_cache_total")
        return {values[1]: child.value for values, child in fam.children()
                if values[0] == "add_step"}


@pytest.fixture
def store(tmp_path, monkeypatch):
    return Store(tmp_path, monkeypatch)


def _grown(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


X = np.arange(8 * 128, dtype=np.uint32).reshape(8, 128)


def test_a_first_start_exports_and_a_later_one_loads_without_tracing(
        store):
    before = store.counted()
    loads = REGISTRY.get("program_cache_load_seconds")
    first = store.process()
    # interpret is this toy's way onto the CPU, not its caller's word
    np.testing.assert_array_equal(first(X, step=3, interpret=False), X + 3)
    assert store.traced == 1
    (name,) = store.files()
    assert re.fullmatch(r"add_step-[0-9a-f]{40}\.jaxexport", name)
    assert (store.dir / "programs" / name).is_file()
    np.testing.assert_array_equal(first(X, step=3, interpret=False), X + 3)
    assert _grown(before, store.counted()) == {"miss": 1}
    later = store.process()
    np.testing.assert_array_equal(later(X, step=3, interpret=False), X + 3)
    np.testing.assert_array_equal(later(X, interpret=False, step=3), X + 3)
    assert store.traced == 1, "a loaded program traced its function"
    assert _grown(before, store.counted()) == {"miss": 1, "hit": 1}
    assert store.files() == [name]
    hits = [c for values, c in loads.children() if values == ("add_step",)]
    assert hits and hits[0].snapshot()[2] >= 1
    span = TRACER.recent(1, name="program.load")[-1]
    assert span.attrs == {"program": "add_step", "outcome": "hit"}
    # another static argument or another shape is another program
    np.testing.assert_array_equal(later(X, step=4, interpret=False), X + 4)
    np.testing.assert_array_equal(
        later(X[:, :64].copy(), step=3, interpret=False), X[:, :64] + 3)
    assert len(store.files()) == 3
    assert _grown(before, store.counted()) == {"miss": 3, "hit": 1}


def test_one_loaded_program_serves_every_chip_of_a_host(store):
    """The lanes of a pod launch one shape on four devices: one file,
    one lookup, and each launch runs where its operands are."""
    before = store.counted()
    store.process()(X, step=1, interpret=False)
    entry = store.process()
    devices = jax.devices()[:4]
    assert len(devices) == 4
    for device in devices:
        out = entry(jax.device_put(X, device), step=1, interpret=False)
        assert out.devices() == {device}
        np.testing.assert_array_equal(out, X + 1)
    assert _grown(before, store.counted()) == {"miss": 1, "hit": 1}
    assert len(store.files()) == 1 and store.traced == 1


def test_an_edit_of_the_source_makes_the_next_start_a_miss(store):
    before = store.counted()
    store.process()(X, step=1, interpret=False)
    store.source.write_text("STEP = 2\n")
    store.process()(X, step=1, interpret=False)
    assert _grown(before, store.counted()) == {"miss": 2}
    assert len(store.files()) == 2
    store.process()(X, step=1, interpret=False)
    assert _grown(before, store.counted()) == {"miss": 2, "hit": 1}


@pytest.mark.parametrize("damage", ["truncated", "empty", "other_avals"])
def test_a_file_that_does_not_fit_is_stale_replaced_and_counted(
        store, damage):
    store.process()(X, step=1, interpret=False)
    (name,) = store.files()
    path = store.dir / "programs" / name
    whole = path.read_bytes()
    if damage == "other_avals":
        store.process()(X[:4].copy(), step=1, interpret=False)
        (other,) = [n for n in store.files() if n != name]
        (store.dir / "programs" / other).replace(path)
    else:
        path.write_bytes(whole[:len(whole) // 2]
                         if damage == "truncated" else b"")
    before = store.counted()
    out = store.process()(X, step=1, interpret=False)
    np.testing.assert_array_equal(out, X + 1)
    assert _grown(before, store.counted()) == {"stale": 1}
    # whole again (its bytes hold the lines it was traced from)
    assert pc._fits(jax.export.deserialize(bytearray(path.read_bytes())),
                    "cpu", [(X.shape, X.dtype)])
    assert len(path.read_bytes()) == len(whole)
    assert store.files() == [name]
    store.process()(X, step=1, interpret=False)
    assert _grown(before, store.counted()) == {"stale": 1, "hit": 1}


def test_an_unwritable_directory_is_said_once_and_the_call_answers(
        store, caplog):
    store.dir.write_text("a file where the directory should be")
    before = store.counted()
    entry = store.process()
    with caplog.at_level(logging.WARNING, logger="pybitmessage_tpu.core"):
        for _ in range(3):
            np.testing.assert_array_equal(
                entry(X, step=1, interpret=False), X + 1)
    assert _grown(before, store.counted()) == {"error": 1}
    said = [r for r in caplog.records if "cannot be written" in r.message]
    assert len(said) == 1 and said[0].levelno == logging.WARNING
    assert store.traced == 1, "the export's trace was paid twice"
    assert store.dir.read_text().startswith("a file")


def test_an_export_the_platform_refuses_traces_live(store, monkeypatch,
                                                     caplog):
    def refuse(*_a, **_k):
        raise NotImplementedError("no export for this platform")
    monkeypatch.setattr(pc, "export_program", refuse)
    before = store.counted()
    entry = store.process()
    with caplog.at_level(logging.WARNING, logger="pybitmessage_tpu.core"):
        for _ in range(2):
            np.testing.assert_array_equal(
                entry(X, step=1, interpret=False), X + 1)
    assert _grown(before, store.counted()) == {"error": 1}
    assert len([r for r in caplog.records
                if "cannot be loaded or exported" in r.message]) == 1
    assert store.files() == []


def test_two_writers_of_one_key_leave_one_whole_file(tmp_path):
    path = tmp_path / "programs" / "p-0.jaxexport"
    blobs = [bytes([i]) * (1 << 20) for i in range(8)]
    start = threading.Barrier(len(blobs))

    def write(blob):
        start.wait()
        for _ in range(4):
            pc.write_whole(path, blob)
    threads = [threading.Thread(target=write, args=(b,)) for b in blobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert path.read_bytes() in blobs
    assert [p.name for p in path.parent.iterdir()] == [path.name]


def test_a_write_that_fails_leaves_no_temporary_file(tmp_path,
                                                     monkeypatch):
    path = tmp_path / "programs" / "p-0.jaxexport"
    pc.write_whole(path, b"whole")

    def fail(*_a):
        raise OSError("disk full")
    monkeypatch.setattr(pc.os, "replace", fail)
    with pytest.raises(OSError):
        pc.write_whole(path, b"half")
    assert path.read_bytes() == b"whole"
    assert [p.name for p in path.parent.iterdir()] == [path.name]


def test_an_export_runs_under_a_frame_with_a_chunk_of_its_own():
    """CPython 3.12 gives a frame that does not fit a 16 KiB chunk the
    next power of two that holds it: a little over 128 KiB of locals
    is a chunk of 256 KiB with half of it free for the trace below."""
    roomy = pc._roomy_frame()
    assert roomy is pc._roomy_frame()
    assert roomy(lambda: "answer") == "answer"
    assert 128 * 1024 < 8 * roomy.__code__.co_nlocals < 136 * 1024
    with pytest.raises(ZeroDivisionError):
        roomy(lambda: 1 // 0)
    seen = []
    jitted = jax.jit(lambda x: (seen.append(sys._getframe(1)), x + 1)[1])
    exported = pc.export_program(jitted, "cpu", [((4,), np.float32)], {})
    assert exported.in_avals[0].shape == (4,)
    names = []
    frame = seen[0]
    while frame is not None:
        names.append(frame.f_code.co_name)
        frame = frame.f_back
    assert "roomy" in names and names.index("roomy") < names.index(
        "export_program")


# -- what stays as it was ----------------------------------------------

def test_an_ordinary_cpu_launch_writes_no_file(tmp_path, monkeypatch):
    """No accelerator said to be there: the backend is ``cpu``."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert pc.accelerator() is None
    store = Store.__new__(Store)
    store.dir, store.source, store.traced = (tmp_path / "c",
                                             tmp_path / "s.py", 0)
    store.source.write_text("")
    before = Store.counted()
    entry = store.process()
    np.testing.assert_array_equal(entry(X, step=2), X + 2)
    np.testing.assert_array_equal(entry(X, step=2, interpret=False),
                                  X + 2)
    assert not store.dir.exists()
    assert _grown(before, Store.counted()) == {}


def test_interpret_mode_is_never_persisted(store):
    before = store.counted()
    entry = store.process()
    np.testing.assert_array_equal(entry(X, step=2, interpret=True), X + 2)
    np.testing.assert_array_equal(entry(X, step=2), X + 2)  # its default
    assert store.files() == []
    assert _grown(before, store.counted()) == {}


def test_a_call_under_a_trace_passes_through(store):
    from jax.sharding import Mesh, PartitionSpec as P
    entry = store.process()
    before = store.counted()

    @jax.jit
    def outer(x):
        return entry(x, step=5, interpret=False) + jnp.uint32(1)
    np.testing.assert_array_equal(outer(X), X + 6)
    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
    sharded = jax.shard_map(
        lambda x: entry(x, step=5, interpret=False), mesh=mesh,
        in_specs=P("d"), out_specs=P("d"), check_vma=False)
    np.testing.assert_array_equal(
        sharded(np.concatenate([X, X])), np.concatenate([X, X]) + 5)
    assert store.files() == []
    assert _grown(before, store.counted()) == {}


def test_a_static_argument_by_position_goes_to_the_jitted_function(store):
    entry = store.process()
    np.testing.assert_array_equal(entry(X, 7, False), X + 7)
    assert store.files() == []


@pytest.mark.parametrize("name, static", [
    ("pallas_search", ("rows", "chunks", "interpret", "unroll")),
    ("pallas_batch_search", ("rows", "chunks", "interpret", "unroll")),
    ("pallas_packed_search", ("rows", "chunks", "pack", "unroll",
                              "interpret")),
])
def test_the_entry_points_keep_what_the_tree_uses(name, static):
    from pybitmessage_tpu.ops import sha512_pallas as sp
    entry = getattr(sp, name)
    raw = entry.__wrapped__
    assert inspect.isfunction(raw) and raw.__name__ == name
    params = inspect.signature(entry).parameters
    assert inspect.signature(entry) == inspect.signature(raw)
    assert set(static) <= set(params)
    assert params["interpret"].default is False
    assert entry.__name__ == name and entry.__doc__ == raw.__doc__
    # the AOT compiles (tests/test_tpu_compile.py) go through these
    assert entry.lower.__self__ is entry.trace.__self__
    assert entry.lower.__self__.__wrapped__ is raw


def test_the_kernels_module_imports_first_in_a_fresh_process():
    """``core`` no longer imports the node with the package, so ``ops``
    may take ``persisted_jit`` from it whoever is imported first."""
    code = ("import pybitmessage_tpu.ops.sha512_pallas as sp, sys\n"
            "assert 'pybitmessage_tpu.core.programcache' in sys.modules\n"
            "assert 'pybitmessage_tpu.core.node' not in sys.modules\n"
            "from pybitmessage_tpu.core import Node\n"
            "assert Node.__module__ == 'pybitmessage_tpu.core.node'\n")
    done = subprocess.run([sys.executable, "-c", code], timeout=120,
                          capture_output=True, text=True,
                          env={**__import__("os").environ,
                               "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]


def test_the_cache_directory_is_the_one_setup_jax_places(monkeypatch,
                                                         tmp_path):
    from pybitmessage_tpu.core import jaxsetup
    monkeypatch.setenv(jaxsetup.CACHE_ENV, str(tmp_path))
    assert jaxsetup.cache_dir() == str(tmp_path) == jaxsetup.setup_jax()
    monkeypatch.delenv(jaxsetup.CACHE_ENV)
    assert jaxsetup.cache_dir() == str(jaxsetup.DEFAULT_CACHE_DIR)


# -- ONE program over several devices (ISSUE 49) -------------------------


class Spanning(Store):
    """A ``shard_map`` over four devices under the store: each device
    adds its place in the mesh and ``step``, and all gather the rows."""

    def process(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:4]), ("nonce",))

        def add_place(x, step: int = 1, interpret: bool = True):
            self.traced += 1

            def one(row):
                place = jax.lax.axis_index("nonce").astype(jnp.uint32)
                return jax.lax.all_gather(row + place + jnp.uint32(step),
                                          "nonce", tiled=True)
            return jax.shard_map(one, mesh=mesh, in_specs=P("nonce", None),
                                 out_specs=P(), check_vma=False)(x)
        pc.source_digest.cache_clear()
        return pc.persisted_jit(
            sources=(str(self.source),),
            static_argnames=("step", "interpret"),
            shardings=((NamedSharding(mesh, P("nonce", None)),),
                       NamedSharding(mesh, P())))(add_place)

    @staticmethod
    def counted():
        fam = REGISTRY.get("program_cache_total")
        return {values[1]: child.value for values, child in fam.children()
                if values[0] == "add_place"}


ROWS4 = np.arange(4 * 8, dtype=np.uint32).reshape(4, 8)
PLACED = ROWS4 + np.arange(4, dtype=np.uint32)[:, None]


def test_a_program_over_four_devices_is_exported_once_and_loaded_after(
        tmp_path, monkeypatch):
    store = Spanning(tmp_path, monkeypatch)
    before = store.counted()
    first = store.process()
    out = first(ROWS4, step=2, interpret=False)
    np.testing.assert_array_equal(out, PLACED + 2)
    # the same answer on every device: the host reads one
    assert len(out.devices()) == 4 and out.is_fully_replicated
    (name,) = store.files()
    assert re.fullmatch(r"add_place-[0-9a-f]{40}\.jaxexport", name)
    stored = jax.export.deserialize(bytearray(
        (store.dir / "programs" / name).read_bytes()))
    assert stored.nr_devices == 4
    later = store.process()
    np.testing.assert_array_equal(later(ROWS4, step=2, interpret=False),
                                  PLACED + 2)
    assert store.traced == 1, "a loaded program traced its function"
    assert _grown(before, store.counted()) == {"miss": 1, "hit": 1}
    assert store.files() == [name]


def test_the_device_count_is_in_a_spanning_program_s_key():
    static = {"rows": 128, "chunks": 512}
    avals = [((4, 20), U32)]
    ENV = ("0.9.0", "0.9.0", "tpu", "libtpu 0.0.34", "TPU v5 lite")
    one = pc.program_key("ici_search", static, avals, ENV, "digest")
    four = pc.program_key("ici_search", dict(static, devices=4), avals,
                          ENV, "digest")
    eight = pc.program_key("ici_search", dict(static, devices=8), avals,
                           ENV, "digest")
    assert len({one, four, eight}) == 3


def test_a_file_made_for_another_number_of_devices_is_stale(
        tmp_path, monkeypatch):
    """The key holds the device count, so only a file put there by hand
    can disagree: it is replaced, never called."""
    store = Spanning(tmp_path, monkeypatch)
    store.process()(ROWS4, step=2, interpret=False)
    (name,) = store.files()
    single = jax.export.export(jax.jit(lambda x: x + jnp.uint32(1)),
                               platforms=("cpu",))(
        jax.ShapeDtypeStruct((4, 8), U32))
    (store.dir / "programs" / name).write_bytes(single.serialize())
    before = store.counted()
    np.testing.assert_array_equal(
        store.process()(ROWS4, step=2, interpret=False), PLACED + 2)
    assert _grown(before, store.counted()) == {"stale": 1}
    assert store.traced == 2


def test_the_search_programs_keys_do_not_hold_a_device_count(store):
    """A program of one device is keyed as it was before there were
    others: the three search programs' files stay where every machine's
    caches have them."""
    store.process()(X, step=1, interpret=False)
    (name,) = store.files()
    static = {"step": 1, "interpret": False}
    assert name == "add_step-%s.jaxexport" % pc.program_key(
        "add_step", static, [(X.shape, X.dtype)], pc.environment(),
        pc.source_digest((str(store.source),)))


@pytest.mark.slow
def test_the_search_kernel_exported_for_the_cpu_answers_as_the_live_call():
    """``pallas_search`` in interpret mode (minutes on the CPU), one
    tile, a target that every nonce meets."""
    from pybitmessage_tpu.ops import sha512_pallas as sp
    static = {"rows": 1, "chunks": 1, "unroll": 1, "interpret": True}
    avals = (((8, 2), U32), ((2,), U32), ((2,), U32))
    jitted = jax.jit(sp.pallas_search.__wrapped__,
                     static_argnames=STATIC_NAMES)
    back = jax.export.deserialize(
        pc.export_program(jitted, "cpu", avals, static).serialize())
    ih = np.arange(16, dtype=np.uint32).reshape(8, 2)
    base = np.zeros(2, np.uint32)
    target = np.full(2, 0xFFFFFFFF, np.uint32)
    stored = jax.jit(back.call)(ih, base, target)
    live = sp.pallas_search(ih, base, target, **static)
    assert np.asarray(live[0])[0]
    for a, b in zip(stored, live):
        np.testing.assert_array_equal(a, b)
