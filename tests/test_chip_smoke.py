"""Rehearsal of chip_smoke.py on the CPU (ISSUE 22).

The script proper must fail at its first check without a TPU.  The
rehearsal patches that check, and everything that makes the flow
tractable here, INSIDE the test — the script has no option for any of
it: trivial difficulty on both nodes, the dispatcher pointed at the
Mosaic tiers, and the three SHA-512 kernels swapped for their XLA
stand-ins of the same contract at a tiny tile (interpret mode takes
minutes to compile on a CPU).  The host side — SendWorker, PowService,
the dispatcher's tiers, the pipeline, both batch engines, telemetry —
is the product's own.
"""

import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import chip_smoke  # noqa: E402


def _last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


@pytest.fixture(autouse=True)
def _no_cache_in_the_checkout(monkeypatch, tmp_path):
    """main() places the compile cache: with the variable set it sets
    nothing in code, so the test process keeps its configuration."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_unpatched_fails_on_a_cpu_backend(capsys):
    rc = chip_smoke.main([])
    assert rc != 0
    out = _last_json(capsys.readouterr().out)
    assert out["ok"] is False
    assert out["device"]["platform"] == "cpu"


def test_four_chip_option_fails_on_a_cpu_backend(capsys):
    assert chip_smoke.main(["--chips", "4"]) != 0
    assert _last_json(capsys.readouterr().out)["ok"] is False


def test_platform_check_names_what_is_missing():
    class Dev:
        def __init__(self, platform):
            self.platform, self.device_kind = platform, "kind"

    assert "platform 'cpu'" in chip_smoke.platform_ok([Dev("cpu")], 1)
    assert "needs 4" in chip_smoke.platform_ok([Dev("tpu")], 4)
    assert chip_smoke.platform_ok([Dev("tpu")] * 4, 4) is None
    assert chip_smoke.device_block([Dev("tpu")] * 4) == {
        "platform": "tpu", "kind": "kind", "count": 4}


@pytest.fixture
def rehearsal(monkeypatch):
    """The CPU stand-in for the chip: see the module docstring."""
    from pybitmessage_tpu.ops import sha512_pallas
    from pybitmessage_tpu.pow import pipeline, verify_service
    from pybitmessage_tpu.pow.dispatcher import PowDispatcher

    monkeypatch.setattr(chip_smoke, "QUEUE_MESSAGES", 6)
    monkeypatch.setattr(chip_smoke, "MESSAGE_BYTES", (40, 300))
    monkeypatch.setattr(chip_smoke, "VERIFY_BURST", 8)
    monkeypatch.setattr(chip_smoke, "SPOILED", 2)
    monkeypatch.setattr(chip_smoke, "CRYPTO_DRAIN", 16)
    monkeypatch.setattr(chip_smoke, "PHASE_TIMEOUT", 240.0)
    # trivial objects plan as single-sync / packed launches, so the
    # packed kernel compiles at more than one shape here
    monkeypatch.setitem(chip_smoke.EXPECTED_COMPILES, "packed_search", 8)
    monkeypatch.setattr(PowDispatcher, "_on_accelerator",
                        lambda self: True)
    monkeypatch.setattr(PowDispatcher, "_device_count", lambda self: 1)
    monkeypatch.setattr(verify_service, "_accelerator_backend",
                        lambda: True)
    for key, value in (("rows", 8), ("impl", "pallas")):
        monkeypatch.setitem(pipeline.solve_batch_pipelined.__kwdefaults__,
                            key, value)
    # a lone object's slab, cut to the tile: the plan and the pipeline
    # read these two where they launch pallas_search
    monkeypatch.setattr(pipeline, "DEFAULT_CHUNKS", 4)
    monkeypatch.setattr(pipeline, "DEFAULT_UNROLL", 1)

    import jax

    from pybitmessage_tpu.parallel.pow_pallas_sharded import _xla_slab
    slab = jax.jit(_xla_slab, static_argnames=("rows", "chunks"))

    def search(ih_words, base, target, rows, chunks, unroll, interpret):
        return slab(ih_words, base, target, rows=rows,
                    chunks=chunks * unroll)

    def batch(ih_words, bases, targets, rows, chunks, unroll, interpret,
              pack=1):
        return pipeline._packed_search_xla(
            ih_words, bases, targets,
            lanes=(rows // pack) * 128 * unroll, chunks=chunks)

    monkeypatch.setattr(sha512_pallas, "pallas_search", search)
    monkeypatch.setattr(sha512_pallas, "pallas_batch_search", batch)
    monkeypatch.setattr(pipeline, "pallas_packed_search", batch)

    async def easy_pair():
        pair = await chip_smoke.setup_pair()
        for node in pair[:2]:
            node.ctx.pow_ntpb = node.ctx.pow_extra = 1
            for part in (node.sender, node.processor):
                part.min_ntpb = part.min_extra = 1
            node.pow_verifier.ntpb = node.pow_verifier.extra = 1
            # the burst is cut to VERIFY_BURST = 8 here
            node.pow_verifier.min_device_batch = 8
        for ident in pair[2:]:
            ident.nonce_trials_per_byte = ident.extra_bytes = 1
        return pair
    return easy_pair


@pytest.mark.asyncio
async def test_rehearsal_passes_with_the_platform_check_patched(
        rehearsal, capsys):
    rep = chip_smoke.Report()
    await chip_smoke.run_one_chip(rep, pair_factory=rehearsal)
    chip_smoke.print_compile_table(rep, {})
    out = capsys.readouterr().out
    assert rep.failures == [], out
    assert "crypto rung: native" in out     # auto is off on a CPU
    assert "every nonce valid by hashlib" in out
    # the compile table; the 1,000-byte message is planned as slabs
    assert "shape key: pallas_slab" in out


@pytest.mark.asyncio
async def test_forced_fall_through_fails_the_smoke(rehearsal, capsys):
    from pybitmessage_tpu.resilience.chaos import CHAOS
    CHAOS.arm("pow.device_launch", probability=1.0, count=1)
    try:
        rep = chip_smoke.Report()
        await chip_smoke.run_one_chip(rep, pair_factory=rehearsal)
    finally:
        CHAOS.disarm("pow.device_launch")
    out = capsys.readouterr().out
    assert any("pow_fallback_total" in f for f in rep.failures), out
    assert any("breaker" in f for f in rep.failures), out


def test_cache_dir_from_the_environment_sets_nothing_in_code(tmp_path):
    import jax

    from pybitmessage_tpu.core.jaxsetup import setup_jax
    before = jax.config.jax_compilation_cache_dir
    assert setup_jax() == str(tmp_path)     # the autouse fixture's
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    import jax

    from pybitmessage_tpu.core import jaxsetup
    monkeypatch.delenv(jaxsetup.CACHE_ENV)
    before = jax.config.jax_compilation_cache_dir
    try:
        root = pathlib.Path(chip_smoke.__file__).resolve().parent
        assert jaxsetup.setup_jax() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            str(root / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_pod_path_rehearsal_on_virtual_devices(monkeypatch, capsys):
    """--chips 4's phase on the virtual CPU devices: a lone object laid
    out over the pipeline's lanes and a queue placed over the devices,
    with their XLA stand-ins at a tiny tile; the ``shard_map``
    partition is not called."""
    from pybitmessage_tpu import parallel
    from pybitmessage_tpu.pow import pipeline
    from pybitmessage_tpu.pow.dispatcher import PowDispatcher

    def never(*_a, **_kw):
        raise AssertionError("the smoke called the shard_map partition")

    monkeypatch.setattr(chip_smoke, "NETWORK_NTPB", 1)
    monkeypatch.setattr(chip_smoke, "NETWORK_EXTRA", 1)
    monkeypatch.setattr(chip_smoke, "QUEUE_MESSAGES", 8)
    monkeypatch.setattr(chip_smoke, "MESSAGE_BYTES", (200, 2000))
    monkeypatch.setattr(chip_smoke, "POD_SINGLE_OBJECTS", 4)
    monkeypatch.setattr(PowDispatcher, "_on_accelerator",
                        lambda self: True)
    monkeypatch.setattr(parallel, "pallas_sharded_solve", never)
    monkeypatch.setattr(parallel, "make_mesh", never)
    monkeypatch.setitem(pipeline.solve_batch_pipelined.__kwdefaults__,
                        "rows", 8)
    # a queue of easy objects planned as one at network difficulty is:
    # whole tiles, no packing; and a lone one in slabs, a short one a
    # lane
    monkeypatch.setattr(pipeline, "PACK_CHOICES", ())
    monkeypatch.setattr(pipeline, "DEFAULT_BATCH_CHUNKS", 4)
    monkeypatch.setattr(pipeline, "SYNC_SINGLE_STEPS", 0)
    monkeypatch.setattr(pipeline, "DEFAULT_CHUNKS", 16)
    monkeypatch.setattr(pipeline, "LONE_LANES_CHUNKS", 16)
    rep = chip_smoke.Report()
    chip_smoke.run_pod(rep)
    out = capsys.readouterr().out
    assert rep.failures == [], out
    assert "single solve backend 'tpu-pallas'" in out
    assert "winners came from more than one lane" in out
    assert "'tpu-pallas-batch'" in out
    assert "every device took launches of the batch" in out
