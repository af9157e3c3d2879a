#!/usr/bin/env python
"""TPU preflight doctor (docs/observability.md "Device telemetry").

``make doctor`` runs this before any multi-chip rendezvous: it
fingerprints the accelerator stack (jax / jaxlib / libtpu versions,
device kind, count, topology), then compile-probes every program in
the device-telemetry catalog with a 1-lane / always-hit-target shape —
the cheapest input that still walks each kernel through trace +
compile + one launch + readback on the live backend.  A probe failure
is matched against a table of known failure signatures (no TPU, a
chip another process holds, out of memory, a rendezvous deadline) and
turned into a NAMED diagnosis with a remediation hint instead of a
40-frame traceback.

Exit status: 0 when every probe passes, 1 otherwise — the multi-chip
driver (ROADMAP item 3) gates the expensive pod rendezvous on it.
Output is one JSON report on stdout (humans and CI both parse it).

``--diagnose FILE`` skips the live probes and instead classifies a
recorded failure tail — either a JSON document with a ``tail`` field
or a raw text log.  A recognized signature prints the
diagnosis and exits 1; an unrecognized tail exits 0 with
``diagnosis: null`` (nothing actionable to report).

Probes run with ``interpret=True`` Pallas on non-TPU backends, so the
doctor is CI-runnable on the CPU mesh — the same parity contract the
rest of the test suite uses.  The report says so at top level
(``"interpret": true``): such a run proves the plumbing, not the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

# runnable as `python tools/tpu_doctor.py` from a checkout: the repo
# root (the package's parent) must be importable
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

#: known failure signatures, checked in order: (regex over the failure
#: text, diagnosis name, remediation hint).
SIGNATURES: list[tuple[str, str, str]] = [
    (r"Unable to initialize backend '?tpu'?|No visible TPU|"
     r"failed to open libtpu|libtpu\.so.*(not found|no such file)",
     "no-tpu-found",
     "no TPU runtime is reachable: check the host actually has "
     "accelerators attached and libtpu is installed; on CPU hosts run "
     "with JAX_PLATFORMS=cpu instead"),
    (r"already in use|libtpu.*in use|Device or resource busy|"
     r"libtpu multi-process lockfile",
     "tpu-device-busy",
     "another process holds the TPU (one process per chip): stop the "
     "other client, or start this one with JAX_PLATFORMS=cpu; do not "
     "remove libtpu's lock file"),
    (r"RESOURCE_EXHAUSTED|out of memory|OOM",
     "device-out-of-memory",
     "the probe shape exceeded device memory: another tenant may be "
     "resident, or HBM is fragmented — check deviceStatus memory "
     "gauges and restart the runtime"),
    (r"DEADLINE_EXCEEDED|deadline exceeded",
     "device-deadline-exceeded",
     "a collective or launch timed out: a peer host in the pod "
     "likely died or never joined the rendezvous — run `make doctor` "
     "on every participating host"),
]

#: always-hit PoW target: every trial value is <= 2^64-1, so a probe
#: solve finishes inside its first (tiny) slab
_ALWAYS = (1 << 64) - 1
_IH = bytes(range(64))


def diagnose_text(text: str):
    """Match ``text`` against the signature table.

    Returns ``{"name", "hint", "match"}`` or None.
    """
    for pattern, name, hint in SIGNATURES:
        m = re.search(pattern, text, re.IGNORECASE)
        if m:
            return {"name": name, "hint": hint, "match": m.group(0)}
    return None


# ---------------------------------------------------------------------------
# 1-lane compile probes, one per catalog program
# ---------------------------------------------------------------------------


def _meshes():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    mesh1 = Mesh(np.array(devs), ("d",))
    if len(devs) % 2 == 0 and len(devs) > 1:
        grid = np.array(devs).reshape(2, len(devs) // 2)
    else:
        grid = np.array(devs).reshape(1, len(devs))
    return mesh1, Mesh(grid, ("obj", "nonce"))


def _interpret():
    import jax
    return jax.default_backend() != "tpu"


def _probe_pow_slab():
    from pybitmessage_tpu.ops import pow_search
    pow_search.solve(_IH, _ALWAYS, lanes=128, chunks_per_call=1)


def _probe_pow_verify():
    from pybitmessage_tpu.ops import pow_search
    pow_search.verify([(0, _IH, _ALWAYS)])


def _probe_pipeline(mode: str, n: int = 1, pack: int = 1,
                    impl: str = "pallas"):
    """One always-hit launch of the kernel plan mode ``mode`` names,
    through the host loop the node itself uses."""
    from pybitmessage_tpu.pow import pipeline
    plan = pipeline.BatchPlan(mode, pack, 1, list(range(n)))
    pipeline.solve_batch_pipelined(
        [(_IH, _ALWAYS)] * n, rows=8, impl=impl, plan=plan,
        interpret=impl == "pallas" and _interpret())


def _probe_pallas_slab():
    _probe_pipeline("slab")


def _probe_ici_slab():
    """One always-hit launch of a lone object's ONE program over every
    chip (``ops/sha512_ici.py``).  On one chip, or on the ``cpu``
    backend, the Mosaic program is never launched and there is none to
    probe."""
    import jax
    from pybitmessage_tpu.pow import pipeline
    devices = jax.devices()
    if not pipeline._one_program("pallas", devices):
        return
    pipeline.solve_batch_pipelined(
        [(_IH, _ALWAYS)], rows=8, impl="pallas", devices=devices,
        plan=pipeline.BatchPlan("slab", 1, 1, [0], one_program=True))


def _probe_batch_search():
    _probe_pipeline("batched")


def _probe_packed_search():
    _probe_pipeline("packed", n=4, pack=2)


def _probe_packed_search_xla():
    _probe_pipeline("packed", n=4, pack=2, impl="xla")


def _probe_sharded_search():
    from pybitmessage_tpu.parallel import pow_sharded
    mesh1, _ = _meshes()
    pow_sharded.sharded_solve(_IH, _ALWAYS, mesh1, lanes=128,
                              chunks_per_call=1)


def _probe_sharded_batch():
    from pybitmessage_tpu.parallel import pow_sharded
    _, mesh2 = _meshes()
    pow_sharded.sharded_solve_batch([(_IH, _ALWAYS)], mesh2, lanes=128,
                                    chunks_per_call=1)


def _probe_pod_slab():
    from pybitmessage_tpu.parallel import pow_pallas_sharded
    mesh1, _ = _meshes()
    pow_pallas_sharded.pallas_sharded_solve(
        _IH, _ALWAYS, mesh1, rows=8, chunks_per_call=1,
        interpret=_interpret())


def _probe_pod_batch():
    from pybitmessage_tpu.parallel import pow_pallas_sharded
    _, mesh2 = _meshes()
    pow_pallas_sharded.pallas_sharded_solve_batch(
        [(_IH, _ALWAYS)], mesh2, rows=8, chunks_per_call=1,
        interpret=_interpret())


def _secp_engine():
    from pybitmessage_tpu.crypto import tpu as ctpu
    ctpu.configure("on")
    return ctpu.get_tpu()


def _probe_secp_verify():
    # garbage operands compile and launch the same program a real
    # verify does; the result (False) is irrelevant to the probe
    _secp_engine().verify_prepared(
        1, b"\x01" * 32, b"\x01" * 32, b"\x02" * 64, b"\x03" * 32)


def _probe_secp_ecdh():
    _secp_engine().ecdh_batch(1, b"\x02" * 64, b"\x03" * 32)


_PROBES = {
    "pow_slab": _probe_pow_slab,
    "pow_verify": _probe_pow_verify,
    "pallas_slab": _probe_pallas_slab,
    "ici_slab": _probe_ici_slab,
    "batch_search": _probe_batch_search,
    "packed_search": _probe_packed_search,
    "packed_search_xla": _probe_packed_search_xla,
    "sharded_search": _probe_sharded_search,
    "sharded_batch": _probe_sharded_batch,
    "pod_slab": _probe_pod_slab,
    "pod_batch": _probe_pod_batch,
    "secp_verify": _probe_secp_verify,
    "secp_ecdh": _probe_secp_ecdh,
}


def _device_table():
    import jax
    out = []
    for d in jax.devices():
        out.append({
            "id": int(getattr(d, "id", -1)),
            "platform": str(getattr(d, "platform", "")),
            "kind": str(getattr(d, "device_kind", "")),
            "process": int(getattr(d, "process_index", 0)),
        })
    return out


def run_preflight(only=None, skip_probes: bool = False) -> dict:
    """Enumerate devices + probe every catalog program.

    Returns the JSON-able report; ``report["ok"]`` drives the exit
    status.
    """
    from pybitmessage_tpu.observability import env_fingerprint
    from pybitmessage_tpu.observability.devicetelemetry import \
        DEVICE_TELEMETRY

    from pybitmessage_tpu.core.jaxsetup import setup_jax
    setup_jax()
    report: dict = {"env": env_fingerprint()}
    try:
        import jax
        report["interpret"] = _interpret()
        report["devices"] = _device_table()
        report["topology"] = {
            "deviceCount": jax.device_count(),
            "localDeviceCount": jax.local_device_count(),
            "processCount": jax.process_count(),
        }
    except Exception as exc:  # pragma: no cover — backend init failure
        report["devices"] = []
        report["error"] = repr(exc)
        report["diagnosis"] = diagnose_text(repr(exc))
        report["ok"] = False
        return report

    # importing the probe targets registers the full program catalog;
    # any registered program WITHOUT a probe is itself a finding — the
    # doctor must grow in lockstep with the catalog (same contract the
    # bmlint devicelaunch checker enforces on the docs)
    probes = dict(_PROBES)
    if only:
        probes = {k: v for k, v in probes.items() if k in only}
    report["probes"] = {}
    ok = True
    if not skip_probes:
        for name, fn in sorted(probes.items()):
            entry: dict = {}
            t0 = time.monotonic()
            try:
                fn()
                entry["ok"] = True
            except Exception as exc:
                ok = False
                entry["ok"] = False
                entry["error"] = repr(exc)
                entry["diagnosis"] = diagnose_text(
                    "%s\n%s" % (type(exc).__name__, exc))
            entry["seconds"] = round(time.monotonic() - t0, 3)
            report["probes"][name] = entry
        unprobed = sorted(set(DEVICE_TELEMETRY.programs()) - set(_PROBES))
        if unprobed and not only:
            ok = False
            report["unprobed"] = unprobed
    report["ok"] = ok
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--diagnose", metavar="FILE",
                    help="classify a recorded failure tail "
                         "(JSON with a 'tail' field, or raw text) instead of "
                         "running live probes")
    ap.add_argument("--only", action="append", default=None,
                    help="probe only this program (repeatable)")
    ap.add_argument("--no-probes", action="store_true",
                    help="environment/device report only")
    args = ap.parse_args(argv)

    if args.diagnose:
        with open(args.diagnose, encoding="utf-8",
                  errors="replace") as fh:
            text = fh.read()
        try:
            doc = json.loads(text)
            tail = doc.get("tail", "") if isinstance(doc, dict) else text
        except ValueError:
            tail = text
        diag = diagnose_text(tail)
        print(json.dumps({"file": args.diagnose, "diagnosis": diag},
                         indent=2))
        return 1 if diag else 0

    report = run_preflight(only=args.only, skip_probes=args.no_probes)
    print(json.dumps(report, indent=2))
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
