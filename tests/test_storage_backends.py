"""Inventory backend parity + slab-store specifics (ISSUE 11).

One parametrized suite drives ``Inventory`` (sqlite),
``FilesystemInventory`` and the new ``SlabStore`` (disk and memory
modes) through the same add/contains/getitem/flush/clean/TTL-grace/
digest contract so the ``inventorystorage`` backends cannot drift.
Slab-only sections cover sealing, kill-and-restart recovery from the
sidecar index (no sealed-slab replay), torn-tail tolerance, the
pinned hot set, whole-slab TTL drops, and 100%-seeded
``storage.slab_io`` chaos losing zero objects.  Satellite
regressions: the cached SQL row count (no ``SELECT count(*)`` per
``__len__``/``clean``) and the v12 inventory indexes.
"""

import hashlib
import time

import pytest

from pybitmessage_tpu.models.constants import EXPIRES_GRACE
from pybitmessage_tpu.resilience.chaos import CHAOS
from pybitmessage_tpu.storage import Database, Inventory, SlabStore
from pybitmessage_tpu.storage.fs_inventory import FilesystemInventory
from pybitmessage_tpu.storage.inventory import InventoryItem
from pybitmessage_tpu.sync.digest import InventoryDigest

BACKENDS = ("sqlite", "filesystem", "slab-disk", "slab-mem")


def _h(i: int) -> bytes:
    return hashlib.sha512(b"backend obj %d" % i).digest()[:32]


@pytest.fixture(params=BACKENDS)
def backend(request, tmp_path):
    dbs = []

    def make():
        if request.param == "sqlite":
            db = Database()
            dbs.append(db)
            return Inventory(db)
        if request.param == "filesystem":
            return FilesystemInventory(tmp_path / "fsinv")
        if request.param == "slab-disk":
            return SlabStore(tmp_path / "slabs", slab_max_bytes=1 << 13,
                             bucket_seconds=600)
        return SlabStore(None, slab_max_bytes=1 << 13, bucket_seconds=600)

    make.name = request.param
    yield make
    for db in dbs:
        db.close()


def test_add_contains_getitem_roundtrip(backend):
    inv = backend()
    now = int(time.time())
    for i in range(50):
        tag = (b"T%02d" % i).ljust(32, b"t") if i % 3 == 0 else b""
        inv.add(_h(i), 2 if i % 2 else 3, 1 + i % 2,
                b"payload %d " % i * 7, now + 600 + i, tag)
    assert len(inv) == 50
    assert _h(7) in inv and _h(999) not in inv
    item = inv[_h(6)]
    assert item.payload == b"payload 6 " * 7
    assert item.type == 3 and item.stream == 1
    assert item.tag == b"T06".ljust(32, b"t")
    with pytest.raises(KeyError):
        inv[_h(999)]
    # duplicate add must not double-count
    inv.add(_h(7), 2, 2, b"other", now + 600, b"")
    assert len(inv) == 50


def test_flush_then_reread(backend):
    inv = backend()
    now = int(time.time())
    for i in range(20):
        inv.add(_h(i), 2, 1, b"p%d" % i, now + 1000, b"")
    inv.flush()
    assert len(inv) == 20
    assert inv[_h(13)].payload == b"p13"
    assert sorted(inv.hashes()) == sorted(_h(i) for i in range(20))


def test_unexpired_hashes_by_stream(backend):
    inv = backend()
    now = int(time.time())
    inv.add(_h(1), 2, 1, b"a", now + 600, b"")
    inv.add(_h(2), 2, 2, b"b", now + 600, b"")
    inv.add(_h(3), 2, 1, b"c", now - 30, b"")   # expired, inside grace
    inv.flush()
    assert sorted(inv.unexpired_hashes_by_stream(1)) == [_h(1)]
    assert sorted(inv.unexpired_hashes_by_stream(2)) == [_h(2)]


def test_by_type_and_tag(backend):
    inv = backend()
    now = int(time.time())
    tag = b"G".ljust(32, b"g")
    inv.add(_h(1), 1, 1, b"pk1", now + 600, tag)
    inv.add(_h(2), 1, 1, b"pk2", now + 600, b"X".ljust(32, b"x"))
    inv.add(_h(3), 2, 1, b"msg", now + 600, b"")
    inv.flush()
    assert sorted(i.payload for i in inv.by_type_and_tag(1)) == \
        [b"pk1", b"pk2"]
    assert [i.payload for i in inv.by_type_and_tag(1, tag)] == [b"pk1"]
    assert [i.payload for i in inv.by_type_and_tag(2)] == [b"msg"]


def test_clean_ttl_grace_semantics(backend):
    """Purge respects the 3 h grace: freshly expired objects stay
    readable (acks may still arrive), long-expired ones go."""
    inv = backend()
    now = int(time.time())
    inv.add(_h(1), 2, 1, b"live", now + 3600, b"")
    inv.add(_h(2), 2, 1, b"grace", now - 60, b"")
    inv.add(_h(3), 2, 1, b"dead", now - EXPIRES_GRACE - 7200, b"")
    inv.flush()
    inv.clean()
    assert _h(1) in inv
    assert _h(2) in inv          # inside the grace window
    assert _h(3) not in inv
    assert len(inv) == 2


@pytest.mark.parametrize("mode", ["incremental", "rebuild"])
def test_digest_incremental_matches_rebuild(backend, mode):
    """The digest a backend maintains incrementally must equal a
    from-scratch rebuild over its unexpired view (sqlite + slab; the
    filesystem backend has no attach_digest — skipped)."""
    inv = backend()
    if not hasattr(inv, "attach_digest"):
        pytest.skip("backend keeps no digest")
    now = int(time.time())
    if mode == "incremental":
        digest = InventoryDigest()
        inv.attach_digest(digest)
        for i in range(80):
            inv.add(_h(i), 2, 1 + i % 2, b"d%d" % i,
                    now + (600 if i % 5 else -30), b"")
        inv.clean()               # unfolds the expired fifth
    else:
        for i in range(80):
            inv.add(_h(i), 2, 1 + i % 2, b"d%d" % i,
                    now + (600 if i % 5 else -30), b"")
        inv.flush()
        digest = InventoryDigest()
        inv.attach_digest(digest)
    expect = InventoryDigest()
    expect.rebuild([(_h(i), 1 + i % 2, now + 600)
                    for i in range(80) if i % 5])
    for stream in (1, 2):
        assert digest.summaries(stream) == expect.summaries(stream)


# -- slab store specifics ----------------------------------------------------


def test_slab_seal_and_restart_recovers_from_idx(tmp_path):
    """Kill-and-restart: sealed slabs are adopted from their fsynced
    sidecar `.idx` files — payload slabs are NOT replayed; only the
    one unsealed slab per shard is."""
    now = int(time.time())
    s = SlabStore(tmp_path / "s", slab_max_bytes=1 << 12)
    for i in range(300):
        s.add(_h(i), 2, 1, b"payload %d " % i * 10, now + 900, b"")
    s.flush()
    sealed = len(s._sealed)
    assert sealed >= 3
    # kill (no orderly shutdown beyond the durable flush) + restart
    s2 = SlabStore(tmp_path / "s", slab_max_bytes=1 << 12)
    assert s2.recovery["sealed_indexed"] == sealed
    assert s2.recovery["replayed"] <= len(s._open)
    assert len(s2) == 300
    assert s2[_h(123)].payload == b"payload 123 " * 10


def test_slab_orphaned_open_slabs_recover_and_purge(tmp_path):
    """A crash between seal and finalize leaves multiple `.open` files
    in one shard.  Restart must track every one of them — the
    non-newest re-enter the sealing queue so flush() finalizes them
    and clean() can still drop their objects (regression: they were
    replayed into the index but tracked nowhere, leaking files and
    index entries past TTL forever)."""
    now = int(time.time())
    clock = [now]
    s = SlabStore(tmp_path / "s", slab_max_bytes=1 << 12,
                  bucket_seconds=600, clock=lambda: clock[0])
    expiry = now + 300
    for i in range(120):
        s.add(_h(i), 2, 1, b"payload %d " % i * 10, expiry, b"")
    s.flush()
    shard = next(d for d in (tmp_path / "s").iterdir() if d.is_dir())
    # simulate the crash window: demote sealed slabs back to .open
    # and delete their sidecars (seal happened, finalize never did)
    for idx in shard.glob("*.idx"):
        idx.unlink()
    for slab in shard.glob("*.slab"):
        slab.rename(slab.with_suffix(".open"))
    opens = list(shard.glob("*.open"))
    assert len(opens) >= 3
    s2 = SlabStore(tmp_path / "s", slab_max_bytes=1 << 12,
                   bucket_seconds=600, clock=lambda: clock[0])
    assert len(s2) == 120          # every record recovered
    assert s2[_h(7)].payload == b"payload 7 " * 10
    # flush finalizes the recovered sealing slabs: sidecars reappear
    s2.flush()
    assert len(list(shard.glob("*.idx"))) >= len(opens) - 1
    # and TTL purge reaches ALL of them once the bucket passes grace
    clock[0] = now + 600 + EXPIRES_GRACE + 3600
    s2.clean()
    assert len(s2) == 0
    assert _h(7) not in s2
    assert not shard.exists()


def test_slab_torn_tail_tolerated(tmp_path):
    now = int(time.time())
    s = SlabStore(tmp_path / "s", slab_max_bytes=1 << 20)
    for i in range(10):
        s.add(_h(i), 2, 1, b"x%d" % i, now + 900, b"")
    s.flush()
    open_files = list((tmp_path / "s").rglob("*.open"))
    assert len(open_files) == 1
    with open(open_files[0], "ab") as fh:
        fh.write(b"\x00" * 17)    # torn partial record from a crash
    s2 = SlabStore(tmp_path / "s", slab_max_bytes=1 << 20)
    assert len(s2) == 10
    assert s2.recovery["torn_bytes"] == 17
    assert s2[_h(3)].payload == b"x3"
    # the torn bytes were truncated away: appends stay consistent
    s2.add(_h(77), 2, 1, b"after", now + 900, b"")
    s2.flush()
    s3 = SlabStore(tmp_path / "s", slab_max_bytes=1 << 20)
    assert s3[_h(77)].payload == b"after"


def test_slab_chaos_100pct_loses_nothing(tmp_path):
    """Seeded ``storage.slab_io`` at 100%: every drain/seal attempt
    fails, yet every object stays readable (write-behind keeps the RAM
    tail) and all of them land on disk once the fault clears."""
    now = int(time.time())
    s = SlabStore(tmp_path / "s", slab_max_bytes=1 << 12)
    CHAOS.arm("storage.slab_io", probability=1.0)
    try:
        for i in range(200):
            s.add(_h(i), 2, 1, b"chaos payload %d " % i * 8,
                  now + 900, b"")
        assert len(s) == 200
        assert s[_h(150)].payload == b"chaos payload 150 " * 8
        assert not list((tmp_path / "s").rglob("*.slab"))
    finally:
        CHAOS.disarm("storage.slab_io")
    s.flush()
    s2 = SlabStore(tmp_path / "s", slab_max_bytes=1 << 12)
    assert len(s2) == 200
    assert all(_h(i) in s2 for i in range(200))


def test_slab_hot_set_serves_without_disk(tmp_path):
    from pybitmessage_tpu.observability import REGISTRY
    now = int(time.time())
    s = SlabStore(tmp_path / "s", hot_bytes=1 << 20)
    s.add(_h(1), 2, 1, b"hot payload", now + 900, b"")
    s.flush()
    before = REGISTRY.sample("slab_store_reads_total",
                             {"source": "disk"}) or 0
    hot_before = REGISTRY.sample("slab_store_reads_total",
                                 {"source": "hot"}) or 0
    assert s[_h(1)].payload == b"hot payload"
    assert REGISTRY.sample("slab_store_reads_total",
                           {"source": "hot"}) == hot_before + 1
    assert (REGISTRY.sample("slab_store_reads_total",
                            {"source": "disk"}) or 0) == before
    # eviction: a tiny budget pushes old pins out; reads fall to disk
    tiny = SlabStore(tmp_path / "t", hot_bytes=64)
    for i in range(10):
        tiny.add(_h(100 + i), 2, 1, b"E" * 40, now + 900, b"")
    tiny.flush()
    assert tiny._hot_total <= 64
    assert tiny[_h(100)].payload == b"E" * 40   # from disk, still there


def test_slab_whole_bucket_drop(tmp_path):
    """TTL compaction drops whole shards (files unlinked, index
    forgotten) without touching live shards."""
    now = int(time.time())
    s = SlabStore(tmp_path / "s", bucket_seconds=60)
    dead_expiry = now - EXPIRES_GRACE - 7200
    for i in range(20):
        s.add(_h(i), 2, 1, b"dead", dead_expiry, b"")
    for i in range(20, 40):
        s.add(_h(i), 2, 1, b"live", now + 600, b"")
    s.flush()
    dead_shard = (tmp_path / "s") / str(dead_expiry // 60)
    assert dead_shard.exists()
    s.clean()
    assert len(s) == 20
    assert _h(5) not in s and _h(25) in s
    assert not dead_shard.exists()
    from pybitmessage_tpu.observability import REGISTRY
    assert (REGISTRY.sample("slab_store_dropped_slabs_total") or 0) >= 1


def test_slab_memory_mode_seal_and_read():
    now = int(time.time())
    s = SlabStore(None, slab_max_bytes=1 << 12, hot_bytes=0)
    for i in range(100):
        s.add(_h(i), 2, 1, b"mem payload %d " % i * 10, now + 900, b"")
    assert len(s._sealed) >= 1      # memory-mode seals roll the slab
    assert s[_h(2)].payload == b"mem payload 2 " * 10
    assert len(s) == 100


def test_node_slab_backend_wiring(tmp_path):
    from pybitmessage_tpu.core.node import Node
    node = Node(str(tmp_path / "node"), listen=False, test_mode=True,
                inventory_backend="slab", tls_enabled=False,
                federation_enabled=False)
    assert isinstance(node.inventory, SlabStore)
    assert node.sync_digest is not None     # attach_digest seeded it
    node.db.close()
    node.pow_journal.close()


# -- satellite regressions ---------------------------------------------------


def test_inventory_len_is_cached_not_rescanned():
    """``__len__`` / ``clean`` must not run ``SELECT count(*)`` table
    scans per call — the row count is maintained incrementally."""
    db = Database()
    inv = Inventory(db)
    now = int(time.time())
    for i in range(30):
        inv.add(_h(i), 2, 1, b"c%d" % i, now + (600 if i % 3 else -30))
    inv.flush()
    scans = []
    orig = db.query

    def spy(sql, params=()):
        if sql.strip().lower().startswith("select count(*) from inventory") \
                and "where" not in sql.lower():
            scans.append(sql)
        return orig(sql, params)

    db.query = spy
    assert len(inv) == 30
    inv.clean()                    # purges nothing (all inside grace)
    assert len(inv) == 30
    # age one third past the purge cutoff and clean again
    db.execute("UPDATE inventory SET expirestime=? WHERE expirestime<?",
               (now - EXPIRES_GRACE - 7200, now))
    inv.clean()
    assert len(inv) == 20
    assert scans == []
    db.close()


def test_inventory_flush_keeps_count_exact_on_replace():
    db = Database()
    inv = Inventory(db)
    now = int(time.time())
    inv.add(_h(1), 2, 1, b"v1", now + 600)
    inv.flush()
    # re-adding a hash already in SQL REPLACEs the row: count stays 1
    inv._pending[_h(1)] = InventoryItem(2, 1, b"v2", now + 600, b"")
    inv.flush()
    assert len(inv) == 1
    assert db.query("SELECT count(*) FROM inventory")[0][0] == 1
    db.close()


def test_inventory_hot_scans_use_indexes():
    """v12 migration: the catch-up scan and the TTL purge must hit
    their covering indexes, not full-scan 10M rows."""
    db = Database()
    now = int(time.time())
    plan = " ".join(str(r) for r in db.query(
        "EXPLAIN QUERY PLAN SELECT hash FROM inventory"
        " WHERE streamnumber=? AND expirestime>?", (1, now)))
    assert "idx_inventory_stream_expires" in plan, plan
    plan = " ".join(str(r) for r in db.query(
        "EXPLAIN QUERY PLAN DELETE FROM inventory WHERE expirestime<?",
        (now,)))
    assert "idx_inventory_expires" in plan, plan
    db.close()


def test_migration_applies_to_existing_v11_db(tmp_path):
    import sqlite3
    path = str(tmp_path / "old.dat")
    db = Database(path)
    db.close()
    # wind the stamp back to the frozen baseline and drop the indexes,
    # simulating a database created before this release
    conn = sqlite3.connect(path)
    conn.execute("DROP INDEX IF EXISTS idx_inventory_stream_expires")
    conn.execute("DROP INDEX IF EXISTS idx_inventory_expires")
    conn.execute("DROP INDEX IF EXISTS idx_sent_ackdata")
    conn.execute("PRAGMA user_version = 11")
    conn.commit()
    conn.close()
    db = Database(path)
    names = {r[0] for r in db.query(
        "SELECT name FROM sqlite_master WHERE type='index'")}
    assert {"idx_inventory_stream_expires", "idx_inventory_expires",
            "idx_sent_ackdata"} <= names
    assert db.get_setting("version") == "13"
    db.close()


@pytest.mark.parametrize("statement", [
    "SELECT status FROM sent WHERE ackdata=?",
    "UPDATE sent SET status='msgsent' WHERE ackdata=?",
])
def test_a_sent_row_is_found_by_its_ackdata_without_a_scan(statement):
    """v13 migration: a status change and ``message_status`` search the
    index; each was a scan of the whole outbox, a thousand rows long in
    ``queue_1k``."""
    db = Database()
    plan = " ".join(str(r) for r in db.query(
        "EXPLAIN QUERY PLAN " + statement, (b"ack",)))
    assert "idx_sent_ackdata" in plan, plan
    db.close()


# -- the 10M-object headline variant (ISSUE 11 tentpole c) -------------------


@pytest.mark.slow
def test_ingest_storm_10m_slab_variant(tmp_path):
    """Full-scale slab acceptance, excluded from the 870 s tier-1 gate
    (run explicitly: ``pytest -m slow -k 10m``).  Preloads a
    multi-million-object slab inventory (10M by default;
    BMTPU_SLAB_TEST_OBJECTS scales it down for smaller hosts), then
    asserts sustained ingest, flat p99 across TTL compaction cycles
    and zero loss — the bench assertions, wired as a test."""
    import importlib.util
    import os
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "bench", Path(__file__).resolve().parent.parent / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    objects = int(os.environ.get("BMTPU_SLAB_TEST_OBJECTS", "10000000"))
    out = bench._bench_slab_store(objects=objects, smoke=False,
                                  root=tmp_path / "slabs")
    assert out["zero_objects_lost"]
    assert out["preloaded_objects"] == objects
