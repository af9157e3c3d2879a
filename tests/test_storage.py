"""Storage layer tests: db schema, inventory cache semantics, knownnodes."""

import threading
import time

import pytest

from pybitmessage_tpu.storage import Database, Inventory, KnownNodes, Peer
from pybitmessage_tpu.storage.inventory import InventoryItem
from pybitmessage_tpu.storage.messages import (
    ACKRECEIVED, MSGQUEUED, MSGSENT, MessageStore,
)


@pytest.fixture
def db():
    d = Database(":memory:")
    yield d
    d.close()


def test_schema_and_settings(db):
    assert db.get_setting("version") == "13"
    db.set_setting("k", "v")
    assert db.get_setting("k") == "v"
    assert db.get_setting("missing", "dflt") == "dflt"


def test_inventory_pending_and_flush(db):
    inv = Inventory(db)
    h = b"\x01" * 32
    inv.add(h, 2, 1, b"payload", int(time.time()) + 1000, b"tag")
    assert h in inv
    assert inv[h].payload == b"payload"
    # not yet in SQL
    assert db.query("SELECT COUNT(*) FROM inventory")[0][0] == 0
    inv.flush()
    assert db.query("SELECT COUNT(*) FROM inventory")[0][0] == 1
    assert h in inv
    assert inv[h].payload == b"payload"
    with pytest.raises(KeyError):
        inv[b"\x02" * 32]


def test_inventory_clean_expires(db):
    inv = Inventory(db)
    now = int(time.time())
    inv.add(b"a" * 32, 2, 1, b"old", now - 4 * 3600, b"")
    inv.add(b"b" * 32, 2, 1, b"new", now + 1000, b"")
    inv.flush()
    inv.clean()
    assert b"a" * 32 not in inv
    assert b"b" * 32 in inv


def test_inventory_by_type_and_stream(db):
    inv = Inventory(db)
    now = int(time.time())
    inv.add(b"a" * 32, 1, 1, b"pk", now + 100, b"T" * 32)
    inv.add(b"b" * 32, 2, 1, b"m1", now + 100, b"")
    inv.add(b"c" * 32, 2, 2, b"m2", now + 100, b"")
    inv.flush()
    inv.add(b"d" * 32, 2, 1, b"m3", now + 100, b"")  # still pending
    assert {i.payload for i in inv.by_type_and_tag(2)} == {b"m1", b"m2", b"m3"}
    assert [i.payload for i in inv.by_type_and_tag(1, b"T" * 32)] == [b"pk"]
    assert set(inv.unexpired_hashes_by_stream(1)) == {
        b"a" * 32, b"b" * 32, b"d" * 32}


def test_inventory_threaded_inserts(db):
    inv = Inventory(db)
    now = int(time.time())

    def put(k):
        for i in range(50):
            inv.add(bytes([k, i]) + b"\x00" * 30, 2, 1, b"x", now + 99, b"")

    threads = [threading.Thread(target=put, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    inv.flush()
    assert db.query("SELECT COUNT(*) FROM inventory")[0][0] == 200


def test_knownnodes_lifecycle(tmp_path):
    path = tmp_path / "knownnodes.json"
    kn = KnownNodes(path)
    p = Peer("10.0.0.1", 8444)
    assert kn.add(p)
    kn.increase_rating(p)
    assert kn.get(p)["rating"] == pytest.approx(0.1)
    for _ in range(20):
        kn.increase_rating(p)
    assert kn.get(p)["rating"] == 1.0  # clamped
    kn.save()

    kn2 = KnownNodes(path)
    assert kn2.get(p)["rating"] == 1.0

    # forget policy: stale vs probation
    kn2.add(Peer("10.0.0.2", 8444), lastseen=int(time.time()) - 29 * 86400)
    bad = Peer("10.0.0.3", 8444)
    kn2.add(bad, lastseen=int(time.time()) - 4 * 3600)
    for _ in range(6):
        kn2.decrease_rating(bad)
    assert kn2.cleanup() == 2
    assert kn2.count() == 1


def test_knownnodes_choose_prefers_rated():
    kn = KnownNodes()
    good = Peer("1.1.1.1", 8444)
    kn.add(good)
    for _ in range(10):
        kn.increase_rating(good)  # rating 1.0 -> p=+inf acceptance
    for i in range(5):
        kn.add(Peer(f"2.2.2.{i}", 8444))
    import random
    counts = sum(kn.choose(rng=random.Random(s)) == good for s in range(50))
    assert counts > 25  # strongly preferred


def test_message_store_state_machine(db):
    ms = MessageStore(db)
    ack = b"A" * 32
    ms.queue_sent(msgid=b"m1", toaddress="BM-to", toripe=b"r" * 20,
                  fromaddress="BM-from", subject="s", message="b",
                  ackdata=ack, ttl=3600)
    assert ms.sent_by_status(MSGQUEUED)[0].ackdata == ack
    ms.update_sent_status(ack, MSGSENT, sleeptill=int(time.time()) - 1)
    assert ms.due_for_resend()[0].ackdata == ack
    ms.bump_retry(ack, 7200, int(time.time()) + 7200)
    assert ms.sent_by_ackdata(ack).retrynumber == 1
    assert ms.sent_by_ackdata(ack).ttl == 7200
    ms.update_sent_status(ack, ACKRECEIVED)
    assert ms.due_for_resend() == []


def test_message_store_inbox_dedup(db):
    ms = MessageStore(db)
    assert ms.deliver_inbox(msgid=b"i1", toaddress="BM-a", fromaddress="BM-b",
                            subject="s", message="m", sighash=b"H" * 32)
    assert not ms.deliver_inbox(msgid=b"i2", toaddress="BM-a",
                                fromaddress="BM-b", subject="s", message="m",
                                sighash=b"H" * 32)
    assert len(ms.inbox()) == 1
    ms.trash_inbox(b"i1")
    assert ms.inbox() == []
    assert len(ms.inbox(include_trash=True)) == 1


def test_message_store_search(db):
    """LIKE search over inbox/sent (reference helper_search.search_sql)."""
    ms = MessageStore(db)
    ms.deliver_inbox(msgid=b"s1", toaddress="BM-a", fromaddress="BM-b",
                     subject="Alpha Report", message="the quick fox")
    ms.deliver_inbox(msgid=b"s2", toaddress="BM-a", fromaddress="BM-c",
                     subject="beta", message="lazy dog fox")
    ms.mark_read(b"s1")
    ms.queue_sent(msgid=b"s3", toaddress="BM-d", toripe=b"r" * 20,
                  fromaddress="BM-a", subject="outgoing alpha",
                  message="sent body", ackdata=b"A" * 32, ttl=3600)
    db.execute("UPDATE sent SET folder='sent'")

    # case-insensitive, any-field by default
    assert {m.msgid for m in ms.search("inbox", "ALPHA")} == {b"s1"}
    assert {m.msgid for m in ms.search("inbox", "fox")} == {b"s1", b"s2"}
    # field restriction
    assert ms.search("inbox", "fox", where="subject") == []
    assert {m.msgid for m in ms.search("inbox", "BM-c",
                                       where="fromaddress")} == {b"s2"}
    # 'new' = unread inbox only
    assert {m.msgid for m in ms.search("new", "fox")} == {b"s2"}
    # sent folder
    assert [m.msgid for m in ms.search("sent", "alpha")] == [b"s3"]
    # a bogus where-field falls back to all-fields, never raw SQL
    assert {m.msgid for m in ms.search("inbox", "fox",
                                       where="1=1; DROP TABLE inbox")} \
        == {b"s1", b"s2"}


def test_message_store_interrupted_pow_reset(db):
    ms = MessageStore(db)
    ms.queue_sent(msgid=b"m", toaddress="t", toripe=b"", fromaddress="f",
                  subject="s", message="m", ackdata=b"ack", ttl=60,
                  status="doingmsgpow")
    ms.reset_interrupted_pow()
    assert ms.sent_by_status(MSGQUEUED)[0].ackdata == b"ack"


def test_pubkeys(db):
    ms = MessageStore(db)
    ms.store_pubkey("BM-x", 4, b"\x01\x02", used_personally=True)
    assert ms.get_pubkey("BM-x") == b"\x01\x02"
    assert ms.get_pubkey("BM-y") is None
    assert ms.purge_stale_pubkeys() == 0  # fresh + personal


def test_schema_migration_hook(tmp_path):
    """PRAGMA user_version + ordered MIGRATIONS (VERDICT r3 #9; the
    reference evolves through class_sqlThread.py:94-460)."""
    from pybitmessage_tpu.storage import db as dbmod

    path = str(tmp_path / "m.dat")
    d = Database(path)
    assert d.query("PRAGMA user_version")[0][0] == dbmod.SCHEMA_VERSION
    assert d.get_setting("version") == str(dbmod.SCHEMA_VERSION)
    d.close()

    # simulate an old database: wind the stamp back, register a future
    # migration, reopen — the migration must apply exactly once
    import sqlite3
    raw = sqlite3.connect(path)
    raw.execute("PRAGMA user_version = %d" % dbmod.SCHEMA_VERSION)
    raw.execute("UPDATE settings SET value=? WHERE key='version'",
                (str(dbmod.SCHEMA_VERSION),))
    raw.commit()
    raw.close()

    future = dbmod.SCHEMA_VERSION + 1
    old_schema_version = dbmod.SCHEMA_VERSION
    dbmod.MIGRATIONS[future] = (
        "ALTER TABLE inbox ADD COLUMN migration_probe int DEFAULT 7",)
    dbmod.SCHEMA_VERSION = future
    try:
        d = Database(path)
        assert d.query("PRAGMA user_version")[0][0] == future
        # the new column exists and is usable
        d.execute("INSERT INTO inbox(msgid, migration_probe)"
                  " VALUES (?, 42)", (b"m1",))
        assert d.query("SELECT migration_probe FROM inbox")[0][0] == 42
        d.close()
        # reopening again must NOT re-run the ALTER (would raise
        # 'duplicate column name')
        d = Database(path)
        assert d.query("PRAGMA user_version")[0][0] == future
        d.close()
    finally:
        dbmod.MIGRATIONS.pop(future)
        dbmod.SCHEMA_VERSION = old_schema_version


def test_pre_user_version_db_adopts_settings_stamp(tmp_path):
    """Databases from rounds before the hook (user_version=0 but a
    settings 'version' row) adopt the stamp without re-running the
    baseline."""
    from pybitmessage_tpu.storage import db as dbmod

    path = str(tmp_path / "legacy.dat")
    d = Database(path)
    d.close()
    import sqlite3
    raw = sqlite3.connect(path)
    raw.execute("PRAGMA user_version = 0")      # pre-hook state
    raw.commit()
    raw.close()
    d = Database(path)
    assert d.query("PRAGMA user_version")[0][0] == dbmod.SCHEMA_VERSION
    d.close()


def test_fresh_db_runs_migration_ladder_too(tmp_path):
    """A MIGRATIONS entry is the single source of truth: a BRAND-NEW
    database must end up with the migrated schema, not just old DBs
    (fresh installs and upgrades cannot diverge)."""
    from pybitmessage_tpu.storage import db as dbmod

    future = dbmod.SCHEMA_VERSION + 1
    old_version = dbmod.SCHEMA_VERSION
    dbmod.MIGRATIONS[future] = (
        "ALTER TABLE inbox ADD COLUMN fresh_probe int DEFAULT 3",)
    dbmod.SCHEMA_VERSION = future
    try:
        d = Database(str(tmp_path / "fresh.dat"))
        assert d.query("PRAGMA user_version")[0][0] == future
        d.execute("INSERT INTO inbox(msgid, fresh_probe) VALUES (?, 9)",
                  (b"f1",))
        assert d.query("SELECT fresh_probe FROM inbox")[0][0] == 9
        d.close()
    finally:
        dbmod.MIGRATIONS.pop(future)
        dbmod.SCHEMA_VERSION = old_version


def test_version_stamp_never_downgrades(tmp_path):
    """Opening a database touched by a NEWER build must not wind its
    user_version back — the newer build would re-run its migrations."""
    from pybitmessage_tpu.storage import db as dbmod

    path = str(tmp_path / "newer.dat")
    Database(path).close()
    import sqlite3
    raw = sqlite3.connect(path)
    raw.execute("PRAGMA user_version = %d" % (dbmod.SCHEMA_VERSION + 5))
    raw.commit()
    raw.close()
    d = Database(path)
    assert d.query("PRAGMA user_version")[0][0] == dbmod.SCHEMA_VERSION + 5
    assert d.get_setting("version") == str(dbmod.SCHEMA_VERSION + 5)
    d.close()
