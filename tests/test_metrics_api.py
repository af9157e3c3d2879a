"""End-to-end /metrics smoke test (tier-1 safe, no crypto deps).

Scrapes the Prometheus endpoint through a real APIServer socket, runs
a scripted PoW solve through the coalescing PowService, and asserts
the acceptance-criteria series are present and moving.  The server is
given a bare namespace instead of a full Node so the test stays
importable without the optional `cryptography` package.
"""

import asyncio
import base64
import hashlib
from types import SimpleNamespace

import pytest

from pybitmessage_tpu.api import APIServer
from pybitmessage_tpu.observability import REGISTRY
from pybitmessage_tpu.pow import PowDispatcher
from pybitmessage_tpu.pow.service import PowService

IH = hashlib.sha512(b"metrics smoke").digest()
EASY = 2 ** 59

#: acceptance criteria: these must all appear in the exposition
REQUIRED_METRICS = ("pow_solve_seconds", "pow_fallback_total",
                    "pow_batch_size", "network_connections",
                    "inventory_items")


async def _get(port: int, path: str, auth: str | None = None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    headers = "GET %s HTTP/1.1\r\n" % path
    if auth:
        headers += "Authorization: Basic %s\r\n" % auth
    writer.write((headers + "\r\n").encode())
    await writer.drain()
    response = await reader.read()
    writer.close()
    head, _, body = response.partition(b"\r\n\r\n")
    return int(head.split()[1]), body.decode("utf-8")


def _series_count(text: str, prefix: str) -> float:
    """Sum the sample values of every series starting with prefix."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(prefix) and not line.startswith("#"):
            total += float(line.rsplit(" ", 1)[1])
    return total


def test_metrics_endpoint_scrape_and_solve():
    # registering modules + series the same way a running node does on
    # pool startup / inventory construction
    from pybitmessage_tpu.network.pool import CONNECTIONS
    from pybitmessage_tpu.storage.db import Database
    from pybitmessage_tpu.storage.inventory import Inventory
    CONNECTIONS.labels(direction="outbound").set(0)
    Inventory(Database())
    assert REGISTRY.sample("inventory_items") == 0

    async def body():
        server = APIServer(SimpleNamespace(), port=0,
                           username="user", password="pass")
        await server.start()
        try:
            auth = base64.b64encode(b"user:pass").decode()
            status, _ = await _get(server.listen_port, "/metrics")
            assert status == 401  # basic auth applies to the scrape
            status, _ = await _get(server.listen_port, "/nope", auth)
            assert status == 404

            status, text = await _get(server.listen_port, "/metrics",
                                      auth)
            assert status == 200
            for name in REQUIRED_METRICS:
                assert "# TYPE %s " % name in text, name
            # well-formed exposition: every sample line parses
            for line in text.splitlines():
                if line and not line.startswith("#"):
                    float(line.rsplit(" ", 1)[1])
            solves0 = _series_count(text, "pow_solve_seconds_count")
            batches0 = _series_count(text, "pow_batch_size_count")

            # scripted PoW solve through the coalescing service
            service = PowService(PowDispatcher(use_tpu=False),
                                 window=0.01)
            service.start()
            try:
                nonce, trials = await service.solve(IH, EASY)
                assert trials > 0
            finally:
                await service.stop()

            # a solve is a stream: the future resolves from the
            # solving thread, which books the solve as it returns
            for _ in range(100):
                status, text = await _get(server.listen_port, "/metrics",
                                          auth)
                assert status == 200
                if _series_count(
                        text, "pow_solve_seconds_count") > solves0:
                    break
                await asyncio.sleep(0.05)
            assert _series_count(
                text, "pow_solve_seconds_count") == solves0 + 1
            assert _series_count(
                text, "pow_batch_size_count") == batches0 + 1
            assert _series_count(text, "pow_trials_total") > 0
            assert _series_count(text, "pow_solved_total") >= 1
        finally:
            await server.stop()

    asyncio.run(body())


def test_metrics_api_command_matches_endpoint():
    """The `metrics` RPC command returns the same exposition format."""
    from pybitmessage_tpu.api.commands import CommandHandler

    async def body():
        handler = CommandHandler(SimpleNamespace())
        text = await handler.dispatch("metrics", [])
        assert "# TYPE pow_solve_seconds histogram" in text
        assert text.endswith("\n")

    asyncio.run(body())


def test_dump_flight_recorder_api_command():
    """`dumpFlightRecorder` returns the ring (newest last) and counts
    an api-triggered dump; the optional kind argument filters."""
    import json

    from pybitmessage_tpu.api.commands import CommandHandler
    from pybitmessage_tpu.observability import FLIGHT_RECORDER, REGISTRY

    async def body():
        handler = CommandHandler(SimpleNamespace())
        FLIGHT_RECORDER.record("breaker", name="api.test", to="open")
        FLIGHT_RECORDER.record("chaos", site="api.test_site")
        before = REGISTRY.sample("flightrec_dumps_total",
                                 {"trigger": "api"})
        out = json.loads(await handler.dispatch("dumpFlightRecorder", []))
        kinds = [e["kind"] for e in out["events"]]
        assert "breaker" in kinds and "chaos" in kinds
        assert REGISTRY.sample("flightrec_dumps_total",
                               {"trigger": "api"}) == before + 1
        out = json.loads(await handler.dispatch(
            "dumpFlightRecorder", ["chaos"]))
        assert out["events"]
        assert all(e["kind"] == "chaos" for e in out["events"])

    asyncio.run(body())


def test_object_timeline_api_command():
    """`objectTimeline` returns the lifecycle stages of one hash and
    refuses malformed hex lengths."""
    import json

    from pybitmessage_tpu.api.commands import APIError, CommandHandler
    from pybitmessage_tpu.observability import LIFECYCLE

    async def body():
        handler = CommandHandler(SimpleNamespace())
        h = b"\xA5" * 32
        LIFECYCLE.record(h, "received")
        LIFECYCLE.record(h, "stored")
        try:
            out = json.loads(await handler.dispatch(
                "objectTimeline", [h.hex()]))
            assert [e["stage"] for e in out["timeline"]] == [
                "received", "stored"]
        finally:
            LIFECYCLE.discard(h)
        with pytest.raises(APIError):
            await handler.dispatch("objectTimeline", ["ab"])

    asyncio.run(body())


def test_federated_status_api_command():
    """`federatedStatus` serves the aggregator's fleet view (and a
    clean disabled answer without one)."""
    import json

    from pybitmessage_tpu.api.commands import CommandHandler
    from pybitmessage_tpu.observability import (Aggregator,
                                                FederationPublisher,
                                                Registry)

    async def body():
        handler = CommandHandler(SimpleNamespace())
        assert json.loads(await handler.dispatch(
            "federatedStatus", []))["enabled"] is False

        agg = Aggregator()
        reg = Registry()
        reg.counter("farm_jobs_total", "j").inc(3)
        FederationPublisher(
            "child-1", reg, transport=agg.ingest,
            health=lambda: {"pow": {"status": "ok"}}).push_once()
        handler = CommandHandler(SimpleNamespace(federation=agg))
        out = json.loads(await handler.dispatch("federatedStatus", []))
        assert out["enabled"] is True
        assert out["fleet"]["nodes"] == 1
        assert out["nodes"]["child-1"]["verdict"] == "ok"

    asyncio.run(body())


def test_federation_push_endpoint_and_federated_metrics():
    """A child pushes its registry over the REAL HTTP path
    (http_transport -> POST /federation/push) and the merged fleet
    view appears on GET /metrics/federated; version mismatches are
    refused; federation-off serves 404."""
    import json

    from pybitmessage_tpu.observability import (Aggregator,
                                                FederationPublisher,
                                                Registry, http_transport)

    async def body():
        agg = Aggregator()
        server = APIServer(SimpleNamespace(federation=agg), port=0,
                           username="user", password="pass")
        await server.start()
        try:
            auth = base64.b64encode(b"user:pass").decode()
            # the child end: real publisher over the real transport
            reg = Registry()
            reg.counter("farm_jobs_total", "j", ("tenant",)).labels(
                tenant="acme").inc(5)
            pub = FederationPublisher(
                "child-9", reg,
                transport=http_transport("127.0.0.1",
                                         server.listen_port,
                                         username="user",
                                         password="pass"))
            ack = await pub.push_once_async()
            assert ack and ack["ok"]

            status, text = await _get(server.listen_port,
                                      "/metrics/federated", auth)
            assert status == 200
            assert 'farm_jobs_total{tenant="acme"} 5' in text
            # auth applies to the fleet view too
            status, _ = await _get(server.listen_port,
                                   "/metrics/federated")
            assert status == 401

            # version mismatch: refused with the expected version
            bad = json.dumps({"v": 999, "node": "x", "seq": 1,
                              "full": True, "metrics": {}})
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.listen_port)
            writer.write((
                "POST /federation/push HTTP/1.1\r\n"
                "Authorization: Basic %s\r\n"
                "Content-Type: application/json\r\n"
                "Content-Length: %d\r\n\r\n" % (auth, len(bad))
            ).encode() + bad.encode())
            await writer.drain()
            response = await reader.read()
            writer.close()
            body_json = json.loads(
                response.partition(b"\r\n\r\n")[2])
            assert body_json["ok"] is False
            assert body_json["reason"] == "version"
        finally:
            await server.stop()

        # federation off: both surfaces answer 404, not a crash
        server = APIServer(SimpleNamespace(), port=0)
        await server.start()
        try:
            status, _ = await _get(server.listen_port,
                                   "/metrics/federated")
            assert status == 404
        finally:
            await server.stop()

    asyncio.run(body())
