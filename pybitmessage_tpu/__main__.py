"""Daemon entry point: ``python -m pybitmessage_tpu``.

Reference: src/bitmessagemain.py Main.start() — single process, clean
shutdown on SIGINT/SIGTERM, optional test mode (-t) and trusted peer;
configuration layered as defaults <- settings.dat <- CLI flags
(reference bmconfigparser + helper_startup.loadConfig).
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import signal
import sys
from pathlib import Path


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pybitmessage_tpu",
        description="TPU-native Bitmessage node")
    p.add_argument("-d", "--data-dir", default=None,
                   help="data directory (default: in-memory; "
                        "--appdata uses ~/.config/pybitmessage-tpu "
                        "or $BITMESSAGE_HOME)")
    p.add_argument("--appdata", action="store_true",
                   help="persist to the standard appdata directory")
    p.add_argument("--daemon", action="store_true",
                   help="detach from the terminal (double fork)")
    p.add_argument("-p", "--port", type=int, default=None,
                   help="P2P listen port (default from settings: 8444)")
    p.add_argument("--no-listen", action="store_true",
                   help="outbound connections only")
    p.add_argument("--api-port", type=int, default=None)
    p.add_argument("--no-api", action="store_true")
    p.add_argument("--api-user", default="")
    p.add_argument("--api-password", default="")
    p.add_argument("-t", "--test-mode", action="store_true",
                   help="divide PoW difficulty by 100 (reference -t)")
    p.add_argument("--trusted-peer", default=None, metavar="HOST:PORT",
                   help="connect only to this peer")
    p.add_argument("--no-dandelion", action="store_true")
    p.add_argument("--no-udp", action="store_true",
                   help="disable UDP LAN discovery")
    p.add_argument("--populate-test-data", action="store_true",
                   help="seed a deterministic identity + sample inbox "
                        "message (reference testmode_init role)")
    p.add_argument("--seed-defaults", action="store_true",
                   help="seed the bootstrap nodes into knownnodes")
    p.add_argument("--set", action="append", default=[],
                   metavar="KEY=VALUE", dest="set_options",
                   help="persist a settings option and continue")
    p.add_argument("-v", "--verbose", action="store_true")
    return p


def load_settings(args):
    """defaults <- settings.dat <- --set <- per-flag CLI overrides."""
    from .core.config import Settings

    path = Path(args.data_dir) / "settings.dat" if args.data_dir else None
    settings = Settings(path)
    for kv in args.set_options:
        key, _, value = kv.partition("=")
        settings.set(key.strip(), value.strip())
    if args.set_options:
        settings.save()
    if args.port is not None:
        settings.set_temp("port", args.port)
    if args.api_port is not None:
        settings.set_temp("apiport", args.api_port)
    if args.api_user:
        settings.set_temp("apiusername", args.api_user)
    if args.api_password:
        settings.set_temp("apipassword", args.api_password)
    if args.api_user and args.api_password and not args.no_api:
        settings.set_temp("apienabled", True)
    if args.no_dandelion:
        settings.set_temp("dandelion", 0)
    if args.no_udp:
        settings.set_temp("udp", False)
    return settings


async def run(args) -> int:
    from .api import APIServer
    from .core import Node
    from .storage.knownnodes import Peer

    settings = load_settings(args)
    # fault injection is opt-in per node (chaos setting / BMTPU_CHAOS
    # env) — production nodes run with every site disarmed
    if settings.get("chaos"):
        from .resilience import CHAOS
        CHAOS.configure(settings.get("chaos"),
                        seed=settings.getint("chaosseed"))
    # explicit PoW slab overrides reach the solver ladder's XLA tier
    # (the Pallas tier has its own measured sweet spot)
    solver = None
    if settings.is_set("powlanes") or settings.is_set("powchunks"):
        from .pow import PowDispatcher
        solver = PowDispatcher(
            tpu_kwargs={
                "lanes": settings.getint("powlanes"),
                "chunks_per_call": settings.getint("powchunks")},
            stall_timeout=settings.getfloat("powstalltimeout"))
    # composable roles (docs/roles.md): role/rolestreams/edgeprocs/
    # roleipclisten/roleipcconnect select the deployment shape; the
    # default ("all") is the fused single-process node
    from .roles import parse_role_streams
    role = settings.get("role")
    node = Node(args.data_dir,
                solver=solver,
                port=settings.getint("port"),
                listen=not args.no_listen,
                role=role,
                role_streams=parse_role_streams(
                    settings.get("rolestreams")) or None,
                role_ipc_listen=settings.get("roleipclisten") or None,
                role_ipc_connect=settings.get("roleipcconnect") or None,
                test_mode=args.test_mode,
                dandelion_enabled=settings.getint("dandelion") > 0,
                tls_enabled=settings.getbool("tls"),
                udp_enabled=settings.getbool("udp") and not args.no_listen,
                inventory_backend=settings.get("inventorystorage"),
                slab_max_bytes=settings.getint("slabmaxbytes"),
                slab_hot_bytes=settings.getint("slabhotbytes"),
                slab_bucket_seconds=settings.getint("slabbucketseconds"),
                pow_window=settings.getfloat("powbatchwindow"),
                sync_enabled=settings.getbool("syncenabled"),
                wiretrace_enabled=settings.getbool("wiretrace"),
                federation_enabled=settings.get("federation") != "off",
                farm_listen=settings.get("powfarmlisten") or None,
                farm_connect=settings.get("powfarmconnect") or None,
                farm_tenant=settings.get("powfarmtenant"),
                farm_secret=settings.get("powfarmsecret"),
                client_listen=settings.get("clientplanelisten") or None,
                client_connect=settings.get("clientconnect") or None,
                client_buckets=settings.getint("clientbuckets"))
    node.settings = settings
    # edgeprocs > 1: this listener shares its port via SO_REUSEPORT so
    # sibling edge processes can bind alongside (docs/roles.md)
    if settings.getint("edgeprocs") > 1:
        node.pool.reuse_port = True
    node.dandelion.stem_probability = settings.getint("dandelion")
    node.processor.list_mode = settings.get("blackwhitelist")
    # observability knobs (docs/observability.md)
    from .observability import FLIGHT_RECORDER
    FLIGHT_RECORDER.resize(settings.getint("flightrecsize"))
    node.health.sample_interval = settings.getfloat("healthinterval")
    node.health.probe.interval = settings.getfloat("looplaginterval")
    # continuous profiling plane: always-on CPU/cost attribution at a
    # low default rate — costStatus / profileDump / GET /debug/profile
    # serve it live, federation carries the cpu_samples_total shares
    # fleet-wide, and the flight recorder's stall dumps gain the
    # stacks of the stall (docs/observability.md)
    if settings.getbool("profiling"):
        from .observability import PROFILER
        PROFILER.hz = settings.getfloat("profilehz")
        PROFILER.start()
    # distributed observability plane (docs/observability.md): hashed
    # peer-bucket label count, snapshot push cadence, optional parent
    # aggregator this node federates its own registry up to
    from .observability import set_peer_buckets
    set_peer_buckets(settings.getint("peerlabelbuckets"))
    if node.federation_publisher is not None:
        node.federation_publisher.interval = \
            settings.getfloat("federationinterval")
        if settings.get("federationpush"):
            from .observability import http_transport
            host, _, port = settings.get("federationpush").rpartition(":")
            parent = http_transport(
                host or "127.0.0.1", int(port),
                username=settings.get("apiusername"),
                password=settings.get("apipassword"))
            # tee: the push still lands in the LOCAL aggregator (this
            # node's own /metrics/federated must keep including the
            # local node) while the PARENT's ack drives the
            # delta/resync bookkeeping.  Both see the same seq stream
            # from seq 1, so their stored state cannot diverge.
            local_ingest = (node.federation.ingest
                            if node.federation is not None else None)

            async def tee(push, _parent=parent, _local=local_ingest):
                if _local is not None:
                    _local(push)
                return await _parent(push)

            node.federation_publisher.transport = tee
            node.federation_publisher.count_bytes = True  # real wire
    # ingest fast path knobs (docs/ingest.md) — applied before start()
    # spawns the pipeline workers
    node.processor.concurrency = settings.getint("ingestworkers")
    if settings.getint("cryptoworkers"):
        node.processor.crypto.size = settings.getint("cryptoworkers")
    # batched native crypto knobs (docs/ingest.md) — applied before
    # start() spawns the engine's drain task.  cryptonative=false is
    # the process-wide switch (set_native_enabled), not just an engine
    # flag: the per-call signing/ecies ladder must honor it too, even
    # with the batch engine off
    from .crypto.native import set_native_enabled
    set_native_enabled(settings.getbool("cryptonative"))
    # accelerator rung (docs/crypto.md): cryptotpu configures the
    # process-wide probe mode (auto = TPU backend only); the engine
    # flag and the launch-worthiness floor ride alongside
    from .crypto import tpu as crypto_tpu
    crypto_tpu.configure(settings.get("cryptotpu"))
    if not settings.getbool("cryptobatch"):
        node.processor.crypto.batch = None
    elif node.processor.crypto.batch is not None:
        engine = node.processor.crypto.batch
        engine.use_native = settings.getbool("cryptonative")
        engine.use_tpu = crypto_tpu.mode() != "off"
        engine.tpu_batch_min = settings.getint("cryptotpubatchmin")
        engine.drain_max = settings.getint("cryptodrainmax")
        engine.window = settings.getfloat("cryptobatchwindow")
        engine.num_threads = settings.getint("cryptonativethreads")
    # trial-decrypt negative screen (ISSUE 17, docs/crypto.md): the
    # processor attaches one by default; the knob detaches it from
    # both the pool probe and the engine's no-match recorder
    if not settings.getbool("cryptoscreen"):
        node.processor.crypto.screen = None
        if node.processor.crypto.batch is not None:
            node.processor.crypto.batch.screen = None
    queue = node.ctx.object_queue
    if hasattr(queue, "high"):
        queue.high = settings.getint("ingestqueuehigh")
        queue.low = max(1, queue.high // 4)
    # kB/s global throttles (reference maxdownloadrate/maxuploadrate)
    node.ctx.download_bucket.rate = settings.getint("maxdownloadrate") * 1024
    node.ctx.upload_bucket.rate = settings.getint("maxuploadrate") * 1024
    node.pool.max_outbound = settings.getint("maxoutboundconnections")
    node.pool.max_total = settings.getint("maxtotalconnections")
    # set-reconciliation sync knobs (docs/sync.md)
    if node.reconciler is not None:
        node.reconciler.interval = settings.getfloat("syncinterval")
        fanout = settings.getint("syncfanout")
        node.reconciler.fanout = None if fanout < 0 else fanout
        node.reconciler.breaker_threshold = \
            settings.getint("breakerfailures")
        node.reconciler.breaker_cooldown = \
            settings.getfloat("breakercooldown")
    # PoW solver farm knobs (docs/pow_farm.md)
    if node.farm_server is not None:
        from .powfarm import TenantConfig
        srv = node.farm_server
        srv.auth_required = settings.getbool("powfarmauth")
        srv.batch_max = settings.getint("powfarmbatch")
        srv.window = settings.getfloat("powfarmwindow")
        srv.max_attempts = settings.getint("powmaxretries")
        srv.scheduler.max_wait = settings.getfloat("powfarmmaxwait")
        srv.scheduler.max_tenants = settings.getint("powfarmmaxtenants")
        srv.scheduler.default_config = TenantConfig(
            quota=settings.getint("powfarmquota"),
            rate=settings.getfloat("powfarmrate"),
            burst=settings.getfloat("powfarmburst"))
        # the operator's tenant table (name:secret[:weight] list) —
        # with powfarmauth=true this is the whole admission roster
        from .core.config import parse_tenant_table
        for name, secret, weight in parse_tenant_table(
                settings.get("powfarmtenants")):
            srv.register_tenant(name, TenantConfig(
                weight=weight,
                quota=settings.getint("powfarmquota"),
                rate=settings.getfloat("powfarmrate"),
                burst=settings.getfloat("powfarmburst"),
                secret=secret.encode("utf-8")))
    if node.farm_client is not None:
        node.farm_client.deadline = settings.getfloat("powfarmdeadline")
        node.farm_client.client.timeout = \
            settings.getfloat("powfarmdeadline")
        node.farm_client.bulk_threshold = \
            settings.getint("powfarmbulkthreshold")
    # resilience knobs (docs/resilience.md)
    node.pool.dial_timeout = settings.getfloat("connecttimeout")
    node.pool.handshake_timeout = settings.getfloat("handshaketimeout")
    node.pool.dial_breaker_threshold = settings.getint("breakerfailures")
    node.pool.dial_breaker_cooldown = settings.getfloat("breakercooldown")
    if hasattr(node.solver, "stall_timeout"):
        node.solver.stall_timeout = settings.getfloat("powstalltimeout")
    if node.pow_service is not None:
        node.pow_service.max_attempts = settings.getint("powmaxretries")
    if hasattr(node.solver, "breakers"):
        cpp = node.solver.breakers.get("cpp")
        if cpp is not None:
            cpp.threshold = settings.getint("breakerfailures")
            cpp.cooldown = settings.getfloat("breakercooldown")
    node.sender.max_acceptable_ntpb = settings.getint(
        "maxacceptablenoncetrialsperbyte")
    node.sender.max_acceptable_extra = settings.getint(
        "maxacceptablepayloadlengthextrabytes")
    if settings.get("sockstype") not in ("none", "SOCKS5", "SOCKS4a"):
        # a plugin name (e.g. "stem"): let it launch/adopt a proxy and
        # rewrite the socks settings (reference start_proxyconfig).
        # FAIL CLOSED: the user asked for proxied traffic — starting
        # up unproxied after a plugin failure would deanonymize them.
        from .core.plugins import start_proxyconfig
        if not start_proxyconfig(settings):
            logging.error(
                "proxy configuration %r failed; refusing to start "
                "unproxied", settings.get("sockstype"))
            node.db.close()
            return 1
    if settings.get("sockstype") in ("SOCKS5", "SOCKS4a"):
        node.ctx.proxy = {
            "type": settings.get("sockstype"),
            "host": settings.get("sockshostname"),
            "port": settings.getint("socksport"),
            "username": settings.get("socksusername"),
            "password": settings.get("sockspassword"),
        }
    # AFTER proxyconfig: a plugin may have just created the hidden
    # service and set onionhostname.  Publish our endpoint as an
    # ONIONPEER object at worker startup (reference sendOnionPeerObj);
    # lowercase because the wire codec round-trips onion hosts in
    # lowercase and the self-recognition check compares exactly.
    if settings.get("onionhostname"):
        node.sender.onion_peer = (settings.get("onionhostname").lower(),
                                  settings.getint("onionport"))
    if args.trusted_peer:
        host, _, port = args.trusted_peer.rpartition(":")
        node.pool.trusted_peer = Peer(host, int(port))
    if args.seed_defaults:
        node.knownnodes.seed_defaults()

    await node.start()

    if args.populate_test_data:
        from .core.testdata import populate
        populate(node)

    upnp_client = None
    if settings.getbool("upnp") and not args.no_listen:
        from .network.upnp import UPnPClient
        upnp_client = UPnPClient()
        try:
            await upnp_client.discover(timeout=5)
            await upnp_client.add_port_mapping(node.pool.listen_port)
        except Exception as exc:
            logging.warning("UPnP port mapping unavailable: %r", exc)
            upnp_client = None

    if settings.getbool("notifysound"):
        # new-message sound through the notification.sound plugin group
        # (reference sound_* plugins driven from the UISignal stream)
        from .core.plugins import get_plugin
        sound = get_plugin("notification.sound")
        if sound is not None:
            soundfile = settings.get("notifysoundfile", "")
            node.ui.subscribe(
                lambda cmd, data: sound(soundfile)
                if cmd == "displayNewInboxMessage" else None)

    notifier = None
    if settings.get("apinotifypath"):
        from .core.notify import ApiNotifier
        notifier = ApiNotifier(node, settings.get("apinotifypath"))
        notifier.start()

    api = None
    # The API is powerful (reads inboxes, sends messages); match the
    # reference's default-off-with-mandatory-auth posture: refuse to
    # serve without credentials except in explicit test mode
    # (reference bmconfigparser 'apienabled' + apiusername/apipassword).
    want_api = not args.no_api and (settings.getbool("apienabled")
                                    or args.test_mode)
    has_creds = settings.get("apiusername") and settings.get("apipassword")
    if want_api and not has_creds and not args.test_mode:
        logging.warning(
            "API disabled: set apiusername/apipassword (or --api-user/"
            "--api-password, or run with -t for test mode)")
        want_api = False
    if want_api:
        api = APIServer(node, port=settings.getint("apiport"),
                        username=settings.get("apiusername"),
                        password=settings.get("apipassword"))
        await api.start()
        logging.info("API listening on 127.0.0.1:%d", api.listen_port)
        if notifier is not None:
            notifier.notify("apiEnabled")

    smtp_gw = None
    if settings.getbool("smtpdenabled"):
        from .gateways import SMTPGateway
        smtp_gw = SMTPGateway(
            node, port=settings.getint("smtpdport"),
            username=settings.get("smtpdusername", ""),
            password=settings.get("smtpdpassword", ""))
        await smtp_gw.start()
        logging.info("SMTP gateway on 127.0.0.1:%d", smtp_gw.listen_port)

    deliverer = None
    if settings.get("smtpdeliver"):
        from .gateways import SMTPDeliverer
        deliverer = SMTPDeliverer(node, settings.get("smtpdeliver"))
        deliverer.start()

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:  # pragma: no cover (non-unix)
            pass
    await stop.wait()
    logging.info("shutting down...")
    if notifier is not None:
        notifier.stop()
    if deliverer is not None:
        deliverer.stop()
    if smtp_gw is not None:
        await smtp_gw.stop()
    if api is not None:
        await api.stop()
    if upnp_client is not None:
        try:
            await upnp_client.delete_port_mapping()
        except Exception:
            logging.debug("UPnP unmap failed", exc_info=True)
    await node.stop()
    settings.save()
    return 0


def _setup_logging(args) -> None:
    """Reference debug.py: a logging.dat fileConfig override wins;
    otherwise console + rotating debug.log (2 MiB x 1) in the data
    directory."""
    level = logging.DEBUG if args.verbose else logging.INFO
    if args.data_dir:
        logging_dat = Path(args.data_dir) / "logging.dat"
        if logging_dat.exists():
            # aliased import: a bare `import logging.config` would bind
            # the name `logging` function-locally and shadow the module
            import logging.config as logging_config
            try:
                logging_config.fileConfig(
                    logging_dat, disable_existing_loggers=False)
                return
            except Exception:
                pass  # fall through to the default config
    handlers: list = [logging.StreamHandler()]
    if args.data_dir:
        from logging.handlers import RotatingFileHandler
        Path(args.data_dir).mkdir(parents=True, exist_ok=True)
        handlers.append(RotatingFileHandler(
            Path(args.data_dir) / "debug.log",
            maxBytes=2 * 1024 * 1024, backupCount=1))
    logging.basicConfig(
        level=level, handlers=handlers,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    _setup_logging(args)
    logging.getLogger("jax").setLevel(logging.INFO)
    from .core.appenv import (SingleInstance, SingleInstanceError,
                              appdata_dir, daemonize)
    if args.appdata and not args.data_dir:
        args.data_dir = str(appdata_dir())
    if args.daemon:  # pragma: no cover - forks away from test runners
        daemonize()
    # after the fork: setup_jax initialises the backend
    from .core.jaxsetup import setup_jax
    setup_jax()
    lock = None
    if args.data_dir:
        lock = SingleInstance(args.data_dir)
        try:
            lock.acquire()
        except SingleInstanceError as exc:
            logging.error("%s", exc)
            return 1
    try:
        return asyncio.run(run(args))
    except KeyboardInterrupt:  # pragma: no cover
        return 0
    except Exception:
        # fatal: dump the flight recorder — the ring holds the
        # breaker/chaos/slab/sync event trail of the seconds before
        # death, which is exactly what the post-mortem needs
        from .observability import FLIGHT_RECORDER
        FLIGHT_RECORDER.dump("fatal")
        raise
    finally:
        if lock is not None:
            lock.release()


if __name__ == "__main__":
    sys.exit(main())
