"""Batched multi-object PoW: dispatcher + service + production sender.

VERDICT r1 #4: the pod-wide (objects x nonce-lanes) grid must be the
*production* path — PowDispatcher uses the mesh when >1 device is
present, and a sweep of queued sends coalesces into ONE batched launch.
Runs on the 8-device virtual CPU mesh from conftest.
"""

import asyncio
import hashlib

import pytest

from pybitmessage_tpu.core import Node
from pybitmessage_tpu.parallel import make_mesh, sharded_solve_batch
from pybitmessage_tpu.pow import PowDispatcher, PowService
from pybitmessage_tpu.storage.messages import ACKRECEIVED


def _host_trial(nonce: int, initial_hash: bytes) -> int:
    d = hashlib.sha512(hashlib.sha512(
        nonce.to_bytes(8, "big") + initial_hash).digest()).digest()
    return int.from_bytes(d[:8], "big")


def test_sharded_solve_batch_on_2d_mesh():
    mesh = make_mesh(8, obj_axis="obj", obj_size=2)
    items = [(hashlib.sha512(b"batch obj %d" % i).digest(), 2**57)
             for i in range(3)]  # 3 objects pad to 4 (obj axis = 2)
    results = sharded_solve_batch(items, mesh, lanes=256, chunks_per_call=8)
    assert len(results) == 3
    for (ih, target), (nonce, trials) in zip(items, results):
        assert _host_trial(nonce, ih) <= target
        assert trials > 0


def test_dispatcher_solve_batch_uses_mesh():
    d = PowDispatcher(use_native=False,
                      tpu_kwargs={"lanes": 256, "chunks_per_call": 8})
    items = [(hashlib.sha512(b"disp %d" % i).digest(), 2**57)
             for i in range(4)]
    results = d.solve_batch(items)
    assert d.last_backend == "tpu-batch"
    for (ih, target), (nonce, _) in zip(items, results):
        assert _host_trial(nonce, ih) <= target


def test_dispatcher_single_solve_sharded():
    d = PowDispatcher(use_native=False,
                      tpu_kwargs={"lanes": 256, "chunks_per_call": 8})
    ih = hashlib.sha512(b"single sharded").digest()
    nonce, trials = d.solve(ih, 2**57)
    assert d.last_backend == "tpu-sharded"
    assert _host_trial(nonce, ih) <= 2**57


@pytest.mark.asyncio
async def test_pow_service_coalesces_concurrent_solves():
    d = PowDispatcher(use_native=False,
                      tpu_kwargs={"lanes": 256, "chunks_per_call": 8})
    svc = PowService(d, window=0.05)
    svc.start()
    try:
        items = [(hashlib.sha512(b"svc %d" % i).digest(), 2**57)
                 for i in range(3)]
        results = await asyncio.gather(
            *(svc.solve(ih, t) for ih, t in items))
        for (ih, target), (nonce, _) in zip(items, results):
            assert _host_trial(nonce, ih) <= target
        # the futures resolve from the solve's thread, the batch is
        # counted at the solve's own end, a turn of the loop or two later
        for _ in range(200):
            if svc.batches:
                break
            await asyncio.sleep(0.01)
        assert svc.batches == 1, "concurrent solves should form one batch"
        assert svc.solved == 3
        assert d.last_backend == "tpu-batch"
    finally:
        await svc.stop()


@pytest.mark.asyncio
async def test_two_queued_messages_one_batched_launch():
    """e2e: two queued sends -> one (objects x nonce-lanes) device launch."""
    node = Node(listen=False, test_mode=True,
                solver=PowDispatcher(
                    use_native=False,
                    tpu_kwargs={"lanes": 2048, "chunks_per_call": 8}))
    assert node.pow_service is not None
    await node.start()
    try:
        me = node.create_identity("me")
        ack1 = await node.send_message(me.address, me.address,
                                       "first", "body one", ttl=300)
        ack2 = await node.send_message(me.address, me.address,
                                       "second", "body two", ttl=300)

        async def both_acked():
            deadline = asyncio.get_running_loop().time() + 120
            while asyncio.get_running_loop().time() < deadline:
                if node.message_status(ack1) == ACKRECEIVED and \
                        node.message_status(ack2) == ACKRECEIVED:
                    return True
                await asyncio.sleep(0.1)
            return False

        assert await both_acked(), "self-sends never completed"
        assert len(node.store.inbox()) == 2
        assert node.pow_service.solved == 2
        assert node.pow_service.batches == 1, \
            "two queued messages should solve in ONE batched call"
        assert node.solver.last_backend == "tpu-batch"
    finally:
        await node.stop()
