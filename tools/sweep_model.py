#!/usr/bin/env python3
"""A discrete-event model of a burst's sweep through the batched solve.

What it models, as the code has it (``pow/pipeline.py``):
``solve_batch_pipelined`` laid out for a sweep of 64 sends (launch
groups of 64 slots, two a chip), ``_PipelineDriver.run`` (depth 2, the
device with the fewest launches in flight and then the least to do
asked first, a device's groups in turn, ``take_in`` before a group's
launch once its launches are all read, ``worth_speculating`` when every
unfinished group of a device has a launch unread, a nonce-range copy
of another chip's object when the device has nothing live: one object a
turn, first hit wins, the other slots cancelled), results visible
only when a launch is harvested, and the kernel's grid (an object
leaves at its hit, a solved or pad slot after one step, every further
grid step of theirs skipped).  Around it: one pipeline thread that
pays for every launch and harvest, one crypto thread that seals one
message at a time, and a closed loop of sweeps.

What it is for: sizing a change to this path before it is written
(ISSUE 40 sized the kernel's step with it, ISSUE 42 the nonce-range
copies for a chip that has run out: ``--copies none,one,half,all`` are
the four rules it weighed, and why the rule is one object a turn).  Two
hand reckonings of
``pod4_burst_64`` were off by 1.6 times and by a whole PR (PERF.md
section 6, PR 38 and PR 39).  ``tests/test_sweep_model.py`` holds it to
the ledger's two burst cells.  No cell runs it.

    python3 tools/sweep_model.py [--shape old,new,16k,grid] [--sweeps 40]
                                 [--copies none,one,half,all]
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import math
import random
from collections import deque

# --- parameters: the chip's and the host's numbers, with their origin ---

#: a grid step that is skipped, and what a slot of a launch costs
#: besides its steps, microseconds (my chip runs, PR 40: launches of 64
#: dead slots take 15.66 ms at 128 chunks x 4 tiles, 6.38 ms at 512 x 1
#: with one step a grid step, 4.29 ms with 64 and 2.55 ms at 1,024 steps
#: of 64 rows: 0.06 us a skipped grid step and 10 us a slot; ISSUE 40 had reckoned 0.2-0.4 us a skip)
SKIP_US, SLOT_US = 0.06, 10.0
#: slots of a launch group, groups a chip, launches in flight a chip
WIDTH, GROUPS_PER_CHIP, DEPTH = 64, 2, 2
#: `pow.fetch` of a finished launch, and the pipeline thread's cost of
#: a launch and of a harvest, ms (`pipeline_host_ms_per_launch.pod4`
#: 2.3-2.5 for the pair, PERF.md section 5; single_send's 2.5 ms a
#: launch under `pow.fetch`)
FETCH_MS, LAUNCH_MS, HARVEST_MS = 2.5, 1.2, 1.2
#: the crypto thread's cost of sealing one message once its ack is in
#: (`sender_host_ms_per_msg` 2.6), the gap between two ack requests at
#: a sweep's head, the way from a harvest back to the sender, and the
#: head of a sweep (status poll, outbox read: 1.3 s of 51 over 23
#: sweeps), ms
SEAL_MS, ACK_GAP_MS, BACK_MS, HEAD_MS = 2.6, 1.8, 0.6, 55.0
#: a solve that starts: plan, groups, window, ms
START_MS = 3.0
#: mean trials of an ack, and of a message of `body` bytes (defaults.py
#: ntpb/extra 1000, TTL 4 d: 6,273 trials a byte).  The overhead is
#: set so that a sweep needs the 1.76-1.83e9 trials the ledger's lines
#: give (kernel rate x busy share x useful share x a sweep's seconds,
#: PR 38 and 39): 64 acks 6.9e8, 64 messages of burst_64's mix 1.08e9
ACK_TRIALS, TRIALS_PER_BYTE, MSG_OVERHEAD = 1.08e7, 6273.0, 450
BODY_BYTES = ((0.60, 200, 800), (0.35, 800, 3000), (0.05, 3000, 8000))
SPECULATE_BELOW = 1.0 / 32
#: what a lane with nothing live and nothing from the queue takes from
#: the lane with the most unresolved objects, a turn: nothing (the tree
#: until PR 41), one object (``solve_batch_pipelined``'s rule since
#: PR 42), half of that lane's objects, all of them
COPY_RULES = ("none", "one", "half", "all")


@dataclasses.dataclass(frozen=True)
class Shape:
    """The static shape of ``pallas_batch_search``: trials of a step,
    steps an object a launch, steps a grid step loops over, and the
    rate of a launch whose 64 slots all search, MH/s (my chip runs,
    PR 40, the kernel alone)."""
    step: int
    chunks: int
    inner: int
    rate: float

    @property
    def slab(self) -> int:
        return self.step * self.chunks

    def slot_ms(self, steps: int) -> float:
        """Device time of one slot that ran ``steps`` steps."""
        outer = self.chunks // self.inner
        skipped = outer - math.ceil(steps / self.inner)
        return (steps * self.step / self.rate
                + skipped * SKIP_US + SLOT_US) / 1e3


SHAPES = {"old": Shape(65536, 128, 1, 290.55),      # PR 24-39
          "new": Shape(8192, 1024, 64, 288.47),     # PR 40
          "16k": Shape(16384, 512, 64, 289.66),     # PR 40's first step
          "grid": Shape(16384, 512, 1, 289.34)}     # that, without the loop


class _Slot:
    __slots__ = ("req", "need", "mean", "done", "copy")

    def __init__(self):
        self.req, self.need, self.mean, self.done = None, 0.0, 1.0, True
        self.copy = False


class _Group:
    def __init__(self, chip):
        self.chip, self.unread = chip, 0
        self.slots = [_Slot() for _ in range(WIDTH)]

    def live(self):
        return sum(not s.done for s in self.slots)


def simulate(chips: int, shape: Shape, sweeps: int = 40, seed: int = 1,
             launch_ms: float = LAUNCH_MS, harvest_ms: float = HARVEST_MS,
             sends: int = 64, copies: str = "one") -> dict:
    """``sweeps`` closed-loop sweeps of ``sends`` messages, acks on, on
    ``chips`` chips: what the benchmark's readers would read.
    ``copies`` is one of :data:`COPY_RULES`."""
    if copies not in COPY_RULES:
        raise ValueError("copies is one of %s" % (COPY_RULES,))
    rng = random.Random(seed)
    groups = [_Group(k % chips) for k in range(GROUPS_PER_CHIP * chips)]
    lanes = [[g for g in groups if g.chip == k] for k in range(chips)]
    turn = [0] * chips
    queues = [deque() for _ in range(chips)]    # (finish_ms, group, searched)
    free_at = [0.0] * chips                     # device FIFO
    busy = [0.0] * chips
    arrivals: list = []       # (t, kind, send, mean) not yet fed, by time
    waits, stat = [], dict(computed=0.0, needed=0.0, launches=0, live=0,
                           launch_ms=0.0, speculated=0, copies=0, won=0)
    #: id of an unresolved request -> the slots that search it
    held: dict = {}
    t = 0.0
    crypto_free = 0.0
    ended = 0

    def message_trials():
        x, body = rng.random(), 0
        for share, lo, hi in BODY_BYTES:
            body = rng.uniform(lo, hi)
            if x < share:
                break
            x -= share
        return TRIALS_PER_BYTE * (body + MSG_OVERHEAD + 1000)

    def begin_sweep(at):
        arrivals.extend((at + HEAD_MS + i * ACK_GAP_MS, "ack",
                         message_trials(), ACK_TRIALS)
                        for i in range(sends))
        arrivals.sort()

    def take_in(g):
        for s in g.slots:
            if not arrivals or arrivals[0][0] > t:
                break
            if s.done:
                search(s, arrivals.pop(0), copy=False)

    def search(s, req, copy):
        # the search is memoryless: a copy's disjoint range needs a
        # draw of its own
        s.req, s.mean, s.copy, s.done = req, req[3], copy, False
        s.need = rng.expovariate(1.0 / s.mean)
        held.setdefault(id(req), []).append(s)

    def take_copies(lane):
        """The copy rule of a lane with nothing live: the group that
        took them, or None."""
        g = next((g for g in lanes[lane] if not g.unread), None)
        of_lane = [{id(s.req): s.req for h in mine for s in h.slots
                    if not s.done} for mine in lanes]
        donor = max(of_lane, key=len)
        if g is None or not donor:
            return None
        # the object the fewest lanes search first, of those the hardest
        order = sorted(donor.values(), key=lambda r: (
            sum(id(r) in objs for objs in of_lane), -r[3]))
        take = {"one": 1, "half": -(-len(order) // 2),
                "all": len(order)}[copies]
        free = [s for s in g.slots if s.done]
        for s, req in zip(free, order[:take]):
            search(s, req, copy=True)
            stat["copies"] += 1
        return g

    def speculate(mine):
        for g in mine:
            if not g.live():
                continue
            p = 1.0
            for s in g.slots:
                if not s.done:
                    p *= -math.expm1(-shape.slab * g.unread / s.mean)
                    if p < SPECULATE_BELOW:
                        return g
        return None

    def next_launch(lane):
        nonlocal t
        mine, cand = lanes[lane], None
        for off in range(len(mine)):
            g = mine[(turn[lane] + off) % len(mine)]
            if g.unread:
                continue
            take_in(g)
            if g.live():
                cand = g
                turn[lane] = (turn[lane] + off + 1) % len(mine)
                break
        if cand is None and copies != "none" and chips > 1 \
                and not any(g.live() for g in mine):
            cand = take_copies(lane)
            if cand is None:
                return False
        elif cand is None:
            cand = speculate(mine)
            if cand is None:
                return False
            stat["speculated"] += 1
        t += launch_ms
        device_ms, searched = 0.0, []
        for s in cand.slots:
            if s.done:
                steps = 1
            elif s.need <= shape.slab:
                steps = max(1, math.ceil(s.need / shape.step))
                searched.append((s, s.req, s.need))
                # a launch dispatched ahead of this one searches on
                s.need = rng.expovariate(1.0 / s.mean)
            else:
                steps = shape.chunks
                searched.append((s, s.req, None))
                s.need -= shape.slab
            device_ms += shape.slot_ms(steps)
            stat["computed"] += steps * shape.step
        start = max(t, free_at[lane])
        free_at[lane] = start + device_ms
        busy[lane] += device_ms
        queues[lane].append((free_at[lane], cand, searched))
        cand.unread += 1
        stat["launches"] += 1
        stat["live"] += cand.live()
        stat["launch_ms"] += device_ms
        return True

    def harvest(lane):
        nonlocal t, crypto_free, ended
        finish, g, searched = queues[lane].popleft()
        t = max(t, finish + FETCH_MS) + harvest_ms
        g.unread -= 1
        for s, req, need in searched:
            if s.req is not req or s.done:
                continue        # an earlier launch answered for it
            if need is None:
                stat["needed"] += shape.slab
                continue
            stat["needed"] += need
            # first hit wins: every slot of the object is retired
            for other in held.pop(id(req)):
                other.done = True
            stat["won"] += s.copy
            waits.append(t + BACK_MS - req[0])
            if req[1] == "ack":
                crypto_free = max(crypto_free, t + BACK_MS) + SEAL_MS
                bisect.insort(arrivals, (crypto_free, "msg", 0.0, req[2]))
            else:
                ended += 1
                if ended % sends == 0:
                    begin_sweep(t + BACK_MS)

    begin_sweep(0.0)
    while ended < sweeps * sends:
        inflight = sum(map(len, queues))
        if not inflight and not any(g.live() for g in groups):
            # the solve has ended: the next request starts another
            t = max(t, arrivals[0][0]) + START_MS
        room = [k for k in range(chips) if len(queues[k]) < DEPTH]
        while room:
            lane = min(room, key=lambda k: (
                len(queues[k]), sum(g.live() for g in lanes[k])))
            if not next_launch(lane):
                room.remove(lane)
            elif len(queues[lane]) >= DEPTH:
                room.remove(lane)
        waiting = [k for k in range(chips) if queues[k]]
        if waiting:
            harvest(min(waiting, key=lambda k: queues[k][0][0]))
    seconds = t / 1e3
    return {
        "sent_msgs_per_s": ended / seconds,
        "pow_wait_ms": sum(waits) / len(waits),
        "useful_trial_share": 100.0 * stat["needed"] / stat["computed"],
        "device_idle_share": 100.0 * (1 - sum(busy) / (chips * t)),
        "kernel_mhash_per_s": stat["computed"] / sum(busy) / 1e3,
        "launches_per_sweep": stat["launches"] / sweeps,
        "live_slots_per_launch": stat["live"] / stat["launches"],
        "launch_ms": stat["launch_ms"] / stat["launches"],
        "speculated_launch_share": 100.0 * stat["speculated"]
        / stat["launches"],
        "copies_per_sweep": stat["copies"] / sweeps,
        "copies_won_share": 100.0 * stat["won"] / max(stat["copies"], 1),
    }


#: the two burst cells: chips, and the one-chip machine's slower host
#: (`pipeline_host_ms_per_launch.queue` 2.77-3.05 in `burst_send_64`)
CELLS = {"pod4_burst_64": dict(chips=4),
         "burst_send_64": dict(chips=1, launch_ms=1.45, harvest_ms=1.45)}


def read(cell: str, shape: str = "old", sweeps: int = 40,
         seeds=(1, 2, 3), copies: str = "one") -> dict:
    """The model's reading of ``cell``, the mean over ``seeds``."""
    runs = [simulate(shape=SHAPES[shape], sweeps=sweeps, seed=s,
                     copies=copies, **CELLS[cell]) for s in seeds]
    return {k: sum(r[k] for r in runs) / len(runs) for k in runs[0]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shape", default="new")
    ap.add_argument("--sweeps", type=int, default=40)
    ap.add_argument("--copies", default="none,one",
                    help="of %s" % ",".join(COPY_RULES))
    args = ap.parse_args(argv)
    for cell in CELLS:
        for shape in args.shape.split(","):
            for copies in args.copies.split(","):
                row = read(cell, shape, args.sweeps, copies=copies)
                print(json.dumps({
                    "cell": cell, "shape": shape, "copies": copies,
                    **{k: round(v, 2) for k, v in row.items()}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
