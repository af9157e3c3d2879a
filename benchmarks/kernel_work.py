"""What one launch of a SHA-512 search kernel computed, from its own
output and its static shape.

Every kernel leaves its grid early: a grid step whose object (or
group) has already hit does no hashing.  So the trials a launch
computed are the steps it really ran times the trials of one step, and
the steps are read from the launch's output — for every launch,
abandoned speculative ones too.  ``kernels.json`` names, for each
program, the entry point the probe wraps, which function here counts
its steps, and how the device trace names it.
"""

from __future__ import annotations

LANE_COLS = 128


def step_trials(rows: int, unroll: int) -> int:
    """Trials one grid step of one (rows, 128) tile computes."""
    return rows * LANE_COLS * unroll


def slab_steps(found, chunks: int) -> int:
    """``pallas_search``: ``found`` is one flag per grid step; steps
    after the first hit are skipped."""
    for i, hit in enumerate(found):
        if hit:
            return i + 1
    return chunks


def batch_steps(out_rows, chunks: int) -> int:
    """``pallas_batch_search``: one row ``[hit_step + 1, hi, lo]`` per
    object (0 = no hit); each object stops at its own hit."""
    return sum(int(r[0]) if int(r[0]) else chunks for r in out_rows)


def packed_steps(out_rows, chunks: int, pack: int) -> int:
    """``pallas_packed_search``: ``pack`` objects share one tile and a
    group runs until its last member has hit."""
    steps = 0
    for g in range(0, len(out_rows), pack):
        member = [int(r[0]) if int(r[0]) else chunks
                  for r in out_rows[g:g + pack]]
        steps += max(member)
    return steps


def launch_trials(counter: str, output, static: dict) -> int:
    """Trials computed by one launch of a program whose step counter
    is ``counter`` (a name from ``kernels.json``)."""
    per_step = step_trials(static["rows"], static["unroll"])
    chunks = static["chunks"]
    if counter == "slab_steps":
        return slab_steps(output, chunks) * per_step
    if counter == "batch_steps":
        return batch_steps(output, chunks) * per_step
    if counter == "packed_steps":
        return packed_steps(output, chunks, static["pack"]) * per_step
    raise KeyError("no step counter named %r" % counter)
