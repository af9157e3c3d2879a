"""``tools/sweep_model.py`` against the two burst cells it was made for.

The numbers are the driver's, on the shape those runs launched (128
chunks of four tiles): ``pod4_burst_64`` from PR 39's line (parent
side: 6f56bca), ``burst_send_64`` from PR 38's (change side, the same
commit).  A model that stops reading them has lost a mechanism, or the
code it mirrors has gained one.  ``NEWER`` holds it to the shape of
PR 40 (1,024 steps of one tile) without the copies of PR 42 and with
them.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from tools import sweep_model  # noqa: E402

LEDGER = {
    # cell: sent_msgs_per_s, pow_wait_ms, useful_trial_share
    "pod4_burst_64": (27.892, 586.29, 83.913),      # ledger, PR 39
    "burst_send_64": (9.487, 2067.5, 94.74),        # ledger, PR 38
}


#: (cell, copy rule): sent_msgs_per_s, pow_wait_ms, useful_trial_share
#: at the shape of PR 40: the driver's lines of PR 41 (the middle of
#: its two sides), and with the copies my chip runs of PR 42 (PERF.md
#: section 6) until the ledger has that PR's line
NEWER = {
    ("pod4_burst_64", "none"): (30.876, 538.2, 96.53),  # ledger, PR 41
    ("burst_send_64", "none"): (10.283, 2051.9, 99.12),  # ledger, PR 41
    # my chip runs, PR 42: the median of three untraced runs, the mean
    # of two traced ones
    ("pod4_burst_64", "one"): (36.630, 542.3, 93.31),
    ("burst_send_64", "one"): (10.283, 2051.9, 99.12),   # one lane
}


@pytest.fixture(scope="module")
def readings():
    return {cell: sweep_model.read(cell, "old", sweeps=30, seeds=(1, 2),
                                   copies="none")
            for cell in LEDGER}


@pytest.fixture(scope="module")
def rules():
    """``pod4_burst_64`` at today's shape under each copy rule."""
    return {rule: sweep_model.read("pod4_burst_64", "new", sweeps=30,
                                   seeds=(1, 2), copies=rule)
            for rule in sweep_model.COPY_RULES}


@pytest.mark.parametrize("cell,rule", sorted(NEWER))
def test_the_model_reads_todays_shape_within_a_tenth(cell, rule, rules):
    got = rules[rule] if cell == "pod4_burst_64" else sweep_model.read(
        cell, "new", sweeps=30, seeds=(1, 2), copies=rule)
    for name, want in zip(("sent_msgs_per_s", "pow_wait_ms",
                           "useful_trial_share"), NEWER[cell, rule]):
        assert got[name] == pytest.approx(want, rel=0.10), name


def test_why_the_rule_is_one_object_a_turn(rules):
    """ISSUE 42's table: one object a starved turn turns the tail into
    search; greedy copies turn four chips into four copies of one
    queue and lose to no copies at all."""
    rate = {rule: r["sent_msgs_per_s"] for rule, r in rules.items()}
    assert rate["one"] > rate["half"] > rate["none"] > rate["all"]
    assert rate["one"] > 1.10 * rate["none"]
    assert rules["none"]["copies_per_sweep"] == 0
    assert 17 < rules["none"]["device_idle_share"] < 25
    assert rules["one"]["device_idle_share"] < 8
    # what the copies cost: the losers' launches in flight at the win
    assert 85 < rules["one"]["useful_trial_share"] \
        < rules["none"]["useful_trial_share"]
    assert rules["all"]["useful_trial_share"] < 75
    assert 6 < rules["one"]["copies_per_sweep"] < 20
    assert 40 < rules["one"]["copies_won_share"] < 70
    with pytest.raises(ValueError):
        sweep_model.simulate(4, sweep_model.SHAPES["new"], sweeps=1,
                             copies="some")


@pytest.mark.parametrize("cell", list(LEDGER))
def test_the_model_reads_the_ledgers_burst_cells_within_a_tenth(
        readings, cell):
    got = readings[cell]
    for name, want in zip(("sent_msgs_per_s", "pow_wait_ms",
                           "useful_trial_share"), LEDGER[cell]):
        assert got[name] == pytest.approx(want, rel=0.10), name


def test_the_model_reproduces_pr_39s_null(readings):
    """Four chips give 2.8 times one, not 4, and a fifth of the chips'
    time is idle: the tail, which the step does not touch."""
    pod, one = readings["pod4_burst_64"], readings["burst_send_64"]
    assert 2.4 < pod["sent_msgs_per_s"] / one["sent_msgs_per_s"] < 3.2
    assert 17 < pod["device_idle_share"] < 25
    assert one["device_idle_share"] < 3


def test_a_dead_slot_costs_what_the_chip_read():
    old, new = sweep_model.SHAPES["old"], sweep_model.SHAPES["new"]
    assert old.slab == new.slab == sweep_model.SHAPES["16k"].slab \
        == 8_388_608
    # launches of 64 dead slots: 15.66, 4.29, 2.55 ms (my chip runs,
    # PR 40, the kernel alone)
    for shape, ms in (("old", 15.66), ("16k", 4.29), ("new", 2.55)):
        assert 64 * sweep_model.SHAPES[shape].slot_ms(1) \
            == pytest.approx(ms, rel=0.04), shape
    # and a live slot that misses costs what it did, but 0.7 %
    assert new.slot_ms(new.chunks) == pytest.approx(
        old.slot_ms(old.chunks), rel=1e-2)
