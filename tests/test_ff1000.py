"""The system against the plain reference in a world nobody steers
(ISSUE-42: the configuration ``ff1000`` and its anchor).

A fleet of ``benchmark/generators/cre_flow.py`` (one ``CRE`` line an
aircraft, every value drawn from a seed) through ``Simulation`` with
``ASAS ON; RESO OFF`` for 1,200 steps, as the served state shows it at
every chunk edge (the edge pack a client's ACDATA frame is made from):

* every aircraft's position against ``plain.dead_reckon``, BlueSky's
  position update step by step over the 20 steps of each interval;
* every CD interval's ``inconf`` against ``plain.interval_of_sample``
  over the whole fleet: the flags an edge carries are the detection's at
  the start of the interval, on the state of the edge before, which a
  counted clock places exactly.

And the anchor the same way: ``SYN SUPER 8`` CD-only, the conflict pairs
``tests/test_cd.py`` pins, through the served state and the counted
clock.
"""
import os
import sys

import numpy as np
import pytest

from bluesky_tpu.simulation.sim import Simulation

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "benchmark"))
from generators import cre_flow  # noqa: E402
from reference import plain  # noqa: E402

STEPS, CHUNK = 1200, 20

#: the configuration's flow in a box of the full fleet's density, the
#: draws inside every type's envelope so that nobody's speed changes
FLOW = dict(box=[46.0, 46.8, -62.0, -61.0], heading_deg=[85.0, 95.0],
            flight_level=[300, 380], mach=[0.80, 0.84],
            types=["A320", "A319", "A321", "B738", "B77W", "E190"])


def _frame(sim):
    """The newest retired edge as a client's ACDATA frame holds it."""
    idx, data = sim._last_edge.acdata_arrays()
    return dict({k: np.asarray(data[k]) for k in (
        "lat", "lon", "alt", "trk", "gs", "vs", "inconf")},
        id=[sim.traf.ids[i] for i in idx],
        simt=sim.sent(sim._last_edge.simt))


def _frames(sim, setup):
    for line in ["HOLD", "ASAS ON", "RESO OFF"] + setup:
        sim.stack.stack(line)
    sim.stack.process()
    sim.setdtmult(100.0)
    sim.chunk_steps = CHUNK
    sim.op()
    frames, last = [], 0
    while last < STEPS + CHUNK:        # edges at counts 20, 40, ... 1220
        sim.step()
        if sim._last_edge is not None and sim._last_edge.nstep > last:
            last = sim._last_edge.nstep
            frames.append(_frame(sim))
    sim.drain_pipeline()
    return frames


def _held_to_the_reference(frames, n, gap_m=0.0):
    """``gap_m``: the most a position may lie from the reference's; 0
    where nobody's velocity changes, and the update is BlueSky's own
    step by step."""
    everyone = np.arange(n)
    flagged = 0
    for k, (a, b) in enumerate(zip(frames[:-1], frames[1:])):
        assert a["id"] == b["id"] and len(a["id"]) == n
        assert plain.counted(a["simt"]) == CHUNK * (k + 1)
        assert plain.counted(b["simt"]) == CHUNK * (k + 2)
        # straight and level, everyone, all the way
        assert np.array_equal(a["trk"], b["trk"])
        assert not a["vs"].any() and not b["vs"].any()
        if not gap_m:
            assert np.array_equal(a["gs"], b["gs"])
        gap = plain.dead_reckon(a, b, everyone, everyone, CHUNK)
        assert float(gap.max()) <= gap_m, (k, float(gap.max()))
        # B's flags: the detection at the interval's first step, on A
        inconf, _, _ = plain.interval_of_sample(everyone, a)
        assert np.array_equal(inconf, b["inconf"]), (
            k, np.flatnonzero(inconf != b["inconf"]))
        flagged += int(inconf.sum())
    return flagged


@pytest.mark.parametrize("seed", (4200000001, 2**31 + 42))
def test_a_cre_flow_fleet_flies_and_detects_as_the_plain_reference(seed):
    n = 64
    frames = _frames(Simulation(nmax=64), cre_flow.commands(FLOW, seed, n))
    assert len(frames) == STEPS // CHUNK + 1
    assert plain.clock_of(f["simt"] for f in frames) == "count"
    flagged = _held_to_the_reference(frames, n)
    # the toy fleet has conflicts to hold the flags to: a tenth of the
    # ownships and more, every interval
    assert flagged >= 0.1 * n * (len(frames) - 1)


def test_the_anchor_super8_cd_only():
    frames = _frames(Simulation(nmax=16), ["SYN SUPER 8"])
    assert len(frames) == STEPS // CHUNK + 1
    # the eight accelerate on their way in, so a position is held to
    # the mean of two frames' velocities: centimetres in an interval
    flagged = _held_to_the_reference(frames, 8, gap_m=0.1)
    # eight aircraft converging on one point: once the first pair is
    # inside the look-ahead every one is in conflict with every other
    # (tests/test_cd.py pins the pairs), and stays so
    counts = [int(f["inconf"].sum()) for f in frames]
    assert set(counts) == {0, 8} and counts == sorted(counts)
    assert flagged >= 8 * 50 and frames[-1]["inconf"].all()
