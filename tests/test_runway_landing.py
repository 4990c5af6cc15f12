"""Runway destinations + the landing chain (reference route.py:741-800).

DEST/ADDWPT with APT/RWNN syntax resolve the displaced threshold from the
runway database (defrwy-registered here — the reference's apt.zip is not
in this snapshot), type the waypoint WPT_RWY, and when the FMS reaches the
final runway waypoint the sim issues the reference's landing sequence:
HDG hold, DELAY 10 SPD 10, DELAY 42 DEL.
"""
import numpy as np
import pytest

from bluesky_tpu.core.route import WPT_RWY
from bluesky_tpu.simulation.sim import Simulation


@pytest.fixture()
def sim():
    s = Simulation(nmax=8)
    # Register a runway: threshold near the aircraft, heading 90
    s.navdb.defrwy("TEST", "RW09", 52.0, 4.1, 90.0)
    return s


def test_runway_threshold_lookup(sim):
    assert sim.navdb.getrwythreshold("TEST", "RW09") == (52.0, 4.1, 90.0)
    assert sim.navdb.getrwythreshold("test", "09") == (52.0, 4.1, 90.0)
    assert sim.navdb.getrwythreshold("TEST", "RWY09") == (52.0, 4.1, 90.0)
    assert sim.navdb.getrwythreshold("TEST", "RW27") is None
    assert sim.navdb.txt2pos("TEST/RW09") == (52.0, 4.1)


def test_dest_runway_creates_rwy_waypoint(sim):
    for cmd in ("CRE KL1 B744 52.0 4.0 90 2000 150",
                "DEST KL1 TEST/RW09"):
        sim.stack.stack(cmd)
        sim.stack.process()
    r = sim.routes.route(0)
    assert r.nwp == 1
    assert r.name[0] == "TEST/RW09"
    assert r.wtype[0] == WPT_RWY


def test_landing_chain_fires(sim):
    """Fly onto the threshold: the chain must hold heading, decelerate
    after 10 s, and delete the aircraft after 42 s."""
    # DTMULT lifts the OP-mode realtime pacing (DELAY timers are
    # simt-scheduled, so the chain is unaffected) — without it this
    # test sleeps ~180 wall seconds to cover 180 sim seconds
    for cmd in ("CRE KL1 B744 52.0 4.0 90 500 150",
                "ALT KL1 0",
                "DEST KL1 TEST/RW09",
                "DTMULT 50",
                "OP"):
        sim.stack.stack(cmd)
        sim.stack.process()
    # threshold is ~3.7 nm east at 150 kt CAS -> reached at ~89 s; read
    # the flag BEFORE the DELAY 42 DEL fires (the delete also drops the
    # host route, so route(0) after deletion is a fresh empty plan)
    r = sim.routes.route(0)
    sim.run(until_simt=110.0)
    assert r.flag_landed, "landing chain did not fire"
    assert sim.traf.ntraf == 1
    hdg = float(np.asarray(sim.traf.state.ac.hdg)[0])
    assert abs((hdg - 90.0 + 180) % 360 - 180) < 5.0
    # 42 s after the chain fired the aircraft must be deleted
    sim.run(until_simt=180.0)
    assert sim.traf.ntraf == 0, "aircraft not deleted after landing"


def test_runway_dest_keeps_last_place(sim):
    """ADDWPT after a runway DEST must insert BEFORE the threshold, and a
    repeated runway DEST must replace it (reference dest semantics)."""
    for cmd in ("CRE KL1 B744 52.0 4.0 90 FL100 250",
                "DEST KL1 TEST/RW09",
                "ADDWPT KL1 52.2 4.05"):
        sim.stack.stack(cmd)
        sim.stack.process()
    r = sim.routes.route(0)
    assert r.name[-1] == "TEST/RW09" and r.wtype[-1] == WPT_RWY
    assert r.nwp == 2
    sim.navdb.defrwy("TEST", "RW27", 52.0, 4.2, 270.0)
    sim.stack.stack("DEST KL1 TEST/RW27")
    sim.stack.process()
    r = sim.routes.route(0)
    assert r.nwp == 2                       # replaced, not appended
    assert r.name[-1] == "TEST/RW27"


def test_deleted_aircraft_leaves_no_stale_route(sim):
    """A reused slot must not inherit the previous occupant's runway
    destination (reference: routes are traf children cleared by the
    delete cascade)."""
    for cmd in ("CRE KL1 B744 52.0 4.0 90 FL100 250",
                "DEST KL1 TEST/RW09"):
        sim.stack.stack(cmd)
        sim.stack.process()
    slot = sim.traf.id2idx("KL1")
    assert sim.routes.route(slot).nwp == 1
    # (free slots are given out longest free first: with the fleet
    # full, the slot freed below is the next one out)
    for k in range(1, sim.traf.nmax):
        sim.stack.stack(f"CRE FILL{k} B744 53.0 {k} 90 FL100 250")
    sim.stack.stack("DEL KL1")
    sim.stack.process()
    assert slot not in sim.routes.routes
    # Recreate into the same slot: clean plan, no runway final
    sim.stack.stack("CRE KL2 B744 52.0 4.0 90 FL100 250")
    sim.stack.process()
    slot2 = sim.traf.id2idx("KL2")
    assert slot2 == slot
    assert sim.routes.route(slot2).nwp == 0
    assert not sim.routes.runway_final_slots()
    sim.stack.stack("DEL KL2")
    sim.stack.process()


def test_no_false_fire_on_lnav_off_far_away(sim):
    """Manual LNAV OFF far from the field must not trigger the chain."""
    for cmd in ("CRE KL1 B744 52.0 0.0 90 FL100 250",
                "DEST KL1 TEST/RW09",
                "LNAV KL1 OFF",
                "OP"):
        sim.stack.stack(cmd)
        sim.stack.process()
    sim.run(until_simt=5.0)
    assert not sim.routes.route(0).flag_landed
    assert sim.traf.ntraf == 1
