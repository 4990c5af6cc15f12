"""Traffic facade tests: create/delete/id2idx invariants over the padded
state (the analogue of the reference's test_traffic.py create/delete suite)."""
import numpy as np
import jax.numpy as jnp
import pytest

from bluesky_tpu.core.traffic import Traffic
from bluesky_tpu.ops import aero


def make_traf(nmax=16):
    return Traffic(nmax=nmax, dtype=jnp.float64)


def test_create_activates_slots_and_sets_state():
    traf = make_traf()
    ok, _ = traf.create(1, "B744", 3000.0, 150.0, None, 52.0, 4.0, 90.0, "KL204")
    assert ok
    traf.flush()
    st = traf.state
    i = traf.id2idx("KL204")
    assert i >= 0
    assert bool(st.ac.active[i])
    assert float(st.ac.lat[i]) == pytest.approx(52.0)
    assert float(st.ac.lon[i]) == pytest.approx(4.0)
    assert float(st.ac.hdg[i]) == pytest.approx(90.0)
    assert float(st.ac.alt[i]) == pytest.approx(3000.0)
    # 150 m/s is CAS -> TAS should be higher at 3 km
    assert float(st.ac.tas[i]) > 150.0
    assert float(st.ac.cas[i]) == pytest.approx(150.0, rel=1e-10)
    assert float(st.ac.selalt[i]) == pytest.approx(3000.0)
    # AP child initialised from traffic state (autopilot.py:45-57)
    assert float(st.ap.trk[i]) == pytest.approx(90.0)
    assert float(st.ap.alt[i]) == pytest.approx(3000.0)
    # active waypoint defaults (activewpdata.py:22-29)
    assert float(st.actwp.lat[i]) == pytest.approx(89.99)
    assert float(st.actwp.spd[i]) == pytest.approx(-999.0)


def test_mach_speed_input():
    traf = make_traf()
    traf.create(1, "B744", 11000.0, 0.8, None, 0.0, 0.0, 0.0, "MACH1")
    traf.flush()
    i = traf.id2idx("MACH1")
    st = traf.state
    assert float(st.ac.mach[i]) == pytest.approx(0.8, rel=1e-9)
    assert float(st.ac.tas[i]) == pytest.approx(
        0.8 * float(aero.vvsound(jnp.asarray(11000.0))), rel=1e-9)


def test_duplicate_callsign_rejected():
    traf = make_traf()
    traf.create(1, "B744", 3000.0, 150.0, None, 0.0, 0.0, 0.0, "AA1")
    traf.flush()
    ok, msg = traf.create(1, "B744", 3000.0, 150.0, None, 0.0, 0.0, 0.0, "AA1")
    assert not ok and "exists" in msg


def test_delete_frees_slot_and_reuse():
    traf = make_traf(nmax=3)       # no slot to spare: the freed one is next
    for k in range(3):
        traf.create(1, "A320", 3000.0, 150.0, None, float(k), 0.0, 0.0, f"AC{k}")
    traf.flush()
    assert traf.ntraf == 3
    i1 = traf.id2idx("AC1")
    traf.delete(i1)
    assert traf.ntraf == 2
    assert traf.id2idx("AC1") == -1
    assert not bool(traf.state.ac.active[i1])
    # other aircraft untouched
    assert traf.id2idx("AC0") >= 0 and traf.id2idx("AC2") >= 0
    # slot is reused by the next create
    traf.create(1, "A320", 3000.0, 150.0, None, 9.0, 0.0, 0.0, "NEW1")
    traf.flush()
    assert traf.id2idx("NEW1") == i1


def test_ntraf_capacity_guard():
    traf = make_traf(nmax=4)
    for k in range(4):
        traf.create(1, "A320", 3000.0, 150.0, None, float(k), 0.0, 0.0, f"AC{k}")
    traf.flush()
    traf.create(1, "A320", 3000.0, 150.0, None, 9.0, 0.0, 0.0, "OVER")
    with pytest.raises(RuntimeError, match="traffic full"):
        traf.flush()


def test_batched_creation_single_flush():
    traf = make_traf(nmax=32)
    for k in range(20):
        traf.create(1, "B738", 5000.0, 140.0, None, float(k) * 0.1, 0.0,
                    float(k * 18), f"BATCH{k}")
    traf.flush()
    st = traf.state
    assert int(np.sum(np.asarray(st.ac.active))) == 20
    for k in range(20):
        i = traf.id2idx(f"BATCH{k}")
        assert float(st.ac.hdg[i]) == pytest.approx(float(k * 18) % 360.0)


def test_reset_clears_everything():
    traf = make_traf()
    traf.create(1, "A320", 3000.0, 150.0, None, 0.0, 0.0, 0.0, "AC0")
    traf.flush()
    traf.reset()
    assert traf.ntraf == 0
    assert not np.asarray(traf.state.ac.active).any()


def test_creconfs_creates_conflicting_intruder():
    from bluesky_tpu.ops import cd
    traf = make_traf()
    traf.create(1, "B744", 3000.0, 200.0, None, 52.0, 4.0, 90.0, "OWN")
    traf.flush()
    traf.creconfs("INTRUDER", "B744", traf.id2idx("OWN"), dpsi=90.0,
                  cpa=1.0, tlosh=120.0)
    st = traf.state
    out = cd.detect(st.ac.lat, st.ac.lon, st.ac.trk, st.ac.gs, st.ac.alt,
                    st.ac.vs, st.ac.active,
                    5.0 * 1852.0, 1000.0 * 0.3048, 300.0)
    i, j = traf.id2idx("OWN"), traf.id2idx("INTRUDER")
    assert bool(out.swconfl[i, j]), "creconfs pair must be in conflict"
