"""A fleet that turns over (PR 35): the queued ``Traffic.delete``, AREA's
tick as one device program, TRAFGEN's guidance queued with its creation,
the chunk pipeline held through both, and the benchmark's ``flow`` check
against its plain reference.

Oracles: the eager ``delete`` of the tree before (tests/test_stack.py's
one-at-a-time replay), the host version of ``Area.update`` in NumPy, and
a TRAFGEN run recorded on that tree (tests/golden/trafgen_200_ticks.json,
written by ``flow_scene.trafgen_record`` there).
"""
import json
import os
import sys

import jax
import numpy as np
import pytest

from bluesky_tpu.core.traffic import Traffic
from bluesky_tpu.simulation.sim import Simulation
from flow_scene import CIRCLE, do, flow_world, trafgen_record
from test_stack import _WriteLog, _np_tree

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "benchmark"))

# here and not in the tests: another test file puts the repo's root,
# which has a check.py of its own, before this on the path
import check                                      # noqa: E402
from reference import flow as ref                 # noqa: E402


# ------------------------------------------------------ the queued delete
def _six(sim):
    """Six aircraft in slots 0 to 5 and partner tables that name each
    other, in caller slots and (through a sort permutation) in sorted
    ones."""
    for k in range(6):
        sim.traf.create(1, "B744", 3000.0 + 100 * k, 150.0, None,
                        52.0 + 0.1 * k, 4.0, 90.0, f"AC{k}")
    st = sim.traf.state
    n, kp = st.asas.partners.shape
    rng = np.random.default_rng(5)
    perm = rng.permutation(n).astype(np.int32)
    partners = np.full((n, kp), -1, np.int32)
    partners_s = np.full(st.asas.partners_s.shape, -1, np.int32)
    for i in range(6):
        others = [j for j in range(6) if j != i]
        partners[i, :5] = others
        partners_s[perm[i], :5] = perm[others]
    sim.traf.state = st.replace(asas=st.asas.replace(
        partners=jax.numpy.asarray(partners),
        partners_s=jax.numpy.asarray(partners_s),
        sort_perm=jax.numpy.asarray(perm)))
    return perm


def _alone(traf):
    traf.delete(traf.id2idx("AC1"))


def _several(traf):
    traf.delete([traf.id2idx("AC0"), traf.id2idx("AC4")])


def _mixed(traf):
    traf.write("ac", "selspd", traf.id2idx("AC2"), 123.0)
    traf.write("ac", "selalt", traf.id2idx("AC1"), 4321.0)
    traf.delete(traf.id2idx("AC1"))
    traf.create(1, "A320", 5000.0, 140.0, None, 50.0, 2.0, 10.0, "NEW1")
    traf.write("ap", "trk", traf.id2idx("NEW1"), 33.0)
    traf.write("ac", "selspd", traf.id2idx("AC3"), 99.0)


def _reuse(traf):
    # a write to the aircraft that goes, for a field a creation fills
    # and for one it does not; the freed slot taken in the same pass
    traf.write("ac", "selspd", 0, 111.0)
    traf.write("ac", "swhdgsel", 0, True)
    # (slots are given out longest free first: the ten never used,
    # then the one just freed)
    traf.delete(0)
    traf.create(11, "B738", 6000.0, 130.0, None, 49.0 + 0.1 * np.arange(11),
                1.0 + 0.1 * np.arange(11), 20.0 + np.arange(11),
                [f"NEW{k}" for k in range(1, 12)])
    assert traf.id2idx("NEW1") == 6 and traf.id2idx("NEW11") == 0
    traf.write("ac", "selalt", 0, 777.0)


def _created_and_deleted(traf):
    traf.create(1, "A320", 5000.0, 140.0, None, 50.0, 2.0, 10.0, "NEW1")
    traf.delete(traf.id2idx("NEW1"))
    traf.delete(traf.id2idx("AC5"))


class _PassLog(_WriteLog):
    """tests/test_stack.py's log of a pass, with a creation's rows put
    where ``create`` was called and not where the queue built them: the
    oracle applies every write one at a time in the order it was
    asked for, as the tree before this one did."""

    def __init__(self, traf):
        super().__init__(traf)
        create, rows = traf.create, traf._creation_rows
        marks = []

        def logged_create(*args, **kw):
            marks.append(len(self.ops))
            self.ops.append(None)
            return create(*args, **kw)

        def placed_rows(batch):
            n0 = len(self.ops)
            out = rows(batch)
            (at,), marks[:] = marks, []        # one creation a pass
            new = self.ops[n0:]
            del self.ops[n0:]
            self.ops[at:at + 1] = new
            return out

        traf.create, traf._creation_rows = logged_create, placed_rows


PASSES = {"alone": (_alone, 1), "several": (_several, 1),
          "with_create_and_setslot": (_mixed, 1),
          "freed_slot_reused_in_the_pass": (_reuse, 1),
          "created_and_deleted_in_the_pass": (_created_and_deleted, 2)}


@pytest.mark.parametrize("name", sorted(PASSES))
def test_queued_delete_equals_the_eager_one(name):
    ops, programs = PASSES[name]
    sim = Simulation(nmax=16)
    perm = _six(sim)
    before = _np_tree(sim.traf.state)
    log = _PassLog(sim.traf)
    # the oracle's delete clears the slot after what was queued before
    # it: _WriteLog flushes there, which the queue must not need
    flushes = []
    sim.traf.flush = lambda: flushes.append(1)
    count = sim.obs.get("sim_state_write_programs")
    p0 = count.value
    ops(sim.traf)
    assert sim.traf.dirty
    del sim.traf.flush
    got = _np_tree(sim.traf.state)
    assert count.value - p0 == programs      # one program a pass
    want = log.replay(before)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    # both partner tables hold no deleted slot, in either space
    gone = [i for i in range(6) if not got.ac.active[i]]
    assert gone or "created" not in name and "alone" not in name
    for i in gone:
        assert (got.asas.partners[i] == -1).all()
        assert (got.asas.partners_s[perm[i]] == -1).all()
        assert not (got.asas.partners == i).any()
        assert not (got.asas.partners_s == perm[i]).any()
    if name == "freed_slot_reused_in_the_pass":
        assert got.ac.active[0] and got.ac.selalt[0] == 777.0
        assert (got.asas.partners[0] == -1).all()
        assert not (got.asas.partners[1:6] == 0).any()


def test_a_slot_is_reused_only_once_the_host_has_seen_it_freed():
    traf = Traffic(nmax=4)
    for k in range(4):
        traf.create(1, "B744", 3000.0, 150.0, None, 52.0, 4.0 + k, 90.0,
                    f"AC{k}")
    _ = traf.state
    # a program of a plugin's own deactivated slot 2 on the device: the
    # host still holds it, and the fleet is full
    traf.create(1, "B744", 3000.0, 150.0, None, 52.0, 9.0, 90.0, "LATE")
    assert traf.ntraf == 5 and traf.id2idx("LATE") == -1
    traf.forget([2])
    assert traf.ids[2] is None and traf.id2idx("AC2") == -1
    _ = traf.state                        # the batch finds the slot now
    assert traf.id2idx("LATE") == 2 and traf.ntraf == 4


# ----------------------------------------------------- AREA on the device
def test_area_on_the_device_names_the_leavers_the_host_version_named(
        tmp_path, monkeypatch):
    from bluesky_tpu import settings
    monkeypatch.setattr(settings, "log_path", str(tmp_path))
    sim = Simulation(nmax=256)
    # SEED 12: under SEED 11 one aircraft stands 0.17 m inside the ring's
    # edge at a tick (5.99991 of 6 nm, at 28.0 s on the counted clock),
    # where the float64 test on the host and the device's float32 differ
    do(sim, "HOLD", "SEED 12", "PAN 52.6 5.4", "ZOOM 10", "MCRE 200",
       "PLUGINS LOAD AREA", "CIRCLE RING 52.6 5.4 6", "AREA RING")
    sim.op()
    sim.fastforward()
    ids = list(sim.traf.ids)
    inside = np.zeros(sim.traf.nmax, bool)     # the host version's own
    named, left = [], []
    for k in range(1, 81):                     # 40 s of AREA ticks
        sim.run(until_simt=0.5 * k)
        # a leaver's slot is frozen where the tick found it
        ac = sim.traf.state.ac
        active = np.asarray([i is not None for i in ids])
        now = np.asarray(sim.areas.checkInside(
            "RING", np.asarray(ac.lat), np.asarray(ac.lon),
            np.asarray(ac.alt))) & active
        named += [(k, ids[s]) for s in np.flatnonzero(
            inside & ~now & active)]
        inside = now
        left += [(k, i) for i, j in zip(ids, sim.traf.ids)
                 if i is not None and j is None]
        ids = list(sim.traf.ids)
        assert [i is not None for i in ids] \
            == list(np.asarray(ac.active))
    assert len(named) > 20 and left == named
    assert sim.pipe_stats["sync_reasons"].get("plugin", 0) == 0
    # the FLST log holds a row for each, stamped with its tick
    sim.datalog.getlogger("FLSTLOG").stop()
    log = next(f for f in os.listdir(tmp_path) if f.startswith("FLSTLOG"))
    rows = [ln.split(", ") for ln in open(tmp_path / log)
            if not ln.startswith("#")]
    assert [(round(float(r[0]) / 0.5), r[1]) for r in rows] == named
    assert all(float(r[4]) > 0 and float(r[5]) >= float(r[4])
               for r in rows)               # 2D and 3D distance flown


def test_a_command_cannot_refill_a_slot_whose_leaver_is_unread():
    """AREA's tick took an aircraft out on the device and the host has
    not read it yet: a pass of the stack that deletes it by name and
    creates another must not hand the newcomer to the later read."""
    sim = flow_world(nmax=42, standing=40, flow_per_h=0)
    area = sim.stack.cmddict["AREA"][2].__self__
    sim.op()
    sim.fastforward()
    for _ in range(2000):
        sim.step()
        unread = [jax.device_get(t[3][0]) for t in area._left]
        if any(int(n) for n, _, _ in unread):
            break
    n, slots, _ = next(u for u in unread if int(u[0]))
    slot = int(slots[0])
    leaver = sim.traf.ids[slot]
    assert leaver is not None and sim.traf.ntraf == 40
    sim.stack.stack(f"DEL {leaver}")
    for k in (1, 2, 3):      # two slots never used, then the leaver's
        sim.stack.stack(f"CRE NEW{k} B744 52.6 5.4 90 FL200 250")
    sim.step()
    sim.drain_pipeline()
    assert sim.traf.id2idx(leaver) == -1
    new = sim.traf.id2idx("NEW3")
    assert new == slot
    assert bool(sim.traf.state.ac.active[new])
    assert [i is not None for i in sim.traf.ids] \
        == list(np.asarray(sim.traf.state.ac.active))


# ------------------------------------------- TRAFGEN, before and after
def test_trafgen_creates_the_same_ids_times_and_states_as_before():
    # the record is of the counted clock (PR 42): a tick every 0.1 s to
    # the step, where the float32 sum's ticks fell 0.05 s off now and then
    # (1.25, 1.35) and drew other spawn counts for their other intervals
    with open(os.path.join(HERE, "golden", "trafgen_200_ticks.json")) as f:
        want = json.load(f)
    got = trafgen_record()
    assert [(t, ids) for t, ids, _ in got] \
        == [(t, ids) for t, ids, _ in want]
    assert sum(len(ids) for _, ids, _ in got) >= 30
    for (_, ids, rows), (_, _, before) in zip(got, want):
        for acid in ids:
            assert rows[acid] == pytest.approx(before[acid], rel=1e-6,
                                               abs=1e-6), acid


# ------------------------------------------------ the pipeline holds
def _run_flow(**kw):
    sim = flow_world(**kw)
    sim.op()
    sim.fastforward()
    sim.run(until_simt=30.0)
    return sim


def test_a_tick_that_only_queues_writes_retires_no_edge():
    sim = _run_flow()
    stats = sim.pipe_stats
    assert stats["sync_reasons"].get("plugin", 0) == 0
    assert sim.obs.get("sim_sync_reason_plugin") is None \
        or sim.obs.get("sim_sync_reason_plugin").value == 0
    assert stats["sync_chunks"] == 0 and stats["pipelined_chunks"] >= 300
    assert sim.obs.get("sim_ac_created").value > 40 + 20
    assert sim.obs.get("sim_ac_deleted").value > 5
    assert sim.obs.get("sim_live_aircraft").value == sim.traf.ntraf
    assert sim.obs.get("sim_plugin_ms").count >= 300
    assert sim.obs.get("sim_route_sync_ms").count >= 20
    # a hook that reads the state on the host still has its edge
    sim.plugins.reads_state["AREA"] = True
    sim.run(until_simt=32.0)
    assert stats["sync_reasons"]["plugin"] >= 3


def test_the_host_runs_ahead_by_one_unclamped_chunk_of_steps():
    """A 0.1 s plugin interval clamps chunks to 2 steps; the host then
    keeps up to ``chunk_steps`` steps in flight, ten such chunks, and at
    the unclamped 20-step chunk one, as it always did."""
    sim = flow_world()
    sim.op()
    sim.fastforward()
    depth = []
    for _ in range(40):
        sim.step()
        depth.append(len(sim._inflight))
        assert sum(e.chunk for e in sim._inflight) <= sim.chunk_steps
    assert max(depth) == 10 and sim._inflight[-1].chunk == 2
    seqs = [e.seq for e in sim._inflight]
    assert seqs == sorted(seqs) and len(set(seqs)) == 10
    sim.drain_pipeline()
    assert not sim._inflight
    plain = Simulation(nmax=16)
    do(plain, "CRE KL1 B744 52 4 90 FL200 250", "DTMULT 50")
    for _ in range(5):
        plain.step()
        assert [e.chunk for e in plain._inflight] == [20]
    plain.drain_pipeline()


def test_pipelined_and_synchronous_runs_are_the_same_run():
    runs = []
    for pipeline in (True, False):
        sim = _run_flow(pipeline=pipeline)
        st = sim.traf.state
        rows = {i: [float(np.asarray(getattr(st.ac, f))[s])
                    for f in ("lat", "lon", "alt", "trk", "gs", "vs")]
                for i, s in sim.traf._id2slot.items()}
        runs.append(rows)
        assert sorted(rows) == sorted(
            sim.traf.ids[s] for s in np.flatnonzero(
                np.asarray(st.ac.active)))
    # the same aircraft, flown the same; not bit for bit, because the
    # host sees a slot freed an edge later with a chunk in flight, a
    # spawn then takes another slot, and a row sum over conflict
    # partners adds in slot order
    assert sorted(runs[0]) == sorted(runs[1]) and len(runs[0]) > 60
    for acid, row in runs[0].items():
        assert row == pytest.approx(runs[1][acid], rel=1e-4, abs=1e-3), acid


# ------------------------------------------- snapshots under the flow
def _host_is_the_device(sim):
    """The host's record names exactly the aircraft the state flies."""
    traf = sim.traf
    active = np.asarray(traf.state.ac.active)
    assert [i is not None for i in traf.ids] == list(active)
    assert traf.ntraf == int(active.sum()) == len(traf._id2slot)
    assert sorted(traf._free_slots()) == list(np.flatnonzero(~active))


@pytest.mark.parametrize("pipeline", [True, False])
def test_a_rollback_under_the_flow_keeps_no_leaver(pipeline):
    """A ring capture falls behind a chunk AREA's tick has already run
    behind, whose leavers the host has not read: the blob must not hold
    their callsigns, or a rollback keeps them for ever (AREA's record of
    them is void for the restored fleet)."""
    from bluesky_tpu.simulation.snapshot import SnapshotRing
    sim = flow_world(nmax=512, standing=200, pipeline=pipeline)
    sim.guard.set_policy("rollback")
    # a capture at nearly every edge, and all of them kept
    sim.snap_ring = SnapshotRing(depth=400, dt=0.1)
    sim.op()
    sim.fastforward()
    sim.run(until_simt=20.0)
    assert sim.obs.get("sim_ac_deleted").value > 20
    assert len(sim.snap_ring) >= 150
    for blob in sim.snap_ring._ring:
        assert [i is not None for i in blob["ids"]] \
            == list(blob["state"].ac.active), float(blob["state"].simt)
    ok, msg = sim.snap_ring.rollback(sim)
    assert ok, msg
    _host_is_the_device(sim)
    held = set(sim.traf._id2slot)
    sim.run(until_simt=sim.simt + 20.0)          # and the flow goes on
    sim.drain_pipeline()
    _host_is_the_device(sim)
    assert sim.obs.get("sim_ac_deleted").value > 10
    assert set(sim.traf._id2slot) - held         # newcomers found slots


def test_a_restore_drops_the_callsign_of_an_inactive_slot():
    """A blob that pairs a callsign with a slot its state has inactive
    (written between a tick's program and the host's reading of it)."""
    from bluesky_tpu.simulation import snapshot as snap
    sim = flow_world(nmax=42, standing=40, flow_per_h=0)
    blob = snap.state_blob(sim)
    blob["state"] = blob["state"].replace(ac=blob["state"].ac.replace(
        active=blob["state"].ac.active & (np.arange(42) != 3)))
    gone = blob["ids"][3]
    assert gone is not None
    ok, msg = snap.restore_blob(sim, blob, full_reset=False)
    assert ok, msg
    assert sim.traf.id2idx(gone) == -1 and sim.traf.ntraf == 39
    _host_is_the_device(sim)


# ------------------------------- the benchmark's check and its reference
SPEC = dict(kind="flow", reference="flow", cd_interval_s=1.0, sample=256,
            conflict_sample=128, pairs=2, circle=list(CIRCLE),
            area_dt_s=0.5,
            limits=dict(interval_flag_mismatch_share=0.1,
                        interval_reso_gap_p50_ms=30.0,
                        interval_position_gap_p99_m=1.0,
                        interval_turned_position_gap_p90_m=100.0,
                        flow_left_not_deleted=0.0,
                        flow_deleted_inside=0.0, flow_ids_reused=0.0))


def _frame(sim):
    st = sim.traf.state
    at = np.flatnonzero(np.asarray(st.ac.active))
    f = {k: np.asarray(getattr(st.ac, k))[at]
         for k in ("lat", "lon", "alt", "trk", "gs", "vs")}
    f.update({k: np.asarray(getattr(st.asas, k))[at]
              for k in ("asase", "asasn", "inconf")})
    return dict(f, id=[sim.traf.ids[s] for s in at], simt=sim.simt)


@pytest.fixture(scope="module")
def evidence():
    sim = flow_world(nmax=512, standing=60, flow_per_h=900)
    sim.op()
    sim.fastforward()
    sim.run(until_simt=20.0)
    frames = []
    for k in range(1, 16):                  # a frame every 0.3 s
        sim.run(until_simt=20.0 + 0.3 * k)
        frames.append(_frame(sim))
    return dict(frames=frames, compares=list(SPEC["limits"]),
                chunk_sim_s=0.1)


def test_program_against_the_flow_reference_through_the_flow_check(
        evidence):
    ok, numbers, also = check.decide(SPEC, evidence, seed=5)
    assert ok, numbers
    assert also["flow_frames"] == 15 and also["flow_judged"] > 1000
    assert also["interval_pairs"] == 2 and also["fleet_changed_share"] > 0


def test_the_flow_checks_control_is_not_correct(evidence):
    ok, numbers, _ = check.decide(
        SPEC, check.control_evidence(SPEC, evidence, 5), seed=5)
    assert not ok
    over = {k for k, v in numbers.items() if v["value"] > v["limit"]}
    assert {"interval_reso_gap_p50_ms", "interval_position_gap_p99_m",
            "interval_turned_position_gap_p90_m",
            "flow_deleted_inside"} <= over, numbers


@pytest.mark.parametrize("fault", ["kept", "deleted", "reused"])
def test_each_flow_number_catches_its_fault(evidence, fault):
    frames = [dict(f) for f in evidence["frames"]]
    b = frames[-1]
    if fault == "kept":
        # an aircraft both frames hold that is, in the frame the last
        # one is judged from, 20 m inside the edge and flying out of it
        # at 200 m/s: 40 m outside 0.3 s later, a tick and more before
        # the last frame
        a = frames[-4]
        k = next(k for k, i in enumerate(a["id"]) if i in set(b["id"]))
        east = (CIRCLE[2] * ref.NM - 20.0) / ref.REARTH \
            / np.cos(np.radians(CIRCLE[0]))
        for key, value in (("lat", CIRCLE[0]), ("trk", 90.0),
                           ("lon", CIRCLE[1] + np.degrees(east)),
                           ("gs", 200.0)):
            a[key] = np.array(a[key])
            a[key][k] = value
        number = "flow_left_not_deleted"
    elif fault == "deleted":
        # an aircraft well inside the circle, gone from the last frame
        k = int(np.argmin(ref.outside_m(CIRCLE, b["lat"], b["lon"])))
        for key in b:
            if key == "id":
                b[key] = b[key][:k] + b[key][k + 1:]
            elif key != "simt":
                b[key] = np.delete(b[key], k)
        number = "flow_deleted_inside"
    else:
        # a callsign of a deleted aircraft given to a new one
        gone = next(i for i in frames[0]["id"] if i not in
                    set(b["id"]) | set(frames[-2]["id"]))
        new = next(k for k, i in enumerate(b["id"])
                   if i not in set(frames[0]["id"]))
        b["id"] = b["id"][:new] + [gone] + b["id"][new + 1:]
        number = "flow_ids_reused"
    ok, numbers, _ = check.decide(
        SPEC, dict(evidence, frames=frames), seed=5)
    assert not ok and numbers[number]["value"] >= 1, numbers
