"""Check kind "flow" at a size a test run can hold, on frames a sound
program would send (a fleet flown step by step, aircraft deleted at the
tick that finds them outside a circle, new ones on its edge): correct;
the bfloat16 reference in its place is not; and each of the three flow
numbers trips on its own fault.  (tests/test_flow.py, in tier-1, holds
the program itself to this check at toy size.)"""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import check                                    # noqa: E402
from reference import flow, plain               # noqa: E402

CIRCLE = [51.0, 4.5, 40.0]
SPEC = dict(kind="flow", reference="flow", cd_interval_s=1.0, sample=256,
            conflict_sample=128, pairs=2, circle=CIRCLE, area_dt_s=0.5,
            limits=dict(interval_flag_mismatch_share=0.01,
                        interval_reso_gap_p50_ms=0.5,
                        interval_position_gap_p99_m=1.0,
                        interval_turned_position_gap_p90_m=5.0,
                        flow_left_not_deleted=0.0, flow_deleted_inside=0.0,
                        flow_ids_reused=0.0))


def _fleet(n, seed):
    """Aircraft over a disc a hair smaller than the circle, flying
    level and straight at 130 to 240 m/s."""
    rng = np.random.default_rng(seed)
    r = 39.95 * plain.NM * np.sqrt(rng.uniform(0, 1, n))
    brg = rng.uniform(0, 2 * np.pi, n)
    f = dict(lat=CIRCLE[0] + np.degrees(r * np.cos(brg) / plain.REARTH),
             lon=CIRCLE[1] + np.degrees(r * np.sin(brg) / plain.REARTH
                                        / np.cos(np.radians(CIRCLE[0]))),
             alt=rng.integers(100, 120, n) * 100 * plain.FT,
             trk=rng.integers(1, 360, n).astype(float),
             gs=rng.uniform(130.0, 240.0, n), vs=np.zeros(n))
    f = {k: np.asarray(v, np.float32) for k, v in f.items()}
    return dict(f, inconf=np.zeros(n, bool), asase=np.zeros(n, np.float32),
                asasn=np.zeros(n, np.float32), simt=1100.0,
                id=[f"AC{k:04d}" for k in range(n)])


def _frames(clock, n=1500, seed=3, count=12, every=6):
    """``count`` frames ``every`` steps apart; AREA ticks every ten
    steps on what it finds outside, two new aircraft a frame.  The
    frames carry the clock ``clock`` from 1100 s on: "sum", a float32
    sum of steps as today's program keeps it, or "count", the step
    count times SIMDT rounded once; detection goes by the same clock."""
    from checks import frames as fr
    f32 = np.float32
    f, out, born = _fleet(n, seed), [], 0
    n0, f["simt"] = plain.sum_clock(1100.0) if clock == "sum" \
        else (22000, 1100.0)
    inside = flow.outside_m(CIRCLE, f["lat"], f["lon"]) <= 0
    for step in range(1, count * every + 1):
        own = np.arange(len(f["id"]))
        f["lat"], f["lon"] = flow.straight_on(f, own, 1)
        f["simt"] = float(f32(f32(f["simt"]) + f32(plain.SIMDT))) \
            if clock == "sum" else float(f32((n0 + step) * plain.SIMDT))
        if step % 10 == 0:
            now = flow.outside_m(CIRCLE, f["lat"], f["lon"]) <= 0
            keep = ~(inside & ~now)
            f = {k: (v[keep] if isinstance(v, np.ndarray) else
                     [i for i, ok in zip(v, keep) if ok] if k == "id" else v)
                 for k, v in f.items()}
            inside = now[keep]
        if step % every == 0:
            new = _fleet(2, 1000 + step)
            for k in f:
                if k == "id":
                    f[k] = f[k] + [f"NEW{born + j:03d}" for j in range(2)]
                elif k != "simt":
                    f[k] = np.concatenate([f[k], new[k]])
            born += 2
            inside = np.concatenate([inside, [False, False]])
            back = fr.flown_back(f, fr.steps_since_detection(
                f["simt"], 20, plain, clock), plain)
            own = np.arange(len(f["id"]))
            f["inconf"], f["asase"], f["asasn"] = \
                plain.interval_of_sample(own, back)
            out.append({k: (np.array(v) if isinstance(v, np.ndarray)
                            else list(v) if k == "id" else v)
                        for k, v in f.items()})
    # everybody here flies straight: no turned aircraft to compare
    return dict(frames=out, chunk_sim_s=0.1, compares=[
        k for k in SPEC["limits"] if "turned" not in k])


@pytest.fixture(scope="module", params=["sum", "count"])
def evidence(request):
    got = _frames(request.param)
    assert plain.clock_of(f["simt"] for f in got["frames"]) == request.param
    return got


def test_sound_frames_are_correct_and_the_control_is_not(evidence):
    ok, numbers, also = check.decide(SPEC, evidence, seed=5)
    assert ok, numbers
    assert also["interval_pairs"] == 2 and also["flow_judged"] > 1000
    assert also["fleet_changed_share"] > 0
    ok, numbers, _ = check.decide(
        SPEC, check.control_evidence(SPEC, evidence, 5), seed=5)
    assert not ok
    over = {k for k, v in numbers.items() if v["value"] > v["limit"]}
    assert {"interval_reso_gap_p50_ms", "interval_position_gap_p99_m",
            "flow_deleted_inside"} <= over, numbers


def _without(frame, k):
    return {key: (np.delete(v, k) if isinstance(v, np.ndarray)
                  else v[:k] + v[k + 1:] if key == "id" else v)
            for key, v in frame.items()}


def test_a_leaver_kept_is_counted(evidence):
    frames = list(evidence["frames"])
    # nobody is deleted from the seventh frame on
    held = set(frames[6]["id"])
    last = frames[6]
    for k in range(7, len(frames)):
        gone = [i for i, acid in enumerate(last["id"])
                if acid not in set(frames[k]["id"])]
        if gone:
            nst = 6 * (k - 6)
            lat, lon = flow.straight_on(last, np.asarray(gone), nst)
            f = dict(frames[k])
            for key in f:
                if key == "id":
                    f[key] = f[key] + [last["id"][i] for i in gone]
                elif key != "simt":
                    add = lat if key == "lat" else lon if key == "lon" \
                        else last[key][gone]
                    f[key] = np.concatenate([f[key], add])
            frames[k] = f
    assert held <= set(frames[-1]["id"])
    ok, numbers, _ = check.decide(SPEC, dict(evidence, frames=frames), 5)
    assert not ok and numbers["flow_left_not_deleted"]["value"] >= 1
    assert numbers["flow_deleted_inside"]["value"] == 0


def test_an_aircraft_deleted_inside_is_counted(evidence):
    frames = list(evidence["frames"])
    k = int(np.argmin(flow.outside_m(CIRCLE, frames[-1]["lat"],
                                     frames[-1]["lon"])))
    frames[-1] = _without(frames[-1], k)
    ok, numbers, _ = check.decide(SPEC, dict(evidence, frames=frames), 5)
    assert not ok and numbers["flow_deleted_inside"]["value"] == 1
    assert numbers["flow_left_not_deleted"]["value"] == 0


def test_a_callsign_given_out_again_is_counted(evidence):
    frames = list(evidence["frames"])
    gone = next(i for i in frames[0]["id"]
                if i not in set(frames[-2]["id"]) | set(frames[-1]["id"]))
    f = dict(frames[-1])
    f["id"] = f["id"][:-1] + [gone]          # the newest aircraft's name
    frames[-1] = f
    ok, numbers, _ = check.decide(SPEC, dict(evidence, frames=frames), 5)
    assert not ok and numbers["flow_ids_reused"]["value"] == 1


def test_the_edge_is_areafilters_flat_earth_circle():
    # kwikdist: a degree of latitude north of the centre is 60.04 nm
    out = flow.outside_m(CIRCLE, np.float32([52.0, 51.0]),
                         np.float32([4.5, 4.5 + 40.0 / 60.04
                                     / np.cos(np.radians(51.0))]))
    assert out[0] == pytest.approx((60.04 - 40.0) * plain.NM, rel=2e-3)
    assert abs(out[1]) < 60.0
