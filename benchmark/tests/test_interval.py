"""Check kind "interval" at a size a test run can hold: frames a few
20-step chunks apart from a sound program are correct and the bfloat16
reference in its place is not; a pair of frames whose fleets differ by
created and deleted ids is still compared, over the ids both hold."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import check                                    # noqa: E402
from checks import frames as fr                 # noqa: E402
from checks import interval                     # noqa: E402
from reference import plain                     # noqa: E402

SPEC = dict(kind="interval", cd_interval_s=1.0, sample=256,
            conflict_sample=128,
            limits=dict(interval_flag_mismatch_share=0.01,
                        interval_reso_gap_p50_ms=0.5,
                        interval_position_gap_p99_m=1.0,
                        interval_turned_position_gap_p90_m=5.0))
CD_STEPS = 20


def _first(n, seed, simt):
    rng = np.random.default_rng(seed)
    f = dict(lat=rng.uniform(50.0, 52.0, n), lon=rng.uniform(3.0, 6.0, n),
             alt=rng.integers(100, 120, n) * 100 * plain.FT,
             trk=rng.integers(1, 360, n).astype(float),
             gs=rng.uniform(130.0, 240.0, n), vs=np.zeros(n))
    f = {k: np.asarray(v, np.float32) for k, v in f.items()}
    f.update(inconf=np.zeros(n, bool), asase=np.zeros(n, np.float32),
             asasn=np.zeros(n, np.float32), simt=simt,
             id=[f"AC{k:04d}" for k in range(n)])
    return f


def _next(a, chunks):
    """The frame ``chunks`` 20-step chunks after ``a`` as a sound program
    would send it: every fifth aircraft turned and slowed on the way
    (flown on the mean of the two velocities), the others straight on,
    step by step; flags and vectors from its last detection."""
    n = len(a["id"])
    own = np.arange(n)
    b = dict(a, simt=a["simt"] + chunks * CD_STEPS * plain.SIMDT)
    turned = own % 5 == 0
    b["trk"] = np.where(turned, (a["trk"] + 4) % 360, a["trk"]) \
        .astype(np.float32)
    b["gs"] = np.where(turned, a["gs"] - 1, a["gs"]).astype(np.float32)
    b["lat"], b["lon"] = plain.fly(a, b, own, own, chunks * CD_STEPS)
    # detected by the clock the two frames carry
    back = fr.flown_back(b, fr.steps_since_detection(
        b["simt"], CD_STEPS, plain,
        plain.clock_of([a["simt"], b["simt"]])), plain)
    b["inconf"], b["asase"], b["asasn"] = plain.interval_of_sample(own, back)
    return b


def _evidence(n=600, seed=3):
    a = _first(n, seed, 1100.0)          # past 1024 s, as a run's probe
    b = _next(a, 4)
    return dict(frames=[a, b, _next(b, 5)], compares=list(SPEC["limits"]),
                chunk_sim_s=1.0, check="interval")


def test_sound_frames_are_correct_and_the_control_is_not():
    ev = _evidence()
    ok, numbers, also = check.decide(SPEC, ev, seed=5)
    assert ok, numbers
    assert also["interval_pairs"] == 2 and also["fleet_changed_share"] == 0
    assert also["interval_reso_compared"] > 10
    ok, numbers, _ = check.decide(
        SPEC, check.control_evidence(SPEC, ev, 5), seed=5)
    assert not ok, numbers
    over = {k for k, v in numbers.items() if v["value"] > v["limit"]}
    assert {"interval_reso_gap_p50_ms", "interval_position_gap_p99_m",
            "interval_turned_position_gap_p90_m"} <= over, numbers


def test_detection_lies_at_most_one_interval_back_whatever_the_chunk():
    ev = _evidence()
    ev["chunk_sim_s"] = 0.5      # 10-step chunks: still up to 20 back
    for a, b, own, ob, nst, _, kb in interval.pairs(SPEC, ev, 5, plain):
        assert nst in (80, 100)
    assert check.decide(SPEC, ev, seed=5)[0]


def _changed(ev, gone, new, rng):
    """Frame B without the aircraft ``gone``, with ``new`` created ones,
    in another order."""
    a, b = ev["frames"][:2]
    n = len(b["id"])
    keep = np.setdiff1d(np.arange(n), gone)
    extra = _first(new, 99, b["simt"])
    order = rng.permutation(len(keep) + new)
    out = {}
    for key in (k for k in b if k != "simt"):
        if key == "id":
            ids = [b["id"][k] for k in keep] \
                + [f"NEW{k:03d}" for k in range(new)]
            out[key] = [ids[k] for k in order]
        else:
            out[key] = np.concatenate([np.asarray(b[key])[keep],
                                       np.asarray(extra[key])])[order]
    out["simt"] = b["simt"]
    return dict(ev, frames=[a, out])


@pytest.mark.parametrize("gone,new", [(0, 0), (40, 0), (0, 25), (40, 25)])
def test_a_pair_whose_fleets_differ_is_used_over_the_ids_both_hold(gone,
                                                                   new):
    rng = np.random.default_rng(11)
    ev = _evidence()
    n = len(ev["frames"][0]["id"])
    ev = _changed(ev, rng.choice(n, gone, replace=False), new, rng)
    got = list(interval.pairs(SPEC, ev, 5, plain))
    assert len(got) == 1
    a, b, own, ob, _, _, _ = got[0]
    assert [a["id"][i] for i in own] == [b["id"][k] for k in ob]
    ok, numbers, also = check.decide(SPEC, ev, seed=5)
    assert also["fleet_changed_share"] == (gone + new) / (n + new)
    # the aircraft both frames hold flew as a sound program flies them
    assert numbers["interval_position_gap_p99_m"]["value"] <= 1.0
    assert numbers["interval_turned_position_gap_p90_m"]["value"] <= 5.0


def test_frames_skips_such_a_pair_and_interval_does_not():
    rng = np.random.default_rng(12)
    ev = _changed(_evidence(), rng.choice(600, 10, replace=False), 0, rng)
    assert list(fr.pairs(SPEC, ev, 5, plain)) == []
    assert len(list(interval.pairs(SPEC, ev, 5, plain))) == 1


def test_evidence_names_its_check_when_read_back(tmp_path):
    ev = _evidence(200, 4)
    check.save_evidence(tmp_path / "evidence.npz", ev)
    back = check.load_evidence(tmp_path / "evidence.npz")
    assert back["check"] == "interval"
    cfg = {"check": {"kind": "frames"}, "checks": {"interval": {
        k: v for k, v in SPEC.items() if k != "kind"}}}
    spec = check.spec_of(cfg, back["check"])
    assert spec == SPEC and check.spec_of(cfg) == cfg["check"]
    assert check.decide(spec, back, 5)[1] == check.decide(SPEC, ev, 5)[1]
