"""The control of ``circle100k-ff`` at rehearsal size, and a way to read
it at full size.

On a saturated disc nobody keeps track, speed and level for a 1000-step
chunk, so the two numbers ``traffic/ff.json`` compares over steady
ownships do not exist and ``decide`` would read them as not correct; the
mix ``ff-sat`` compares the other three.  The tests hold that, at 1,500
aircraft on the disc of the configuration's ``rehearsal_size``: a sound
program's frames are correct under the configuration's own limits and
``ff-sat``'s ``compares``, the bfloat16 reference in its place is not,
and under ``ff.json``'s five even the sound frames are not.

``check.control_evidence`` recomputes, in bfloat16, every ownship frame B
flags, against every aircraft, in one array of pairs: 3,000 ownships in
``eu100k-ff``, all 100,000 here, which no host holds.  ``control_in_pieces``
is the same control with the ownships taken a few thousand at a time (an
ownship's flag and vector depend on its own pairs alone, so the numbers
are equal: tested below), and the command line reads the evidence of
finished runs with it, off the chip:

    python3 benchmark/tests/test_control_circle.py \
        benchmark/configs/circle100k.json <evidence.npz>...

prints, for each run (its seed read from ``seed<n>_`` in the path), the
program's numbers and the control's.
"""
import json
import os
import re
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import check                                    # noqa: E402
from checks import frames as fr                 # noqa: E402
from generators import circle_fleet             # noqa: E402
from reference import plain                     # noqa: E402


def control_in_pieces(spec, evidence, seed, rows=2048):
    """``check.control_evidence`` for kind "frames", ``rows`` ownships
    at a time."""
    q = plain.Precision("bfloat16")
    ev = dict(evidence)
    frames = [dict(f) for f in evidence["frames"]]
    for a, b, own, ob, nst, _, kb in fr.pairs(spec, evidence, seed, plain):
        out = frames[kb]
        every = np.union1d(ob, np.flatnonzero(b["inconf"]))
        parts = [plain.interval_of_sample(every[k:k + rows], b, q)
                 for k in range(0, len(every), rows)]
        inconf, ase, asn = (np.concatenate(p) for p in zip(*parts))
        lat, lon = plain.fly(a, b, own, ob, nst, q)
        for key, val, at in (("inconf", inconf, every),
                             ("asase", ase, every), ("asasn", asn, every),
                             ("lat", lat, ob), ("lon", lon, ob)):
            out[key] = np.array(b[key])
            out[key][at] = val
    ev["frames"] = frames
    return ev


# ---------------------------------------------------------------- tests
def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _load("configs", "circle100k.json")
SAT = _load("traffic", "ff-sat.json")["probe"]["compares"]
FF = _load("traffic", "ff.json")["probe"]["compares"]
CHUNK_STEPS = 1000


def _disc(n, seed):
    """``n`` aircraft uniform by area over the rehearsal's disc, with
    MCRE's draws of heading, altitude and speed."""
    rng = np.random.default_rng(seed)
    params = dict(CFG["generator"]["params"],
                  **CFG["rehearsal_size"]["params"])
    centres = circle_fleet.views(params)
    cnt = circle_fleet.counts(centres, n)
    half = 0.5 * params["view_deg"]
    lat = np.concatenate([c[0] + rng.uniform(-half, half, k)
                          for c, k in zip(centres, cnt)])
    lon = np.concatenate([c[1] + rng.uniform(-half, half, k)
                          for c, k in zip(centres, cnt)])
    f = dict(lat=lat, lon=lon,
             alt=rng.integers(2000, 39000, n) * plain.FT,
             trk=rng.integers(1, 360, n).astype(float),
             gs=rng.integers(250, 450, n) * plain.KTS, vs=np.zeros(n))
    return {k: np.asarray(v, np.float32) for k, v in f.items()}


def _frames(n, seed):
    """Frame A, and frame B one chunk later as a sound program would
    send it from a saturated fleet: every aircraft turned and changed
    speed on the way (flown on the mean of the two velocities, which is
    what the check holds such aircraft to), flags and resolution vectors
    from a detection some steps before B."""
    rng = np.random.default_rng([seed, 1])
    a = _disc(n, seed)
    own = np.arange(n)
    a.update(inconf=np.ones(n, bool), asase=np.zeros(n, np.float32),
             asasn=np.zeros(n, np.float32), simt=150.0,
             id=[f"AC{k:04d}" for k in range(n)])
    b = dict(a, simt=200.0)
    b["trk"] = ((a["trk"] + rng.uniform(5.0, 40.0, n)) % 360) \
        .astype(np.float32)
    b["gs"] = (a["gs"] - rng.uniform(1.0, 10.0, n)).astype(np.float32)
    b["lat"], b["lon"] = plain.fly(a, b, own, own, CHUNK_STEPS)
    # detected by the clock the two frames carry
    back = fr.flown_back(b, fr.steps_since_detection(
        b["simt"], CHUNK_STEPS, plain,
        plain.clock_of([a["simt"], b["simt"]])), plain)
    b["inconf"], b["asase"], b["asasn"] = plain.interval_of_sample(own, back)
    return [a, b]


def _evidence(compares):
    return dict(frames=_frames(int(CFG["rehearsal_size"]["aircraft"]), 3),
                compares=compares, chunk_sim_s=50.0)


def test_the_configuration_limits_what_the_mix_compares():
    assert set(SAT) == set(CFG["check"]["limits"])
    assert set(SAT) < set(FF)


def test_sound_frames_are_correct_and_the_control_is_not():
    spec, ev = CFG["check"], _evidence(SAT)
    ok, numbers, also = check.decide(spec, ev, seed=5)
    assert ok, numbers
    # the disc is saturated: most of the sampled ownships are flagged,
    # and none of them is steady
    assert also["chunk_unsteady_share"] == 1.0
    assert also["chunk_reso_compared"] > 100
    ok, numbers, _ = check.decide(
        spec, check.control_evidence(spec, ev, 5), seed=5)
    assert not ok, numbers
    over = {k for k, v in numbers.items() if v["value"] > v["limit"]}
    assert {"chunk_reso_gap_p50_ms",
            "chunk_turned_position_gap_p90_m"} <= over, numbers


def test_the_five_numbers_of_ff_cannot_be_read_here():
    spec = dict(CFG["check"], limits=_load(
        "configs", "eu100k.json")["check"]["limits"])
    ok, numbers, _ = check.decide(spec, _evidence(FF), seed=5)
    assert not ok
    assert {k for k, v in numbers.items() if v["value"] is None} == {
        "chunk_position_gap_p99_m", "chunk_steady_flag_mismatch_share"}


def test_the_control_in_pieces_is_the_control():
    spec, ev = CFG["check"], _evidence(SAT)
    whole = check.decide(spec, check.control_evidence(spec, ev, 5), 5)
    pieces = check.decide(spec, control_in_pieces(spec, ev, 5, rows=200), 5)
    assert whole == pieces


if __name__ == "__main__":
    spec_ = _load(os.path.abspath(sys.argv[1]))["check"]
    for path_ in sys.argv[2:]:
        found = re.search(r"seed(\d+)_", path_)
        seed_ = int(found[1]) if found else 0
        ev_ = check.load_evidence(path_)
        for tag, e in (("program", ev_),
                       ("control", control_in_pieces(spec_, ev_, seed_))):
            ok_, numbers_, also_ = check.decide(spec_, e, seed_)
            print(path_, tag, f"correct={ok_}", json.dumps(
                {k: v["value"] for k, v in numbers_.items()} | also_),
                flush=True)
