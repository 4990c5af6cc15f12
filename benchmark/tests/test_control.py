"""The control at a size a test run can hold: the plain reference,
computed in bfloat16 and put in the program's place, has to come out as
not correct; the same reference in float32 in that place is correct."""
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import check                                    # noqa: E402
from checks import frames as fr                 # noqa: E402
from checks import pieces as pc                 # noqa: E402
from generators import wall_batch               # noqa: E402
from reference import plain                     # noqa: E402


def _fleet(n, seed):
    rng = np.random.default_rng(seed)
    f = dict(lat=rng.uniform(50.0, 52.0, n), lon=rng.uniform(3.0, 6.0, n),
             alt=rng.integers(100, 120, n) * 100 * plain.FT,
             trk=rng.integers(1, 360, n).astype(float),
             gs=rng.uniform(130.0, 240.0, n), vs=np.zeros(n))
    return {k: np.asarray(v, np.float32) for k, v in f.items()}


CHUNK_STEPS = 1000


def _frames(n, seed):
    """Frame A, and frame B one 1000-step chunk later as a sound program
    would send it: most aircraft flown straight on, step by step, every
    fifth turned and slowed on the way (flown on the mean of the two
    velocities, which is what the check holds such aircraft to), flags
    and resolution vectors from a detection some steps before B."""
    a = _fleet(n, seed)
    own = np.arange(n)
    a.update(inconf=np.zeros(n, bool), asase=np.zeros(n, np.float32),
             asasn=np.zeros(n, np.float32), simt=100.0,
             id=[f"AC{k:04d}" for k in range(n)])
    b = dict(a, simt=150.0)
    turned = own % 5 == 0
    b["trk"] = np.where(turned, (a["trk"] + 20) % 360, a["trk"]) \
        .astype(np.float32)
    b["gs"] = np.where(turned, a["gs"] - 5, a["gs"]).astype(np.float32)
    b["lat"], b["lon"] = plain.fly(a, b, own, own, CHUNK_STEPS)
    # detected by the clock the two frames carry
    back = fr.flown_back(b, fr.steps_since_detection(
        b["simt"], CHUNK_STEPS, plain,
        plain.clock_of([a["simt"], b["simt"]])), plain)
    b["inconf"], b["asase"], b["asasn"] = plain.interval_of_sample(own, back)
    return [a, b]


SPEC_FRAMES = dict(kind="frames", cd_interval_s=1.0, sample=256,
                   conflict_sample=128,
                   limits=dict(chunk_flag_mismatch_share=0.01,
                               chunk_steady_flag_mismatch_share=0.01,
                               chunk_position_gap_p99_m=1.0,
                               chunk_reso_gap_p50_ms=0.5,
                               chunk_turned_position_gap_p90_m=5.0))


def test_frames_control_is_not_correct():
    ev = dict(frames=_frames(600, 3), compares=list(SPEC_FRAMES["limits"]),
              chunk_sim_s=50.0)
    ok, numbers, also = check.decide(SPEC_FRAMES, ev, seed=5)
    assert ok, numbers
    assert also["chunk_reso_compared"] > 10
    ok, numbers, _ = check.decide(
        SPEC_FRAMES, check.control_evidence(SPEC_FRAMES, ev, 5), seed=5)
    assert not ok, numbers
    over = {k for k, v in numbers.items() if v["value"] > v["limit"]}
    assert {"chunk_reso_gap_p50_ms", "chunk_position_gap_p99_m",
            "chunk_turned_position_gap_p90_m"} <= over, numbers


def test_evidence_is_read_back_as_it_was_saved(tmp_path):
    ev = dict(frames=_frames(200, 4), compares=list(SPEC_FRAMES["limits"]),
              chunk_sim_s=50.0)
    check.save_evidence(tmp_path / "evidence.npz", ev)
    back = check.load_evidence(tmp_path / "evidence.npz")
    assert check.decide(SPEC_FRAMES, back, 5)[1] \
        == check.decide(SPEC_FRAMES, ev, 5)[1]


SPEC_PIECES = dict(kind="pieces", sample=2, follow_marks=1,
                   limits=dict(pieces_not_once=0, mark_states_missing=0,
                               mark0_median_piece_worst_gap_m=30.0,
                               mark0_position_gap_p50_m=30.0))
PARAMS = dict(id_seed=1, hdg_noise_deg=3.0, spd_noise_kts=10.0,
              own_cas_kts=250.0, wall_cas_kts=200.0, marks_s=[20.0, 30.0],
              echo_aircraft=[[0, 1, 4, 7, 10, 13, 16, 19], [0]],
              setup_commands=[], stream=2)
IDS = ["OWNSHIP"] + [f"AB{k:05d}" for k in range(20)]


def test_pieces_control_is_not_correct():
    pieces = wall_batch.pieces(PARAMS, 11, 3, "P", IDS)
    states = {}
    for p in pieces:           # a sound program: the float32 reference
        for m, (lat, lon) in enumerate(
                pc.step_pieces([p], plain.Precision(), 2, plain)):
            states[(p["name"], m)] = {
                a["id"]: (round(float(lat[i]), 4), round(float(lon[i]), 4))
                for i, a in enumerate(p["aircraft"])}
    ev = dict(pieces=pieces, states=states, duplicates=0)
    ok, numbers, _ = check.decide(SPEC_PIECES, ev, seed=5)
    assert ok, numbers
    ok, numbers, _ = check.decide(
        SPEC_PIECES, check.control_evidence(SPEC_PIECES, ev, 5), seed=5)
    assert not ok, numbers
