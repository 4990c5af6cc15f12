"""A frame's detection step from the clock the frame carries: frames
written by a float32 sum of 0.05 s steps (today's program) and by a step
count times 0.05 s rounded once give the steps since the last detection
that a float64 count of steps gives, at ``simt`` near 900, 5,000 and
40,000 s; the reference's walk of the sum is the sum, step for step; and
the stored evidence of one toy run of each kind of check reads the
numbers it read before the checks counted steps (``testdata/
evidence_*.npz``, ``evidence.expected.json``: written by the parent's
``check.py`` from the parent's rehearsal runs)."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import check                                         # noqa: E402
from checks import frames as fr                      # noqa: E402
from reference import plain                          # noqa: E402

F = np.float32
NEAR = (900.0, 5000.0, 40000.0)
CHUNKS = (20, 1000)          # a CD interval (op) and a fast-forward chunk


@pytest.fixture(scope="module")
def summed():
    """The float32 sum itself, step by step, to past 40,000 s: the clock
    after each step, and for each step the step of the last detection
    before it (the first step that began at or after each whole second,
    ``simt >= asas_tnext`` with ``asas_tnext`` counting up by one)."""
    n = int(41000 / plain.SIMDT)
    clock = np.zeros(n + 1)
    detected = np.zeros(n + 1, int)      # at step k's start: last detection
    s, dt, tnext, last = F(0.0), F(plain.SIMDT), F(0.0), 0
    for k in range(n):
        if s >= tnext:                   # step k detects, then steps
            last, tnext = k, F(tnext + F(1.0))
        detected[k + 1] = last
        s = F(s + dt)
        clock[k + 1] = float(s)
    return clock, detected


def edges_near(t, chunk):
    """Chunk edges (whole chunks of steps from nought) around ``t``: a
    minute of them, by a float64 count of steps."""
    k0 = int(t / plain.SIMDT) // chunk * chunk
    return [k0 + j * chunk for j in range(max(1, int(60 / plain.SIMDT)
                                              // chunk))]


def test_the_walk_of_the_sum_is_the_sum(summed):
    clock, _ = summed
    rng = np.random.default_rng(5)
    ks = list(range(64)) + [int(k) for k in rng.integers(0, len(clock) - 1,
                                                         400)]
    for e in range(16):                  # either side of every binade's top
        at = int(np.searchsorted(clock, 2.0 ** e))
        ks += range(max(0, at - 3), min(len(clock) - 1, at + 4))
    for k in ks:
        assert plain.sum_clock(clock[k]) == (k, clock[k])
        between = float(F(0.5 * (clock[k] + clock[k + 1])))
        if clock[k] < between < clock[k + 1]:
            assert plain.sum_clock(between) == (k, clock[k])


@pytest.mark.parametrize("near", NEAR)
@pytest.mark.parametrize("chunk", CHUNKS)
def test_detection_step_of_a_summed_clock(summed, near, chunk):
    clock, detected = summed
    for k in edges_near(near, chunk):
        simts = [clock[k - chunk], clock[k]]
        assert plain.clock_of(simts) == "sum"
        assert plain.steps_at(clock[k], "sum") == k
        want = min(chunk, k - detected[k])
        assert fr.steps_since_detection(clock[k], chunk, plain, "sum") \
            == want, (k, clock[k])


@pytest.mark.parametrize("near", NEAR)
@pytest.mark.parametrize("chunk", CHUNKS)
def test_detection_step_of_a_counted_clock(near, chunk):
    per_s = int(round(1.0 / plain.SIMDT))
    offs = (0, 1, 7, per_s - 1)          # edges on and off a whole second
    for k in [e + o for e in edges_near(near, chunk) for o in offs]:
        simt = float(F(k * plain.SIMDT))
        assert plain.steps_at(simt, "count") == k
        # the last step began at k - 1; the detection before it was at
        # the first step of that step's whole second
        want = min(chunk, k - (k - 1) // per_s * per_s)
        assert fr.steps_since_detection(simt, chunk, plain, "count") \
            == want, (k, simt)
    # a counted clock is told from the sum wherever its frames are not
    # all values of the sum, which they are only near nought
    ks = edges_near(near, chunk)[:4]
    assert plain.clock_of(float(F(k * plain.SIMDT)) for k in ks) == "count"


def test_frames_that_both_clocks_could_have_written_are_the_sums():
    """Near nought the two clocks pass through the same values: such
    frames are read as today's program wrote them, bit for bit."""
    both = [float(F(k * plain.SIMDT)) for k in range(1, 9)
            if plain.sum_clock(float(F(k * plain.SIMDT)))
            == (k, float(F(k * plain.SIMDT)))]
    assert both and plain.clock_of(both) == "sum"
    assert plain.clock_of(both + [float(F(18001 * plain.SIMDT))]) == "count"


def test_the_pieces_reference_steps_the_sum():
    """A piece's echoes carry no time, so ``checks/pieces.py`` cannot
    take the clock from them: ``plain.step`` keeps today's float32 sum,
    on which 400 steps read under 20 s and a mark at 20 s runs one step
    later than a counted clock would run it (PERF.md section 7)."""
    st = plain.new_fleet([52.0], [4.0], [90.0], [6000.0], [150.0])
    for _ in range(400):
        plain.step(st, plain.Precision())
    assert st["simt"] < F(20.0) and plain.steps_at(st["simt"], "sum") == 400
    assert plain.counted(20.0) == 400


EXPECTED = json.load(open(os.path.join(BENCH, "testdata",
                                       "evidence.expected.json")))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_stored_evidence_reads_what_it_read(name):
    want = EXPECTED[name]
    with open(os.path.join(BENCH, "configs", want["config"] + ".json")) as f:
        cfg = json.load(f)
    ev = check.load_evidence(os.path.join(BENCH, "testdata", name))
    spec = check.spec_of(cfg, ev.get("check"))
    ok, numbers, also = check.decide(spec, ev, want["seed"])
    got = {k: v["value"] for k, v in numbers.items()} | also
    assert ok is want["correct"] and got == want["numbers"]
