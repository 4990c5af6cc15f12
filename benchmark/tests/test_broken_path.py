"""A whole run with the timed path broken underneath: the harness's look
for a chip is skipped (``--rehearsal`` names the CPU), the rest of the
run is the run, and ``correct`` has to come out false, by the number
that is there to catch the fault."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def run(cell, extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra_env)
    env["PYTHONPATH"] = os.path.join(HERE, "breaker") + os.pathsep \
        + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2147483999",
         "--seconds", "8", "--trace", "0", "--rehearsal"],
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def sound():
    got = {}

    def of(cell):
        if cell not in got:
            got[cell] = run(cell, {})
        return got[cell]
    return of


@pytest.mark.parametrize("cell,fault,number", [
    ("wallmc-farm", "BENCHMARK_BREAK_STEP",
     "mark0_median_piece_worst_gap_m"),
    ("eu100k-op", "BENCHMARK_BREAK_STEP", "interval_position_gap_p99_m"),
    ("eu100k-op", "BENCHMARK_BREAK_FLAGS", "interval_flag_mismatch_share"),
])
def test_a_broken_timed_path_is_not_correct(sound, cell, fault, number):
    """A step that leaves the fleet where it was; an answer (the conflict
    flags of a frame) altered where it is produced."""
    assert sound(cell)["correct"] is True, sound(cell)
    broken = run(cell, {fault: "1"})
    assert broken["correct"] is False, broken
    gap = broken["compared"][number]
    assert gap["value"] > gap["limit"]
