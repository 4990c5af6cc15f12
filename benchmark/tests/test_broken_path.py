"""A whole run with the timed path broken underneath: the harness's look
for a chip is skipped (``--rehearsal`` names the CPU), the rest of the
run is the run, and ``correct`` has to come out false."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def run(extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra_env)
    env["PYTHONPATH"] = os.path.join(HERE, "breaker") + os.pathsep \
        + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "wallmc-farm", "--seed", "2147483999",
         "--seconds", "8", "--trace", "0", "--rehearsal"],
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_step_that_leaves_the_fleet_where_it_was_is_not_correct():
    sound = run({})
    assert sound["correct"] is True, sound
    broken = run({"BENCHMARK_BREAK_STEP": "1"})
    assert broken["correct"] is False, broken
    gap = broken["compared"]["mark0_median_piece_worst_gap_m"]
    assert gap["value"] > gap["limit"]
