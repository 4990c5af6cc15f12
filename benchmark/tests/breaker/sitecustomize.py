"""Breaks the timed path underneath a rehearsal run: every process that
starts with this directory on PYTHONPATH and ``--sim`` on its command
line (the worker) gets a position update that returns its input, a step
that leaves the fleet where it was.  Used by test_broken_path.py only."""
import os
import sys

if os.environ.get("BENCHMARK_BREAK_STEP") == "1" \
        and "--sim" in getattr(sys, "orig_argv", []):
    from bluesky_tpu.core import kinematics

    def update_position(ac, pilot, simdt):
        return ac

    kinematics.update_position = update_position
