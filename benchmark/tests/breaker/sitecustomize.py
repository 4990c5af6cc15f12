"""Breaks the timed path underneath a rehearsal run: every process that
starts with this directory on PYTHONPATH and ``--sim`` on its command
line (the worker) gets, by the environment's word,

``BENCHMARK_BREAK_STEP=1``: a position update that returns its input, a
  step that leaves the fleet where it was;
``BENCHMARK_BREAK_FLAGS=1``: an answer altered where it is produced: every
  ACDATA frame leaves the worker with its conflict flags inverted.

Used by test_broken_path.py only."""
import os
import sys

if "--sim" in getattr(sys, "orig_argv", []):
    if os.environ.get("BENCHMARK_BREAK_STEP") == "1":
        from bluesky_tpu.core import kinematics

        def update_position(ac, pilot, simdt):
            return ac

        kinematics.update_position = update_position

    if os.environ.get("BENCHMARK_BREAK_FLAGS") == "1":
        import numpy as np
        from bluesky_tpu.network import node

        _send_stream = node.Node.send_stream

        def send_stream(self, name, data):
            if name == b"ACDATA" and "inconf" in data:
                data = dict(data, inconf=~np.asarray(data["inconf"], bool))
            return _send_stream(self, name, data)

        node.Node.send_stream = send_stream
