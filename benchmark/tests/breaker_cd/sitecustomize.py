"""A worker that skips conflict detection: every process that starts
with this directory on PYTHONPATH and ``--sim`` on its command line (the
worker) gets, where the environment says ``BENCHMARK_SKIP_DETECTION=1``,
a dense CD interval that returns the state it was given, so no aircraft
is ever flagged while every frame flies on as it should.

Used by test_ff1000.py only."""
import os
import sys

if "--sim" in getattr(sys, "orig_argv", []) \
        and os.environ.get("BENCHMARK_SKIP_DETECTION") == "1":
    from bluesky_tpu.core import asas

    def update(state, cfg, **kw):
        return state, None

    asas.update = update
