"""Window kind "stream": ``advance`` with the mix's streams consumed by
this client for the whole run, which ``advance`` already does for every
name in ``consumers``; what this kind adds is what a client that reads
ACDATA feels and how its chunks are counted.

``frame_gap_p95_ms``: the 95th percentile of the gaps between arrivals
of ACDATA frames at the client inside the window (``Session.acdata_t``,
stamped when the frame has been received and unpacked).

``chunks_per_unit``: a free-running world retires many chunks between
two 1 Hz SIMINFO frames, so an advance is no chunk here.  Chunks are the
worker's own count (the mix's ``chunk_counter``) between the two METRICS
DUMP echoes that bracket the window, over the simulated time between
those echoes, each read off the two SIMINFO frames before it.
"""
import statistics

from . import advance
from ._common import chunk_count


def _simt_at(siminfo, t):
    """Simulated time at the client's stamp ``t``: the two 1 Hz SIMINFO
    frames before it, carried on at their rate (the frame after the
    closing echo already holds the probe's HOLD)."""
    before = [a for a in siminfo if a[0] <= t][-2:]
    if len(before) < 2 or before[1][0] <= before[0][0]:
        return None
    (t0, s0), (t1, s1) = before
    return s1 + (s1 - s0) / (t1 - t0) * (t - t1)


def run(sv, cfg, mix, size, args, rundir):
    out = advance.run(sv, cfg, mix, size, args, rundir)
    s, q, ctx = sv.s, out["q"], out["ctx"]
    inside = [t for t in s.acdata_t if ctx["t_open"] <= t <= ctx["t_close"]]
    gaps = [1e3 * (b - a) for a, b in zip(inside[:-1], inside[1:])]
    if len(gaps) >= 20:
        q["frame_gap_p95_ms"] = statistics.quantiles(gaps, n=20)[18]
    # one world on one worker: the stamp of that worker's echo
    seen = {text: t for t, text, _ in s.echo}
    at = [_simt_at(s.siminfo, seen[next(iter(m.values()))])
          for m in (ctx["m0"], ctx["m1"])]
    count = [chunk_count(mix, m) for m in (ctx["m0"], ctx["m1"])]
    if None not in at and None not in count and at[1] > at[0]:
        ctx["chunks_per_unit"] = (count[1] - count[0]) / (at[1] - at[0])
    out["note"] += (f"; {len(inside)} ACDATA frames, "
                    f"{ctx['chunks_per_unit']:.4f} chunks a simulated second")
    return out
