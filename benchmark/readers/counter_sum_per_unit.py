"""Increase of worker counters over the window, summed, per unit of the
window's work (simulated seconds, or pieces): from the worker's own
METRICS DUMP before and after.  None where the worker has no such
counter."""
from served import metric


def read(ctx, params):
    ends = [[metric(ctx[m], name) for name in params["counters"]]
            for m in ("m0", "m1")]
    if None in ends[1] or not ctx["units"]:
        return None
    return (sum(ends[1]) - sum(v or 0.0 for v in ends[0])) / ctx["units"]
