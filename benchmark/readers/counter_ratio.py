"""Increase of worker counters over the window (``num``, summed) over
the increase of others (``den``, summed), both from the same two METRICS
DUMPs before and after, so that the lag between a dump and the window's
end cancels.  None where the worker lacks one of the counters, or the
denominator did not move."""
from served import metric


def _increase(ctx, names):
    ends = [[metric(ctx[m], name) for name in names] for m in ("m0", "m1")]
    if None in ends[1]:
        return None
    return sum(ends[1]) - sum(v or 0.0 for v in ends[0])


def read(ctx, params):
    num, den = (_increase(ctx, params[k]) for k in ("num", "den"))
    return num / den if num is not None and den else None
