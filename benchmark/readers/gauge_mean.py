"""A worker gauge over the window: the mean of its level at the window's
two ends, from the worker's own METRICS DUMP before and after.  None
where the worker has no such gauge."""
from served import metric


def read(ctx, params):
    ends = [metric(ctx[m], params["gauge"]) for m in ("m0", "m1")]
    return None if None in ends else 0.5 * (ends[0] + ends[1])
