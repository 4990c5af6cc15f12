"""Device time of the events that match ``patterns`` (the Mosaic
kernels) in the PROFILE DEVICE trace [ms], per CD interval of the
traced chunk programs."""
from . import _trace


def read(ctx, params):
    got = _trace.traced(ctx, params["program"])
    if not got or "cd_interval_s" not in ctx:
        return None
    secs, n = ctx["trace"].time_of(params["patterns"])
    if n == 0:
        return None
    return 1e3 * secs / (got[1] / ctx["cd_interval_s"])
