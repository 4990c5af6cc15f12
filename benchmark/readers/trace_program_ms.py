"""Device time of the chunk programs (those whose name contains
``program``) in the PROFILE DEVICE trace [ms], per unit of work
(simulated second, or piece)."""
from . import _trace


def read(ctx, params):
    got = _trace.traced(ctx, params["program"])
    return 1e3 * got[0] / got[1] if got else None
