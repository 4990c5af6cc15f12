"""Mean of a worker histogram's observations inside the window."""
from . import _hist


def read(ctx, params):
    d = _hist.delta(ctx, params["hist"])
    return d[0] / d[1] if d and d[1] > 0 else None
