"""Sum of a worker histogram's observations inside the window, per unit
of the window's work (simulated seconds, or pieces)."""
from . import _hist


def read(ctx, params):
    d = _hist.delta(ctx, params["hist"])
    return d[0] / ctx["units"] if d and d[1] > 0 and ctx["units"] else None
