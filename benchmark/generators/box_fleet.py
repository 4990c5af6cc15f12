"""A fleet spread uniformly over a latitude/longitude box, as stack
commands: ``MCRE`` draws positions over the view, headings, altitudes
and speeds from the program's generator, which ``SEED`` sets, and a view
is a square of degrees, so the box is covered by square views with the
aircraft split evenly over them (after ``chip_smoke.py``, PR 21).
``CRE`` flushes per aircraft and cannot build a fleet of this size.

The fleet is drawn from ``--seed``: one ``SEED`` line, derived from it
and below 2**31 (the program makes a 32-bit key of it), then the views
in order.  Another seed is another 100,000 aircraft.

``commands(params, seed, n)`` returns the lines.
"""
import numpy as np


def commands(params, seed, n):
    lat0, lat1, lon0, lon1 = (float(x) for x in params["box"])
    tile = float(params["view_deg"])
    nlat = int(round((lat1 - lat0) / tile))
    nlon = int(round((lon1 - lon0) / tile))
    ntiles = nlat * nlon
    draw = np.random.default_rng([int(seed), 11]).integers(1, 2**31 - 1)
    cmds = [f"SEED {int(draw)}"]
    for k in range(ntiles):
        i, j = divmod(k, nlon)
        cnt = n // ntiles + (1 if k < n % ntiles else 0)
        if cnt:
            cmds += [f"PAN {lat0 + (i + 0.5) * tile} "
                     f"{lon0 + (j + 0.5) * tile}",
                     f"ZOOM {2.0 / tile}", f"MCRE {cnt}"]
    return cmds
