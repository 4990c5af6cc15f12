"""A fleet spread uniformly by area over a disc on the ground, as stack
commands: the source's spawn circle (BlueSky ``plugins/trafgen.py``, 230
nm about 52.6N 5.4E) filled as standing traffic.  ``MCRE`` draws
positions uniformly in degrees over the view, and headings, altitudes
and speeds from the program's generator, which ``SEED`` sets; a view is
a square of degrees.  So the disc is covered by every square view of
``view_deg`` degrees, on the grid about the disc's centre, whose own
centre lies inside the disc (great-circle distance), and the aircraft
are split over the views in proportion to each view's area on the
ground, the cosine of its latitude: the density is uniform per square
mile, the total exactly ``n``, and the edge a staircase of ``view_deg``.
``CRE`` flushes per aircraft and cannot build a fleet of this size.

The fleet is drawn from ``--seed`` as ``box_fleet`` draws it: one
``SEED`` line, derived from it and below 2**31, then the views in order
(south to north, west to east).  Another seed is another fleet.

``views(params)`` returns the views' centres; ``commands(params, seed,
n)`` the lines.
"""
import numpy as np

NM = 1852.0
REARTH = 6371000.0


def views(params):
    """Centres (lat, lon) of the views whose centre lies in the disc."""
    clat, clon = (float(x) for x in params["centre"])
    radius = float(params["radius_nm"]) * NM
    tile = float(params["view_deg"])
    reach = np.degrees(radius / REARTH)
    ni = int(reach / tile) + 1
    nj = int(reach / np.cos(np.radians(min(abs(clat) + reach, 89.0)))
             / tile) + 1
    lat = clat + tile * np.arange(-ni, ni + 1)[:, None]
    lon = clon + tile * np.arange(-nj, nj + 1)[None, :]
    la, lo, la0 = np.radians(lat), np.radians(lon - clon), np.radians(clat)
    hav = np.sin(0.5 * (la - la0)) ** 2 \
        + np.cos(la) * np.cos(la0) * np.sin(0.5 * lo) ** 2
    inside = 2.0 * REARTH * np.arcsin(np.sqrt(hav)) <= radius
    i, j = np.nonzero(inside)
    return [(round(float(lat[a, 0]), 6), round(float(lon[0, b]), 6))
            for a, b in zip(i, j)]


def counts(centres, n):
    """``n`` split over the views by the cosine of their latitude: the
    whole part of each share, the remainder one each to the first
    views."""
    w = np.cos(np.radians([lat for lat, _ in centres]))
    cnt = np.floor(n * w / w.sum()).astype(int)
    cnt[:n - int(cnt.sum())] += 1
    return cnt


def commands(params, seed, n):
    centres = views(params)
    zoom = 2.0 / float(params["view_deg"])
    draw = np.random.default_rng([int(seed), 11]).integers(1, 2**31 - 1)
    cmds = [f"SEED {int(draw)}"]
    for (lat, lon), cnt in zip(centres, counts(centres, n)):
        if cnt:
            cmds += [f"PAN {lat} {lon}", f"ZOOM {zoom}", f"MCRE {cnt}"]
    return cmds
