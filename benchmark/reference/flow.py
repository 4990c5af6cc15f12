"""The plain reference of a deployment whose fleet turns over: edge
sources spawn into a circle and an experiment area deletes whatever
leaves it (BlueSky ``plugins/trafgen.py`` with ``plugins/area.py``).

For the aircraft two frames both hold it is ``plain``: the same step,
detection and MVP (``interval_of_sample``, ``fly``, ``dead_reckon``,
``Precision``), imported and not copied, since a later PR can edit
neither file.  What it adds is the circle: how far outside it an
aircraft is (BlueSky ``tools/geo.py`` ``kwikdist``, the flat-earth
distance ``tools/areafilter.py`` tests a CIRCLE with), and where frame
A's aircraft are some steps later flying straight on, which says who
leaves the circle before frame B.  It imports nothing of the program.
"""
import numpy as np

from .plain import (F, NM, REARTH, SIMDT, Precision,          # noqa: F401
                    clock_of, dead_reckon, fly, interval_of_sample,
                    steps_at)

#: the most an aircraft's path bends away from straight flight [m/s2]:
#: a 25 degree bank (g tan 25 = 4.6) and the airframe's 1.5 to 2 along
#: the path
BEND = 6.5


def outside_m(circle, lat, lon, q=None):
    """How far outside the circle ``(lat, lon, radius_nm)`` each position
    is [m], negative inside: ``kwikdist`` from the centre less the
    radius."""
    q = q or Precision()
    clat, clon, radius = (F(x) for x in circle)
    lat, lon = q(lat), q(lon)
    dlat = np.radians(lat - clat)
    dlon = np.radians((lon - clon + F(180.0)) % F(360.0) - F(180.0))
    cavelat = np.cos(np.radians(F(0.5) * (lat + clat)))
    angle = np.sqrt(dlat * dlat + dlon * dlon * cavelat * cavelat)
    return q(F(REARTH) * angle - radius * F(NM))


def straight_on(frame, own, nst, q=None):
    """Where the aircraft ``own`` of a frame are ``nst`` steps later on
    the frame's own velocity, in simdt steps of BlueSky's position
    update: (lat, lon)."""
    return fly(frame, frame, own, own, nst, q)


def leaves(frame, own, nst, circle, q=None):
    """For the aircraft ``own`` of a frame: metres outside the circle
    now, and ``nst`` steps later flying straight on."""
    lat, lon = straight_on(frame, own, nst, q)
    return (outside_m(circle, frame["lat"][own], frame["lon"][own], q),
            outside_m(circle, lat, lon, q))


def slack_m(frame, own, nst):
    """What "inside" and "outside" allow before the reference says
    either of an aircraft ``nst`` steps on: one step's flight, and what
    a path can bend away from the straight one in that time."""
    t = F(nst * SIMDT)
    return frame["gs"][own] * F(SIMDT) + F(0.5 * BEND) * t * t + F(1.0)
