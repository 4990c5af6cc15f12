"""A fleet as BlueSky's ``scenario/1000.scn`` makes one: one ``CRE``
line an aircraft, ``CRE acid,type,lat,lon,hdg,alt,spd``, every value
drawn from ``--seed``.  The source's file is not in the repository, so
the lines are not its lines: the geometry is an eastbound oceanic flow
(``params``: the box, the band of headings, the flight levels, the Mach
numbers, the types), because straight flight with the clock turned up
disperses a box of random headings within one window (configs/
ff1000.json, ``assumed``).

``commands(params, seed, n)`` returns exactly ``n`` lines with ``n``
distinct callsigns; the same seed gives the same lines.
"""
import numpy as np

#: the generator's own stream of ``--seed``
STREAM = 1000


def fleet(params, seed, n):
    """The drawn values, a dict of [n] arrays (and the callsigns): what
    ``commands`` writes, for whoever wants the numbers."""
    rng = np.random.default_rng([int(seed), STREAM])
    lat0, lat1, lon0, lon1 = (float(x) for x in params["box"])
    hdg0, hdg1 = (float(x) for x in params["heading_deg"])
    fl0, fl1 = (int(x) for x in params["flight_level"])
    m0, m1 = (float(x) for x in params["mach"])
    types = list(params["types"])
    return dict(
        acid=[f"FF{k:04d}" for k in range(n)],
        type=[types[k] for k in rng.integers(0, len(types), n)],
        lat=rng.uniform(lat0, lat1, n), lon=rng.uniform(lon0, lon1, n),
        hdg=rng.uniform(hdg0, hdg1, n),
        # a whole thousand feet: FL300, FL310, ... FL400
        fl=10 * rng.integers(fl0 // 10, fl1 // 10 + 1, n),
        mach=rng.uniform(m0, m1, n))


def commands(params, seed, n):
    f = fleet(params, seed, n)
    return [f"CRE {f['acid'][k]},{f['type'][k]},{f['lat'][k]:.5f},"
            f"{f['lon'][k]:.5f},{f['hdg'][k]:.2f},FL{f['fl'][k]:d},"
            f"{f['mach'][k]:.4f}" for k in range(n)]
