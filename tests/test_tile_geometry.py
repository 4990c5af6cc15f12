"""The pair geometry every visited tile evaluates, held to float64
(ISSUE 30).

``cd_tiled.tile_geometry`` and its radius (now ``_dwgs84_from_trig``,
the diameter; ``_rwgs84_from_trig`` at the parent) were rewritten for
fewer float32 operations a pair (the CD kernel issues as many vector
operations a cycle as the chip has slots for, so its cost is its
operation count: PERF.md section 5).  Each rewrite is algebraically the
expression it replaces; these cases show that its float32 result lies no
further from a float64 evaluation of the reference's formulas
(``geo.qdrdist_matrix``: haversine, the radius at ``lat_o + lat_i``, the
cross-equator blend) than the longer forms' did.  The longer forms are
kept below, verbatim from the parent commit, as the yardstick; both
errors are in every failure message and in PERF.md section 6.

Nothing is compiled for a step here: 20,000 pairs a case, elementwise.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from bluesky_tpu.ops import cd_tiled, geo, kmath

A, B = geo.A_WGS84, geo.B_WGS84
ULP1 = float(np.spacing(np.float32(1.0)))
N = 20000


# ------------------------------------------------------------------ parent
def _parent_rwgs84_from_trig(cosphi, sinphi):
    an = A * A * cosphi
    bn = B * B * sinphi
    ad = A * cosphi
    bd = B * sinphi
    return jnp.sqrt(an * an + bn * bn) * jax.lax.rsqrt(ad * ad + bd * bd)


def _parent_sin_poly(x):
    x2 = x * x
    return x * (1.0 - x2 / 6.0 * (1.0 - x2 / 20.0 * (1.0 - x2 / 42.0)))


def _parent_tile_geometry(own, intr, same_hemisphere=False):
    sl_o, cl_o = own["sl"], own["cl"]
    sl_i, cl_i = intr["sl"], intr["cl"]
    cos_sum = cl_o * cl_i - sl_o * sl_i
    sin_sum = sl_o * cl_i + cl_o * sl_i
    res1 = _parent_rwgs84_from_trig(cos_sum, sin_sum)
    if same_hemisphere:
        r = res1
    else:
        denom = own["abslat"] + intr["abslat"] \
            + jnp.where(own["lat"] == 0.0, 1e-6, 0.0)
        res2 = 0.5 * (own["abslat"] * (own["rloc"] + A)
                      + intr["abslat"] * (intr["rloc"] + A)) / denom
        r = jnp.where(own["lat"] * intr["lat"] < 0.0, res2, res1)
    dlat = jnp.radians(intr["lat"] - own["lat"])
    dlon_deg = intr["lon"] - own["lon"]
    dlon = jnp.radians(dlon_deg - 360.0 * jnp.round(dlon_deg * (1.0 / 360.0)))
    sh_lat = _parent_sin_poly(0.5 * dlat)
    sh_lon = _parent_sin_poly(0.5 * dlon)
    root = sh_lat * sh_lat + cl_o * cl_i * sh_lon * sh_lon
    root = jnp.clip(root, 0.0, 1.0)
    s = jnp.sqrt(root)
    dist = 2.0 * r * kmath.asin_taylor(s, s * s)
    qy = _parent_sin_poly(dlon) * cl_i
    qx = _parent_sin_poly(dlat) + sl_o * cl_i * (2.0 * sh_lon * sh_lon)
    rh = jax.lax.rsqrt(jnp.maximum(qx * qx + qy * qy, 1e-37))
    return dist, qy * rh, qx * rh


# --------------------------------------------------------------- reference
def _rwgs84_64(latd):
    lat = np.radians(latd)
    an, bn = A * A * np.cos(lat), B * B * np.sin(lat)
    ad, bd = A * np.cos(lat), B * np.sin(lat)
    return np.sqrt((an * an + bn * bn) / (ad * ad + bd * bd))


def _reference_64(lat1, lon1, lat2, lon2):
    """geo.qdrdist_matrix's formulas on the same float32 positions, in
    float64: distance [m], sin and cos of the bearing."""
    lat1, lon1, lat2, lon2 = (np.asarray(a, np.float64)
                              for a in (lat1, lon1, lat2, lon2))
    res1 = _rwgs84_64(lat1 + lat2)
    denom = np.abs(lat1) + np.abs(lat2) + np.where(lat1 == 0.0, 1e-6, 0.0)
    with np.errstate(invalid="ignore", divide="ignore"):
        res2 = 0.5 * (np.abs(lat1) * (_rwgs84_64(lat1) + A)
                      + np.abs(lat2) * (_rwgs84_64(lat2) + A)) / denom
    r = np.where(lat1 * lat2 < 0.0, res2, res1)
    p1, p2, l1, l2 = (np.radians(a) for a in (lat1, lat2, lon1, lon2))
    s1, s2 = np.sin(0.5 * (p2 - p1)), np.sin(0.5 * (l2 - l1))
    root = s1 * s1 + np.cos(p1) * np.cos(p2) * s2 * s2
    dist = 2.0 * r * np.arctan2(np.sqrt(root), np.sqrt(1.0 - root))
    qy = np.sin(l2 - l1) * np.cos(p2)
    qx = np.cos(p1) * np.sin(p2) - np.sin(p1) * np.cos(p2) * np.cos(l2 - l1)
    h = np.hypot(qx, qy)
    with np.errstate(invalid="ignore", divide="ignore"):
        return (dist, np.where(h > 0.0, qy / h, 0.0),
                np.where(h > 0.0, qx / h, 0.0))


# ------------------------------------------------------------------- pairs
def _pairs(kind):
    rng = np.random.default_rng(30)
    if kind == "continental":       # eu100k's box, any two aircraft
        la1, la2 = rng.uniform(35, 60, (2, N))
        lo1, lo2 = rng.uniform(-10, 30, (2, N))
    elif kind == "regional":        # the circle of circle100k
        la1, lo1 = rng.uniform(50, 55, N), rng.uniform(2, 8, N)
        la2 = la1 + rng.uniform(-2, 2, N)
        lo2 = lo1 + rng.uniform(-3, 3, N)
    elif kind == "close":           # inside the look-ahead's reach
        la1, lo1 = rng.uniform(35, 60, N), rng.uniform(-10, 30, N)
        la2 = la1 + rng.uniform(-.2, .2, N)
        lo2 = lo1 + rng.uniform(-.3, .3, N)
    elif kind == "equator":         # both hemispheres: the res2 blend
        la1, la2 = rng.uniform(-2, 2, (2, N))
        la1[:50] = 0.0              # the 1e-6 epsilon of the reference
        lo1 = rng.uniform(-10, 30, N)
        lo2 = lo1 + rng.uniform(-3, 3, N)
    elif kind == "antimeridian":    # dlon wraps by a turn
        la1 = rng.uniform(-60, 60, N)
        la2 = la1 + rng.uniform(-2, 2, N)
        east, west = rng.uniform(177, 180, N), rng.uniform(-180, -177, N)
        swap = rng.random(N) < 0.5
        lo1, lo2 = np.where(swap, west, east), np.where(swap, east, west)
    elif kind == "polar":
        la1, la2 = rng.uniform(85, 90, (2, N))
        la1[:10] = 90.0             # cos(radians(90)) is -4.4e-8 in float32
        lo1, lo2 = rng.uniform(-180, 180, (2, N))
    elif kind == "co-located":
        la1, lo1 = rng.uniform(-80, 80, N), rng.uniform(-180, 180, N)
        la2, lo2 = la1.copy(), lo1.copy()
    return tuple(np.asarray(a, np.float32) for a in (la1, lo1, la2, lo2))


def _errors(tile_geometry, kind):
    """Distance error in float32 ulps of the float64 value (metres for
    co-located pairs, whose distance is 0), bearing sine and cosine
    errors in ulps of 1, over the pairs within 400 km: beyond it the
    Taylor arcsin is conservative by design (kmath.asin_taylor)."""
    la1, lo1, la2, lo2 = _pairs(kind)
    same = bool(np.all(la1.astype(np.float64) * la2 >= 0.0))
    own = cd_tiled.precompute_trig(jnp.asarray(la1), jnp.asarray(lo1))
    intr = cd_tiled.precompute_trig(jnp.asarray(la2), jnp.asarray(lo2))
    out = jax.jit(lambda o, i: tile_geometry(o, i, same_hemisphere=same))(
        own, intr)
    assert all(o.dtype == jnp.float32 for o in out)
    dist, sin, cos = (np.asarray(o, np.float64) for o in out)
    dist64, sin64, cos64 = _reference_64(la1, lo1, la2, lo2)
    near = dist64 < 4e5
    scale = np.spacing(np.maximum(dist64, 1.0).astype(np.float32))
    return {"dist": (np.abs(dist - dist64) / scale)[near],
            "sin": (np.abs(sin - sin64) / ULP1)[near],
            "cos": (np.abs(cos - cos64) / ULP1)[near]}


def _radius_errors(rwgs84_of_sum):
    """Radius error in float32 ulps over latitude sums of -180 to 180."""
    sums = np.linspace(-180.0, 180.0, 200001).astype(np.float32)
    ang = jnp.radians(jnp.asarray(sums))
    r = rwgs84_of_sum(jnp.cos(ang), jnp.sin(ang))
    assert r.dtype == jnp.float32 and bool(jnp.all(jnp.isfinite(r)))
    r = np.asarray(r, np.float64)
    r64 = _rwgs84_64(sums.astype(np.float64))
    return np.abs(r - r64) / np.spacing(r64.astype(np.float32))


#: error of the polar and antimeridian cases is the inputs', not the
#: formulas': lon_i - lon_o rounds at 358 degrees (3e-5 degrees, hundreds
#: of ulps of a distance), and a delta of half a turn is past the sine
#: polynomial's range.  Both forms share it; their maxima then differ by
#: the rounding noise on top (read: 1230.1 for 1229.1 ulps, 67941.75 for
#: 67940.75), so those are held to 0.1% of each other.
SHARED_ERROR = ("antimeridian", "polar")
#: the bearings are the parent's but for the polynomial's Horner form, and
#: in these three cases the mean over the sample reads above the parent's
#: in the fourth digit (1.00052, 1.00015, 1.00017 of it: PERF.md section
#: 6): held to 0.1%.  Every other mean outside SHARED_ERROR is at or
#: below the parent's, and held there.
MEAN_NOISE = (("continental", "sin"), ("equator", "sin"), ("equator", "cos"))
KINDS = ("continental", "regional", "close", "equator", "antimeridian",
         "polar", "co-located")
CASES = [(kind, q) for kind in KINDS for q in ("dist", "sin", "cos")] \
    + [("latitude sums", "radius")] \
    + [(v, "dv2 guard") for v in (0.0, 1e-7, 1e-6, float("nan"))]


@pytest.fixture(scope="module")
def errors():
    cache = {}

    def get(kind):
        if kind not in cache:
            cache[kind] = (_errors(cd_tiled.tile_geometry, kind),
                           _errors(_parent_tile_geometry, kind))
        return cache[kind]
    return get


@pytest.mark.parametrize("kind,quantity", CASES,
                         ids=[f"{q}-{k}" for k, q in CASES])
def test_no_further_from_float64_than_the_parent(kind, quantity, errors):
    if quantity == "dv2 guard":
        # a sum of squares is never negative: on it the one maximum IS the
        # reference's where(abs(dv2) < 1e-6, 1e-6, dv2), NaN included
        x = jnp.asarray([kind], jnp.float32)
        old = jnp.where(jnp.abs(x) < 1e-6, 1e-6, x)
        new = cd_tiled.floor_speed2(x)
        assert new.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
        return
    if quantity == "radius":
        # (half a float32 is exact: the diameter's error IS the radius's)
        new = _radius_errors(
            lambda c, s: 0.5 * cd_tiled._dwgs84_from_trig(s))
        old = _radius_errors(_parent_rwgs84_from_trig)
        slack = 1.0
    else:
        new, old = (e[quantity] for e in errors(kind))
        slack = 1.001 if kind in SHARED_ERROR else 1.0
    record = (f"{quantity}, {kind}: max {new.max():.3f} (parent "
              f"{old.max():.3f}), mean {new.mean():.4f} (parent "
              f"{old.mean():.4f}) ulps over {new.size} values")
    assert new.max() <= old.max() * slack, record
    if (kind, quantity) in MEAN_NOISE:
        slack = 1.001
    assert new.mean() <= old.mean() * slack, record
    if kind not in SHARED_ERROR:
        # and in itself a handful of roundings, not a lost digit
        assert new.max() < 8.0, record
