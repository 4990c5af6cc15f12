"""The sparse schedule over the life of its layout (ISSUE 28).

A stripe layout is made at a chunk edge and used until the next refresh,
fifty CD intervals later in fast-forward.  ``block_reachability`` reads
the true positions every interval, so an old layout is exact; what ages
is the schedule's size.  With stripes only as tall as the reach radius
the stripe two over comes into reach within seconds, a row's runs double
and some rows overflow to the full-grid fallback.  These cases hold the
refresh (``core/asas._sparse_sort_refresh``) to a layout whose schedule
at the end of its life is still close to the one it started with, at the
density and block size of ``eu100k`` (100 aircraft per square degree,
blocks of 256) on a tenth of its area: 10,000 aircraft over 10 x 10
degrees, counted with the interval's own functions
(``cd_sched.schedule_counts``).  No step is compiled here.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from bluesky_tpu.core import asas
from bluesky_tpu.ops import cd_sched

NM, FT = 1852.0, 0.3048
RPZ, HPZ, TLOOK = 5 * NM, 1000 * FT, 300.0
#: a chunk of FF: 1000 steps of 0.05 s
LIFE_S = 50.0


def fleet(n, box, seed):
    """Uniform over ``box`` (lat0, lat1, lon0, lon1) as ``MCRE`` draws a
    fleet: 2,000 to 39,000 ft, 250 to 450 kts CAS turned into a TAS by
    the ISA density and capped at 310 m/s, any heading, level."""
    rng = np.random.default_rng(seed)
    alt = rng.uniform(2000.0, 39000.0, n) * FT
    cas = rng.uniform(250.0, 450.0, n) * 0.514444
    rho = (1.0 - 2.25577e-5 * alt) ** 4.2559
    return dict(lat=rng.uniform(box[0], box[1], n),
                lon=rng.uniform(box[2], box[3], n), alt=alt,
                gs=np.minimum(cas / np.sqrt(rho), 310.0),
                trk=rng.uniform(0.0, 360.0, n))


def flown(f, t):
    """The fleet ``t`` seconds on, every aircraft straight ahead."""
    north = f["gs"] * np.cos(np.radians(f["trk"])) * t / 111194.9
    east = f["gs"] * np.sin(np.radians(f["trk"])) * t \
        / (111194.9 * np.cos(np.radians(f["lat"])))
    return dict(f, lat=f["lat"] + north, lon=f["lon"] + east)


def cols(f):
    n = len(f["lat"])
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return (f32(f["lat"]), f32(f["lon"]), f32(f["gs"]), f32(f["alt"]),
            jnp.zeros(n, jnp.float32), jnp.ones(n, bool))


def layout(f, life_s):
    """The refresh's own layout for this fleet and lifetime."""
    n = len(f["lat"])
    n_tot = cd_sched.padded_size(n, 256)
    dest, _, fresh, _ = asas._sparse_sort_refresh(
        *cols(f), jnp.arange(n, dtype=jnp.int32),
        jnp.full((n_tot, 8), -1, jnp.int32), life_s,
        block=256, tlookahead=TLOOK, rpz=RPZ, hpz=HPZ)
    assert tuple(int(v) for v in fresh) == schedule(f, dest)
    return dest


def schedule(f, dest):
    pairs, overflow = cd_sched.schedule_counts(
        *cols(f), dest, block=256, rpz=RPZ, hpz=HPZ, tlookahead=TLOOK)
    return int(pairs), int(overflow)


@pytest.fixture(scope="module")
def box():
    return fleet(10000, (40.0, 50.0, 0.0, 10.0), 28)


#: Read on this fleet: without the margin 555 block pairs fresh, 689 at
#: 7 s with one overflow row, 865 at 49 s (+56%); with it 582 fresh and
#: 604 at 49 s (+4%), no overflow row at any age.  Two more fleets at
#: latitudes 35 to 45: +60 and +66% against +7 and +8%.
@pytest.mark.parametrize("life_s,holds", [(LIFE_S, True), (0.0, False)],
                         ids=["with_the_drift", "reach_alone"])
def test_the_schedule_at_the_end_of_a_layouts_life(box, life_s, holds):
    dest = layout(box, life_s)
    fresh, over0 = schedule(box, dest)
    ages = [schedule(flown(box, t), dest) for t in (7.0, 14.0, 28.0, 49.0)]
    aged = ages[-1][0]
    assert over0 == 0
    near = aged <= 1.15 * fresh and not any(o for _, o in ages)
    assert near == holds, (fresh, ages)
    if not holds:
        # what the margin is for, so that the case cannot pass by a hair
        assert aged > 1.4 * fresh


def test_no_lifetime_is_the_layout_of_the_reach_alone(box):
    """``life_s`` = 0 is the stripe sort as it was: the reach radius at
    the fleet's fastest and nothing more."""
    lat, lon, gs, alt, vs, act = cols(box)
    was = cd_sched.stripe_sort_dest(
        lat, lon, gs, act, cd_sched.reach_threshold_m(gs, act, TLOOK, RPZ),
        256, 32, alt=alt, vs=vs)
    assert np.array_equal(np.asarray(layout(box, 0.0)), np.asarray(was))
    assert not np.array_equal(np.asarray(layout(box, LIFE_S)),
                              np.asarray(was))


def test_a_global_fleet_keeps_its_layout():
    """Stripes that the padding's count already makes taller than reach
    plus drift (140 degrees over 31 stripes is 4.5 degrees; reach and
    50 s of drift 2.2) do not change."""
    f = fleet(6000, (-70.0, 70.0, -180.0, 180.0), 3)
    assert np.array_equal(np.asarray(layout(f, 0.0)),
                          np.asarray(layout(f, LIFE_S)))


def test_a_fleet_that_overflows_when_fresh_keeps_the_reach_alone():
    """Where a row's windows pass ``S_CAP`` segments on the layout just
    made (here: one stripe, fourteen longitude groups of a block each,
    alternately at FL100 and FL300, so seven runs where six fit; at
    N=100k the 230 nm circle, 340 of its 391 rows), the fallback runs
    every interval whatever the stripes' height, and taller stripes
    would only add block pairs: the layout is that of no lifetime."""
    rng = np.random.default_rng(5)
    n = 3584
    k = np.arange(n) // 256
    f = dict(lat=52.0 + rng.uniform(0.0, 0.01, n),
             lon=5.0 + 0.02 * k + rng.uniform(0.0, 0.01, n),
             alt=np.where(k % 2 == 0, 10000.0, 30000.0) * FT,
             gs=rng.uniform(130.0, 230.0, n), trk=rng.uniform(0, 360, n))
    dest = layout(f, LIFE_S)
    assert schedule(f, dest)[1] > 0
    assert np.array_equal(np.asarray(dest), np.asarray(layout(f, 0.0)))


def test_the_lifetime_is_traced_not_compiled_in():
    """Another chunk length (OP's 30 s, FF's 50 s) is another value of
    one argument of the same compiled refresh."""
    f = fleet(600, (40.0, 45.0, 0.0, 5.0), 4)
    layout(f, 30.0)
    n0 = asas._sparse_sort_refresh._cache_size()
    layout(f, 50.0)
    assert asas._sparse_sort_refresh._cache_size() == n0
