"""Step-function tests: integration sanity, scheduling, determinism,
padding isolation."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bluesky_tpu.core.traffic import Traffic
from bluesky_tpu.core.step import (SimConfig, _scan_chunk, run_steps,
                                   run_steps_edge, run_steps_worlds_edge,
                                   stack_worlds, step_jit, world_slice)
from bluesky_tpu.core.asas import AsasConfig
from bluesky_tpu.core.noise import NoiseConfig
from bluesky_tpu.ops import aero


def advance(st, cfg, nchunks, chunk=200):
    """Advance in fixed 200-step chunks so each cfg compiles run_steps once."""
    for _ in range(nchunks):
        st = run_steps(st, cfg, chunk)
    return st


def make_scene(nmax=16, n=2, spacing=1.0, gs_cas=150.0):
    traf = Traffic(nmax=nmax, dtype=jnp.float64)
    for k in range(n):
        traf.create(1, "B744", 5000.0, gs_cas, None, 50.0 + k * spacing,
                    4.0 + k * spacing, 90.0, f"AC{k}")
    traf.flush()
    return traf


def test_straight_flight_moves_east():
    traf = make_scene(n=1)
    cfg = SimConfig(asas=AsasConfig(swasas=False))
    st = advance(traf.state, cfg, 1)   # 10 s
    i = traf.id2idx("AC0")
    assert float(st.simt) == pytest.approx(10.0, rel=1e-9)
    assert float(st.ac.lon[i]) > 4.0          # moved east
    assert float(st.ac.lat[i]) == pytest.approx(50.0, abs=1e-6)  # no drift
    # distance flown ~ gs * t
    dlon = float(st.ac.lon[i]) - 4.0
    dist_m = np.radians(dlon) * aero.Rearth * np.cos(np.radians(50.0))
    assert dist_m == pytest.approx(float(st.ac.gs[i]) * 10.0, rel=1e-2)


def test_altitude_capture():
    traf = make_scene(n=1)
    i = traf.id2idx("AC0")
    st = traf.state
    # command a climb of 300 m via selalt
    st = st.replace(ac=st.ac.replace(selalt=st.ac.selalt.at[i].set(5300.0)))
    cfg = SimConfig(asas=AsasConfig(swasas=False))
    st = advance(st, cfg, 10)  # 100 s at default 1500 fpm => 762 m max
    assert float(st.ac.alt[i]) == pytest.approx(5300.0, abs=1.0)
    assert abs(float(st.ac.vs[i])) < 0.5


def test_heading_capture():
    traf = make_scene(n=1)
    i = traf.id2idx("AC0")
    st = traf.state
    st = st.replace(ap=st.ap.replace(trk=st.ap.trk.at[i].set(180.0)))
    cfg = SimConfig(asas=AsasConfig(swasas=False))
    st = advance(st, cfg, 12)  # 120 s is plenty for a 90-deg turn
    assert float(st.ac.hdg[i]) == pytest.approx(180.0, abs=1.0)


def test_speed_capture():
    traf = make_scene(n=1)
    i = traf.id2idx("AC0")
    st = traf.state
    # 145 m/s stays inside the B744 envelope floor (vminer=140); commanding
    # below vmin is *supposed* to be overridden by the perf limits.
    st = st.replace(ac=st.ac.replace(selspd=st.ac.selspd.at[i].set(145.0)))
    cfg = SimConfig(asas=AsasConfig(swasas=False))
    st = advance(st, cfg, 12)
    assert float(st.ac.cas[i]) == pytest.approx(145.0, abs=1.0)


def test_determinism_same_seed_bitwise():
    cfg = SimConfig(noise=NoiseConfig(turb_active=True, adsb_transnoise=True,
                                      adsb_trunctime=1.0))
    outs = []
    for _ in range(2):
        traf = make_scene(n=4, spacing=0.05)
        st = run_steps(traf.state, cfg, 100)
        outs.append(st)
    a, b = outs
    for name in ("lat", "lon", "alt", "hdg", "tas", "vs"):
        np.testing.assert_array_equal(np.asarray(getattr(a.ac, name)),
                                      np.asarray(getattr(b.ac, name)),
                                      err_msg=name)


def test_padding_slots_frozen():
    traf = make_scene(nmax=16, n=2, spacing=0.05)
    # Snapshot to host first: run_steps donates its input state buffers.
    fields = ("lat", "lon", "alt", "hdg", "tas", "gs", "vs", "trk")
    live = np.asarray(traf.state.ac.active)
    before = {f: np.array(getattr(traf.state.ac, f)) for f in fields}
    cfg = SimConfig(noise=NoiseConfig(turb_active=True))
    st = run_steps(traf.state, cfg, 100)
    for name in fields:
        arr0 = before[name][~live]
        arr1 = np.asarray(getattr(st.ac, name))[~live]
        np.testing.assert_array_equal(arr0, arr1, err_msg=name)


def test_asas_resolves_head_on_conflict():
    """Two head-on aircraft: with ASAS+MVP they must keep separation larger
    than without resolution."""
    def closest_approach(reso_on):
        traf = Traffic(nmax=8, dtype=jnp.float64)
        traf.create(1, "B744", 5000.0, 150.0, None, 52.0, 3.7, 90.0, "W")
        traf.create(1, "B744", 5000.0, 150.0, None, 52.0, 4.3, 270.0, "E")
        traf.flush()
        cfg = SimConfig(asas=AsasConfig(swasas=True, reso_on=reso_on))
        st = traf.state
        mindist = 1e12
        for _ in range(30):     # 30 x 10 s = 300 s
            st = run_steps(st, cfg, 200)
            lat = np.asarray(st.ac.lat)[:2]
            lon = np.asarray(st.ac.lon)[:2]
            d = np.radians(lon[1] - lon[0]) * aero.Rearth \
                * np.cos(np.radians(52.0))
            d = np.hypot(d, np.radians(lat[1] - lat[0]) * aero.Rearth)
            mindist = min(mindist, d)
        return mindist

    d_off = closest_approach(False)
    d_on = closest_approach(True)
    assert d_off < 5.0 * aero.nm * 0.2          # unresolved: near collision
    assert d_on > d_off * 5                     # resolved: much larger miss


def test_step_scheduling_fms_and_asas_intervals():
    """ASAS state (inconf) must refresh at dtasas, not every simdt."""
    traf = make_scene(n=2, spacing=0.02)   # close pair -> conflict
    cfg = SimConfig()
    st = step_jit(traf.state, cfg)
    # First step at simt=0 triggers ASAS (asas_tnext=0) and FMS (simt<dt)
    assert float(st.asas_tnext) == pytest.approx(cfg.asas.dtasas)
    assert float(st.fms_t0) == pytest.approx(0.0)
    st2 = step_jit(st, cfg)
    # Second step at 0.05 s: neither fires again
    assert float(st2.asas_tnext) == pytest.approx(cfg.asas.dtasas)


def test_run_steps_matches_single_steps():
    traf = make_scene(n=2, spacing=0.05)
    cfg = SimConfig(asas=AsasConfig(swasas=False))
    st_loop = traf.state
    for _ in range(50):
        st_loop = step_jit(st_loop, cfg)
    # run_steps donates its input, so it must be the last user of traf.state
    st_scan = run_steps(traf.state, cfg, 50)
    for name in ("lat", "lon", "alt", "hdg", "tas"):
        np.testing.assert_allclose(np.asarray(getattr(st_scan.ac, name)),
                                   np.asarray(getattr(st_loop.ac, name)),
                                   rtol=0, atol=0, err_msg=name)


# ------------------------------------------------- the one chunk scan
# One body serves every runner: the leaves a static flag leaves out are
# absent from the carry, the world axis is a lift.  Every combination
# must step the state the bare single-world scan steps.

_scan_chunk_jit = jax.jit(
    _scan_chunk, static_argnames=("cfg", "nsteps", "checked", "worlds"))

NWORLDS, NSCAN = 2, 25

LEAF_SETS = {
    "none": (),
    "checked": ("checked",),
    "scanstats": ("scanstats",),
    "fingerprint": ("fingerprint",),
    "all": ("checked", "scanstats", "fingerprint"),
}


def _world_state(w):
    rng = np.random.default_rng(100 + w)
    n = 10 + 2 * w
    traf = Traffic(nmax=16, dtype=jnp.float32)
    traf.create(n, "B744", rng.uniform(3000.0, 11000.0, n),
                rng.uniform(130.0, 240.0, n), None,
                52.0 + rng.uniform(-0.3, 0.3, n),
                4.0 + rng.uniform(-0.3, 0.3, n),
                rng.uniform(0.0, 360.0, n))
    traf.flush()
    return traf.state


@pytest.fixture(scope="module")
def bare_scans():
    """Each world through the all-off single-world scan (``run_steps``
    donates, so it steps a copy)."""
    states = [_world_state(w) for w in range(NWORLDS)]
    copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)
    return states, [run_steps(copy(s), SimConfig(), NSCAN) for s in states]


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("leaves", list(LEAF_SETS))
@pytest.mark.parametrize("worlds", [False, True], ids=["one", "worlds"])
def test_scan_chunk_leaf_sets(bare_scans, worlds, leaves):
    from bluesky_tpu.obs import fingerprint as fpmod, scanstats as ssmod
    states, refs = bare_scans
    on = LEAF_SETS[leaves]
    cfg = SimConfig(scanstats="scanstats" in on,
                    fingerprint="fingerprint" in on)
    state = stack_worlds(states) if worlds else states[0]
    out = _scan_chunk_jit(state, cfg, NSCAN, "checked" in on, worlds)

    if worlds:
        for w, ref in enumerate(refs):
            _assert_trees_equal(world_slice(out.state, w), ref)
    else:
        _assert_trees_equal(out.state, refs[0])

    lead = (NWORLDS,) if worlds else ()
    packs = dict(
        bad=(jnp.zeros((), jnp.int32), "checked"),
        stats=(ssmod.init(states[0], cfg), "scanstats"),
        fp=(fpmod.init(states[0], cfg), "fingerprint"))
    for field, (one_world, flag) in packs.items():
        got = getattr(out, field)
        if flag not in on:
            assert got is None, field
            continue
        want = jax.tree_util.tree_leaves(one_world)
        got = jax.tree_util.tree_leaves(got)
        assert [g.shape for g in got] == [lead + x.shape for x in want], \
            field
    if "checked" in on:
        assert np.all(np.asarray(out.bad) == -1)


def _bad_configs():
    from bluesky_tpu.diff.smooth import SmoothConfig
    return {
        "smooth_tiled": (SimConfig(cd_backend="tiled",
                                   smooth=SmoothConfig()),
                         "differentiable mode"),
        "shard_mode": (SimConfig(cd_shard_mode="rows"),
                       "Unknown SimConfig.cd_shard_mode"),
        "resolver": (SimConfig(cd_backend="tiled",
                               asas=AsasConfig(reso_method="NOPE")),
                     "Unknown resolver"),
        "cd_backend": (SimConfig(cd_backend="quadtree"),
                       "Unknown SimConfig.cd_backend"),
    }


@pytest.mark.parametrize("case", ["smooth_tiled", "shard_mode",
                                  "resolver", "cd_backend"])
def test_bad_config_refused_on_both_paths(case):
    """A SimConfig ``step`` refuses is refused with the same error
    through the world axis (it used to trace there without complaint)."""
    cfg, match = _bad_configs()[case]
    state = _world_state(0)
    with pytest.raises(ValueError, match=match) as one:
        run_steps_edge(state, cfg, 5)
    with pytest.raises(ValueError, match=match) as many:
        run_steps_worlds_edge(stack_worlds([state, state]), cfg, 5)
    assert str(one.value) == str(many.value)
