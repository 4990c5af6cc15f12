"""Sparse segment-scheduled CD backend (ops/cd_sched.py) vs the tiled
oracle.

The scheduler only changes WHICH provably-empty tiles are skipped
(stripe sort + contiguous segment windows + overflow fallback), so every
reduction must match ``cd_tiled.detect_resolve_tiled`` to f32
reassociation tolerance, across geometries that exercise each schedule
regime: spread (segments), dense clump (overflow fallback -> full
grid), equator-crossing (res2 radius branch kept), antimeridian wrap
(no false skips), and climbing traffic (vertical reachability term).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from bluesky_tpu.ops import cd, cd_sched, cd_tiled, cr_mvp

pytestmark = pytest.mark.slow    # multi-minute lane (see pyproject)

NM, FT = 1852.0, 0.3048
CFG = cr_mvp.MVPConfig(rpz_m=5 * NM * 1.05, hpz_m=1000 * FT * 1.05,
                       tlookahead=300.0)


def make_args(n, geom, seed=0, act_frac=0.95, vs_spread=15.0):
    rng = np.random.default_rng(seed)
    if geom == "regional":
        ang = rng.uniform(0, 2 * np.pi, n)
        r = 3.8 * np.sqrt(rng.random(n))
        lat = 52.6 + r * np.cos(ang)
        lon = 5.4 + r * np.sin(ang) / 0.6
    elif geom == "equator":
        lat = rng.uniform(-8.0, 8.0, n)
        lon = rng.uniform(-10.0, 30.0, n)
    elif geom == "antimeridian":
        lat = rng.uniform(-10.0, 10.0, n)
        lon = (rng.uniform(170.0, 190.0, n) + 180.0) % 360.0 - 180.0
    elif geom == "global":
        lat = np.degrees(np.arcsin(rng.uniform(-0.94, 0.94, n)))
        lon = rng.uniform(-180.0, 180.0, n)
    else:                       # continental
        lat = rng.uniform(35.0, 60.0, n)
        lon = rng.uniform(-10.0, 30.0, n)
    gs = rng.uniform(130.0, 240.0, n)
    trk = rng.uniform(0.0, 360.0, n)
    alt = rng.uniform(3000.0, 11000.0, n)
    vs = rng.uniform(-vs_spread, vs_spread, n)
    active = rng.random(n) > (1.0 - act_frac)
    gse = gs * np.sin(np.radians(trk))
    gsn = gs * np.cos(np.radians(trk))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return [f32(lat), f32(lon), f32(trk), f32(gs), f32(alt), f32(vs),
            f32(gse), f32(gsn), jnp.asarray(active), jnp.zeros(n, bool)]


def run_both(args, **kw):
    ref = cd_tiled.detect_resolve_tiled(
        *args, 5 * NM, 1000 * FT, 300.0, CFG, block=256)
    out = cd_sched.detect_resolve_sched(
        *args, 5 * NM, 1000 * FT, 300.0, CFG, block=256, interpret=True,
        **kw)
    return out, ref


def bearing_ulp_slack(args):
    """[n] metres a second that ONE float32 ulp of a pair's bearing moves
    an ownship's horizontal MVP sum by, from a float64 evaluation of the
    dense reference on the same inputs; 0 for all but near head-on pairs.

    The MVP term of a pair points along its miss vector ``drel + vrel *
    tcpa``.  For a pair 80 km apart that will pass within 17 m, an ulp of
    bearing turns ``drel`` by 9 mm and the term's direction by ``eps *
    dist / dcpa`` = 5.6e-4: more than assert_match's rtol, from one
    rounding.  The two CPU compilations of the same pair math (a lax scan
    body and an interpreted kernel) do round differently here and there,
    so where ``dist / dcpa`` exceeds 1000 the sums may differ by the ulp
    this returns, and nowhere else.  (Readings, PERF.md section 6: the
    parent commit of PR 30 breaks the bare tolerance on seed 61 of
    test_layered_schedule_is_exact's scene, PR 30 on its seed 7; over
    seven seeds the excess is 0.02 to 0.19 of this ulp.)"""
    lat, lon, trk, gs, alt, vs, gse, gsn = (
        jnp.asarray(np.asarray(a, np.float64)) for a in args[:8])
    c = jax.jit(cd.detect)(lat, lon, trk, gs, alt, vs, args[8],
                           5 * NM, 1000 * FT, 300.0)
    dve, dvn, _, _ = cr_mvp.pair_contributions(c, alt, gse, gsn, vs, CFG)
    # (inside 10 m MVP turns drel by a right angle instead: no miss vector)
    cond = np.asarray(c.dist) / np.maximum(np.sqrt(np.maximum(
        np.asarray(c.dcpa2), 0.0)), 10.0)
    near = np.asarray(c.swconfl) & (cond > 1000.0)
    return np.finfo(np.float32).eps * np.sum(
        np.where(near, cond * np.hypot(dve, dvn), 0.0), axis=1)


def assert_match(out, ref, args):
    assert bool(jnp.all(out.inconf == ref.inconf))
    assert int(out.nconf) == int(ref.nconf)
    assert int(out.nlos) == int(ref.nlos)
    slack = bearing_ulp_slack(args)
    for f in ("tcpamax", "sum_dve", "sum_dvn", "sum_dvv", "tsolv"):
        # Reassociation-only differences: the schedule changes tile
        # ORDER, never pair math, so deviations are f32 rounding of the
        # sums (rel ~1e-7 even in 2000-conflict clumps).
        a, b = np.asarray(getattr(out, f)), np.asarray(getattr(ref, f))
        tol = 5e-3 + 1e-4 * np.abs(b)
        if f in ("sum_dve", "sum_dvn"):
            tol = tol + slack
        bad = np.nonzero(~(np.abs(a - b) <= tol))[0]
        assert bad.size == 0, (f, bad, a[bad], b[bad], slack[bad])
    pa = [frozenset(int(x) for x in row if x >= 0)
          for row in np.asarray(out.topk_idx)]
    pb = [frozenset(int(x) for x in row if x >= 0)
          for row in np.asarray(ref.topk_idx)]
    assert pa == pb


@pytest.mark.parametrize("geom", ["continental", "regional", "equator",
                                  "antimeridian", "global"])
def test_parity_geometries(geom):
    n = 1300
    args = make_args(n, geom)
    out, ref = run_both(args)
    assert_match(out, ref, args)


def test_parity_with_inactive_and_climbers():
    n = 1200
    args = make_args(n, "continental", seed=7, act_frac=0.7, vs_spread=16.0)
    out, ref = run_both(args)
    assert_match(out, ref, args)


def test_row_split_path_is_exact(monkeypatch):
    """The >400k row-split (multiple pallas_call invocations over row
    slices, see _MAX_ROWS) must concatenate BIT-EXACTLY to the
    single-call result — rows are independent, so per-row reductions
    see identical operations in identical order.  Exercised at small N
    by shrinking _MAX_ROWS (ragged final slice included), covering both
    windowed rows and the per-slice overflow fallback, with and without
    in-kernel resume.  (_ONE_VARIANT_ROWS is pinned low for BOTH runs
    so the comparison isolates the split, not the same-hemisphere
    kernel specialization.)"""
    monkeypatch.setattr(cd_sched, "_ONE_VARIANT_ROWS", 4)

    def run(args, **kw):
        return cd_sched.detect_resolve_sched(
            *args, 5 * NM, 1000 * FT, 300.0, CFG, block=256,
            interpret=True, **kw)

    for geom in ("continental", "regional"):
        args = make_args(2600, geom, seed=11)
        monkeypatch.setattr(cd_sched, "_MAX_ROWS", 7)   # 43 rows -> 7 calls
        out = run(args)
        monkeypatch.setattr(cd_sched, "_MAX_ROWS", 1408)  # single call
        ref = run(args)
        assert int(ref.nconf) > 0
        for f in ("inconf", "nconf", "nlos", "tcpamax", "sum_dve",
                  "sum_dvn", "sum_dvv", "tsolv", "topk_idx", "topk_tin"):
            np.testing.assert_array_equal(
                np.asarray(getattr(out, f)), np.asarray(getattr(ref, f)),
                err_msg=f"{geom}:{f}")

    # resume path across slice boundaries
    n = 2600
    args = make_args(n, "continental", seed=12)
    n_tot = cd_sched.padded_size(n, 256)
    thresh = cd_sched.reach_threshold_m(args[3], args[8], 300.0, 5 * NM)
    perm = cd_sched.stripe_sort_dest(args[0], args[1], args[3], args[8],
                                     thresh, 256, 32)
    partners = jnp.full((n_tot, 8), -1, jnp.int32)
    kw = dict(perm=perm, partners=partners, resume_rpz_m=5 * NM * 1.05)
    monkeypatch.setattr(cd_sched, "_MAX_ROWS", 7)
    rd_s, p_s, a_s = run(args, **kw)
    monkeypatch.setattr(cd_sched, "_MAX_ROWS", 1408)
    rd_r, p_r, a_r = run(args, **kw)
    np.testing.assert_array_equal(np.asarray(p_s), np.asarray(p_r))
    np.testing.assert_array_equal(np.asarray(a_s), np.asarray(a_r))
    assert int(rd_s.nconf) == int(rd_r.nconf) > 0


def test_all_inactive():
    args = make_args(900, "continental", act_frac=0.0)
    out = cd_sched.detect_resolve_sched(
        *args, 5 * NM, 1000 * FT, 300.0, CFG, block=256, interpret=True)
    assert int(out.nconf) == 0 and int(out.nlos) == 0
    assert not bool(jnp.any(out.inconf))
    assert bool(jnp.all(out.topk_idx == -1))


def test_small_n_delegates():
    # n <= 2*block takes the plain kernel path
    args = make_args(300, "regional", seed=3)
    out, ref = run_both(args)
    assert_match(out, ref, args)


def test_cached_stale_dest_is_exact():
    """A stale sort (computed from OLD positions) must still give exact
    results — reachability is recomputed from true positions."""
    n = 1100
    old = make_args(n, "continental", seed=1)
    new = make_args(n, "continental", seed=2)
    thresh = cd_sched.reach_threshold_m(old[3], old[8], 300.0, 5 * NM)
    dest = cd_sched.stripe_sort_dest(old[0], old[1], old[3], old[8],
                                     thresh, 256, 32, alt=old[4], vs=old[5])
    out = cd_sched.detect_resolve_sched(
        *new, 5 * NM, 1000 * FT, 300.0, CFG, block=256, interpret=True,
        perm=dest.astype(jnp.int32))
    ref = cd_tiled.detect_resolve_tiled(
        *new, 5 * NM, 1000 * FT, 300.0, CFG, block=256)
    assert_match(out, ref, new)


def test_stripe_sort_dest_is_injective_and_padded():
    n = 5000
    args = make_args(n, "continental", seed=5)
    thresh = cd_sched.reach_threshold_m(args[3], args[8], 300.0, 5 * NM)
    dest = np.asarray(cd_sched.stripe_sort_dest(
        args[0], args[1], args[3], args[8], thresh, 256, 32,
        alt=args[4], vs=args[5]))
    assert len(np.unique(dest)) == n            # injective
    assert dest.max() < n + 32 * 256            # inside padded layout


def test_layered_schedule_is_exact():
    """The altitude-layered sort + wider segment budget (the dense-
    geometry mode kept available behind n_layers/s_cap — see the
    PERF_ANALYSIS dead-end addendum) stays bit-compatible: layering
    only reorders slots and the vertical term only skips provably-empty
    tiles."""
    n = 3000
    args = make_args(n, "regional", seed=7)
    thresh = cd_sched.reach_threshold_m(args[3], args[8], 300.0, 5 * NM)
    perm = cd_sched.stripe_sort_dest(
        args[0], args[1], args[3], args[8], thresh, 256, 32,
        alt=args[4], vs=args[5], n_layers=16)
    dest = np.asarray(perm)
    assert len(np.unique(dest)) == n            # layered sort injective
    out, ref = run_both(args, perm=perm, s_cap=12)
    assert int(ref.nconf) > 0
    assert_match(out, ref, args)


def test_auto_layer_gate_traces():
    """n_layers='auto' (the on-device density gate) produces a valid
    injective destination table for both sparse and dense scenes."""
    for geom in ("continental", "regional"):
        args = make_args(1500, geom, seed=3)
        thresh = cd_sched.reach_threshold_m(args[3], args[8], 300.0,
                                            5 * NM)
        dest = np.asarray(cd_sched.stripe_sort_dest(
            args[0], args[1], args[3], args[8], thresh, 256, 32,
            alt=args[4], vs=args[5], n_layers="auto"))
        assert len(np.unique(dest)) == 1500
        assert dest.max() < 1500 + 32 * 256


def test_vertical_reach_term_never_drops_conflicts():
    """Pure-vertical-crossing geometry: co-located columns of aircraft at
    different altitudes with strong climb/descent — the vertical bound
    must keep every genuinely convergent block pair."""
    n = 600
    rng = np.random.default_rng(11)
    lat = 52.0 + rng.uniform(-2.0, 2.0, n)
    lon = 4.0 + rng.uniform(-2.0, 2.0, n)
    gs = np.full(n, 150.0)
    trk = rng.uniform(0, 360, n)
    alt = np.where(np.arange(n) % 2 == 0, 3000.0, 9000.0)
    vs = np.where(np.arange(n) % 2 == 0, 18.0, -18.0)   # converging
    active = np.ones(n, bool)
    gse = gs * np.sin(np.radians(trk))
    gsn = gs * np.cos(np.radians(trk))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    args = [f32(lat), f32(lon), f32(trk), f32(gs), f32(alt), f32(vs),
            f32(gse), f32(gsn), jnp.asarray(active), jnp.zeros(n, bool)]
    out, ref = run_both(args)
    assert int(ref.nconf) > 0          # the scenario really converges
    assert_match(out, ref, args)


def test_inkernel_resume_matches_host_path():
    """update_tiled impl='sparse' (in-kernel keep+merge on the
    sorted-space table) vs impl='lax' (host partner_keep/merge_partners)
    over several intervals: flags, counts and engagement must match
    exactly; partner SETS may differ only on rows with more simultaneous
    conflicts than the K-slot table (eviction-order artifact of the
    bounded approximation, both paths approximate the dense set)."""
    import functools
    from unittest import mock
    from bluesky_tpu.core import asas as asasmod
    from bluesky_tpu.core.asas import AsasConfig
    from bluesky_tpu.core.traffic import Traffic

    n = 500
    rng = np.random.default_rng(4)
    traf = Traffic(nmax=n, dtype=jnp.float32)
    ang = rng.uniform(0, 2 * np.pi, n)
    r = 1.5 * np.sqrt(rng.random(n))
    lat = 52.6 + r * np.cos(ang)
    lon = 5.4 + r * np.sin(ang) / 0.6
    traf.create(n, "B744", rng.uniform(9000, 10000, n),
                rng.uniform(130, 240, n), None, lat, lon,
                rng.uniform(0, 360, n))
    traf.flush()
    cfg = AsasConfig()

    with mock.patch.object(
            cd_sched, "detect_resolve_sched",
            functools.partial(cd_sched.detect_resolve_sched,
                              interpret=True)):
        st_lax = traf.state
        st_sp = asasmod.refresh_spatial_sort(traf.state, cfg, block=256,
                                             impl="sparse")
        for it in range(3):
            st_lax, rd_l = asasmod.update_tiled(st_lax, cfg, block=256,
                                                impl="lax")
            st_sp, rd_s = asasmod.update_tiled(st_sp, cfg, block=256,
                                               impl="sparse")
            assert bool(jnp.all(rd_l.inconf == rd_s.inconf))
            assert int(rd_l.nconf) == int(rd_s.nconf)
            assert int(rd_l.nlos) == int(rd_s.nlos)
            assert bool(jnp.all(st_lax.asas.active == st_sp.asas.active))

            dest = np.asarray(st_sp.asas.sort_perm)
            n_tot = cd_sched.padded_size(n, 256)
            inv = np.full(n_tot + 1, -1, np.int64)
            inv[dest] = np.arange(n)
            ps = np.asarray(st_sp.asas.partners_s)[:n_tot]
            nconf_row = np.asarray(
                jnp.sum(jnp.asarray(rd_l.topk_tin) < 1e8, axis=1))
            k = st_lax.asas.partners.shape[1]
            for i in range(n):
                set_s = frozenset(int(inv[x]) for x in ps[dest[i]] if x >= 0)
                set_l = frozenset(int(x) for x in
                                  np.asarray(st_lax.asas.partners)[i]
                                  if x >= 0)
                if set_s != set_l:
                    # only K-overflow rows may differ
                    assert nconf_row[i] >= k or len(set_l) == k, \
                        (i, set_l, set_s, nconf_row[i])

            # drift the scene so resume/keep churns
            ac = st_lax.ac
            adv = lambda st: st.replace(ac=st.ac.replace(
                lat=st.ac.lat + st.ac.gsnorth / 111000.0,
                lon=st.ac.lon + st.ac.gseast / 68000.0))
            st_lax = adv(st_lax)
            st_sp = adv(st_sp)


def test_sparse_delete_purges_sorted_table():
    from bluesky_tpu.core import asas as asasmod
    from bluesky_tpu.core.asas import AsasConfig
    from bluesky_tpu.core.traffic import Traffic

    n = 64
    traf = Traffic(nmax=n, dtype=jnp.float32)
    traf.create(4, "B744", [3000.0] * 4, [150.0] * 4, None,
                [52.0, 52.001, 52.002, 52.003], [4.0] * 4,
                [90.0, 270.0, 90.0, 270.0])
    traf.flush()
    st = asasmod.refresh_spatial_sort(traf.state, AsasConfig(), block=256,
                                      impl="sparse")
    dest = np.asarray(st.asas.sort_perm)
    # hand-plant a partner pair in sorted space, then delete aircraft 1
    ps = st.asas.partners_s.at[dest[0], 0].set(int(dest[1]))
    ps = ps.at[dest[1], 0].set(int(dest[0]))
    traf.state = st.replace(asas=st.asas.replace(partners_s=ps))
    traf.delete(1)
    ps2 = np.asarray(traf.state.asas.partners_s)
    assert (ps2[dest[1]] == -1).all()          # deleted row purged
    assert dest[1] not in ps2                  # no references remain


