"""The saturated disc (``circle100k``, ISSUE 27) at a size the CPU holds:

(a) the fleet generator ``benchmark/generators/circle_fleet.py``;
(b) the served program (``Simulation``, ``CDMETHOD SPARSE``, ``RESO
    MVP``) on a disc of the source's density, where the partner table of
    most ownships is full, against the plain NumPy reference
    (``benchmark/reference/plain.py``) and the dense ``ops/cd.py``, with
    the bfloat16 reference failing the same comparison;
(c) the three count series the deployment brought (``sim_conf_pairs``,
    ``sim_cd_block_pairs``, ``sim_cd_overflow_rows``) and the two of the
    outgoing layout (ISSUE 28: ``sim_cd_block_pairs_aged``,
    ``sim_cd_overflow_rows_aged``) against counts made apart from the
    program.
"""
import os
import sys

import numpy as np
import pytest

from bluesky_tpu.simulation.sim import Simulation

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)

from generators import circle_fleet               # noqa: E402
from reference import plain                       # noqa: E402

NM, FT = 1852.0, 0.3048
DISC = {"centre": [52.6, 5.4], "radius_nm": 230.0, "view_deg": 0.25}
#: 1,500 aircraft at the source's 0.60 per nm2 (the configuration's
#: rehearsal size)
SMALL = dict(DISC, radius_nm=28.2, view_deg=0.05)


def do(sim, *lines):
    for line in lines:
        sim.stack.stack(line)
    sim.stack.process()
    out = "\n".join(sim.scr.echobuf)
    sim.scr.echobuf.clear()
    return out


# ------------------------------------------------------ (a) the generator
@pytest.mark.parametrize("params,n", [(SMALL, 1500), (DISC, 100000)])
def test_fleet_is_exactly_n_inside_the_disc_and_uniform_by_area(params, n):
    cmds = circle_fleet.commands(params, 4260000001, n)
    assert cmds[0].startswith("SEED ") \
        and 0 < int(cmds[0].split()[1]) < 2**31
    body = cmds[1:]
    assert len(body) % 3 == 0
    pans, zooms, mcres = body[0::3], body[1::3], body[2::3]
    assert {z for z in zooms} == {f"ZOOM {2.0 / params['view_deg']}"}
    cnt = np.array([int(m.split()[1]) for m in mcres])
    assert all(m.startswith("MCRE ") for m in mcres) and cnt.sum() == n
    lat, lon = np.array([[float(x) for x in p.split()[1:]] for p in pans]).T
    # every view's centre lies in the disc: great-circle distance, by
    # the spherical law of cosines here (the generator uses haversine)
    c0, l0 = np.radians(params["centre"])
    ang = np.arccos(np.clip(
        np.sin(c0) * np.sin(np.radians(lat)) + np.cos(c0)
        * np.cos(np.radians(lat)) * np.cos(np.radians(lon) - l0), -1, 1))
    assert (ang * 6371000.0 <= params["radius_nm"] * NM * (1 + 1e-9)).all()
    # and no view of the grid inside the disc is left out: the views'
    # area on the ground is the disc's, to the staircase of its edge
    area = (params["view_deg"] * 60.0) ** 2 * np.cos(np.radians(lat)).sum()
    assert area == pytest.approx(np.pi * params["radius_nm"] ** 2,
                                 rel=0.02)
    # uniform per square mile: counts follow cos(latitude) to one aircraft
    share = n * np.cos(np.radians(lat)) / np.cos(np.radians(lat)).sum()
    assert np.abs(cnt - share).max() < 1.0
    extra = cnt - np.floor(share).astype(int)  # remainders: the first views
    assert (extra[:extra.sum()] == 1).all() and (extra[extra.sum():] == 0).all()


def test_fleet_is_a_function_of_the_seed():
    a = circle_fleet.commands(SMALL, 2147483999, 1500)
    assert a == circle_fleet.commands(SMALL, 2147483999, 1500)
    b = circle_fleet.commands(SMALL, 2147484000, 1500)
    assert a[0] != b[0] and a[1:] == b[1:]     # another SEED, same views


# ------------------------------------- (b) the program on a saturated disc
N_SAT = 2000
#: the source's density, 0.60 aircraft per nm2, for N_SAT aircraft
SAT = dict(DISC, radius_nm=float(np.sqrt(N_SAT / 0.60 / np.pi)),
           view_deg=0.05)


@pytest.fixture(scope="module")
def saturated():
    """An embedded Simulation loaded with the generator's lines, stepped
    one step at a time over four CD intervals.  Returns, for each
    interval, the state the detection read (the step's input: ASAS runs
    ahead of the kinematics in ``core/step.step``) and what the interval
    left in ``asas``, over the live aircraft."""
    import jax
    sim = Simulation(nmax=2048)
    out = do(sim, "CDMETHOD SPARSE", "RESO MVP",
             *circle_fleet.commands(SAT, 27, N_SAT))
    assert "failed" not in out and sim.traf.ntraf == N_SAT
    sim.op()
    intervals = []
    for _ in range(64):
        sim.drain_pipeline()
        st = sim.traf.state
        live = np.flatnonzero(np.asarray(st.ac.active))
        pre = {k: np.asarray(getattr(st.ac, k))[live] for k in
               ("lat", "lon", "alt", "trk", "gs", "vs", "gseast",
                "gsnorth")}
        pre["state"] = jax.tree.map(np.asarray, st)
        tnext = float(st.asas_tnext)
        sim.step(max_chunk=1)
        sim.drain_pipeline()
        st = sim.traf.state
        if float(st.asas_tnext) > tnext:
            post = {k: np.asarray(getattr(st.asas, k))[live] for k in
                    ("inconf", "asase", "asasn", "active")}
            post["nconf"] = int(st.asas.nconf_cur)
            post["partners"] = np.asarray(st.asas.partners_s)
            intervals.append((pre, post))
            if len(intervals) == 4:
                break
    assert len(intervals) == 4
    return sim, intervals


def _against(pre, post, inconf, ase, asn):
    """(share of flags that differ, the gaps [m/s] of the resolution
    vectors over the ownships both flag)."""
    both = inconf & post["inconf"]
    gap = np.hypot(ase - post["asase"], asn - post["asasn"])[both]
    return float(np.mean(inconf != post["inconf"])), gap


def test_the_partner_table_is_full_for_most_ownships(saturated):
    _, intervals = saturated
    for _, post in intervals:
        # directional pairs per live aircraft: at least 8, the table's
        # width, so the deployment's regime is the one tested
        assert post["nconf"] / N_SAT >= 8.0
    full = (intervals[-1][1]["partners"] >= 0).all(axis=1).sum()
    assert full > N_SAT / 2


#: The comparison's tolerances, each with its reason.  The program's
#: tiles compute a pair's geometry by rank-1-factored haversine with
#: rsqrt bearings and a Taylor arcsin (``ops/cd_tiled.tile_geometry``),
#: the plain reference and the dense ``ops/cd.py`` by the full formulas
#: with arctan2: float32 roundings apart.
#: Flags: a pair whose closest approach lies within a metre of the 5 nm
#: zone, or whose entry time lies on the look-ahead, may fall on either
#: side; with a score of partners an ownship's flag rarely hangs on one
#: pair.  Read 0 in 2,000 in four intervals (bfloat16: 16 to 24).
FLAG_MISS = 0.002
#: Vectors [m/s]: sums over a score of partners of displacements that
#: grow as 1/tcpa.  Read: median 0.0014 to 0.0018, 90th percentile 0.009
#: to 0.011 (bfloat16: 85 and 179), so ten times the readings; a single
#: ownship may be a whole vector apart (185 m/s read once, where one
#: borderline pair entered one side's sum), so no maximum is held.
RESO_P50, RESO_P90 = 0.02, 0.1


def _holds(pre, post, inconf, ase, asn):
    miss, gap = _against(pre, post, inconf, ase, asn)
    assert len(gap) > N_SAT / 2
    return (miss <= FLAG_MISS, np.median(gap) <= RESO_P50,
            np.percentile(gap, 90) <= RESO_P90)


def test_flags_and_vectors_match_the_plain_reference(saturated):
    _, intervals = saturated
    own = np.arange(N_SAT)
    for pre, post in intervals:
        assert all(_holds(pre, post, *plain.interval_of_sample(own, pre)))


def test_the_bfloat16_reference_fails_the_same_comparison(saturated):
    _, intervals = saturated
    pre, post = intervals[-1]
    assert not any(_holds(pre, post, *plain.interval_of_sample(
        np.arange(N_SAT), pre, plain.Precision("bfloat16"))))


def _dense(sim, st):
    """``ops/cd.py`` and ``cr_mvp.resolve`` over an [N, N] matrix on the
    state ``st``: (flags, east and north vectors, directional pairs)."""
    import jax.numpy as jnp
    from bluesky_tpu.ops import cd as cdops, cr_mvp
    cfg = sim.cfg.asas
    mvpcfg = cr_mvp.MVPConfig(
        rpz_m=cfg.rpz_m, hpz_m=cfg.hpz_m, tlookahead=cfg.dtlookahead,
        swresohoriz=cfg.swresohoriz, swresospd=cfg.swresospd,
        swresohdg=cfg.swresohdg, swresovert=cfg.swresovert)
    ac, asas = st.ac, st.asas
    cd = cdops.detect(*(jnp.asarray(getattr(ac, k)) for k in
                        ("lat", "lon", "trk", "gs", "alt", "vs",
                         "active")),
                      cfg.rpz, cfg.hpz, cfg.dtlookahead)
    _, _, _, _, ase, asn = cr_mvp.resolve(
        cd, ac.alt, ac.gseast, ac.gsnorth, ac.vs, ac.trk, ac.gs,
        ac.selalt, st.ap.vs, asas.alt, cfg.vmin, cfg.vmax, cfg.vsmin,
        cfg.vsmax, mvpcfg, noreso=asas.noreso, resooff=asas.resooff)
    live = np.flatnonzero(ac.active)
    return (np.asarray(cd.inconf)[live], np.asarray(ase)[live],
            np.asarray(asn)[live], int(np.asarray(cd.swconfl).sum()))


def _sparse_on_a_layout_made(sim, st, age_s):
    """The served interval again on the state ``st`` (one step of the
    fixture's own compiled program, so nothing compiles), under the
    layout the refresh would have made ``age_s`` seconds earlier for a
    life of ``age_s``, had every aircraft flown straight since: what
    the interval left in ``asas`` over the live aircraft."""
    import jax
    import jax.numpy as jnp
    from bluesky_tpu.core import asas as asasmod
    st = jax.tree.map(jnp.asarray, st)
    ac, cfg = st.ac, sim.cfg.asas
    back = -age_s / 111194.9
    lat0 = ac.lat + ac.gsnorth * back
    lon0 = ac.lon + ac.gseast * back / jnp.cos(jnp.radians(ac.lat))
    dest, partners_s, _, _ = asasmod._sparse_sort_refresh(
        lat0, lon0, ac.gs, ac.alt, ac.vs, ac.active, st.asas.sort_perm,
        st.asas.partners_s, age_s, block=min(sim.cfg.cd_block, 256),
        tlookahead=float(cfg.dtlookahead), rpz=float(cfg.rpz),
        hpz=float(cfg.hpz))
    live = np.flatnonzero(ac.active)
    # the step donates what it is given: keep what is compared after
    dest_was, tnext_was = np.asarray(dest), float(st.asas_tnext)
    assert not np.array_equal(dest_was, np.asarray(st.asas.sort_perm))
    # no refresh is due (the sim made its layout at t = 0 and the state
    # is four seconds old), so the step keeps the layout it is given
    sim.drain_pipeline()
    sim.traf.state = st.replace(asas=st.asas.replace(
        sort_perm=dest, partners_s=partners_s))
    sim.step(max_chunk=1)
    sim.drain_pipeline()
    out = sim.traf.state
    assert np.array_equal(np.asarray(out.asas.sort_perm), dest_was)
    assert float(out.asas_tnext) > tnext_was
    return {k: np.asarray(getattr(out.asas, k))[live]
            for k in ("inconf", "asase", "asasn")}


@pytest.mark.parametrize("layout_age_s", [None, 0.0, 50.0],
                         ids=["served", "fresh", "a_chunk_old"])
def test_flags_and_vectors_match_the_dense_path(saturated, layout_age_s):
    """``ops/cd.py`` and ``cr_mvp.resolve`` over an [N, N] matrix, on
    the state each interval read: against what the served interval left
    (its layout one to three seconds old), and against the sparse
    interval run apart on a layout just made and on one a whole chunk
    of fast-forward old (ISSUE 28: the stripes carry that drift; the
    flags and vectors may not)."""
    sim, intervals = saturated
    if layout_age_s is None:
        for pre, post in intervals[1:]:  # the later ones: vs is no longer 0
            inconf, ase, asn, nconf = _dense(sim, pre["state"])
            assert all(_holds(pre, post, inconf, ase, asn))
            # directional conflict pairs: a borderline pair in twenty
            # thousand may differ (read: 0, 1, 0, 0)
            assert abs(nconf - post["nconf"]) <= 1e-3 * post["nconf"]
        return
    pre, post = intervals[-1]
    got = _sparse_on_a_layout_made(sim, pre["state"], layout_age_s)
    # a layout decides which empty tiles are skipped, never a pair's
    # arithmetic: the flags are the served interval's to the last one,
    # the vectors sums of the same terms in another order
    assert np.array_equal(got["inconf"], post["inconf"])
    assert all(_holds(pre, got, *_dense(sim, pre["state"])[:3]))


# ------------------------------------------------- (c) the count series
def _numpy_schedule(lat, lon, gs, alt, vs, active, dest, block=256,
                    extra=32, s_cap=6, wmax=16):
    """(block pairs, overflow rows) of the single-grid schedule, counted
    with loops: bounding boxes per block of the padded layout, the
    conservative reach bound of ``cd_tiled.block_reachability`` written
    out again, then each row's runs of reachable blocks cut into
    segments of ``wmax``."""
    f = np.float32
    rpz, hpz, tl = f(5 * NM), f(1000 * FT), f(300.0)
    nb = -(-len(lat) // block) + extra
    boxes = []
    for b in range(nb):
        m = active & (dest // block == b)
        boxes.append(None if not m.any() else (
            lat[m].min(), lat[m].max(), lon[m].min(), lon[m].max(),
            gs[m].max(), alt[m].min(), alt[m].max(), np.abs(vs[m]).max()))
    pairs = over = 0
    for r in boxes:
        row = []
        for c in boxes:
            if r is None or c is None:
                row.append(False)
                continue
            dlat = max(f(0), r[0] - c[1], c[0] - r[1])
            lin = max(f(0), r[2] - c[3], c[2] - r[3])
            wrap = max(f(0), f(360) - (max(r[3], c[3]) - min(r[2], c[2])))
            dlon = min(lin, wrap)
            lmax = max(abs(r[0]), abs(r[1]), abs(c[0]), abs(c[1]))
            zonal = f(2) * f(6335000.0) * np.arcsin(np.clip(
                np.cos(np.radians(min(f(90), lmax)))
                * np.sin(np.radians(f(0.5) * dlon)), f(0), f(1)))
            ok = max(dlat * f(110000.0), zonal) \
                <= (rpz + tl * (r[4] + c[4])) * f(1.05)
            agap = max(f(0), r[5] - c[6], c[5] - r[6])
            ok &= agap <= (hpz + tl * (r[7] + c[7])) * f(1.05)
            row.append(bool(ok))
        runs = [len(run) for run in "".join(
            "x" if v else " " for v in row).split()]
        over += sum(-(-ln // wmax) for ln in runs) > s_cap
        pairs += sum(runs)
    return pairs, over


def _spread(n, rng):
    """Spread over Europe like ``eu100k``: short windows, no overflow."""
    return (rng.uniform(35.0, 60.0, n), rng.uniform(-10.0, 30.0, n),
            rng.integers(2000, 39000, n).astype(float))


def _clump(n, rng):
    """All within reach of each other in one latitude stripe, fourteen
    longitude groups of a block each, alternately at FL100 and FL300:
    a row reaches every other block, seven runs where six fit."""
    k = np.arange(n) // 256
    return (52.0 + rng.uniform(0.0, 0.01, n),
            5.0 + 0.02 * k + rng.uniform(0.0, 0.01, n),
            np.where(k % 2 == 0, 10000.0, 30000.0))


@pytest.mark.parametrize("fleet,n,overflows", [(_spread, 1024, False),
                                               (_clump, 3584, True)])
def test_count_series_equal_counts_made_apart(fleet, n, overflows):
    rng = np.random.default_rng(5)
    lat, lon, alt_ft = fleet(n, rng)
    hdg, spd = rng.integers(1, 360, n), rng.integers(250, 450, n)
    sim = Simulation(nmax=n)
    do(sim, "CDMETHOD SPARSE", "RESO MVP")
    sim.traf.create(n, "B744", alt_ft * FT, spd * 0.514444, None,
                    lat, lon, hdg.astype(float))
    sim.traf.flush()
    sim.op()
    st = sim.traf.state
    cols = [np.asarray(getattr(st.ac, k)) for k in
            ("lat", "lon", "gs", "alt", "vs", "active")]
    hists = {k: sim.obs.get(k) for k in
             ("sim_conf_pairs", "sim_cd_block_pairs",
              "sim_cd_overflow_rows")}
    assert all(h.count == 0 for h in hists.values())
    sim.step(max_chunk=1)          # refresh, then one step: it detects
    sim.drain_pipeline()
    st = sim.traf.state
    dest = np.asarray(st.asas.sort_perm)
    pairs, over = _numpy_schedule(*cols, dest)
    assert (over > 0) == overflows
    assert hists["sim_cd_block_pairs"].count == 1
    assert hists["sim_cd_block_pairs"].sum == pairs
    assert hists["sim_cd_overflow_rows"].sum == over
    # the pack's directional count, halved, as SIMINFO shows it
    assert hists["sim_conf_pairs"].count == 1
    assert hists["sim_conf_pairs"].sum == int(st.asas.nconf_cur) // 2
    assert (int(st.asas.nconf_cur) > 0)
    # a second chunk inside sort_every keeps the layout: no new schedule
    sim.step(max_chunk=1)
    sim.drain_pipeline()
    assert hists["sim_cd_block_pairs"].count == 1
    assert hists["sim_conf_pairs"].count == 2
    # ISSUE 28: the next refresh also counts what the layout it replaces
    # had come to, at the positions of its last interval; the first one
    # replaced no layout and observed nothing
    aged = {k: sim.obs.get(k) for k in
            ("sim_cd_block_pairs_aged", "sim_cd_overflow_rows_aged")}
    assert all(h.count == 0 for h in aged.values())
    st = sim.traf.state
    cols = [np.asarray(getattr(st.ac, k)) for k in
            ("lat", "lon", "gs", "alt", "vs", "active")]
    # due now, not thirty simulated seconds of interpreted kernels on
    sim._sort_simt -= sim.cfg.asas.sort_every * sim.cfg.asas.dtasas
    sim.step(max_chunk=1)
    sim.drain_pipeline()
    pairs, over = _numpy_schedule(*cols, dest)
    assert hists["sim_cd_block_pairs"].count == 2
    assert aged["sim_cd_block_pairs_aged"].count == 1
    assert aged["sim_cd_block_pairs_aged"].sum == pairs
    assert aged["sim_cd_overflow_rows_aged"].sum == over
    assert (over > 0) == overflows
