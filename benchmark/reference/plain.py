"""The plain reference: the simulation's semantics in straightforward NumPy.

Written from the published BlueSky algorithms (Hoekstra & Ellerbroek; the
open-source ``bluesky`` tools/aero.py, tools/geo.py, traffic/asas/
StateBasedCD.py, MVP.py, asas.py ResumeNav, traffic.py kinematics,
performance/openap) that the program under test mirrors.  It imports
nothing of the program and takes nothing the program made: the fleet it
steps is the one the benchmark's generator wrote, or the state a client
received, and the airframe envelope is its own table below.

Everything is float32 elementwise NumPy over the aircraft axis, with
conflict detection and resolution over explicit (ownship, intruder) pair
lists, so one code path serves 21 aircraft (all pairs) and a sample of
ownships against 100,000 intruders (pre-filtered pairs).

``Precision`` is the control's handle: ``Precision("bfloat16")`` rounds
every stored quantity and every pair geometry to bfloat16, the nearest
precision below the float32 both configurations state.
"""
import math

import numpy as np

F = np.float32

# ---- constants (BlueSky tools/aero.py, tools/geo.py) -------------------
KTS = 0.514444
FT = 0.3048
FPM = FT / 60.0
NM = 1852.0
G0 = 9.80665
R_AIR = 287.05287
P0 = 101325.0
RHO0 = 1.225
T0 = 288.15
TSTRAT = 216.65
BETA = -0.0065
REARTH = 6371000.0
A_WGS84 = 6378137.0
B_WGS84 = 6356752.314245

# OpenAP flight phases (openap/phase.py)
PH_IC, PH_CL, PH_CR, PH_DE, PH_AP, PH_GD = 2, 3, 4, 5, 6, 8

#: en-route envelope of the one airframe both configurations fly
#: (rounded public B747-400 figures; CAS [m/s], vs [m/s], hmax [m]).
#: Neither configuration leaves the en-route phases, and ``envelope``
#: raises if an aircraft does.
B744 = dict(vminer=140.0, vmaxer=190.0, vsmin=-3000.0 * FPM,
            vsmax=2000.0 * FPM, hmax=13747.0, axmax=1.5)

#: ASAS settings of both configurations (BlueSky asas.py defaults)
ASAS = dict(dtasas=1.0, dtlookahead=300.0, rpz=5.0 * NM, hpz=1000.0 * FT,
            resofach=1.05, resofacv=1.05,
            vmin=100.0 * KTS, vmax=180.0 * KTS,
            vsmin=-3000.0 * FPM, vsmax=3000.0 * FPM)
SIMDT = 0.05


class Precision:
    """float32 (the configurations' precision) or its control."""

    def __init__(self, name="float32"):
        if name not in ("float32", "bfloat16"):
            raise ValueError(f"unknown precision {name!r}")
        self.name = name
        if name == "bfloat16":
            import ml_dtypes
            self._bf = ml_dtypes.bfloat16

    def __call__(self, x):
        x = np.asarray(x, F)
        if self.name == "bfloat16":
            return x.astype(self._bf).astype(F)
        return x


# ---- the simulation clock, as a frame carries it ----------------------
# Today's program keeps its clock as a float32 sum of SIMDT steps, which
# drifts (a step is 0.04980 s from 4,096 to 16,384 s, 0.05078 s to 65,536
# s, 0.04688 s above); a program that counts its steps would carry
# float32(n * SIMDT), rounded once.  Which of the two wrote a frame shows
# in the frame's own ``simt``, so the reference takes the clock from
# there ("sum" or "count") and counts steps, never seconds.
def sum_clock(simt):
    """``(k, s)``: the most steps ``k`` of the float32 sum of SIMDT steps
    from nought whose clock ``s`` has not passed ``simt``; ``simt`` is a
    value of that sum where ``s == simt``.  Inside a binade every step
    adds the same rounded amount, so it is walked in one stride; below
    1 s (where a sum can tie) and across a binade's top, step by step.
    All in Python floats, which hold float32 values and these sums
    exactly."""
    simt = float(F(simt))
    k, s = 0, 0.0
    while True:
        nxt = float(F(F(s) + F(SIMDT)))
        if nxt > simt:
            return k, s
        inc, n = nxt - s, 1
        if s >= 1.0:
            top = 2.0 ** math.frexp(s)[1]
            n = max(1, int((min(simt, top - 2.0 * inc) - s) // inc))
        k, s = k + n, s + n * inc


def counted(simt):
    """The step count ``n`` for which ``simt`` is float32(n * SIMDT),
    or None."""
    n = int(round(float(F(simt)) / SIMDT))
    return n if float(F(n * SIMDT)) == float(F(simt)) else None


def clock_of(simts):
    """Which clock wrote frames that carry ``simts``: "count" where every
    one is float32(n * SIMDT) for a whole n and some one is no value of
    the float32 sum; else "sum", today's program's."""
    simts = list(simts)
    if all(counted(t) is not None for t in simts) \
            and not all(sum_clock(t)[1] == float(F(t)) for t in simts):
        return "count"
    return "sum"


def steps_at(simt, clock):
    """The steps the clock had made when it read ``simt``."""
    if clock == "count":
        return int(round(float(F(simt)) / SIMDT))
    return sum_clock(simt)[0]


# ---- atmosphere and airspeeds -----------------------------------------
def vatmos(h):
    T = np.maximum(F(T0) + F(BETA) * h, F(TSTRAT))
    rhotrop = F(RHO0) * (T / F(T0)) ** F(4.256848030018761)
    dhstrat = np.maximum(F(0.0), h - F(11000.0))
    rho = rhotrop * np.exp(-dhstrat / F(6341.552161))
    p = rho * F(R_AIR) * T
    return p, rho, T


def vcas2tas(cas, h):
    p, rho, _ = vatmos(h)
    qdyn = F(P0) * ((F(1.0) + F(RHO0) * cas * cas / F(7.0 * P0)) ** F(3.5)
                    - F(1.0))
    tas = np.sqrt(F(7.0) * p / rho
                  * ((F(1.0) + qdyn / p) ** F(2.0 / 7.0) - F(1.0)))
    return np.where(cas < 0, -tas, tas)


def vtas2cas(tas, h):
    p, rho, _ = vatmos(h)
    qdyn = p * ((F(1.0) + rho * tas * tas / (F(7.0) * p)) ** F(3.5)
                - F(1.0))
    cas = np.sqrt(F(7.0 * P0 / RHO0)
                  * ((qdyn / F(P0) + F(1.0)) ** F(2.0 / 7.0) - F(1.0)))
    return np.where(tas < 0, -cas, cas)


def vmach2tas(m, h):
    _, _, T = vatmos(h)
    return m * np.sqrt(F(1.4 * R_AIR) * T)


def vcasormach2tas(spd, h):
    return np.where(np.abs(spd) < 1.0, vmach2tas(spd, h), vcas2tas(spd, h))


# ---- geodesy ----------------------------------------------------------
def rwgs84(latd):
    lat = np.radians(latd)
    coslat, sinlat = np.cos(lat), np.sin(lat)
    an = F(A_WGS84 * A_WGS84) * coslat
    bn = F(B_WGS84 * B_WGS84) * sinlat
    ad = F(A_WGS84) * coslat
    bd = F(B_WGS84) * sinlat
    return np.sqrt((an * an + bn * bn) / (ad * ad + bd * bd))


def qdrdist_pairs(lat1, lon1, lat2, lon2):
    """Bearing [deg] and distance [m] from 1 to 2, BlueSky's matrix
    form: the same-hemisphere radius is taken at lat1 + lat2."""
    res1 = rwgs84(lat1 + lat2)
    r1, r2 = rwgs84(lat1), rwgs84(lat2)
    denom = np.abs(lat1) + np.abs(lat2) + np.where(lat1 == 0.0, F(1e-6),
                                                   F(0.0))
    res2 = F(0.5) * (np.abs(lat1) * (r1 + F(A_WGS84))
                     + np.abs(lat2) * (r2 + F(A_WGS84))) / denom
    r = np.where(lat1 * lat2 < 0.0, res2, res1)
    la1, lo1, la2, lo2 = (np.radians(x) for x in (lat1, lon1, lat2, lon2))
    sin1 = np.sin(F(0.5) * (la2 - la1))
    sin2 = np.sin(F(0.5) * (lo2 - lo1))
    c1, c2 = np.cos(la1), np.cos(la2)
    root = sin1 * sin1 + c1 * c2 * sin2 * sin2
    d = F(2.0) * r * np.arctan2(np.sqrt(root), np.sqrt(F(1.0) - root))
    qdr = np.degrees(np.arctan2(
        np.sin(lo2 - lo1) * c2,
        c1 * np.sin(la2) - np.sin(la1) * c2 * np.cos(lo2 - lo1)))
    return qdr, d


# ---- conflict detection and MVP over pair lists -----------------------
def candidate_pairs(own, lat, lon, alt, vs, gs, block=512):
    """(i, j) index arrays of every pair ownship i in ``own`` x intruder
    j != i that the vertical and latitude reach do not rule out.  The
    filter only drops pairs that cannot be in conflict inside the
    look-ahead, so detection over the rest is detection over all."""
    tl, rpz, hpz = (F(ASAS[k]) for k in ("dtlookahead", "rpz", "hpz"))
    mlat = F(REARTH * np.pi / 180.0)
    ii, jj = [], []
    for a in range(0, len(own), block):
        o = own[a:a + block]
        dalt = np.abs(alt[None, :] - alt[o, None])
        ok = dalt - (np.abs(vs[None, :]) + np.abs(vs[o, None])) * tl \
            < hpz + F(5.0)
        dlat = np.abs(lat[None, :] - lat[o, None]) * mlat
        ok &= dlat - (gs[None, :] + gs[o, None]) * tl < rpz + F(500.0)
        ok[np.arange(len(o)), o] = False
        r, c = np.nonzero(ok)
        ii.append(o[r])
        jj.append(c)
    return np.concatenate(ii), np.concatenate(jj)


def detect_pairs(i, j, lat, lon, trk, gs, alt, vs, q):
    """State-based detection (StateBasedCD.py) for pairs (i, j)."""
    tl, rpz, hpz = (F(ASAS[k]) for k in ("dtlookahead", "rpz", "hpz"))
    qdr, dist = qdrdist_pairs(lat[i], lon[i], lat[j], lon[j])
    qdr, dist = q(qdr), q(dist)
    qr = np.radians(qdr)
    dx, dy = dist * np.sin(qr), dist * np.cos(qr)
    tr = np.radians(trk)
    u, v = q(gs * np.sin(tr)), q(gs * np.cos(tr))
    du, dv = u[j] - u[i], v[j] - v[i]
    dv2 = du * du + dv * dv
    dv2 = np.where(np.abs(dv2) < 1e-6, F(1e-6), dv2)
    vrel = np.sqrt(dv2)
    tcpa = q(-(du * dx + dv * dy) / dv2)
    dcpa2 = q(dist * dist - tcpa * tcpa * dv2)
    r2 = rpz * rpz
    swhor = dcpa2 < r2
    dtinhor = np.sqrt(np.maximum(F(0.0), r2 - dcpa2)) / vrel
    tinhor = np.where(swhor, tcpa - dtinhor, F(1e8))
    touthor = np.where(swhor, tcpa + dtinhor, F(-1e8))
    dalt = alt[j] - alt[i]
    dvs = vs[j] - vs[i]
    dvs = np.where(np.abs(dvs) < 1e-6, F(1e-6), dvs)
    tcrosshi = (dalt + hpz) / -dvs
    tcrosslo = (dalt - hpz) / -dvs
    tinver = np.minimum(tcrosshi, tcrosslo)
    toutver = np.maximum(tcrosshi, tcrosslo)
    tinconf = q(np.maximum(tinver, tinhor))
    toutconf = q(np.minimum(toutver, touthor))
    swconfl = swhor & (tinconf <= toutconf) & (toutconf > 0.0) \
        & (tinconf < tl)
    swlos = (dist < rpz) & (np.abs(dalt) < hpz)
    return dict(swconfl=swconfl, swlos=swlos, qdr=qdr, dist=dist,
                tcpa=tcpa, tinconf=tinconf, u=u, v=v)


def mvp_pairs(cd, i, j, alt, vs, q):
    """Per-pair MVP displacement (MVP.py) for the pairs of ``cd``."""
    tl = F(ASAS["dtlookahead"])
    rpz_m = F(ASAS["rpz"] * ASAS["resofach"])
    hpz_m = F(ASAS["hpz"] * ASAS["resofacv"])
    qr = np.radians(cd["qdr"])
    dist, tcpa = cd["dist"], cd["tcpa"]
    drel_e, drel_n = np.sin(qr) * dist, np.cos(qr) * dist
    vrel_e, vrel_n = cd["u"][j] - cd["u"][i], cd["v"][j] - cd["v"][i]
    drel_v, vrel_v = alt[j] - alt[i], vs[j] - vs[i]
    dcpa_e = drel_e + vrel_e * tcpa
    dcpa_n = drel_n + vrel_n * tcpa
    dabsh = np.sqrt(dcpa_e * dcpa_e + dcpa_n * dcpa_n)
    ih = rpz_m - dabsh
    headon = dabsh <= 10.0
    sdist = np.maximum(dist, F(1e-9))
    dcpa_e = np.where(headon, drel_n / sdist * F(10.0), dcpa_e)
    dcpa_n = np.where(headon, -drel_e / sdist * F(10.0), dcpa_n)
    dabsh = np.where(headon, F(10.0), dabsh)
    abstcpa = np.maximum(np.abs(tcpa), F(1e-9))
    dve = (ih * dcpa_e) / (abstcpa * dabsh)
    dvn = (ih * dcpa_n) / (abstcpa * dabsh)
    apply_err = (rpz_m < dist) & (dabsh < dist)
    r1 = np.clip(rpz_m / sdist, -1.0, 1.0)
    r2 = np.clip(dabsh / sdist, -1.0, 1.0)
    err = np.cos(np.arcsin(r1) - np.arcsin(r2))
    err = np.where(apply_err, err, F(1.0))
    err = np.where(np.abs(err) < 1e-9, F(1e-9), err)
    dve, dvn = q(dve / err), q(dvn / err)
    has = np.abs(vrel_v) > 0.0
    iv = np.where(has, hpz_m, hpz_m - np.abs(drel_v))
    tsolv = np.where(has, np.abs(drel_v / np.where(has, vrel_v, F(1.0))),
                     cd["tinconf"])
    slow = tsolv > tl
    tsolv = np.where(slow, cd["tinconf"], tsolv)
    iv = np.where(slow, hpz_m, iv)
    ts = np.where(np.abs(tsolv) < 1e-9, F(1e-9), tsolv)
    dvv = q(np.where(has, (iv / ts) * -np.sign(vrel_v), iv / ts))
    return dve, dvn, dvv, q(tsolv)


def resolve(n, cd, i, j, st, q):
    """ASAS commands of every ownship that is in conflict: sums of the
    pair displacements, caps, the altitude command (MVP.py:33-143).
    Returns dict of [n] arrays; rows of aircraft not in conflict are
    meaningless and flagged by ``inconf``."""
    m = cd["swconfl"]
    ic, jc = i[m], j[m]
    sub = {k: (v[m] if k not in ("u", "v") else v) for k, v in cd.items()}
    dve, dvn, dvv, tsolv = mvp_pairs(sub, ic, jc, st["alt"], st["vs"], q)

    def rowsum(x):
        # float32 accumulation, like the program's row sums
        out = np.zeros(n, F)
        np.add.at(out, ic, x.astype(F))
        return out
    sum_e, sum_n, sum_v = rowsum(dve), rowsum(dvn), rowsum(dvv)
    tmin = np.full(n, F(1e9))
    np.minimum.at(tmin, ic, tsolv)
    inconf = np.zeros(n, bool)
    inconf[ic] = True
    dve_, dvn_, dvv_ = -sum_e, -sum_n, F(-0.5) * sum_v
    newe = dve_ + cd["u"]
    newn = dvn_ + cd["v"]
    newv = dvv_ + st["vs"]
    has_reso = dve_ * dve_ + dvn_ * dvn_ > 0.0
    newtrk = q(np.degrees(np.arctan2(newe, newn)) % F(360.0))
    newgs = np.sqrt(newe * newe + newn * newn)
    newgs = q(np.clip(newgs, F(ASAS["vmin"]), F(ASAS["vmax"])))
    newvs = q(np.clip(newv, F(ASAS["vsmin"]), F(ASAS["vsmax"])))
    asase = np.where(has_reso, newgs * np.sin(np.radians(newtrk)), F(0.0))
    asasn = np.where(has_reso, newgs * np.cos(np.radians(newtrk)), F(0.0))
    signdvs = np.sign(newvs - st["ap_vs"] * np.sign(st["selalt"]
                                                    - st["alt"]))
    signalt = np.sign(st["asas_alt"] - st["selalt"])
    newalt = np.where((signdvs == 0) | (signdvs == signalt),
                      st["asas_alt"], st["selalt"])
    altcond = (tmin < F(ASAS["dtlookahead"])) & (np.abs(dvv_) > 0.0)
    newalt = q(np.where(altcond, newvs * tmin + st["alt"], newalt))
    return dict(inconf=inconf, trk=newtrk, tas=newgs, vs=newvs,
                alt=newalt, asase=q(asase), asasn=q(asasn))


def resume_keep(i, j, lat, lon, gse, gsn, trk):
    """ResumeNav's keep predicate for pairs (asas.py:409-471)."""
    rpz = F(ASAS["rpz"])
    rpz_m = F(ASAS["rpz"] * ASAS["resofach"])
    de = F(REARTH) * (np.radians(lon[j] - lon[i])
                      * np.cos(F(0.5) * np.radians(lat[j] + lat[i])))
    dn = F(REARTH) * np.radians(lat[j] - lat[i])
    ve, vn = gse[j] - gse[i], gsn[j] - gsn[i]
    past = de * ve + dn * vn > 0.0
    hd = np.sqrt(de * de + dn * dn)
    bouncing = (np.abs(trk[i] - trk[j]) < 30.0) & (hd < rpz_m)
    return ~past | (hd < rpz) | bouncing


# ---- the whole step, for a fleet small enough for all pairs -----------
def new_fleet(lat, lon, hdg, alt_m, cas_ms, sel_hdg=None, sel_cas_ms=None,
              world=None, q=None):
    """State of a fleet as creation leaves it (traffic.py create): level,
    on its heading, at its CAS, nothing selected but what it has; then
    the heading and CAS that HDG and SPD commands selected, if any.
    ``world`` numbers the world each aircraft lives in (one by default):
    aircraft of different worlds never meet, so several small worlds
    step as one array."""
    q = q or Precision()
    f64 = lambda x: np.asarray(x, np.float64)   # noqa: E731
    alt64, cas64 = f64(alt_m), f64(cas_ms)
    # creation converts CAS to TAS on the host in float64
    T = np.maximum(T0 + BETA * alt64, TSTRAT)
    rho = RHO0 * (T / T0) ** 4.256848030018761 \
        * np.exp(-np.maximum(0.0, alt64 - 11000.0) / 6341.552161)
    p = rho * R_AIR * T
    qd = P0 * ((1.0 + RHO0 * cas64 * cas64 / (7.0 * P0)) ** 3.5 - 1.0)
    tas = np.sqrt(7.0 * p / rho * ((1.0 + qd / p) ** (2.0 / 7.0) - 1.0))
    n = len(alt64)
    hdg = f64(hdg)
    st = dict(
        lat=q(lat), lon=q(lon), alt=q(alt64), hdg=q(hdg), trk=q(hdg),
        tas=q(tas), gs=q(tas), vs=np.zeros(n, F),
        gsn=q(tas * np.cos(np.radians(hdg))),
        gse=q(tas * np.sin(np.radians(hdg))),
        ax=np.full(n, F(KTS)), bank=np.full(n, F(np.radians(25.0))),
        selspd=q(cas64), selalt=q(alt64), ap_trk=q(hdg),
        ap_vs=np.zeros(n, F), apvsdef=np.full(n, F(1500.0 * FPM)),
        asas_trk=q(hdg), asas_tas=q(tas), asas_alt=q(alt64),
        asas_vs=np.zeros(n, F), asas_active=np.zeros(n, bool),
        asase=np.zeros(n, F), asasn=np.zeros(n, F),
        inconf=np.zeros(n, bool), resopairs=np.zeros((n, n), bool),
        swaltsel=np.zeros(n, bool),
        simt=F(0.0), asas_tnext=F(0.0))
    world = np.zeros(n, int) if world is None else np.asarray(world)
    same = world[:, None] == world[None, :]
    st["pair_i"], st["pair_j"] = np.nonzero(same & ~np.eye(n, dtype=bool))
    if sel_hdg is not None:
        st["ap_trk"] = q(f64(sel_hdg))
    if sel_cas_ms is not None:
        st["selspd"] = q(f64(sel_cas_ms))
    return st


def envelope(tas, vs, alt):
    """Phase, CAS limits and bank of the airframe (openap phase.py,
    perfoap.py)."""
    roc = vs / F(0.00508)
    altft = alt / F(FT)
    ph = np.zeros(tas.shape, np.int32)
    lvl = (roc <= 100) & (roc >= -100)
    ph = np.where((altft <= 10) & lvl, PH_GD, ph)
    ph = np.where((altft >= 0) & (altft <= 1000) & (roc >= 0), PH_IC, ph)
    ph = np.where((altft >= 0) & (altft <= 1000) & (roc <= 0), PH_AP, ph)
    ph = np.where((altft >= 1000) & (roc >= 100), PH_CL, ph)
    ph = np.where((altft >= 1000) & (roc <= -100), PH_DE, ph)
    ph = np.where((altft >= 5000) & lvl, PH_CR, ph)
    er = (ph == PH_CL) | (ph == PH_CR) | (ph == PH_DE)
    if not np.all(er):
        raise ValueError("an aircraft left the en-route phases, which "
                         "is all this reference's envelope table holds")
    vmin = np.full(tas.shape, F(B744["vminer"]))
    vmax = np.full(tas.shape, F(B744["vmaxer"]))
    bank = np.radians(np.where(ph == PH_CR, F(35.0), F(25.0)))
    return ph, vmin, vmax, bank.astype(F)


def asas_update(st, q):
    """One CD&R interval over all pairs (asas.py update)."""
    n = len(st["lat"])
    i, j = st["pair_i"], st["pair_j"]
    cd = detect_pairs(i, j, st["lat"], st["lon"], st["trk"], st["gs"],
                      st["alt"], st["vs"], q)
    if cd["swconfl"].any():
        r = resolve(n, cd, i, j, st, q)
        upd = r["inconf"]
        for k in ("trk", "tas", "vs", "alt"):
            st["asas_" + k] = np.where(upd, r[k], st["asas_" + k])
        st["asase"] = np.where(upd, r["asase"], st["asase"])
        st["asasn"] = np.where(upd, r["asasn"], st["asasn"])
        st["inconf"] = upd
    else:
        st["inconf"] = np.zeros(n, bool)
    pairs = st["resopairs"]
    pairs[i, j] |= cd["swconfl"]
    keep = resume_keep(i, j, st["lat"], st["lon"], st["gse"], st["gsn"],
                       st["trk"])
    pairs[i, j] &= keep
    st["asas_active"] = pairs.any(axis=1)


def step(st, q):
    """One simdt of the whole pipeline (traffic.py update order)."""
    dt = F(SIMDT)
    ap_tas = vcasormach2tas(st["selspd"], st["alt"])
    st["ap_vs"] = st["apvsdef"]          # nothing selected a VS
    if st["simt"] >= st["asas_tnext"]:
        asas_update(st, q)
        st["asas_tnext"] = F(st["asas_tnext"] + F(ASAS["dtasas"]))
    act = st["asas_active"]
    p_trk = np.where(act, st["asas_trk"], st["ap_trk"])
    p_tas = np.where(act, st["asas_tas"], ap_tas)
    p_alt = np.where(act, st["asas_alt"], st["selalt"])
    p_vs = np.abs(np.where(act, st["asas_vs"], st["ap_vs"]))
    p_hdg = p_trk % F(360.0)
    ph, vmin, vmax, bank = envelope(st["tas"], st["vs"], st["alt"])
    st["bank"] = bank
    # envelope limits (perfoap.py limits)
    p_alt = np.minimum(p_alt, F(B744["hmax"]))
    cas = np.clip(vtas2cas(p_tas, p_alt), vmin, vmax)
    p_tas = vcas2tas(cas, p_alt)
    vsmax_acc = (F(1.0) - st["ax"] / F(B744["axmax"])) * F(B744["vsmax"])
    a_vs = np.where(p_vs > F(B744["vsmax"]), vsmax_acc, p_vs)
    p_vs = np.where(p_vs < F(B744["vsmin"]), F(B744["vsmin"]), a_vs)
    accel = np.where(ph == PH_GD, F(2.0), F(0.5))
    # airspeed, heading, vertical speed (traffic.py UpdateAirSpeed)
    dspd = p_tas - st["tas"]
    ax = (np.abs(dspd) > F(KTS)) * np.sign(dspd) * accel
    tas = q(st["tas"] + ax * dt)
    turnrate = np.degrees(F(G0) * np.tan(bank) / np.maximum(tas, F(0.01)))
    delhdg = (p_hdg - st["hdg"] + F(180.0)) % F(360.0) - F(180.0)
    swhdg = np.abs(delhdg) > np.abs(F(2.0) * dt * turnrate)
    hdg = q((st["hdg"] + dt * turnrate * swhdg * np.sign(delhdg))
            % F(360.0))
    dalt = p_alt - st["alt"]
    swalt = np.abs(dalt) > np.maximum(F(10.0 * FT),
                                      np.abs(F(2.0) * dt * np.abs(st["vs"])))
    tvs = swalt * np.sign(dalt) * np.abs(p_vs)
    dvs = tvs - st["vs"]
    need = np.abs(dvs) > F(300.0 * FPM)
    az = need * np.sign(dvs) * F(300.0 * FPM)
    vs = np.where(need, st["vs"] + az * dt, tvs)
    vs = q(np.where(np.isfinite(vs), vs, F(0.0)))
    hr = np.radians(hdg)
    gsn, gse = q(tas * np.cos(hr)), q(tas * np.sin(hr))
    alt = q(np.where(swalt, st["alt"] + vs * dt, p_alt))
    lat = q(st["lat"] + np.degrees(dt * gsn / F(REARTH)))
    coslat = np.cos(np.radians(lat))
    lon = q(st["lon"] + np.degrees(dt * gse / coslat / F(REARTH)))
    st.update(tas=tas, gs=tas, hdg=hdg, trk=hdg, vs=vs, ax=ax.astype(F),
              gsn=gsn, gse=gse, alt=alt, lat=lat, lon=lon, swaltsel=swalt,
              simt=F(st["simt"] + dt))


# ---- one CD&R interval of a sample of ownships against a whole fleet --
def interval_of_sample(own, frame, q=None):
    """Detection and MVP commands for the ownships ``own`` (indices into
    the frame) against every aircraft of ``frame``, a dict of [N] arrays
    lat, lon, alt, trk, gs, vs as a client received them.  Returns
    (inconf[own], asase[own], asasn[own])."""
    q = q or Precision()
    a = {k: q(frame[k]) for k in ("lat", "lon", "alt", "trk", "gs", "vs")}
    n = len(a["lat"])
    i, j = candidate_pairs(np.asarray(own), a["lat"], a["lon"], a["alt"],
                           a["vs"], a["gs"])
    cd = detect_pairs(i, j, a["lat"], a["lon"], a["trk"], a["gs"],
                      a["alt"], a["vs"], q)
    st = dict(alt=a["alt"], vs=a["vs"], ap_vs=np.zeros(n, F),
              selalt=a["alt"], asas_alt=a["alt"])
    r = resolve(n, cd, i, j, st, q)
    return r["inconf"][own], r["asase"][own], r["asasn"][own]


def fly(frame_a, frame_b, own, ob, nst, q=None):
    """Where the ownships ``own`` of frame A (``ob``: their places in
    frame B) are ``nst`` steps later, flown on the mean of the two
    frames' velocities in simdt steps of BlueSky's position update:
    (lat, lon)."""
    q = q or Precision()
    lat, lon = q(frame_a["lat"][own]), q(frame_a["lon"][own])
    ha, hb = np.radians(frame_a["trk"][own]), np.radians(frame_b["trk"][ob])
    gsn = q(F(0.5) * (frame_a["gs"][own] * np.cos(ha)
                      + frame_b["gs"][ob] * np.cos(hb)))
    gse = q(F(0.5) * (frame_a["gs"][own] * np.sin(ha)
                      + frame_b["gs"][ob] * np.sin(hb)))
    h = F(SIMDT)
    for _ in range(nst):
        lat = q(lat + np.degrees(h * gsn / F(REARTH)))
        lon = q(lon + np.degrees(h * gse / np.cos(np.radians(lat))
                                 / F(REARTH)))
    return lat, lon


def dead_reckon(frame_a, frame_b, own, ob, nst, q=None):
    """Gap [m] between ``fly``'s positions and frame B's (``ob``: the
    ownships' places in frame B)."""
    lat, lon = fly(frame_a, frame_b, own, ob, nst, q)
    dn = np.radians(frame_b["lat"][ob] - lat) * F(REARTH)
    de = np.radians(frame_b["lon"][ob] - lon) * F(REARTH) \
        * np.cos(np.radians(lat))
    return np.sqrt(dn * dn + de * de)
