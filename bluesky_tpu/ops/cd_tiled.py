"""Blockwise conflict detection + MVP accumulation for large N.

The dense kernel (``ops/cd.py``) materialises [N, N] matrices — fine to
~16k aircraft, impossible at the 100k north star (10^10 f32 entries).  This
module computes exactly the same per-ownship *reductions* without ever
holding an N x N array: the pair space is tiled into [Br, Bc] blocks that are
streamed through on-chip memory, flash-attention-style (SURVEY.md §5.7 calls
for precisely this blockwise decomposition of the CPA geometry).

Per ownship row the step needs only (see core/asas.py):
  * ``inconf``      — any conflict flag            (OR-reduction)
  * ``tcpamax``     — max of tcpa over conflicts   (MAX-reduction)
  * MVP sums        — sum of per-pair displacement (SUM-reduction; the tail
                      of the resolver, ``cr_mvp.resolve_from_sums``, is
                      per-aircraft and shared with the dense path)
  * ``tsolv``       — min vertical solve time      (MIN-reduction)
  * conflict/LoS counts                            (scalar SUMs)
  * partner candidates for resume-nav hysteresis (below).

Resume-nav (reference asas.py:409-471) keeps a *pair set* alive until past
CPA.  The dense path stores it as an [N, N] bool; here it becomes a fixed-K
**partner table** ``[N, K]`` of intruder indices: a running top-K (by
earliest conflict-entry time) is carried through the column-block scan, so
each CD interval yields the K genuinely most urgent conflicts per ownship;
these are merged with the surviving previous partners, and the resume
predicates are evaluated on gathered partner state (an [N, K] problem,
linear in N).  K defaults to 8: an ownship tracks at most K simultaneous
hysteresis partners — conflicts re-detect every interval, so this bounds only
how many *past* conflicts can hold ASAS engaged at once.  Empirical bound
(measured on the bench geometry): at N=10,000 inside the 230 nm regional
circle — already ~3x the density of the busiest real airspace — the
per-ownship simultaneous conflict-partner distribution is mean 2.5,
p50 2, p99 7, max 11; only 0.24% of ownships ever exceed 8, and for
those the table keeps the 8 *most urgent* (earliest entry time), so the
divergence is limited to the resume timing of their least-urgent past
partners.  Raise ``Traffic(k_partners=...)`` for denser studies.

Semantics match the reference StateBasedCD + MVP summation
(StateBasedCD.py:7-103, MVP.py:14-143) pair-for-pair; only the reduction
*order* differs (blockwise f32 reassociation), so golden tests compare to the
dense path at tolerance (tests/test_cd_tiled.py).
"""
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import cr_mvp, geo, kmath


class RowConflictData(NamedTuple):
    """Per-ownship reductions of the pair space — no [N,N] anywhere."""
    inconf: jnp.ndarray     # [N] bool
    tcpamax: jnp.ndarray    # [N]
    sum_dve: jnp.ndarray    # [N]  sum over conflict pairs of MVP east term
    sum_dvn: jnp.ndarray    # [N]
    sum_dvv: jnp.ndarray    # [N]
    tsolv: jnp.ndarray      # [N]  min vertical solve time (1e9 = none)
    nconf: jnp.ndarray      # scalar int32 — directional conflict pairs
    nlos: jnp.ndarray       # scalar int32 — LoS pairs
    topk_idx: jnp.ndarray   # [N, K] int32 — K most urgent intruders,
    topk_tin: jnp.ndarray   # [N, K]         urgency order (1e9 = empty)


def _pad1(a, npad, value):
    return a if npad == 0 else jnp.concatenate(
        [a, jnp.full((npad,), value, a.dtype)])


# --------------------------------------------------------------------------
# Delta-polynomial pair geometry.
#
# The dense path evaluates the haversine + bearing with pairwise sin/cos/
# atan2 — ~a dozen transcendentals per PAIR.  Here the per-pair trig reduces
# to odd polynomials of the coordinate DELTAS plus products of per-AIRCRAFT
# sin/cos columns:
#   dlat, dlon are formed by direct subtraction (well-conditioned: the
#     cancellation happens on the raw degree values, keeping absolute error
#     at f32 eps of the coordinates — NOT on cos(delta) near 1, which would
#     lose all precision of close pairs),
#   sin(dlat/2) etc. come from a degree-7 odd Taylor evaluation (exact to
#     f32 for the |delta| < pi/2 range where precision matters; for far
#     pairs the small overshoot only pushes distances up, never creating
#     false conflicts),
#   the bearing uses sin(qdr) = qy/h, cos(qdr) = qx/h with
#     qy = sin(dlon)*cl_i,  qx = sin(dlat) + sl_o*cl_i*(2*sin^2(dlon/2))
#     so the angle itself is never formed,
#   rwgs84(lat_o+lat_i) (the reference matrix quirk, geo.py:117-128)
#     expands via the angle-sum identities from the per-aircraft columns.
# Per pair one atan2 (arc length) and a few sqrt survive.  The dense path
# keeps the literal reference formulas as the parity anchor.
# --------------------------------------------------------------------------

#: per-aircraft columns consumed by tile_geometry, in slab order
TRIG_FIELDS = ("lat", "lon", "sl", "cl", "rloc", "abslat")


def precompute_trig(lat, lon):
    """Per-aircraft trig/radius columns for the factored pair geometry."""
    rlat = jnp.radians(lat)
    return {
        "lat": lat, "lon": lon,
        "sl": jnp.sin(rlat), "cl": jnp.cos(rlat),
        "rloc": geo.rwgs84(lat),
        "abslat": jnp.abs(lat),
    }


#: rwgs84 as a function of sin^2 alone: with c^2 = 1 - s^2 the quotient
#: (a^4 c^2 + b^4 s^2) / (a^2 c^2 + b^2 s^2) is a^2 (1 - E4 s^2) / (1 - E2 s^2)
_E2 = 1.0 - (geo.B_WGS84 / geo.A_WGS84) ** 2
_E4 = 1.0 - (geo.B_WGS84 / geo.A_WGS84) ** 4


def _dwgs84_from_trig(sinphi):
    """The DIAMETER ``2 * geo.rwgs84`` from the sine of the latitude angle
    (the haversine arc's factor 2 rides in the constant).

    r = a sqrt(num / den) = a num rsqrt(num den), num = 1 - E4 s^2, den =
    1 - E2 s^2: one rsqrt and eight multiply-adds for a pair.  cos^2 is
    taken as 1 - sin^2: a sine that is off by a rounding moves the
    radius by parts in 1e10 (it varies by 0.3% over the globe)."""
    s2 = sinphi * sinphi
    num = 1.0 - _E4 * s2
    den = 1.0 - _E2 * s2
    return (2.0 * geo.A_WGS84) * num * jax.lax.rsqrt(num * den)


# sin(x) and sin(x/2) as the degree-7 odd Taylor polynomial in Horner form
# over x^2, the halving folded into the coefficients: x (c0 + x2 (c1 + x2
# (c2 + x2 c3))).  Error < 2e-4 at pi/2, < 1e-7 below 0.5 rad, and conflict
# geometry only needs precision for deltas far below that.
_SIN = (1.0, -1.0 / 6.0, 1.0 / 120.0, -1.0 / 5040.0)
_SIN_HALF = tuple(c / 2.0 ** (2 * k + 1) for k, c in enumerate(_SIN))


def _odd_poly(x, x2, c):
    return x * (c[0] + x2 * (c[1] + x2 * (c[2] + x2 * c[3])))


def tile_geometry(own, intr, same_hemisphere=False):
    """Pair distance [m] + bearing sin/cos for one tile.

    ``same_hemisphere=True`` (static) asserts no pair in the tile can
    have lat_o * lat_i < 0, eliding the reference's cross-equator radius
    branch (geo.py:117-128 ``res2``) — bit-identical for such tiles
    because the per-pair ``where`` would always pick ``res1``.  Callers
    must only set it when the assertion provably holds (ops/cd_sched.py
    derives it from the active fleet's latitude signs).

    ``own``/``intr`` are dicts of TRIG_FIELDS columns, broadcast-shaped
    (ownship vs intruder axes).  Mirrors geo.qdrdist_matrix semantics
    (including the radius-at-sum-of-latitudes quirk and the 1e-6 epsilon,
    geo.py:117-128) via the delta-polynomial scheme above.  Returns
    (dist, sin_qdr, cos_qdr).

    VPU-lean transcendentals (shared verbatim by the lax and Pallas
    backends, so they cannot drift): the arc length uses the odd-Taylor
    arcsin (kmath.asin_taylor — f32-exact for every distance that can
    flip a conflict/LoS flag, conservative beyond) and the bearing
    normalization uses one rsqrt instead of sqrt + two divides.

    Every visited tile runs this, and the kernel issues as many vector
    operations a cycle as the chip has slots for (PERF.md section 5), so
    its cost is its operation count: each line below is written for the
    fewest float32 operations that still evaluate the same expression
    (``scripts/kernel_bundles.py`` counts them on the v5e schedule, and
    ``tests/test_tile_geometry.py`` holds each quantity as close to its
    float64 value as the longer forms were).
    """
    sl_o, cl_o = own["sl"], own["cl"]
    sl_i, cl_i = intr["sl"], intr["cl"]

    # Mean DIAMETER (reference matrix quirk: the radius is evaluated at
    # lat_o + lat_i)
    slcl = sl_o * cl_i
    diam = _dwgs84_from_trig(slcl + cl_o * sl_i)
    if not same_hemisphere:
        denom = own["abslat"] + intr["abslat"] \
            + jnp.where(own["lat"] == 0.0, 1e-6, 0.0)
        diam2 = (own["abslat"] * (own["rloc"] + geo.A_WGS84)
                 + intr["abslat"] * (intr["rloc"] + geo.A_WGS84)) / denom
        diam = jnp.where(own["lat"] * intr["lat"] < 0.0, diam2, diam)

    # Coordinate deltas; dlon wrapped into [-180, 180] (the reference's
    # pairwise sin/cos are periodic — the polynomial needs the wrap).
    # floor(x + 1/2) is the chip's one-instruction rounding (round-half-
    # even costs seven); the two differ only where dlon is 180 to a
    # rounding, which then wraps to -180: the same angle.
    dlat = jnp.radians(intr["lat"] - own["lat"])
    dlon_deg = intr["lon"] - own["lon"]
    turns = jnp.floor(dlon_deg * (1.0 / 360.0) + 0.5)
    dlon = jnp.radians(dlon_deg - 360.0 * turns)

    dlat2 = dlat * dlat
    dlon2 = dlon * dlon
    sh_lat = _odd_poly(dlat, dlat2, _SIN_HALF)
    sh_lon = _odd_poly(dlon, dlon2, _SIN_HALF)
    sh_lon2 = sh_lon * sh_lon
    root = sh_lat * sh_lat + (cl_o * cl_i) * sh_lon2
    root = jnp.clip(root, 0.0, 1.0)
    dist = diam * kmath.asin_taylor(jnp.sqrt(root), root)

    # Bearing sin/cos as ratios — the angle is never formed.
    # qx = cl_o*sl_i - sl_o*cl_i*cos(dlon) = sin(dlat) + sl_o*cl_i*(1-cos
    # dlon), with 1-cos(dlon) = 2*sin^2(dlon/2): all well-conditioned terms.
    qy = _odd_poly(dlon, dlon2, _SIN) * cl_i
    qx = _odd_poly(dlat, dlat2, _SIN) + slcl * (2.0 * sh_lon2)
    # Clamp must stay f32-NORMAL (1e-60 underflows to 0 -> rsqrt=inf ->
    # NaN bearings for co-located pairs, silently dropping their
    # conflicts); 1e-37 keeps rsqrt finite and 0*rsqrt = 0 like the
    # 0/h of the division form.
    rh = jax.lax.rsqrt(jnp.maximum(qx * qx + qy * qy, 1e-37))
    return dist, qy * rh, qx * rh


def floor_speed2(dv2):
    """The reference's guard on the squared relative speed,
    ``where(abs(dv2) < 1e-6, 1e-6, dv2)``, as one maximum: equal for a sum
    of squares, which is never negative, NaN included."""
    return jnp.maximum(dv2, 1e-6)


def spatial_permutation(lat, lon, active):
    """[N] permutation ordering aircraft along a Morton (Z-order) curve.

    Blocks of the tiled pair space are contiguous SLOT ranges; slots are
    assigned in creation order, so without sorting every block's
    bounding box spans the whole airspace and the reachability skip
    never fires.  Sorting by interleaved 16-bit quantized lat/lon makes
    blocks spatially tight, which is what turns the O(N^2) pair sweep
    into ~O(N * local density) for spread-out traffic.  Inactive slots
    sort last (their block is skipped entirely).
    """
    def spread16(x):
        # 16 -> 32 bit Morton spread (standard bit tricks)
        x = x.astype(jnp.uint32)
        x = (x | (x << 8)) & jnp.uint32(0x00FF00FF)
        x = (x | (x << 4)) & jnp.uint32(0x0F0F0F0F)
        x = (x | (x << 2)) & jnp.uint32(0x33333333)
        x = (x | (x << 1)) & jnp.uint32(0x55555555)
        return x

    # 15-bit quantization -> 30-bit code, so the inactive sentinel fits
    # in int32 without x64
    qlat = jnp.clip((lat + 90.0) / 180.0 * 32767.0, 0, 32767)
    qlon = jnp.clip((lon + 180.0) / 360.0 * 32767.0, 0, 32767)
    code = spread16(qlat.astype(jnp.uint32)) \
        | (spread16(qlon.astype(jnp.uint32)) << 1)
    # inactive last: force their code above every active one
    key = jnp.where(active, code.astype(jnp.int32),
                    jnp.int32(0x7FFFFFFF))
    return jnp.argsort(key)


def run_spatially_sorted(kernel, lat, lon, trk, gs, alt, vs, gseast,
                         gsnorth, active, noreso, *args, perm=None,
                         extra_cols=None, **kw):
    """Run a tiled CD&R kernel in Morton-sorted slot space and map the
    results back to the caller's slot order.

    Shared by the lax and Pallas backends: permutes every per-aircraft
    input, invokes ``kernel`` (which must accept the same leading
    arguments plus *args/**kw and return a RowConflictData), then
    inverse-permutes the row outputs and maps the partner indices
    through the permutation (they are sorted-space positions).

    ``perm`` lets the caller supply a (possibly stale) cached permutation
    — exact for ANY permutation, since block reachability is recomputed
    from the true positions; staleness only loosens the block bounding
    boxes (core/asas.py carries it in ``AsasArrays.sort_perm``).
    """
    if perm is None:
        perm = spatial_permutation(lat, lon, active)
    # Invert by scatter: an O(N) store instead of a second O(N log^2 N)
    # TPU sort (argsort of 100k keys costs more than the CD kernel).
    inv = jnp.zeros_like(perm).at[perm].set(
        jnp.arange(perm.shape[0], dtype=perm.dtype))
    g = lambda a: a[perm]
    if extra_cols:
        kw = dict(kw, extra_cols={k: g(v) for k, v in extra_cols.items()})
    rd = kernel(g(lat), g(lon), g(trk), g(gs), g(alt), g(vs),
                g(gseast), g(gsnorth), g(active), g(noreso),
                *args, **kw)
    extra = None
    if not isinstance(rd, RowConflictData):    # (rd, swarm_sums) pair
        rd, extra = rd
    back = lambda a: a[inv]
    topk_idx = jnp.where(
        rd.topk_idx >= 0,
        perm[jnp.maximum(rd.topk_idx, 0)].astype(jnp.int32), -1)
    rd = RowConflictData(
        inconf=back(rd.inconf), tcpamax=back(rd.tcpamax),
        sum_dve=back(rd.sum_dve), sum_dvn=back(rd.sum_dvn),
        sum_dvv=back(rd.sum_dvv), tsolv=back(rd.tsolv),
        nconf=rd.nconf, nlos=rd.nlos,
        topk_idx=back(topk_idx), topk_tin=back(rd.topk_tin))
    if extra is not None:
        return rd, tuple(back(a) for a in extra)
    return rd


def block_summaries(lat, lon, gs, active, nb, block, alt=None, vs=None):
    """Per-block active-aircraft summaries: the ONLY quantities the
    reachability bound reads.  Returns a dict of [nb] arrays
    (latmin/latmax/lonmin/lonmax/gsmax, plus altmin/altmax/vsmax when
    ``alt``/``vs`` are given).  Split out of ``block_reachability`` so
    the spatial domain-decomposition mode (ops/cd_sched.py) can compute
    summaries for its OWN blocks locally, all-gather the [nb]-sized
    summary vectors (O(N/block) metadata, never the O(N) columns), and
    evaluate reachability rows from them with bit-identical math."""
    shape = (nb, block)
    blat = lat.reshape(shape)
    blon = lon.reshape(shape)
    bgs = gs.reshape(shape)
    act = active.reshape(shape)
    inf = jnp.asarray(jnp.inf, lat.dtype)
    out = dict(
        latmin=jnp.min(jnp.where(act, blat, inf), axis=1),
        latmax=jnp.max(jnp.where(act, blat, -inf), axis=1),
        lonmin=jnp.min(jnp.where(act, blon, inf), axis=1),
        lonmax=jnp.max(jnp.where(act, blon, -inf), axis=1),
        gsmax=jnp.max(jnp.where(act, bgs, 0.0), axis=1))
    if alt is not None:
        balt = alt.reshape(shape)
        bvs = jnp.abs(vs.reshape(shape))
        out.update(
            altmin=jnp.min(jnp.where(act, balt, inf), axis=1),
            altmax=jnp.max(jnp.where(act, balt, -inf), axis=1),
            vsmax=jnp.max(jnp.where(act, bvs, 0.0), axis=1))
    return out


def reachability_from_summaries(row, col, rpz, tlookahead, hpz=None,
                                min_reach_m=0.0, min_vreach_m=0.0,
                                margin_m=0.0):
    """[nbr, nbc] bool reachability between two summary sets (the
    pairwise half of ``block_reachability``; ``row`` and ``col`` may be
    the same dict — the classic square case — or a device's own rows
    against the gathered global columns in the spatial mesh mode).
    ``margin_m`` widens the horizontal bound (the spatial refresh's
    drift allowance when validating halo coverage ahead of time)."""
    latmin_r, latmax_r = row["latmin"], row["latmax"]
    latmin_c, latmax_c = col["latmin"], col["latmax"]
    maxabslat_r = jnp.maximum(jnp.abs(latmin_r), jnp.abs(latmax_r))
    maxabslat_c = jnp.maximum(jnp.abs(latmin_c), jnp.abs(latmax_c))

    dlat_gap = jnp.maximum(0.0, jnp.maximum(
        latmin_r[:, None] - latmax_c[None, :],
        latmin_c[None, :] - latmax_r[:, None]))
    # Circular longitude gap between the two [min, max] intervals:
    # linear gap, or around the back of the sphere, whichever is smaller
    lin_gap = jnp.maximum(0.0, jnp.maximum(
        row["lonmin"][:, None] - col["lonmax"][None, :],
        col["lonmin"][None, :] - row["lonmax"][:, None]))
    wrap_gap = jnp.maximum(0.0, 360.0 - (
        jnp.maximum(row["lonmax"][:, None], col["lonmax"][None, :])
        - jnp.minimum(row["lonmin"][:, None], col["lonmin"][None, :])))
    dlon_gap = jnp.minimum(lin_gap, wrap_gap)

    cos_lb = jnp.cos(jnp.radians(jnp.minimum(
        90.0, jnp.maximum(maxabslat_r[:, None], maxabslat_c[None, :]))))
    r_min = 6335000.0
    zonal = 2.0 * r_min * jnp.arcsin(jnp.clip(
        cos_lb * jnp.sin(jnp.radians(0.5 * jnp.minimum(dlon_gap, 360.0))),
        0.0, 1.0))
    merid = dlat_gap * 110000.0
    dist_lb = jnp.maximum(merid, zonal)
    thresh = rpz + tlookahead * (row["gsmax"][:, None]
                                 + col["gsmax"][None, :])
    # min_reach_m widens the bound for reductions over pairs beyond the
    # conflict horizon (the Swarm 7.5 nm neighbourhood: with a short
    # DTLOOK the conflict bound alone could skip genuine neighbours)
    thresh = jnp.maximum(thresh, min_reach_m) + margin_m
    reach = dist_lb <= thresh * 1.05
    if hpz is not None and "altmin" in row:
        altgap = jnp.maximum(0.0, jnp.maximum(
            row["altmin"][:, None] - col["altmax"][None, :],
            col["altmin"][None, :] - row["altmax"][:, None]))
        vthresh = hpz + tlookahead * (row["vsmax"][:, None]
                                      + col["vsmax"][None, :])
        # min_vreach_m: vertical analogue of min_reach_m (the Swarm
        # 1500 ft neighbourhood exceeds hpz, so the conflict bound alone
        # would skip genuine co-cruising neighbours one band up)
        vthresh = jnp.maximum(vthresh, min_vreach_m)
        reach = reach & (altgap <= vthresh * 1.05)
    return reach


def block_reachability(lat, lon, gs, active, nb, block, rpz, tlookahead,
                       alt=None, vs=None, hpz=None, min_reach_m=0.0,
                       min_vreach_m=0.0):
    """[nb, nb] bool: which block pairs can possibly contain a conflict
    or LoS.

    EXACT skip predicate (shared by the lax and Pallas tiled backends):
    a pair farther apart than ``rpz + tlookahead * (gsmax_r + gsmax_c)``
    has horizontal conflict-entry time >= (dist - rpz)/vrel > tlookahead
    and dist > rpz, so neither swconfl nor swlos can hold.

    With ``alt``/``vs``/``hpz`` given, an analogous EXACT vertical skip
    is AND-ed in: blocks whose altitude ranges are separated by more
    than ``hpz + tlookahead * (vsmax_r + vsmax_c)`` have vertical entry
    time ``tinver >= (altgap - hpz)/dvs > tlookahead`` (so
    ``tinconf = max(tinver, tinhor)`` exceeds the lookahead) and
    ``|dalt| > hpz`` (no LoS).  This is what makes the altitude-layered
    sort of ``cd_sched.stripe_sort_dest`` pay off: cruise blocks only
    reach ~one flight-level band instead of the whole column.

    Distance lower bounds between the blocks' active-aircraft bounding
    boxes, valid on the whole sphere:
    * meridional: the central angle of any pair is >= its latitude
      difference, and the reference radius is >= 6,335 km, so
      ``dlat_gap * 110,000 m/deg`` under-estimates every pair distance;
    * zonal: the minimum distance between two meridians ``dlon`` apart
      for points with |lat| <= L is ``2 R asin(cos L * sin(dlon/2))``
      (attained at +/-L) — correct at the poles (cos L -> 0: no skip
      from longitude alone) unlike a naive ``dlon * cos L`` scaling;
    * the longitude gap is CIRCULAR: min of the linear gap and the
      wrap-around gap, so clusters on both sides of the antimeridian
      are never falsely skipped.
    Empty blocks get +/-inf bounds -> infinite gap -> always skipped.
    """
    summ = block_summaries(lat, lon, gs, active, nb, block, alt=alt, vs=vs)
    return reachability_from_summaries(summ, summ, rpz, tlookahead,
                                       hpz=hpz if alt is not None else None,
                                       min_reach_m=min_reach_m,
                                       min_vreach_m=min_vreach_m)


def detect_resolve_tiled(lat, lon, trk, gs, alt, vs, gseast, gsnorth,
                         active, noreso, rpz, hpz, tlookahead, mvpcfg,
                         block=512, k_partners=8, prefilter=True,
                         spatial_sort=True, perm=None, extra_cols=None,
                         reso="mvp"):
    """One fused pass over all aircraft pairs in [block, block] tiles.

    Args mirror ``ops.cd.detect`` plus the MVP inputs; ``mvpcfg`` is a
    ``cr_mvp.MVPConfig``.  Returns a ``RowConflictData``.

    ``prefilter=True`` adds an EXACT block-level reachability skip — the
    TPU analogue of the reference C++ prefilter (asas.hpp:24-27): a tile
    whose two blocks' bounding boxes are farther apart than
    ``rpz + tlookahead * (gsmax_r + gsmax_c)`` cannot contain a conflict
    (horizontal entry time >= (dist - rpz)/vrel > tlookahead) or LoS
    (dist > rpz), so the column scan skips its work entirely via
    ``lax.cond`` — sequential scan iterations on TPU really do elide the
    untaken branch.  Distance lower bounds are conservative
    (meridional/zonal components at <110 km/deg, cos at the highest
    |lat| of either block; antimeridian-spanning blocks degrade to
    "never skip").  Computed tiles are bit-identical with/without.
    """
    n = lat.shape[0]
    if spatial_sort and n > block:
        # Morton-order the slots so blocks are spatially tight (the
        # reachability skip is useless on creation-ordered slots)
        return run_spatially_sorted(
            functools.partial(detect_resolve_tiled, block=block,
                              k_partners=k_partners, prefilter=prefilter,
                              spatial_sort=False, reso=reso),
            lat, lon, trk, gs, alt, vs, gseast, gsnorth, active, noreso,
            rpz, hpz, tlookahead, mvpcfg, perm=perm, extra_cols=extra_cols)
    block = min(block, max(n, 1))
    kk = min(k_partners, block)   # per-tile candidates merged into the top-K
    nb = -(-n // block)
    # With a single tile the cap kk=block=n is exact (at most n-1 partners
    # exist); across multiple tiles a sub-K per-tile candidate list would
    # silently drop hysteresis partners beyond `block`.
    if nb > 1 and block < k_partners:
        raise ValueError(
            f"block ({block}) must be >= k_partners ({k_partners}) "
            "when the pair space spans multiple tiles")
    npad = nb * block - n
    dtype = lat.dtype

    packed = {
        "alt": _pad1(alt, npad, 0.0), "vs": _pad1(vs, npad, 0.0),
        "gse": _pad1(gseast, npad, 0.0), "gsn": _pad1(gsnorth, npad, 0.0),
    }
    # Per-aircraft trig columns for the rank-1-factored pair geometry
    packed.update(precompute_trig(_pad1(lat, npad, 0.0),
                                  _pad1(lon, npad, 0.0)))
    # East/north velocity components for the CPA math (StateBasedCD.py:31-40
    # uses trk/gs; gseast/gsnorth are the same numbers assembled in traffic).
    trkrad = jnp.radians(_pad1(trk, npad, 0.0))
    packed["u"] = _pad1(gs, npad, 0.0) * jnp.sin(trkrad)
    packed["v"] = _pad1(gs, npad, 0.0) * jnp.cos(trkrad)
    # tas/gs ratio: Eby's TAS velocity basis (ve = tr*u); 1.0 when no
    # tas column is supplied (MVP never reads it)
    tas = (extra_cols or {}).get("tas")
    packed["tr"] = _pad1(jnp.ones_like(gs) if tas is None
                         else tas / jnp.maximum(gs, 1e-6), npad, 1.0)
    if reso == "swarm":
        packed["trk"] = _pad1(trk, npad, 0.0)
        packed["cas"] = _pad1((extra_cols or {}).get("cas", gs), npad, 0.0)
    if reso == "eby":
        # Exact TAS velocity columns (the lax dict has no slab-row
        # budget, unlike the Pallas kernels' tas/gs-ratio encoding,
        # so the gs->0 hover-in-headwind corner is exact here)
        tas_col = _pad1(gs if tas is None else tas, npad, 0.0)
        packed["ute"] = tas_col * jnp.sin(trkrad)
        packed["utn"] = tas_col * jnp.cos(trkrad)
    packed = {k: v.reshape(nb, block) for k, v in packed.items()}
    act_b = _pad1(active, npad, False).reshape(nb, block)
    nor_b = _pad1(noreso, npad, False).reshape(nb, block)

    r2 = rpz * rpz
    bigval = jnp.asarray(1e9, dtype)
    col_ids = jnp.arange(nb * block, dtype=jnp.int32).reshape(nb, block)

    # Reachability flags for the exact tile skip (see docstring); the
    # Swarm mode widens the bound to its 7.5 nm neighbourhood so short
    # lookaheads cannot skip genuine swarm neighbours.
    if reso == "swarm":
        from . import cr_swarm
        min_reach = cr_swarm.R_SWARM
    else:
        min_reach = 0.0
    reach = block_reachability(_pad1(lat, npad, 0.0),
                               _pad1(lon, npad, 0.0),
                               _pad1(gs, npad, 0.0), act_b.reshape(-1),
                               nb, block, rpz, tlookahead,
                               min_reach_m=min_reach)

    def tile(ri, ci, rows_active, carry):
        """Compute one [block, block] tile and fold it into the row carry."""
        (inconf, tcpamax, sdve, sdvn, sdvv, tsolv, nconf, nlos,
         topk_tin, topk_idx) = carry[:10]
        r = {k: v[ri] for k, v in packed.items()}
        c = {k: v[ci] for k, v in packed.items()}
        cols_active = act_b[ci]
        cols_noreso = nor_b[ci]

        # Pair mask: both active, not the same aircraft (generalised
        # diagonal exclusion, StateBasedCD.py:11,22).
        same = (ri * block + jnp.arange(block, dtype=jnp.int32))[:, None] \
            == col_ids[ci][None, :]
        pairmask = (rows_active[:, None] & cols_active[None, :]) & ~same
        excl = jnp.where(pairmask, 0.0, bigval)

        # Horizontal geometry — factored haversine (tile_geometry docstring)
        rT = {k: r[k][:, None] for k in TRIG_FIELDS}
        cT = {k: c[k][None, :] for k in TRIG_FIELDS}
        dist0, sinqdr, cosqdr = tile_geometry(rT, cT)
        dist = dist0 + excl
        dx = dist * sinqdr
        dy = dist * cosqdr

        # (own minus intruder and the maximum for the guard: the forms of
        # cd_pallas._tile_pairs, kept in lockstep)
        du = r["u"][:, None] - c["u"][None, :]
        dv = r["v"][:, None] - c["v"][None, :]
        dv2 = floor_speed2(du * du + dv * dv)
        # One rsqrt replaces the sqrt + two divides of the reference
        # formulation (1/vrel and 1/dv2 both derive from it)
        rvrel = jax.lax.rsqrt(dv2)

        tcpa = (du * dx + dv * dy) * (rvrel * rvrel) + excl
        dcpa2 = dist * dist - tcpa * tcpa * dv2
        swhorconf = dcpa2 < r2

        dtinhor = jnp.sqrt(jnp.maximum(0.0, r2 - dcpa2)) * rvrel
        # (no where(swhorconf, ..., +-1e8) here: swconfl asks for swhorconf
        # itself, and every reader of tinconf below is masked by swconfl)
        tinhor = tcpa - dtinhor
        touthor = tcpa + dtinhor

        # Vertical geometry
        dalt = c["alt"][None, :] - r["alt"][:, None] + excl
        dvs = c["vs"][None, :] - r["vs"][:, None]
        dvs = jnp.where(jnp.abs(dvs) < 1e-6, 1e-6, dvs)
        nrdvs = -1.0 / dvs            # one divide for both crossings
        tcrosshi = (dalt + hpz) * nrdvs
        tcrosslo = (dalt - hpz) * nrdvs
        tinver = jnp.minimum(tcrosshi, tcrosslo)
        toutver = jnp.maximum(tcrosshi, tcrosslo)

        tinconf = jnp.maximum(tinver, tinhor)
        toutconf = jnp.minimum(toutver, touthor)
        swconfl = (swhorconf & (tinconf <= toutconf) & (toutconf > 0.0)
                   & (tinconf < tlookahead) & pairmask)
        swlos = (dist < rpz) & (jnp.abs(dalt) < hpz) & pairmask

        if reso == "eby":
            # Eby pair displacement (cr_eby.pair_contrib) on the exact
            # TAS velocity columns
            from . import cr_eby
            dve_p, dvn_p, dvv_p = cr_eby.pair_contrib(
                dx, dy, c["alt"][None, :] - r["alt"][:, None],
                c["ute"][None, :] - r["ute"][:, None],
                c["utn"][None, :] - r["utn"][:, None],
                c["vs"][None, :] - r["vs"][:, None], mvpcfg.rpz_m)
            tsolv_p = jnp.full_like(dve_p, 1e9)
            mvpmask = swconfl          # Eby has no noreso handling
        else:
            # MVP pair contributions (shared core, MVP.py:149-231)
            dve_p, dvn_p, dvv_p, tsolv_p = cr_mvp.pair_contrib_trig(
                sinqdr, cosqdr, dist, tcpa, tinconf,
                c["alt"][None, :] - r["alt"][:, None],
                c["gse"][None, :] - r["gse"][:, None],
                c["gsn"][None, :] - r["gsn"][:, None],
                c["vs"][None, :] - r["vs"][:, None],
                mvpcfg)
            mvpmask = swconfl & ~cols_noreso[None, :]
        maskf = mvpmask.astype(dtype)

        if reso == "swarm":
            # Swarm neighbour sums (Swarm.py:47-66 via cr_swarm.pair_weight)
            from . import cr_swarm
            dtrk = (c["trk"][None, :] - r["trk"][:, None]
                    + 180.0) % 360.0 - 180.0
            w = cr_swarm.pair_weight(
                dx, dy, c["alt"][None, :] - r["alt"][:, None], dtrk,
                pairmask).astype(dtype)
            sw = carry[-1]
            sw = (sw[0] + jnp.sum(w, axis=1),
                  sw[1] + jnp.sum(w * c["cas"][None, :], axis=1),
                  sw[2] + jnp.sum(w * c["vs"][None, :], axis=1),
                  sw[3] + jnp.sum(w * dtrk, axis=1),
                  sw[4] + jnp.sum(w * dx, axis=1),
                  sw[5] + jnp.sum(w * dy, axis=1),
                  sw[6] + jnp.sum(w * c["alt"][None, :], axis=1))

        # Fold tile reductions into the row carry
        inconf = inconf | jnp.any(swconfl, axis=1)
        tcpamax = jnp.maximum(tcpamax, jnp.max(tcpa * swconfl, axis=1))
        sdve = sdve + jnp.sum(dve_p * maskf, axis=1)
        sdvn = sdvn + jnp.sum(dvn_p * maskf, axis=1)
        sdvv = sdvv + jnp.sum(dvv_p * maskf, axis=1)
        tsolv = jnp.minimum(
            tsolv, jnp.min(jnp.where(mvpmask, tsolv_p, 1e9), axis=1))
        nconf = nconf + jnp.sum(swconfl, dtype=jnp.int32)
        nlos = nlos + jnp.sum(swlos, dtype=jnp.int32)

        # Partner candidates: the kk most urgent (earliest conflict entry)
        # in this block, merged into the running per-ownship top-K.
        urg = jnp.where(swconfl, tinconf, bigval)
        negv, jbest = jax.lax.top_k(-urg, kk)             # [block, kk]
        cand_tin = -negv
        cand_idx = (ci * block + jbest).astype(jnp.int32)
        cat_tin = jnp.concatenate([topk_tin, cand_tin], axis=1)
        cat_idx = jnp.concatenate([topk_idx, cand_idx], axis=1)
        negv, sel = jax.lax.top_k(-cat_tin, kk)
        topk_tin = -negv
        topk_idx = jnp.take_along_axis(cat_idx, sel, axis=1)
        out = (inconf, tcpamax, sdve, sdvn, sdvv, tsolv, nconf, nlos,
               topk_tin, topk_idx)
        if reso == "swarm":
            out = out + (sw,)
        return (out, None)

    def row_block(ri):
        rows_active = act_b[ri]
        z = jnp.zeros((block,), dtype)
        carry0 = (jnp.zeros((block,), bool),              # inconf
                  jnp.zeros((block,), dtype),             # tcpamax (>=0, see
                  z, z, z,                                #   cd.detect note)
                  jnp.full((block,), 1e9, dtype),         # tsolv
                  jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                  jnp.full((block, kk), bigval, dtype),   # running top-K tin
                  jnp.full((block, kk), -1, jnp.int32))   # running top-K idx
        if reso == "swarm":
            carry0 = carry0 + ((z, z, z, z, z, z, z),)    # neighbour sums

        def colstep(carry, ci):
            if not prefilter:
                return tile(ri, ci, rows_active, carry)
            return jax.lax.cond(
                reach[ri, ci],
                lambda c: tile(ri, ci, rows_active, c)[0],
                lambda c: c, carry), None

        carry, _ = jax.lax.scan(colstep, carry0, jnp.arange(nb))
        return carry

    out = jax.lax.map(row_block, jnp.arange(nb))
    (inconf, tcpamax, sdve, sdvn, sdvv, tsolv, nconf, nlos,
     topk_tin, topk_idx) = out[:10]
    topk_idx = jnp.where(topk_tin < bigval, topk_idx, -1)

    unb = lambda a: a.reshape(nb * block, *a.shape[2:])[:n]
    rd = RowConflictData(
        inconf=unb(inconf), tcpamax=unb(tcpamax),
        sum_dve=unb(sdve), sum_dvn=unb(sdvn), sum_dvv=unb(sdvv),
        tsolv=unb(tsolv),
        nconf=jnp.sum(nconf, dtype=jnp.int32),
        nlos=jnp.sum(nlos, dtype=jnp.int32),
        topk_idx=unb(topk_idx), topk_tin=unb(topk_tin))
    if reso == "swarm":
        return rd, tuple(unb(a) for a in out[10])
    return rd


def topk_partners(rd, k):
    """The [N, K] partner candidates from a RowConflictData (-1 = empty).

    The running top-K merge in the scan already ordered them by urgency;
    this just pads/crops to the table width K.
    """
    idx = rd.topk_idx[:, :k]
    pad = k - idx.shape[1]
    if pad > 0:
        idx = jnp.pad(idx, ((0, 0), (0, pad)), constant_values=-1)
    return idx


def partner_keep(partners, lat, lon, gseast, gsnorth, trk, active,
                 rpz, rpz_m):
    """Resume-nav predicates on the partner table (reference asas.py:426-455).

    Same math as ``cr_mvp.resume_nav`` but on gathered [N, K] partner state
    instead of the [N, N] matrix.  Returns a bool [N, K] keep mask.
    """
    n = lat.shape[0]
    valid = partners >= 0
    j = jnp.clip(partners, 0, n - 1)

    latj, lonj = lat[j], lon[j]
    dist_e, dist_n = cr_mvp.resume_displacement(
        lat[:, None], lon[:, None], latj, lonj)
    vrel_e = gseast[j] - gseast[:, None]
    vrel_n = gsnorth[j] - gsnorth[:, None]

    alive = active[:, None] & active[j]
    keep = cr_mvp.resume_keep_core(dist_e, dist_n, vrel_e, vrel_n,
                                   trk[:, None], trk[j], alive, rpz, rpz_m)
    return keep & valid


def merge_partners(new_idx, old_idx, old_keep):
    """Merge fresh conflict partners with surviving previous partners.

    ``new_idx`` [N, K] (most urgent first, -1 empty) takes precedence; old
    partners surviving ``old_keep`` fill remaining slots, duplicates dropped.
    Returns the new [N, K] partner table.
    """
    k = new_idx.shape[1]
    old = jnp.where(old_keep, old_idx, -1)
    # Drop old entries that reappear among the new ones
    dup = jnp.any((old[:, :, None] == new_idx[:, None, :])
                  & (new_idx[:, None, :] >= 0), axis=2)
    old = jnp.where(dup, -1, old)

    cat = jnp.concatenate([new_idx, old], axis=1)        # [N, 2K]
    valid = cat >= 0
    pos = jnp.arange(2 * k, dtype=jnp.int32)[None, :]
    key = jnp.where(valid, pos, 2 * k + pos)             # valid first, stable
    order = jnp.argsort(key, axis=1)[:, :k]
    return jnp.take_along_axis(cat, order, axis=1)
