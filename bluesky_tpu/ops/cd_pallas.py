"""Pallas TPU kernel for the fused blockwise CD&R pass.

Same computation as ``ops/cd_tiled.py`` (which is the portable lax.scan
formulation and the golden-test oracle for this kernel): the N x N pair space
of the state-based conflict detection (reference
``bluesky/traffic/asas/StateBasedCD.py``) plus the MVP displacement sums
(reference ``MVP.py:14-143``) is computed in [block, block] tiles and reduced
per ownship, never materialising an N² array.

Here the tile loop is a real TPU kernel: the grid is (ownship blocks,
intruder blocks), each program reads two [_NF, block] slabs of packed
aircraft state from VMEM, evaluates the CPA geometry + MVP contribution on a
[block, block] tile with the VPU, and accumulates the per-ownship reductions
in-place in the output blocks (revisited across the intruder grid dimension
— the standard Pallas accumulation pattern).  The pair math is the *same
code* as the lax backend — ``cd_tiled.tile_geometry`` (rank-1-factored
haversine, VPU-lean: rsqrt bearings + odd-Taylor arcsin arc length from
``kmath``) and ``cr_mvp.pair_contrib_trig`` are shape-agnostic jnp and trace
straight into the kernel — so the tiled backends cannot drift apart.

Layout note: the tile is oriented **intruder-major**: intruders vary along
sublanes (axis 0), ownships along lanes (axis 1).  Per-ownship reductions
are then axis-0 reduces that land in the natural (1, block) lane layout of
the accumulator blocks; only the intruder-side operands need a
(1, block) -> (block, 1) relayout.

Partner candidates for resume-nav hysteresis: a running top-K (by earliest
conflict-entry time) is accumulated in the candidate output refs across the
intruder-block grid dimension — K-pass masked index-min extraction per tile,
skipped entirely for conflict-free tiles — so the kernel yields exactly the
K most urgent intruders per ownship, same as ``cd_tiled``'s carry-based
top-K merge.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import cd_tiled, cr_mvp
from .cd_tiled import RowConflictData, TRIG_FIELDS, block_reachability, \
    precompute_trig, tile_geometry

# Packed state row order for the [nb, 16, block] slabs: 6 trig/geometry
# columns (cd_tiled.TRIG_FIELDS), the gs velocity components + altitude
# columns, the track angle (resume-nav "bouncing" predicate), the
# tas/gs ratio (Eby builds its velocity from TAS: ve = tr*u), then the
# active and noreso masks.
#
# The "tr" row is OVERLOADED per resolver: Eby reads it as the tas/gs
# ratio, Swarm reads it as the calibrated airspeed (its alignment term,
# Swarm.py:75-84) — the two resolvers never combine, and reusing the
# slot keeps the slab at 16 rows (a 17th would break the whole-vreg
# alignment of the sched kernel's Element-indexed slabs and cost ~25%
# more slab DMA in every mode).
_FIELDS = TRIG_FIELDS + ("u", "v", "alt", "vs", "gse", "gsn", "trk",
                         "tr", "active", "noreso")
_NF = len(_FIELDS)
_IDX = {k: i for i, k in enumerate(_FIELDS)}
_BIG = 1e9

#: number of per-ownship Swarm neighbour-sum accumulators appended to
#: the kernel outputs when reso == "swarm": w, w*cas, w*vs, w*dtrk,
#: w*dx, w*dy, w*alt (cr_swarm.resolve_from_sums input order).
_N_SWARM = 7

#: Identity elements of the 10 accumulator outputs, in output-tuple order:
#: inconf, tcpamax, sdve, sdvn, sdvv, tsolv, ncnt, lcnt, ctin, cidx.
#: Single source of truth for every kernel's accumulator-init block.
_ACC_NEUTRAL = (0.0, 0.0, 0.0, 0.0, 0.0, _BIG, 0.0, 0.0, _BIG, 2**30)


def shard_map(body, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (the bodies use
    collectives the checker cannot see through)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _init_accumulators(refs, block, kk):
    """Write the identity element into each accumulator ref (10 refs in
    output order)."""
    for ref, v in zip(refs[:8], _ACC_NEUTRAL[:8]):
        ref[0] = jnp.full((1, block), v, jnp.float32)
    refs[8][0] = jnp.full((kk, block), _ACC_NEUTRAL[8], jnp.float32)
    refs[9][0] = jnp.full((kk, block), _ACC_NEUTRAL[9], jnp.int32)


def _kernel(reach_ref, row0_ref, own_ref, intr_ref,
            inconf_ref, tcpamax_ref, sdve_ref, sdvn_ref, sdvv_ref,
            tsolv_ref, ncnt_ref, lcnt_ref, ctin_ref, cidx_ref,
            *swarm_refs, block, kk, cpp, rpz, hpz, tlookahead, mvpcfg,
            same_hemi=False, reso="mvp", rstride=1):
    ib = pl.program_id(0)
    jp = pl.program_id(1)      # program handles cpp column tiles
    # Global row id of local row i is row0 + i*rstride (0/1 except
    # under shard_map, where each device owns a strided row subset of
    # the global grid but column/partner ids stay global; the stride
    # interleaves rows across devices for load balance).  col0 offsets
    # intruder ids the same way when the COLUMN slabs are a local halo
    # window rather than the full grid (the domain-decomposition mesh
    # mode of ops/cd_sched.py): DMA/reach indices stay local, global
    # ids = (col0 + local block) * block + lane.
    row0 = row0_ref[0, 0]
    col0 = row0_ref[0, 1]

    # Initialise the accumulators on the first intruder program; the
    # tile compute below is skipped entirely for unreachable tiles, so
    # the init must not depend on it.  Accumulating t >= 0 maxima into
    # 0 / minima into BIG reproduces the former set-at-jb==0 semantics.
    @pl.when(jp == 0)
    def _():
        _init_accumulators((inconf_ref, tcpamax_ref, sdve_ref, sdvn_ref,
                            sdvv_ref, tsolv_ref, ncnt_ref, lcnt_ref,
                            ctin_ref, cidx_ref), block, kk)
        for ref in swarm_refs:
            ref[0] = jnp.zeros((1, block), jnp.float32)

    # Exact block-level reachability skip (cd_tiled.block_reachability):
    # a scalar-predicated branch in Mosaic, so unreachable tiles cost no
    # VPU work.  The cpp sub-tiles run sequentially in one program,
    # amortizing grid/DMA overhead (skipped sub-tiles still skip).
    # reach_ref holds a BIT-PACKED 8-row SMEM window around the current
    # row (the whole [nb, nb] matrix is 61 MB of SMEM at N=1M, and even
    # one unpacked row breaks the SMEM budget there; 8-row granularity
    # because SMEM block rows must be 8-divisible).
    for k in range(cpp):
        jb = jp * cpp + k

        @pl.when(((reach_ref[ib % 8, jb // 32] >> (jb % 32)) & 1) > 0)
        def _compute(k=k, jb=jb):
            _tile_body(ib, col0 + jb, k, own_ref, intr_ref, inconf_ref,
                       tcpamax_ref, sdve_ref, sdvn_ref, sdvv_ref,
                       tsolv_ref, ncnt_ref, lcnt_ref, ctin_ref,
                       cidx_ref, block=block, kk=kk, rpz=rpz, hpz=hpz,
                       tlookahead=tlookahead, mvpcfg=mvpcfg,
                       same_hemi=same_hemi, reso=reso, row_off=row0,
                       row_stride=rstride, swarm_refs=swarm_refs or None)


def _tile_body(ib, jb, ksub, own_ref, intr_ref,
               inconf_ref, tcpamax_ref, sdve_ref, sdvn_ref, sdvv_ref,
               tsolv_ref, ncnt_ref, lcnt_ref, ctin_ref, cidx_ref,
               *, block, kk, rpz, hpz, tlookahead, mvpcfg,
               same_hemi=False, resume_refs=None, rpz_m=None, reso="mvp",
               row_off=0, row_stride=1, swarm_refs=None):
    oslab = own_ref[0]                                    # (_NF, block)
    islab_t = intr_ref[ksub].T                            # (block, _NF): ONE
    # lane->sublane relayout shared by all intruder columns

    def own(k):            # ownship operand, varies along lanes: (1, block)
        return oslab[_IDX[k]:_IDX[k] + 1, :]

    def intr(k):           # intruder operand, varies along sublanes
        return islab_t[:, _IDX[k]:_IDX[k] + 1]            # (block, 1)

    gid_own = (row_off + ib * row_stride) * block \
        + jax.lax.broadcasted_iota(
            jnp.int32, (1, block), 1)                     # ownships on lanes
    gid_int = jb * block + jax.lax.broadcasted_iota(
        jnp.int32, (block, 1), 0)                         # intruders sublanes
    act_o = own("active") > 0.5                           # (1, block)
    act_i = intr("active") > 0.5                          # (block, 1)
    pairmask = (act_o & act_i) & (gid_own != gid_int)

    # All-inactive tiles (sentinel/padding worklist entries, empty blocks)
    # contribute nothing — skip the whole geometry for the cost of one
    # OR-reduce.
    @pl.when(jnp.any(pairmask))
    def _live_tile():
        _tile_pairs(pairmask, gid_int, own, intr, inconf_ref, tcpamax_ref,
                    sdve_ref, sdvn_ref, sdvv_ref, tsolv_ref, ncnt_ref,
                    lcnt_ref, ctin_ref, cidx_ref, kk=kk, rpz=rpz, hpz=hpz,
                    tlookahead=tlookahead, mvpcfg=mvpcfg,
                    same_hemi=same_hemi, jb=jb, resume_refs=resume_refs,
                    rpz_m=rpz_m, reso=reso, swarm_refs=swarm_refs)


def _tile_pairs(pairmask, gid_int, own, intr,
                inconf_ref, tcpamax_ref, sdve_ref, sdvn_ref, sdvv_ref,
                tsolv_ref, ncnt_ref, lcnt_ref, ctin_ref, cidx_ref,
                *, kk, rpz, hpz, tlookahead, mvpcfg, same_hemi=False,
                jb=None, resume_refs=None, rpz_m=None, reso="mvp",
                swarm_refs=None):
    block = pairmask.shape[1]
    excl = jnp.where(pairmask, 0.0, _BIG)

    # Horizontal geometry — the factored haversine (cd_tiled.tile_geometry),
    # evaluated [intruder, ownship] so per-ownship reductions are axis 0.
    trig_o = {k: own(k) for k in TRIG_FIELDS}
    trig_i = {k: intr(k) for k in TRIG_FIELDS}
    dist0, sinqdr, cosqdr = tile_geometry(trig_o, trig_i,
                                          same_hemisphere=same_hemi)
    dist = dist0 + excl
    dx = dist * sinqdr
    dy = dist * cosqdr

    # Closing velocity, own minus intruder: the sign tcpa wants, so no
    # negation is spent on a pair
    du = own("u") - intr("u")
    dv = own("v") - intr("v")
    dv2 = cd_tiled.floor_speed2(du * du + dv * dv)
    # Same rsqrt-based CPA math as cd_tiled.tile — kept in lockstep
    rvrel = jax.lax.rsqrt(dv2)

    tcpa = (du * dx + dv * dy) * (rvrel * rvrel) + excl
    dcpa2 = dist * dist - tcpa * tcpa * dv2
    r2 = rpz * rpz
    swhorconf = dcpa2 < r2

    dtinhor = jnp.sqrt(jnp.maximum(0.0, r2 - dcpa2)) * rvrel
    # (no where(swhorconf, ..., +-1e8) here: swconfl asks for swhorconf
    # itself, and every reader of tinconf below is masked by swconfl)
    tinhor = tcpa - dtinhor
    touthor = tcpa + dtinhor

    dalt = intr("alt") - own("alt") + excl
    dvs = intr("vs") - own("vs")
    dvs = jnp.where(jnp.abs(dvs) < 1e-6, 1e-6, dvs)
    nrdvs = -1.0 / dvs
    tcrosshi = (dalt + hpz) * nrdvs
    tcrosslo = (dalt - hpz) * nrdvs
    tinver = jnp.minimum(tcrosshi, tcrosslo)
    toutver = jnp.maximum(tcrosshi, tcrosslo)

    tinconf = jnp.maximum(tinver, tinhor)
    toutconf = jnp.minimum(toutver, touthor)
    swconfl = (swhorconf & (tinconf <= toutconf) & (toutconf > 0.0)
               & (tinconf < tlookahead) & pairmask)
    swlos = (dist < rpz) & (jnp.abs(dalt) < hpz) & pairmask

    # Everything past the flags only matters when the tile has at least one
    # conflict or LoS pair: every accumulator update below is then a no-op
    # (max with 0, sum with 0, min with BIG).  Conflicts are rare even in
    # *reachable* tiles, so predicating the whole MVP + reduction tail on a
    # single any-hit flag cuts the common tile to the core CPA geometry.
    # The gate hands back whether a CONFLICT was among the hits (read off
    # the per-ownship row it reduces anyway), so the candidate gates below
    # ask no second whole-tile reduction of a tile that had no hit.
    def _accumulate():
        if reso == "eby":
            # Eby pair displacement (cr_eby.pair_contrib — same code as
            # the dense matrix path) built on TAS velocities via the
            # per-aircraft tas/gs ratio column: ve = tr*u.
            from . import cr_eby
            dve_p, dvn_p, dvv_p = cr_eby.pair_contrib(
                dx, dy, intr("alt") - own("alt"),
                intr("tr") * intr("u") - own("tr") * own("u"),
                intr("tr") * intr("v") - own("tr") * own("v"),
                intr("vs") - own("vs"), mvpcfg.rpz_m)
            tsolv_p = jnp.full_like(dve_p, _BIG)
            mvpmask = swconfl           # Eby has no noreso handling
        else:
            dve_p, dvn_p, dvv_p, tsolv_p = cr_mvp.pair_contrib_trig(
                sinqdr, cosqdr, dist, tcpa, tinconf,
                intr("alt") - own("alt"), intr("gse") - own("gse"),
                intr("gsn") - own("gsn"), intr("vs") - own("vs"), mvpcfg)
            nor_i = intr("noreso") > 0.5
            mvpmask = swconfl & ~nor_i
        maskf = mvpmask.astype(dist.dtype)

        conff = swconfl.astype(dist.dtype)
        t_inconf = jnp.max(conff, axis=0, keepdims=True)
        t_tcpamax = jnp.max(tcpa * conff, axis=0, keepdims=True)
        t_sdve = jnp.sum(dve_p * maskf, axis=0, keepdims=True)
        t_sdvn = jnp.sum(dvn_p * maskf, axis=0, keepdims=True)
        t_sdvv = jnp.sum(dvv_p * maskf, axis=0, keepdims=True)
        t_tsolv = jnp.min(jnp.where(mvpmask, tsolv_p, _BIG),
                          axis=0, keepdims=True)
        t_ncnt = jnp.sum(conff, axis=0, keepdims=True)
        t_lcnt = jnp.sum(swlos.astype(dist.dtype), axis=0, keepdims=True)

        inconf_ref[0] = jnp.maximum(inconf_ref[0], t_inconf)
        tcpamax_ref[0] = jnp.maximum(tcpamax_ref[0], t_tcpamax)
        sdve_ref[0] = sdve_ref[0] + t_sdve
        sdvn_ref[0] = sdvn_ref[0] + t_sdvn
        sdvv_ref[0] = sdvv_ref[0] + t_sdvv
        tsolv_ref[0] = jnp.minimum(tsolv_ref[0], t_tsolv)
        ncnt_ref[0] = ncnt_ref[0] + t_ncnt
        lcnt_ref[0] = lcnt_ref[0] + t_lcnt
        return jnp.max(t_inconf)

    # (a float through the gate: Mosaic's cond carries no bool)
    any_conf = jax.lax.cond(jnp.any(swconfl | swlos), _accumulate,
                            lambda: jnp.float32(0.0)) > 0.5

    if reso == "swarm":
        # Swarm neighbour sums (reference Swarm.py:47-66 via
        # cr_swarm.pair_weight — the same predicate the lax tiled and
        # dense paths use, so the three backends cannot drift).  The
        # neighbourhood (7.5 nm / 1500 ft / <90 deg track) is far rarer
        # than reachability, so the whole accumulation is predicated on
        # one any-neighbour flag.  The "tr" slab row carries cas in
        # swarm mode (see the _FIELDS note).
        from . import cr_swarm
        dtrk = (intr("trk") - own("trk") + 180.0) % 360.0 - 180.0
        dalt_raw = intr("alt") - own("alt")
        w_mask = cr_swarm.pair_weight(dx, dy, dalt_raw, dtrk, pairmask)

        @pl.when(jnp.any(w_mask))
        def _swarm_sums():
            wf = w_mask.astype(dist.dtype)
            terms = (wf, wf * intr("tr"), wf * intr("vs"), wf * dtrk,
                     wf * dx, wf * dy, wf * intr("alt"))
            for ref, t in zip(swarm_refs, terms):
                ref[0] = ref[0] + jnp.sum(t, axis=0, keepdims=True)

    # In-kernel resume-nav: evaluate the keep predicate for every OLD
    # partner pair this tile visits (reference asas.py:426-455 — the
    # same cr_mvp.resume_keep_core the host paths use, so the math
    # cannot drift).  The tile already holds all required pair state, so
    # this replaces the [N,K] gather storm of the host-side
    # ``cd_tiled.partner_keep`` (measured ~60 ms/interval at N=100k with
    # TPU gathers serializing at ~30 ns/element).  Pairs OUTSIDE the
    # visited windows are provably non-conflicting within the lookahead
    # AND out of LoS; the kernel path releases them (no keep bit) — a
    # documented, bounded divergence from the dense path, which can hold
    # a far-but-approaching pair engaged until CPA (such pairs re-engage
    # on their next detection).
    def _extract_merge(cand_mask):
        """Fold this tile's candidate conflicts (cand_mask) into the
        running per-ownship top-kk held in the candidate refs.
        Extraction is masked index-min passes (argmin has no stable
        Mosaic lowering); the pass count is bounded by the tile's MAX
        per-ownship candidate count (usually 1-3 ≪ kk) — passes beyond
        it would only extract the BIG sentinel, which is exactly what
        the unrun passes' slots hold."""
        urg0 = jnp.where(cand_mask, tinconf, _BIG)
        cmax = jnp.max(jnp.sum(cand_mask.astype(jnp.int32), axis=0))
        pio = jax.lax.broadcasted_iota(jnp.int32, (kk, block), 0)
        carry0 = (urg0,
                  jnp.full((kk, block), _BIG, urg0.dtype),
                  jnp.full((kk, block), 2**30, jnp.int32))

        def extract(p, carry):
            urg, tins, idxs = carry
            minv = jnp.min(urg, axis=0, keepdims=True)    # (1, block)
            jloc = jnp.min(jnp.where(urg == minv, gid_int, jnp.int32(2**30)),
                           axis=0, keepdims=True)
            tins = jnp.where(pio == p, minv, tins)
            idxs = jnp.where(pio == p, jloc, idxs)
            urg = jnp.where(gid_int == jloc, _BIG, urg)
            return urg, tins, idxs

        _, tins, idxs = jax.lax.fori_loop(
            0, jnp.minimum(cmax, kk), extract, carry0)
        cat_t = jnp.concatenate([ctin_ref[0], tins], axis=0)    # (2kk, block)
        cat_i = jnp.concatenate([cidx_ref[0], idxs], axis=0)
        rio = jax.lax.broadcasted_iota(jnp.int32, (2 * kk, block), 0)
        new_t, new_i = [], []
        for _s in range(kk):
            minv = jnp.min(cat_t, axis=0, keepdims=True)
            rloc = jnp.min(jnp.where(cat_t == minv, rio, jnp.int32(2**30)),
                           axis=0, keepdims=True)
            sel = jnp.min(jnp.where(rio == rloc, cat_i, jnp.int32(2**30)),
                          axis=0, keepdims=True)
            new_t.append(minv)
            new_i.append(sel)
            cat_t = jnp.where(rio == rloc, _BIG, cat_t)
        ctin_ref[0] = jnp.concatenate(new_t, axis=0)
        cidx_ref[0] = jnp.concatenate(new_i, axis=0)

    if resume_refs is None:
        # Partner candidates only; conflict-free tiles skip entirely.
        @pl.when(any_conf)
        def _():
            _extract_merge(swconfl)
    else:
        # In-kernel resume-nav (reference asas.py:409-471, the same
        # cr_mvp.resume_keep_core the host paths use so the math cannot
        # drift): evaluate the keep predicate for every visited pair,
        # (a) OR it into the keep bits of OLD partner pairs present in
        # this tile, and (b) filter the FRESH candidates with it — the
        # dense path prunes the union (old | swconfl) through resume_nav
        # each interval, so a fresh conflict already past CPA must not
        # enter the table either.  Pairs OUTSIDE the visited windows are
        # provably non-conflicting within the lookahead AND out of LoS;
        # the kernel path releases them — a documented, bounded
        # divergence from the dense path, which can hold a
        # far-but-approaching pair engaged until CPA (such pairs
        # re-engage on their next detection).
        pold_ref, keep_ref = resume_refs
        pold = pold_ref[0]                        # (kk, block) sorted ids
        in_rng = (pold >= jb * block) & (pold < (jb + 1) * block)

        @pl.when(jnp.any(in_rng) | any_conf)
        def _resume_and_candidates():
            # Flat-earth displacement of cr_mvp.resume_displacement from
            # per-aircraft trig: cos(0.5*(lat_o+lat_i)) =
            # sqrt((1+cos(lat_o+lat_i))/2), exact for |lat sum| <= 180.
            cos_sum = own("cl") * intr("cl") - own("sl") * intr("sl")
            cos_half = jnp.sqrt(jnp.maximum(0.5 + 0.5 * cos_sum, 0.0))
            from . import geo
            dist_e = geo.REARTH * jnp.radians(intr("lon") - own("lon")) \
                * cos_half
            dist_n = geo.REARTH * jnp.radians(intr("lat") - own("lat"))
            vrel_e = intr("gse") - own("gse")
            vrel_n = intr("gsn") - own("gsn")
            keep_pair = cr_mvp.resume_keep_core(
                dist_e, dist_n, vrel_e, vrel_n, own("trk"), intr("trk"),
                pairmask, rpz, rpz_m)

            @pl.when(jnp.any(in_rng))
            def _keep_old():
                for k in range(kk):
                    match = (gid_int == pold[k:k + 1, :]) & keep_pair
                    hit = jnp.max(match.astype(jnp.float32), axis=0,
                                  keepdims=True)
                    keep_ref[0, k:k + 1] = jnp.maximum(
                        keep_ref[0, k:k + 1], hit)

            @pl.when(any_conf)
            def _fresh():
                _extract_merge(swconfl & keep_pair)


def _merge_partners_block(pold_ref, keep_ref, ctin_ref, cidx_ref,
                          pnew_ref, pact_ref, kk):
    """In-kernel partner merge for one ownship block (kernel-space
    equivalent of ``cd_tiled.merge_partners`` + the active flag).

    Fresh conflict candidates (already urgency-ordered in the ctin/cidx
    refs) take the leading slots; old partners surviving their keep bit
    fill the rest in original slot order; duplicates are dropped.  The
    compaction is ``kk`` masked-min selection passes over the (2kk,
    block) concatenation — pure VPU, no sort."""
    big_i = jnp.int32(2 ** 30)
    new_ids = jnp.where(ctin_ref[0] < _BIG, cidx_ref[0], -1)   # (kk, block)
    old_ids = jnp.where(keep_ref[0] > 0.5, pold_ref[0], -1)
    dup = jnp.zeros_like(old_ids, dtype=bool)
    for m in range(kk):
        nm = new_ids[m:m + 1, :]
        dup = dup | ((old_ids == nm) & (nm >= 0))
    old_ids = jnp.where(dup, -1, old_ids)

    cat = jnp.concatenate([new_ids, old_ids], axis=0)          # (2kk, block)
    rio = jax.lax.broadcasted_iota(jnp.int32, cat.shape, 0)
    key = jnp.where(cat >= 0, rio, big_i)
    outs = []
    for _s in range(kk):
        m = jnp.min(key, axis=0, keepdims=True)
        val = jnp.min(jnp.where(key == m, cat, big_i), axis=0,
                      keepdims=True)
        outs.append(jnp.where(m < big_i, val, -1))
        key = jnp.where(key == m, big_i, key)
    pnew = jnp.concatenate(outs, axis=0)
    pnew_ref[0] = pnew
    pact_ref[0] = jnp.max((pnew >= 0).astype(jnp.float32), axis=0,
                          keepdims=True)


def _kernel_resume(reach_ref, row0_ref, own_ref, intr_ref, pold_ref,
                   inconf_ref, tcpamax_ref, sdve_ref, sdvn_ref, sdvv_ref,
                   tsolv_ref, ncnt_ref, lcnt_ref, ctin_ref, cidx_ref,
                   keep_ref, pnew_ref, pact_ref,
                   *swarm_refs, block, kk, cpp, rpz, hpz, tlookahead,
                   mvpcfg, rpz_m, same_hemi=False, reso="mvp", rstride=1):
    """Full-grid kernel with in-kernel resume-nav (the sparse scheduler's
    overflow fallback): same tile sweep as ``_kernel`` plus the keep
    evaluation per visited tile and the partner merge on the last
    intruder program."""
    ib = pl.program_id(0)
    jp = pl.program_id(1)
    row0 = row0_ref[0, 0]
    col0 = row0_ref[0, 1]

    @pl.when(jp == 0)
    def _():
        _init_accumulators((inconf_ref, tcpamax_ref, sdve_ref, sdvn_ref,
                            sdvv_ref, tsolv_ref, ncnt_ref, lcnt_ref,
                            ctin_ref, cidx_ref), block, kk)
        keep_ref[0] = jnp.zeros((kk, block), jnp.float32)
        for ref in swarm_refs:
            ref[0] = jnp.zeros((1, block), jnp.float32)

    for k in range(cpp):
        jb = jp * cpp + k

        @pl.when(((reach_ref[ib % 8, jb // 32] >> (jb % 32)) & 1) > 0)
        def _compute(k=k, jb=jb):
            _tile_body(ib, col0 + jb, k, own_ref, intr_ref, inconf_ref,
                       tcpamax_ref, sdve_ref, sdvn_ref, sdvv_ref,
                       tsolv_ref, ncnt_ref, lcnt_ref, ctin_ref,
                       cidx_ref, block=block, kk=kk, rpz=rpz, hpz=hpz,
                       tlookahead=tlookahead, mvpcfg=mvpcfg,
                       same_hemi=same_hemi,
                       resume_refs=(pold_ref, keep_ref), rpz_m=rpz_m,
                       reso=reso, row_off=row0, row_stride=rstride,
                       swarm_refs=swarm_refs or None)

    @pl.when(jp == pl.num_programs(1) - 1)
    def _finish():
        _merge_partners_block(pold_ref, keep_ref, ctin_ref, cidx_ref,
                              pnew_ref, pact_ref, kk)


def _kernel_cand(own_ref, cand_ref, cgid_ref,
                 inconf_ref, tcpamax_ref, sdve_ref, sdvn_ref, sdvv_ref,
                 tsolv_ref, ncnt_ref, lcnt_ref, ctin_ref, cidx_ref,
                 *, block, kk, rpz, hpz, tlookahead, mvpcfg, reso="mvp"):
    """Candidate-list variant: ownship block i vs its GATHERED candidate
    aircraft (sub-chunk j of the per-block candidate table).

    Tiles are (candidate, ownship)-shaped exactly like the block kernels,
    but the intruder axis holds only aircraft that passed the exact
    point-to-bounding-box reachability bound (_build_candidates) — the
    pair count approaches the physics floor (aircraft within
    rpz + tlookahead * closing speed) instead of the block-granular
    superset.  Candidate global ids ride along in ``cgid_ref`` (sentinel
    entries point at the all-inactive padding row and mask out).
    """
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        _init_accumulators((inconf_ref, tcpamax_ref, sdve_ref, sdvn_ref,
                            sdvv_ref, tsolv_ref, ncnt_ref, lcnt_ref,
                            ctin_ref, cidx_ref), block, kk)

    oslab = own_ref[0]                                    # (_NF, block)
    cslab_t = cand_ref[0].T                               # (block, _NF)

    def own(k):
        return oslab[_IDX[k]:_IDX[k] + 1, :]

    def intr(k):
        return cslab_t[:, _IDX[k]:_IDX[k] + 1]

    gid_own = i * block + jax.lax.broadcasted_iota(
        jnp.int32, (1, block), 1)
    gid_int = cgid_ref[0].T                               # (block, 1)
    act_o = own("active") > 0.5
    act_i = intr("active") > 0.5
    pairmask = (act_o & act_i) & (gid_own != gid_int)

    @pl.when(jnp.any(pairmask))
    def _live_tile():
        _tile_pairs(pairmask, gid_int, own, intr, inconf_ref, tcpamax_ref,
                    sdve_ref, sdvn_ref, sdvv_ref, tsolv_ref, ncnt_ref,
                    lcnt_ref, ctin_ref, cidx_ref, kk=kk, rpz=rpz, hpz=hpz,
                    tlookahead=tlookahead, mvpcfg=mvpcfg, reso=reso)


def _build_candidates(lat, lon, gs, active, nb, block, c_cap, rpz,
                      tlookahead, sub=32):
    """Per-ownship-block candidate aircraft: exact bbox-to-bbox bound at
    ``sub``-aircraft granularity.

    For each ownship block's active bounding box, a sub-block of ``sub``
    consecutive (Morton-sorted) aircraft is a candidate iff the
    conservative distance lower bound between the boxes is within
    ``rpz + tlookahead * (gsmax_row + gsmax_sub)`` — the same exact skip
    predicate as ``block_reachability`` evaluated 8x finer.  Candidate
    sub-block ids are compacted per row with a SORT (ascending id keys),
    not a scatter — TPU scatters over the [nb, n] domain serialize into
    hundreds of ms, while a batched [nb, nb*block/sub] sort is
    milliseconds — then expanded to aircraft ids.

    Returns ``(cand [nb, c_cap] int32, row_over [nb] bool)``; entries
    beyond a row's count hold the sentinel id ``n`` (the all-inactive
    padding column).  Rows whose candidate count exceeds c_cap are
    OVERFLOW rows: their table is forced all-sentinel (so the candidate
    kernel skips them for free) and the caller must cover them with a
    row-masked full-grid pass — the straddle blocks of the Morton curve
    (bounding boxes spanning Z-order jumps) make a handful of such rows
    unavoidable at any practical capacity.
    """
    n = lat.shape[0]                       # nb*block, padded sorted space
    nsb = n // sub                         # number of sub-blocks
    c_sub = c_cap // sub

    def boxes(shape):
        inf = jnp.asarray(jnp.inf, lat.dtype)
        blat, blon = lat.reshape(shape), lon.reshape(shape)
        act = active.reshape(shape)
        return (jnp.min(jnp.where(act, blat, inf), axis=1),
                jnp.max(jnp.where(act, blat, -inf), axis=1),
                jnp.min(jnp.where(act, blon, inf), axis=1),
                jnp.max(jnp.where(act, blon, -inf), axis=1),
                jnp.max(jnp.where(act, gs.reshape(shape), 0.0), axis=1),
                jnp.any(act, axis=1))

    rlatmin, rlatmax, rlonmin, rlonmax, rgsmax, _ = boxes((nb, block))
    slatmin, slatmax, slonmin, slonmax, sgsmax, s_any = boxes((nsb, sub))
    r_abslat = jnp.maximum(jnp.abs(rlatmin), jnp.abs(rlatmax))
    s_abslat = jnp.maximum(jnp.abs(slatmin), jnp.abs(slatmax))

    # [nb, nsb] box-to-box gaps — same conservative bound family as
    # block_reachability (meridional <110 km/deg; zonal via the min
    # meridian distance at the highest |lat|; circular longitude gap)
    dlat_gap = jnp.maximum(0.0, jnp.maximum(
        rlatmin[:, None] - slatmax[None, :],
        slatmin[None, :] - rlatmax[:, None]))
    lin_gap = jnp.maximum(0.0, jnp.maximum(
        rlonmin[:, None] - slonmax[None, :],
        slonmin[None, :] - rlonmax[:, None]))
    wrap_gap = jnp.maximum(0.0, 360.0 - (
        jnp.maximum(rlonmax[:, None], slonmax[None, :])
        - jnp.minimum(rlonmin[:, None], slonmin[None, :])))
    dlon_gap = jnp.minimum(lin_gap, wrap_gap)
    cos_lb = jnp.cos(jnp.radians(jnp.minimum(
        90.0, jnp.maximum(r_abslat[:, None], s_abslat[None, :]))))
    zonal = 2.0 * 6335000.0 * jnp.arcsin(jnp.clip(
        cos_lb * jnp.sin(jnp.radians(0.5 * jnp.minimum(dlon_gap, 360.0))),
        0.0, 1.0))
    dist_lb = jnp.maximum(dlat_gap * 110000.0, zonal)
    thresh = rpz + tlookahead * (rgsmax[:, None] + sgsmax[None, :])
    mask = (dist_lb <= thresh * 1.05) & s_any[None, :]

    count = jnp.sum(mask, axis=1, dtype=jnp.int32)
    row_over = count > c_sub
    # Sort-based compaction: candidate ids ascend, non-candidates sink
    key = jnp.where(mask, jnp.arange(nsb, dtype=jnp.int32)[None, :],
                    jnp.int32(2**30))
    cand_sub = jnp.sort(key, axis=1)[:, :c_sub]          # [nb, c_sub]
    valid = (cand_sub < 2**30) & ~row_over[:, None]
    cand = jnp.where(valid, cand_sub, 0)[:, :, None] * sub \
        + jnp.arange(sub, dtype=jnp.int32)[None, None, :]
    cand = jnp.where(valid[:, :, None], cand, n).reshape(nb, c_sub * sub)
    return cand, row_over


def interleave_rows(nb, ndev):
    """Device-major row interleave for the shard_map row split (device
    d owns global rows d, d+D, 2D+d, ... — measured to cut the
    contiguous split's 1.2-1.5x row-density imbalance to ~1.0-1.1x,
    scripts/scaling_table.py).  Returns ``(rows_l, nbrp, rperm, rinv)``:
    rows per device, the padded row count, the permutation placing
    global row j*D+d at new index d*rows_l+j, and its inverse.  Shared
    by cd_pallas.run_full_sharded and cd_sched's shard branch so the
    two kernels' row<->device mapping can never drift apart."""
    import numpy as onp
    nbrp = -(-nb // ndev) * ndev
    rows_l = nbrp // ndev
    rperm = onp.arange(nbrp).reshape(rows_l, ndev).T.reshape(-1)
    return rows_l, nbrp, rperm, onp.argsort(rperm)


def full_grid_pass(packed, reach, *, block, kk, cpp, kern_kw,
                   interpret=False, pold=None, rpz_m=None,
                   packed_own=None, row0=None, rstride=1, col0=None):
    """Grid over ALL tile pairs; unreachable ones branch past the body.

    Several column tiles per grid program amortize the per-program
    overhead (grid steps + slab DMA) across the skipped tiles.  ``reach``
    [nbr, nbc] restricts the pass to a tile subset (prefilter skip and
    the mixed-mode / sparse-scheduler overflow rows — ops/cd_sched.py
    reuses this as its exact fallback).  ``packed`` is the
    [nbc, _NF, block] intruder slab array; returns the 10 accumulator
    outputs in standard order.

    ``packed_own``/``row0``/``rstride`` support a ROW SUBSET of the grid
    (the per-device share under ``shard_map``): the ownship side reads
    ``packed_own`` [nbr, _NF, block] whose local row i is GLOBAL row
    ``row0 + i*rstride`` (``row0`` a traced int32 scalar, ``rstride``
    static) — so pair exclusion and partner ids stay in the global slot
    space, and an interleaved (strided) row assignment balances load
    across devices.  Default (None/1): square grid over ``packed``
    itself with identity row ids — the single-chip path, bit-identical
    to before.

    With ``pold`` ([nbr, kk, block] int32 partner table in the global
    slot space) the kernel also evaluates in-kernel resume-nav and
    appends 3 outputs: keep [nbr, kk, block] f32, merged partners
    [nbr, kk, block] int32, active [nbr, 1, block] f32.
    """
    nbc = packed.shape[0]
    own_arr = packed if packed_own is None else packed_own
    nbr = own_arr.shape[0]
    assert reach.shape == (nbr, nbc), (reach.shape, nbr, nbc)
    dtype = packed.dtype
    cpp = min(cpp, nbc)
    nbp = -(-nbc // cpp) * cpp
    nb8 = -(-nbr // 8) * 8
    nw = -(-nbp // 32)
    bits = jnp.zeros((nb8, nw * 32), jnp.uint32).at[:nbr, :nbc].set(
        reach.astype(jnp.uint32))
    reach_i = jnp.sum(
        bits.reshape(nb8, nw, 32)
        << jnp.arange(32, dtype=jnp.uint32)[None, None, :],
        axis=2, dtype=jnp.uint32).astype(jnp.int32)
    # [row0, col0] ride one SMEM scalar pair; col0 offsets intruder ids
    # when ``packed`` is a local halo window of the global grid (the
    # cd_sched domain-decomposition mode) instead of the whole grid.
    row0_arr = jnp.stack([
        jnp.asarray(0 if row0 is None else row0, jnp.int32),
        jnp.asarray(0 if col0 is None else col0, jnp.int32)]).reshape(1, 2)
    packed_f = packed
    if nbp != nbc:
        # Padded intruder buffer; the padded columns' reach bits are 0,
        # so their tiles are never computed.
        packed_f = jnp.concatenate(
            [packed, jnp.zeros((nbp - nbc, _NF, block), dtype)], axis=0)

    acc_spec = lambda: pl.BlockSpec(
        (1, 1, block), lambda i, j: (i, 0, 0), memory_space=pltpu.VMEM)
    cand_spec = lambda: pl.BlockSpec(
        (1, kk, block), lambda i, j: (i, 0, 0), memory_space=pltpu.VMEM)
    acc = [jax.ShapeDtypeStruct((nbr, 1, block), dtype)] * 8 + [
        jax.ShapeDtypeStruct((nbr, kk, block), dtype),       # ctin
        jax.ShapeDtypeStruct((nbr, kk, block), jnp.int32)]   # cidx
    in_specs = [
        pl.BlockSpec((8, nw), lambda i, j: (i // 8, 0),
                     memory_space=pltpu.SMEM),       # reach window
        pl.BlockSpec((1, 2), lambda i, j: (0, 0),
                     memory_space=pltpu.SMEM),       # global row/col offsets
        pl.BlockSpec((1, _NF, block), lambda i, j: (i, 0, 0),
                     memory_space=pltpu.VMEM),       # ownship slab
        pl.BlockSpec((cpp, _NF, block), lambda i, j: (j, 0, 0),
                     memory_space=pltpu.VMEM),       # intruder slabs
    ]
    out_specs = [acc_spec() for _ in range(8)] + [cand_spec(), cand_spec()]
    args = [reach_i, row0_arr, own_arr, packed_f]
    if pold is None:
        kern = functools.partial(_kernel, cpp=cpp, rstride=rstride,
                                 **kern_kw)
    else:
        kern = functools.partial(_kernel_resume, cpp=cpp, rstride=rstride,
                                 rpz_m=float(rpz_m), **kern_kw)
        in_specs.append(cand_spec())                 # pold
        args.append(pold)
        out_specs += [cand_spec(), cand_spec(), acc_spec()]
        acc += [jax.ShapeDtypeStruct((nbr, kk, block), dtype),      # keep
                jax.ShapeDtypeStruct((nbr, kk, block), jnp.int32),  # merged
                jax.ShapeDtypeStruct((nbr, 1, block), dtype)]       # active
    if kern_kw.get("reso") == "swarm":
        # Swarm neighbour-sum accumulators ride as trailing outputs
        out_specs += [acc_spec() for _ in range(_N_SWARM)]
        acc += [jax.ShapeDtypeStruct((nbr, 1, block), dtype)] * _N_SWARM
    return list(pl.pallas_call(
        kern,
        grid=(nbr, nbp // cpp),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=acc,
        interpret=interpret,
    )(*args))


def interpret_default(interpret):
    """Resolve ``interpret=None``: the Mosaic compiler on an accelerator,
    the Pallas interpreter (loop-based, jit-friendly) in a process that
    was put on the CPU BY NAME (``JAX_PLATFORMS`` / ``jax_platforms``
    names ``cpu``, as the test suite and the virtual-mesh dryrun do).

    A process that asked for nothing and landed on the CPU did not get
    the chip it was started for (another process holds it, or the
    runtime failed to initialise): interpreting there would serve
    traffic ~1000x slower and say nothing, so it raises instead."""
    if interpret is not None:
        return interpret
    if jax.default_backend() != "cpu":
        return False
    named = (jax.config.jax_platforms or "").lower().split(",")
    if "cpu" in named:
        return True
    raise RuntimeError(
        f"Pallas CD backend on {jax.devices()[0]} "
        f"(platform {jax.default_backend()!r}): this process did not "
        "ask for the CPU, so it should have an accelerator and found "
        "none (is another process holding the chip?).  Set "
        "JAX_PLATFORMS=cpu to run the kernels in the interpreter on "
        "purpose.")


def detect_resolve_pallas(lat, lon, trk, gs, alt, vs, gseast, gsnorth,
                          active, noreso, rpz, hpz, tlookahead, mvpcfg,
                          block=256, k_partners=8, interpret=None,
                          spatial_sort=True, cols_per_prog=4,
                          cand_cap=0, perm=None, extra_cols=None,
                          reso="mvp", mesh=None, mesh_axis="ac"):
    """Pallas-backed equivalent of ``cd_tiled.detect_resolve_tiled``.

    Returns a ``RowConflictData``; reductions match the lax formulation to
    float tolerance (identical per-tile math, same block iteration order).
    Always computes in float32 (the TPU-native dtype for this kernel).

    ``cand_cap`` > 0 enables the mixed-mode candidate scheduler: a
    per-ownship-block table of sub-block-granular candidate aircraft
    (exact bound), with overflow rows covered by a row-masked full-grid
    pass.  Measured on v5e at N=100k it is at best ~10% ahead of the
    default block grid (the reach annulus is dominated by the
    rpz + tlookahead*vrel physics radius, not by block granularity), so
    it stays off by default; it is exact at any capacity and may win for
    much sparser or larger-N fleets.

    With ``mesh`` the full-grid pass runs under ``shard_map`` on the
    ``mesh_axis`` dimension: each device owns a contiguous slice of row
    blocks (one per-device Pallas program over its rows), the intruder
    slab array replicates (the GSPMD all-gather over ICI), and row ids
    are offset to the global slot space — SURVEY §5.7/5.8's
    block-distributed CD for the Pallas backend.
    """
    interpret = interpret_default(interpret)
    n = lat.shape[0]
    if spatial_sort and n > block:
        # Morton-order the slots (cd_tiled.run_spatially_sorted) so the
        # in-kernel reachability skip has tight blocks to work with.
        return cd_tiled.run_spatially_sorted(
            functools.partial(detect_resolve_pallas, block=block,
                              k_partners=k_partners, interpret=interpret,
                              spatial_sort=False,
                              cols_per_prog=cols_per_prog,
                              cand_cap=cand_cap, reso=reso,
                              mesh=mesh, mesh_axis=mesh_axis),
            lat, lon, trk, gs, alt, vs, gseast, gsnorth, active, noreso,
            rpz, hpz, tlookahead, mvpcfg, perm=perm, extra_cols=extra_cols)
    dtype = jnp.float32
    # Scoped-VMEM budget: the tile temporaries exceed the 16 MiB stack
    # limit above block=256 on v5e (measured 18-21 MiB at block=512).
    block = min(block, 256)
    if n <= 128:
        block = 128
    else:
        block = min(block, 1 << (n - 1).bit_length())
    nb = -(-n // block)
    npad = nb * block - n

    def pad(a):
        a = a.astype(dtype)
        return a if npad == 0 else jnp.concatenate(
            [a, jnp.zeros((npad,), dtype)])

    trkrad = jnp.radians(trk.astype(dtype))
    fields = precompute_trig(pad(lat), pad(lon))
    fields.update({
        "u": pad(gs.astype(dtype) * jnp.sin(trkrad)),
        "v": pad(gs.astype(dtype) * jnp.cos(trkrad)),
        "alt": pad(alt), "vs": pad(vs), "gse": pad(gseast),
        "gsn": pad(gsnorth), "trk": pad(trk),
        # tas/gs ratio: Eby's velocity basis (ve = tr*u = tas*sin(trk));
        # 1.0 when no tas given (MVP never reads it; no-wind tas == gs).
        # In swarm mode the slot carries cas instead (see _FIELDS note).
        "tr": pad((extra_cols or {}).get("cas", gs).astype(dtype)
                  if reso == "swarm"
                  else jnp.ones_like(gs.astype(dtype))
                  if not extra_cols or "tas" not in extra_cols
                  else extra_cols["tas"].astype(dtype)
                  / jnp.maximum(gs.astype(dtype), 0.5)),
        "active": pad(active.astype(dtype)),
        "noreso": pad(noreso.astype(dtype)),
    })
    # [nb, _NF, block]: per-block slabs of the per-aircraft columns
    packed = jnp.stack([fields[k] for k in _FIELDS]).reshape(
        _NF, nb, block).transpose(1, 0, 2)

    # Exact tile-skip flags (shared bound with the lax backend); swarm
    # widens the bound to its 7.5 nm neighbourhood (short lookaheads
    # must not skip genuine non-conflicting swarm neighbours)
    if reso == "swarm":
        from . import cr_swarm
        min_reach = cr_swarm.R_SWARM
    else:
        min_reach = 0.0
    reach = block_reachability(
        pad(lat), pad(lon), pad(gs), fields["active"] > 0.5,
        nb, block, float(rpz), float(tlookahead), min_reach_m=min_reach)

    kk = k_partners
    kern_kw = dict(block=block, kk=kk, rpz=float(rpz), hpz=float(hpz),
                   tlookahead=float(tlookahead), mvpcfg=mvpcfg, reso=reso)

    acc = lambda m: [jax.ShapeDtypeStruct((m, 1, block), dtype)] * 8 + [
        jax.ShapeDtypeStruct((m, kk, block), dtype),       # ctin
        jax.ShapeDtypeStruct((m, kk, block), jnp.int32)]   # cidx

    def run_full(reach_in=None):
        return full_grid_pass(packed, reach if reach_in is None else reach_in,
                              block=block, kk=kk, cpp=cols_per_prog,
                              kern_kw=kern_kw, interpret=interpret)

    def run_full_sharded():
        """Row blocks INTERLEAVED over the mesh (device d owns global
        rows d, d+D, d+2D, ... — measured to cut the contiguous split's
        1.2-1.5x row-density imbalance to ~1.0-1.1x); each device sweeps
        its rows against the replicated intruder slabs with GLOBAL row
        ids via the row0 + i*rstride mapping."""
        from jax.sharding import PartitionSpec as P
        ndev = mesh.shape[mesh_axis]
        rows_l, nbrp, rperm, inv = interleave_rows(nb, ndev)
        own_p, reach_p = packed, reach
        if nbrp != nb:
            own_p = jnp.concatenate(
                [packed, jnp.zeros((nbrp - nb, _NF, block), dtype)])
            reach_p = jnp.concatenate(
                [reach, jnp.zeros((nbrp - nb, nb), bool)])
        own_p, reach_p = own_p[rperm], reach_p[rperm]

        def body(own_l, reach_l, packed_g):
            row0 = jax.lax.axis_index(mesh_axis)
            return tuple(full_grid_pass(
                packed_g, reach_l, block=block, kk=kk, cpp=cols_per_prog,
                kern_kw=kern_kw, interpret=interpret,
                packed_own=own_l, row0=row0, rstride=ndev))

        outs = shard_map(
            body, mesh,
            (P(mesh_axis), P(mesh_axis), P()),
            P(mesh_axis))(own_p, reach_p, packed)
        return [o[inv][:nb] for o in outs]

    def run_cand(cand):
        """Grid over (ownship block, candidate sub-chunk): the intruder
        axis holds only aircraft that can possibly conflict with the
        block (exact bound, _build_candidates), so the pair count
        approaches the physics floor instead of the block-granular
        superset — the win that makes spread-out 100k-aircraft
        geometries pair-math-bound rather than tile-granularity-bound."""
        nsub = cand.shape[1] // block
        # Gather candidate columns; sentinel id n selects the appended
        # all-zero (inactive) column.
        allf = jnp.stack([fields[k] for k in _FIELDS])     # [_NF, n]
        allf = jnp.concatenate(
            [allf, jnp.zeros((_NF, 1), dtype)], axis=1)
        csl = allf[:, cand]                                # [_NF, nb, c_cap]
        csl = csl.transpose(1, 0, 2).reshape(nb, _NF, nsub, block) \
            .transpose(0, 2, 1, 3).reshape(nb * nsub, _NF, block)
        cgid = cand.reshape(nb * nsub, 1, block)

        kern = functools.partial(_kernel_cand, **kern_kw)
        own_map = lambda i, j: (i, 0, 0)
        sub_map = lambda i, j: (i * nsub + j, 0, 0)
        acc_spec = lambda: pl.BlockSpec((1, 1, block), own_map,
                                        memory_space=pltpu.VMEM)
        cand_spec = lambda: pl.BlockSpec((1, kk, block), own_map,
                                         memory_space=pltpu.VMEM)
        return list(pl.pallas_call(
            kern,
            grid=(nb, nsub),
            in_specs=[
                pl.BlockSpec((1, _NF, block), own_map,
                             memory_space=pltpu.VMEM),     # ownship slab
                pl.BlockSpec((1, _NF, block), sub_map,
                             memory_space=pltpu.VMEM),     # candidate slab
                pl.BlockSpec((1, 1, block), sub_map,
                             memory_space=pltpu.VMEM),     # candidate ids
            ],
            out_specs=[acc_spec() for _ in range(8)]
            + [cand_spec(), cand_spec()],
            out_shape=acc(nb),
            interpret=interpret,
        )(packed, csl, cgid))

    # Mixed-mode dispatch: the candidate pass covers rows whose table
    # fits the static capacity; the handful of overflow rows (Morton
    # straddle blocks, or every row when the whole fleet is mutually
    # reachable — dense regional traffic) are covered by a row-masked
    # full-grid pass and the row-disjoint outputs merged.  Identical
    # results either way — the split is purely a scheduling optimization.
    c_cap = -(-cand_cap // block) * block if cand_cap else 0
    if reso == "swarm" and c_cap:
        raise ValueError("cand_cap mixed mode does not carry the swarm "
                         "neighbour sums; use cand_cap=0 with RESO SWARM")
    if mesh is not None and mesh.shape[mesh_axis] > 1:
        outs = run_full_sharded()
    elif nb >= 8 and 0 < c_cap < nb * block:
        cand, row_over = _build_candidates(
            pad(lat), pad(lon), pad(gs), fields["active"] > 0.5,
            nb, block, c_cap, float(rpz), float(tlookahead))
        outs_c = run_cand(cand)
        reach_f = reach & row_over[:, None]

        def neutral(_):
            return [jnp.full(o.shape, v, o.dtype)
                    for o, v in zip(outs_c, _ACC_NEUTRAL)]

        outs_f = jax.lax.cond(jnp.any(row_over), run_full, neutral, reach_f)
        rsel = row_over[:, None, None]
        outs = [jnp.where(rsel, f, c) for f, c in zip(outs_f, outs_c)]
    else:
        outs = run_full()

    (inconf, tcpamax, sdve, sdvn, sdvv, tsolv, ncnt, lcnt,
     ctin, cidx) = outs[:10]

    unb = lambda a: a.reshape(nb * block)[:n]
    # Candidates: [nb, kk, block] -> [N, kk], already urgency-sorted
    topk_tin = ctin.transpose(0, 2, 1).reshape(nb * block, kk)[:n]
    topk_idx = cidx.transpose(0, 2, 1).reshape(nb * block, kk)[:n]
    topk_idx = jnp.where(topk_tin < _BIG, topk_idx, -1)

    rd = RowConflictData(
        inconf=unb(inconf) > 0.5,
        tcpamax=unb(tcpamax),
        sum_dve=unb(sdve), sum_dvn=unb(sdvn), sum_dvv=unb(sdvv),
        tsolv=unb(tsolv),
        # Cast per-block float counts to int32 BEFORE summing: a float32
        # total silently loses exactness past 2^24 pairs (plausible at 100k).
        nconf=jnp.sum(ncnt.astype(jnp.int32), dtype=jnp.int32),
        nlos=jnp.sum(lcnt.astype(jnp.int32), dtype=jnp.int32),
        topk_idx=topk_idx, topk_tin=topk_tin)
    if reso == "swarm":
        return rd, tuple(unb(a) for a in outs[10:10 + _N_SWARM])
    return rd
