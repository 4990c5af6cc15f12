"""Airborne Separation Assurance: device-side CD&R coordinator.

Parity with the reference ASAS coordinator (``bluesky/traffic/asas/asas.py``):
per-interval conflict detection -> resolution -> pair bookkeeping ->
resume-navigation recovery (asas.py:473-504, 409-471), with protected-zone
radii/margins and resolver configuration.

TPU-first: the reference keeps conflict pairs as Python lists/sets of
callsign tuples and loops over them.  Here the whole update is jitted: the
pair state is the [N,N] ``resopairs`` matrix, bookkeeping is boolean algebra,
and the conflict/LoS *counts* are device scalars.  Host-side code (stack
commands CONF/LOS lists, logging) extracts pair lists lazily via
``ops.cd.pairs_from_mask`` only when asked.

Resolver selection: MVP is the default (and currently only) device resolver;
the registry hook mirrors the reference's CDmethods/CRmethods dicts
(asas.py:41-55) for host-side extension.
"""
import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import aero, cd as cdops, cd_tiled, cr_mvp
from ..ops.cd import ConflictData
from ..ops.cd_tiled import RowConflictData
from .state import SimState


class AsasConfig(NamedTuple):
    """ASAS settings (reference asas.py:10-13 defaults + setters).

    Static under jit: toggling recompiles (cached per configuration), which
    matches how rarely these change vs how hot the step loop is.
    """
    swasas: bool = True
    dtasas: float = 1.0          # [s] CD&R interval
    dtlookahead: float = 300.0   # [s]
    rpz: float = 5.0 * aero.nm   # [m] protected-zone radius (R)
    hpz: float = 1000.0 * aero.ft  # [m] protected-zone half-height (dh)
    mar: float = 1.05            # resolution margin factor
    resofach: float = 1.05       # horizontal resolution factor (Rm = R*fac)
    resofacv: float = 1.05       # vertical resolution factor
    swresohoriz: bool = False
    swresospd: bool = False
    swresohdg: bool = False
    swresovert: bool = False
    reso_on: bool = True         # conflict resolution enabled (RESO MVP/OFF)
    reso_method: str = "MVP"     # MVP / EBY / SWARM / SSD (CRmethods
                                 # registry, asas.py:41-55); static under
                                 # jit like the rest of the config
    swprio: bool = False         # PRIORULES on/off (asas.py SetPrio)
    priocode: str = "FF1"        # FF1/FF2/FF3/LAY1/LAY2
    sort_every: int = 30         # tiled backends: CD intervals between
                                 # Morton re-sorts (any staleness is exact —
                                 # see AsasArrays.sort_perm)
    vmin: float = 100.0 * aero.kts   # [m/s] resolution speed caps
    vmax: float = 180.0 * aero.kts   # (reference asas.py setters)
    vsmin: float = -3000.0 * aero.fpm
    vsmax: float = 3000.0 * aero.fpm

    @property
    def rpz_m(self):
        return self.rpz * self.resofach

    @property
    def hpz_m(self):
        return self.hpz * self.resofacv


def _head_rows(state: SimState, rows: int) -> SimState:
    """``state`` with what an ASAS interval reads (``ac``, ``ap``,
    ``asas``) cut to the leading ``rows`` slots, ``resopairs`` to
    ``[rows, rows]``; every other leaf as it was."""
    n = state.ac.lat.shape[0]
    head = functools.partial(jax.tree_util.tree_map, lambda x: x[:rows]
                             if x.ndim and x.shape[0] == n else x)
    return state.replace(
        ac=head(state.ac), ap=head(state.ap),
        asas=head(state.asas).replace(
            resopairs=state.asas.resopairs[:rows, :rows]))


def _pad_rows(state: SimState, head, rows: int) -> SimState:
    """Put the ASAS arrays of an interval run on the leading ``rows``
    slots (``head``) back at ``state``'s size, each slot past ``rows``
    as an inactive row leaves the whole interval: no conflict, ASAS
    off, the resolution it had.  ``resopairs`` is written in place:
    outside ``[rows, rows]`` it holds only pairs with a slot nobody
    occupies, which every deletion purges (``Traffic.delete``,
    ``purge_tables``), so it is False there already."""
    asas = state.asas
    n = asas.active.shape[0]
    kept = {f: getattr(asas, f).at[:rows].set(getattr(head, f))
            for f in ("trk", "tas", "vs", "alt", "asase", "asasn")}
    blank = {f: jnp.pad(getattr(head, f), (0, n - rows))
             for f in ("active", "inconf", "tcpamax")}
    return state.replace(asas=asas.replace(
        resopairs=asas.resopairs.at[:rows, :rows].set(head.resopairs),
        nconf_cur=head.nconf_cur, nlos_cur=head.nlos_cur,
        **kept, **blank))


def update(state: SimState, cfg: AsasConfig, smooth=None,
           rows: int = 0) -> Tuple[SimState, ConflictData]:
    """One ASAS interval (``_update_all``) over the leading ``rows``
    slots of the state, or over all of them (``rows`` 0, or no fewer
    than the state holds).  ``rows`` is the caller's bound on the slots
    an aircraft occupies (``core/step.cd_dense_rows``): every pair with
    two live aircraft is computed by the same operations as over all
    slots, and the pairs left out are pairs the detection's ``pairmask``
    forces to no-conflict, whose terms in every sum are exact zeros.
    The returned ``ConflictData`` has the size the interval ran at."""
    if not 0 < rows < state.ac.lat.shape[0]:
        return _update_all(state, cfg, smooth)
    head, cd = _update_all(_head_rows(state, rows), cfg, smooth)
    return _pad_rows(state, head.asas, rows), cd


def _update_all(state: SimState, cfg: AsasConfig,
                smooth=None) -> Tuple[SimState, ConflictData]:
    """One ASAS interval: detect, resolve, bookkeep, resume (asas.py:473-504).

    ``smooth`` (diff.smooth.SmoothConfig; None on the serving path)
    engages the differentiable-mode relaxations: the hard conflict
    indicator becomes sigmoid pair weights on the MVP contribution sums
    (``soft_conflict_weight``), the resolver's min reduction a softmin,
    and the velocity caps straight-through clips.  The per-aircraft
    engagement *selection* (``upd``/``active`` gating below) stays
    hard-forward — both branches of each ``jnp.where`` are
    differentiable, and the gradient signal rides the smooth weights.
    MVP is the differentiable resolver; the other methods raise.
    """
    ac, asas = state.ac, state.asas

    cd = cdops.detect(ac.lat, ac.lon, ac.trk, ac.gs, ac.alt, ac.vs,
                      ac.active, cfg.rpz, cfg.hpz, cfg.dtlookahead)

    if smooth is not None and cfg.reso_on \
            and cfg.reso_method.upper() != "MVP":
        raise ValueError(
            "differentiable mode (SimConfig.smooth) relaxes the MVP "
            f"resolver only, not {cfg.reso_method!r} — use RESO MVP "
            "(or RESO OFF) for gradient workloads.")

    wconf = None
    if smooth is not None:
        from ..diff import smooth as smoothmod
        wconf = smoothmod.soft_conflict_weight(
            cd, cfg.rpz, cfg.dtlookahead, smooth)

    if cfg.reso_on:
        mvpcfg = cr_mvp.MVPConfig(
            rpz_m=cfg.rpz_m, hpz_m=cfg.hpz_m, tlookahead=cfg.dtlookahead,
            swresohoriz=cfg.swresohoriz, swresospd=cfg.swresospd,
            swresohdg=cfg.swresohdg, swresovert=cfg.swresovert,
            swprio=cfg.swprio, priocode=cfg.priocode)
        method = cfg.reso_method.upper()
        if method in ("MVP", "SWARM"):
            newtrk, newgs, newvs, newalt, asase, asasn = cr_mvp.resolve(
                cd, ac.alt, ac.gseast, ac.gsnorth, ac.vs, ac.trk, ac.gs,
                ac.selalt, state.ap.vs, asas.alt,
                cfg.vmin, cfg.vmax, cfg.vsmin, cfg.vsmax, mvpcfg,
                noreso=asas.noreso, resooff=asas.resooff,
                wconf=wconf, smooth=smooth)
        if method == "EBY":
            from ..ops import cr_eby
            newtrk, newgs, newvs, newalt = cr_eby.resolve(
                cd, ac.alt, ac.vs, ac.trk, ac.tas,
                cfg.rpz_m, cfg.vmin, cfg.vmax)
            asase = newgs * jnp.sin(jnp.radians(newtrk))
            asasn = newgs * jnp.cos(jnp.radians(newtrk))
        elif method == "SWARM":
            from ..ops import cr_swarm
            # Swarm blends the MVP output computed above with alignment
            # and flock centering (Swarm.py:68-110).  The CA gate is the
            # PREVIOUS interval's active flags — the resume-nav
            # hysteresis output, which is what asas.active holds at
            # reference resolve time (Swarm.py:70-73).
            # selspd may hold a Mach number; resolve to CAS like the
            # autopilot does (the reference Swarm blends raw selspd,
            # Swarm.py:72 — a unit bug upstream, fixed here)
            _, selcas, _ = aero.vcasormach(ac.selspd, ac.alt)
            newtrk, newgs, newvs, newalt = cr_swarm.resolve(
                cd, ac.lat, ac.lon, ac.alt, ac.trk, ac.gs, ac.cas,
                ac.vs, ac.gseast, ac.gsnorth, ac.active,
                newtrk, newgs, newvs, asas.active,
                state.ap.trk, selcas, ac.selvs,
                cfg.vmin, cfg.vmax)
            asase = newgs * jnp.sin(jnp.radians(newtrk))
            asasn = newgs * jnp.cos(jnp.radians(newtrk))
        elif method == "SSD":
            from ..ops import cr_ssd
            # PRIORULES RS1..RS9 select the SSD ruleset (reference
            # SSD.py:429-558); non-RS priocodes (the MVP FF*/LAY* family)
            # fall back to the RS1 default like the reference's separate
            # registries do.
            rs = cfg.priocode.upper() if cfg.swprio \
                and cfg.priocode.upper().startswith("RS") else "RS1"
            ssdcfg = cr_ssd.SSDConfig(rpz_m=cfg.rpz_m,
                                      tlookahead=cfg.dtlookahead,
                                      priocode=rs)
            newtrk, newgs = cr_ssd.resolve(
                cd, ac.lat, ac.lon, ac.alt, ac.trk, ac.gs, ac.vs,
                ac.gseast, ac.gsnorth, ac.active,
                cfg.vmin, cfg.vmax, ssdcfg, hdg=ac.hdg,
                ap_trk=state.ap.trk, ap_tas=state.ap.tas)
            # SSD is a horizontal method (SSD.py:99-104)
            newvs, newalt = asas.vs, asas.alt
            asase = newgs * jnp.sin(jnp.radians(newtrk))
            asasn = newgs * jnp.cos(jnp.radians(newtrk))
        elif method != "MVP":
            raise ValueError(
                f"Unknown AsasConfig.reso_method {cfg.reso_method!r}; "
                "expected MVP, EBY, SWARM or SSD.")
        # Swarm commands apply to the whole swarm once any conflict
        # exists (the reference only calls resolve when confpairs is
        # non-empty, asas.py:487, and Swarm then sets all active);
        # others gate on inconf.  Non-updated aircraft keep the previous
        # resolution state (the reference overwrites all, but only
        # `active` aircraft consume them — keeping them avoids NaN
        # leakage from padding garbage).
        if method == "SWARM":
            upd = ac.active & jnp.any(cd.swconfl)
        else:
            upd = cd.inconf
        asas = asas.replace(
            trk=jnp.where(upd, newtrk, asas.trk),
            tas=jnp.where(upd, newgs, asas.tas),
            vs=jnp.where(upd, newvs, asas.vs),
            alt=jnp.where(upd, newalt, asas.alt),
            asase=jnp.where(upd, asase, asas.asase),
            asasn=jnp.where(upd, asasn, asas.asasn))

    # Pair bookkeeping (asas.py:489-502): resopairs accumulates conflicts
    resopairs = asas.resopairs | cd.swconfl

    # ResumeNav (asas.py:409-471)
    resopairs, active = cr_mvp.resume_nav(
        resopairs, cd.swlos, ac.lat, ac.lon, ac.gseast, ac.gsnorth, ac.trk,
        ac.active, cfg.rpz, cfg.rpz * cfg.resofach)

    if cfg.reso_on and cfg.reso_method.upper() == "SWARM":
        # The whole swarm follows ASAS, not only conflict pairs — but
        # only once any conflict triggered a resolve (asas.py:487 gate +
        # Swarm.py:101-102 active.fill(True))
        active = jnp.where(jnp.any(cd.swconfl), ac.active, active)

    asas = asas.replace(
        resopairs=resopairs,
        active=active & cfg.reso_on,
        inconf=cd.inconf,
        tcpamax=cd.tcpamax,
        nconf_cur=jnp.sum(cd.swconfl, dtype=jnp.int32),
        nlos_cur=jnp.sum(cd.swlos, dtype=jnp.int32))
    return state.replace(asas=asas), cd


def impl_for_backend(cd_backend: str) -> str:
    """SimConfig.cd_backend -> update_tiled/refresh_spatial_sort impl."""
    return {"pallas": "pallas", "sparse": "sparse"}.get(cd_backend, "lax")


@functools.partial(jax.jit, static_argnames=(
    "block", "tlookahead", "rpz", "hpz", "min_reach_m", "min_vreach_m"))
def _sparse_sort_refresh(lat, lon, gs, alt, vs, active, old_perm,
                         partners_s, life_s, *, block, tlookahead, rpz,
                         hpz, min_reach_m=0.0, min_vreach_m=0.0):
    """The sparse refresh as ONE compiled program: run eagerly it is a
    chain of ~30 host-dispatched ops per refresh (what that chain costs
    on this machine is not measured); jitted it is a single
    dispatch.  Returns ``(dest, partners_s, fresh, aged)``.

    ``life_s`` [s] is how long the layout will be used before the next
    refresh (a traced scalar: another chunk length compiles nothing).
    The stripes are made taller than the reach radius by the drift of
    that lifetime, ``2 * gsmax * life_s``: two blocks two stripes apart
    each spread by up to ``gsmax * life_s`` towards the other while the
    layout ages, and with stripes only as tall as the reach they come
    into each other's reach within seconds.  Their runs are ragged (a
    block here, a gap there), rows then need more than ``S_CAP``
    segments, and one such row sends every interval from then on
    through the full-grid fallback: 16 ms of a 57 ms interval at
    N=100k, in 22 to 32 of a chunk's 50 intervals (PERF.md, PR 28).
    Taller stripes cost block pairs (+10% for 50 s), so they are kept
    only where they buy that: where even the fresh schedule of the
    taller layout overflows (a fleet so dense that a row's windows
    alone pass ``S_CAP``, the 230 nm circle at 100k), the fallback runs
    every interval whatever the stripes' height, and the layout is
    that of the reach alone.

    ``fresh`` and ``aged`` are what the interval's schedule visits at
    these positions (``cd_sched.schedule_counts``: block pairs, rows
    sent to the full-grid fallback, int32 scalars) under the layout
    returned and under the one it replaces, at the end of its life.
    The chunk-edge refresh reads them."""
    from ..ops import cd_sched
    gsmax = jnp.max(jnp.where(active, gs, 0.0))
    reach = cd_sched.reach_threshold_m(gs, active, tlookahead, rpz)
    counts = functools.partial(
        cd_sched.schedule_counts, lat, lon, gs, alt, vs, active,
        block=block, rpz=rpz, hpz=hpz, tlookahead=tlookahead,
        min_reach_m=min_reach_m, min_vreach_m=min_vreach_m)

    def layout(thresh):
        # Altitude layering stays OFF: measured end-to-end on the v5e
        # at N=100k it loses ~4% even on the dense 230 nm circle (1.74x
        # vs 1.82x real-time) — the schedule-level 2.3x pair reduction
        # is real, but the regional wall time is dominated by per-pair
        # conflict tails (2.5M concurrent conflicts), and the real
        # fleet's TAS spread fattens the layered blocks.  The mechanism
        # remains available (stripe_sort_dest n_layers, incl. the
        # on-device "auto" gate) for fleets with genuinely banded
        # cruise altitudes.
        dest = cd_sched.stripe_sort_dest(
            lat, lon, gs, active, thresh, block, 32,
            alt=alt, vs=vs).astype(jnp.int32)
        return dest, counts(dest)

    dest, fresh = layout(reach + 2.0 * gsmax * life_s)
    dest, fresh = jax.lax.cond(fresh[1] > 0, lambda: layout(reach),
                               lambda: (dest, fresh))
    # Remap the sorted-space partner table old-layout -> new-layout:
    # old slot -> caller slot (inverse of the old dest) -> new slot.
    # Costs a few [n_tot,K] gathers ONCE per refresh — amortized over
    # sort_every intervals, vs. per-interval gathers if the table
    # lived in caller space.
    n = lat.shape[0]
    n_tot = cd_sched.padded_size(n, block)
    inv_old = cd_sched.slot_inverse(old_perm, n, n_tot)
    pv = partners_s[:n_tot]
    caller_vals = jnp.where(
        pv >= 0, inv_old[jnp.clip(pv, 0, n_tot)], -1)
    new_vals = jnp.where(
        caller_vals >= 0,
        dest[jnp.clip(caller_vals, 0, n - 1)], -1)
    per_caller = new_vals[jnp.clip(old_perm, 0, n_tot - 1), :]   # [n, K]
    spad = partners_s.shape[0]
    new_partners = jnp.full((spad, pv.shape[1]), -1,
                            jnp.int32).at[dest].set(per_caller)
    return dest, new_partners, fresh, counts(old_perm)


def _sparse_refresh_of(state: SimState, cfg: AsasConfig, block,
                       life_s=None):
    """``_sparse_sort_refresh`` on a state, with the reach the interval
    itself will use (SWARM widens it, as ``update_tiled`` does), for a
    layout kept ``life_s`` seconds (default: the refresh cadence)."""
    ac = state.ac
    if life_s is None:
        life_s = cfg.sort_every * cfg.dtasas
    min_reach = min_vreach = 0.0
    if cfg.reso_on and cfg.reso_method.upper() == "SWARM":
        from ..ops import cr_swarm
        min_reach = float(cr_swarm.R_SWARM)
        min_vreach = float(cr_swarm.DH_SWARM)
    return _sparse_sort_refresh(
        ac.lat, ac.lon, ac.gs, ac.alt, ac.vs, ac.active,
        state.asas.sort_perm, state.asas.partners_s, float(life_s),
        block=min(block, 256), tlookahead=float(cfg.dtlookahead),
        rpz=float(cfg.rpz), hpz=float(cfg.hpz),
        min_reach_m=min_reach, min_vreach_m=min_vreach)


def _rebucket_callers(active, dest0, dev, n, n_tot, ndev, C):
    """Caller-slot re-bucketing shared by the stripe and tile refreshes
    (a full [n] bijection): device d's caller shard [d*C, (d+1)*C) gets
    exactly the active aircraft whose sorted slots d owns (packed in
    sorted order), inactive rows fill the per-shard tails.  Returns
    ``(newslot [n], src [n], counts [ndev])`` — counts <= C is the
    caller's occupancy contract to check."""
    aidx = jnp.arange(n, dtype=jnp.int32)
    key = jnp.where(active, dest0, n_tot + aidx)   # actives first, by slot
    order = jnp.argsort(key)
    act_o = active[order]
    dev_o = dev[order]
    oh = (dev_o[:, None] == jnp.arange(ndev, dtype=jnp.int32)[None, :]) \
        & act_o[:, None]
    counts = jnp.sum(oh, axis=0, dtype=jnp.int32)          # [ndev]
    rank_o = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=1)
    slot_act_o = dev_o * C + rank_o
    # free caller slots (per-shard tails) in ascending order for the
    # inactive fillers; counts <= C is checked by the host caller
    free = (aidx % C) >= counts[jnp.minimum(aidx // C, ndev - 1)]
    free_slots = jnp.sort(jnp.where(free, aidx, n))
    n_act = jnp.sum(active, dtype=jnp.int32)
    inact_rank = jnp.clip(aidx - n_act, 0, n - 1)
    newslot_o = jnp.where(act_o, slot_act_o,
                          free_slots[inact_rank]).astype(jnp.int32)
    newslot = jnp.zeros((n,), jnp.int32).at[order].set(newslot_o)
    src = jnp.zeros((n,), jnp.int32).at[newslot].set(aidx)
    return newslot, src, counts


def _remap_partners_sorted(old_perm, partners_s, active, dest0,
                           dest_sent, n, n_tot):
    """Sorted-space partner-table remap old layout -> new layout (old
    sorted -> old caller -> new sorted), shared by the stripe and tile
    refreshes — same chain as ``_sparse_sort_refresh`` plus the caller
    migration, which cancels out because the table is keyed in sorted
    space."""
    from ..ops import cd_sched
    inv_old = cd_sched.slot_inverse(old_perm, n, n_tot)
    pv = partners_s[:n_tot]
    caller_vals = jnp.where(pv >= 0, inv_old[jnp.clip(pv, 0, n_tot)], -1)
    cv = jnp.clip(caller_vals, 0, n - 1)
    new_vals = jnp.where((caller_vals >= 0) & active[cv],
                         dest0[cv], -1)
    row_ok = (old_perm < n_tot) & active
    per_caller = jnp.where(row_ok[:, None],
                           new_vals[jnp.clip(old_perm, 0, n_tot - 1), :],
                           -1)
    return jnp.full((n_tot, pv.shape[1]), -1, jnp.int32) \
        .at[dest_sent].set(per_caller, mode="drop")


@functools.partial(jax.jit, static_argnames=(
    "block", "ndev", "extra", "halo", "tlookahead", "rpz",
    "min_reach_m", "margin_s"))
def _spatial_shard_refresh(lat, lon, gs, alt, vs, active, old_perm,
                           partners_s, *, block, ndev, extra, halo,
                           tlookahead, rpz, min_reach_m, margin_s):
    """Spatial-mode sort refresh: stripe sort + device RE-BUCKETING as
    one compiled program.

    Unlike ``_sparse_sort_refresh`` (which only moves aircraft between
    SORTED slots), the spatial mode also migrates aircraft between
    CALLER slots so that caller shard d of the device mesh holds
    exactly the aircraft whose sorted latitude-stripe slots device d
    owns — the invariant that makes the per-interval padded scatter and
    result back-map device-local (zero per-interval O(N) collectives,
    ops/cd_sched.py spatial branch).  Inactive rows fill the per-shard
    gaps and carry the SENTINEL sort slot ``n_tot`` (dropped from the
    scatter; their results read the accumulator identities).

    Returns ``(newslot, src, sort_perm_new, partners_new, stats)``:

    * ``newslot`` [n]: old caller slot -> new caller slot (the host
      applies it to ids/routes/conditions via
      ``Traffic.apply_slot_permutation``),
    * ``src`` [n]: new caller slot -> old caller slot (gather index for
      permuting every [n]-leading state leaf),
    * ``sort_perm_new`` [n]: new caller slot -> sorted slot (sentinel
      ``n_tot`` on inactive rows),
    * ``partners_new`` [n_tot, K]: the sorted-space partner table
      remapped old layout -> new layout (old sorted -> old caller ->
      new sorted),
    * ``stats``: ``(counts [ndev], halo_ok, halo_need, gsmax)`` —
      per-device active occupancy, whether the ``halo``-block window
      covers every reachable block pair even after ``margin_s`` seconds
      of worst-case drift (the exact conservative
      rpz + lookahead*(gs_i+gs_j) bound, horizontally widened by
      2*gsmax*margin_s), and the widest halo actually needed.
    """
    from ..ops import cd_sched
    n = lat.shape[0]
    nb = -(-n // block) + extra
    n_tot = nb * block
    nb_l = nb // ndev
    S = nb_l * block
    C = n // ndev
    thresh = cd_sched.reach_threshold_m(gs, active, tlookahead, rpz)
    dest0 = cd_sched.stripe_sort_dest(
        lat, lon, gs, active, thresh, block, extra,
        alt=alt, vs=vs, spread_pad=True).astype(jnp.int32)
    dev = jnp.minimum(dest0 // S, ndev - 1)
    newslot, src, counts = _rebucket_callers(
        active, dest0, dev, n, n_tot, ndev, C)
    dest_sent = jnp.where(active, dest0, n_tot)
    sort_perm_new = dest_sent[src]
    partners_new = _remap_partners_sorted(
        old_perm, partners_s, active, dest0, dest_sent, n, n_tot)

    # ---- halo coverage check, drift-margin widened ----
    pcols = cd_sched.scatter_padded(
        [lat, lon, gs, active.astype(lat.dtype)], dest_sent, n_tot)
    plat, plon, pgs, pact = pcols
    summ = cd_tiled.block_summaries(plat, plon, pgs, pact > 0.5,
                                    nb, block)
    gsmax = jnp.max(jnp.where(active, gs, 0.0))
    # min_reach_m: the interval's schedule widens reachability to the
    # SWARM neighbourhood radius (cd_sched min_reach_m=R_SWARM), so the
    # coverage check must validate the SAME widened bound — and it
    # applies no vertical gating at all, so its reach is a superset of
    # the interval's vertically-gated one for any min_vreach_m.
    reach_m = cd_tiled.reachability_from_summaries(
        summ, summ, float(rpz), float(tlookahead),
        min_reach_m=float(min_reach_m),
        margin_m=2.0 * gsmax * margin_s)
    bi = jnp.arange(nb, dtype=jnp.int32)
    d_i = bi // nb_l
    lo = d_i * nb_l - halo
    hi = (d_i + 1) * nb_l + halo
    outside = (bi[None, :] < lo[:, None]) | (bi[None, :] >= hi[:, None])
    halo_ok = ~jnp.any(reach_m & outside)
    # widest halo the current geometry would need (readback/diagnosis):
    # blocks past the owning device's own range, over reachable pairs
    need = jnp.maximum(jnp.maximum(
        (d_i * nb_l)[:, None] - bi[None, :],
        bi[None, :] - ((d_i + 1) * nb_l)[:, None] + 1), 0)
    halo_need = jnp.max(jnp.where(reach_m, need, 0))
    return newslot, src, sort_perm_new, partners_new, \
        (counts, halo_ok, halo_need, gsmax)


class ShardContractError(RuntimeError):
    """The geometry broke a spatial/tiles decomposition contract at a
    sort refresh: a stripe or tile holds more aircraft than its caller
    shard, or reachability escapes the halo window, the edge+corner
    neighbourhood or the pinned slab budgets.  The one failure of a
    shard refresh the sim answers by falling back a mode
    (tiles -> spatial -> replicate)."""


_morton_perm_jit = jax.jit(
    lambda lat, lon, active: cd_tiled.spatial_permutation(
        lat, lon, active).astype(jnp.int32))


def refresh_spatial_sort(state: SimState, cfg: AsasConfig,
                         block: int = 512, impl: str = "lax",
                         life_s=None) -> SimState:
    """Recompute the cached spatial sort for the tiled/pallas/sparse
    backends.  HOST-called at chunk boundaries, deliberately outside the
    jitted step (see the note in ``update_tiled``); cadence is the
    caller's (Simulation refreshes every ``cfg.sort_every`` CD intervals
    of sim time, bench once per scan chunk) — any staleness is exact.
    The compute itself is one jitted program per flavor (one dispatch
    in place of an eager chain of ~30; the chain's cost on this machine
    is not measured).  ``life_s``: as ``refresh_sparse_counted``."""
    ac = state.ac
    if impl == "sparse":
        return refresh_sparse_counted(state, cfg, block, life_s)[0]
    perm = _morton_perm_jit(ac.lat, ac.lon, ac.active)
    return state.replace(asas=state.asas.replace(sort_perm=perm))


def refresh_sparse_counted(state: SimState, cfg: AsasConfig,
                           block: int = 256, life_s=None):
    """The sparse backend's chunk-edge refresh with its schedule's
    counters: ``(state, fresh, aged)``, each ``(block pairs, overflow
    rows)`` as device scalars of the refresh program itself (nothing
    waits for them here; Simulation reads them when it retires the
    chunk this layout starts).  ``fresh`` is the schedule of the layout
    just made, ``aged`` that of the layout it replaces at the same
    positions: what that one had come to by the end of its life (of no
    meaning when the outgoing ``sort_perm`` was no stripe layout: the
    first refresh, a change of backend).  ``life_s`` [s] is how long
    the caller will use the new layout (default: the refresh cadence,
    ``sort_every * dtasas``); the stripes are sized for it
    (``_sparse_sort_refresh``)."""
    dest, partners_s, fresh, aged = _sparse_refresh_of(
        state, cfg, block, life_s)
    return state.replace(asas=state.asas.replace(
        sort_perm=dest, partners_s=partners_s)), fresh, aged


def refresh_spatial_shard(state: SimState, cfg: AsasConfig, ndev: int,
                          block: int = 256, halo_blocks: int = 0):
    """Spatial-mode chunk-edge refresh: stripe sort, caller-slot
    re-bucketing, partner remap and the halo-coverage check as one
    jitted program, then the state permutation applied host-side.

    Returns ``(state, newslot, stats)`` — ``newslot`` is the
    old-caller -> new-caller slot map as a numpy array (the caller
    remaps ids/routes/conditions with it,
    ``Traffic.apply_slot_permutation``), ``stats`` a dict with the
    per-device occupancy, halo coverage flag and needed halo width.

    Raises ``ShardContractError`` when the geometry cannot satisfy the
    spatial contract — a device's stripe population exceeding its
    caller-shard capacity (QarSUMO-style partition imbalance), or
    reachability crossing more than the halo window even after the
    drift margin — instead of silently risking missed conflicts; the
    caller falls back to the column-replicated mode (or a wider halo).
    """
    from ..ops import cd_sched
    ac = state.ac
    n = ac.lat.shape[0]
    block = min(block, 256)
    extra, nb, nb_l, n_tot = cd_sched.spatial_layout(n, block, ndev)
    if state.asas.partners_s.shape[0] < n_tot:
        raise RuntimeError(
            f"spatial refresh: partners_s holds "
            f"{state.asas.partners_s.shape[0]} rows < n_tot={n_tot} — "
            "enable spatial mode first (it resizes the sorted tables)")
    halo_max = (ndev - 1) * nb_l           # multi-hop exchange ceiling
    # halo_blocks == 0 -> AUTO: check coverage against the widest
    # possible window, then pin 1.25x the measured need (>= one
    # device) so drift headroom survives between refreshes; the caller
    # stores the pinned width in SimConfig.cd_halo_blocks so every
    # interval compiles against the same static window.
    auto = not halo_blocks
    halo = halo_max if auto else min(int(halo_blocks), halo_max)
    # The interval's schedule widens reachability to the SWARM
    # neighbourhood radius; validate halo coverage against the same
    # widened bound (cd_sched.detect_resolve_sched's min_reach).
    min_reach = 0.0
    if cfg.reso_on and cfg.reso_method.upper() == "SWARM":
        from ..ops import cr_swarm
        min_reach = float(cr_swarm.R_SWARM)
    newslot, srcidx, sort_perm, partners_new, stats = \
        _spatial_shard_refresh(
            ac.lat, ac.lon, ac.gs, ac.alt, ac.vs, ac.active,
            state.asas.sort_perm, state.asas.partners_s[:n_tot],
            block=block, ndev=int(ndev), extra=extra, halo=halo,
            tlookahead=float(cfg.dtlookahead), rpz=float(cfg.rpz),
            min_reach_m=min_reach,
            margin_s=float(cfg.sort_every * cfg.dtasas))
    counts, halo_ok, halo_need, gsmax = stats
    if auto:
        halo = min(max(nb_l, int(np.ceil(1.25 * int(halo_need)))),
                   halo_max)
    counts = np.asarray(counts)
    C = n // ndev
    if counts.max() > C:
        raise ShardContractError(
            f"spatial refresh: stripe occupancy overflow — device "
            f"{int(counts.argmax())} owns {int(counts.max())} aircraft "
            f"> caller-shard capacity {C} (nmax/{ndev}). Raise nmax or "
            "use SHARD REPLICATE for this geometry.")
    if not bool(halo_ok):
        raise ShardContractError(
            f"spatial refresh: halo coverage violated — reachability "
            f"(drift-margin widened) needs {int(halo_need)} halo blocks "
            f"> {halo} available per side. Use SHARD REPLICATE or fewer "
            "devices for this geometry.")

    def permute(leaf):
        if hasattr(leaf, "ndim") and leaf.ndim >= 1 \
                and leaf.shape[0] == n:
            return leaf[srcidx]
        return leaf
    new_state = jax.tree.map(permute, state)
    asas_new = new_state.asas
    # caller-space partner ids (tiled path) move WITH the slots
    p = asas_new.partners
    p = jnp.where(p >= 0, newslot[jnp.clip(p, 0, n - 1)], -1)
    spad = state.asas.partners_s.shape[0] - n_tot
    if spad > 0:
        partners_new = jnp.concatenate(
            [partners_new,
             jnp.full((spad, partners_new.shape[1]), -1, jnp.int32)])
    new_state = new_state.replace(asas=asas_new.replace(
        sort_perm=sort_perm, partners_s=partners_new, partners=p))
    info = dict(counts=counts, occupancy=float(counts.max() / max(C, 1)),
                halo_blocks=halo, halo_need=int(halo_need),
                gsmax=float(gsmax), nb=nb, nb_local=nb_l, n_tot=n_tot,
                extra_blocks=extra,
                halo_rows=2 * halo * block * ndev)
    return new_state, np.asarray(newslot), info


@functools.partial(jax.jit, static_argnames=(
    "block", "extra", "tiles", "budgets", "tlookahead", "rpz",
    "min_reach_m", "margin_s"))
def _tile_shard_refresh(lat, lon, gs, alt, vs, active, old_perm,
                        partners_s, *, block, extra, tiles, budgets,
                        tlookahead, rpz, min_reach_m, margin_s):
    """Tiles-mode sort refresh: 2-D tile-major sort + device
    re-bucketing + the corner-halo contract validation as one compiled
    program — the lat x lon generalisation of
    ``_spatial_shard_refresh`` (same return structure, same caller-slot
    bijection and partner-remap shapes).

    Validation replaces the stripe window check with TWO conditions on
    the drift-margin-widened reachability: (1) every reachable block
    pair stays inside the canonical edge+corner neighbourhood of
    ``cd_sched.tile_offsets`` (a reach escaping it could not be shipped
    by the per-offset exchange at all), and (2) with ``budgets`` given,
    each offset's measured per-receiver import need fits its pinned
    slab budget.  Because the interval's exports select from the SAME
    (unwidened) reachability, margin-widened need >= interval need —
    so a passing refresh guarantees no conflict pair can be missed
    until the next one.

    ``stats`` is ``(counts [ndev], halo_ok, budget_ok, needs [n_offs],
    gsmax)`` — needs are the measured per-offset import-block maxima
    (the host pins budgets at 1.25x these in auto mode).
    """
    from ..ops import cd_sched
    n = lat.shape[0]
    nb = -(-n // block) + extra
    n_tot = nb * block
    tR, tC = int(tiles[0]), int(tiles[1])
    ndev = tR * tC
    nb_t = nb // ndev
    S = nb_t * block
    C = n // ndev
    thresh = cd_sched.reach_threshold_m(gs, active, tlookahead, rpz)
    dest0 = cd_sched.tile_sort_dest(
        lat, lon, gs, active, thresh, block, extra, (tR, tC),
        alt=alt, vs=vs).astype(jnp.int32)
    dev = jnp.minimum(dest0 // S, ndev - 1)
    newslot, src, counts = _rebucket_callers(
        active, dest0, dev, n, n_tot, ndev, C)
    dest_sent = jnp.where(active, dest0, n_tot)
    sort_perm_new = dest_sent[src]
    partners_new = _remap_partners_sorted(
        old_perm, partners_s, active, dest0, dest_sent, n, n_tot)

    # ---- corner-halo contract check, drift-margin widened ----
    pcols = cd_sched.scatter_padded(
        [lat, lon, gs, active.astype(lat.dtype)], dest_sent, n_tot)
    plat, plon, pgs, pact = pcols
    summ = cd_tiled.block_summaries(plat, plon, pgs, pact > 0.5,
                                    nb, block)
    gsmax = jnp.max(jnp.where(active, gs, 0.0))
    reach_m = cd_tiled.reachability_from_summaries(
        summ, summ, float(rpz), float(tlookahead),
        min_reach_m=float(min_reach_m),
        margin_m=2.0 * gsmax * margin_s)
    # column need per RECEIVER tile: any of tile v's rows reaching col b
    cn_t = jnp.any(reach_m.reshape(ndev, nb_t, nb), axis=1) \
        .reshape(ndev, ndev, nb_t)            # [recv, src tile, nb_t]
    treach = jnp.any(cn_t, axis=2)                         # [recv, src]
    offs = cd_sched.tile_offsets((tR, tC))
    allowed = np.eye(ndev, dtype=bool)
    for off in offs:
        for u, v in cd_sched._offset_pairs((tR, tC), off):
            allowed[v, u] = True               # v imports from sender u
    halo_ok = ~jnp.any(treach & ~jnp.asarray(allowed))
    needs = []
    for off in offs:
        uv = np.full(ndev, -1, np.int32)
        for u, v in cd_sched._offset_pairs((tR, tC), off):
            uv[v] = u
        cnt = jnp.sum(
            cn_t[jnp.arange(ndev), jnp.maximum(uv, 0)],
            axis=-1, dtype=jnp.int32)                      # [recv]
        needs.append(jnp.max(jnp.where(jnp.asarray(uv >= 0), cnt, 0)))
    needs = jnp.stack(needs)
    if budgets:
        budget_ok = jnp.all(
            needs <= jnp.asarray(budgets, jnp.int32))
    else:
        budget_ok = jnp.asarray(True)
    return newslot, src, sort_perm_new, partners_new, \
        (counts, halo_ok, budget_ok, needs, gsmax)


def refresh_tile_shard(state: SimState, cfg: AsasConfig, tiles,
                       block: int = 256, budgets=()):
    """Tiles-mode chunk-edge refresh: 2-D tile sort, caller-slot
    re-bucketing, partner remap and the corner-halo contract check as
    one jitted program, then the state permutation applied host-side —
    the lat x lon counterpart of ``refresh_spatial_shard``.

    ``budgets`` = () is AUTO: validate the neighbourhood contract, then
    pin each canonical offset's slab budget at 1.25x its measured need
    (>= 4 blocks drift headroom, <= the whole tile) — the caller stores
    the pinned tuple in SimConfig.cd_tile_budgets so every interval
    compiles against the same static exchange.

    Raises ``ShardContractError`` on a tile occupancy overflow (a tile's
    population exceeding its caller-shard capacity), on reachability
    escaping the edge+corner neighbourhood, or on a pinned budget
    falling short of the measured need — never silently misses
    conflicts; the caller falls back (tiles -> spatial -> replicate).
    """
    from ..ops import cd_sched
    ac = state.ac
    n = ac.lat.shape[0]
    block = min(block, 256)
    tR, tC = int(tiles[0]), int(tiles[1])
    ndev = tR * tC
    extra, nb, nb_t, n_tot = cd_sched.spatial_layout(n, block, ndev)
    if state.asas.partners_s.shape[0] < n_tot:
        raise RuntimeError(
            f"tile refresh: partners_s holds "
            f"{state.asas.partners_s.shape[0]} rows < n_tot={n_tot} — "
            "enable tiles mode first (it resizes the sorted tables)")
    min_reach = 0.0
    if cfg.reso_on and cfg.reso_method.upper() == "SWARM":
        from ..ops import cr_swarm
        min_reach = float(cr_swarm.R_SWARM)
    auto = not budgets
    budgets = tuple(int(b) for b in budgets) if budgets else ()
    newslot, srcidx, sort_perm, partners_new, stats = \
        _tile_shard_refresh(
            ac.lat, ac.lon, ac.gs, ac.alt, ac.vs, ac.active,
            state.asas.sort_perm, state.asas.partners_s[:n_tot],
            block=block, extra=extra, tiles=(tR, tC), budgets=budgets,
            tlookahead=float(cfg.dtlookahead), rpz=float(cfg.rpz),
            min_reach_m=min_reach,
            margin_s=float(cfg.sort_every * cfg.dtasas))
    counts, halo_ok, budget_ok, needs, gsmax = stats
    counts = np.asarray(counts)
    needs = np.asarray(needs)
    C = n // ndev
    if counts.max() > C:
        t_bad = int(counts.argmax())
        raise ShardContractError(
            f"tile refresh: tile occupancy overflow — tile "
            f"({t_bad // tC},{t_bad % tC}) owns {int(counts.max())} "
            f"aircraft > caller-shard capacity {C} (nmax/{ndev}). Raise "
            "nmax, use a different tile shape, or SHARD "
            "SPATIAL/REPLICATE for this geometry.")
    if not bool(halo_ok):
        raise ShardContractError(
            f"tile refresh: corner-halo contract violated — "
            f"(drift-margin widened) reachability escapes the "
            f"edge+corner neighbourhood of the {tR}x{tC} tile mesh. "
            "Use SHARD SPATIAL/REPLICATE or fewer tiles for this "
            "geometry.")
    if not bool(budget_ok):
        raise ShardContractError(
            f"tile refresh: halo slab budget exceeded — measured "
            f"per-offset import need {needs.tolist()} > pinned budgets "
            f"{list(budgets)}. Re-run SHARD TILE {tR}x{tC} to re-pin, "
            "or SHARD SPATIAL/REPLICATE for this geometry.")
    if auto:
        budgets = tuple(
            int(min(max(4, -(-int(nd) * 5 // 4)), nb_t))
            for nd in needs)

    def permute(leaf):
        if hasattr(leaf, "ndim") and leaf.ndim >= 1 \
                and leaf.shape[0] == n:
            return leaf[srcidx]
        return leaf
    new_state = jax.tree.map(permute, state)
    asas_new = new_state.asas
    # caller-space partner ids (tiled path) move WITH the slots
    p = asas_new.partners
    p = jnp.where(p >= 0, newslot[jnp.clip(p, 0, n - 1)], -1)
    spad = state.asas.partners_s.shape[0] - n_tot
    if spad > 0:
        partners_new = jnp.concatenate(
            [partners_new,
             jnp.full((spad, partners_new.shape[1]), -1, jnp.int32)])
    new_state = new_state.replace(asas=asas_new.replace(
        sort_perm=sort_perm, partners_s=partners_new, partners=p))
    offs = cd_sched.tile_offsets((tR, tC))
    info = dict(counts=counts, occupancy=float(counts.max() / max(C, 1)),
                tile_shape=(tR, tC), offsets=offs,
                budgets=budgets, needs=needs.tolist(),
                gsmax=float(gsmax), nb=nb, nb_local=nb_t, n_tot=n_tot,
                extra_blocks=extra,
                halo_rows=int(sum(budgets)) * block * ndev)
    return new_state, np.asarray(newslot), info


def spatial_table_size(n, block=256, ndev=1):
    """Rows of the sorted-space partner table in spatial mode (the
    padded layout is device-divisible, so the table is sized to it
    EXACTLY — a per-interval slice of a sharded table would cost an
    O(N*K) reshard every interval)."""
    from ..ops import cd_sched
    return cd_sched.spatial_layout(n, block, ndev)[3]


def update_tiled(state: SimState, cfg: AsasConfig, block: int = 512,
                 impl: str = "lax", mesh=None, mesh_axis: str = "ac",
                 shard_mode: str = "replicate", halo_blocks: int = 0,
                 tile_shape=None,
                 tile_budgets=()) -> Tuple[SimState, RowConflictData]:
    """One ASAS interval via the blockwise large-N backend (ops/cd_tiled.py).

    Same pipeline as ``update`` — detect, resolve, bookkeep, resume
    (reference asas.py:473-504) — but no [N,N] array ever exists: the pair
    space is streamed in tiles and resume-nav hysteresis lives in the [N,K]
    partner table instead of the resopairs matrix.  ``impl`` selects the
    lax.scan formulation ('lax', runs everywhere) or the Pallas TPU kernel
    ('pallas', ops/cd_pallas.py).

    ``mesh`` shards the Pallas kernels' row blocks over a device mesh
    via ``shard_map`` (see ``ops/cd_sched.detect_resolve_sched``); the
    lax backend needs no manual sharding (GSPMD partitions it from the
    state shardings alone).
    """
    ac, asas = state.ac, state.asas
    k = asas.partners.shape[1]
    mvpcfg = cr_mvp.MVPConfig(
        rpz_m=cfg.rpz_m, hpz_m=cfg.hpz_m, tlookahead=cfg.dtlookahead,
        swresohoriz=cfg.swresohoriz, swresospd=cfg.swresospd,
        swresohdg=cfg.swresohdg, swresovert=cfg.swresovert)

    # Cached spatial sort, refreshed by the HOST at chunk boundaries
    # (refresh_spatial_sort below) — never inside the step: an in-jit
    # ``lax.cond``ed refresh was measured to cost the full ~70 ms
    # argsort EVERY interval, because XLA speculatively hoists the pure
    # sort out of the conditional, so the cache never cached.  Any
    # staleness (including the initial identity layout) is exact —
    # block reachability is recomputed from true positions each
    # interval; staleness only loosens the windows.
    perm = asas.sort_perm

    # Resolver mode: the blockwise kernels accumulate per-pair sums for
    # MVP or Eby (additive row reductions — reference MVP.py:149-231,
    # Eby.py:73-138); Swarm adds 7 neighbour sums (all backends); SSD
    # runs the MVP kernels for detection/partner bookkeeping and
    # resolves from the gathered partner table afterwards
    # (cr_ssd.resolve_from_partners — reference asas.py:41-55 keeps CD
    # and CR orthogonal, so any resolver must run at any N).
    reso_m = cfg.reso_method.upper()
    kern_reso = "mvp"
    if cfg.reso_on and reso_m == "EBY":
        kern_reso = "eby"
    elif cfg.reso_on and reso_m == "SWARM":
        kern_reso = "swarm"
    elif cfg.reso_on and reso_m not in ("MVP", "SSD"):
        raise ValueError(
            f"Unknown AsasConfig.reso_method {cfg.reso_method!r}; "
            "expected MVP, EBY, SWARM or SSD.")
    swarm_sums = None
    if impl == "sparse":
        from ..ops import cd_sched
        block = min(block, 256)
        n = ac.lat.shape[0]
        extra_eff = 32
        if shard_mode in ("spatial", "tiles"):
            # Spatial/tiles modes key the padded layout off the
            # sorted-space partner table, which SHARD sizing made
            # EXACTLY the device-divisible padded size (a per-interval
            # slice of a sharded table would reshard O(N*K) every
            # interval).
            n_tot = asas.partners_s.shape[0]
            nb0 = -(-n // block)
            if n_tot % block or n_tot // block <= nb0:
                raise ValueError(
                    f"{shard_mode} mode needs partners_s sized to the "
                    f"padded layout (got {n_tot} rows for n={n}, "
                    f"block={block}) — enable it via "
                    "Simulation.set_shard/SHARD SPATIAL|TILE")
            extra_eff = n_tot // block - nb0
        else:
            n_tot = cd_sched.padded_size(n, block)
        out = cd_sched.detect_resolve_sched(
            ac.lat, ac.lon, ac.trk, ac.gs, ac.alt, ac.vs,
            ac.gseast, ac.gsnorth, ac.active, asas.noreso,
            cfg.rpz, cfg.hpz, cfg.dtlookahead, mvpcfg, block=block,
            k_partners=asas.partners_s.shape[1], perm=perm,
            partners=asas.partners_s[:n_tot],
            resume_rpz_m=cfg.rpz * cfg.resofach,
            tas=ac.tas if kern_reso == "eby" else None,
            cas=ac.cas if kern_reso == "swarm" else None,
            reso=kern_reso, mesh=mesh, mesh_axis=mesh_axis,
            shard_mode=shard_mode, extra_blocks=extra_eff,
            halo_blocks=halo_blocks, tile_shape=tile_shape,
            tile_budgets=tile_budgets)
        if kern_reso == "swarm":
            rd, partners_s, act_new, swarm_sums = out
        else:
            rd, partners_s, act_new = out
    else:
        if impl == "pallas":
            from ..ops import cd_pallas
            detect_fn = functools.partial(cd_pallas.detect_resolve_pallas,
                                          mesh=mesh, mesh_axis=mesh_axis)
        else:
            detect_fn = cd_tiled.detect_resolve_tiled
        extra = None
        if kern_reso == "eby":
            extra = {"tas": ac.tas}
        elif kern_reso == "swarm":
            extra = {"cas": ac.cas}
        out = detect_fn(
            ac.lat, ac.lon, ac.trk, ac.gs, ac.alt, ac.vs,
            ac.gseast, ac.gsnorth, ac.active, asas.noreso,
            cfg.rpz, cfg.hpz, cfg.dtlookahead, mvpcfg, block=block,
            k_partners=k, perm=perm, reso=kern_reso, extra_cols=extra)
        if kern_reso == "swarm":
            rd, swarm_sums = out
        else:
            rd = out

    if cfg.reso_on and kern_reso == "swarm":
        from ..ops import cr_swarm
        # MVP collision-avoidance part from the accumulated MVP sums
        # (the reference runs MVP first, Swarm.py:68), then the blend
        # with the neighbour sums; mvp_active is the PREVIOUS interval's
        # engagement flags, like the dense path (Swarm.py:70-73).
        m_trk, m_gs, m_vs, _m_alt, _e, _n = cr_mvp.resolve_from_sums(
            rd.sum_dve, rd.sum_dvn, rd.sum_dvv, rd.tsolv,
            ac.alt, ac.gseast, ac.gsnorth, ac.vs, ac.trk, ac.gs,
            ac.selalt, state.ap.vs, asas.alt,
            cfg.vmin, cfg.vmax, cfg.vsmin, cfg.vsmax, mvpcfg,
            resooff=asas.resooff)
        _, selcas, _ = aero.vcasormach(ac.selspd, ac.alt)
        newtrk, newgs, newvs, newalt = cr_swarm.resolve_from_sums(
            *swarm_sums, ac.alt, ac.trk, ac.cas, ac.vs,
            ac.gseast, ac.gsnorth, ac.active,
            m_trk, m_gs, m_vs, asas.active,
            state.ap.trk, selcas, ac.selvs, cfg.vmin, cfg.vmax)
        asase = newgs * jnp.sin(jnp.radians(newtrk))
        asasn = newgs * jnp.cos(jnp.radians(newtrk))
        # the whole swarm updates once any conflict exists (Swarm
        # semantics, see core/asas.update)
        upd = ac.active & (rd.nconf > 0)
        asas = asas.replace(
            trk=jnp.where(upd, newtrk, asas.trk),
            tas=jnp.where(upd, newgs, asas.tas),
            vs=jnp.where(upd, newvs, asas.vs),
            alt=jnp.where(upd, newalt, asas.alt),
            asase=jnp.where(upd, asase, asas.asase),
            asasn=jnp.where(upd, asasn, asas.asasn))
    elif cfg.reso_on and reso_m == "EBY":
        from ..ops import cr_eby
        newtrk, newgs, newvs, newalt = cr_eby.resolve_from_sums(
            rd.sum_dve, rd.sum_dvn, rd.sum_dvv,
            ac.alt, ac.vs, ac.trk, ac.tas, cfg.vmin, cfg.vmax)
        asase = newgs * jnp.sin(jnp.radians(newtrk))
        asasn = newgs * jnp.cos(jnp.radians(newtrk))
        upd = rd.inconf
        asas = asas.replace(
            trk=jnp.where(upd, newtrk, asas.trk),
            tas=jnp.where(upd, newgs, asas.tas),
            vs=jnp.where(upd, newvs, asas.vs),
            alt=jnp.where(upd, newalt, asas.alt),
            asase=jnp.where(upd, asase, asas.asase),
            asasn=jnp.where(upd, asasn, asas.asasn))
    elif cfg.reso_on and reso_m == "MVP":
        newtrk, newgs, newvs, newalt, asase, asasn = cr_mvp.resolve_from_sums(
            rd.sum_dve, rd.sum_dvn, rd.sum_dvv, rd.tsolv,
            ac.alt, ac.gseast, ac.gsnorth, ac.vs, ac.trk, ac.gs,
            ac.selalt, state.ap.vs, asas.alt,
            cfg.vmin, cfg.vmax, cfg.vsmin, cfg.vsmax, mvpcfg,
            resooff=asas.resooff)
        upd = rd.inconf
        asas = asas.replace(
            trk=jnp.where(upd, newtrk, asas.trk),
            tas=jnp.where(upd, newgs, asas.tas),
            vs=jnp.where(upd, newvs, asas.vs),
            alt=jnp.where(upd, newalt, asas.alt),
            asase=jnp.where(upd, asase, asas.asase),
            asasn=jnp.where(upd, asasn, asas.asasn))

    def ssd_resolve(cur_asas, ptable):
        """SSD from the [N, P] partner table (cr_ssd.resolve_from_partners
        docstring records the K-truncation semantics).  Horizontal-only,
        like the dense path (SSD.py:99-104)."""
        from ..ops import cr_ssd
        rs = cfg.priocode.upper() if cfg.swprio \
            and cfg.priocode.upper().startswith("RS") else "RS1"
        ssdcfg = cr_ssd.SSDConfig(rpz_m=cfg.rpz_m,
                                  tlookahead=cfg.dtlookahead, priocode=rs)
        newtrk, newgs = cr_ssd.resolve_from_partners(
            ptable, rd.inconf, ac.lat, ac.lon, ac.alt, ac.trk, ac.gs,
            ac.vs, ac.gseast, ac.gsnorth, ac.active,
            cfg.vmin, cfg.vmax, ssdcfg, hdg=ac.hdg,
            ap_trk=state.ap.trk, ap_tas=state.ap.tas)
        upd = rd.inconf
        return cur_asas.replace(
            trk=jnp.where(upd, newtrk, cur_asas.trk),
            tas=jnp.where(upd, newgs, cur_asas.tas),
            asase=jnp.where(upd, newgs * jnp.sin(jnp.radians(newtrk)),
                            cur_asas.asase),
            asasn=jnp.where(upd, newgs * jnp.cos(jnp.radians(newtrk)),
                            cur_asas.asasn))

    if impl == "sparse":
        if cfg.reso_on and reso_m == "SSD":
            # The in-kernel-merged table is SORTED-space; translate to
            # caller slots for the gathered VO construction (one scatter
            # + two [N, K] gathers per interval).
            n = ac.lat.shape[0]
            ptable = cd_sched.partners_to_caller(
                perm, partners_s, n, n_tot)
            asas = ssd_resolve(asas, ptable)
        if cfg.reso_on and kern_reso == "swarm":
            # Whole swarm follows ASAS once any conflict triggered a
            # resolve (asas.py:487 gate + Swarm.py:101-102)
            act_new = jnp.where(rd.nconf > 0, ac.active, act_new)
        # Resume-nav already happened IN-KERNEL (keep + merge on the
        # sorted-space table) — just store the new table + flags.
        spad = asas.partners_s.shape[0] - partners_s.shape[0]
        if spad > 0:
            partners_s = jnp.concatenate(
                [partners_s,
                 jnp.full((spad, partners_s.shape[1]), -1, jnp.int32)])
        asas = asas.replace(
            partners_s=partners_s,
            active=act_new & cfg.reso_on,
            inconf=rd.inconf,
            tcpamax=rd.tcpamax.astype(asas.tcpamax.dtype),
            nconf_cur=rd.nconf,
            nlos_cur=rd.nlos)
        return state.replace(asas=asas), rd

    # Resume-nav on the partner table, matching the dense path's pruning of
    # (old | new swconfl) through resume_nav (asas.py:409-471) as closely as
    # the K-wide table allows: prune the old partners first (so stale
    # past-CPA entries cannot evict still-engaged ones from the K slots),
    # merge in this interval's fresh conflicts, then prune the merged table
    # (so a borderline fresh conflict already past CPA releases immediately
    # instead of staying engaged one interval longer than the dense path).
    prune = lambda tbl: cd_tiled.partner_keep(
        tbl, ac.lat, ac.lon, ac.gseast, ac.gsnorth, ac.trk,
        ac.active, cfg.rpz, cfg.rpz * cfg.resofach)
    new_idx = cd_tiled.topk_partners(rd, k)
    merged = cd_tiled.merge_partners(new_idx, asas.partners,
                                     prune(asas.partners))
    partners = jnp.where(prune(merged), merged, -1)

    if cfg.reso_on and reso_m == "SSD":
        # SSD resolves from the freshly merged table (fresh top-K
        # conflicts first + still-engaged partners — caller space here)
        asas = ssd_resolve(asas, partners)

    act_tbl = jnp.any(partners >= 0, axis=1)
    if cfg.reso_on and kern_reso == "swarm":
        # Whole swarm follows ASAS once any conflict triggered a resolve
        # (asas.py:487 gate + Swarm.py:101-102 active.fill(True))
        act_tbl = jnp.where(rd.nconf > 0, ac.active, act_tbl)
    asas = asas.replace(
        partners=partners,
        active=act_tbl & cfg.reso_on,
        inconf=rd.inconf,
        tcpamax=rd.tcpamax.astype(asas.tcpamax.dtype),
        nconf_cur=rd.nconf,
        nlos_cur=rd.nlos)
    return state.replace(asas=asas), rd
