"""Trajectory optimization: Adam descent through the chunked step scan.

The driver descends on per-aircraft **lateral waypoint offsets**
(meters perpendicular to the initial track, applied to every route
waypoint + the cached active waypoint) and **departure-time offsets**
(seconds, applied as an along-track shift of the initial position) via
``jax.value_and_grad`` over the smooth rollout:

* the rollout is the REAL step scan (core/step.step) with
  ``SimConfig.smooth`` set — the documented relaxations of
  diff/smooth.py — chunked and wrapped in ``jax.checkpoint`` across
  chunk boundaries, so backward-pass memory stays O(chunk·state +
  nchunks·state) instead of O(nsteps·state);
* the objective (diff/objectives.py) accumulates in the scan carry:
  soft LoS (annealed temperature, traced so annealing never
  recompiles) + fuel + deviation penalty;
* the integrity-guard word of ``run_steps_checked`` is EXTENDED over
  the backward pass (``GUARD_BAD_*``): >= 0 pins the first non-finite
  forward step exactly like the serving guard, -2 flags a non-finite
  objective, -3 non-finite gradients — the optimizer halts on any trip
  and the host routes it through the existing guard machinery
  (fault/guard.py trip records);
* multi-start batching rides the PR-6 world axis: ``restarts > 1``
  stacks R perturbed offset particles on a leading world axis and
  steps them with ``core/step.step(worlds=True)`` in ONE scan (the
  many-scenarios-per-device shape of arXiv:2406.08496), returning the
  best particle.

Optimized plans are verified against the HARD metric: a plain
(smooth=None) scan of the offset-applied state counting exact LoS
pairs per step.  The headline demo (tests/test_diff.py,
scripts/grad_smoke.py) optimizes a 50-aircraft conflict scene to zero
hard-metric LoS.
"""
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.step import (SimConfig, state_finite, step, stack_worlds,
                         world_slice)
from ..ops import aero
from . import objectives
from .objectives import ObjectiveWeights, TSHIFT_SCALE
from .smooth import SmoothConfig

#: guard word extensions over run_steps_checked's contract
#: (>= 0 = first bad forward step, -1 = clean):
GUARD_BAD_VALUE = -2     # non-finite objective out of the forward pass
GUARD_BAD_GRADS = -3     # non-finite gradients out of the backward pass


class OffsetParams(NamedTuple):
    """The optimized decision variables, one row per aircraft slot.
    Normalized units (lateral in protected-zone radii, time shifts
    tanh-bounded to a ±TSHIFT_SCALE-second departure slot) keep Adam's
    step size geometry-free."""
    lateral: jnp.ndarray    # [*, N] lateral waypoint offset [rpz units]
    tshift: jnp.ndarray     # [*, N] departure-time offset [tanh units]


def tshift_seconds(tshift_param):
    """Effective departure-time offset [s]: tanh-squashed so the
    optimizer can never 'teleport' an aircraft past its whole conflict
    (an unbounded time shift trivially zeroes the objective by moving
    the crossing outside the horizon — a degenerate optimum, not a
    plan).  The ±TSHIFT_SCALE bound models a realistic departure slot."""
    return TSHIFT_SCALE * jnp.tanh(tshift_param)


def apply_offsets(state, params: OffsetParams, rpz):
    """Apply the decision variables to a base state, differentiably.

    * lateral: every route waypoint and the cached active waypoint
      shift ``lateral * rpz`` meters perpendicular to the aircraft's
      current track;
    * tshift: the initial position shifts ``tshift_seconds(tshift)``
      BACKWARD along the current ground velocity (a positive shift
      delays the crossing like a later departure would).

    Padding rows are frozen (offsets masked by ``active``).
    """
    ac = state.ac
    live = ac.active
    lat_m = jnp.where(live, params.lateral * rpz, 0.0)
    dt_s = jnp.where(live, tshift_seconds(params.tshift), 0.0)

    trkrad = jnp.radians(ac.trk)
    tn, te = jnp.cos(trkrad), jnp.sin(trkrad)
    # perpendicular (left of track) unit vector
    pn, pe = -te, tn
    coslat = jnp.maximum(jnp.abs(ac.coslat), 1e-6)
    dlat_wp = jnp.degrees(pn * lat_m / aero.Rearth)
    dlon_wp = jnp.degrees(pe * lat_m / aero.Rearth / coslat)

    route = state.route.replace(
        wplat=state.route.wplat + dlat_wp[:, None],
        wplon=state.route.wplon + dlon_wp[:, None])
    actwp = state.actwp.replace(lat=state.actwp.lat + dlat_wp,
                                lon=state.actwp.lon + dlon_wp)
    dlat_t = jnp.degrees(-dt_s * ac.gsnorth / aero.Rearth)
    dlon_t = jnp.degrees(-dt_s * ac.gseast / aero.Rearth / coslat)
    ac = ac.replace(lat=ac.lat + dlat_t, lon=ac.lon + dlon_t)
    return state.replace(ac=ac, route=route, actwp=actwp)


# ------------------------------------------------------------- rollouts
def _rollout(state, cfg: SimConfig, nsteps: int, chunk: int,
             weights: ObjectiveWeights, temp, worlds: bool,
             los_margin: float = 1.0):
    """The chunked, checkpointed objective rollout.

    Returns ``(cost, final_state, bad)`` where ``cost`` is the
    accumulated step objective (scalar, or [W] with a world axis),
    and ``bad`` the per-rollout first-bad-step guard word (as
    run_steps_checked; [W] when batched).  ``jax.checkpoint`` wraps the
    chunk body: the forward stores only chunk-boundary states and the
    backward recomputes within each chunk — O(chunk) live activations.
    """
    nchunks = max(1, -(-nsteps // chunk))
    stepfn = lambda s: step(s, cfg, worlds)
    rpz_s = cfg.asas.rpz * los_margin    # margin-inflated SOFT zone
    hpz_s = cfg.asas.hpz
    costfn = objectives.step_cost
    if worlds:
        costfn = jax.vmap(objectives.step_cost,
                          in_axes=(0, None, None, None, None, None))
    finitefn = jax.vmap(state_finite) if worlds else state_finite

    def chunk_body(carry, i0):
        def body(c, i):
            s, acc, bad = c
            s = stepfn(s)
            acc = acc + costfn(s, rpz_s, hpz_s, weights, temp, cfg.simdt)
            bad = jnp.where(bad >= 0, bad,
                            jnp.where(finitefn(s), -1, i))
            return (s, acc, bad), None
        return jax.lax.scan(body, carry,
                            i0 + jnp.arange(chunk, dtype=jnp.int32))

    chunk_body = jax.checkpoint(chunk_body)
    zero = jnp.zeros((state.simt.shape[0],) if worlds else (),
                     state.simt.dtype)
    badw = jnp.full(zero.shape, -1, jnp.int32)
    (state, acc, bad), _ = jax.lax.scan(
        chunk_body, (state, zero, badw),
        jnp.arange(nchunks, dtype=jnp.int32) * chunk)
    return acc, state, bad


@partial(jax.jit, static_argnames=("cfg", "nsteps"))
def _hard_los_scan(state, cfg: SimConfig, nsteps: int):
    """Module-level jitted verification scan (cfg/nsteps static, so
    repeated before/after verifications of one OPT — and every OPT
    piece of a sweep — hit the same compiled program)."""
    rpz, hpz = cfg.asas.rpz, cfg.asas.hpz

    def body(c, _):
        s, mx, tot = c
        s = step(s, cfg)
        n = objectives.hard_los_count(s, rpz, hpz)
        return (s, jnp.maximum(mx, n),
                tot + (n > 0).astype(jnp.int32)), None

    (s, mx, tot), _ = jax.lax.scan(
        body, (state, jnp.zeros((), jnp.int32),
               jnp.zeros((), jnp.int32)),
        None, length=nsteps)
    return mx, tot, s


def hard_los_trace(state, cfg: SimConfig, nsteps: int,
                   simdt: Optional[float] = None):
    """HARD-metric verification scan: step the EXACT (smooth=None) scan
    and return ``(max_los, total_los_steps, final_state)`` — the peak
    directional LoS pair count over every step and the number of steps
    with any LoS.  This is the metric optimized plans are judged by.

    ``simdt`` re-times the scan (default: keep cfg's): the driver
    verifies at the SERVING resolution (0.05 s), where the bang-bang
    dead-bands are tight — measured < 1 km of a 400 s smooth-dt=1 plan
    — rather than at the coarse optimization dt, whose 2°-wide heading
    dead-band is an artifact of the step size, not of the plant."""
    if simdt is not None:
        nsteps = max(1, int(round(nsteps * cfg.simdt / float(simdt))))
        cfg = cfg._replace(simdt=float(simdt))
    cfg = cfg._replace(smooth=None)
    mx, tot, s = _hard_los_scan(state, cfg, nsteps)
    return int(mx), int(tot), s


# ------------------------------------------------- checked value_and_grad
def checked_value_and_grad(fn):
    """``jax.value_and_grad(fn, has_aux=True)`` with the integrity-guard
    word extended over the backward pass.

    ``fn(params, ...) -> (cost, aux)`` where ``aux`` carries the
    forward guard word under key ``"bad"``.  Returns
    ``(value, aux, grads, bad)`` with ``bad``:

    * ``>= 0``             — first non-finite FORWARD step (the
                             run_steps_checked contract, unchanged),
    * ``GUARD_BAD_VALUE``  — the objective itself came back non-finite,
    * ``GUARD_BAD_GRADS``  — the BACKWARD pass produced a non-finite
                             gradient leaf,
    * ``-1``               — clean.
    """
    vg = jax.value_and_grad(fn, has_aux=True)

    def checked(*args, **kwargs):
        (value, aux), grads = vg(*args, **kwargs)
        gfinite = jnp.array(True)
        for leaf in jax.tree_util.tree_leaves(grads):
            gfinite &= jnp.all(jnp.isfinite(leaf))
        fwd_bad = jnp.max(jnp.asarray(aux["bad"]))
        bad = jnp.where(
            fwd_bad >= 0, fwd_bad,
            jnp.where(~jnp.all(jnp.isfinite(jnp.asarray(value))),
                      GUARD_BAD_VALUE,
                      jnp.where(~gfinite, GUARD_BAD_GRADS, -1)))
        return value, aux, grads, bad.astype(jnp.int32)

    return checked


# ------------------------------------------------------------ the driver
class OptResult(NamedTuple):
    lateral_m: np.ndarray       # [N] optimized lateral offsets [m]
    tshift_s: np.ndarray        # [N] optimized time offsets [s]
    objective: list             # per-iteration total objective
    grad_norm: list             # per-iteration gradient 2-norm
    temps: list                 # annealing schedule actually used
    hard_los_before: int        # peak hard LoS pairs, zero offsets
    hard_los_after: int         # peak hard LoS pairs, optimized
    bad: int                    # final guard word (-1 clean)
    iters: int
    nsteps: int
    restarts: int
    best_restart: int

    def to_payload(self, traf_ids=None, slots=None):
        """JSON-able summary for the OPT journal record / client echo."""
        sl = list(slots) if slots is not None else \
            list(range(len(self.lateral_m)))
        d = {
            "iters": self.iters, "nsteps": self.nsteps,
            "restarts": self.restarts, "best_restart": self.best_restart,
            "objective_first": float(self.objective[0]),
            "objective_last": float(self.objective[-1]),
            "objective_trace": [round(float(v), 6)
                                for v in self.objective],
            "hard_los_before": self.hard_los_before,
            "hard_los_after": self.hard_los_after,
            "bad": self.bad,
            "lateral_m": [round(float(self.lateral_m[s]), 2)
                          for s in sl],
            "tshift_s": [round(float(self.tshift_s[s]), 3) for s in sl],
        }
        if traf_ids is not None:
            d["acid"] = [traf_ids[s] for s in sl]
        return d


def _adam(params, grads, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = jax.tree_util.tree_map(lambda m_, g: b1 * m_ + (1 - b1) * g,
                               m, grads)
    v = jax.tree_util.tree_map(lambda v_, g: b2 * v_ + (1 - b2) * g * g,
                               v, grads)
    mh = jax.tree_util.tree_map(lambda m_: m_ / (1 - b1 ** t), m)
    vh = jax.tree_util.tree_map(lambda v_: v_ / (1 - b2 ** t), v)
    params = jax.tree_util.tree_map(
        lambda p, m_, v_: p - lr * m_ / (jnp.sqrt(v_) + eps),
        params, mh, vh)
    return params, m, v


def optimize(state, asas_cfg=None, *, tend: float = 600.0,
             simdt: float = 1.0, chunk: int = 50, iters: int = 60,
             lr: float = 0.15, temp0: float = 0.3, temp1: float = 0.05,
             weights: Optional[ObjectiveWeights] = None,
             smooth: Optional[SmoothConfig] = None,
             with_asas: bool = False, restarts: int = 1, seed: int = 0,
             opt_tshift: bool = True, init_noise: float = 0.1,
             los_margin: float = 1.2, verify_simdt: float = 0.05,
             verbose=None) -> OptResult:
    """Descend on waypoint/time offsets until the (annealed) soft-LoS
    objective is minimized; verify against the hard metric.

    ``state`` is a plain single-world SimState (e.g. ``sim.traf.state``
    at OPT-command time).  The optimization rollout runs the smooth
    scan at ``simdt`` (coarser than the serving 0.05 s — guidance and
    the objective are what matter, and the hard verification runs at
    the same dt); ASAS stays OUT of the optimization loop by default
    (strategic deconfliction of the open-loop plans — set
    ``with_asas=True`` to optimize THROUGH the smooth MVP resolver).

    ``restarts > 1`` runs R perturbed starts batched on the world axis
    in one scan (PR-6 world axis) and returns the best particle.
    """
    from ..core.asas import AsasConfig
    asas_cfg = asas_cfg if asas_cfg is not None else AsasConfig()
    weights = weights or ObjectiveWeights()
    smooth = smooth or SmoothConfig()
    rpz, hpz = float(asas_cfg.rpz), float(asas_cfg.hpz)
    opt_asas = asas_cfg if with_asas \
        else asas_cfg._replace(swasas=False)
    cfg = SimConfig(simdt=float(simdt), asas=opt_asas,
                    cd_backend="dense", smooth=smooth)
    nsteps = max(1, int(round(float(tend) / float(simdt))))
    chunk = max(1, min(int(chunk), nsteps))
    nsteps = -(-nsteps // chunk) * chunk     # whole chunks (scan shape)
    iters = max(1, int(iters))               # 0 iters has no iterate to
    #                                          return; run one
    nmax = state.ac.lat.shape[0]
    worlds = restarts > 1

    base = state
    if worlds:
        base = stack_worlds([state] * restarts)

    def cost_fn(params, bstate, temp):
        pl = params.lateral
        pt = params.tshift if opt_tshift \
            else jax.lax.stop_gradient(params.tshift)
        if worlds:
            s = jax.vmap(apply_offsets, in_axes=(0, 0, None))(
                bstate, OffsetParams(pl, pt), rpz)
            dev = jax.vmap(objectives.deviation_penalty,
                           in_axes=(0, 0, None, None))(
                pl * rpz, tshift_seconds(pt), rpz, weights)
        else:
            s = apply_offsets(bstate, OffsetParams(pl, pt), rpz)
            dev = objectives.deviation_penalty(
                pl * rpz, tshift_seconds(pt), rpz, weights)
        acc, final, bad = _rollout(s, cfg, nsteps, chunk, weights,
                                   temp, worlds, los_margin=los_margin)
        per = acc + dev                      # scalar or [W]
        return jnp.sum(per), {"per_restart": per, "bad": bad}

    vgc = checked_value_and_grad(cost_fn)

    @jax.jit
    def opt_iter(params, m, v, t, temp):
        value, aux, grads, bad = vgc(params, base, temp)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in
                             jax.tree_util.tree_leaves(grads)))
        params, m, v = _adam(params, grads, m, v, t, lr)
        return params, m, v, value, aux["per_restart"], gnorm, bad

    shape = (restarts, nmax) if worlds else (nmax,)
    dtype = state.ac.lat.dtype
    key = jax.random.PRNGKey(seed)
    # Jittered initialization is REQUIRED, not cosmetic: an exactly
    # head-on pair sits on a symmetry saddle of the soft-LoS objective
    # (the lateral derivative of the pair distance is dy/dist = 0 on
    # the aligned ridge), so zero offsets have zero deconfliction
    # gradient.  ~init_noise·rpz of seeded noise breaks every such tie;
    # multi-start particles get progressively wider draws.
    lat0 = init_noise * jax.random.normal(key, shape, dtype)
    if worlds:
        widen = jnp.linspace(1.0, 3.0, restarts, dtype=dtype)
        lat0 = lat0 * widen[:, None]
    params = OffsetParams(lateral=lat0, tshift=jnp.zeros(shape, dtype))
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)

    temps = objectives.anneal_schedule(temp0, temp1, iters)
    # opt_step spans (ISSUE-12 satellite): one per descent iteration —
    # the optimize driver was missing from the PR-11 span vocabulary
    from ..obs.trace import get_recorder
    rec = get_recorder()
    trace, gnorms = [], []
    bad_word = -1
    for it in range(iters):
        # keep the pre-update iterate: on a guard trip the Adam update
        # has already folded the non-finite gradients into the NEW
        # params, and "halt at the last finite iterate" must mean it
        params_prev = params
        with rec.span("opt_step", cat="opt", it=it,
                      restarts=restarts, nsteps=nsteps):
            params, m, v, value, per, gnorm, bad = opt_iter(
                params, m, v, it + 1, jnp.asarray(temps[it], dtype))
            bad_word = int(bad)
        trace.append(float(value))
        gnorms.append(float(gnorm))
        if verbose:
            verbose(it, float(value), float(gnorm), bad_word)
        if bad_word != -1:
            params = params_prev       # guard trip: halt the descent
            break

    per = np.asarray(per)
    best = int(np.argmin(per)) if worlds else 0
    bp = OffsetParams(*[np.asarray(world_slice(p, best) if worlds else p)
                        for p in params])
    lateral_m = np.where(np.asarray(state.ac.active),
                         bp.lateral * rpz, 0.0)
    tshift_s = np.where(np.asarray(state.ac.active) & opt_tshift,
                        TSHIFT_SCALE * np.tanh(bp.tshift), 0.0)

    # hard-metric verification of the zero-offset and optimized plans
    zerop = OffsetParams(jnp.zeros((nmax,), dtype),
                         jnp.zeros((nmax,), dtype))
    los_before, _, _ = hard_los_trace(
        apply_offsets(state, zerop, rpz), cfg, nsteps,
        simdt=verify_simdt)
    optp = OffsetParams(
        jnp.asarray(lateral_m / rpz, dtype),
        jnp.asarray(np.arctanh(np.clip(tshift_s / TSHIFT_SCALE,
                                       -0.999999, 0.999999)), dtype))
    los_after, _, _ = hard_los_trace(
        apply_offsets(state, optp, rpz), cfg, nsteps,
        simdt=verify_simdt)

    return OptResult(
        lateral_m=lateral_m, tshift_s=tshift_s, objective=trace,
        grad_norm=gnorms, temps=temps[:len(trace)],
        hard_los_before=los_before, hard_los_after=los_after,
        bad=bad_word, iters=len(trace), nsteps=nsteps,
        restarts=restarts, best_restart=best)


def grad_once(state, asas_cfg=None, *, tend: float = 600.0,
              simdt: float = 1.0, chunk: int = 50, temp: float = 1.0,
              weights: Optional[ObjectiveWeights] = None,
              smooth: Optional[SmoothConfig] = None,
              with_asas: bool = False, los_margin: float = 1.2):
    """One checked value_and_grad evaluation at zero offsets (the GRAD
    stack command): returns ``(objective, grad_norm, bad)``."""
    from ..core.asas import AsasConfig
    asas_cfg = asas_cfg if asas_cfg is not None else AsasConfig()
    weights = weights or ObjectiveWeights()
    smooth = smooth or SmoothConfig()
    rpz = float(asas_cfg.rpz)
    opt_asas = asas_cfg if with_asas else asas_cfg._replace(swasas=False)
    cfg = SimConfig(simdt=float(simdt), asas=opt_asas,
                    cd_backend="dense", smooth=smooth)
    nsteps = max(1, int(round(float(tend) / float(simdt))))
    chunk = max(1, min(int(chunk), nsteps))

    def cost_fn(params, bstate, t):
        s = apply_offsets(bstate, params, rpz)
        acc, _, bad = _rollout(s, cfg, nsteps, chunk, weights, t, False,
                               los_margin=los_margin)
        return acc, {"bad": bad}

    nmax = state.ac.lat.shape[0]
    dtype = state.ac.lat.dtype
    params = OffsetParams(jnp.zeros((nmax,), dtype),
                          jnp.zeros((nmax,), dtype))
    value, _aux, grads, bad = checked_value_and_grad(cost_fn)(
        params, state, jnp.asarray(temp, dtype))
    gnorm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in
                               jax.tree_util.tree_leaves(grads))))
    return float(value), gnorm, int(bad)


# --------------------------------------------------------------- scenes
def conflict_scene(n_ac: int = 50, *, leg_km: float = 60.0,
                   pair_spacing_km: float = 80.0, alt_m: float = 8000.0,
                   spd_ms: float = 240.0, lat0: float = 48.0,
                   lon0: float = 4.0, nmax: Optional[int] = None,
                   dtype=None, wmax: int = 8):
    """A guaranteed-conflict scene: ``n_ac // 2`` head-on pairs on an
    east-west axis, pairs stacked north-south far enough apart that
    only partners conflict.  Every aircraft files a single waypoint at
    its partner's start (LNAV direct), so with zero offsets each pair
    meets nose-to-nose at its midpoint — the 50-aircraft demo scene
    gradient descent must deconflict to zero hard LoS.

    Returns ``(traf, cfg_asas)`` — a host Traffic facade whose state is
    ready to roll out.
    """
    from ..core.asas import AsasConfig
    from ..core.traffic import Traffic

    n_pairs = max(1, n_ac // 2)
    n = 2 * n_pairs
    dlat_pair = pair_spacing_km / 111.0
    dlon_leg = leg_km / 111.0     # deliberately ~cos-uncorrected: scene
    #                               scale only needs to be approximate
    lats, lons, hdgs = [], [], []
    for k in range(n_pairs):
        plat = lat0 + k * dlat_pair
        lats += [plat, plat]
        lons += [lon0 - dlon_leg, lon0 + dlon_leg]
        hdgs += [90.0, 270.0]
    traf = Traffic(nmax=nmax or n, wmax=wmax,
                   dtype=dtype or jnp.float32, pair_matrix=True)
    traf.create(n, "B744", alt_m, spd_ms, None,
                np.asarray(lats), np.asarray(lons), np.asarray(hdgs),
                acid=[f"OPT{i:03d}" for i in range(n)])
    traf.flush()

    st = traf.state
    # single-waypoint LNAV-direct routes: each aircraft aims at its
    # partner's start point (functional table writes; route edits at
    # stack cadence go through core/route.py — this is a scene builder)
    nmax_eff = st.ac.lat.shape[0]
    partner = np.arange(n) ^ 1
    wplat = np.array(st.route.wplat)
    wplon = np.array(st.route.wplon)
    wplat[:n, 0] = np.asarray(lats)[partner]
    wplon[:n, 0] = np.asarray(lons)[partner]
    nwp = np.array(st.route.nwp)
    nwp[:n] = 1
    aw_lat = np.array(st.actwp.lat)
    aw_lon = np.array(st.actwp.lon)
    aw_lat[:n] = np.asarray(lats)[partner]
    aw_lon[:n] = np.asarray(lons)[partner]
    lnav = np.zeros(nmax_eff, bool)
    lnav[:n] = True
    st = st.replace(
        route=st.route.replace(
            wplat=jnp.asarray(wplat, st.route.wplat.dtype),
            wplon=jnp.asarray(wplon, st.route.wplon.dtype),
            nwp=jnp.asarray(nwp, jnp.int32),
            iactwp=jnp.where(jnp.asarray(lnav), 0, st.route.iactwp)),
        actwp=st.actwp.replace(
            lat=jnp.asarray(aw_lat, st.actwp.lat.dtype),
            lon=jnp.asarray(aw_lon, st.actwp.lon.dtype)),
        ac=st.ac.replace(
            swlnav=jnp.asarray(lnav),
            swvnav=jnp.zeros((nmax_eff,), bool)))
    traf.state = st
    return traf, AsasConfig()
