"""The simulation step: one fused, jittable state -> state function.

Mirrors the reference hot loop ``Traffic.update`` (traffic.py:383-423) and
its caller ``Simulation.step`` (simulation/qtgl/simulation.py:62-128), with
the reference's time-staggered scheduling (FMS at ~1.01 s, ASAS at 1 s,
kinematics every simdt=0.05 s) reproduced *inside* jit via ``lax.cond`` on
device clocks — so a whole chunk of steps runs as one ``lax.scan`` with a
single host sync per chunk instead of the reference's per-step Python
dispatch.

Pipeline order per step (identical to traffic.py:383-423, OpenAP flavour):
  atmosphere -> ADS-B -> FMS (gated) -> ASAS CD&R (gated) -> AP/ASAS
  arbitration -> performance update -> envelope limits -> airspeed ->
  groundspeed (wind) -> position -> turbulence
"""
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import asas as asasmod
from . import autopilot, kinematics, noise, perf as perfmod, pilot, wind as windmod
from .asas import AsasConfig
from .noise import NoiseConfig
from .state import SimState, time_of_count


class SimConfig(NamedTuple):
    """Static simulation configuration (hashable -> jit-static).

    Changing a field recompiles the step (cached per value) — these change
    at stack-command cadence, not step cadence.
    """
    simdt: float = 0.05          # [s] (reference simulation.py:15)
    fms_dt: float = autopilot.FMS_DT
    asas: AsasConfig = AsasConfig()
    noise: NoiseConfig = NoiseConfig()
    use_wind: bool = False
    # CD&R backend: 'dense' materialises [N,N] (exact reference parity,
    # fine to ~16k AC); 'tiled' streams [cd_block]² tiles with a [N,K]
    # partner table — required for the 100k north star (ops/cd_tiled.py);
    # 'pallas' is the tiled scheme as a hand-written TPU kernel
    # (ops/cd_pallas.py, TPU-only); 'sparse' is the segment-scheduled
    # kernel with the stripe sort (ops/cd_sched.py, TPU-only) — the
    # fastest large-N path for spread-out fleets, exact-equal results.
    cd_backend: str = "dense"
    cd_block: int = 512
    # The dense backend runs its interval on the leading cd_rows slots
    # (core/asas.update); 0 = on all of them.  Never a setting: whoever
    # dispatches a chunk fills it in from the slots the fleet occupies
    # (cd_dense_rows below), and the other backends never read it.
    cd_rows: int = 0
    # Device mesh for the Pallas backends' shard_map row split (the lax
    # and dense backends shard via GSPMD from state shardings alone and
    # ignore this).  A jax.sharding.Mesh is hashable, so the config
    # stays jit-static; parallel.sharding.sharded_step_fn fills it in.
    cd_mesh: object = None
    cd_mesh_axis: str = "ac"
    # Multi-chip decomposition of the sparse backend on that mesh:
    # 'replicate' = interleaved row blocks vs replicated O(N) columns
    # (the round-4 scheme, ~200x ceiling as D grows); 'spatial' =
    # device-owned latitude stripes with conservative halo exchange —
    # O(N/D) state/schedule/sort per device, O(halo) wire per interval
    # (docs/PERF_ANALYSIS.md §multi-chip).  Spatial requires the
    # stripe-bucketed caller layout kept by the spatial sort refresh
    # (core/asas.refresh_spatial_shard / the SHARD stack command).
    cd_shard_mode: str = "replicate"
    # Halo width in 256-wide blocks each side of a device's stripe
    # range (0 = one full neighbour device, always covering; smaller
    # values cut the boundary exchange and are validated against the
    # exact reach bound + drift margin at every refresh).
    cd_halo_blocks: int = 0
    # 2-D tile decomposition ('tiles' shard mode): (R, C) shape of the
    # ('lat', 'lon') device mesh, and the per-canonical-offset halo
    # slab budgets pinned by the tile refresh (() = unpinned, whole
    # neighbour tiles).  Tuples, so the config stays hashable/static.
    cd_tile_shape: tuple = ()
    cd_tile_budgets: tuple = ()
    # Differentiable mode (bluesky_tpu/diff/): a diff.smooth.SmoothConfig
    # swaps the hard gates for the documented relaxations (conflict
    # sigmoid, softmin resolver reductions, straight-through clamps,
    # stop-gradiented RNG draws) so jax.grad through run_steps carries
    # useful gradients.  None — the default, and the ONLY value the
    # serving path ever sets — takes every original code path at trace
    # time: bit-identical to the pre-relaxation step (tests/test_diff.py
    # pins this).  A NamedTuple of floats, so the config stays hashable.
    smooth: object = None
    # In-scan telemetry (obs/scanstats.py): fold per-step device-side
    # stats through the chunk-scan carry and emit them once per chunk
    # as extra non-donated outputs next to EdgeTelemetry.  False — the
    # default — leaves the pack out of the one scan body's carry at
    # trace time (the bare scan's HLO, pinned by obs_smoke's parity
    # hash); True adds pure carry folds and ZERO host syncs or in-scan
    # collectives (tests/test_hlo_collectives.py pins the budget).
    scanstats: bool = False
    # SDC-defense state fingerprint (obs/fingerprint.py): fold a 32-bit
    # bit-pattern witness of the stepped state through the chunk-scan
    # carry and emit it once per chunk next to EdgeTelemetry, so the
    # serving layer can compare hedge-duplicate / shadow-audit / voted
    # re-executions of the same piece bit-for-bit.  False — the default
    # — leaves the pack out of the carry (the scanstats contract); True
    # adds pure bitwise carry folds with ZERO host syncs and ZERO
    # in-scan collectives.
    fingerprint: bool = False


def _check_cfg(state: SimState, cfg: SimConfig):
    """Refuse a SimConfig the ASAS gate cannot run (trace time; the
    same checks for one world and for many)."""
    if not cfg.asas.swasas:
        return
    if cfg.cd_backend not in ("dense", "tiled", "pallas", "sparse"):
        raise ValueError(
            f"Unknown SimConfig.cd_backend {cfg.cd_backend!r}; "
            "expected 'dense', 'tiled', 'pallas' or 'sparse'.")
    if cfg.smooth is not None and cfg.cd_backend != "dense":
        raise ValueError(
            "SimConfig.smooth (differentiable mode) relaxes the "
            "dense CD&R path only: the tiled/pallas/sparse kernels "
            "carry integer partner tables that do not differentiate."
            "  Use cd_backend='dense' (diff workloads run small-N).")
    if cfg.cd_shard_mode not in ("replicate", "spatial", "tiles"):
        raise ValueError(
            f"Unknown SimConfig.cd_shard_mode {cfg.cd_shard_mode!r}; "
            "expected 'replicate', 'spatial' or 'tiles'.")
    if cfg.cd_shard_mode in ("spatial", "tiles") \
            and cfg.cd_backend != "sparse":
        raise ValueError(
            f"cd_shard_mode='{cfg.cd_shard_mode}' is the sparse "
            "backend's domain decomposition (stripes/tiles are a "
            "property of the sorted schedule); use "
            "cd_backend='sparse'")
    if cfg.cd_shard_mode == "tiles" and (
            not cfg.cd_tile_shape or len(cfg.cd_tile_shape) != 2):
        raise ValueError(
            "cd_shard_mode='tiles' needs cd_tile_shape=(R, C) — "
            "set it via Simulation.set_shard / SHARD TILE RxC")
    if cfg.cd_backend == "dense" and state.asas.resopairs.size == 0:
        raise ValueError(
            "State was allocated with pair_matrix=False (no [N,N] "
            "resopairs) but SimConfig.cd_backend is "
            f"'{cfg.cd_backend}'. Use SimConfig(cd_backend='tiled') or "
            "allocate Traffic(pair_matrix=True).")
    if cfg.cd_backend != "dense" and cfg.asas.reso_on:
        rm = cfg.asas.reso_method.upper()
        if rm not in ("MVP", "EBY", "SWARM", "SSD"):
            raise ValueError(
                f"Unknown resolver {cfg.asas.reso_method!r}; every "
                "backend carries MVP/EBY (pair sums), SWARM "
                "(neighbour sums) and SSD (partner-table VOs) — "
                "reference asas.py:41-55 keeps CD and CR orthogonal.")


#: Lowest rung of the dense interval's row ladder: the lane width.
CD_ROWS_MIN = 128


def cd_dense_rows(cfg: SimConfig, nmax: int, bound: int):
    """``(cfg, rows)`` for the next chunk: the config to hand its
    runner, and how many leading slots its dense CD&R interval runs on.
    ``rows`` is ``bound`` (one more than the highest slot that holds an
    aircraft, by the host's record at dispatch; creations and deletions
    reach the state only at a chunk's edge, so it holds for the chunk)
    rounded up to 128, 256, 512, ... and capped at ``nmax``, so a fleet
    compiles a handful of programs a chunk length.  It is ``nmax``
    where the bound is not to be used: the differentiable mode (its
    tests pin its programs) and a device mesh (slicing a sharded axis
    moves data).  ``cd_rows`` stays 0 for ``nmax``: one key for the
    whole-fleet program.  For the dense backend only: the others have
    no such interval and never read the field."""
    rows = nmax
    if cfg.smooth is None and cfg.cd_mesh is None:
        rows = CD_ROWS_MIN
        while rows < bound:
            rows *= 2
        rows = min(rows, nmax)
    return cfg._replace(cd_rows=rows if rows < nmax else 0), rows


def _select_worlds(mask, new_tree, old_tree):
    """Per-world select: ``mask`` is [W] bool, tree leaves are [W, ...];
    worlds where mask is False keep their old leaves bit-exactly."""
    def sel(new, old):
        m = mask.reshape(mask.shape + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old)
    return jax.tree_util.tree_map(sel, new_tree, old_tree)


def _each(worlds: bool):
    """``each(fn)``: ``fn`` itself for one world, ``jax.vmap(fn)`` over
    the leading axis of a stacked [W, ...] state."""
    return jax.vmap if worlds else (lambda fn: fn)


def _gate(worlds: bool):
    """``gate(due, fn, state)``: run the per-world ``fn`` where its
    clock says so.  For one world that is ``lax.cond``.  For many it is
    NOT ``vmap`` of that cond: under plain vmap a ``lax.cond`` lowers
    to a select that runs BOTH branches every step, so the 1 Hz ASAS
    interval and the ~1 s FMS update would burn their full cost every
    0.05 s step in every world (~20x the arithmetic — measured 25x
    slower than unbatched, the opposite of batching).  Each gate is
    instead a scalar ``any world due`` cond around the vmapped branch
    plus a per-world select, so a step where NO world hits the gate (19
    of 20 at the default cadences) skips the branch exactly like the
    single-world scan does; packed scenarios share their cadence by
    construction (same SimConfig), so the union schedule stays the
    single-world schedule even for worlds at different sim times.
    """
    if not worlds:
        return lambda due, fn, s: jax.lax.cond(due, fn, lambda s: s, s)
    return lambda due, fn, s: jax.lax.cond(
        jnp.any(due),
        lambda s: _select_worlds(due, jax.vmap(fn)(s), s),
        lambda s: s, s)


def step(state: SimState, cfg: SimConfig, worlds: bool = False) -> SimState:
    """Advance the simulation by one simdt. Pure; jit/scan/donate-friendly.

    ``worlds``: the state is a stacked [W, ...] pytree (``stack_worlds``)
    and every world advances one simdt — semantically ``jax.vmap(step)``
    and bit-identical to it (the W=1 parity test pins this against the
    unbatched step), with the gates hoisted out of the vmap (``_gate``).
    The two forms share this text, not their program.
    """
    _check_cfg(state, cfg)
    each, gate = _each(worlds), _gate(worlds)
    simdt = jnp.asarray(cfg.simdt, state.simt.dtype)
    simt = state.simt                             # scalar, or [W]

    # ---------- Atmosphere (traffic.py:389) ----------
    state = state.replace(ac=each(kinematics.update_atmosphere)(state.ac))

    # ---------- ADS-B broadcast model (traffic.py:392) ----------
    if cfg.noise.turb_active or cfg.noise.adsb_transnoise:
        rng, k_adsb, k_turb = each(
            lambda k: tuple(jax.random.split(k, 3)))(state.rng)
    else:
        # no noise consumer this step: skip the PRNG split entirely
        # (the key is never read below; the stream stays untouched so
        # toggling noise mid-run starts from the same key)
        rng = k_adsb = k_turb = state.rng
    state = state.replace(
        rng=rng,
        adsb=each(lambda a, ac, k, t: noise.adsb_update(
            a, ac, k, t, cfg.noise,
            smooth=cfg.smooth))(state.adsb, state.ac, k_adsb, simt))

    # ---------- FMS / autopilot (traffic.py:395), gated at fms_dt ----------
    fms_due = (state.fms_t0 + cfg.fms_dt < simt) | (simt < state.fms_t0) \
        | (simt < cfg.fms_dt)

    def run_fms(s):
        return autopilot.update_fms(s).replace(fms_t0=s.simt)

    state = gate(fms_due, run_fms, state)
    state = each(autopilot.update_continuous)(state)

    # ---------- ASAS CD&R (traffic.py:396), gated at dtasas ----------
    if cfg.asas.swasas:
        def run_asas(s):
            if cfg.cd_backend in ("tiled", "pallas", "sparse"):
                impl = asasmod.impl_for_backend(cfg.cd_backend)
                s2, _cd = asasmod.update_tiled(
                    s, cfg.asas, block=cfg.cd_block, impl=impl,
                    mesh=cfg.cd_mesh, mesh_axis=cfg.cd_mesh_axis,
                    shard_mode=cfg.cd_shard_mode,
                    halo_blocks=cfg.cd_halo_blocks,
                    tile_shape=cfg.cd_tile_shape or None,
                    tile_budgets=cfg.cd_tile_budgets)
            else:
                s2, _cd = asasmod.update(s, cfg.asas, smooth=cfg.smooth,
                                         rows=cfg.cd_rows)
            return s2.replace(
                asas_tnext=s.asas_tnext
                + jnp.asarray(cfg.asas.dtasas, s.asas_tnext.dtype))

        state = gate(simt >= state.asas_tnext, run_asas, state)

    def tail(state, k_turb):
        # ---------- Pilot arbitration (traffic.py:397) ----------
        if cfg.use_wind:
            windn, winde = windmod.getdata(state.wind, state.ac.lat,
                                           state.ac.lon, state.ac.alt)
        else:
            windn = winde = None
        state = pilot.ap_or_asas(state, windn, winde)

        # ---------- Performance model update (traffic.py:399-401) ----------
        new_perf, bank = perfmod.update(state.perf, state.ac.tas,
                                        state.ac.vs, state.ac.alt)
        state = state.replace(perf=new_perf, ac=state.ac.replace(bank=bank))

        # ---------- Envelope limits (traffic.py:404) ----------
        state = pilot.apply_limits(state, smooth=cfg.smooth)

        # ---------- Kinematics (traffic.py:406-409) ----------
        accel = perfmod.acceleration(state.perf.phase)
        ac = kinematics.update_airspeed(state.ac, state.pilot, accel, simdt,
                                        smooth=cfg.smooth)
        ac = kinematics.update_groundspeed(ac, windn, winde)
        ac = kinematics.update_position(ac, state.pilot, simdt)

        # ---------- Turbulence (traffic.py:416) ----------
        ac = noise.turbulence_woosh(ac, k_turb, simdt, cfg.noise,
                                    smooth=cfg.smooth)

        # Freeze padding slots: inactive rows keep their values
        # bit-exactly so garbage can never leak into streams/logs.
        live = ac.active
        frz = lambda new, old: jnp.where(live, new, old)
        ac = ac.replace(
            lat=frz(ac.lat, state.ac.lat), lon=frz(ac.lon, state.ac.lon),
            alt=frz(ac.alt, state.ac.alt), hdg=frz(ac.hdg, state.ac.hdg),
            trk=frz(ac.trk, state.ac.trk), tas=frz(ac.tas, state.ac.tas),
            gs=frz(ac.gs, state.ac.gs), vs=frz(ac.vs, state.ac.vs))

        # the clock counts its steps; the time is derived from the
        # count, rounded once (core/state.time_of_count)
        nstep = state.nstep + 1
        return state.replace(ac=ac, nstep=nstep, simt=time_of_count(
            nstep, cfg.simdt, state.simt.dtype))

    return each(tail)(state, k_turb)


class ChunkOut(NamedTuple):
    """The chunk scan's carry and its result.  A leaf its static flag
    leaves out is ``None`` (an empty pytree to ``scan`` and ``jit``), so
    each config key compiles one fixed carry; under ``worlds`` every
    present leaf has the leading [W] axis.  This is the one place that
    orders the optional packs."""
    state: SimState
    bad: object = None      # ``checked``: int32 first bad step, -1 clean
    stats: object = None    # ``cfg.scanstats``: obs/scanstats.ScanStats
    fp: object = None       # ``cfg.fingerprint``: FingerprintPack


def _scan_chunk(state: SimState, cfg: SimConfig, nsteps: int,
                checked: bool, worlds: bool = False) -> ChunkOut:
    """The ONE chunk scan every runner shares, for one world or a
    stacked [W, ...] state: ``nsteps`` of ``step``, with the integrity
    guard (``checked``: the index of the first step whose post-step
    state was not finite, per world, so a trip pins the (world, step)
    pair), the in-scan telemetry (``cfg.scanstats``) and the SDC
    fingerprint (``cfg.fingerprint``) folded through the carry.  All
    three are jit-static, so a leaf that is off is absent from the
    traced program: with every flag off this is the bare scan of
    ``step``.  Single source of truth, so the guard semantics measured
    by guard_overhead.py are exactly the ones the sim runs."""
    each = _each(worlds)
    stats0 = fp0 = bad0 = None
    if cfg.scanstats:
        from ..obs import scanstats as ssmod
        stats0 = each(lambda s: ssmod.init(s, cfg))(state)
    if cfg.fingerprint:
        from ..obs import fingerprint as fpmod
        fp0 = each(lambda s: fpmod.init(s, cfg))(state)
    if checked:
        bad0 = jnp.full(state.simt.shape, -1, jnp.int32)

    def body(c, i):
        s = step(c.state, cfg, worlds)
        bad, stats, fp = c.bad, c.stats, c.fp
        if checked:
            bad = jnp.where(bad >= 0, bad,
                            jnp.where(each(state_finite)(s), -1, i))
        if cfg.scanstats:
            stats = each(lambda st, sw: ssmod.fold(st, sw, cfg))(stats, s)
        if cfg.fingerprint:
            fp = each(lambda f, sw: fpmod.fold(f, sw, cfg))(fp, s)
        return ChunkOut(s, bad, stats, fp), None

    # the step index is scanned over only where the guard records it
    xs = jnp.arange(nsteps, dtype=jnp.int32) if checked else None
    out, _ = jax.lax.scan(body, ChunkOut(state, bad0, stats0, fp0), xs,
                          length=nsteps)
    return out


@partial(jax.jit, static_argnames=("cfg", "nsteps"), donate_argnums=0)
def run_steps(state: SimState, cfg: SimConfig, nsteps: int) -> SimState:
    """Advance nsteps with one compiled scan; state buffers are donated.

    This is the reference's lockstep ``STEP``/fast-forward chunk
    (simulation.py:216-223) as a single device program: host syncs once per
    chunk, matching SURVEY.md §2.10's "lax.scan over k steps inside one jit".
    """
    return _scan_chunk(state, cfg, nsteps, checked=False).state


#: Per-aircraft fields the in-scan integrity guard watches.  A non-finite
#: value anywhere in the pipeline reaches one of these within a step or
#: two (vs -> alt, trk/gsnorth/gseast -> lat/lon, thrust/drag -> tas), so
#: guarding the kinematic outputs bounds detection latency to ~one step
#: while keeping the check to a single fused reduce.
GUARD_FIELDS = ("lat", "lon", "alt", "tas", "gs", "vs")


def state_finite(state: SimState) -> jnp.ndarray:
    """Scalar bool: every guarded field is finite on the live rows.

    Padding rows are excluded: they hold whatever the freeze preserved
    and are masked everywhere downstream, so only live-row corruption
    counts as a trip.
    """
    ac = state.ac
    bad = jnp.zeros_like(ac.active)
    for f in GUARD_FIELDS:
        bad |= ~jnp.isfinite(getattr(ac, f))
    return ~jnp.any(bad & ac.active)


@partial(jax.jit, static_argnames=("cfg", "nsteps"), donate_argnums=0)
def run_steps_checked(state: SimState, cfg: SimConfig, nsteps: int):
    """``run_steps`` with the state-integrity guard folded into the scan
    carry: returns ``(state, bad_step)`` where ``bad_step`` is the index
    of the FIRST step (0-based within the chunk) whose post-step state
    had a non-finite guarded value on a live row, or -1 for a clean
    chunk.  The per-step cost is one fused isfinite all-reduce over the
    guarded [N] columns — measured < 2% of the full pipeline at N=100k
    (BENCH_GUARD.json) — and the step index gives the host the bisection
    for free: the fault is pinned to one simdt without re-running the
    chunk.
    """
    out = _scan_chunk(state, cfg, nsteps, checked=True)
    return out.state, out.bad


class EdgeTelemetry(NamedTuple):
    """Chunk-edge telemetry by field: everything the host's chunk-edge
    subsystems (guard response, metrics, trails, ACDATA stream) read
    from the device.  The one host-side view every consumer reads; the
    chunk program returns it as an ``EdgePack`` (one pack of four
    buffers) and ``unpack_telemetry`` gives the fields back as row
    views.

    Observability contract (docs/OBSERVABILITY.md): the flight
    recorder's chunk-sequence correlation tag is HOST-side state on
    ``simulation.pipeline.ChunkEdge``, stamped at dispatch — it must
    NOT become a field here.  Adding a device op for telemetry would
    break the recorder-off guarantee (zero added device ops,
    bit-identical stepped state, pinned by tests/test_obs.py).
    """
    simt: jnp.ndarray       # [s] sim time at the chunk edge
    nstep: jnp.ndarray      # int32 step count at the chunk edge
    bad: jnp.ndarray        # int32 first bad step in chunk, -1 = clean
    nconf_cur: jnp.ndarray  # scalar int32 directional conflict count
    nlos_cur: jnp.ndarray   # scalar int32 directional LoS count
    # Per-aircraft kinematic fields (metrics + ACDATA consumers)
    active: jnp.ndarray
    lat: jnp.ndarray
    lon: jnp.ndarray
    alt: jnp.ndarray
    hdg: jnp.ndarray
    trk: jnp.ndarray
    tas: jnp.ndarray
    gs: jnp.ndarray
    cas: jnp.ndarray
    vs: jnp.ndarray
    # ASAS display fields (ACDATA)
    inconf: jnp.ndarray
    tcpamax: jnp.ndarray
    asasn: jnp.ndarray
    asase: jnp.ndarray


class EdgePack(NamedTuple):
    """``EdgeTelemetry`` as the chunk program returns it: one pack of
    four buffers, the fields grouped by dtype and shape (``PACK_ROWS``
    has each buffer's rows), values copied and never converted.  A
    result buffer costs the runtime's call path some 60 us of dispatch
    whatever it holds, so the fields do not leave as nineteen.

    Two properties make the pipelined chunk loop possible:

    * The four are *fresh results* (a stack of the post-chunk columns),
      never aliases of the (donated) state buffers — so the host can
      dispatch the NEXT chunk (donating the state) and still read this
      edge's values while it runs.
    * The whole pack transfers as ONE ``jax.device_get`` of the four,
      replacing the dozens of per-field ``np.asarray`` pulls
      metrics/ScreenIO used to issue per chunk edge; ``ints`` and
      ``simt`` alone are the poll of a few bytes that carries the
      deferred guard word.
    """
    ints: jnp.ndarray       # int32 [4]
    simt: jnp.ndarray       # scalar of the state's float dtype
    cols: jnp.ndarray       # [12, N] of the state's float dtype
    masks: jnp.ndarray      # bool [2, N]


#: The row order of each stacked buffer of an ``EdgePack``: what
#: ``pack_telemetry`` stacks and ``unpack_telemetry`` indexes.
PACK_ROWS = EdgePack(
    ints=("nstep", "bad", "nconf_cur", "nlos_cur"),
    simt=None,
    cols=("lat", "lon", "alt", "hdg", "trk", "tas", "gs", "cas", "vs",
          "tcpamax", "asasn", "asase"),
    masks=("active", "inconf"))


def pack_telemetry(state: SimState, bad=None) -> EdgePack:
    """Build the edge pack from a post-chunk state (inside jit)."""
    ac, asas = state.ac, state.asas
    if bad is None:
        bad = jnp.full((), -1, jnp.int32)
    fields = EdgeTelemetry(
        simt=state.simt, nstep=state.nstep, bad=bad,
        nconf_cur=asas.nconf_cur, nlos_cur=asas.nlos_cur,
        active=ac.active, lat=ac.lat, lon=ac.lon, alt=ac.alt,
        hdg=ac.hdg, trk=ac.trk, tas=ac.tas, gs=ac.gs, cas=ac.cas,
        vs=ac.vs, inconf=asas.inconf, tcpamax=asas.tcpamax,
        asasn=asas.asasn, asase=asas.asase)

    def stacked(names, dtype):
        rows = [getattr(fields, n) for n in names]
        if any(r.dtype != dtype for r in rows):     # a stack would
            raise TypeError(                        # convert in silence
                f"edge pack: {names} are not all {dtype}")
        return jnp.stack(rows)

    return EdgePack(ints=stacked(PACK_ROWS.ints, jnp.int32),
                    simt=fields.simt,
                    cols=stacked(PACK_ROWS.cols, fields.simt.dtype),
                    masks=stacked(PACK_ROWS.masks, jnp.bool_))


def unpack_telemetry(pack: EdgePack) -> EdgeTelemetry:
    """The fields of a pack (device or host arrays; one world's, or a
    stacked one with its leading world axis) as an ``EdgeTelemetry``
    of row views: indexing only, no copy and no conversion."""
    fields = {n: pack.ints[..., i] for i, n in enumerate(PACK_ROWS.ints)}
    for buf in ("cols", "masks"):
        rows = getattr(pack, buf)
        fields.update((n, rows[..., i, :])
                      for i, n in enumerate(getattr(PACK_ROWS, buf)))
    return EdgeTelemetry(simt=pack.simt, **fields)


def _edge_scan(state: SimState, cfg: SimConfig, nsteps: int,
               checked: bool, worlds: bool = False):
    """``(state, telemetry, stats, fp)``: the stepped state, the
    telemetry pack, and the optional packs of ``ChunkOut`` (``None``
    where the flag is off), which join the telemetry as non-donated
    outputs and ride the same lazy chunk-edge pull.  Always four, for
    one world and for many."""
    out = _scan_chunk(state, cfg, nsteps, checked, worlds)
    bad = out.bad
    if bad is None:
        bad = jnp.full(out.state.simt.shape, -1, jnp.int32)
    telem = _each(worlds)(pack_telemetry)(out.state, bad)
    return out.state, telem, out.stats, out.fp


@partial(jax.jit, static_argnames=("cfg", "nsteps", "checked"),
         donate_argnums=0)
def run_steps_edge(state: SimState, cfg: SimConfig, nsteps: int,
                   checked: bool = False):
    """``run_steps`` (or the guarded scan, ``checked=True``) returning
    ``(state, EdgePack, stats, fp)`` (``_edge_scan``; the last two
    ``None`` unless their flag is on).  State buffers are donated like
    ``run_steps``; the telemetry pack is four fresh result buffers, so
    it survives the next chunk's donation — the enabling contract of
    the pipelined chunk loop (simulation/sim.py)."""
    return _edge_scan(state, cfg, nsteps, checked)


@partial(jax.jit, static_argnames=("cfg", "nsteps", "checked"))
def run_steps_edge_keep(state: SimState, cfg: SimConfig, nsteps: int,
                        checked: bool = False):
    """``run_steps_edge`` WITHOUT input donation: the caller keeps the
    pre-chunk state buffers valid.  The pipelined loop uses this for
    the chunk after a snapshot-ring capture edge, so the full pre-chunk
    pytree can be copied to the host *while the next chunk runs*
    instead of blocking the dispatch (the off-critical-path capture)."""
    return _edge_scan(state, cfg, nsteps, checked)


step_jit = jax.jit(step, static_argnames=("cfg", "worlds"))


# --------------------------------------------------------------- multi-world
# Batched multi-world stepping: the same scan with a leading WORLD axis
# on the whole SimState pytree, so ONE device program advances W
# independent scenarios per dispatch (docs/PERF_ANALYSIS.md
# §multi-world).  Per-world scalars (simt, rng, nconf/nlos, the guard
# word) ride the pytree and become [W]-vectors for free; per-world
# clocks may differ, so worlds at different sim times batch together.
# One compile per (nmax-bucket, chunk-length, cfg) key serves every
# fleet of compatible scenarios — the serving layer packs compatible
# BATCH pieces into exactly these batches (network/server.py).


def _check_worlds_cfg(cfg: SimConfig):
    """World batching composes with single-device configs only: the
    mesh decompositions put per-DEVICE structure on the aircraft axis
    (spatial stripes are a property of one world's sorted layout), so
    they compose with the world axis later, not now."""
    if cfg.cd_mesh is not None \
            or cfg.cd_shard_mode in ("spatial", "tiles"):
        raise ValueError(
            "world-batched stepping runs single-device per world: "
            "cd_mesh must be None and cd_shard_mode != "
            "'spatial'/'tiles' (pack refuses sharded pieces — see "
            "WORLDS docs)")


def stack_worlds(states) -> SimState:
    """Stack a list of same-shape SimStates into one [W, ...] pytree."""
    states = list(states)
    if not states:
        raise ValueError("stack_worlds: need at least one world")
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def world_slice(wtree, w: int):
    """World ``w``'s slice of any stacked pytree (state or telemetry)."""
    return jax.tree_util.tree_map(lambda x: x[w], wtree)


def unstack_worlds(wstate: SimState):
    """Split a stacked state back into per-world SimStates."""
    nw = int(wstate.simt.shape[0])
    return [world_slice(wstate, w) for w in range(nw)]


@partial(jax.jit, static_argnames=("cfg", "nsteps", "checked"),
         donate_argnums=0)
def run_steps_worlds_edge(state: SimState, cfg: SimConfig, nsteps: int,
                          checked: bool = False):
    """Multi-world ``run_steps_edge``: the same four outputs with a
    leading world axis on the state, every buffer of the telemetry pack
    (``bad`` is [W]: per world, the first bad step or -1) and every
    optional pack.  ``world_slice(telem, w)`` is a plain per-world
    ``EdgePack`` —
    the serving layer demuxes the pack back to the individual BATCH
    pieces with it.  W=1 is bit-identical to the unbatched path
    (tests/test_worlds.py pins this)."""
    _check_worlds_cfg(cfg)
    return _edge_scan(state, cfg, nsteps, checked, worlds=True)
