"""Benchmark: the north-star configuration on one chip.

Default run (the driver's): N=100,000 aircraft, full CD&R pipeline
(FMS + state-based CD + MVP resolution @1 Hz + perf + kinematics,
simdt=0.05), Pallas blockwise backend with the exact spatial prefilter,
over a continental-scale airspace (35-60N, -10..30E — EU-sized; 100k
concurrent aircraft over a 230 nm circle would be ~25x the density of
the busiest real airspace).  Prints ONE JSON line
{"metric", "value", "unit", "vs_baseline"}.

Baseline: the reference runs 600-800 aircraft in real time on a desktop
CPU (BlueSky ICRAT-2016 paper §IX; BASELINE.md) at simdt=0.05 =>
~700 * 20 = 14,000 aircraft-steps/sec with the full pipeline.

``python bench.py N`` benches another size (backend picked by size);
``python bench.py --detail`` additionally sweeps backends/sizes and
writes the dense/tiled/pallas/sparse crossover table to
BENCH_DETAIL.json (a row that crashes is recorded with failed=True and
the sweep exits non-zero); ``python bench.py --sharded [N]`` runs the
mesh-sharded path on the devices JAX sees; ``python bench.py --grad [N]`` measures the
differentiable scan (forward+backward vs forward-only steps/s) into
BENCH_GRAD.json.  Every JSON-writing mode honours a shared ``--out
<file>`` flag, and sweep scripts reuse ``write_bench_json`` /
``platform_tag`` instead of duplicating the tagging boilerplate.

Every command-line mode prints a rate per chip, so every one of them
refuses to run without a chip (``require_chip``): a rate from XLA's CPU
backend or the Pallas interpreter is not a number anybody deploys.
"""
import json
import sys
import time

import numpy as np

import bluesky_tpu  # noqa: F401 — before jax: names the compile cache

BASELINE_AC_STEPS_PER_SEC = 700 * 20.0


def platform_tag():
    """The repo's bench row convention: ``backend:device_kind`` (so
    tpu:v5e history and cpu:cpu rows coexist in one file)."""
    import jax
    return (f"{jax.default_backend()}:"
            f"{jax.devices()[0].device_kind.lower()}")


def require_chip():
    """Exit non-zero off the chip, naming the device found; on it,
    return the device fields every metric line carries."""
    from bluesky_tpu.obs.devprof import device_info
    dev = device_info()
    if dev["platform"] == "cpu":
        raise SystemExit(
            f"bench.py measures a chip and JAX found none: platform "
            f"{dev['platform']!r}, device_kind {dev['device_kind']!r}, "
            f"{dev['count']} device(s)")
    return dev


def git_rev():
    """Short git revision of the repo this bench.py sits in (the
    BENCH_HISTORY provenance tag); 'unknown' outside a checkout."""
    import os
    import subprocess
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        rev = out.stdout.strip()
        return rev if out.returncode == 0 and rev else "unknown"
    except Exception:
        return "unknown"


def append_history(series, rows, path=None, rev=None, tag=None):
    """Append measured rows to the BENCH_HISTORY.jsonl series (the
    ISSUE-12 perf-regression sentinel's input): one JSON line per row —
    ``{"series", "ts", "git_rev", "platform", "row"}`` — so
    ``scripts/bench_history.py compare`` can diff the newest rows
    against the tracked baseline.  Projected and failed rows are not
    history (nothing was measured).  Returns the number appended."""
    if path is None:
        try:
            from bluesky_tpu import settings
            path = getattr(settings, "bench_history_path",
                           "BENCH_HISTORY.jsonl")
        except Exception:
            path = "BENCH_HISTORY.jsonl"
    if not path:
        return 0
    measured = [r for r in rows
                if isinstance(r, dict)
                and not r.get("projected") and not r.get("failed")]
    if not measured:
        return 0
    rev = rev or git_rev()
    tag = tag or platform_tag()
    ts = round(time.time(), 3)
    with open(path, "a") as f:
        for r in measured:
            f.write(json.dumps(
                {"series": series, "ts": ts, "git_rev": rev,
                 "platform": r.get("platform", tag), "row": r},
                sort_keys=True) + "\n")
    return len(measured)


def write_bench_json(path, rows, history=True, **extra):
    """Shared BENCH_*.json writer: platform-tag every measured row and
    write ``{"rows": rows, **extra}`` — the boilerplate every sweep
    script used to duplicate (scripts/world_sweep.py now calls this).
    Rows that already carry a tag (history, projections) keep it.

    Unless ``history=False`` (reprojection round-trips, merges of
    already-recorded rows), the measured rows are also appended to the
    BENCH_HISTORY.jsonl sentinel series named after the file."""
    import os
    tag = platform_tag()
    for r in rows:
        if isinstance(r, dict) and not r.get("projected"):
            r.setdefault("platform", tag)
    out = {"rows": rows}
    out.update({k: v for k, v in extra.items() if v is not None})
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")
    if history:
        series = os.path.splitext(os.path.basename(path))[0]
        append_history(series, rows, tag=tag)
    return out


def pop_out_flag(argv, default):
    """Consume ``--out <file>`` from argv (shared by every bench mode),
    returning the output path."""
    if "--out" in argv:
        i = argv.index("--out")
        if i + 1 >= len(argv):
            raise SystemExit("--out needs a file path")
        path = argv[i + 1]
        del argv[i:i + 2]
        return path
    return default


def _make_traffic(n_ac, geometry, pair_matrix, dtype, nmax=None):
    from bluesky_tpu.core.traffic import Traffic
    rng = np.random.default_rng(0)
    if geometry == "global":
        # 100k concurrent aircraft worldwide: ~5-10x today's global peak —
        # the realistic reading of the 100k north star
        lat = np.degrees(np.arcsin(rng.uniform(-0.94, 0.94, n_ac)))  # area-uniform, ~±70
        lon = rng.uniform(-180.0, 180.0, n_ac)
    elif geometry == "continental":
        lat = rng.uniform(35.0, 60.0, n_ac)
        lon = rng.uniform(-10.0, 30.0, n_ac)
    else:   # regional: the trafgen 230 nm spawn circle footprint
        ang = rng.uniform(0, 2 * np.pi, n_ac)
        r = 3.8 * np.sqrt(rng.random(n_ac))
        lat = 52.6 + r * np.cos(ang)
        lon = 5.4 + r * np.sin(ang) / 0.6
    traf = Traffic(nmax=nmax or n_ac, dtype=dtype,
                   pair_matrix=pair_matrix)
    traf.create(n_ac, "B744",
                rng.uniform(3000.0, 11000.0, n_ac),
                rng.uniform(130.0, 240.0, n_ac), None,
                lat, lon, rng.uniform(0.0, 360.0, n_ac))
    traf.flush()
    return traf


def _pick_backend(n_ac):
    # The sparse scheduler covers every large-N size: past ~360k
    # aircraft the rows are split into <=_MAX_ROWS-row kernel
    # invocations (cd_sched.py row split), which keeps the segment
    # schedule all the way to 1M+.
    return "dense" if n_ac <= 8192 else "sparse"


def run_one(n_ac, backend=None, geometry=None, nsteps=1000, reps=3):
    """Full-pipeline aircraft-steps/s for one configuration.

    nsteps=1000 (50 sim-seconds per chunk): fast-forward/BATCH runs use
    long scan chunks, which amortize the per-chunk sort refresh and
    dispatch the same way a production run does (what one dispatch
    costs on this machine is not measured).
    """
    import jax
    import jax.numpy as jnp
    from bluesky_tpu.core.asas import impl_for_backend, refresh_spatial_sort
    from bluesky_tpu.core.step import SimConfig, run_steps

    backend = backend or _pick_backend(n_ac)
    geometry = geometry or ("continental" if n_ac > 16384 else "regional")
    traf = _make_traffic(n_ac, geometry, backend == "dense", jnp.float32)
    cfg = SimConfig(cd_backend=backend)
    state = traf.state

    def resort(st):
        # Host-side chunk-edge sort refresh, as Simulation.update does
        # (the sort is deliberately not in the jitted step; its cost is
        # part of the measured wall time, amortized over the chunk).
        if backend in ("tiled", "pallas", "sparse"):
            return refresh_spatial_sort(st, cfg.asas, block=cfg.cd_block,
                                        impl=impl_for_backend(backend))
        return st

    state = run_steps(resort(state), cfg, nsteps)     # warmup/compile
    jax.block_until_ready(state)
    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        state = run_steps(resort(state), cfg, nsteps)
        jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        best = max(best, n_ac * nsteps / dt)
    # sim-seconds advanced per wall-second
    x_realtime = best * cfg.simdt / n_ac
    return dict(n=n_ac, backend=backend, geometry=geometry,
                ac_steps_per_s=round(best, 1),
                x_realtime=round(x_realtime, 1),
                # protocol fields (VERDICT r4 #6): throughput depends on
                # the scan-chunk length through per-chunk refresh +
                # dispatch amortization — see PERF_ANALYSIS §chunk-length
                nsteps_chunk=nsteps, reps=f"best-of-{reps}",
                resort="per-chunk")


def run_chunked(n_ac, backend=None, geometry=None, chunk=20,
                total_steps=1000, pipeline=True, reps=3, shard="off",
                shard_devices=0):
    """Multi-chunk protocol with per-chunk-edge host work — the
    production ``Simulation.step`` loop's cost model, measurable with
    the pipeline on or off.

    Each chunk edge does what the sim does: re-dispatch the spatial
    sort (tiled/pallas/sparse), dispatch the next chunk, and consume
    the edge telemetry pack.  ``pipeline=False`` blocks on the guard
    word + pulls the pack before dispatching the next chunk (the
    pre-pipeline loop); ``pipeline=True`` dispatches first and
    consumes the PREVIOUS chunk's pack while the new chunk runs
    (double-buffered dispatch + deferred readback).  The emitted row
    carries the host-edge overhead breakdown: ``dispatch_gap_s`` (host
    time spent enqueueing work per run) and ``telemetry_pull_s`` (host
    time blocked reading the guard word + pack).
    """
    import jax
    import jax.numpy as jnp
    from bluesky_tpu.core.asas import impl_for_backend, refresh_spatial_sort
    from bluesky_tpu.core.step import (SimConfig, run_steps_edge,
                                       unpack_telemetry)

    backend = backend or _pick_backend(n_ac)
    geometry = geometry or ("continental" if n_ac > 16384 else "regional")
    # mesh-aware chunk runner (ISSUE 5/19): the production cost model on
    # a device mesh — 'replicate' shards rows vs replicated columns,
    # 'spatial' runs the latitude-stripe decomposition, 'tiles' the 2-D
    # lat x lon tile decomposition with corner-halo exchange (sparse
    # backend; nmax gets 2x re-bucketing headroom)
    ndev = 0
    mesh = None
    tiles = None
    if shard and shard != "off":
        import jax as _jax
        from bluesky_tpu.parallel import sharding as shd
        ndev = shard_devices or len(_jax.devices())
        if shard == "tiles":
            # near-square R x C factorization with R >= C (8 -> 4x2)
            c = int(np.sqrt(ndev))
            while c > 1 and ndev % c:
                c -= 1
            tiles = (ndev // max(c, 1), max(c, 1))
            mesh = shd.make_tile_mesh(tiles)
        else:
            mesh = shd.make_mesh(ndev)
        if shard in ("spatial", "tiles") and backend != "sparse":
            backend = "sparse"
    nmax = 2 * n_ac if shard in ("spatial", "tiles") else n_ac
    if ndev:
        nmax = -(-nmax // ndev) * ndev
    traf = _make_traffic(n_ac, geometry, backend == "dense", jnp.float32,
                         nmax=nmax)
    cfg = SimConfig(cd_backend=backend)
    state = traf.state
    if mesh is not None:
        from bluesky_tpu.parallel import sharding as shd
        if shard == "tiles":
            state, _, tl_info = shd.prepare_tiles(state, mesh, cfg.asas,
                                                  tiles=tiles)
            cfg = cfg._replace(cd_shard_mode="tiles", cd_mesh=mesh,
                               cd_tile_shape=tl_info["tile_shape"],
                               cd_tile_budgets=tl_info["budgets"])
        elif shard == "spatial":
            state, _, sp_info = shd.prepare_spatial(state, mesh, cfg.asas)
            cfg = cfg._replace(cd_shard_mode="spatial", cd_mesh=mesh,
                               cd_mesh_axis="ac",
                               cd_halo_blocks=sp_info["halo_blocks"])
        else:
            if backend in ("pallas", "sparse"):
                cfg = cfg._replace(cd_mesh=mesh, cd_mesh_axis="ac")
            state = shd.shard_state(state, mesh)
    nchunks = max(1, total_steps // chunk)

    def resort(st):
        if shard == "tiles":
            from bluesky_tpu.core.asas import refresh_tile_shard
            return refresh_tile_shard(
                st, cfg.asas, tiles, block=min(cfg.cd_block, 256),
                budgets=cfg.cd_tile_budgets)[0]
        if shard == "spatial":
            from bluesky_tpu.core.asas import refresh_spatial_shard
            return refresh_spatial_shard(
                st, cfg.asas, ndev, block=min(cfg.cd_block, 256),
                halo_blocks=cfg.cd_halo_blocks)[0]
        if backend in ("tiled", "pallas", "sparse"):
            return refresh_spatial_sort(st, cfg.asas, block=cfg.cd_block,
                                        impl=impl_for_backend(backend))
        return st

    def consume(telem):
        # the sim's edge work: guard word poll (the pack's two small
        # buffers) + one bulk pack pull, unpacked on the host
        jax.device_get((telem.ints, telem.simt))
        int(unpack_telemetry(jax.device_get(telem)).bad)

    def dispatch(st):
        # one chunk edge: host refresh + dispatch
        st, telem, _, _ = run_steps_edge(resort(st), cfg, chunk,
                                         checked=True)
        return st, telem

    # warmup/compile
    state, telem = dispatch(state)
    jax.block_until_ready(state)
    consume(telem)

    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        dispatch_gap = 0.0
        telem_pull = 0.0
        prev = None
        for _k in range(nchunks):
            td = time.perf_counter()
            state, telem = dispatch(state)
            dispatch_gap += time.perf_counter() - td
            if not pipeline:
                tp = time.perf_counter()
                consume(telem)
                telem_pull += time.perf_counter() - tp
            else:
                if prev is not None:
                    tp = time.perf_counter()
                    consume(prev)
                    telem_pull += time.perf_counter() - tp
                prev = telem
        if prev is not None:
            tp = time.perf_counter()
            consume(prev)
            telem_pull += time.perf_counter() - tp
        jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        rate = n_ac * chunk * nchunks / dt
        row = dict(n=n_ac, backend=backend, geometry=geometry,
                   ac_steps_per_s=round(rate, 1),
                   x_realtime=round(rate * cfg.simdt / n_ac, 1),
                   nsteps_chunk=chunk, nchunks=nchunks,
                   shard=shard, shard_devices=ndev,
                   **(dict(tile_shape=f"{tiles[0]}x{tiles[1]}")
                      if tiles else {}),
                   pipeline=bool(pipeline),
                   dispatch_gap_s=round(dispatch_gap, 4),
                   telemetry_pull_s=round(telem_pull, 4),
                   dispatch_gap_ms_per_chunk=round(
                       1e3 * dispatch_gap / nchunks, 3),
                   telemetry_pull_ms_per_chunk=round(
                       1e3 * telem_pull / nchunks, 3),
                   wall_s=round(dt, 4))
        if best is None or row["ac_steps_per_s"] > best["ac_steps_per_s"]:
            best = row
    best["reps"] = f"best-of-{reps}"
    best["protocol"] = ("chunked, host re-sort per chunk"
                        + ", edge telemetry "
                        + ("deferred (pipelined)" if pipeline
                           else "blocking (sync)"))
    return best


def make_world_states(n_ac, worlds, dtype=None, geometry="regional",
                      pair_matrix=True, seed=0):
    """W per-world SimStates from one base fleet: headings rotated and
    PRNG keys re-seeded per world so the scenarios genuinely diverge
    (a Monte-Carlo sweep's shape) while sharing the nmax bucket."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    traf = _make_traffic(n_ac, geometry, pair_matrix, dtype)
    base = traf.state
    states = []
    for w in range(worlds):
        hdg = jnp.mod(base.ac.hdg + 360.0 * w / max(worlds, 1), 360.0)
        states.append(base.replace(
            # distinct buffers (donation rejects one buffer twice)
            ac=base.ac.replace(hdg=hdg, trk=jnp.copy(hdg)),
            rng=jax.random.PRNGKey(seed + w)))
    return states


def run_worlds(n_ac, worlds, nsteps=200, reps=2, backend="dense",
               baseline_reps=None):
    """Multi-world throughput: W scenarios of N aircraft advanced as
    ONE stacked scan (core/step.run_steps_worlds_edge) vs the one-piece-per-
    worker baseline — the same compiled single-world program dispatched
    serially, which is the chip-time a worker-process fleet sharing one
    device gets (docs/PERF_ANALYSIS.md §multi-world).

    Emits the batched row AND the baseline row; ``speedup`` is
    aggregate aircraft-steps/s batched over baseline.
    """
    import jax
    import jax.numpy as jnp
    from bluesky_tpu.core.step import (SimConfig, run_steps,
                                       run_steps_worlds_edge, stack_worlds)

    cfg = SimConfig(cd_backend=backend)
    states = make_world_states(n_ac, worlds,
                               pair_matrix=(backend == "dense"))

    # ---- baseline: serial single-world dispatches of the same program.
    # Workers time-sharing one chip cannot beat the serial per-dispatch
    # rate, so K dispatches bound a K-worker fleet's aggregate.
    k = baseline_reps if baseline_reps is not None else min(worlds, 8)
    solo = jax.tree_util.tree_map(jnp.copy, states[0])
    solo = run_steps(solo, cfg, nsteps)            # warmup/compile
    jax.block_until_ready(solo)
    t0 = time.perf_counter()
    for _ in range(k):
        solo = run_steps(solo, cfg, nsteps)
    jax.block_until_ready(solo)
    base_dt = time.perf_counter() - t0
    base_rate = k * n_ac * nsteps / base_dt
    baseline = dict(n=n_ac, worlds=1, protocol="one-piece-per-worker "
                    "(serial single-world dispatches, shared chip)",
                    backend=backend, nsteps_chunk=nsteps,
                    dispatches=k,
                    ac_steps_per_s=round(base_rate, 1),
                    x_realtime_per_world=round(
                        base_rate * cfg.simdt / n_ac, 2))

    # ---- batched: one stacked dispatch steps every world.
    wstate = run_steps_worlds_edge(stack_worlds(states), cfg, nsteps)[0]
    jax.block_until_ready(wstate)                  # warmup/compile
    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        wstate = run_steps_worlds_edge(wstate, cfg, nsteps)[0]
        jax.block_until_ready(wstate)
        dt = time.perf_counter() - t0
        best = max(best, worlds * n_ac * nsteps / dt)
    row = dict(n=n_ac, worlds=worlds, protocol="world-batched "
               "(one stacked vmapped scan per dispatch)",
               backend=backend, nsteps_chunk=nsteps,
               ac_steps_per_s=round(best, 1),
               x_realtime_per_world=round(
                   best * cfg.simdt / (worlds * n_ac), 2),
               speedup=round(best / base_rate, 2),
               reps=f"best-of-{reps}")
    return row, baseline


def run_grad(n_ac=200, tend=400.0, simdt=1.0, chunk=50, reps=2):
    """Differentiable-simulation bench (ISSUE 7): steps/s of the
    forward+backward smooth scan vs the forward-only smooth scan vs
    the hard serving scan, on the conflict demo scene.

    Three rows, same aircraft count and horizon:

    * ``forward_hard``     — run_steps with the exact step (the serving
                             scan; smooth=None baseline),
    * ``forward_smooth``   — the checkpointed objective rollout, value
                             only (what one optimizer line search pays),
    * ``forward_backward`` — jax.value_and_grad of the same rollout
                             (one full descent iteration's device work).

    ``bwd_over_fwd`` on the gradient row is the AD overhead factor the
    docs quote; BENCH_GRAD.json is written by the --grad CLI via the
    shared ``write_bench_json`` tagger.
    """
    import jax
    import jax.numpy as jnp
    from bluesky_tpu.core.step import SimConfig, run_steps
    from bluesky_tpu.diff import objectives
    from bluesky_tpu.diff import optimize as dopt
    from bluesky_tpu.diff.smooth import SmoothConfig

    traf, acfg = dopt.conflict_scene(n_ac, dtype=jnp.float32)
    state = traf.state
    nsteps = max(1, int(round(tend / simdt)))
    chunk = max(1, min(chunk, nsteps))
    nsteps = -(-nsteps // chunk) * chunk
    cfg_hard = SimConfig(simdt=simdt, asas=acfg._replace(swasas=False),
                         cd_backend="dense")
    cfg_sm = cfg_hard._replace(smooth=SmoothConfig())
    weights = objectives.ObjectiveWeights()
    nmax = state.ac.lat.shape[0]
    params = dopt.OffsetParams(jnp.zeros((nmax,), jnp.float32),
                               jnp.zeros((nmax,), jnp.float32))

    def cost(p, temp):
        s = dopt.apply_offsets(state, p, float(acfg.rpz))
        acc, _, _ = dopt._rollout(s, cfg_sm, nsteps, chunk, weights,
                                  temp, False)
        return acc

    fwd_hard = lambda: run_steps(jax.tree_util.tree_map(jnp.copy, state),
                                 cfg_hard, nsteps)
    fwd_smooth = jax.jit(cost)
    fwd_bwd = jax.jit(jax.value_and_grad(cost))
    temp = jnp.asarray(0.2, jnp.float32)

    def bench_one(fn, label):
        jax.block_until_ready(fn())          # warmup/compile
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            dt = time.perf_counter() - t0
            if best is None or dt < best:
                best = dt
        return dict(n=n_ac, mode=label, nsteps=nsteps,
                    nsteps_chunk=chunk, simdt=simdt,
                    ac_steps_per_s=round(n_ac * nsteps / best, 1),
                    wall_s=round(best, 4), reps=f"best-of-{reps}")

    rows = [bench_one(fwd_hard, "forward_hard"),
            bench_one(lambda: fwd_smooth(params, temp),
                      "forward_smooth"),
            bench_one(lambda: fwd_bwd(params, temp),
                      "forward_backward")]
    fwd = rows[1]["wall_s"]
    rows[2]["bwd_over_fwd"] = round(rows[2]["wall_s"] / fwd, 2) \
        if fwd else None
    rows[1]["smooth_over_hard"] = round(fwd / rows[0]["wall_s"], 2) \
        if rows[0]["wall_s"] else None
    for r in rows:
        print(json.dumps(r))
    return rows


def cd_pairs_per_s(n_ac, backend, geometry, reps=3):
    """CD&R kernel alone: effective pair rate."""
    import jax
    import jax.numpy as jnp
    from bluesky_tpu.ops import cd_pallas, cd_tiled, cr_mvp

    traf = _make_traffic(n_ac, geometry, False, jnp.float32)
    ac = traf.state.ac
    NM, FT = 1852.0, 0.3048
    cfg = cr_mvp.MVPConfig(rpz_m=5 * NM * 1.05, hpz_m=1000 * FT * 1.05,
                           tlookahead=300.0)
    if backend == "dense":
        from bluesky_tpu.ops import cd
        fn = jax.jit(lambda: cd.detect(
            ac.lat, ac.lon, ac.trk, ac.gs, ac.alt, ac.vs, ac.active,
            5 * NM, 1000 * FT, 300.0).swconfl)
    elif backend == "sparse":
        from bluesky_tpu.ops import cd_sched
        thresh = cd_sched.reach_threshold_m(ac.gs, ac.active, 300.0,
                                            5 * NM)
        dest = jax.block_until_ready(
            jax.jit(cd_sched.stripe_sort_dest, static_argnums=(5, 6))(
                ac.lat, ac.lon, ac.gs, ac.active, thresh, 256, 32,
                alt=ac.alt, vs=ac.vs))     # same sort as the sim path
        fn = jax.jit(lambda: cd_sched.detect_resolve_sched(
            ac.lat, ac.lon, ac.trk, ac.gs, ac.alt, ac.vs, ac.gseast,
            ac.gsnorth, ac.active, traf.state.asas.noreso,
            5 * NM, 1000 * FT, 300.0, cfg, perm=dest.astype(jnp.int32)))
    else:
        kern = cd_pallas.detect_resolve_pallas if backend == "pallas" \
            else cd_tiled.detect_resolve_tiled
        fn = jax.jit(lambda: kern(
            ac.lat, ac.lon, ac.trk, ac.gs, ac.alt, ac.vs, ac.gseast,
            ac.gsnorth, ac.active, traf.state.asas.noreso,
            5 * NM, 1000 * FT, 300.0, cfg))
    jax.block_until_ready(fn())
    t = 1e9
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        t = min(t, time.perf_counter() - t0)
    return n_ac * n_ac / t


def main(n_ac=100_000):
    dev = require_chip()
    # The standard 1000-step chunk is the protocol for the 100k
    # headline; the million-aircraft scale keeps the short chunks its
    # recorded rows were taken with (whether this machine needs one
    # device execution kept short is not measured).
    nsteps = 1000 if n_ac <= 200_000 else 40
    result_cfg = run_one(n_ac, nsteps=nsteps)
    gpairs = cd_pairs_per_s(n_ac, result_cfg["backend"],
                            result_cfg["geometry"]) / 1e9
    best = result_cfg["ac_steps_per_s"]
    result = {
        "metric": (f"aircraft-steps/sec/chip (N={n_ac}, CD+MVP @1Hz, "
                   f"simdt=0.05, {result_cfg['backend']}, "
                   f"{result_cfg['geometry']}, "
                   f"CD {gpairs:.1f} Gpairs/s, "
                   f"{result_cfg['x_realtime']:.0f}x realtime)"),
        "value": best,
        "unit": "aircraft-steps/s",
        "vs_baseline": round(best / BASELINE_AC_STEPS_PER_SEC, 2),
        "platform": dev["platform"],
        "device_kind": dev["device_kind"],
        "device_count": dev["count"],
    }
    print(json.dumps(result))
    return result


def _record_failure(rows, n, backend, geometry, e):
    """Record a failed sweep row (a crash) instead of silently dropping
    it; ``detail`` exits non-zero when the table holds one."""
    msg = f"{type(e).__name__}: {str(e)[:160]}"
    rows.append(dict(n=n, backend=backend, geometry=geometry,
                     failed=True, error=msg))
    print(f"# {backend} N={n} {geometry}: {msg}")


def detail():
    """Crossover table: backend x N x geometry -> BENCH_DETAIL.json.
    A row that raises is recorded as failed and the sweep goes on, then
    exits non-zero."""
    require_chip()
    rows = []
    for n in (1000, 4000, 8192, 16384, 50_000, 100_000):
        for backend in ("dense", "tiled", "pallas", "sparse"):
            if backend == "dense" and n > 16384:
                continue        # [N,N] f32 stops fitting comfortably
            if backend == "sparse" and n < 16384:
                continue        # scheduling overhead ~ the whole grid
            geoms = ("regional", "continental") if n < 50_000 \
                else ("regional", "continental", "global")
            for geometry in geoms:
                try:
                    # The slow lax 'tiled' backend gets short chunks at
                    # large N (regional 100k ran ~0.6M ac-steps/s); the
                    # fast kernels keep long chunks so per-chunk dispatch
                    # + host re-sort stay amortized like production
                    # fast-forward runs.
                    nsteps = 100 if (backend == "tiled"
                                     and n >= 50_000) else 400
                    r = run_one(n, backend, geometry, nsteps=nsteps,
                                reps=2)
                    rows.append(r)
                    print(json.dumps(r))
                except Exception as e:  # noqa: BLE001 (sweep keeps going)
                    _record_failure(rows, n, backend, geometry, e)
    # 10x the north star: one-million-aircraft scale demo, at the short
    # chunks its recorded rows were taken with (1000 steps at N=1M is a
    # device execution of several minutes).
    for backend in ("pallas", "sparse"):
        try:
            r = run_one(1_000_000, backend, "global",
                        nsteps=40 if backend == "pallas" else 20, reps=2)
            rows.append(r)
            print(json.dumps(r))
        except Exception as e:  # noqa: BLE001
            _record_failure(rows, 1_000_000, backend, "global", e)
    with open("BENCH_DETAIL.json", "w") as f:
        json.dump(rows, f, indent=1)
    nfailed = sum(1 for r in rows if r.get("failed"))
    if nfailed:
        raise SystemExit(f"bench --detail: {nfailed} of {len(rows)} "
                         "rows failed (see BENCH_DETAIL.json)")
    return rows


def sharded(n_ac=4096, n_devices=0, nsteps=100, backend="sparse"):
    """Multi-chip path: the scanned step with the CD backend sharded
    over an aircraft-axis mesh (parallel/sharding.py; 'sparse' runs the
    headline segment-scheduled kernel's shard_map row split, 'tiled'
    the GSPMD lax formulation) on the devices JAX sees
    (``n_devices`` = 0: all of them)."""
    dev = require_chip()
    import jax
    import jax.numpy as jnp
    from bluesky_tpu.core.asas import impl_for_backend, refresh_spatial_sort
    from bluesky_tpu.core.step import SimConfig
    from bluesky_tpu.parallel import sharding as shard

    ndev = n_devices or dev["count"]
    mesh = shard.make_mesh(ndev)
    traf = _make_traffic(n_ac, "continental", False, jnp.float32)
    cfg = SimConfig(cd_backend=backend, cd_block=256)
    # Sort once before sharding: on the identity layout every block's
    # bounding box spans the airspace and the reachability skip does
    # nothing, understating the blockwise rate.
    state = refresh_spatial_sort(traf.state, cfg.asas, block=cfg.cd_block,
                                 impl=impl_for_backend(backend))
    state = shard.shard_state(state, mesh)
    run = shard.sharded_step_fn(mesh, cfg, nsteps=nsteps)
    state = jax.block_until_ready(run(state))     # compile + warm
    t0 = time.perf_counter()
    state = jax.block_until_ready(run(state))
    dt = time.perf_counter() - t0
    rate = n_ac * nsteps / dt
    result = {
        "metric": (f"sharded aircraft-steps/s (N={n_ac}, {ndev}x "
                   f"{jax.devices()[0].platform} mesh, {backend} CD, "
                   f"blocks/device="
                   f"{-(-n_ac // cfg.cd_block) / ndev:.1f})"),
        "value": round(rate, 1),
        "unit": "aircraft-steps/s",
        "vs_baseline": round(rate / BASELINE_AC_STEPS_PER_SEC, 2),
        "platform": dev["platform"],
        "device_kind": dev["device_kind"],
        "device_count": dev["count"],
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    require_chip()
    if "--grad" in sys.argv:
        # differentiable-simulation rows: forward+backward vs
        # forward-only steps/s of the smooth scan (+ the hard serving
        # scan for reference) -> BENCH_GRAD.json (or --out <file>)
        out = pop_out_flag(sys.argv, "BENCH_GRAD.json")
        args = [a for a in sys.argv[1:] if not a.startswith("--")]
        n = int(args[0]) if args else 200
        rows = run_grad(n)
        gr = rows[2]
        write_bench_json(out, rows, headline={
            "n": n, "bwd_over_fwd": gr.get("bwd_over_fwd"),
            "fwd_bwd_ac_steps_per_s": gr["ac_steps_per_s"],
            "note": ("one optimizer iteration's device work vs one "
                     "forward rollout; checkpointed scan keeps "
                     "backward memory O(chunk)")})
    elif "--detail" in sys.argv:
        detail()
    elif "--sharded" in sys.argv:
        args = [a for a in sys.argv[1:] if not a.startswith("--")]
        sharded(n_ac=int(args[0]) if args else 4096,
                backend=args[1] if len(args) > 1 else "sparse")
    elif "--worlds" in sys.argv:
        # multi-world batched throughput vs the one-piece-per-worker
        # baseline: `bench.py --worlds W [N]` (scripts/world_sweep.py
        # runs the full W x N matrix into BENCH_WORLDS.json)
        i = sys.argv.index("--worlds")
        w = int(sys.argv[i + 1]) if len(sys.argv) > i + 1 else 256
        rest = sys.argv[1:i] + sys.argv[i + 2:]   # drop the W operand
        args = [a for a in rest if not a.startswith("--")]
        n = int(args[0]) if args else 500
        row, baseline = run_worlds(n, w)
        print(json.dumps(baseline))
        print(json.dumps(row))
    elif "--pipeline" in sys.argv:
        # chunked production-loop protocol with the async-pipeline edge
        # model on/off and the host-edge overhead breakdown
        # (dispatch_gap_s / telemetry_pull_s) in the emitted row
        mode = sys.argv[sys.argv.index("--pipeline") + 1].lower() \
            if len(sys.argv) > sys.argv.index("--pipeline") + 1 else "on"
        shard = sys.argv[sys.argv.index("--shard") + 1].lower() \
            if "--shard" in sys.argv else "off"
        args = [a for a in sys.argv[1:]
                if not a.startswith("--")
                and a not in ("on", "off", "replicate", "spatial",
                              "tiles")]
        n = int(args[0]) if args else 100_000
        chunk = int(args[1]) if len(args) > 1 else 20
        print(json.dumps(run_chunked(n, chunk=chunk,
                                     pipeline=(mode != "off"),
                                     shard=shard)))
    else:
        n = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
        main(n_ac=n)
