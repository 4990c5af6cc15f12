"""Shard the simulation over a device mesh.

The reference scales two ways (SURVEY.md §2.10): NumPy vectorization within a
process, and a process farm for *independent* scenarios.  Neither helps one
big traffic scene.  Here the aircraft axis itself is sharded over a
``jax.sharding.Mesh``:

* every per-aircraft array ``[N]`` is split along axis 0 ('ac'),
* the O(N^2) pair matrices ``[N, N]`` are split along rows — each device owns
  the conflict rows of its aircraft block and all-gathers the column side
  (position/velocity of all aircraft) over ICI, which is exactly the
  block-distributed CD with halo exchange called for in SURVEY.md §5.7,
* waypoint tables ``[N, W]`` split along rows; scalars/PRNG keys replicate.

We annotate shardings and let GSPMD insert the collectives (all-gather of the
broadcast operands of ``ops/cd.py``'s [N,1] x [1,N] math) rather than
hand-writing shard_map — the step stays one jitted program on any mesh size,
and the same code runs single-chip when the mesh has one device.

A second mesh axis ('ens') replicates whole scenarios for Monte-Carlo
ensembles (BASELINE config #4): see ``ensemble_step``.

Three decompositions for the sparse backend's shard_map kernels
(SimConfig.cd_shard_mode / the SHARD stack command):

* ``replicate`` — interleaved row blocks per device against the
  replicated O(N) column state (round 4; ~200x ceiling as D grows,
  docs/PERF_ANALYSIS.md §multi-chip);
* ``spatial`` — device-OWNED latitude stripes with conservative halo
  exchange (``prepare_spatial``): the spatial sort refresh re-buckets
  each aircraft into the caller shard of the device owning its sorted
  stripe slot, so per-interval scatter/trig/reachability/windows are
  O(N/D) device-local and only boundary slabs + per-block summaries
  ride ICI.  Bit-identical to the single-chip sparse schedule
  (tests/test_spatial.py) with zero O(N) column all-gathers on the
  compiled HLO (tests/test_hlo_collectives.py);
* ``tiles`` — 2-D lat x lon tiles on a ``('lat', 'lon')`` device mesh
  (``make_tile_mesh`` + ``prepare_tiles``): stripes cut only latitude,
  so on a global scene a D-device stripe still spans 360 degrees of
  longitude and its halo slab scales with the full stripe WIDTH; tiles
  cut both axes, halo wire scales with the tile PERIMETER (edge + 4
  corner slabs, multi-hop ppermute along both mesh axes), and the
  per-tile occupancy bound follows the 2-D population split.  Same
  refusal contract: the tile refresh validates corner-halo coverage
  per re-bucketing and REFUSES geometries it cannot cover.
"""
import threading
import time
from functools import partial

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.state import SimState
from ..core.step import SimConfig, step


def init_multihost(coordinator_address=None, num_processes=None,
                   process_id=None):
    """Join a multi-host mesh (the reference's MPI/NCCL scale-out role,
    SURVEY §5.8, as jax.distributed over DCN).

    Call ONCE per host process before any other JAX use; afterwards
    ``jax.devices()`` lists every chip in the job, so ``make_mesh()``
    and the sharded step below span hosts with no further changes —
    GSPMD routes intra-host collectives over ICI and cross-host ones
    over DCN.  On Cloud TPU pods the arguments default from the
    environment (``jax.distributed.initialize()`` with none needed).
    Single-host (and this repo's one-chip CI) skips this entirely.
    """
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def make_mesh(n_devices=None, devices=None):
    """1-D mesh over the aircraft axis (all JOB devices after
    ``init_multihost`` — i.e. every chip on every host)."""
    devices = devices if devices is not None else jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), ("ac",))


def make_tile_mesh(tiles, devices=None):
    """2-D ``('lat', 'lon')`` mesh for the tiles decomposition: device
    (r, c) owns tile ``t = r*C + c`` of the R x C lat x lon grid.  The
    flattened row-major device order matches the tile-major sorted
    layout of ``ops/cd_sched.tile_sort_dest``, so ``P(('lat', 'lon'))``
    on the aircraft axis IS the tile ownership map."""
    tR, tC = int(tiles[0]), int(tiles[1])
    if tR < 1 or tC < 1:
        raise ValueError(f"tile mesh shape must be positive, got "
                         f"{tR}x{tC}")
    devices = devices if devices is not None else jax.devices()
    if len(devices) < tR * tC:
        raise ValueError(f"tile mesh {tR}x{tC} needs {tR * tC} devices, "
                         f"have {len(devices)}")
    return Mesh(np.asarray(devices[:tR * tC]).reshape(tR, tC),
                ("lat", "lon"))


def _ac_axes(mesh: Mesh):
    """The mesh axis (or axis tuple) the aircraft dimension shards on:
    'ac' on the 1-D mesh, the flattened ('lat', 'lon') product on a
    tile mesh."""
    if "ac" in mesh.shape:
        return "ac"
    if "lat" in mesh.shape and "lon" in mesh.shape:
        return ("lat", "lon")
    raise ValueError(f"mesh has neither an 'ac' nor a ('lat', 'lon') "
                     f"axis set: {dict(mesh.shape)}")


def state_shardings(state: SimState, mesh: Mesh):
    """NamedSharding pytree for a SimState: rank>=1 arrays with a leading
    aircraft axis shard on 'ac' (or the flattened ('lat', 'lon') tile
    axes); scalars and the PRNG key replicate."""
    nmax = state.nmax
    ax = _ac_axes(mesh)

    def spec(leaf):
        if hasattr(leaf, "ndim") and leaf.ndim >= 1 and leaf.shape[0] == nmax:
            return NamedSharding(mesh, P(ax, *([None] * (leaf.ndim - 1))))
        return NamedSharding(mesh, P())

    return jax.tree.map(spec, state)


def shard_state(state: SimState, mesh: Mesh) -> SimState:
    """Place a host-built state onto the mesh with the canonical shardings."""
    return jax.tree.map(lambda x, s: jax.device_put(x, s), state,
                        state_shardings(state, mesh))


def spatial_state_shardings(state: SimState, mesh: Mesh):
    """Spatial-mode shardings: the canonical per-aircraft split plus
    the sorted-space partner table sharded over its (device-divisible)
    padded rows — it must never re-enter an interval replicated, or the
    shard_map boundary would reshard O(N*K) every interval."""
    sh = state_shardings(state, mesh)
    return sh.replace(asas=sh.asas.replace(
        partners_s=NamedSharding(mesh, P(_ac_axes(mesh), None))))


def prepare_spatial(state: SimState, mesh: Mesh, acfg, block: int = 256,
                    halo_blocks: int = 0, put: bool = True):
    """Enter the spatial domain-decomposition mode: size the
    sorted-space partner table to the device-divisible padded layout,
    run the spatial refresh (stripe sort + caller-slot re-bucketing +
    halo-coverage check), and place the state on the mesh with the
    canonical shardings (the re-bucketed caller axis IS the stripe
    ownership map: device d's shard holds the aircraft of its latitude
    stripes).

    Returns ``(state, newslot, info)`` — ``newslot`` the old->new
    caller slot map the host applies to its id/route bookkeeping
    (``Traffic.apply_slot_permutation``), ``info`` the refresh stats
    (occupancy, halo need, layout) for SHARD readback.

    Entering the mode RESETS engagement hysteresis (the partner table
    is rebuilt empty in the new layout): conservative — engaged pairs
    re-detect on the next CD interval.
    """
    import jax.numpy as jnp
    from ..core import asas as asasmod
    ndev = mesh.shape["ac"]
    n = state.nmax
    if n % ndev:
        raise ValueError(f"spatial mode: nmax={n} must divide into the "
                         f"{ndev}-device mesh")
    n_tot = asasmod.spatial_table_size(n, block, ndev)
    kk = state.asas.partners_s.shape[1]
    state = state.replace(asas=state.asas.replace(
        partners_s=jnp.full((n_tot, kk), -1, jnp.int32)))
    state, newslot, info = asasmod.refresh_spatial_shard(
        state, acfg, ndev, block=block, halo_blocks=halo_blocks)
    if put:
        # single-host placement; a multi-host job places the shards
        # itself (jax.make_array_from_callback over
        # spatial_state_shardings — see tests/multihost_worker.py)
        state = jax.tree.map(lambda x, s: jax.device_put(x, s), state,
                             spatial_state_shardings(state, mesh))
    return state, newslot, info


def prepare_tiles(state: SimState, mesh: Mesh, acfg, tiles=None,
                  block: int = 256, budgets=(), put: bool = True):
    """Enter the 2-D tiles decomposition: size the sorted-space partner
    table to the device-divisible padded layout of the R*C-device tile
    grid, run the tile refresh (tile-major sort + caller-slot
    re-bucketing + corner-halo coverage check, auto-pinning the
    per-offset halo slab budgets at 1.25x the measured need when
    ``budgets`` is empty), and place the state on the mesh.

    ``tiles`` defaults to the mesh's own ('lat', 'lon') shape.  Returns
    ``(state, newslot, info)`` like ``prepare_spatial``; pin
    ``info['budgets']`` into ``SimConfig.cd_tile_budgets`` (and
    ``info['tile_shape']`` into ``cd_tile_shape``) so the compiled
    interval and every later refresh validate the SAME static window.
    """
    import jax.numpy as jnp
    from ..core import asas as asasmod
    if tiles is None:
        try:
            tiles = (mesh.shape["lat"], mesh.shape["lon"])
        except KeyError:
            raise ValueError(
                "prepare_tiles needs a ('lat', 'lon') mesh (build it "
                "with make_tile_mesh) or an explicit tiles=(R, C)")
    tR, tC = int(tiles[0]), int(tiles[1])
    ndev = tR * tC
    n = state.nmax
    if n % ndev:
        raise ValueError(f"tiles mode: nmax={n} must divide into the "
                         f"{tR}x{tC}={ndev}-tile grid")
    n_tot = asasmod.spatial_table_size(n, block, ndev)
    kk = state.asas.partners_s.shape[1]
    state = state.replace(asas=state.asas.replace(
        partners_s=jnp.full((n_tot, kk), -1, jnp.int32)))
    state, newslot, info = asasmod.refresh_tile_shard(
        state, acfg, (tR, tC), block=block, budgets=tuple(budgets))
    if put:
        state = jax.tree.map(lambda x, s: jax.device_put(x, s), state,
                             spatial_state_shardings(state, mesh))
    return state, newslot, info


def unprepare_spatial(state: SimState):
    """Leave spatial/tiles mode: restore the default-size sorted tables
    (hysteresis resets, like entering — conservative either way).
    Caller slots keep their last bucketing (valid, just no longer
    maintained)."""
    import jax.numpy as jnp
    from ..core.state import SORT_PAD
    n = state.nmax
    kk = state.asas.partners_s.shape[1]
    return state.replace(asas=state.asas.replace(
        partners_s=jnp.full((n + SORT_PAD, kk), -1, jnp.int32),
        sort_perm=jnp.arange(n, dtype=jnp.int32)))


def sharded_step_fn(mesh: Mesh, cfg: SimConfig, nsteps: int = 1):
    """Compile the (scanned) step with explicit in/out shardings on mesh.

    The dense/tiled backends shard purely via GSPMD from the state
    shardings; the Pallas backends ('pallas', 'sparse') additionally
    need the mesh itself for their shard_map row split, so it is filled
    into the config here (see ``ops/cd_sched.detect_resolve_sched``).

    With ``cfg.scanstats`` the compiled program returns ``(state,
    ScanStats)`` instead of bare state: the in-scan accumulators ride
    the same scan carry (obs/scanstats.py) with their per-aircraft
    folds kept as [ndev] per-device partials — GSPMD keeps the
    row-split reductions shard-local, so the stats add ZERO in-scan
    collectives (tests/test_hlo_collectives.py pins ON vs OFF equal).
    With ``cfg.fingerprint`` the FingerprintPack joins after it.
    """
    if cfg.cd_backend in ("pallas", "sparse") and cfg.cd_mesh is None:
        if "ac" in mesh.shape:
            cfg = cfg._replace(cd_mesh=mesh, cd_mesh_axis="ac")
        elif "lat" in mesh.shape and "lon" in mesh.shape:
            # tile mesh: the shard_map body splits over both axes; the
            # 1-D mesh_axis name is unused on that path
            cfg = cfg._replace(cd_mesh=mesh)

    def run(state):
        from ..core.step import _scan_chunk
        out = _scan_chunk(state, cfg, nsteps, checked=False)
        ret = tuple(x for x in (out.state, out.stats, out.fp)
                    if x is not None)
        return ret[0] if len(ret) == 1 else ret

    return jax.jit(run, donate_argnums=0)


# --------------------------------------------------------------------------
# Monte-Carlo ensembles: vmap over a replica axis, sharded over devices.
# Replaces the reference's BATCH process farm (server.py:269-287) with a
# single SPMD program: each device owns whole replicas, no cross-device
# traffic at all (embarrassingly parallel, DCN-friendly across slices).
# --------------------------------------------------------------------------

def make_ensemble_mesh(n_devices=None, devices=None):
    devices = devices if devices is not None else jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), ("ens",))


def ensemble_step_fn(mesh: Mesh, cfg: SimConfig, nsteps: int = 1):
    """vmapped step over a leading replica axis, replicas sharded on 'ens'.

    Input: a SimState pytree whose every leaf has a leading replica axis
    (build with ``stack_replicas``).
    """
    def run_one(state):
        def body(s, _):
            return step(s, cfg), None
        out, _ = jax.lax.scan(body, state, None, length=nsteps)
        return out

    vrun = jax.vmap(run_one)

    def espec(leaf):
        return NamedSharding(mesh, P("ens", *([None] * (leaf.ndim - 1))))

    def run(states):
        states = jax.lax.with_sharding_constraint(
            states, jax.tree.map(espec, states))
        return vrun(states)

    return jax.jit(run, donate_argnums=0)


def stack_replicas(states):
    """Stack a list of equal-shape SimStates into one leading replica axis."""
    import jax.numpy as jnp
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *states)


# --------------------------------------------------------------------------
# Mesh-epoch recovery (ISSUE-10): losing a device group ends the EPOCH,
# not the run.  MeshGuard is the liveness sentinel a sharded sim consults
# at every chunk dispatch; on a trip the sim tears the epoch down,
# reloads the last checksummed snapshot onto the survivor mesh and steps
# on degraded (simulation/sim._handle_mesh_lost).
# --------------------------------------------------------------------------

class MeshLostError(RuntimeError):
    """A device group of the active mesh is dead or unreachable.

    Carries the lost group indices and the surviving device list so the
    recovery layer can re-form a smaller mesh without re-deriving the
    topology from a wedged runtime.
    """

    def __init__(self, msg, lost_groups=(), survivors=None):
        super().__init__(msg)
        self.lost_groups = tuple(lost_groups)
        self.survivors = list(survivors) if survivors is not None else []


class MeshGuard:
    """Liveness sentinel for one mesh epoch.

    Device groups model the unit of correlated failure: on a real
    multi-process mesh they are the per-process device partitions (a
    host dying takes its whole group); on a single-process (virtual)
    mesh the device list splits into two contiguous halves so chaos
    tests can kill "host 1" of the 8-device CPU mesh (``FAULT MESHKILL
    1`` -> devices 4-7 dead, survivors 0-3).

    Detection is two-pronged:

    * ``check()`` — cheap dispatch-time precheck: raises
      ``MeshLostError`` for any group marked dead (the ``FAULT
      MESHKILL`` injector, or a stale peer heartbeat observed earlier).
    * ``guarded_ready(x)`` — heartbeat-stamped collective timeout
      wrapper around a device sync: ``jax.block_until_ready`` runs in a
      side thread while this process keeps stamping its own heartbeat
      file; if the wait exceeds ``timeout`` (a collective blocked on a
      dead peer never returns) the peer stamps decide who died.
    """

    def __init__(self, mesh=None, heartbeat_dir=None, timeout=0.0,
                 hb_timeout=10.0):
        self.timeout = float(timeout)        # collective wait budget [s]
        self.hb_timeout = float(hb_timeout)  # peer stamp staleness [s]
        self.heartbeat_dir = heartbeat_dir
        self.epoch = 0
        self._killed = set()
        self.groups = []
        self.mesh = None
        self.set_mesh(mesh)

    # ------------------------------------------------------------ topology
    def set_mesh(self, mesh):
        """Bind a (new) mesh: recompute device groups, clear kill marks
        — a re-formed survivor mesh starts its epoch healthy."""
        self.mesh = mesh
        self._killed = set()
        devs = list(mesh.devices.flat) if mesh is not None else []
        self.groups = self._partition(devs)

    @staticmethod
    def _partition(devs):
        if not devs:
            return []
        try:
            nproc = jax.process_count()
        except RuntimeError:
            nproc = 1
        if nproc > 1:
            by_proc = {}
            for d in devs:
                by_proc.setdefault(getattr(d, "process_index", 0),
                                   []).append(d)
            return [by_proc[k] for k in sorted(by_proc)]
        if len(devs) < 2:
            return [devs]
        half = (len(devs) + 1) // 2
        return [devs[:half], devs[half:]]

    @property
    def survivors(self):
        """Devices of every still-live group, in mesh order."""
        return [d for k, g in enumerate(self.groups)
                if k not in self._killed for d in g]

    # ---------------------------------------------------------- injection
    def kill_group(self, k):
        """Mark device group ``k`` dead (the FAULT MESHKILL injector).
        The fault surfaces at the next ``check()``/``guarded_ready()``,
        i.e. the next chunk dispatch — like a real host loss, nothing
        happens until the fabric next touches the mesh."""
        k = int(k)
        if not 0 <= k < len(self.groups):
            raise ValueError(f"no device group {k} "
                             f"(mesh has {len(self.groups)})")
        if len(self.groups) - len(self._killed | {k}) < 1:
            raise ValueError("cannot kill the last live device group")
        self._killed.add(k)
        return self.groups[k]

    # ---------------------------------------------------------- detection
    def check(self):
        """Dispatch-time precheck: raise MeshLostError if any group of
        the bound mesh is marked dead."""
        if self.mesh is None or not self._killed:
            return
        lost = sorted(self._killed)
        raise MeshLostError(
            f"mesh epoch {self.epoch}: device group(s) "
            f"{','.join(map(str, lost))} dead "
            f"({len(self.survivors)} device(s) survive)",
            lost_groups=lost, survivors=self.survivors)

    # ------------------------------------------------- cross-process pulse
    def _hb_path(self, pid=None):
        import os
        if not self.heartbeat_dir:
            return None
        if pid is None:
            try:
                pid = jax.process_index()
            except RuntimeError:
                pid = 0
        return os.path.join(self.heartbeat_dir, f"meshhb-{pid}")

    def stamp(self):
        """Refresh this process's heartbeat file (mtime is the pulse)."""
        import os
        path = self._hb_path()
        if path is None:
            return
        os.makedirs(self.heartbeat_dir, exist_ok=True)
        with open(path, "w") as f:
            f.write(f"{time.time():.3f}\n")

    def stale_peers(self, hb_timeout=None):
        """Process indices whose heartbeat stamp is older than
        ``hb_timeout`` (missing stamps are NOT stale: a peer that never
        stamped may simply not have started)."""
        import os
        if not self.heartbeat_dir or not os.path.isdir(self.heartbeat_dir):
            return []
        budget = self.hb_timeout if hb_timeout is None else float(hb_timeout)
        try:
            me = jax.process_index()
        except RuntimeError:
            me = 0
        now = time.time()
        stale = []
        for name in sorted(os.listdir(self.heartbeat_dir)):
            if not name.startswith("meshhb-"):
                continue
            try:
                pid = int(name.split("-", 1)[1])
            except ValueError:
                continue
            if pid == me:
                continue
            try:
                age = now - os.path.getmtime(
                    os.path.join(self.heartbeat_dir, name))
            except OSError:
                continue
            if age > budget:
                stale.append(pid)
        return stale

    def guarded_ready(self, x):
        """``jax.block_until_ready(x)`` under the heartbeat-stamped
        collective timeout: the wait runs in a daemon thread while this
        process keeps stamping; past ``timeout`` seconds (0 = block
        forever) — or if the wait errors out with a peer already stale —
        the epoch is declared lost."""
        self.check()
        if self.timeout <= 0:
            self.stamp()
            return jax.block_until_ready(x)
        box = {}

        def _wait():
            try:
                box["out"] = jax.block_until_ready(x)
            except Exception as e:          # noqa: BLE001 — the backend
                box["err"] = e              # aborts in its own way
        t = threading.Thread(target=_wait, daemon=True)
        t.start()
        deadline = time.monotonic() + self.timeout
        beat = max(0.05, min(1.0, self.timeout / 4.0))
        while True:
            t.join(beat)
            self.stamp()
            if not t.is_alive():
                break
            stale = self.stale_peers()
            if stale or time.monotonic() > deadline:
                raise MeshLostError(
                    f"mesh epoch {self.epoch}: collective wait exceeded "
                    f"{self.timeout:.1f}s"
                    + (f", peer process(es) {stale} silent "
                       f"> {self.hb_timeout:.1f}s" if stale else ""),
                    lost_groups=stale, survivors=self.survivors)
        if "err" in box:
            stale = self.stale_peers()
            if stale:
                raise MeshLostError(
                    f"mesh epoch {self.epoch}: collective failed "
                    f"({box['err']}) with peer process(es) {stale} "
                    "silent", lost_groups=stale,
                    survivors=self.survivors) from box["err"]
            raise box["err"]
        return box["out"]
