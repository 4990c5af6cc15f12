"""The simulation loop: fixed-dt stepping, fast-time control, benchmark.

Parity with the reference ``Simulation`` node (simulation/qtgl/simulation.py:
18-287): sim states INIT/HOLD/OP/END, wall-clock pacing with fast-forward and
DTMULT, scenario-command scheduling each step, BENCHMARK timing, and the
event surface (op/pause/reset/ff/...) the stack binds to.

TPU-first difference: the reference steps once per loop iteration (simdt,
then checks the stack).  Here the device advances in *chunks* of k steps with
one ``lax.scan`` program (core/step.run_steps) and the host syncs only at
chunk edges — stack commands, scenario triggers, loggers and plugin hooks all
run at chunk boundaries.  With the default chunk of 20 steps (1 s sim time)
command latency matches the reference's ASAS interval; BENCHMARK/FF runs use
big chunks for full throughput.

Chunk edges are *pipelined* by default (settings.chunk_pipeline /
CHUNKSTEPS PIPELINE): step() dispatches the next chunk before running the
previous chunk's edge subsystems, which consume the fused EdgeTelemetry
pack (core/step.run_steps_edge) instead of pulling fields off the live
state — host edge work overlaps in-flight device compute, the guard word
is polled one chunk deferred, and any edge that must mutate state falls
back to a synchronous chunk that is bit-identical to the unpipelined
loop.  docs/PERF_ANALYSIS.md §chunk-edge pipeline has the full contract.
"""
import collections
import contextlib
import os
import time
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..core.asas import AsasConfig
from ..core.noise import NoiseConfig
from ..core.route import RouteManager
from ..core.state import count_of_time, time_as_held
from ..core.step import SimConfig, cd_dense_rows
from ..core.traffic import Traffic
from ..obs import devprof as obs_devprof
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from .pipeline import ChunkEdge

# Sim states (reference bluesky/__init__.py:12)
INIT, HOLD, OP, END = range(4)


class _SyncReasonsView:
    """dict-like view over the ``sim_sync_reason_<r>`` registry
    counters — keeps the historical ``pipe_stats["sync_reasons"]``
    read/write surface while the data lives in the metrics registry."""
    _PREFIX = "sim_sync_reason_"

    def __init__(self, reg):
        self._reg = reg

    def __getitem__(self, k):
        m = self._reg.get(self._PREFIX + k)
        if m is None:
            raise KeyError(k)
        return int(m.value)

    def __setitem__(self, k, v):
        self._reg.counter(self._PREFIX + k)._set(v)

    def get(self, k, default=None):
        m = self._reg.get(self._PREFIX + k)
        return default if m is None else int(m.value)

    def __contains__(self, k):
        return self._reg.get(self._PREFIX + k) is not None

    def __iter__(self):
        for m in self._reg:
            if isinstance(m, obs_metrics.Counter) \
                    and m.name.startswith(self._PREFIX):
                yield m.name[len(self._PREFIX):]

    def keys(self):
        return list(self)

    def items(self):
        return [(k, self[k]) for k in self]

    def __len__(self):
        return sum(1 for _ in self)

    def __eq__(self, other):
        return dict(self.items()) == other

    def __repr__(self):
        return repr(dict(self.items()))


class _EdgeRetire:
    """What one edge retirement learns on its way (``_edge_span``)."""
    __slots__ = ("wait_s", "t_wait_end", "dropped")

    def __init__(self):
        self.wait_s = 0.0            # blocked on the chunk's outputs
        self.t_wait_end = 0.0        # perf_counter at the wait's end
        self.dropped = False         # a deferred trip voided the edge


class _PipeStatsView:
    """The historical ``sim.pipe_stats`` dict surface, backed by the
    sim's metrics registry (ISSUE-11 migration): reads/writes go to the
    ``sim_chunks_*`` counters, ``"sync_reasons"`` to the per-reason
    counter family, so HEALTH/CHUNKSTEPS readbacks, tests and the
    multi-world runner keep working unchanged."""
    _COUNTERS = {"pipelined_chunks": "sim_chunks_pipelined",
                 "sync_chunks": "sim_chunks_sync",
                 "deferred_trips": "sim_deferred_trips"}

    def __init__(self, reg):
        self._reg = reg
        self._reasons = _SyncReasonsView(reg)
        for name in self._COUNTERS.values():
            reg.counter(name)

    def __getitem__(self, k):
        if k == "sync_reasons":
            return self._reasons
        return int(self._reg.counter(self._COUNTERS[k]).value)

    def __setitem__(self, k, v):
        self._reg.counter(self._COUNTERS[k])._set(v)

    def get(self, k, default=None):
        try:
            return self[k]
        except KeyError:
            return default

    def keys(self):
        return list(self._COUNTERS) + ["sync_reasons"]

    def items(self):
        return [(k, self[k]) for k in self.keys()]

    def __contains__(self, k):
        return k in self._COUNTERS or k == "sync_reasons"

    def __repr__(self):
        return repr({k: (dict(v.items())
                         if k == "sync_reasons" else v)
                     for k, v in self.items()})


class DisplayState:
    """Display state shared by the headless Screen and the node-mode
    ScreenIO (screenio.py duck-types this surface): shape registry, pan
    centre, zoom, feature switches, altitude filter, symbol toggle,
    editline inserts, ND selection.  Every display command in the stack
    works against this mixin in both modes."""

    def _init_display(self):
        self.objdata = {}     # named display shapes (screenio objappend)
        self.ctrlat = 0.0
        self.ctrlon = 0.0
        self.scrzoom = 1.0
        self.user_view = False  # True once PAN/ZOOM issued (radar.py)
        self.features = {}
        self.altfilter = None       # (bottom, top) in meters or None
        self.swsymbol = True
        self.editline = ""
        self.nd_acid = None
        self.route_acid = ""        # ROUTEDATA selection (showroute)
        self.ssd_all = False        # SSD disc selection (reference
        self.ssd_conflicts = False  # guiclient.py:283-296 show_ssd)
        self.ssd_ownship = set()

    def showroute(self, acid=""):
        """Select the aircraft whose route streams in ROUTEDATA
        (reference scr.showroute, called from POS)."""
        self.route_acid = acid
        return True

    def reset(self):
        """Clear display state on sim RESET (reference ScreenIO.reset)."""
        self._init_display()

    def getviewbounds(self):
        """Lat/lon box currently in view (screenio pan/zoom state)."""
        half = 1.0 / max(self.scrzoom, 1e-9)
        return (self.ctrlat - half, self.ctrlat + half,
                self.ctrlon - half, self.ctrlon + half)

    def objappend(self, objtype, objname, data):
        """Mirror a named shape to the display (screenio.py objappend);
        empty objtype deletes."""
        if not objtype:
            self.objdata.pop(objname, None)
        else:
            self.objdata[objname] = (objtype, data)
        return True

    def addnavwpt(self, name, lat, lon):
        """Mirror a user-defined waypoint to the display (reference
        navdatabase.py:136 -> scr.addnavwpt; ScreenIO broadcasts it as
        the DEFWPT event the Qt client consumes, guiclient.py:232)."""
        self.custwpts = getattr(self, "custwpts", {})
        self.custwpts[name] = (float(lat), float(lon))
        return True

    def pan(self, lat, lon):
        self.ctrlat = float(lat)
        self.ctrlon = float(lon)
        self.user_view = True       # radar stops auto-fitting
        return True

    def zoom(self, factor, absolute=False):
        self.scrzoom = float(factor) if absolute \
            else self.scrzoom * float(factor)
        self.user_view = True
        return True

    def feature(self, sw, arg=None):
        """SWRAD switches (screenio.feature): toggle/record per name."""
        self.features[sw.upper()] = arg if arg is not None \
            else not self.features.get(sw.upper(), False)
        return True

    def filteralt(self, flag, bottom=None, top=None):
        self.altfilter = (bottom, top) if flag else None
        return True

    def symbol(self):
        self.swsymbol = not self.swsymbol
        return True

    def cmdline(self, text):
        """INSEDIT: text inserted on the console edit line."""
        self.editline = text
        return True

    def shownd(self, acid=None):
        self.nd_acid = acid
        return True

    def show_ssd(self, *args):
        """Select which aircraft draw their solution-space disc on the
        radar (reference guiclient.py:283-296: ALL / CONFLICTS / OFF or
        a toggled set of callsigns)."""
        arg = {str(a).upper() for a in args}
        if "ALL" in arg:
            self.ssd_all, self.ssd_conflicts = True, False
        elif "CONFLICTS" in arg:
            self.ssd_all, self.ssd_conflicts = False, True
        elif "OFF" in arg:
            self.ssd_all, self.ssd_conflicts = False, False
            self.ssd_ownship = set()
        else:
            remove = self.ssd_ownship.intersection(arg)
            self.ssd_ownship = self.ssd_ownship.union(arg) - remove
        return True


class Screen(DisplayState):
    """Echo/plot sink — headless stand-in for ScreenIO (screenio.py:11-263).

    Collects echo lines so stack command output is observable; the network
    node subclass streams instead.
    """

    def __init__(self):
        self.echobuf = []
        self._init_display()

    def echo(self, text="", flags=0):
        self.echobuf.append(text)
        return True


class Simulation:
    """Host simulation driver owning traffic, config and the step loop."""

    # Allowed device-chunk sizes, largest first (each size = one compiled
    # scan program per SimConfig).
    CHUNK_LADDER = (1000, 200, 20, 5, 1)

    def __init__(self, nmax: Optional[int] = None, wmax: int = 32,
                 dtype=None, openap_path: Optional[str] = None,
                 rng_seed: int = 0, chunk_steps: Optional[int] = None,
                 datalog_registry=None, world_tag: str = ""):
        dtype = dtype or jnp.float32
        from .. import settings as _pipe_settings
        if nmax is None:
            # capacity is a start-up setting like every other one: a
            # spawned worker gets it from the server's --config-file
            nmax = int(_pipe_settings.nmax)
        # Multi-world identity (simulation/worlds.py): a non-empty tag
        # marks this sim as one world of a packed BATCH piece — spliced
        # into preempt-checkpoint filenames and log output so W worlds
        # sharing a process never collide on disk.
        self.world_tag = str(world_tag)
        # per-process uniquifier for on-disk names when this sim has no
        # .node of its own (world sims of a packed piece): the runner
        # sets it to the owning worker's node id so two workers sharing
        # a snapshot dir never clobber each other's checkpoints
        self.host_tag = ""
        # the [N,N] pair matrix is allocated by the first flush under
        # the dense backend, not here (see the cfg setter)
        self.traf = Traffic(nmax=nmax, wmax=wmax, dtype=dtype,
                            openap_path=openap_path, rng_seed=rng_seed,
                            pair_matrix=False)
        self.routes = None           # ``_new_routes`` below, once the
        #                              registry is there to time it in
        self.scr = Screen()
        self.cfg = SimConfig()
        self.state_flag = INIT
        # Per-sim datalog registry (utils/datalog.LogRegistry): assigned
        # BEFORE metrics/guard construction — both define event loggers
        # into it.  Standalone sims share the process default registry;
        # multi-world sims get their own tagged one.
        from ..utils import datalog as _datalog
        self.datalog = datalog_registry if datalog_registry is not None \
            else _datalog.default_registry()
        # Interactive device-chunk length: settings knob + CHUNKSTEPS
        # stack command (ctor arg overrides for embedded use)
        self.chunk_steps = int(chunk_steps if chunk_steps is not None
                               else getattr(_pipe_settings,
                                            "chunk_steps", 20))
        # Async chunk pipeline (docs/PERF_ANALYSIS.md §chunk-edge
        # pipeline): when on, step() dispatches chunk k+1 before running
        # chunk k's edge subsystems off the fused telemetry pack, with a
        # synchronous fallback whenever edge work must mutate state.
        self.pipeline_enabled = bool(getattr(_pipe_settings,
                                             "chunk_pipeline", True))
        # ChunkEdges of the chunks in flight, oldest first.  A dt clamp
        # shortens chunks (a 0.1 s plugin interval: 2 steps, of which
        # nine in ten run no CD interval and take the device a
        # millisecond), and one chunk of lookahead then hides nothing of
        # the host's work; so the host may run ahead of the device by as
        # many steps as ONE unclamped interactive chunk has
        # (``chunk_steps``), however many chunks that is.  At the
        # default 20-step chunk that is one.
        self._inflight = collections.deque()
        self._n_next = 0             # planned count after those chunks
        self._n_plan = 0             # the count ``_plan_chunk`` planned from
        self._last_edge = None       # newest retired edge (ACDATA cache)
        self._retiring = False       # reentrancy guard for drains
        # In-scan telemetry (ISSUE-14, obs/scanstats.py): per-step
        # device-side stats folded through the chunk scan, drained at
        # each edge.  Settings knob at startup; the SCANSTATS stack
        # command toggles at runtime (the flag is jit-static, so each
        # value compiles its own chunk program).
        if bool(getattr(_pipe_settings, "scanstats", False)):
            self.cfg = self.cfg._replace(scanstats=True)
        self._scan_last = None       # newest drained chunk summary dict
        # SDC state fingerprint (ISSUE-17, obs/fingerprint.py): fold a
        # 32-bit witness of the stepped state through the chunk scan,
        # chained host-side per piece so completions/heartbeats ship one
        # comparable word.  Settings knob at startup; the FINGERPRINT
        # stack command toggles at runtime (jit-static flag, one chunk
        # program per value, same contract as scanstats).
        if bool(getattr(_pipe_settings, "fingerprint", False)):
            self.cfg = self.cfg._replace(fingerprint=True)
        self._fp_chain = 0           # running piece-chain fold (32-bit)
        self._fp_chunks = 0          # chunks folded into the chain
        self._fp_steps = 0           # steps folded into the chain
        self._fp_corrupt_mask = 0    # FAULT BITFLIP PAYLOAD: XORed into
        #                              the next shipped summary once
        # Observability (ISSUE-11, docs/OBSERVABILITY.md): a PER-SIM
        # metrics registry (two sims in one process — tests, W-world
        # packs — must not mix series) + the per-process flight
        # recorder.  pipe_stats is a compatibility view over the
        # registry counters.
        self.obs = obs_metrics.Registry()
        self.recorder = obs_trace.get_recorder()
        if bool(getattr(_pipe_settings, "trace_enabled", False)):
            self.recorder.enable()
        self.pipe_stats = _PipeStatsView(self.obs)
        self.obs.counter("sim_guard_trips",
                         help="integrity-guard trips (all policies)")
        self.obs.counter("sim_mesh_trips",
                         help="mesh-epoch events (mesh_lost+resharded)")
        self.obs.counter("sim_steps",
                         help="steps of the chunks retired")
        self.obs.counter("sim_clock_s",
                         help="simulated seconds those chunks took, by "
                              "the simt their edge packs carry")
        self.obs.gauge("sim_step_count",
                       help="the device's step count at the newest "
                            "retired edge (the simulation clock)")
        self.obs.gauge("sim_edge_pack_buffers",
                       help="device buffers in the edge pack a chunk "
                            "program returns beside the state (four; "
                            "each costs the dispatch its allocation)")
        self._pack_noted = False     # ... set at the first pack seen,
        #                              and again after a reset
        _h = self.obs.histogram
        _h("sim_chunk_latency_ms",
           help="chunk dispatch -> edge retirement wall ms")
        _h("sim_device_wait_ms",
           help="edge retirement: blocked on the chunk's outputs")
        _h("sim_edge_work_ms",
           help="edge retirement less the wait: the host's edge work")
        _h("sim_stack_ms",
           help="one pass of the stack that ran a command")
        _h("sim_piece_reset_ms",
           help="sim.reset() at the start of a BATCH piece")
        _h("sim_piece_turnaround_ms",
           help="worker: STATECHANGE out of OP sent -> next BATCH")
        _h("sim_piece_ms",
           help="worker: one BATCH piece (or pack), BATCH received -> "
                "STATECHANGE out of OP sent")
        _h("sim_piece_own_ms",
           help="that piece less every timed scope directly beneath it")
        _h("sim_pack_build_ms",
           help="worker: the world sims of a pack built and loaded")
        self.obs.counter("sim_piece_slow",
                         help="pieces over twice the running median of "
                              "sim_piece_ms")
        _h("sim_dispatch_ms",
           help="one chunk dispatch, its sort refresh and mesh check "
                "included")
        _h("sim_frame_ms",
           help="one ACDATA frame: build, pull and send")
        _h("sim_node_idle_ms",
           help="worker loop: one idle stretch while not OP (the end of "
                "a step to the next event, or to the next step once the "
                "wait for one has run its 20 ms out)")
        self.obs.counter("sim_node_idle_woken",
                         help="worker loop: idle waits that an event "
                              "ended")
        self.obs.counter("sim_node_idle_timed_out",
                         help="worker loop: idle waits that ran their "
                              "bound out with nothing arriving")
        self.obs.counter("sim_node_turns_nowait",
                         help="worker loop: turns that did not wait "
                              "for an event (the sim was stepping); "
                              "over sim_steps, one a chunk")
        _h("sim_node_poll_ms",
           help="worker loop: a turn's look at the event socket, its "
                "wait included, one a turn")
        _h("sim_pipeline_empty_ms",
           help="at a chunk dispatch: how long the host has known the "
                "device to hold no chunk (0 behind an unretired one)")
        _h("sim_edge_pull_ms",
           help="bulk edge-telemetry device->host pull wall ms")
        _h("sim_sort_refresh_ms",
           help="spatial-sort refresh wall ms (ROADMAP item 1)")
        _h("sim_snapshot_capture_ms",
           help="snapshot-ring capture wall ms")
        _h("sim_plugin_ms",
           help="one due plugin hook (preupdate, update or collect)")
        self.obs.gauge("sim_live_aircraft",
                       help="aircraft in the host's record after a "
                            "plugin hook ran")
        _c = obs_metrics.DEFAULT_COUNT_BUCKETS
        _h("sim_conf_pairs", buckets=_c,
           help="conflict pairs alive at a retired chunk edge")
        _h("sim_cd_dense_rows", buckets=_c,
           help="dense CD: leading slots the interval of a dispatched "
                "chunk runs on (nmax where the bound is not used)")
        _h("sim_cd_block_pairs", buckets=_c,
           help="sparse CD: block pairs the schedule a chunk starts "
                "with visits per interval")
        _h("sim_cd_overflow_rows", buckets=_c,
           help="sparse CD: block rows that schedule sends to the "
                "full-grid fallback")
        _h("sim_cd_block_pairs_aged", buckets=_c,
           help="sparse CD: block pairs the schedule of the layout a "
                "refresh replaces had come to, at the end of its life")
        _h("sim_cd_overflow_rows_aged", buckets=_c,
           help="sparse CD: rows that outgoing layout's schedule sent "
                "to the full-grid fallback, at the end of its life")
        self._edge_pull_sink = \
            self.obs.get("sim_edge_pull_ms").observe
        self._chunk_seq = 0          # host-side dispatch sequence tag
        #                              (correlation id; the edge pack
        #                              stays device-op-free by design)
        self._seq_dispatched = 0     # tag of the newest dispatch
        self._last_dispatch_end = None   # program-time stamp of the
        #                                  newest dispatch's return
        self._t_drained = None       # ... and of the end of the wait
        #                              that retired the newest dispatched
        #                              chunk; None while one is unretired
        self._refresh_ms = 0.0       # the last dispatch's sort refresh
        self._sched_counts = None    # that refresh's schedule counters
        #                              (device scalars) and its span,
        #                              until a ChunkEdge takes them
        # Device observability (ISSUE-12, obs/devprof.py): compile
        # telemetry + memory watermarks + PROFILE DEVICE trace windows.
        # Always present; every hook early-outs when its feature is off.
        self.devprof = obs_devprof.DevProf(self.obs, self.recorder,
                                           ladder=self.CHUNK_LADDER)
        # the timed scope of every instrumented site, on the program's
        # clock (obs/trace.py ``Timed``); core code is handed it
        self.timed = obs_trace.Timed(self.obs, self.devprof.program_time,
                                     self.recorder)
        self.traf.instrument(self.timed)
        # what a creation stamps takes the planned clock: a hook that
        # only queues writes runs while a chunk is in flight
        self.traf.simt_source = lambda: self.simt_planned
        self._new_routes(wmax)
        self.dtmult = 1.0
        self.ffmode = False
        self.ffstop: Optional[float] = None
        self.syst = -1.0          # wall-clock anchor
        self.bencht = 0.0
        self.benchdt = -1.0
        self._step_count = 0
        self._sort_simt = -1.0    # simt of last spatial-sort refresh
        self._sort_backend = None  # cd_backend the cached sort belongs to
        self._wall_t0 = time.perf_counter()
        import datetime
        self._utc0 = datetime.datetime.combine(datetime.date.today(),
                                               datetime.time())
        # Named areas + deferred conditional commands (chunk-edge subsystems)
        from ..utils.areafilter import AreaRegistry
        from ..core.conditional import ConditionList
        from ..utils.plotter import Plotter
        self.areas = AreaRegistry(self.scr)
        self.cond = ConditionList(self)
        self.plotter = Plotter(self)
        from ..core.metrics import Metrics
        self.metrics = Metrics(self)
        self.telnet = None            # StackTelnetServer when enabled
        # Fault tolerance: periodic in-memory snapshot ring + the
        # state-integrity guard responding to in-scan finite trips
        # (docs/FAULT_TOLERANCE.md; knobs in settings).
        from .. import settings as _fault_settings
        from .snapshot import SnapshotRing
        from ..fault.guard import IntegrityGuard
        self.snap_ring = SnapshotRing(
            depth=getattr(_fault_settings, "snap_ring_depth", 4),
            dt=getattr(_fault_settings, "snap_ring_dt", 30.0))
        self.guard = IntegrityGuard(self)
        # Durable runs (docs/FAULT_TOLERANCE.md): periodic on-disk
        # autosnapshot (off by default — one atomic write per interval)
        # and the preemption flag the SIGTERM handler / FAULT PREEMPT
        # injector raise; the owning node drains the chunk, checkpoints
        # and exits (simnode), an embedded run checkpoints and pauses.
        self.autosave_dt = float(getattr(
            _fault_settings, "snapshot_autosave_dt", 0.0))
        self._autosave_t = -float("inf")
        self.preempt_requested = False
        # FAULT STRAGGLE (fault/injectors.straggle): the merely-slow /
        # stuck-but-alive worker model.  Both survive RESET on purpose —
        # they model a property of the HOST (thermal throttling, a noisy
        # neighbor), not of the scenario, so a BATCH piece landing on a
        # straggling worker stays straggling.
        self.straggle_factor = 0.0    # extra wall-s owed per sim-s
        self.straggle_stall = False   # freeze progress, keep loop alive
        self._straggle_debt = 0.0     # owed throttle sleep, paid in
        #                               small slices so the node loop
        #                               keeps pumping heartbeats
        self.traf.delete_hooks.append(self.cond.delac)
        self.traf.permute_hooks.append(self.cond.permute)
        # Spatial mode: a freshly created aircraft has no sorted slot
        # (sentinel until the next stripe refresh would make it
        # INVISIBLE to CD), so any creation forces the refresh at the
        # very next dispatch — the flush and the refresh sit in the
        # same host edge, so no chunk ever steps a blind aircraft.
        self.traf.create_hooks.append(
            lambda slots: self._invalidate_sort()
            if self.shard_mode in ("spatial", "tiles") else None)
        self._shard_fallback = False
        # Mesh-epoch recovery (docs/FAULT_TOLERANCE.md, ISSUE-10): a
        # sharded run is a sequence of mesh EPOCHS — (device set, shard
        # layout, snapshot provenance).  The MeshGuard liveness sentinel
        # is consulted at every chunk dispatch; losing a device group
        # ends the epoch (structured mesh_lost trip, snapshot re-shard
        # onto the survivors in _handle_mesh_lost), not the run.
        from ..parallel.sharding import MeshGuard as _MeshGuard
        self.mesh_epoch = 0
        self.mesh_degraded = False
        self.mesh_events = []        # pending MESHLOST notices (simnode)
        self._mesh_refresh_ms = 0.0  # wall ms of the last shard refresh
        self.mesh_guard_enabled = bool(getattr(
            _fault_settings, "mesh_guard_enabled", True))
        self.mesh_guard = _MeshGuard(
            heartbeat_dir=str(getattr(_fault_settings,
                                      "mesh_heartbeat_dir", "") or "")
            or None,
            timeout=float(getattr(_fault_settings,
                                  "mesh_dispatch_timeout", 0.0)),
            hb_timeout=float(getattr(_fault_settings,
                                     "mesh_heartbeat_timeout", 10.0)))
        # Multi-chip decomposition (docs/PERF_ANALYSIS.md §multi-chip):
        # 'off' | 'replicate' (interleaved rows vs replicated columns) |
        # 'spatial' (device-owned latitude stripes + halo exchange) |
        # 'tiles' (2-D lat x lon tiles + corner-halo exchange).
        # SHARD stack command at runtime; settings.shard_mode at start.
        self.shard_mode = "off"
        self.shard_mesh = None
        self.shard_stats = {}
        from .. import settings as _shard_settings
        _sm = str(getattr(_shard_settings, "shard_mode", "off")).lower()
        if _sm in ("replicate", "spatial", "tiles"):
            # a shard_mode that was set and cannot be had (too few
            # devices, a bad tile shape) raises: the run would otherwise
            # carry on unsharded and say so only in an echo nobody reads
            if _sm in ("spatial", "tiles") \
                    and self.cfg.cd_backend != "sparse":
                # a settings-driven spatial/tiles deployment implies
                # the sparse backend (stripes/tiles are its schedule)
                self.cfg = self.cfg._replace(cd_backend="sparse",
                                             cd_block=256)
            _tiles = None
            if _sm == "tiles":
                _ts = str(getattr(_shard_settings,
                                  "shard_tile_shape", "") or "")
                if "x" in _ts.lower():
                    r, c = _ts.lower().split("x", 1)
                    _tiles = (int(r), int(c))
            self.set_shard(
                _sm, int(getattr(_shard_settings, "shard_devices", 0)),
                halo_blocks=int(getattr(_shard_settings,
                                        "shard_halo_blocks", 0)),
                tiles=_tiles)
        # Late import to avoid cycles; stack binds commands to this sim.
        from ..stack.stack import Stack
        self.stack = Stack(self)
        # Plugin system (discovery + hook scheduling at chunk edges);
        # enabled_plugins from settings are best-effort (plugin.py:103-105).
        from ..plugins import PluginManager
        from .. import settings as _settings
        self.plugins = PluginManager(self)
        for pname in getattr(_settings, "enabled_plugins", []):
            self.plugins.load(pname.upper())
        # Periodic loggers (reference traffic.py:86-89 defaults: SNAPLOG/
        # INSTLOG/SKYLOG) + their auto-registered stack commands, in
        # this sim's own registry.
        for name, dt in (("SNAPLOG", 30.0), ("INSTLOG", 30.0),
                         ("SKYLOG", 60.0)):
            if self.datalog.getlogger(name) is None:
                self.datalog.define_periodic(name, f"{name} logfile.", dt)
        self.datalog.register_stack_commands(self)

    def _new_routes(self, wmax: int):
        """A fresh RouteManager (start-up, RESET), timed in this sim's
        registry."""
        self.routes = RouteManager(self.traf, wmax)
        self.routes.instrument(self.timed)

    @property
    def cfg(self) -> SimConfig:
        return self._cfg

    @cfg.setter
    def cfg(self, cfg: SimConfig):
        """The [N,N] ``resopairs`` matrix is held only while the dense
        backend runs (10 GB at N=100k, and only ``ops/cd.py`` reads it):
        every config change says here whether the state needs it, and
        the next ``Traffic.flush`` — which precedes every dispatch —
        allocates or frees it."""
        self._cfg = cfg
        self.traf.pair_matrix = cfg.cd_backend == "dense"

    @property
    def navdb(self):
        """Lazy shared navigation database (loads on first named-position
        lookup; pickle-cached after the first process)."""
        from ..navdb import get_navdb
        return get_navdb()

    # ----------------------------------------------------------- time/state
    @property
    def nstep(self) -> int:
        """The steps the state has taken: the simulation clock (reads
        the device; waits for a chunk in flight)."""
        return int(self.traf.state.nstep)

    def clock(self, nstep: int) -> float:
        """The simulation time of a step count as the host keeps it:
        the count times ``simdt``, the exact product (float64).  The
        host's timers (scenario triggers, plugin and logger intervals,
        the FF stop, ring captures) compare to 1e-9 s and get a time
        that is good to that at any count; the device holds this
        rounded to its state's float type (``sent``)."""
        return int(nstep) * self.cfg.simdt

    def sent(self, simt: float) -> float:
        """A time of ``clock`` as it leaves the worker (a stream frame,
        a heartbeat, a reply): rounded to the state's float type once,
        which is the device's ``state.simt`` bit for bit
        (``core/state.time_of_count``); ``float32(n * 0.05)`` for the
        default state."""
        return time_as_held(simt, self.traf.dtype)

    @property
    def simt(self) -> float:
        return self.clock(self.nstep)

    @property
    def nstep_planned(self) -> int:
        """The step count WITHOUT forcing a device sync: while chunks
        are in flight (pipelined stepping) the count at the newest
        one's edge, which the host knows (it chose their lengths);
        with none in flight the device's."""
        if self._inflight:
            return self._n_next
        return self.nstep

    @property
    def simt_planned(self) -> float:
        """The host's clock at ``nstep_planned``: no device sync while
        a chunk is in flight, and exact, being a count's time."""
        return self.clock(self.nstep_planned)

    def steps_until(self, t: float, nstep: int) -> int:
        """The steps from count ``nstep`` to the first count whose time
        has reached ``t``, to the 1e-9 s the host's timers compare to
        (0 where it already has)."""
        t -= 1e-9
        m = max(nstep, int(np.ceil(t / self.cfg.simdt)))
        while m > nstep and self.clock(m - 1) >= t:
            m -= 1
        while self.clock(m) < t:
            m += 1
        return m - nstep

    @property
    def simdt(self) -> float:
        return self.cfg.simdt

    def setdt(self, dt: float):
        """A new step length.  The clock is a count of steps, so it
        restarts as the count of new steps nearest the time reached
        (the time moves by less than half a new step)."""
        simt = self.simt
        self.cfg = self.cfg._replace(simdt=float(dt))
        self.set_clock(count_of_time(simt, dt))
        return True

    def set_clock(self, nstep: int):
        """Write a step count, and the time derived from it, into the
        state (no chunk in flight)."""
        st = self.traf.state
        self.traf.state = st.replace(
            nstep=jnp.asarray(nstep, st.nstep.dtype),
            simt=jnp.asarray(self.sent(self.clock(nstep)), st.simt.dtype))

    @property
    def utc(self):
        """Simulated UTC clock = epoch + simt (simulation.py setutc)."""
        import datetime
        return self._utc0 + datetime.timedelta(seconds=self.simt)

    def setutc(self, *args):
        """TIME/DATE: RUN / REAL/UTC / HH:MM:SS.hh / day,month,year,time
        (reference simulation.py setutc)."""
        import datetime
        if not args or args[0] is None or str(args[0]).upper() == "RUN":
            self._utc0 = datetime.datetime.combine(
                datetime.date.today(), datetime.time()) \
                - datetime.timedelta(seconds=self.simt)
            return True
        a0 = str(args[0]).upper()
        if a0 in ("REAL", "UTC"):
            now = datetime.datetime.now(datetime.timezone.utc) \
                .replace(tzinfo=None) if a0 == "UTC" \
                else datetime.datetime.now()
            self._utc0 = now - datetime.timedelta(seconds=self.simt)
            return True
        try:
            if len(args) >= 4:   # DATE day, month, year, HH:MM:SS
                day, month, year = int(args[0]), int(args[1]), int(args[2])
                t = datetime.datetime.strptime(
                    str(args[3]).split(".")[0], "%H:%M:%S").time()
                base = datetime.datetime.combine(
                    datetime.date(year, month, day), t)
            else:                # TIME HH:MM:SS[.hh]
                t = datetime.datetime.strptime(
                    a0.split(".")[0], "%H:%M:%S").time()
                base = datetime.datetime.combine(self.utc.date(), t)
        except ValueError as e:
            return False, f"TIME/DATE: {e}"
        self._utc0 = base - datetime.timedelta(seconds=self.simt)
        return True

    def setFixdt(self, flag, tend=None):
        """FIXDT ON/OFF [tend]: fixed-dt stepping — equivalent to
        fast-forward pacing in this architecture (simulation.py
        setFixdt)."""
        if flag:
            self.fastforward(tend)
        else:
            self.ffmode = False
        return True

    def setdtmult(self, mult: float):
        self.dtmult = float(mult)
        return True

    def op(self):
        """Start/resume (reference simulation.py OP)."""
        self.state_flag = OP
        self.syst = -1.0
        self.ffmode = False
        return True

    def pause(self):
        self._retire_edge("pause")
        self.state_flag = HOLD
        return True

    def stop(self):
        self._retire_edge("stop")
        self.state_flag = END
        self.datalog.reset()
        return True

    def reset_traffic(self):
        """Traffic-scoped reset: clear aircraft + routes + deferred
        conditions, keep sim settings/stack/logs/plugins.

        Mirrors the reference's ``bs.traf.reset()`` (trafficarrays cascade:
        routes and conditional commands are traf children there), which is
        what the SYN generators call (reference synthetic.py:48,58,...) —
        unlike the full ``reset`` they must NOT wipe SimConfig (CDMETHOD,
        DT), datalog or plugin state."""
        self._retire_edge("reset")
        self._last_edge = None
        self.traf.reset()
        self.cond.reset()
        self._new_routes(self.routes.wmax)
        self._invalidate_sort()
        return True

    def reset(self):
        self._retire_edge("reset")
        self._last_edge = None
        self.state_flag = INIT
        self._invalidate_sort()
        self.traf.reset()
        self.areas.reset()
        self.cond.reset()
        self._new_routes(self.routes.wmax)
        # scanstats/fingerprint are runtime knobs, not scenario state
        # (like the TRACE recorder): the toggles survive RESET while
        # the rest of the config rebuilds to defaults
        self.cfg = SimConfig(scanstats=self.cfg.scanstats,
                             fingerprint=self.cfg.fingerprint)
        self._scan_last = None
        self._pack_noted = False
        # a new scenario starts a fresh fingerprint chain: the chain is
        # a witness of ONE piece's stepped states, comparable only
        # between executions of the same scenario content
        self._fp_chain = 0
        self._fp_chunks = 0
        self._fp_steps = 0
        self._fp_corrupt_mask = 0
        # traf.reset rebuilt default-shape tables on the default device
        self.shard_mode, self.shard_mesh = "off", None
        self.shard_stats = {}
        self._shard_fallback = False
        # a new scenario starts a fresh mesh-epoch history
        self.mesh_guard.set_mesh(None)
        self.mesh_guard.epoch = 0
        self.mesh_epoch = 0
        self.mesh_degraded = False
        self.mesh_events = []
        self._mesh_refresh_ms = 0.0
        self.dtmult = 1.0
        self.ffmode = False
        self.stack.reset()
        self.datalog.reset()
        self.scr.reset()
        self.metrics.reset()
        self.snap_ring.clear()
        self.guard.reset()
        self._autosave_t = -float("inf")
        # a stale preemption notice (FAULT PREEMPT timer armed before
        # the RESET) must not fire into the freshly-reset sim
        self.preempt_requested = False
        # After stack.reset: plugin reset hooks may stack commands (e.g.
        # TRAFGEN redraws its spawn circle) that must survive the reset.
        self.plugins.reset()
        self.plotter.reset()
        return True

    # -------------------------------------------------------------- sharding
    @staticmethod
    def _default_tile_shape(ndev: int):
        """Near-square R x C factorization of ``ndev`` with R >= C
        (more latitude bands than longitude buckets — traffic spreads
        wider in latitude on continental scenes): 8 -> 4x2, 4 -> 2x2,
        6 -> 3x2; a prime falls back to ndev x 1 (degenerate stripes)."""
        ndev = int(ndev)
        c = int(np.sqrt(ndev))
        while c > 1 and ndev % c:
            c -= 1
        return (ndev // max(c, 1), max(c, 1))

    def _shard_ndev(self, default=0):
        """Device count of the bound shard mesh (works for both the
        1-D 'ac' mesh and the 2-D ('lat', 'lon') tile mesh)."""
        return int(self.shard_mesh.devices.size) if self.shard_mesh \
            else int(default)

    def set_shard(self, mode: str, ndev: int = 0, halo_blocks: int = 0,
                  devices=None, tiles=None):
        """Select the multi-chip mode: ``off`` | ``replicate`` |
        ``spatial`` | ``tiles`` over the first ``ndev`` devices
        (0 = all).  ``devices`` overrides the device list — the
        mesh-epoch recovery path passes the SURVIVORS of a lost group
        so the re-formed mesh excludes the dead devices.

        ``replicate``: the round-4 scheme — state sharded on the
        aircraft axis, sparse/pallas kernels row-split with replicated
        O(N) columns.  ``spatial``: device-owned latitude stripes with
        halo exchange (sparse backend only) — aircraft are re-bucketed
        into the owning device's caller shard at every sort refresh,
        O(N/D) schedule/sort per device, O(halo) wire per interval.
        ``tiles``: 2-D lat x lon tiles on a ('lat', 'lon') mesh
        (``tiles=(R, C)``, default a near-square factorization of
        ndev): halo wire scales with the tile PERIMETER (edge + corner
        slabs) instead of the stripe width.  Switching modes resets
        engagement hysteresis (conservative: pairs re-detect next
        interval).
        """
        import jax as _jax
        from ..parallel import sharding as shd
        mode = str(mode).lower()
        if mode not in ("off", "replicate", "spatial", "tiles"):
            raise ValueError(f"SHARD {mode}: off/replicate/spatial/tiles")
        self.drain_pipeline()
        self.traf.flush()
        if mode in ("spatial", "tiles") and self.cfg.cd_backend != "sparse":
            raise ValueError(
                f"SHARD {mode.upper()} needs the sparse backend "
                "(stripes/tiles are a property of the sorted schedule) "
                "— CDMETHOD SPARSE first")
        # leave the previous mode's table layout
        if self.shard_mode in ("spatial", "tiles") \
                and mode not in ("spatial", "tiles"):
            self.traf.state = shd.unprepare_spatial(self.traf.state)
        if mode == "off":
            self.shard_mode, self.shard_mesh = "off", None
            self.mesh_guard.set_mesh(None)
            self.cfg = self.cfg._replace(cd_mesh=None,
                                         cd_shard_mode="replicate",
                                         cd_tile_shape=(),
                                         cd_tile_budgets=())
            self._invalidate_sort()
            return True
        devs = list(devices) if devices is not None else _jax.devices()
        ndev = ndev or len(devs)
        if ndev > len(devs):
            raise ValueError(f"SHARD: {ndev} devices requested, "
                             f"{len(devs)} available")
        if mode == "tiles":
            if tiles is None:
                cur = tuple(self.cfg.cd_tile_shape)
                tiles = cur if len(cur) == 2 \
                    and cur[0] * cur[1] == ndev \
                    else self._default_tile_shape(ndev)
            tiles = (int(tiles[0]), int(tiles[1]))
            if tiles[0] * tiles[1] != ndev:
                raise ValueError(
                    f"SHARD TILE {tiles[0]}x{tiles[1]} needs "
                    f"{tiles[0] * tiles[1]} devices, asked for {ndev}")
            mesh = shd.make_tile_mesh(tiles, devices=devs)
        else:
            mesh = shd.make_mesh(ndev, devices=devs)
        tile_budgets = ()
        if mode == "tiles":
            state, newslot, info = shd.prepare_tiles(
                self.traf.state, mesh, self.cfg.asas, tiles=tiles,
                block=min(self.cfg.cd_block, 256))
            tile_budgets = tuple(info["budgets"])
            self.traf.state = state
            self.traf.apply_slot_permutation(newslot)
            self.shard_stats = info
            self._sort_simt = self.simt
            self._sort_backend = "sparse"
            self._last_edge = None      # slots moved: ACDATA cache stale
        elif mode == "spatial":
            state, newslot, info = shd.prepare_spatial(
                self.traf.state, mesh, self.cfg.asas,
                block=min(self.cfg.cd_block, 256),
                halo_blocks=halo_blocks)
            self.traf.state = state
            self.traf.apply_slot_permutation(newslot)
            self.shard_stats = info
            self._sort_simt = self.simt
            self._sort_backend = "sparse"
            self._last_edge = None      # slots moved: ACDATA cache stale
        else:
            self.traf.state = shd.shard_state(self.traf.state, mesh)
            self._invalidate_sort()
        self.shard_mode, self.shard_mesh = mode, mesh
        # bind the liveness sentinel to the new mesh (clears any kill
        # marks: a freshly formed mesh starts its epoch healthy)
        self.mesh_guard.set_mesh(mesh)
        if mode == "spatial":
            # pin the (auto-sized) halo so every interval compiles
            # against the exact window the refresh validated
            halo_blocks = self.shard_stats["halo_blocks"]
        self.cfg = self.cfg._replace(
            cd_mesh=mesh, cd_mesh_axis="ac",
            cd_shard_mode=mode if mode in ("spatial", "tiles")
            else "replicate",
            cd_halo_blocks=halo_blocks,
            # pin the (auto-sized) tile budgets the same way
            cd_tile_shape=tiles if mode == "tiles" else (),
            cd_tile_budgets=tile_budgets)
        return True

    def _spatial_refresh(self, state):
        """Spatial/tiles-mode chunk-edge sort refresh: stripe (or 2-D
        tile) re-sort + caller-slot re-bucketing + halo check (one
        jitted program), the host id/route remap, and stat capture for
        SHARD readback.  Unlike the plain refresh this must sync the
        device (the occupancy/halo guards read scalars) — paid once per
        ``sort_every`` intervals."""
        from ..core.asas import (ShardContractError, refresh_spatial_shard,
                                 refresh_tile_shard)
        _t0 = time.perf_counter()
        try:
            if self.shard_mode == "tiles":
                state, newslot, info = refresh_tile_shard(
                    state, self.cfg.asas, self.cfg.cd_tile_shape,
                    block=min(self.cfg.cd_block, 256),
                    budgets=self.cfg.cd_tile_budgets)
            else:
                state, newslot, info = refresh_spatial_shard(
                    state, self.cfg.asas, self.shard_mesh.shape["ac"],
                    block=min(self.cfg.cd_block, 256),
                    halo_blocks=self.cfg.cd_halo_blocks)
            self._mesh_refresh_ms = (time.perf_counter() - _t0) * 1e3
        except ShardContractError as e:
            # The geometry broke the decomposition contract (stripe/tile
            # occupancy past a shard's capacity, or reach past the
            # halo window / pinned slab budgets).  Running on with a
            # stale bucketing loses the drift-margin guarantee, so
            # schedule a fallback at the next step() boundary (a safe
            # sync point: tiles -> spatial -> replicate) and step this
            # one chunk on the still-margin-covered old sort.  Nothing
            # wider is caught: a compiler or device-memory error from
            # the refresh program is not a property of the geometry and
            # ends the run with its own message.
            self.scr.echo(f"SHARD {self.shard_mode.upper()} contract "
                          f"violated: {e}")
            self._shard_fallback = True
            return state
        self.traf.apply_slot_permutation(newslot)
        self.shard_stats = info
        self._last_edge = None          # slots moved: ACDATA cache stale
        return state

    # ------------------------------------------------- mesh-epoch recovery
    def _handle_mesh_lost(self, err):
        """End the current mesh epoch after a device-group loss and form
        the next one (docs/FAULT_TOLERANCE.md §mesh epochs).

        Sequence: record a structured ``mesh_lost`` trip through the
        integrity-guard trip log; void the in-flight edge (it rode the
        dead mesh); pick the restore point — newest snapshot-ring entry,
        else the on-disk autosave (checksum-verified, shard header
        checked before unpickling); tear the mesh down; restore; re-form
        a smaller mesh from the survivors, degrading
        tiles -> spatial -> replicate -> single-chip until one layout
        holds; then
        record the ``resharded`` trip, bump the epoch and queue a
        MESHLOST notice for the owning node.  Restoring onto a different
        D forces the full re-sort/re-bucket + conservative halo
        re-validation (snapshot.restore_blob cross-mesh detection).
        """
        from . import snapshot as snap
        old_epoch = self.mesh_epoch
        old_mode = self.shard_mode
        old_nd = self._shard_ndev()
        lost = list(getattr(err, "lost_groups", ()))
        survivors = list(getattr(err, "survivors", ()) or [])
        # the in-flight chunks rode the dead mesh: their edges are void
        for edge in self._inflight:
            self.recorder.instant(
                "chunk_voided", seq=edge.seq, chunk=edge.chunk,
                epoch=old_epoch, world=self.world_tag)
        self._inflight.clear()
        self._last_edge = None
        self.scr.echo(f"MESH LOST (epoch {old_epoch}): {err}")
        self.guard.mesh_trip("mesh_lost", epoch=old_epoch,
                             lost_groups=lost, ndev=old_nd,
                             mode=old_mode, error=str(err))
        # restore point: newest ring entry first (in-memory, most
        # recent), else the on-disk autosave — surfaced shard header
        # first so a corrupt/mismatched file is diagnosed pre-unpickle
        blob = self.snap_ring.newest()
        src = "ring"
        if blob is None:
            path = self._autosave_path()
            if os.path.isfile(path):
                hdr, herr = snap.peek_shard(path)
                if herr:
                    self.scr.echo(f"mesh recovery: autosave header "
                                  f"unusable ({herr})")
                else:
                    if hdr is not None and hdr.get("ndev", 0) != old_nd:
                        self.scr.echo(
                            "mesh recovery: autosave captured on a "
                            f"{hdr.get('ndev')}-device "
                            f"{hdr.get('mode')} mesh — re-shard will "
                            "re-sort/re-bucket")
                    blob, rerr = snap.read_blob(path)
                    src = path
                    if blob is None:
                        self.scr.echo(f"mesh recovery: autosave "
                                      f"unusable ({rerr})")
        # epoch teardown: leave the dead mesh entirely (state back on
        # the default device, spatial tables unsized)
        try:
            self.set_shard("off")
        except (ValueError, RuntimeError) as e:  # pragma: no cover
            self.scr.echo(f"mesh teardown failed: {e}")
        restored = False
        if blob is not None:
            ok, msg = snap.restore_blob(self, blob, full_reset=False)
            restored = bool(ok)
            self.scr.echo(f"mesh recovery: {msg}" if ok else
                          f"mesh recovery restore FAILED: {msg}")
        else:
            self.scr.echo("mesh recovery: no checksummed snapshot — "
                          "re-sharding the live state")
        # epoch re-formation: survivors form a smaller mesh; a mode
        # whose contract the survivors cannot satisfy (spatial stripes
        # need nmax % D == 0 and halo-valid occupancy) degrades
        nd = len(survivors)
        new_mode = "off"
        if nd >= 1:
            if old_mode == "tiles":
                chain = ["tiles", "spatial", "replicate"]
            elif old_mode == "replicate":
                chain = ["replicate"]
            else:
                chain = [old_mode, "replicate"]
            for m in chain:
                try:
                    self.set_shard(m, nd, devices=survivors)
                    new_mode = m
                    break
                except (ValueError, RuntimeError) as e:
                    self.scr.echo(f"mesh recovery: SHARD "
                                  f"{m.upper()} {nd} failed ({e})")
        nd_now = self._shard_ndev(default=1)
        self.mesh_epoch = old_epoch + 1
        self.mesh_guard.epoch = self.mesh_epoch
        self.mesh_degraded = (new_mode != old_mode) or (nd_now < old_nd)
        self.guard.mesh_trip("resharded", epoch=self.mesh_epoch,
                             mode=new_mode, ndev=int(nd_now),
                             restored=restored,
                             restore_src=(src if blob is not None
                                          else None))
        self.scr.echo(
            f"MESH EPOCH {self.mesh_epoch}: "
            f"{new_mode.upper() if new_mode != 'off' else 'SINGLE-CHIP'}"
            f" on {nd_now} device(s)"
            + (" [degraded]" if self.mesh_degraded else "")
            + (f", restored from {src}" if restored else
               ", continuing on live state"))
        # notice for the owning node -> server (MESHLOST event):
        # recovered epochs keep their piece in flight (audit records
        # only); an unrecovered one requeues it PREEMPTED-style
        self.mesh_events.append(dict(
            recovered=True, epoch=self.mesh_epoch,
            prev_epoch=old_epoch, lost_groups=lost,
            mode=new_mode, ndev=int(nd_now),
            prev_mode=old_mode, prev_ndev=int(old_nd),
            degraded=bool(self.mesh_degraded), restored=restored,
            simt=float(self.simt_planned)))

    def mesh_health(self):
        """The HEALTH ``mesh`` section: epoch, device count, shard
        mode, last shard-refresh wall ms, degradation state."""
        d = dict(epoch=int(self.mesh_epoch),
                 devices=self._shard_ndev(),
                 mode=str(self.shard_mode),
                 last_refresh_ms=round(float(self._mesh_refresh_ms),
                                       3),
                 degraded=bool(self.mesh_degraded))
        if self.shard_mode == "tiles":
            ts = tuple(self.cfg.cd_tile_shape)
            d["tiles"] = f"{ts[0]}x{ts[1]}" if len(ts) == 2 else ""
            d["tile_budgets"] = list(self.cfg.cd_tile_budgets)
        return d

    def scan_health(self):
        """The HEALTH ``sim`` section: in-scan telemetry enablement plus
        the newest drained chunk's summary (obs/scanstats.summarize) —
        chunk-peak conflicts, min closest approach, clamp-saturation
        ratio — plus the sort-refresh readback (last-refresh time).
        Pure host state: no device reads."""
        d = dict(scanstats=bool(self.cfg.scanstats),
                 fingerprint=bool(self.cfg.fingerprint),
                 sort_refresh=self.refresh_health())
        if self._scan_last is not None:
            d.update(self._scan_last)
        return d

    def set_scanstats(self, on: bool) -> bool:
        """Toggle in-scan telemetry.  Drains the pipeline first (the
        in-flight chunk was compiled with the OLD flag and its edge
        must retire under it); the next dispatch compiles the new chunk
        program.  Returns True if the flag changed."""
        on = bool(on)
        if on == bool(self.cfg.scanstats):
            return False
        self.drain_pipeline()
        self.cfg = self.cfg._replace(scanstats=on)
        if not on:
            self._scan_last = None
        return True

    # ------------------------------------------------- SDC fingerprint
    def set_fingerprint(self, on: bool) -> bool:
        """Toggle the SDC state-fingerprint fold (``set_scanstats``
        contract: drain the pipeline, then swap the jit-static flag).
        Turning it ON mid-piece starts the chain at the current state —
        comparable only to executions toggled at the same step, so the
        serving layer flips it via scenario content (FINGERPRINT ON as
        the first stacked command), never mid-flight."""
        on = bool(on)
        if on == bool(self.cfg.fingerprint):
            return False
        self.drain_pipeline()
        self.cfg = self.cfg._replace(fingerprint=on)
        self._fp_chain = 0
        self._fp_chunks = 0
        self._fp_steps = 0
        return True

    def fp_summary(self):
        """The shipped fingerprint summary (heartbeats + the SDCFP
        completion event), or None before any chunk folded.  A FAULT
        BITFLIP PAYLOAD mask corrupts every shipped word until the next
        RESET — the wire-corruption injection point: the stepped state
        (and the device fold) stay untouched, only the reported witness
        lies."""
        if not self.cfg.fingerprint or self._fp_chunks == 0:
            return None
        from ..obs import fingerprint as fpmod
        word = (self._fp_chain ^ self._fp_corrupt_mask) & 0xFFFFFFFF
        return fpmod.summarize(word, self._fp_chunks, self._fp_steps)

    def _drain_fingerprint(self, edge) -> None:
        """Retire one edge's FingerprintPack into the running piece
        chain (host-side rotate-XOR)."""
        if edge.fingerprint is None:
            return
        import jax as _jax
        from ..obs import fingerprint as fpmod
        pack = _jax.device_get(edge.fingerprint)
        edge.fingerprint = None
        self._fp_chain = fpmod.chain(self._fp_chain,
                                     fpmod.combine(pack))
        self._fp_chunks += 1
        self._fp_steps += int(np.asarray(pack.steps))

    # --------------------------------------------------------- sort refresh
    def _invalidate_sort(self):
        """THE spatial-sort invalidation point (ISSUE-15): every event
        that voids the cached stripe sort — creation flush, RESET,
        snapshot restore, backend switch, shard-mode change — routes
        through here, so the refresh due-gate has a single source of
        truth (-1 = refresh ahead of the next dispatch)."""
        self._sort_simt = -1.0
        self._sort_backend = None

    def refresh_health(self):
        """The HEALTH ``sim`` sort-refresh readback: the due gate's
        state.  Pure host state: no device reads."""
        return dict(last_refresh_simt=float(self._sort_simt))

    # ----------------------------------------------------- preempt/autosave
    def request_preempt(self):
        """Raise the preemption flag (SIGTERM handler, FAULT PREEMPT):
        handled at the next chunk edge so the in-flight device chunk
        drains instead of being torn mid-scan."""
        self.preempt_requested = True
        return True

    def handle_preempt(self):
        """Drain-side response to a preemption notice: write a final
        atomic checksummed checkpoint and pause.  Returns
        ``(path_or_None, err_or_None)``.  Node wrappers call this at
        the chunk edge, then notify the server and exit cleanly; an
        embedded sim just pauses with the checkpoint on disk."""
        from .. import settings as _settings
        from . import snapshot as snap
        self.preempt_requested = False
        d = getattr(_settings, "preempt_snapshot_dir", "") \
            or _settings.log_path
        tag = getattr(getattr(self, "node", None), "node_id",
                      b"").hex()[:8] or self.host_tag or "sim"
        if self.world_tag:
            # one checkpoint file per world of a packed piece — W
            # worlds sharing a process must not clobber one path
            tag = f"{tag}-{self.world_tag}"
        path = os.path.join(d, f"preempt-{tag}.snap")
        self.pause()
        try:
            os.makedirs(d, exist_ok=True)
            snap.save(self, path)
        except OSError as e:
            self.scr.echo(f"preempt checkpoint FAILED: {e}")
            return None, str(e)
        self.scr.echo(f"preempted at simt={self.simt:.2f}: "
                      f"checkpoint written to {path}")
        return path, None

    def _autosave_path(self):
        from .. import settings as _settings
        return getattr(_settings, "snapshot_autosave_path", "") \
            or os.path.join(_settings.log_path, "autosave.snap")

    def _autosave(self):
        """Persist the newest SnapshotRing entry (or a fresh capture
        when the ring is empty/stale) to disk atomically — the
        periodic on-disk checkpoint a preempted/killed process resumes
        from.  A failed write degrades to an echo, never an exception
        out of the step loop."""
        from . import snapshot as snap
        blob = self.snap_ring.newest()
        if blob is None \
                or float(np.asarray(blob["state"].simt)) <= self._autosave_t:
            blob = snap.state_blob(self)
        path = self._autosave_path()
        try:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            snap.write_blob(blob, path)
        except OSError as e:
            self.scr.echo(f"autosnapshot failed: {e}")
        self._autosave_t = self.simt

    def fastforward(self, nsec: Optional[float] = None):
        """FF [sec]: run at full speed [for nsec] (simulation.py:180-185)."""
        self.ffmode = True
        self.ffstop = self.simt + nsec if nsec else None
        return True

    def benchmark(self, fname: str = "IC", tend: float = 60.0):
        """BENCHMARK [scen, t]: load scenario, FF a span, report wall time
        (simulation.py:187-190, completion report :72-77)."""
        ok, msg = self.stack.ic(fname)
        if not ok:
            return False, msg
        self.bencht = 0.0
        self.benchdt = float(tend)
        self.fastforward(float(tend))
        self.op()
        return True

    # -------------------------------------------- differentiable workloads
    def optimize_trajectories(self, tend=None, iters=None, lr=None,
                              restarts=None, **kw):
        """Gradient-based trajectory optimization of the CURRENT fleet
        (the OPT stack command; bluesky_tpu/diff/optimize.py).

        Drains the pipeline + flushes pending creations so the
        optimizer sees the true state, descends on per-aircraft lateral
        waypoint / departure-time offsets against the soft-LoS + fuel
        objective, verifies against the hard metric, and routes any
        guard trip (non-finite forward step, objective or gradient —
        the run_steps_checked word extended over the backward pass)
        through the integrity guard's trip log.  Returns the
        diff.optimize.OptResult.
        """
        from .. import settings as _s
        from ..diff import optimize as diffopt
        self.drain_pipeline()
        self.traf.flush()
        result = diffopt.optimize(
            self.traf.state, self.cfg.asas,
            tend=float(tend if tend is not None
                       else getattr(_s, "opt_tend", 600.0)),
            simdt=float(kw.pop("simdt", getattr(_s, "opt_simdt", 1.0))),
            chunk=int(kw.pop("chunk", getattr(_s, "opt_chunk", 50))),
            iters=int(iters if iters is not None
                      else getattr(_s, "opt_iters", 40)),
            lr=float(lr if lr is not None
                     else getattr(_s, "opt_lr", 0.15)),
            temp0=float(kw.pop("temp0", getattr(_s, "opt_temp0", 0.3))),
            temp1=float(kw.pop("temp1", getattr(_s, "opt_temp1", 0.05))),
            restarts=int(restarts if restarts is not None
                         else getattr(_s, "opt_restarts", 1)),
            los_margin=float(kw.pop("los_margin",
                                    getattr(_s, "opt_los_margin", 1.2))),
            verify_simdt=float(kw.pop("verify_simdt",
                                      getattr(_s, "opt_verify_dt",
                                              0.05))),
            **kw)
        if result.bad != -1:
            # backward-pass guard trip: record through the SAME
            # machinery forward trips use (fault/guard.py), so FAULTLOG
            # consumers and tests see one trip stream
            self.guard.trips.append({
                "simt": self.simt, "bad_step": int(result.bad),
                "ids": [], "action": "opt_halt",
                "source": "diff.optimize backward guard"})
            self.scr.echo(
                f"OPT: integrity-guard trip (word {result.bad}: "
                f"{'non-finite gradients' if result.bad == -3 else 'non-finite objective' if result.bad == -2 else 'forward step'})"
                " — descent halted at the last finite iterate")
        return result

    # ----------------------------------------------------------------- step
    def step(self, max_chunk: Optional[int] = None):
        """One host iteration: scenario triggers + stack + a device chunk.

        Mirrors the per-step order of simulation.py:62-128 at chunk
        granularity.  Returns False once END is reached.

        Pipelined stepping (default, ``settings.chunk_pipeline``): the
        next chunk is dispatched BEFORE the previous chunk's edge
        subsystems run, so host edge work (guard word, metrics, trails,
        stream telemetry, snapshot capture) overlaps in-flight device
        compute.  Any edge that must read-modify the state — pending
        stack commands (incl. every scenario-trigger boundary), queued
        aircraft creations, armed conditionals, runway approach, due
        logger/plot hooks and plugin hooks that read the state on the
        host (``plugins/__init__.py``), FF stop, preemption, guard policy
        ``halt``, autosave — retires the deferred edge first and steps
        synchronously, bit-identically to the unpipelined loop.
        """
        if self.state_flag == END:
            return False
        plan = self._plan_chunk(max_chunk)
        if plan is None:
            return True
        chunk, simt = plan

        from ..parallel.sharding import MeshLostError
        try:
            reasons = self._sync_reasons(simt, chunk)
            if reasons:
                self._retire_edge(reasons[0])
                # every co-occurring cause counts (a chunk held back by
                # cond AND datalog is one sync chunk but two reasons) —
                # recording only reasons[0] silently under-reported the
                # later list entries
                sync_hist = self.pipe_stats["sync_reasons"]
                for r in reasons:
                    sync_hist[r] = sync_hist.get(r, 0) + 1
                self._step_sync(chunk, self.simt)
            else:
                self._step_pipelined(chunk, simt)
                # hooks due at this chunk's edge that only queue writes:
                # run now, on the planned clock, with the chunk in
                # flight; what they queued is enqueued behind it at
                # once, as one write program, and nobody waits
                t_next = self.simt_planned
                if self.plugins.has_due(t_next, reads_state=False):
                    self.plugins.update(t_next, reads_state=False)
                    self.traf.flush()
        except MeshLostError as e:
            # a device group died: end the mesh epoch, not the run
            self._handle_mesh_lost(e)

        self._after_chunk()
        return True

    def _plan_chunk(self, max_chunk: Optional[int] = None):
        """The host pre-chunk phase of ``step()``: pump external command
        sources, process the stack, decide whether a device chunk runs
        this iteration and how long it is.  Returns ``(chunk, simt)``
        ready for dispatch, or ``None`` when this iteration is already
        handled without a chunk (HOLD, straggle stall/debt, FF horizon
        reached, stack-only work).  Split out of ``step()`` so the
        multi-world runner (simulation/worlds.py) can plan every
        world's chunk first and dispatch the compatible ones as ONE
        stacked device program."""
        if self._shard_fallback:
            self._shard_fallback = False
            nd = self._shard_ndev()
            if self.shard_mode == "tiles":
                # degrade one rung at a time: stripes keep the O(N/D)
                # schedule if the 1-D contract still holds; only then
                # the column-replicated floor
                from ..core.asas import ShardContractError
                try:
                    self.scr.echo("SHARD: falling back to SPATIAL "
                                  f"({nd} devices)")
                    self.set_shard("spatial", nd)
                except (ValueError, ShardContractError) as e:
                    self.scr.echo(f"SHARD: SPATIAL fallback failed "
                                  f"({e}); falling back to REPLICATE "
                                  f"({nd} devices)")
                    self.set_shard("replicate", nd)
            else:
                self.scr.echo("SHARD: falling back to REPLICATE "
                              f"({nd} devices)")
                self.set_shard("replicate", nd)

        # External TCP/telnet command lines (tools/network.py bridge)
        if self.telnet is not None:
            self.telnet.pump()
        # Scenario commands due at current sim time (stack.checkfile).
        # The planned count avoids a device sync while a chunk is in
        # flight; the chunk below is planned from it, in steps.
        nplan = self.nstep_planned
        simt = self.clock(nplan)
        self.stack.checkfile(simt)
        # Process pending commands (may change state/config/traffic).
        # Commands observe and mutate the post-chunk state, so the
        # deferred edge retires first — this IS the trigger-boundary /
        # stack-command synchronous fallback.
        if self.stack.cmdstack:
            self._retire_edge("stack")
            # and what a plugin's own program deleted since that edge:
            # a command sees the fleet the device holds, and cannot
            # free and refill a slot whose leaver is still to be read
            self.collect_plugins()
            self.stack.process()
            nplan = self.nstep_planned  # RESET/IC may move the clock
            simt = self.clock(nplan)

        if self.state_flag == INIT and self.traf.ntraf > 0:
            self.op()   # auto-start like simulation.py:89-98

        if self.state_flag != OP:
            self._retire_edge("hold")
            return None

        # FAULT STRAGGLE STALL: skip the device chunk entirely — simt
        # freezes while the host loop keeps pumping events, so progress
        # heartbeats still flow with a flat simt/chunk count.  That is
        # exactly the signature the server's straggler detector hedges
        # on (a SILENT worker is the watchdog/busy-budget case instead).
        if self.straggle_stall:
            time.sleep(0.02)
            return None

        # FAULT STRAGGLE <factor>: pay outstanding throttle debt in
        # SMALL slices, one per host-loop iteration, instead of one
        # chunk-sized sleep — an FF chunk is 50 sim-s, so a block
        # sleep of factor*50 wall-s would silence the event loop and
        # make the "slow but alive" worker look DEAD (no heartbeats)
        # rather than slow, hiding it from rate-based hedging.
        if self._straggle_debt > 0:
            pay = min(self._straggle_debt, 0.05)
            self._straggle_debt -= pay
            time.sleep(pay)
            return None

        # Benchmark bookkeeping
        if self.benchdt > 0.0 and self.bencht == 0.0:
            self.bencht = time.perf_counter()

        if self.traf.dirty:
            # queued creations and slot writes go into the state arrays:
            # retire the deferred edge, then apply them (sync fallback)
            self._retire_edge("flush")
        self.traf.flush()

        # Determine the chunk: stop exactly at the next scenario trigger.
        # IMPORTANT: every distinct nsteps compiles a separate scan program,
        # so the chunk is quantized to a small ladder — at most a handful of
        # compilations per configuration instead of one per trigger distance.
        if max_chunk is not None:
            chunk = max_chunk        # explicit caller bound (run horizon)
        else:
            chunk = self.chunk_steps
            if self.ffmode:
                chunk = max(chunk, 1000)
        limit = chunk
        # Subsystem dt clamps (conditionals <= 1 s, trail resolution,
        # smallest plugin interval).  These derive from a handful of
        # stable per-config dt values, so running them as EXACT step
        # counts costs a bounded number of extra compilations — tracked
        # separately from trigger distances, which are arbitrary.
        dtclamp = None
        if self.cond.ncond > 0:
            dtclamp = max(1, int(round(1.0 / self.cfg.simdt)))
        # Landing detection must sample at ~1 s, like conditionals — but
        # only once an aircraft is actually near its threshold, so
        # en-route fast-forward keeps its long chunks.  The gate radius
        # covers the worst one-chunk travel (ladder max x simdt at each
        # aircraft's own ground speed, floored at 340 m/s) so no aircraft
        # — supersonic or strong-tailwind included — can jump from
        # outside the gate past the landing guard within a single
        # unclamped chunk.
        self._rwy_near = self._runway_approach_active()
        if self._rwy_near:
            c = max(1, int(round(1.0 / self.cfg.simdt)))
            dtclamp = c if dtclamp is None else min(dtclamp, c)
        if self.traf.trails.active:
            c = max(1, int(round(self.traf.trails.dt / self.cfg.simdt)))
            dtclamp = c if dtclamp is None else min(dtclamp, c)
        plugdt = self.plugins.min_dt()
        if plugdt is not None:
            c = max(1, int(round(plugdt / self.cfg.simdt)))
            dtclamp = c if dtclamp is None else min(dtclamp, c)
        if self.plotter.plots:
            pdt = min(p.dt for p in self.plotter.plots)
            c = max(1, int(round(pdt / self.cfg.simdt)))
            dtclamp = c if dtclamp is None else min(dtclamp, c)
        if self.metrics.metric_number >= 0:
            c = max(1, int(round(self.metrics.dt / self.cfg.simdt)))
            dtclamp = c if dtclamp is None else min(dtclamp, c)
        if dtclamp is not None:
            limit = min(limit, dtclamp)
        tnext = self.stack.next_trigger_time()
        if tnext is not None:
            steps_to_trigger = self.steps_until(tnext, nplan)
            if steps_to_trigger > 0:
                limit = min(limit, steps_to_trigger)
        if self.ffstop is not None:
            steps_to_stop = self.steps_until(self.ffstop, nplan)
            if steps_to_stop <= 0:
                self._end_ff()
                return None
            limit = min(limit, steps_to_stop)
        # Quantize to the ladder — EXCEPT when the binding constraint is
        # a dt clamp, which runs exactly (a 0.1 s plugin interval gives
        # 2-step chunks, not 1-step).  Arbitrary trigger distances stay
        # ladder-quantized so scenarios can't force a compile per
        # distinct distance (run_steps nsteps is a static jit arg).
        # A CHUNKSTEPS value off the ladder joins it (the user asked for
        # that exact size and accepts its one-off compilation).
        ladder = self.CHUNK_LADDER
        if self.chunk_steps not in ladder:
            ladder = tuple(sorted(set(ladder) | {int(self.chunk_steps)},
                                  reverse=True))
        chunk = 1
        for c in ladder:
            if c <= limit:
                chunk = c
                break
        if dtclamp is not None and limit == dtclamp \
                and dtclamp < self.CHUNK_LADDER[-3] and chunk < limit:
            chunk = limit

        # Wall-clock pacing (skipped in fast-forward), simulation.py:67-70
        if not self.ffmode and self.dtmult <= 1.0 and self.syst >= 0:
            now = time.perf_counter()
            if now < self.syst:
                time.sleep(self.syst - now)
        if self.syst < 0:
            self.syst = time.perf_counter()
        self.syst += chunk * self.cfg.simdt / max(self.dtmult, 1e-9)

        # Plugin preupdate hooks fire before the device chunk
        # (simulation.py:83); one that reads the state on the host may
        # also mutate it, so a due hook of that kind retires the
        # deferred edge first.  One that only queues writes does not.
        if self.plugins.has_due(simt):
            if self.plugins.has_due(simt, reads_state=True):
                self._retire_edge("plugin")
                # such hooks may mutate traffic DIRECTLY (traf.delete/
                # create) without a stack command, so the ACDATA edge
                # cache cannot be trusted past them
                self._last_edge = None
            self.plugins.preupdate(simt)
            self.traf.flush()   # preupdate hooks may have queued aircraft

        self._n_plan = nplan
        return chunk, simt

    def _after_chunk(self):
        """Post-dispatch horizon check shared by ``step()`` and the
        multi-world runner."""
        if self.ffstop is not None \
                and self.simt_planned >= self.ffstop - 1e-9:
            self._end_ff()
        # rate-limited Prometheus text dump (metrics_export_path knob;
        # no-op when unset) + throttled device-memory watermark sample
        # (devprof_mem_dt knob; off by default)
        self.obs.maybe_export()
        self.devprof.sample_memory()

    # ------------------------------------------------- chunk dispatch/edges
    def _sync_reasons(self, simt: float, chunk: int):
        """Why the upcoming chunk edge cannot be deferred (empty list =
        safe to pipeline).  Every reason is a subsystem that reads or
        mutates the post-chunk state on the host at that edge."""
        reasons = []
        if not self.pipeline_enabled:
            reasons.append("off")
        # The edge clock is the time of the count at the edge, exact: a
        # hook due exactly at the edge (the common case: dt grids align
        # with chunk edges) is classed by it to the 1e-9 s below.
        t_edge = self.clock(self._n_plan + chunk)
        if self.cond.ncond > 0:
            reasons.append("cond")          # ATALT/ATSPD sample + fire
        if self._rwy_near:
            reasons.append("runway")        # landing chain reads state
        if self.plotter.plots:
            reasons.append("plot")          # PLOT samples live attrs
        if self.plugins.has_due(t_edge, reads_state=True):
            reasons.append("plugin")        # a hook at the edge that
            #                                 reads the state on the host
        if self.datalog.any_due(t_edge):
            reasons.append("datalog")       # periodic logger samples
        if self.ffstop is not None and t_edge >= self.ffstop - 1e-9:
            reasons.append("ff-stop")       # _end_ff timing boundary
        if self.preempt_requested:
            reasons.append("preempt")       # drain + checkpoint next
        if self.guard.enabled and self.guard.policy == "halt":
            reasons.append("guard-halt")    # halt wants the tripped
            #                                 state frozen at its edge
        if self.autosave_dt > 0 \
                and t_edge - self._autosave_t >= self.autosave_dt - 1e-9:
            reasons.append("autosave")      # on-disk persist reads state
        return reasons

    def _dispatch_chunk(self, state, chunk: int, keep: bool, simt: float):
        """Enqueue the (due) spatial-sort refresh and the chunk program
        back-to-back — both are async dispatches with no host readback
        between them, so a re-sort edge costs one extra enqueue instead
        of a host round-trip.  Returns the runner's four futures
        (``core/step._edge_scan``: state, telemetry, and the scanstats
        and fingerprint packs or None).

        ``keep=True`` selects the non-donating runner: the caller needs
        the *input* state buffers to stay valid (snapshot-ring capture
        overlapping the dispatched chunk).
        """
        dp = self.devprof
        t0 = time.perf_counter()
        seq = self._next_seq()
        cfg, rows = self.chunk_cfg()
        with self.timed("chunk_dispatch", "sim_dispatch_ms", seq=seq,
                        chunk=chunk, simt=simt, world=self.world_tag,
                        epoch=self.mesh_epoch) as sc:
            self._note_cd_rows(rows, sc)
            # Mesh-epoch liveness precheck: a dead device group (FAULT
            # MESHKILL, or a peer whose heartbeat stamp went stale) must
            # surface BEFORE the chunk is enqueued onto the dead mesh —
            # raising MeshLostError here routes into _handle_mesh_lost.
            if self.shard_mesh is not None and self.mesh_guard_enabled:
                with self.timed("mesh_check", seq=seq,
                                epoch=self.mesh_epoch,
                                world=self.world_tag):
                    self.mesh_guard.check()
            win = dp.begin_chunk(seq)
            self._refresh_ms = 0.0
            state = self._pre_dispatch_refresh(state, simt, chunk)
            from ..core.step import run_steps_edge, run_steps_edge_keep
            runner = run_steps_edge_keep if keep else run_steps_edge
            nd = self._shard_ndev(default=1)
            dp.note_dispatch(
                ("edge_keep" if keep else "edge")
                + ("+checked" if self.guard.enabled else "")
                # a rung of the dense interval is a program of its own
                + (f"+rows{cfg.cd_rows}" if cfg.cd_rows else ""),
                chunk, self.traf.nmax, nd)
            t_enq = time.perf_counter()
            self._note_pipeline_empty(dp.program_time(t_enq))
            out = runner(state, cfg, chunk, checked=self.guard.enabled)
            if not keep:
                dp.check_donation(state, out)
        t1 = time.perf_counter()
        self._last_dispatch_end = dp.program_time(t1)
        if win:
            # a windowed chunk is dispatched like any other: only its
            # host stamps are kept, for the device trace's clock
            dp.note_chunk(seq, chunk, t0, t_enq, t1, self._refresh_ms)
        return out

    def chunk_cfg(self, pack=()):
        """``(cfg, rows)`` for the chunk about to be dispatched:
        ``self.cfg`` with the dense interval's rows filled in
        (``core/step.cd_dense_rows``) from the slots this fleet occupies
        and, for a stacked dispatch, those of the other worlds of its
        ``pack`` (one program, so the largest bound).  The other
        backends have no such interval: ``self.cfg`` as it is and None,
        without a look at the fleet."""
        if self.cfg.cd_backend != "dense":
            return self.cfg, None
        bound = max(s.traf.slot_bound for s in (self, *pack))
        return cd_dense_rows(self.cfg, self.traf.nmax, bound)

    def _note_cd_rows(self, rows, span):
        """One observation of ``sim_cd_dense_rows`` and the tag
        ``cd_rows`` on the ``chunk_dispatch`` scope, for a chunk whose
        backend is the dense one (``rows`` None on the others)."""
        if rows is not None:
            span.tag(cd_rows=rows)
            self.obs.get("sim_cd_dense_rows").observe(rows)

    def _note_pack(self, telem):
        """Set the gauge ``sim_edge_pack_buffers`` from the first edge
        pack since construction or a reset: the device buffers a
        dispatch allocates for the telemetry (its own pack's, for a
        world of a stacked dispatch).  Not per chunk: the pack's form
        is the program's, and no setting selects it."""
        if not self._pack_noted:
            self._pack_noted = True
            self.obs.get("sim_edge_pack_buffers").set(
                len(jax.tree_util.tree_leaves(telem)))

    def _note_pipeline_empty(self, now):
        """One observation of ``sim_pipeline_empty_ms`` for the chunk
        about to be enqueued at ``now`` (program clock): how long the
        host has known the device to hold no chunk program, which is
        the time since the wait that retired the newest dispatched
        chunk returned (``_edge_span``), across resets, scenario loads
        and pieces; 0.0 behind a chunk that is still unretired, so
        every dispatch observes.  A lower bound of the device's idle
        time: write programs and ``make_state`` run inside it."""
        t, self._t_drained = self._t_drained, None
        self.obs.get("sim_pipeline_empty_ms").observe(
            0.0 if t is None else max(0.0, (now - t) * 1e3))

    def _next_seq(self) -> int:
        """Bump and return the host-side chunk-sequence correlation tag
        (docs/OBSERVABILITY.md): one per dispatched chunk, stamped onto
        the ChunkEdge and every span of that chunk.  Host-side by
        design — the EdgeTelemetry device pack must not grow an op for
        it (the recorder-off path is bit-identical)."""
        self._chunk_seq += 1
        self._seq_dispatched = self._chunk_seq
        return self._chunk_seq

    def _pre_dispatch_refresh(self, state, simt: float, chunk: int):
        """The (due) chunk-edge spatial-sort refresh — split from
        ``_dispatch_chunk`` so the multi-world runner can refresh each
        world's layout before stacking them into one joint dispatch.
        ``chunk`` is the length in steps of the chunk about to be
        dispatched: a layout lives until the first edge past the
        cadence, so for the longer of the two."""
        if self.cfg.cd_backend in ("tiled", "pallas", "sparse"):
            due = self.cfg.asas.sort_every * self.cfg.asas.dtasas
            # Also force a refresh when the backend changed: 'sparse'
            # stores stripe DESTINATIONS in sort_perm, the others a
            # Morton PERMUTATION — feeding one into the other scrambles
            # the sorted layout.
            if (simt - self._sort_simt >= due
                    or self._sort_simt < 0
                    or self._sort_backend != self.cfg.cd_backend):
                with self.timed("sort_refresh", "sim_sort_refresh_ms",
                                backend=self.cfg.cd_backend,
                                shard=self.shard_mode,
                                world=self.world_tag) as sp:
                    if self.shard_mode in ("spatial", "tiles"):
                        state = self._spatial_refresh(state)
                    elif self.cfg.cd_backend == "sparse":
                        # the schedule's counters leave the refresh
                        # program as device scalars and are read when
                        # the chunk this layout starts is retired; the
                        # outgoing layout's only when it was one of
                        # this backend's own
                        from ..core.asas import refresh_sparse_counted
                        had_layout = self._sort_backend == "sparse"
                        state, fresh, aged = refresh_sparse_counted(
                            state, self.cfg.asas,
                            block=self.cfg.cd_block,
                            life_s=max(due, chunk * self.cfg.simdt))
                        self._sched_counts = (
                            fresh, aged if had_layout else None, sp)
                    else:
                        from ..core.asas import impl_for_backend, \
                            refresh_spatial_sort
                        state = refresh_spatial_sort(
                            state, self.cfg.asas,
                            block=self.cfg.cd_block,
                            impl=impl_for_backend(self.cfg.cd_backend))
                self._refresh_ms = sp.ms
                self._sort_simt = simt
                self._sort_backend = self.cfg.cd_backend
        return state

    def _step_pipelined(self, chunk: int, simt: float):
        """Double-buffered dispatch: enqueue the next chunk, THEN retire
        the previous chunk's edge off its telemetry pack while the new
        chunk runs on the device."""
        inflight = self._inflight
        ring = self.snap_ring
        # Will retiring the pending edge capture a rollback restore
        # point?  Then this dispatch must NOT donate its input buffers:
        # they hold exactly the post-chunk state that goes into the
        # ring, and the device->host copy overlaps the dispatched chunk.
        # Captures feed the rollback policy AND the mesh-epoch recovery
        # restore point: under an active mesh the ring must keep
        # filling regardless of guard policy, or a device-group loss
        # would have nothing checksummed to re-shard from.
        capture_due = (ring.dt > 0
                       and simt - ring.t_last >= ring.dt - 1e-9)
        capture_now = (bool(inflight) and capture_due
                       and ((self.guard.enabled
                             and self.guard.policy == "rollback")
                            or self.shard_mode != "off"))
        state_in = self.traf.state
        new_state, telem, sstats, fpack = self._dispatch_chunk(
            state_in, chunk, keep=capture_now, simt=simt)
        self.traf.state = new_state
        self._step_count += chunk
        self._straggle_charge(chunk)
        self._n_next = self._n_plan + chunk
        self._note_pack(telem)
        inflight.append(ChunkEdge(telem, chunk, self.clock,
                                  nstep_planned=self._n_next,
                                  seq=self._seq_dispatched,
                                  obs_sink=self._edge_pull_sink,
                                  stats=sstats, fingerprint=fpack,
                                  sched=self._take_sched_counts(),
                                  t_dispatch=self._last_dispatch_end))
        self.pipe_stats["pipelined_chunks"] += 1
        # Retire the oldest edges (a deferred trip among them voids the
        # rest) until what is in flight spans no more steps than one
        # unclamped chunk; all but the new one when the ring takes the
        # state this dispatch kept, which is the one behind the edge
        # before it.
        while len(inflight) > 1 and (
                capture_now or sum(e.chunk for e in inflight)
                > max(self.chunk_steps, chunk)):
            edge = inflight.popleft()
            self._finish_edge(edge, capture_state=state_in
                              if capture_now and len(inflight) == 1
                              else None)

    def _step_sync(self, chunk: int, simt: float):
        """The synchronous chunk: dispatch, block on the guard word,
        then run every edge subsystem against the live state — the
        pre-pipeline behavior, bit-identical step math."""
        self.pipe_stats["sync_chunks"] += 1
        state, telem, sstats, fpack = self._dispatch_chunk(
            self.traf.state, chunk, keep=False, simt=simt)
        self._apply_chunk_result(state, telem, chunk, stats=sstats,
                                 fingerprint=fpack)

    def _apply_chunk_result(self, state, telem, chunk: int,
                            seq: Optional[int] = None, stats=None,
                            fingerprint=None):
        """Install one synchronously-completed chunk's result and run
        every edge subsystem against it — the post-dispatch half of
        ``_step_sync``.  The multi-world runner calls this per world
        with that world's slice of the joint stacked dispatch, so guard
        response (rollback/quarantine), conditionals, trails, loggers
        and ring captures all stay per-world (it passes each world its
        own ``seq`` correlation tag from the shared dispatch)."""
        self.traf.state = state
        self._step_count += chunk
        self._straggle_charge(chunk)
        if seq is None:
            seq = self._seq_dispatched
        self._note_pack(telem)
        edge = ChunkEdge(telem, chunk, self.clock,    # the device's count
                         seq=seq, obs_sink=self._edge_pull_sink,
                         stats=stats, fingerprint=fingerprint,
                         sched=self._take_sched_counts(),
                         t_dispatch=self.devprof.program_time())
        with self._edge_span(edge) as ret:
            self._apply_edge(edge, chunk, ret)

    def _apply_edge(self, edge, chunk: int, ret):
        """The body of ``_apply_chunk_result``, inside its
        ``chunk_edge`` span."""
        with self._device_wait(ret):
            # The reads that block on the chunk: the guard word — or,
            # with the guard off, the clock every subsystem below reads.
            bad = edge.bad_step if self.guard.enabled else -1
            if not self.guard.enabled:
                _ = self.simt
        tripped = False
        if bad >= 0:
            # Integrity-guarded chunk: the isfinite check rides the scan
            # carry and pins a trip to one step of the chunk; the guard
            # then quarantines or rolls back at this chunk edge.
            self.guard.trip(bad, chunk)
            tripped = True
        # Publish the edge to the ACDATA cache only when its pack still
        # describes the live state: a trip just scrubbed/rolled back the
        # fleet, so the tripped pack (NaN positions, deleted slots) must
        # never reach the stream.  Conditional/runway mutations below go
        # through the stack, which clears the cache (stack.py); plugin
        # hooks can mutate traffic DIRECTLY, so a due hook clears it
        # explicitly after the subsystem block.
        self._last_edge = None if tripped else edge
        # Drain the in-scan stats pack only off a CLEAN edge: a tripped
        # chunk's accumulators are downstream of the poisoned step.
        if not tripped:
            self._observe_counts(edge)
            self._drain_scanstats(edge)
            self._drain_fingerprint(edge)
        plugins_due = self.plugins.has_due(self.simt, reads_state=True)

        # Chunk-edge subsystems: plugin updates, conditional triggers,
        # trails, loggers (the reference runs these per 0.05 s step,
        # simulation.py:110-116; here they sample the chunk-edge state)
        self.plugins.collect(edge.seq)
        self.plugins.update(self.simt)
        self.traf.flush()
        self.cond.update()
        self._check_runway_landings()
        self.plotter.update(self.simt)
        self.metrics.update()
        self.traf.trails.update(self.simt)
        self.datalog.postupdate(self)
        if plugins_due:
            self._last_edge = None

        # Periodic snapshot-ring capture: the post-chunk state is
        # verified finite when the guard is on, so ring entries are
        # always healthy restore points.  The rollback policy consumes
        # the ring, and the mesh-epoch recovery restores its newest
        # entry after a device-group loss — a capture is a full
        # device->host copy of the state pytree (tens of MB at 100k
        # aircraft), so configurations needing neither must not pay.
        if self.state_flag == OP \
                and ((self.guard.enabled
                      and self.guard.policy == "rollback")
                     or self.shard_mode != "off"):
            self.snap_ring.maybe_capture(self)

        # Periodic on-disk autosnapshot (snapshot_autosave_dt, off by
        # default): persist the newest ring entry — or a fresh capture
        # when no ring is being kept — with the atomic checksummed
        # writer, so a later preemption/kill resumes from here.
        if self.autosave_dt > 0 and self.state_flag == OP \
                and self.simt - self._autosave_t \
                >= self.autosave_dt - 1e-9:
            self._autosave()

    def _straggle_charge(self, chunk: int):
        # FAULT STRAGGLE <factor>: every simulated second OWES `factor`
        # extra wall seconds, added to the debt ledger paid off in
        # slices above — this worker's progress rate sinks below the
        # fleet median while its heartbeats keep flowing.
        if self.straggle_factor > 0:
            self._straggle_debt += \
                chunk * self.cfg.simdt * self.straggle_factor

    def _finish_edge(self, edge, capture_state=None):
        """Retire one DEFERRED chunk edge: poll the guard word (the
        one-scalar completion fence), respond to a late trip, then run
        the passive edge consumers off the fused telemetry pack.  Runs
        while the next chunk computes on the device."""
        with self._edge_span(edge) as ret:
            with self._device_wait(ret):
                # The reads that block on the chunk, in the order they
                # always came: the guard word, the device's own edge
                # clock.
                bad = edge.bad_step
                tripped = self.guard.enabled and bad >= 0
                ahead = list(self._inflight)
                actual = edge.nstep_device \
                    if ahead and not tripped else None
            if tripped:
                ret.dropped = True
                self._deferred_trip(edge, bad)
                return
            # Re-anchor the planned count against the device's own
            # (one scalar, already materialized).  The host chose every
            # chunk's length, so this is a no-op; it guarantees the two
            # can never part.
            if actual is not None and actual != edge.nstep_planned:
                for nxt in ahead:
                    actual += nxt.chunk
                    nxt.nstep_planned = actual
                self._n_next = actual
            # Passive consumers: each samples the edge state from the
            # pack (ONE bulk device->host copy, and only if somebody
            # reads).
            self._observe_counts(edge)
            self._drain_scanstats(edge)
            self._drain_fingerprint(edge)
            # what a plugin's own program took out of the state before
            # this chunk: the host's record follows here, where this
            # edge's pack already shows the slots inactive
            self.plugins.collect(edge.seq)
            self.metrics.update(edge)
            if self.traf.trails.active:
                pack = edge.fetch()
                self.traf.trails.update(edge.simt,
                                        np.asarray(pack.lat),
                                        np.asarray(pack.lon),
                                        active=np.asarray(pack.active))
            self._last_edge = edge
            # Off-critical-path snapshot-ring capture: the dispatch
            # kept (did not donate) these buffers, so the full pytree
            # copy runs concurrently with the in-flight chunk.  They
            # hold what ran behind this chunk too, a plugin's tick
            # among it: the host's record follows that far first.
            if capture_state is not None:
                self.collect_plugins(edge.seq + 1)
                self.snap_ring.capture(self, state=capture_state,
                                       simt=edge.simt)

    def _take_sched_counts(self):
        """The counters the last sparse refresh left, for the edge of
        the chunk that was dispatched right behind it."""
        sched, self._sched_counts = self._sched_counts, None
        return sched

    def _observe_counts(self, edge):
        """The count series of a retired edge, read once the chunk is
        known complete (so nothing here waits): the conflict pairs
        alive, from the pack, and, when this chunk started from a fresh
        sparse layout, what its schedule visits and what the schedule
        of the layout it replaced had come to."""
        self.obs.get("sim_conf_pairs").observe(edge.conf_pairs)
        if edge.sched is not None:
            import jax as _jax
            fresh, aged, span = edge.sched
            fresh, aged = _jax.device_get((fresh, aged))
            # the span closed at dispatch; its tags are the dict its
            # recorded event holds, so they still reach a later dump
            for counts, of in ((fresh, ""), (aged, "_aged")):
                if counts is None:       # a first refresh: no layout
                    continue             # of this backend went out
                pairs, overflow = (int(v) for v in counts)
                self.obs.get("sim_cd_block_pairs" + of).observe(pairs)
                self.obs.get("sim_cd_overflow_rows" + of).observe(
                    overflow)
                span.tag(**{"block_pairs" + of: pairs,
                            "overflow_rows" + of: overflow})

    def _drain_scanstats(self, edge):
        """Drain one clean edge's in-scan accumulator pack (ISSUE-14):
        ONE device->host pull of the small ScanStats pytree, folded
        into the registry (histogram bucket counts merge count-exactly,
        so the series ship fleet-wide through the existing heartbeat
        ``Registry.delta()`` path) and summarized for HEALTH/heartbeat
        consumption; a recorder event carries the summary under the
        chunk's correlation tag.  No-op when the edge carries no pack
        (scanstats off for the producing chunk)."""
        if edge.stats is None:
            return
        import jax as _jax
        from ..obs import scanstats as ssmod
        self._scan_last = ssmod.drain(self.obs,
                                      _jax.device_get(edge.stats))

    @contextlib.contextmanager
    def _edge_span(self, edge):
        """One edge retirement: the ``chunk_edge`` scope, and on a clean
        exit the chunk-latency series (dispatch return -> retirement
        done) and the split of the retirement into the wait for the
        chunk (``_device_wait``) and the host's own work.  All on the
        program's clock, which stops inside the profiler
        (``DevProf.program_time``).  A body that sets ``dropped`` (a
        deferred guard trip) books nothing."""
        dp = self.devprof
        ret = _EdgeRetire()
        with self.timed("chunk_edge", seq=edge.seq, chunk=edge.chunk,
                        world=self.world_tag) as sc:
            yield ret
        if ret.dropped:
            return
        wait_ms = ret.wait_s * 1e3
        work_ms = sc.ms - wait_ms
        latency_ms = sc.ms + (sc.c0 - edge.t_dispatch) * 1e3
        obs = self.obs.get
        obs("sim_chunk_latency_ms").observe(latency_ms)
        obs("sim_device_wait_ms").observe(wait_ms)
        obs("sim_edge_work_ms").observe(work_ms)
        dp.note_edge(edge.seq, ret.t_wait_end, work_ms)
        # the clock as this edge's pack carries it (scalars the
        # retirement has read): the steps retired, the simulated time
        # they took by the pack's own ``simt``, the device's count
        n = edge.nstep_device
        obs("sim_steps").inc(edge.chunk)
        obs("sim_clock_s").inc(edge.simt_device
                               - self.sent(self.clock(n - edge.chunk)))
        obs("sim_step_count").set(n)
        # the span is closed; its tags are the dict its event holds
        sc.tag(latency_ms=round(latency_ms, 3), n=n)
        if edge.seq == self._seq_dispatched:
            # nothing is in flight behind this chunk: the device holds
            # no chunk program from the moment the wait returned
            self._t_drained = dp.program_time(ret.t_wait_end)
        dp.end_window()          # after the n-th windowed edge only

    @contextlib.contextmanager
    def _device_wait(self, ret):
        """The part of an edge retirement that blocks on the chunk's
        outputs: the reads inside it are the ones the retirement makes
        anyway, so this adds no transfer and no synchronisation."""
        with self.timed("device_wait") as sc:
            yield
        ret.t_wait_end = time.perf_counter()
        ret.wait_s += sc.ms * 1e-3

    def _deferred_trip(self, edge, bad: int):
        """A guard word that came back tripped one chunk LATE (the
        deferred-readback contract): the fleet has already advanced
        into the next chunk, computed from the poisoned state.  Drop
        the in-flight edge (its telemetry is downstream of the fault)
        and run the guard response against the CURRENT state —
        ``rollback`` restores a pre-fault ring entry exactly as in the
        synchronous path (the ring horizon dwarfs the one-chunk lag);
        ``quarantine`` deletes every aircraft non-finite NOW, catching
        any spread the extra chunk caused.  ``halt`` never defers
        (guard-halt is a sync fallback reason)."""
        lag = len(self._inflight)
        self._inflight.clear()
        self._last_edge = None
        self.pipe_stats["deferred_trips"] += 1
        rec = self.guard.trip(int(bad), edge.chunk)
        if isinstance(rec, dict):
            rec["deferred"] = True
            rec["detect_lag_chunks"] = lag

    def _retire_edge(self, reason: str = "sync"):
        """Synchronization point: finish the deferred edge work of the
        in-flight chunk (if any) before host code reads or mutates the
        state.  Safe to call anywhere; reentrancy-guarded because edge
        work itself (guard rollback -> reset_traffic) drains."""
        if not self._inflight or self._retiring:
            return
        self._retiring = True
        try:
            while self._inflight:     # a deferred trip voids the rest
                self._finish_edge(self._inflight.popleft())
            # The retired edge state IS the live state again (nothing
            # was dispatched after it), so a due ring capture can use
            # the classic path at this sync boundary.
            if self.state_flag == OP \
                    and ((self.guard.enabled
                          and self.guard.policy == "rollback")
                         or self.shard_mode != "off"):
                self.snap_ring.maybe_capture(self)
        finally:
            self._retiring = False

    def drain_pipeline(self):
        """Public alias: block until no chunk is in flight and all edge
        work has run (callers: node shutdown, tests, snapshots)."""
        self._retire_edge("drain")
        self.collect_plugins()
        return True

    def collect_plugins(self, upto=None):
        """The host's record brought level with what the plugins' own
        programs took out of the state before the chunk with sequence
        tag ``upto`` (all of them, waiting for them, if None): before a
        command reads the fleet, and before a snapshot pairs the host's
        tables with a state."""
        if self.plugins.collect(upto):
            # the host forgot aircraft the newest edge's pack still
            # shows: no stream frame from it
            self._last_edge = None

    def _runway_approach_active(self) -> bool:
        """Any unlanded runway-destination aircraft within its landing
        gate?  Cheap host flat-earth test — gates the 1 s landing
        sampling clamp so cruise fast-forward keeps long chunks.

        The gate radius is per-aircraft: threshold proximity guard plus
        the worst one-chunk travel at that aircraft's actual ground
        speed (floored at 340 m/s so a stale/slow reading still covers
        normal jets).

        While a pipelined chunk is in flight, the test samples the last
        RETIRED edge's telemetry pack instead of the live state — an
        ``np.asarray`` on the in-flight buffers would block the host
        until the chunk drains, silently serializing the pipeline for
        every scenario with runway-destination aircraft.  The pack is
        up to one extra chunk stale, so the gate widens by one more
        chunk of worst-case travel."""
        cands = self.routes.runway_final_slots()
        if not cands:
            return False
        edge = self._last_edge if self._inflight else None
        if edge is not None:
            pack = edge.fetch()
            lat = np.asarray(pack.lat)
            lon = np.asarray(pack.lon)
            gs = np.asarray(pack.gs)
            staleness = 2.0        # [chunks] covered by the gate radius
        else:
            st = self.traf.state
            lat = np.asarray(st.ac.lat)
            lon = np.asarray(st.ac.lon)
            gs = np.asarray(st.ac.gs)
            staleness = 1.0
        chunk_s = staleness * self.CHUNK_LADDER[0] * self.cfg.simdt
        # Worst-case acceleration cushion: gs is sampled at chunk START,
        # and an aircraft can accelerate through the chunk (perf-model
        # accel is ~0.5-2 m/s^2); 2 m/s^2 * chunk_s bounds the extra
        # travel so the gate still covers one full unclamped chunk.
        accel_cushion = 2.0 * chunk_s
        for slot, r in cands:
            if self.traf.ids[slot] is None:
                continue
            last = r.nwp - 1
            gate_nm = 5.0 + chunk_s * (
                max(340.0, float(gs[slot]) + accel_cushion)) / 1852.0
            dlat = lat[slot] - r.lat[last]
            dlon = (lon[slot] - r.lon[last]) * np.cos(np.radians(r.lat[last]))
            if np.hypot(dlat, dlon) * 60.0 <= gate_nm:
                return True
        return False

    def _check_runway_landings(self):
        """Runway-landing chain (reference route.py getnextwp:741-775).

        When the device FMS has reached an aircraft's FINAL waypoint and
        that waypoint is a runway threshold (DEST/ADDWPT ``APT/RWNN``),
        issue the reference's landing command sequence: hold the runway
        heading, decelerate after 10 s, delete after 42 s.  Runs at chunk
        edges; a 3 nm proximity guard distinguishes "reached the
        threshold" from a manual LNAV OFF far from the field.
        """
        # The pre-chunk gate (step(), gate_nm covers one-chunk travel)
        # proves nobody can be near a threshold this chunk — skip the
        # device transfers entirely for the cruise phase.
        if not getattr(self, "_rwy_near", True):
            return
        cands = self.routes.runway_final_slots()
        if not cands:
            return
        st = self.traf.state
        swlnav = np.asarray(st.ac.swlnav)
        iact = np.asarray(st.route.iactwp)
        lat = np.asarray(st.ac.lat)
        lon = np.asarray(st.ac.lon)
        fired = False
        for slot, r in cands:
            acid = self.traf.ids[slot]
            last = r.nwp - 1
            if acid is None or iact[slot] < last or swlnav[slot]:
                continue
            dlat = lat[slot] - r.lat[last]
            dlon = (lon[slot] - r.lon[last]) * np.cos(np.radians(r.lat[last]))
            if np.hypot(dlat, dlon) * 60.0 > 3.0:     # [nm] proximity guard
                continue
            # Runway heading from the threshold database when known, else
            # the final leg bearing (same number the FMS flew)
            apt, _, rwy = r.name[last].partition("/")
            thr = self.navdb.getrwythreshold(apt, rwy) if rwy else None
            if thr is not None:
                hdg = thr[2]
            elif last > 0:
                from ..ops import hostgeo
                hdg = float(hostgeo.qdrdist(
                    r.lat[last - 1], r.lon[last - 1],
                    r.lat[last], r.lon[last])[0]) % 360.0
            else:
                hdg = float(np.asarray(st.ac.trk)[slot])
            r.flag_landed = True
            fired = True
            self.stack.stack(f"HDG {acid} {hdg:.1f}")
            self.stack.stack(f"DELAY 10 SPD {acid} 10")
            self.stack.stack(f"DELAY 42 DEL {acid}")
        if fired:
            self.stack.process()

    def _end_ff(self):
        self.ffmode = False
        self.ffstop = None
        if self.benchdt > 0.0:
            wall = time.perf_counter() - self.bencht
            self.scr.echo(
                f"Benchmark complete: {wall:.3f} s wall for "
                f"{self.benchdt:.1f} s sim ({self.benchdt / max(wall, 1e-9):.1f}x)")
            self.benchdt = -1.0
        self.pause()

    def run(self, until_simt: Optional[float] = None, max_iters: int = 10 ** 9):
        """Drive step() until END/HOLD or a sim-time horizon.

        Horizon math uses the planned clock so the loop itself never
        forces a device sync; the pipeline drains before returning so
        callers observe a fully-retired state."""
        it = 0
        while it < max_iters:
            it += 1
            mc = None
            if until_simt is not None:
                remaining = until_simt - self.simt_planned
                if remaining <= 1e-9:
                    break
                # stop exactly at the horizon (ladder-quantized downstream)
                mc = max(1, int(round(remaining / self.cfg.simdt)))
            alive = self.step(max_chunk=mc)
            if self.preempt_requested:
                # embedded-run preemption: checkpoint + pause here (a
                # networked node drains via simnode instead, which also
                # notifies the server and exits the process)
                self.handle_preempt()
                break
            if not alive or self.state_flag in (HOLD, END):
                if self.state_flag == HOLD and until_simt is not None \
                        and self.simt_planned < until_simt - 1e-9:
                    break
                if self.state_flag != OP:
                    break
        self.drain_pipeline()
        return self.simt
