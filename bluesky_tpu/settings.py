"""Config/flag system (parity: bluesky/settings.py:8-133, modernized).

Two-level scheme like the reference: a config file plus per-module
registered defaults (``set_variable_defaults``).  Divergence from the
reference (SURVEY.md §5.6 build note): the config file is a restricted
``key = value`` Python file evaluated with ``ast.literal_eval`` per line —
config is data, not arbitrary code — and unknown keys are kept so modules
registering defaults later still pick them up.

Data paths default to the read-only reference data mount when present so
navdata/performance coefficients load out of the box; everything degrades
gracefully when they are absent.
"""
import ast
import os
import sys

# BLUESKY_TPU_NO_REF=1 pretends the read-only reference mount is absent
# (standalone mode): navdata starts empty, performance falls back to the
# BUILTIN coefficients, and the scenario library is the local dir only.
# BLUESKY_TPU_DATA=/path points at a BlueSky data checkout (deployment
# hook used by the Dockerfile; takes precedence over the dev mount).
_NO_REF = os.environ.get("BLUESKY_TPU_NO_REF") == "1"
_REF_DATA = os.environ.get("BLUESKY_TPU_DATA") \
    or ("" if _NO_REF else "/root/reference/data")

# ----------------------------------------------------------------- defaults
simdt = 0.05
nmax = 1024                       # aircraft slots of a Simulation (padded
                                  # capacity; fixed for the life of the
                                  # process, every compiled program is
                                  # shaped by it)
chunk_steps = 20                  # interactive device-chunk length in
                                  # steps (1 s sim time at simdt=0.05);
                                  # CHUNKSTEPS stack command at runtime.
                                  # FF/BATCH runs still use >=1000-step
                                  # chunks.  Off-ladder values compile
                                  # one extra scan program.
chunk_pipeline = True             # async chunk pipeline: dispatch chunk
                                  # k+1 before chunk k's edge work, edge
                                  # subsystems read the fused telemetry
                                  # pack, guard readback is deferred one
                                  # chunk (docs/PERF_ANALYSIS.md)
performance_model = "openap"
prefer_compiled = True            # use the C host extension when built
data_path = _REF_DATA if os.path.isdir(_REF_DATA) else "data"
cache_path = os.path.join(os.path.expanduser("~"), ".cache", "bluesky_tpu")
navdata_path = os.path.join(data_path, "navdata")
# `bluesky-tpu --import-navdata <dir>` copies a reference-format navdata
# tree here; it backs standalone deployments when no mount is configured
imported_navdata_path = os.path.join(cache_path, "navdata")
if not os.path.isdir(navdata_path) and os.path.isdir(imported_navdata_path):
    navdata_path = imported_navdata_path
perf_path = os.path.join(data_path, "performance")
log_path = "output"
scenario_path = "scenario"
# the reference's ~90-file scenario library, searched after the local
# dir (like the navdata/performance mounts above)
_REF_SCN = "" if _NO_REF else "/root/reference/scenario"
ref_scenario_path = _REF_SCN if os.path.isdir(_REF_SCN) else ""
plugin_path = "plugins"
enabled_plugins = ["datafeed"]
event_port = 9000
stream_port = 9001
wevent_port = 10000
wstream_port = 10001
discovery_port = 11000
max_nnodes = 1                    # workers a server spawns on this host.
                                  # 1: the worker owns every chip of the
                                  # host (SHARD spreads one sim over
                                  # them) and nothing is named.  N > 1:
                                  # each worker is given one device slot,
                                  # 0 .. N-1, and holds that chip alone
                                  # (at most as many as the host has
                                  # chips; on a named CPU the slot is
                                  # reported and restricts nothing)
sim_detached = False
telnet_port = 8888

# ----- fault tolerance (docs/FAULT_TOLERANCE.md has the tuning guide)
guard_enabled = True              # in-scan isfinite integrity guard
guard_policy = "quarantine"       # "quarantine" | "rollback" | "halt"
snap_ring_depth = 4               # rollback horizon = depth * dt sim-sec
snap_ring_dt = 30.0               # [sim s] between ring captures (0 = off)
batch_max_crashes = 3             # consecutive worker losses before a
                                  # BATCH piece is circuit-broken
connect_backoff_base = 0.25       # [s] first client connect retry delay
connect_backoff_cap = 4.0         # [s] backoff ceiling (jitter on top)
node_watchdog_warn = 30.0         # [s] event-loop silence before warning
node_watchdog_kill = 0.0          # [s] silence before exit(70); 0 = never
fault_seed = 0                    # RNG seed for the FAULT injectors

# ----- overload / straggler serving layer (docs/FAULT_TOLERANCE.md
# rows #10/#11): progress heartbeats, speculative re-dispatch,
# admission control and bounded stream buffering
hb_busy_multiplier = 10.0         # [x hb_timeout] PING-silence budget for
                                  # a worker mid-BATCH / in OP (long device
                                  # chunks + first-compile legitimately
                                  # block the event loop for minutes)
straggler_timeout = 30.0          # [s] fresh heartbeats but no sim-time/
                                  # chunk advance on an in-flight piece
                                  # before it is hedged (0 = never)
hedge_enabled = True              # speculative straggler re-dispatch
hedge_rate_factor = 0.2           # also hedge when a worker's progress
                                  # rate < factor * fleet median
batch_queue_max = 4096            # pending BATCH pieces before a
                                  # submission gets BATCHREJECTED
                                  # (0 = unbounded, pre-PR3 behavior)
batch_retry_after = 5.0           # [s] BATCHREJECTED retry hint when no
                                  # drain-rate estimate exists yet
stream_sndhwm = 1000              # [msgs] send buffer bound on the stream
                                  # sockets; a stalled GUI client gets
                                  # drops (counted), never back-pressure
quarantine_report_cap = 64        # BATCHQUARANTINE replay history kept
                                  # for late-joining clients

# ----- multi-world serving (docs/PERF_ANALYSIS.md §multi-world)
world_pack = False                # pack compatible BATCH pieces into
                                  # world-batches: one worker steps W
                                  # scenarios per device dispatch
                                  # (vmapped world axis, core/step.py).
                                  # WORLDS stack command at runtime.
world_batch_max = 8               # max pieces per world-batch dispatch
                                  # (the per-bucket packing width; 1 =
                                  # packing effectively off).  Every
                                  # (nmax-bucket, chunk-length) pair
                                  # compiles one stacked scan program
                                  # per distinct W it sees.

# ----- multi-chip decomposition (docs/PERF_ANALYSIS.md §multi-chip)
shard_mode = "off"                # "off" | "replicate" (row-interleaved
                                  # kernels vs replicated O(N) columns) |
                                  # "spatial" (device-owned latitude
                                  # stripes + halo exchange; sparse
                                  # backend only) | "tiles" (2-D lat x
                                  # lon tiles + corner-halo exchange;
                                  # sparse backend only).  SHARD stack
                                  # command switches at runtime.
shard_devices = 0                 # mesh size (0 = every visible device)
shard_halo_blocks = 0             # spatial halo width in 256-slot blocks
                                  # per side (0 = one full neighbour
                                  # device; validated against the exact
                                  # reach bound + drift margin at every
                                  # refresh)
shard_tile_shape = ""             # tiles mode: "RxC" lat x lon grid
                                  # ("" = near-square factorization of
                                  # the device count, e.g. 8 -> "4x2");
                                  # per-offset halo slab budgets are
                                  # auto-pinned by the tile refresh

# ----- mesh-epoch recovery (docs/FAULT_TOLERANCE.md §mesh epochs):
# losing a device group ends the mesh epoch, not the run — survivors
# re-form a smaller mesh and resume from the last checksummed snapshot
mesh_guard_enabled = True         # MeshGuard dead-peer check at every
                                  # chunk dispatch of a sharded sim
mesh_dispatch_timeout = 0.0       # [wall s] collective-wait budget per
                                  # chunk edge; exceeding it with stale
                                  # peer heartbeats trips mesh_lost
                                  # (0 = block forever, single-host)
mesh_heartbeat_dir = ""           # shared dir for cross-process mesh
                                  # heartbeat stamps ("" = off; set for
                                  # multi-host meshes, e.g. an NFS path)
mesh_heartbeat_timeout = 10.0     # [wall s] peer stamp staleness before
                                  # the peer counts as dead

# ----- differentiable simulation (bluesky_tpu/diff/; OPT/GRAD stack
# commands; docs/PERF_ANALYSIS.md §differentiable).  The OPT driver
# descends on per-aircraft waypoint/time offsets with jax.value_and_grad
# over the smooth step scan; these are its defaults (stack-command
# arguments override per run).
opt_tend = 600.0                  # [sim s] optimization rollout horizon
opt_simdt = 1.0                   # [s] smooth-rollout step (coarser than
                                  # the serving 0.05 s; the hard-metric
                                  # verification runs at opt_verify_dt)
opt_chunk = 50                    # steps per jax.checkpoint chunk —
                                  # backward memory stays O(chunk)
opt_iters = 40                    # Adam iterations
opt_lr = 0.15                     # Adam LR (normalized offset units)
opt_temp0 = 0.3                   # soft-LoS temperature: anneal start
opt_temp1 = 0.05                  # ... and end (fractions of rpz/hpz)
opt_restarts = 1                  # multi-start particles batched on the
                                  # PR-6 world axis (best particle wins)
opt_los_margin = 1.2              # soft-zone inflation over the hard
                                  # rpz: buffer against the measured
                                  # <1 km smooth-vs-hard model mismatch
opt_verify_dt = 0.05              # [s] hard-metric verification step

# ----- durable runs (preemption-safe checkpoints + BATCH journal)
snapshot_autosave_dt = 0.0        # [sim s] between on-disk autosnapshots
                                  # of the newest ring entry (0 = off)
snapshot_autosave_path = ""       # "" -> <log_path>/autosave.snap
preempt_snapshot_dir = ""         # "" -> log_path; SIGTERM / FAULT
                                  # PREEMPT final checkpoints land here
batch_journal_fsync = True        # fsync each BATCH journal record (WAL
                                  # durability vs append latency)

# ----- broker HA (network/ha.py; docs/FAULT_TOLERANCE.md §broker HA).
# A warm-standby server tails the live journal and takes over when the
# leader dies: leadership is a lease (journal record + atomic lease
# file) with a monotonically-bumped epoch; every record an HA leader
# appends carries its writer epoch so replay fences a deposed leader's
# late appends off as audit-only.
ha_standby = False                # start this server as a warm standby
                                  # (tail the journal, serve nothing
                                  # until the lease is acquired)
ha_lease_ttl = 10.0               # [wall s] leader silence before the
                                  # standby may acquire the lease
ha_poll_dt = 1.0                  # [wall s] lease renewal (leader) /
                                  # lease+journal polling (standby)
ha_fence_strict = True            # replay drops a deposed leader's
                                  # stale-epoch completions from the
                                  # queue math (False surfaces them as
                                  # fenced but trusts them anyway)

# ----- observability (docs/OBSERVABILITY.md; bluesky_tpu/obs/)
trace_enabled = False             # flight recorder on at startup (the
                                  # TRACE stack command toggles at
                                  # runtime; PROFILE TRACE is a synonym)
trace_ring_size = 4096            # bounded event ring per process —
                                  # older spans fall off, dumps stay
                                  # incident-sized
trace_dir = ""                    # TRACE DUMP / auto-dump target dir
                                  # ("" -> log_path)
trace_autodump = True             # dump the ring on guard/mesh trips
                                  # (throttled to 1/s) so the spans
                                  # leading up to an incident survive it
metrics_export_path = ""          # Prometheus text-format dump file
                                  # ("" = off); rewritten atomically at
                                  # most every metrics_export_dt wall-s.
                                  # Set per process (sim and server
                                  # processes each export their own).
metrics_export_dt = 10.0          # [wall s] min interval between
                                  # metrics-export rewrites
scanstats = False                 # in-scan telemetry: fold per-step
                                  # device-side stats (conflict/LoS
                                  # histograms, clamp saturation, min
                                  # separation, stripe occupancy)
                                  # through the chunk scan carry and
                                  # drain them at each chunk edge.
                                  # SCANSTATS stack command toggles at
                                  # runtime; off traces identical HLO.

# ----- device observability + perf sentinel (obs/devprof.py)
devprof_compile_telemetry = True  # per-compile trace/lower/backend
                                  # duration histograms + cache hit/miss
                                  # counters keyed to the CHUNKSTEPS
                                  # ladder (host-side bookkeeping only)
devprof_mem_dt = 0.0              # [wall s] min interval between
                                  # live-bytes/peak watermark samples at
                                  # chunk edges (0 = off; sampling walks
                                  # jax.live_arrays(), so keep throttled)
devprof_donation_check = False    # after a donating dispatch, count
                                  # input buffers XLA failed to reuse
                                  # (forces a host sync — debug only)
perf_slo_factor = 0.0             # serving SLO watch: journal a
                                  # perf_regression audit record when a
                                  # worker's FF rate drops below
                                  # factor * fleet median (0 = off;
                                  # sensible values sit BELOW the
                                  # hedge_rate_factor so hedging fires
                                  # first and the journal explains why)
# ----- self-healing serving (network/mitigate.py; MITIGATE stack
# command; docs/FAULT_TOLERANCE.md §mitigation).  The mitigation engine
# maps sentinel signals (SLO perf_regression, straggler stall, degraded
# mesh epochs, admission-queue pressure, memory watermarks) to the
# actuators the fabric already has.  Every action passes a per-action
# token-bucket rate limit, exponential per-target backoff and a global
# budget; decisions are journaled as audit-only ``mitigation`` records.
# With mitigate_enabled off the engine is inert: journal and HEALTH
# output are bit-identical to a build without it.
mitigate_enabled = False          # closed-loop mitigation on the server
mitigate_budget = 64              # lifetime cap on degrading actions a
                                  # server may take (0 = unbounded);
                                  # restores (unshed/unrepack) are free
mitigate_rate = 4                 # token-bucket capacity per action ...
mitigate_rate_window = 60.0       # ... refilled over this window [s]
mitigate_backoff_base = 5.0       # [s] first per-(action,target) delay
mitigate_backoff_cap = 300.0      # [s] exponential-backoff ceiling
mitigate_shed_hi = 0.8            # shed load (tighten batch_queue_max)
                                  # when queue depth rises past this
                                  # fraction of the admission limit ...
mitigate_shed_lo = 0.3            # ... and restore it only once depth
                                  # falls below this fraction
                                  # (hysteresis: no shed/unshed flap)
mitigate_shed_factor = 0.5        # shed tightens batch_queue_max to
                                  # factor x the configured limit
mitigate_mem_budget = 0           # [bytes] fleet live-bytes watermark
                                  # budget (devprof_live_bytes_total
                                  # from worker heartbeats; 0 = off)
mitigate_mem_hi = 0.9             # re-pack (shrink world_batch_max)
                                  # when fleet live bytes rise past
                                  # this fraction of the budget ...
mitigate_mem_lo = 0.6             # ... and restore below this fraction
mitigate_repack_factor = 0.5      # re-pack shrinks world_batch_max to
                                  # factor x the configured width
# ----- silent-data-corruption defense (ISSUE-17; network/server.py,
# obs/fingerprint.py; SDC + FINGERPRINT stack commands;
# docs/FAULT_TOLERANCE.md §SDC).  Workers fold a cheap int32
# bit-pattern fingerprint of the sim state through the compiled chunk
# scan and ship it on completion; the server compares redundant
# executions (hedge duplicates, sampled shadow audits), journals
# audit-only sdc_suspect/sdc_vote records, and — with the mitigation
# engine on — quarantines the 2-of-3 out-voted deviant worker.
fingerprint = False               # worker-side: fold the state
                                  # fingerprint through the chunk scan
                                  # carry (jit-static; off traces
                                  # identical HLO, on adds no host
                                  # syncs or collectives).  FINGERPRINT
                                  # stack command toggles at runtime.
sdc_enabled = False               # server-side: compare fingerprints
                                  # of redundant executions, journal
                                  # suspects, place 2-of-3 votes.  Off
                                  # keeps journal and HEALTH output
                                  # bit-identical to a build without
                                  # the defense (audit-only contract).
sdc_audit_rate = 0.0              # fraction of completed fast-forward
                                  # pieces shadow re-executed for a
                                  # fingerprint comparison (0 = off;
                                  # deterministic accumulator sampling,
                                  # 1.0 = audit every FF piece)
journal_warn_bytes = 67108864     # [bytes] HEALTH warns when the BATCH
                                  # journal (WAL) grows past this
                                  # (64 MiB; 0 = never warn)
bench_history_path = "BENCH_HISTORY.jsonl"
                                  # append-only bench-row history every
                                  # write_bench_json() call extends
                                  # ("" = off); scripts/bench_history.py
                                  # compares newest rows vs baseline

config_file = ""                  # the file init() loaded; the server
                                  # hands it to the workers it spawns
_overrides = {}                   # file/CLI values for late-registered keys


def init(cfgfile: str = "") -> bool:
    """Load ``key = value`` lines from cfgfile into this module."""
    if not cfgfile or not os.path.isfile(cfgfile):
        return False
    mod = sys.modules[__name__]
    mod.config_file = os.path.abspath(cfgfile)
    with open(cfgfile) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#") or "=" not in line:
                continue
            key, _, raw = line.partition("=")
            key = key.strip()
            try:
                val = ast.literal_eval(raw.strip())
            except (ValueError, SyntaxError):
                val = raw.strip()
            setattr(mod, key, val)
            _overrides[key] = val
    return True


def set_variable_defaults(**kwargs):
    """Per-module defaults registered at import time (settings.py:121-133):
    only set if neither a default nor a config override exists yet."""
    mod = sys.modules[__name__]
    for key, value in kwargs.items():
        if key in _overrides:
            setattr(mod, key, _overrides[key])
        elif not hasattr(mod, key):
            setattr(mod, key, value)
