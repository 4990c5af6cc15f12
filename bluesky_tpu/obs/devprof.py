"""Device-level observability (ISSUE-12): compile telemetry, memory
watermarks, donation accounting and on-demand device-trace windows.

The PR-11 flight recorder stops at host-side span timestamps; this
module answers the questions those spans can only hint at:

* **What compiles, when?**  A module-level ``jax.monitoring`` duration
  listener feeds per-compile trace/lower/backend histograms into every
  subscribed registry, and host-side cache accounting keyed on
  ``(program, nsteps, nmax, ndev)`` splits compile-cache misses into
  *ladder warm-up* (``nsteps`` on the sim's ``CHUNK_LADDER``) vs
  *off-ladder recompiles* (a CHUNKSTEPS value outside the ladder, a
  changed nmax bucket, a resized mesh).  ``METRICS DUMP`` / ``HEALTH``
  surface both, so a mid-run recompile storm is visible.

* **How close to memory limits?**  ``sample_memory()`` walks
  ``jax.live_arrays()`` at chunk edges (throttled by the
  ``devprof_mem_dt`` knob) into per-device live-byte gauges plus a
  self-tracked peak — on backends whose ``device.memory_stats()``
  report a peak the larger of the two wins.  An optional donation
  check counts input buffers the runner expected XLA to reuse but
  which survived the dispatch (``devprof_donation_check``; forces a
  host sync, debug only).

* **Where does a chunk's wall time go?**  ``PROFILE DEVICE [n] [dir]``
  opens a window over the next ``n`` chunk dispatches: a
  ``jax.profiler`` trace brackets them (the XLA trace lands in
  ``dir``).  The windowed chunks are dispatched and retired exactly
  as any other (no fence): while the window is open the flight
  recorder records every span, ring on or off, each also as a
  ``bs/<name>`` ``TraceAnnotation`` in the profiler's own file, and
  when it closes the window's spans and each chunk's host stamps
  (dispatch start, enqueue, dispatch return, ``device_wait`` end) go
  to ``<dir>_spans.json`` beside the profiler's directory — what a
  reader needs to put the host spans on the device trace's clock.
  Each windowed chunk is also one ``devprof_chunk`` complete event:
  *compute* (dispatch return → ``device_wait`` end), *halo* (the
  ``sort_refresh`` span) and *edge* (``chunk_edge`` self time).  Time
  inside ``start_trace``/``stop_trace`` is the ``profile_start`` /
  ``profile_stop`` spans and is kept out of every wall-time
  histogram (``program_time``).

Contract (docs/OBSERVABILITY.md): with every feature off, the hooks
are attribute checks only — zero device ops, bit-identical stepped
state, covered by the obs_smoke <2% overhead gate.
"""
import glob
import json
import os
import threading
import time
import weakref

# jax.monitoring duration events -> histogram series.  Durations arrive
# in seconds; the registry ladders are ms.
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "devprof_compile_trace_ms",
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        "devprof_compile_lower_ms",
    "/jax/core/compile/backend_compile_duration":
        "devprof_compile_backend_ms",
}

# jax.monitoring plain events of the persistent compilation cache ->
# counters.  backend_compile_duration fires for a program loaded from
# the cache too (it brackets the lookup), so these two tell a warm
# start from a cold one.
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "devprof_persistent_cache_hits",
    "/jax/compilation_cache/cache_misses":
        "devprof_persistent_cache_misses",
}

# Byte-scale bucket ladder for anything we might histogram in bytes —
# the gauges don't need it, but compile durations can hit many seconds.
COMPILE_MS_BUCKETS = (1.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
                      1000.0, 2500.0, 5000.0, 10000.0, 30000.0, 60000.0)

_SUBSCRIBERS = weakref.WeakSet()     # registries fed by the listener
_LISTENER_LOCK = threading.Lock()
_LISTENER_INSTALLED = False


def _on_compile_event(event, duration_secs, **kw):
    name = _COMPILE_EVENTS.get(event)
    if name is None:
        return
    ms = duration_secs * 1e3
    for reg in list(_SUBSCRIBERS):
        reg.histogram(name, buckets=COMPILE_MS_BUCKETS).observe(ms)
        if event.endswith("backend_compile_duration"):
            reg.counter("devprof_backend_compiles").inc()


def _on_cache_event(event, **kw):
    name = _CACHE_EVENTS.get(event)
    if name is None:
        return
    for reg in list(_SUBSCRIBERS):
        reg.counter(name).inc()


def install_compile_listener(registry):
    """Subscribe ``registry`` to the process-wide jax.monitoring compile
    events.  The listeners themselves are registered once per process
    (JAX has no unregister API); subscription is a WeakSet so dead sims
    drop out on their own."""
    global _LISTENER_INSTALLED
    _SUBSCRIBERS.add(registry)
    if _LISTENER_INSTALLED:
        return
    with _LISTENER_LOCK:
        if _LISTENER_INSTALLED:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_compile_event)
        monitoring.register_event_listener(_on_cache_event)
        _LISTENER_INSTALLED = True


def device_info():
    """The devices this process computes on, as JAX reports them — what
    a worker puts in its REGISTER payload and every measurement prints
    beside its numbers — and, where this process was given one, its
    device slot (``bluesky_tpu.device_slot``; ``count`` stays JAX's)."""
    import jax
    from .. import device_slot
    devs = jax.devices()
    info = {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "count": len(devs)}
    slot = device_slot()
    if slot is not None:
        info["slot"] = slot
    return info


def require_slot_device():
    """A worker that was given a device slot has that one chip and no
    other device: where JAX found something else (no chip behind the
    slot, the CPU it fell back to, more chips than one) the worker ends
    here, at start-up, naming the slot and what JAX found.  On a CPU
    asked for by name the slot restricts nothing and nothing is held."""
    import jax
    from .. import cpu_by_name, device_slot
    slot = device_slot()
    if slot is None or cpu_by_name(jax.config.jax_platforms):
        return
    try:
        devs = jax.devices()
        found = f"{len(devs)} x {devs[0].platform} ({devs[0].device_kind})"
        good = len(devs) == 1 and devs[0].platform != "cpu"
    except RuntimeError as e:
        found, good = f"no device ({e})", False
    if not good:
        raise SystemExit(
            f"bluesky_tpu worker: device slot {slot} names one chip of "
            f"this host and JAX found {found}: not carrying on on the "
            "CPU or on another worker's chip")


# The annotation a window writes right after the profiler starts, with
# the host's stamp taken inside it.
CLOCK_MARK = "bs/clock"


class DevProf:
    """Per-sim device observability.  Always present on a Simulation
    (``sim.devprof``); every hook early-outs on plain attribute checks
    when its feature is off, so the disabled path adds no device ops.
    """

    def __init__(self, obs, recorder, ladder=()):
        self.obs = obs
        self.recorder = recorder
        self.ladder = tuple(int(x) for x in ladder)
        self._seen = set()           # (program, nsteps, nmax, ndev)
        self._peaks = {}             # device id -> peak live bytes seen
        self._last_mem = -1e18       # monotonic stamp of last sample
        self._window = None          # active profile-window dict
        self._window_req = None      # (n_chunks, logdir) pending
        self.windows = []            # completed-window records
        self.profiler_s = 0.0        # seconds spent starting/stopping
        #                              the profiler, ever
        from .. import settings
        if bool(getattr(settings, "devprof_compile_telemetry", True)):
            install_compile_listener(obs)
        obs.counter("devprof_cache_hits",
                    help="chunk dispatches whose (program, nsteps, "
                         "nmax, ndev) key was already compiled")
        obs.counter("devprof_cache_misses_ladder",
                    help="first-seen dispatch keys with nsteps on the "
                         "chunk ladder (expected warm-up compiles)")
        obs.counter("devprof_cache_misses_offladder",
                    help="first-seen dispatch keys OFF the chunk "
                         "ladder (accidental/mid-run recompiles)")

    # ------------------------------------------------ compile telemetry
    def note_dispatch(self, program, nsteps, nmax, ndev):
        """Host-side compile-cache accounting for one chunk dispatch.
        jit caches on (program identity, static args, input avals); the
        key below is the sim-level projection of that, so a first-seen
        key == one real compile.  A key is counted as a miss exactly
        once (set semantics), which is what the acceptance test pins."""
        from .. import settings
        if not bool(getattr(settings, "devprof_compile_telemetry", True)):
            return
        key = (program, int(nsteps), int(nmax), int(ndev))
        if key in self._seen:
            self.obs.get("devprof_cache_hits").inc()
            return
        self._seen.add(key)
        if int(nsteps) in self.ladder:
            self.obs.get("devprof_cache_misses_ladder").inc()
        else:
            self.obs.get("devprof_cache_misses_offladder").inc()
            self.recorder.instant("devprof_recompile", cat="devprof",
                                  program=program, nsteps=int(nsteps),
                                  nmax=int(nmax), ndev=int(ndev))

    def compile_summary(self):
        """One-line HEALTH/METRICS summary of the cache accounting."""
        g = lambda n: int(getattr(self.obs.get(n), "value", 0) or 0)
        bc = self.obs.get("devprof_backend_compiles")
        parts = [f"ladder warm-up {g('devprof_cache_misses_ladder')}",
                 f"off-ladder {g('devprof_cache_misses_offladder')}",
                 f"hits {g('devprof_cache_hits')}"]
        if bc is not None:
            parts.append(f"backend compiles {int(bc.value)}")
        return ", ".join(parts)

    # ------------------------------------------------ memory watermarks
    def sample_memory(self, now=None, force=False):
        """Per-device live-bytes + peak gauges from ``jax.live_arrays``
        (throttled by the ``devprof_mem_dt`` knob; 0 = off).  Returns
        the per-device live-byte dict, or None when skipped."""
        from .. import settings
        dt = float(getattr(settings, "devprof_mem_dt", 0.0))
        if dt <= 0.0 and not force:
            return None
        now = time.monotonic() if now is None else now
        if not force and now - self._last_mem < dt:
            return None
        self._last_mem = now
        import jax
        per = {}
        for arr in jax.live_arrays():
            try:
                for sh in arr.addressable_shards:
                    did = sh.device.id
                    per[did] = per.get(did, 0) + int(sh.data.nbytes)
            except Exception:
                devs = list(getattr(arr, "devices", lambda: [])())
                if not devs:
                    continue
                share = int(arr.nbytes) // len(devs)
                for d in devs:
                    per[d.id] = per.get(d.id, 0) + share
        total = 0
        for did, nbytes in sorted(per.items()):
            total += nbytes
            peak = max(self._peaks.get(did, 0), nbytes)
            # A backend that reports real allocator stats knows the true
            # peak (transients between our edge samples); trust it when
            # larger.  CPU reports None — the self-tracked peak stands.
            try:
                stats = jax.devices()[did].memory_stats()
                if stats and stats.get("peak_bytes_in_use"):
                    peak = max(peak, int(stats["peak_bytes_in_use"]))
            except Exception:
                pass
            self._peaks[did] = peak
            self.obs.gauge(f"devprof_live_bytes_dev{did}",
                           help="live device bytes at last chunk-edge "
                                "sample").set(nbytes)
            self.obs.gauge(f"devprof_peak_bytes_dev{did}",
                           help="peak live device bytes seen").set(peak)
        self.obs.gauge("devprof_live_bytes_total",
                       help="live device bytes, all devices").set(total)
        return per

    def watermarks(self):
        """{device id: (live, peak)} from the gauges (last sample)."""
        out = {}
        for did, peak in sorted(self._peaks.items()):
            g = self.obs.get(f"devprof_live_bytes_dev{did}")
            out[did] = (int(g.value) if g else 0, int(peak))
        return out

    def check_donation(self, state_in, out=None):
        """Count input buffers a donating dispatch left alive (XLA
        declined the donation — usually a layout/alias mismatch).
        Only meaningful once the dispatch has been consumed, so it
        blocks on ``out`` first: the debug knob
        ``devprof_donation_check`` buys that host sync, nothing else
        does."""
        from .. import settings
        if not bool(getattr(settings, "devprof_donation_check", False)):
            return 0
        import jax
        if out is not None:
            jax.block_until_ready(out)
        missed = 0
        for leaf in jax.tree_util.tree_leaves(state_in):
            if hasattr(leaf, "is_deleted") and not leaf.is_deleted():
                missed += 1
        if missed:
            self.obs.counter(
                "devprof_donation_missed",
                help="donated input buffers XLA re-allocated instead "
                     "of reusing").inc(missed)
            self.recorder.instant("devprof_donation_missed",
                                  cat="devprof", buffers=missed)
        return missed

    # ------------------------------------------------- profile windows
    @property
    def window_active(self):
        return self._window is not None

    def request_window(self, n_chunks=1, logdir=None):
        """Arm a device-trace window over the next ``n_chunks`` chunk
        dispatches (the PROFILE DEVICE command).  Returns the resolved
        trace dir."""
        from .. import settings
        if not logdir:
            base = str(getattr(settings, "trace_dir", "") or "") \
                or str(getattr(settings, "log_path", "output"))
            logdir = os.path.join(base, "devprof")
        self._window_req = (max(int(n_chunks), 1), logdir)
        # spans count from here, so that the dispatch that starts the
        # profiler is one of them
        self.recorder.open_window()
        return logdir

    def program_time(self, t=None):
        """``perf_counter`` less the time ever spent inside the
        profiler's start and stop: differences of this clock are what
        the wall-time histograms observe, so a traced run's registry
        reads the program and not the profiler."""
        return (time.perf_counter() if t is None else t) \
            - self.profiler_s

    def begin_chunk(self, seq):
        """Dispatch-side hook: start the armed window (if any) and
        report whether this chunk is inside one.  Admission is capped
        at ``n`` — the pipeline dispatches chunk k+1 before chunk k's
        edge retires, so without the cap an extra chunk would slip in
        while the last windowed edges drain."""
        if self._window_req is not None and self._window is None:
            n, logdir = self._window_req
            self._window_req = None
            rec = self.recorder
            t0 = time.perf_counter()
            try:
                import jax
                os.makedirs(logdir, exist_ok=True)
                with rec.span("profile_start", cat="devprof",
                              dir=logdir):
                    jax.profiler.start_trace(logdir)
                rec.annotation = jax.profiler.TraceAnnotation
                # one annotation to set the two clocks by: its host
                # stamp here, its place in the profiler's file later
                with rec.annotation(CLOCK_MARK):
                    clock_us = rec.wall_us()
            except Exception as e:
                rec.close_window()
                rec.instant("device_profile_failed",
                            cat="devprof", error=str(e)[:200])
                return False
            finally:
                self.profiler_s += time.perf_counter() - t0
            self._window = {"n": n, "left": n, "admitted": 0,
                            "dir": logdir, "seq0": seq, "t0": t0,
                            "clock_us": clock_us, "chunks": {}}
        w = self._window
        if w is None or w["admitted"] >= w["n"]:
            return False
        w["admitted"] += 1
        return True

    def note_chunk(self, seq, chunk, t_start, t_enqueue, t_return,
                   halo_ms):
        """The dispatch side of a windowed chunk, as ``perf_counter``
        stamps: ``chunk_dispatch`` start, just before the runner call,
        and its return.  ``note_edge`` completes it."""
        w = self._window
        if w is None:
            return
        us = self.recorder.wall_us
        w["chunks"][seq] = {"seq": seq, "chunk": chunk,
                            "dispatch_start_us": us(t_start),
                            "enqueue_us": us(t_enqueue),
                            "dispatch_end_us": us(t_return),
                            "halo_ms": round(float(halo_ms), 3)}

    def note_edge(self, seq, t_wait_end, edge_ms):
        """Edge-retire hook: completes one windowed chunk's
        attribution.  The window itself is closed by ``end_window``,
        which the sim calls once the ``chunk_edge`` span is shut."""
        w = self._window
        if w is None:
            return
        c = w["chunks"].get(seq)
        if c is None:
            return
        rec = self.recorder
        c["wait_end_us"] = rec.wall_us(t_wait_end)
        c["compute_ms"] = round(
            (c["wait_end_us"] - c["dispatch_end_us"]) * 1e-3, 3)
        c["edge_ms"] = round(float(edge_ms), 3)
        if rec.active:
            rec.complete("devprof_chunk", c["dispatch_end_us"],
                         c["wait_end_us"] - c["dispatch_end_us"]
                         + max(edge_ms, 0.001) * 1e3, cat="devprof",
                         seq=seq, chunk=c["chunk"],
                         compute_ms=c["compute_ms"],
                         halo_ms=c["halo_ms"], edge_ms=c["edge_ms"])
        w["left"] -= 1

    def end_window(self, force=False):
        """Stop the profiler once the n-th windowed edge has retired
        (or now, with ``force``), and write the window's spans and its
        chunks' host stamps to ``<dir>_spans.json``."""
        w = self._window
        if w is None or (w["left"] > 0 and not force):
            return None
        self._window = None
        rec = self.recorder
        t0 = time.perf_counter()
        with rec.span("profile_stop", cat="devprof", dir=w["dir"]):
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception as e:
                rec.instant("device_profile_failed",
                            cat="devprof", error=str(e)[:200])
        spans = rec.close_window()
        chunks = [w["chunks"][k] for k in sorted(w["chunks"])]
        path = w["dir"].rstrip("/\\") + "_spans.json"
        try:
            with open(path, "w") as f:
                json.dump({"dir": w["dir"], "pid": os.getpid(),
                           "clock": "wall-anchored perf_counter [us] "
                                    "(obs/trace.py)",
                           "profiler_zero_us": self._profiler_zero_us(
                               w["dir"], w["clock_us"]),
                           "n_chunks": w["n"], "seq0": w["seq0"],
                           "chunks": chunks, "spans": spans}, f)
        except OSError as e:
            rec.instant("device_profile_failed", cat="devprof",
                        error=str(e)[:200])
            path = None
        t1 = time.perf_counter()
        self.profiler_s += t1 - t0
        rec.complete("device_profile", rec.wall_us(w["t0"]),
                     (t1 - w["t0"]) * 1e6, cat="devprof",
                     dir=w["dir"], n_chunks=w["n"], seq0=w["seq0"])
        record = {"dir": w["dir"], "n_chunks": w["n"],
                  "seq0": w["seq0"], "spans_file": path,
                  "wall_s": round(t1 - w["t0"], 4),
                  "chunks": w["chunks"]}
        self.windows.append(record)
        return record

    def _profiler_zero_us(self, logdir, clock_us):
        """The host clock (wall-anchored us, obs/trace.py) at the zero
        of the profiler's clock, which counts from the profile's start:
        the host stamp taken inside the ``CLOCK_MARK`` annotation less
        that annotation's start in the file the profiler just wrote.
        Exact to the annotation's own length (microseconds); None
        where the file or the mark cannot be read."""
        try:
            from jax.profiler import ProfileData
            path = sorted(glob.glob(os.path.join(
                logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
            for plane in ProfileData.from_file(path).planes:
                if not plane.name.startswith("/host:"):
                    continue
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name == CLOCK_MARK:
                            return clock_us - ev.start_ns * 1e-3
        except Exception as e:     # a window never takes the run down
            self.recorder.instant("device_profile_failed",
                                  cat="devprof", error=str(e)[:200])
        return None

    def abort_window(self):
        """Close a half-open window (drain/shutdown paths)."""
        self.end_window(force=True)
        if self._window_req is not None:
            self._window_req = None
            self.recorder.close_window()
