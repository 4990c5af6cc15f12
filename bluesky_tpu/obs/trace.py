"""Flight recorder: a bounded ring of typed span events, dumped as
Chrome/Perfetto trace-event JSON.

Design points (docs/OBSERVABILITY.md has the user guide):

* **Per-process singleton.**  One ``Recorder`` per process covers the
  sim thread, the node event loop and (in a broker process) the server
  thread — ``pid`` separates processes on the merged timeline, ``tid``
  separates threads inside one.

* **Off = free.**  ``span()`` on a disabled recorder returns a shared
  no-op context manager before touching any argument-dependent work,
  and no instrumentation site adds device ops — the stepped state is
  bit-identical with the recorder off (pinned by tests/test_obs.py).

* **Wall-anchored timestamps.**  Events are stamped with
  ``perf_counter`` (monotonic, ns-resolution) shifted by a per-process
  wall anchor captured at import, so dumps from different processes
  land on ONE timeline when ``scripts/trace_report.py`` merges them
  (cross-process skew = NTP-level, fine for ms-scale spans).

* **Typed spans + correlation tags.**  ``SPAN_TYPES`` names the
  vocabulary; tags carry the same correlation ids the BATCH journal
  uses — ``piece`` (scenario name), ``world`` (index in a pack),
  ``seq`` (host-side chunk sequence number), ``epoch`` (mesh epoch) —
  so one piece's sim, worker and server spans line up.

* **One tree per piece and per chunk.**  Every span carries an ``id``
  and the ``parent`` that was open on its thread when it started, and
  inherits its parent's ``piece`` and ``seq`` tags; a span's self time
  is its duration less what its children cover
  (``scripts/trace_report.py`` prints it).

* **One clock with the device trace.**  While a ``PROFILE DEVICE``
  window is open (``open_window``) every span is recorded whether or
  not the ring is on, and is also a ``jax.profiler.TraceAnnotation``
  named ``bs/<name>`` in the profiler's own file; ``close_window``
  hands the window's spans back to ``obs/devprof.py``, which writes
  them beside the profiler's directory.

* **Auto-dump.**  Guard/mesh trips dump the ring (throttled) so the
  events *leading up to* an incident survive it.

* **Timed scopes: the parent stack, always on.**  ``Timed`` is what a
  site opens: two reads of the owner's clock and one histogram
  observation whether or not anything records, the span only while the
  recorder is active; each scope books its time with the scope open
  around it on its thread, by name, so a scope knows its own time when
  it closes (``_Scope.own_ms``) without an event, an id or a tag.
"""
import itertools
import json
import os
import threading
import time
from collections import deque

# The span vocabulary.  Unknown names are not rejected (plugins may
# add their own), but everything the core emits is listed here and in
# docs/OBSERVABILITY.md.  Instants are not typed; beside the incident
# ones (guard_trip, mesh_lost, resharded, chunk_voided ...) the worker
# emits ``piece_slow``: a piece over twice the running median, its
# parts as tags.
SPAN_TYPES = ("piece", "piece_reset", "pack_build", "stack_run",
              "make_state", "state_write", "plugin_update",
              "chunk_dispatch",
              "sort_refresh", "mesh_check", "chunk_edge", "device_wait",
              "acdata_frame", "node_idle", "node_poll",
              "profile_start", "profile_stop",
              "snapshot_capture", "piece_turn", "journal_append",
              "worker_spawn",
              "demux", "pack_fill", "opt_step", "device_profile",
              "devprof_chunk")

# Tags a span takes over from its parent: what one piece's and one
# chunk's spans share.
INHERITED_TAGS = ("piece", "seq")

# Wall anchor: perf_counter() + _EPOCH == time.time() at import, so
# every process's event clocks share one (NTP-aligned) origin.
_EPOCH = time.time() - time.perf_counter()


def _now_us():
    return (time.perf_counter() + _EPOCH) * 1e6


class _NullSpan:
    """Shared no-op context manager for the disabled path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def tag(self, **tags):
        pass

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()
_SPAN_IDS = itertools.count(1)


class _Span:
    __slots__ = ("rec", "name", "cat", "tags", "t0", "id", "parent",
                 "_ann")

    def __init__(self, rec, name, cat, tags):
        self.rec = rec
        self.name = name
        self.cat = cat
        self.tags = tags
        self._ann = None

    def __enter__(self):
        rec = self.rec
        stack = rec._stack()
        self.id = next(_SPAN_IDS)
        par = stack[-1] if stack else None
        self.parent = par.id if par is not None else None
        if par is not None:
            for k in INHERITED_TAGS:
                if k in par.tags and k not in self.tags:
                    self.tags[k] = par.tags[k]
        stack.append(self)
        if rec.annotation is not None:
            self._ann = rec.annotation(
                "bs/" + self.name,
                **{k: v if isinstance(v, (int, float)) else str(v)
                   for k, v in self.tags.items() if v is not None})
            self._ann.__enter__()
        self.t0 = _now_us()
        return self

    def tag(self, **tags):
        """Add tags known only once the span is under way."""
        self.tags.update(tags)

    def event(self, t1):
        return {"name": self.name, "cat": self.cat, "ph": "X",
                "ts": self.t0, "dur": t1 - self.t0,
                "pid": os.getpid(), "tid": threading.get_ident(),
                "id": self.id, "parent": self.parent,
                "args": self.tags}

    def __exit__(self, *exc):
        t1 = _now_us()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = self.rec._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:          # begin()/end() pairs may interleave
            stack.remove(self)
        self.rec._append(self.event(t1))
        return False


class Recorder:
    """Bounded ring of trace events + Perfetto JSON dump."""

    def __init__(self, maxlen=None):
        if maxlen is None:
            from .. import settings
            maxlen = int(getattr(settings, "trace_ring_size", 4096))
        self.enabled = False
        self._ring = deque(maxlen=max(int(maxlen), 16))
        self._lock = threading.Lock()
        self._local = threading.local()   # per-thread open-span stack
        #                                   and open-scope stack (Timed)
        self._window = None          # spans of an open PROFILE DEVICE
        #                              window (obs/devprof.py)
        self.annotation = None       # jax.profiler.TraceAnnotation
        #                              while that window's profiler runs
        self._dump_n = 0
        self._last_autodump = -1e18
        self.dumps = []              # paths written this process

    # ---------------------------------------------------------- control
    def enable(self, on=True):
        self.enabled = bool(on)
        return self.enabled

    def disable(self):
        self.enabled = False

    def clear(self):
        """Forget the ring and the calling thread's open spans and
        scopes."""
        with self._lock:
            self._ring.clear()
        self._stack().clear()
        self.scopes().clear()

    def __len__(self):
        return len(self._ring)

    @property
    def maxlen(self):
        return self._ring.maxlen

    # ---------------------------------------------------------- record
    @property
    def active(self):
        """Spans are being recorded: the ring is on, or a PROFILE
        DEVICE window is open."""
        return self.enabled or self._window is not None

    def _stack(self):
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def scopes(self):
        """The calling thread's open ``Timed`` scopes, outermost first:
        kept here because the recorder is what every registry's scopes
        share in a process (a world sim's edge books with the worker's
        ``piece``)."""
        try:
            return self._local.scopes
        except AttributeError:
            self._local.scopes = []
            return self._local.scopes

    def _append(self, ev):
        with self._lock:
            if self.enabled:
                self._ring.append(ev)
            if self._window is not None:
                self._window.append(ev)

    def span(self, name, cat="sim", **tags):
        """Duration event context manager; no-op when disabled."""
        if not self.enabled and self._window is None:
            return _NULL_SPAN
        return _Span(self, name, cat, tags)

    def begin(self, name, cat="sim", **tags):
        """Open a span that outlives the calling function (a piece, an
        idle stretch of the node loop); close it with ``end``."""
        return self.span(name, cat, **tags).__enter__()

    @staticmethod
    def end(span, **tags):
        """Close a ``begin`` span; ``tags`` known only now are added."""
        if span is not None:
            span.tag(**tags)
            span.__exit__(None, None, None)

    def open_window(self):
        """Record every span from now until ``close_window``, ring on or
        off — and, while ``annotation`` is set (the profiler runs), each
        also as ``annotation("bs/<name>", **tags)``."""
        with self._lock:
            # bounded like the ring: a window armed on a sim that never
            # dispatches again must not grow for ever
            self._window = deque(maxlen=65536)

    def close_window(self):
        """End the window: its events, plus the calling thread's still
        open spans cut at this instant (``"open": True``)."""
        self.annotation = None
        with self._lock:
            events, self._window = list(self._window or ()), None
        now = _now_us()
        return events + [dict(sp.event(now), open=True)
                         for sp in self._stack()]

    def instant(self, name, cat="sim", **tags):
        """Instant event (guard trip, mesh_lost, hedge fired...)."""
        if not self.enabled:
            return
        self._append({"name": name, "cat": cat, "ph": "i",
                      "ts": _now_us(), "s": "p",
                      "pid": os.getpid(),
                      "tid": threading.get_ident(), "args": tags})

    def complete(self, name, t0_us, dur_us, cat="sim", **tags):
        """Record an already-timed duration (for call sites that keep
        their own perf_counter stamps, e.g. the chunk-latency path)."""
        if not self.enabled and self._window is None:
            return
        self._append({"name": name, "cat": cat, "ph": "X",
                      "ts": t0_us, "dur": dur_us, "pid": os.getpid(),
                      "tid": threading.get_ident(), "args": tags})

    @staticmethod
    def wall_us(perf_s=None):
        """Wall-anchored µs for a perf_counter() stamp (default: now)."""
        if perf_s is None:
            return _now_us()
        return (perf_s + _EPOCH) * 1e6

    # ------------------------------------------------------------- dump
    def dump(self, path=None, reason="manual", proc="sim"):
        """Write the ring as Chrome trace-event JSON.  Returns the path
        (atomic tmp+replace write), or None when the ring is empty.
        The ring is NOT cleared: a later dump extends the story."""
        with self._lock:
            events = list(self._ring)
        if not events:
            return None
        if path is None:
            from .. import settings
            d = str(getattr(settings, "trace_dir", "") or "") \
                or str(getattr(settings, "log_path", "output"))
            os.makedirs(d, exist_ok=True)
            self._dump_n += 1
            path = os.path.join(
                d, f"trace-{proc}-{os.getpid()}-{self._dump_n:03d}"
                   f"-{reason}.json")
        else:
            pd = os.path.dirname(path)
            if pd:
                os.makedirs(pd, exist_ok=True)
        doc = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": {"proc": proc, "pid": os.getpid(),
                             "reason": reason,
                             "ring": [len(events), self.maxlen]}}
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        self.dumps.append(path)
        return path

    def auto_dump(self, reason, proc="sim"):
        """Throttled incident dump (guard/mesh trips): at most one per
        second so a trip storm can't fill the disk; honours the
        ``trace_autodump`` knob."""
        if not self.enabled:
            return None
        from .. import settings
        if not bool(getattr(settings, "trace_autodump", True)):
            return None
        now = time.monotonic()
        if now - self._last_autodump < 1.0:
            return None
        self._last_autodump = now
        try:
            return self.dump(reason=reason, proc=proc)
        except OSError:
            return None          # a bad trace dir never kills the run


class _Scope:
    """One timed stretch of a thread (``Timed``).  After it closes:
    ``ms`` its length, ``parts`` the milliseconds of the scopes that
    closed directly beneath it, by name, and ``own_ms`` the rest.
    ``span`` is the recorder's span while one is recorded, else None."""
    __slots__ = ("owner", "name", "hist", "cat", "tags", "span", "c0",
                 "ms", "parts")

    def __init__(self, owner, name, hist, cat, tags):
        self.owner = owner
        self.name = name
        self.hist = hist
        self.cat = cat
        self.tags = tags
        self.span = None
        self.ms = None
        self.parts = {}

    def __enter__(self):
        owner = self.owner
        rec = owner.recorder
        self.c0 = owner.clock()
        rec.scopes().append(self)
        if self.name is not None and rec.active:
            self.span = _Span(rec, self.name, self.cat,
                              self.tags).__enter__()
        return self

    def tag(self, **tags):
        """Tags for the span, if one is recorded; dropped otherwise."""
        if self.span is not None:
            self.span.tag(**tags)

    @property
    def own_ms(self):
        return self.ms - sum(self.parts.values())

    def __exit__(self, *exc):
        if self.span is not None:
            self.span.__exit__(*exc)
        owner = self.owner
        self.ms = ms = (owner.clock() - self.c0) * 1e3
        stack = owner.recorder.scopes()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:          # begin()/end() pairs may interleave
            stack.remove(self)
        if stack:
            parts = stack[-1].parts
            key = self.name or self.hist
            parts[key] = parts.get(key, 0.0) + ms
        if self.hist is not None:
            obs = owner.obs
            (obs.get(self.hist) or obs.histogram(self.hist)).observe(ms)
        return False


class Timed:
    """The timed scope every instrumented site opens:
    ``with timed("stack_run", "sim_stack_ms", n=3) as sc:``.

    Always: ``clock`` is read once on entry and once on exit, the
    difference is observed in histogram ``hist`` of ``registry`` (if
    one is named) and booked, under ``name``, with the scope that is
    open around this one on the same thread.  Only while the recorder
    is active: the scope is also the span ``name`` with ``tags``
    (``sc.span``; ``sc.tag()`` adds to it and is a no-op otherwise).  A
    ``name`` of None times a stretch that has a series and no span.

    One per registry (a ``Simulation`` owns one and hands it to the
    core code it instruments); the open-scope stack is the recorder's,
    so the scopes of several registries in one thread nest."""

    def __init__(self, registry, clock=time.perf_counter, recorder=None):
        self.obs = registry
        self.clock = clock
        self.recorder = recorder if recorder is not None \
            else get_recorder()

    def __call__(self, name, hist=None, cat="sim", **tags):
        return _Scope(self, name, hist, cat, tags)

    def begin(self, name, hist=None, cat="sim", **tags):
        """Open a scope that outlives the calling function (a piece, an
        idle stretch of the node loop); close it with ``end``."""
        return self(name, hist, cat, **tags).__enter__()

    @staticmethod
    def end(scope, **tags):
        """Close a ``begin`` scope (None: nothing is open); ``tags``
        known only now are added."""
        if scope is not None:
            scope.tag(**tags)
            scope.__exit__(None, None, None)


_RECORDER = None
_RECORDER_LOCK = threading.Lock()


def get_recorder():
    """The per-process recorder singleton."""
    global _RECORDER
    if _RECORDER is None:
        with _RECORDER_LOCK:
            if _RECORDER is None:
                _RECORDER = Recorder()
    return _RECORDER
