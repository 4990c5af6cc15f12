"""Host-side view of one chunk edge's packed telemetry.

The pipelined chunk loop (sim.py) dispatches chunk k+1 before running
chunk k's edge subsystems; those subsystems must therefore read chunk
k's values from somewhere other than ``traf.state`` (whose buffers were
just donated into the next dispatch).  ``ChunkEdge`` wraps the
``EdgePack`` the chunk program returned (core/step.py: one pack of
four buffers) and exposes it with two-stage laziness:

* ``bad_step`` reads ONLY the pack's two small buffers (``ints``: the
  guard word, the edge's step count, the conflict counts; ``simt``:
  the edge clock; one ``device_get`` of the two) — a poll of a few
  bytes that doubles as the chunk-completion fence (it blocks until
  the chunk that produced this edge has finished, bounding the
  pipeline to one chunk in flight).
* Any field access triggers ONE ``jax.device_get`` of the four,
  unpacked on the host into an ``EdgeTelemetry`` of row views and
  cached — so an edge nobody samples (no metrics due, no GUI attached)
  costs a single scalar transfer, and an edge everybody samples costs
  exactly one bulk copy instead of a dozen ``np.asarray`` pulls.

Thread note: ScreenIO may fetch an edge from the node thread while the
sim thread retires the next one; ``fetch`` is idempotent and the object
is never mutated after construction, so the race is benign.

Observability (ISSUE-11): the chunk-sequence correlation tag lives
HERE, on the host edge object, not in the device pack — the recorder's
off-path contract forbids adding device ops, and a host counter stamped
at dispatch identifies the chunk just as uniquely.  ``t_dispatch``
anchors the chunk-latency series and the chunk_edge trace span; the
bulk ``fetch`` reports its wall cost to the owning sim's
``sim_edge_pull_ms`` histogram through ``obs_sink``.
"""
import time
from typing import Optional

import jax
import numpy as np

from ..core.step import PACK_ROWS, unpack_telemetry


class ChunkEdge:
    """One retired-or-pending chunk edge: telemetry + host bookkeeping."""

    def __init__(self, telemetry, chunk: int, clock,
                 nstep_planned: Optional[int] = None,
                 seq: int = -1, obs_sink=None, stats=None,
                 fingerprint=None, sched=None, t_dispatch=None):
        self._telemetry = telemetry
        # ``(fresh, aged, the sort_refresh span)`` when the producing
        # chunk started from a fresh sparse layout: each a pair (block
        # pairs, overflow rows) of device scalars of the refresh
        # program (core/asas.refresh_sparse_counted), read at
        # retirement; ``aged`` (the outgoing layout's) is None at a
        # first refresh.  Same eager-set rule as ``stats`` below.
        self.sched = sched
        # in-scan telemetry pack (obs/scanstats.ScanStats device pytree)
        # when SimConfig.scanstats was on for the producing chunk; it
        # rides the edge object so the drain happens at retirement,
        # after the same completion fence as the guard word.  Must be
        # set HERE, not lazily — __getattr__ forwards unknown names to
        # the telemetry pack.
        self.stats = stats
        # SDC fingerprint pack (obs/fingerprint.FingerprintPack device
        # pytree) when SimConfig.fingerprint was on for the producing
        # chunk; drained into the sim's running piece chain at
        # retirement.  Same eager-set rule as ``stats``.
        self.fingerprint = fingerprint
        self.chunk = int(chunk)
        # the owning sim's clock: step count -> the host's time
        # (``Simulation.clock``)
        self._clock = clock
        # the step count the host planned this edge at (it chose the
        # chunk's length); None where it planned none (a synchronous
        # chunk), and the count is the device's
        self.nstep_planned = nstep_planned
        self._np = None
        self._scal = None
        # correlation tag: per-sim monotonic dispatch sequence number
        # (host-side by design — see module docstring)
        self.seq = int(seq)
        # the owning sim passes its program clock (perf_counter less
        # the time inside the profiler, obs/devprof.program_time)
        self.t_dispatch = time.perf_counter() if t_dispatch is None \
            else t_dispatch
        # Histogram fed by fetch() (the owning sim's registry); None
        # keeps the pre-obs behavior for standalone edges.
        self._obs_sink = obs_sink

    # ------------------------------------------------------------- fetch
    def _scalars(self):
        """The pack's small buffers ``(ints, simt)`` on the host: ONE
        ``device_get`` of the two, whose copies overlap, cached (a bulk
        ``fetch`` that came first left them here).  Blocks until the
        producing chunk completes (the pipeline's completion fence); a
        retirement reads them all, and one round trip costs less than
        several in a row."""
        if self._scal is None:
            t = self._telemetry
            self._scal = jax.device_get((t.ints, t.simt))
        return self._scal

    def _int(self, name: str) -> int:
        """One of the pack's integer scalars, by its field name."""
        return int(self._scalars()[0][PACK_ROWS.ints.index(name)])

    @property
    def nstep(self) -> int:
        """The step count at this edge: the host's where it planned one
        at dispatch (no device read), else the device's."""
        if self.nstep_planned is not None:
            return self.nstep_planned
        return self.nstep_device

    @property
    def bad_step(self) -> int:
        """First bad step index within the chunk (-1 clean): the
        deferred guard word (see ``_scalars``: the completion fence)."""
        return self._int("bad")

    def fetch(self):
        """The whole pack as an ``EdgeTelemetry`` of host NumPy row
        views — one device_get of the four, cached."""
        if self._np is None:
            t0 = time.perf_counter()
            pack = jax.device_get(self._telemetry)
            if self._obs_sink is not None:
                self._obs_sink((time.perf_counter() - t0) * 1e3)
            self._scal = (pack.ints, pack.simt)
            self._np = unpack_telemetry(pack)
        return self._np

    @property
    def fetched(self) -> bool:
        return self._np is not None

    # ------------------------------------------------------------ fields
    @property
    def simt(self) -> float:
        """Sim time at this edge as the host keeps it: its clock at the
        edge's step count (no device read where the count was planned
        at dispatch)."""
        return self._clock(self.nstep)

    @property
    def simt_device(self) -> float:
        """The device's own edge clock, the time it derived from its
        step count: a scalar read (does not pull the whole pack)."""
        return float(self._scalars()[1])

    @property
    def nstep_device(self) -> int:
        """The device's own step count at this edge: a scalar read,
        used to verify the count the host planned."""
        return self._int("nstep")

    @property
    def conf_pairs(self) -> int:
        """Conflict pairs alive at this edge: the pack's directional
        count halved, a scalar the chunk program already wrote."""
        return self._int("nconf_cur") // 2

    def __getattr__(self, name):
        # telemetry field access (lat, lon, active, nconf_cur, ...)
        pack = self.fetch()
        try:
            return getattr(pack, name)
        except AttributeError:
            raise AttributeError(
                f"ChunkEdge has no field {name!r}") from None

    def acdata_arrays(self):
        """The ACDATA per-aircraft field dict (screenio stream), sliced
        by the live mask; one bulk fetch backs all of it."""
        pack = self.fetch()
        idx = np.flatnonzero(np.asarray(pack.active))
        data = {name: np.asarray(getattr(pack, name))[idx]
                for name in ("lat", "lon", "alt", "trk", "tas", "gs",
                             "cas", "vs", "inconf", "tcpamax", "asasn",
                             "asase")}
        return idx, data
