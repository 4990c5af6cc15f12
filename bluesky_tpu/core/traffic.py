"""Host-side Traffic facade: create/delete/lookup over the device state.

This is the replacement for the reference's ``Traffic`` singleton
(traffic.py:55-756) *minus* the physics (which lives in jitted functions in
this package).  It owns:

* the device ``SimState`` (padded arrays + active mask),
* host-only bookkeeping the device must never see: callsign and type strings,
  the id->slot map (replacing ``id2idx``'s list.index, traffic.py:485-501).

Creation semantics follow reference ``Traffic.create`` (traffic.py:192-312):
random defaults in an area, CAS-or-Mach initial speed, atmosphere init, AP /
active-waypoint / ASAS / ADS-B / performance child rows.  Deletion is a mask
flip (the reference compacts arrays, traffic.py:365-381; slot identity is
stable here, which also keeps the [N,N] pair matrices valid).

Writes are *batched*: ``write()`` queues a per-slot write on the host
(sub-state, field, slot, value, in command order; the value of a
``[N, W]`` table is one row), ``create()`` gives its aircraft their
slots and queues their rows, ``delete()`` queues the slots to clear; the
first reader of ``state`` — a command that reads the state, a dispatch,
a stream frame, a snapshot — applies everything queued as ONE compiled,
donated program (``_write_program``: the deleted slots' partner memory
purged, then the rows scattered), so a pass of the stack costs one
device program per run of writes, not one per command.
``state`` is the flush point: no reader can see a state that lacks a
queued write.  Duplicate writes are resolved on the host (last wins: a
scatter with repeated indices is not ordered on the device) and the row
count is padded to a short ladder, so a second pass of the same shape
compiles nothing.
"""
import collections
import functools
import os
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..models import perf_coeffs
from ..obs.metrics import Registry
from ..obs.trace import Timed
from ..ops import aero
from .state import SimState, make_state


def purge_tables(partners, partners_s, resopairs, sort_perm, idx):
    """The conflict memory of the slots ``idx`` (int32 ``[R]``; an entry
    of ``N`` or more is padding) cleared from the three tables that hold
    it: their own rows, and every entry of another row that names them —
    a freed slot is given to the next ``create``, which may come before
    an ASAS interval would have dropped the stale entry.  The
    sorted-space table (sparse backend) holds them at ``sort_perm[idx]``
    of the padded layout.  Traced: the write program and a plugin's own
    program that deactivates aircraft (plugins/area.py) both call it."""
    n, ns = partners.shape[0], partners_s.shape[0]
    partners = partners.at[idx, :].set(-1, mode="drop")
    partners = jnp.where(jnp.isin(partners, idx), -1, partners)
    sidx = jnp.where(idx < n, sort_perm[jnp.minimum(idx, n - 1)], ns)
    partners_s = partners_s.at[sidx, :].set(-1, mode="drop")
    partners_s = jnp.where(jnp.isin(partners_s, sidx), -1, partners_s)
    if resopairs.size:
        resopairs = resopairs.at[idx, :].set(False, mode="drop") \
            .at[:, idx].set(False, mode="drop")
    return partners, partners_s, resopairs


@functools.partial(jax.jit, donate_argnums=(0, 1), static_argnames="layout")
def _write_program(arrs, tables, sort_perm, gone, idx, vals, layout):
    """The write program.  First, where a pass deleted aircraft
    (``tables`` is not None), ``purge_tables`` over the slots ``gone``;
    then ``arrs[k][idx[k]] = vals[g][r]`` with ``(g, r) = layout[k]``.
    ``idx`` is int32 ``[F, R]``; ``vals`` holds one ``[F_g, R]`` matrix
    per dtype (``[F_g, R, W]`` for the rows of ``[N, W]`` tables), so a
    batch is a handful of transfers whatever its number of fields.
    Padding rows carry an out-of-range index and are dropped."""
    if tables is not None:
        tables = purge_tables(*tables, sort_perm, gone)
    return [a.at[idx[k]].set(vals[g][r], mode="drop")
            for k, (a, (g, r)) in enumerate(zip(arrs, layout))], tables


@jax.jit
def _gather_row(arrs, slot):
    return jnp.stack([a[slot] for a in arrs])


def _row_bucket(n, nmax):
    """Rows of a write program: powers of four, at most ``nmax`` (a
    field has no more distinct slots than that)."""
    r = 1
    while r < n:
        r *= 4
    return min(r, max(nmax, 1))


class Traffic:
    """Host facade over a padded SimState."""

    def __init__(self, nmax: int = 64, wmax: int = 32, dtype=jnp.float32,
                 openap_path: Optional[str] = None, rng_seed: int = 0,
                 area=(-1.0, 1.0, -1.0, 1.0),
                 pair_matrix: bool = True, k_partners: int = 8):
        self.nmax = nmax
        self.wmax = wmax
        self.dtype = dtype
        self.pair_matrix = pair_matrix
        self.k_partners = k_partners
        self._pending = []          # queued creation dicts
        self._writes = {}           # (sub-state, field) -> {slot: value}
        self._nwrites = 0           # writes queued since the last program
        self._gone = []             # deleted slots whose tables to purge
        # the owner's timed scope (``instrument``); a bare Traffic
        # counts in a registry of its own, which nobody reads
        self._timed = Timed(Registry())
        # the simulated time for what is stamped on creation (trails):
        # the owner gives its planned clock, which waits for no chunk
        self.simt_source = lambda: float(self._state.simt)
        self.state = make_state(nmax, wmax, dtype, rng_seed,
                                pair_matrix, k_partners)
        from .. import settings
        model = getattr(settings, "performance_model", "openap")
        if openap_path is None and model == "openap":
            # Default to the real OpenAP coefficient data when present
            # (settings.perf_path/OpenAP; reference coeff.py:7,16-19),
            # falling back to the built-in approximate tables.
            cand = os.path.join(settings.perf_path, "OpenAP")
            if os.path.isdir(os.path.join(cand, "fixwing")):
                openap_path = cand
            elif not getattr(Traffic, "_warned_builtin", False):
                Traffic._warned_builtin = True
                print(f"perf: no OpenAP coefficient data at {cand} — "
                      "using the BUILTIN approximate set (unknown types "
                      "map to 'NA'; see docs/DATA.md)")
        self.coeffdb = perf_coeffs.CoeffDB(openap_path, model=model,
                                           perf_path=settings.perf_path)
        self.area = area  # default creation area (lat0, lat1, lon0, lon1)
        self._rng = np.random.default_rng(rng_seed)
        # Host-side per-slot bookkeeping
        self.ids: List[Optional[str]] = [None] * nmax
        self.types: List[Optional[str]] = [None] * nmax
        self._id2slot = {}
        self._autoid = 0
        self._free = self._free_of = None   # queue of free slots, of ``ids``
        # counts the fleets this facade has held (RESET, a snapshot
        # restored): what was learnt of one fleet's slots is void for
        # the next
        self.epoch = 0
        # Observers notified with an old->new slot map when the SPATIAL
        # shard refresh re-buckets caller slots by latitude stripe
        # (parallel/sharding.prepare_spatial; routes/conditions/trails
        # register here).  Slots remain stable between refreshes; any
        # host subsystem caching slot indices across chunk edges in
        # spatial mode must subscribe.  (Defined before Trails below —
        # it subscribes at construction.)
        self.permute_hooks = []
        # Display trails (reference traffic.py:79 bs.traf.trails)
        from .trails import Trails
        self.trails = Trails(self)
        # Observers notified with slot indices on deletion (conditional
        # commands, AREA plugin, ... — reference cond.delac wiring) and on
        # creation flush (slot array; reference TrafficArrays.create cascade)
        self.delete_hooks = []
        self.create_hooks = []

    def apply_slot_permutation(self, newslot):
        """Re-bucket host bookkeeping after a spatial shard refresh
        moved aircraft between caller slots (``newslot[old] = new``).
        The device state was already permuted by the refresh; this
        remaps ids/types and fans out to ``permute_hooks``."""
        newslot = np.asarray(newslot)
        src = np.empty(self.nmax, dtype=np.intp)      # new -> old slot
        src[newslot] = np.arange(self.nmax, dtype=np.intp)
        self.ids = np.asarray(self.ids, dtype=object)[src].tolist()
        self.types = np.asarray(self.types, dtype=object)[src].tolist()
        # remap the live id -> slot map in O(ntraf), not O(nmax)
        self._id2slot = {i: int(newslot[s])
                         for i, s in self._id2slot.items()}
        for hook in self.permute_hooks:
            hook(newslot)

    # ----------------------------------------------------- state and queue
    @property
    def state(self) -> SimState:
        """The device state with everything queued applied: THE flush
        point.  Assigning stores the new state; what is queued after a
        read lands on top of what was assigned."""
        if self.dirty:
            self._apply_queued()
        return self._state

    @state.setter
    def state(self, value: SimState):
        self._state = value

    @property
    def dirty(self) -> bool:
        """Creations or writes are queued: the next read of ``state``
        runs a write program."""
        return bool(self._writes or self._pending or self._gone)

    def instrument(self, timed):
        """Count and time in the owner's scope (``obs/trace.py``
        ``Timed``: its registry, its clock) the write programs:
        ``sim_state_write_ms``, ``sim_state_writes``,
        ``sim_state_write_programs``; and the aircraft that enter and
        leave the host's record: ``sim_ac_created``, ``sim_ac_deleted``,
        with ``sim_delete_ms`` for what a leaving costs the host; and
        the empty state a ``reset`` builds: ``sim_make_state_ms``."""
        self._timed, registry = timed, timed.obs
        registry.histogram(
            "sim_delete_ms",
            help="one forget(): the host's record of deleted aircraft "
                 "cleared and the delete hooks run")
        registry.counter("sim_ac_created",
                         help="aircraft given a slot by create()")
        registry.counter("sim_ac_deleted",
                         help="aircraft whose slot the host took back "
                              "(delete(), or forget() after a program "
                              "deactivated them on the device)")
        registry.histogram(
            "sim_state_write_ms",
            help="one write program: rows built, cast and dispatched")
        registry.counter("sim_state_writes",
                         help="slot writes and creation fields folded "
                              "into write programs")
        registry.counter("sim_state_write_programs",
                         help="write programs dispatched")
        registry.histogram(
            "sim_make_state_ms",
            help="one reset(): the empty state built and dispatched as "
                 "one compiled program")

    def write(self, sub, field, slot, value):
        """Queue ``state.<sub>.<field>[slot] = value``; the next read of
        ``state`` applies it.  A later write to the same slot and field
        replaces an earlier one.  For a ``[N, W]`` table the value is
        the slot's whole row."""
        self._writes.setdefault((sub, field), {})[int(slot)] = value
        self._nwrites += 1

    def read_slot(self, sub, fields, slot):
        """The values of ``fields`` of one sub-state at ``slot``: one
        gather and one device-to-host transfer, as a NumPy vector."""
        node = getattr(self.state, sub)
        return np.asarray(_gather_row(
            tuple(getattr(node, f) for f in fields), np.int32(slot)))

    # ------------------------------------------------------------------ info
    @property
    def ntraf(self) -> int:
        # a batch that found the fleet full has no slots yet (it raises
        # when it is applied) and is counted as it was
        return len(self._id2slot) + sum(
            len(b["acid"]) for b in self._pending if b["slots"] is None)

    @property
    def slot_bound(self) -> int:
        """One more than the highest slot that holds an aircraft by the
        host's record, creations queued with their slots included.  No
        slot at or past it is active on the device: a creation enters
        the record before it is written, and a deletion leaves it no
        earlier than the device (``forget``)."""
        return max(self._id2slot.values(), default=-1) + 1

    def id2idx(self, acid):
        """Slot index of a callsign; -1 if unknown (traffic.py:485-501)."""
        if not isinstance(acid, str):
            return [self.id2idx(a) for a in acid]
        if acid in ('#', '*'):
            # last created
            if self._pending:
                return -2  # pending, unknown slot yet; flush first
            slots = [s for s, i in enumerate(self.ids) if i is not None]
            return slots[-1] if slots else -1
        return self._id2slot.get(acid.upper(), -1)

    # ---------------------------------------------------------------- create
    def create(self, n=1, actype="B744", acalt=None, acspd=None, dest=None,
               aclat=None, aclon=None, achdg=None, acid=None):
        """Queue creation of n aircraft (reference traffic.py:192-252)."""
        if acid is None:
            pre = chr(self._rng.integers(65, 91)) + chr(self._rng.integers(65, 91))
            acid = [f"{pre}{self._autoid + i:>05}" for i in range(n)]
            self._autoid += n
        elif isinstance(acid, str):
            if acid.upper() in self._id2slot:
                return False, acid + " already exists."
            acid = [acid.upper()]
        if isinstance(actype, str):
            actype = n * [actype]

        lat0, lat1, lon0, lon1 = self.area
        if aclat is None:
            aclat = self._rng.random(n) * (lat1 - lat0) + lat0
        if aclon is None:
            aclon = self._rng.random(n) * (lon1 - lon0) + lon0
        aclat = np.atleast_1d(np.asarray(aclat, dtype=np.float64))
        aclon = np.atleast_1d(np.asarray(aclon, dtype=np.float64))
        aclon = np.where(aclon > 180.0, aclon - 360.0, aclon)
        aclon = np.where(aclon < -180.0, aclon + 360.0, aclon)
        if achdg is None:
            achdg = self._rng.integers(1, 360, n).astype(np.float64)
        if acalt is None:
            acalt = self._rng.integers(2000, 39000, n) * aero.ft
        if acspd is None:
            acspd = self._rng.integers(250, 450, n) * aero.kts
        achdg = np.broadcast_to(np.atleast_1d(np.asarray(achdg, np.float64)), (n,))
        acalt = np.broadcast_to(np.atleast_1d(np.asarray(acalt, np.float64)), (n,))
        acspd = np.broadcast_to(np.atleast_1d(np.asarray(acspd, np.float64)), (n,))

        batch = dict(
            acid=[a.upper() for a in acid], actype=[t.upper() for t in actype],
            lat=aclat, lon=aclon, hdg=achdg, alt=acalt, spd=acspd,
            slots=None)
        self._pending.append(batch)
        if len(self._free_slots()) >= n:
            # slots are given now, so that what follows a creation in
            # the same pass can write to its aircraft; a fleet too full
            # says so where it always did, when the batch is applied
            self._take_slots(batch)
        return True, None

    def _free_slots(self):
        """The free slots as a queue: lowest first to begin with, then
        the longest free first (``forget`` appends), so a fleet that
        deletes nobody is given its slots in the order it always was.
        A slot freed a moment ago still has its leaver's neighbours
        around it in whatever is laid out by position; one that has
        waited its turn behind the other free slots has been laid out
        with them since (what that is worth to the sparse schedule, and
        with how many spare slots it holds: PERF.md section 6, PR 35).
        Rebuilt whenever ``ids`` is another list than the one it was
        built from (RESET, a snapshot restored, a spatial
        re-bucketing)."""
        if self._free_of is not self.ids:
            self._free = collections.deque(
                i for i, v in enumerate(self.ids) if v is None)
            self._free_of = self.ids
        return self._free

    def _take_slots(self, batch):
        """Give a queued batch the slots longest free and enter its
        aircraft in the host's record.  Writes queued earlier to one of
        those slots, for a field a creation fills, were meant for an
        aircraft since deleted: the creation's row replaces them."""
        free, n = self._free_slots(), len(batch["acid"])
        if len(free) < n:
            raise RuntimeError(
                f"traffic full: need {n} slots, {len(free)} free "
                f"(nmax={self.nmax}); raise nmax")
        slots = [free.popleft() for _ in range(n)]
        for s, i, t in zip(slots, batch["acid"], batch["actype"]):
            self.ids[s] = i
            self.types[s] = t
            self._id2slot[i] = s
        # set aside until the batch's rows are built and say which
        # fields a creation fills; the rest is written as queued
        batch["before"] = before = {}
        for key in list(self._writes):
            w = self._writes[key]
            old = {s: w.pop(s) for s in slots if s in w}
            if old:
                before[key] = old
            if not w:
                del self._writes[key]
        batch["slots"] = np.asarray(slots)
        self._timed.obs.counter("sim_ac_created").inc(n)

    def _sync_pair_matrix(self):
        """Hold the [N,N] ``resopairs`` matrix exactly while
        ``pair_matrix`` says the dense backend needs it (the owner flips
        the flag when the backend changes): allocate it empty — pairs
        re-detect within one CD interval — or free it."""
        asas = self.state.asas
        if self.pair_matrix != (asas.resopairs.size > 0):
            shape = (self.nmax, self.nmax) if self.pair_matrix else (0, 0)
            self.state = self.state.replace(asas=asas.replace(
                resopairs=jnp.zeros(shape, bool)))

    def flush(self):
        """Bring the device state up to date before it is stepped:
        size the pair matrix for the backend in use, on a state that,
        like every read of ``state``, has what is queued applied."""
        self._sync_pair_matrix()

    def _apply_queued(self):
        """Queued deletions, creations and writes, as one write program
        under a ``state_write`` span."""
        with self._timed("state_write", "sim_state_write_ms") as sp:
            # detached first: the hooks below read the state again
            batch, self._pending = self._pending, []
            gone, self._gone = self._gone, []
            for b in batch:
                if b["slots"] is None:
                    self._take_slots(b)       # raises: the fleet is full
            slots, created = self._creation_rows(batch) if batch \
                else (None, {})
            nfolded = self._nwrites + len(created)
            writes, self._writes, self._nwrites = self._writes, {}, 0
            cols = dict(created)
            for b in batch:
                # writes older than a creation, to a slot it took: a
                # field the creation fills is the creation's
                for key, old in b["before"].items():
                    if key not in created:
                        writes[key] = old | writes.get(key, {})
            for key, w in writes.items():
                wslots = np.fromiter(w, np.int64, len(w))
                wvals = list(w.values())
                if key in created:
                    # what is queued for a new aircraft after its
                    # creation replaces the creation's value
                    cslots, cvals = created[key]
                    keep = ~np.isin(cslots, wslots)
                    wslots = np.concatenate([cslots[keep], wslots])
                    wvals = np.concatenate(
                        [np.asarray(cvals)[keep], wvals])
                cols[key] = (wslots, wvals)
            sp.tag(n=nfolded, fields=len(cols), deletes=len(gone),
                   rows=self._scatter(cols, gone))
        obs = self._timed.obs
        obs.counter("sim_state_writes").inc(nfolded)
        obs.counter("sim_state_write_programs").inc()
        if batch:
            self.trails.create(slots, created["ac", "lat"][1],
                               created["ac", "lon"][1],
                               t=self.simt_source())
            for hook in self.create_hooks:
                hook(slots)

    def _scatter(self, cols, gone=()):
        """Run ``_write_program`` over ``cols`` (``(sub, field) ->
        (slots, values)``, slots distinct within a field) and the
        deleted slots ``gone``, and store the state it returns.  Fields
        go in sorted order and rows are padded to ``_row_bucket``, so
        the program depends on which fields a batch writes, on its
        bucket and on whether it deletes, never on its values.  Returns
        the bucket."""
        st = self._state
        keys = sorted(cols)
        nrows = _row_bucket(max([len(cols[k][0]) for k in keys]
                                + [len(gone)]), self.nmax)
        arrs, layout, groups = [], [], {}
        idx = np.empty((len(keys), nrows), np.int32)
        for k, key in enumerate(keys):
            arr = getattr(getattr(st, key[0]), key[1])
            if arr.ndim > 2:
                raise ValueError(f"{key[0]}.{key[1]} is no per-slot "
                                 f"vector or table: shape {arr.shape}")
            slots, vals = cols[key]
            idx[k, :len(slots)] = slots
            idx[k, len(slots):] = arr.shape[0]     # dropped
            dt = np.dtype(arr.dtype)
            rows = groups.setdefault((dt, arr.shape[1:]), [])
            layout.append((list(groups).index((dt, arr.shape[1:])),
                           len(rows)))
            row = np.zeros((nrows,) + arr.shape[1:], dt)
            row[:len(slots)] = np.asarray(vals).astype(dt)
            rows.append(row)
            arrs.append(arr)
        tables = sort_perm = None
        gone_idx = np.full(nrows, self.nmax, np.int32)
        if len(gone):
            asas = st.asas
            tables = (asas.partners, asas.partners_s, asas.resopairs)
            sort_perm = asas.sort_perm
            gone_idx[:len(gone)] = gone
        out, tables = _write_program(
            arrs, tables, sort_perm, gone_idx, idx,
            tuple(np.stack(rows) for rows in groups.values()),
            layout=tuple(layout))
        if tables is not None:
            st = st.replace(asas=st.asas.replace(
                partners=tables[0], partners_s=tables[1],
                resopairs=tables[2]))
        subs = {}
        for key, arr in zip(keys, out):
            subs.setdefault(key[0], {})[key[1]] = arr
        self._state = st.replace(**{
            sub: getattr(st, sub).replace(**fields)
            for sub, fields in subs.items()})
        return nrows

    def _creation_rows(self, batch):
        """The rows of the queued aircraft, built on the host, at the
        slots ``create`` gave them: ``(slots, {(sub, field): (slots,
        values)})``."""
        ids = sum((b['acid'] for b in batch), [])
        types = sum((b['actype'] for b in batch), [])
        lat = np.concatenate([b['lat'] for b in batch])
        lon = np.concatenate([b['lon'] for b in batch])
        hdg = np.concatenate([b['hdg'] for b in batch])
        alt = np.concatenate([b['alt'] for b in batch])
        spd = np.concatenate([b['spd'] for b in batch])
        n = len(ids)
        slots = np.concatenate([b['slots'] for b in batch])

        # Initial speeds: CAS-or-Mach interpretation (traffic.py:268-272)
        tas, cas, mach = _np_vcasormach(spd, alt)
        hdgrad = np.radians(hdg)
        p, rho, temp = _np_vatmos(alt)
        zeros, ones = np.zeros(n), np.ones(n)
        false, true = np.zeros(n, bool), np.ones(n, bool)
        bank25 = np.full(n, np.radians(25.0))
        rows = dict(
            ac=dict(
                active=true, lat=lat, lon=lon, alt=alt, hdg=hdg, trk=hdg,
                tas=tas, gs=tas, gsnorth=tas * np.cos(hdgrad),
                gseast=tas * np.sin(hdgrad), cas=cas, mach=mach, vs=zeros,
                p=p, rho=rho, temp=temp,
                selspd=cas, selalt=alt, selvs=zeros,
                swlnav=false, swvnav=false, abco=false, belco=true,
                apvsdef=np.full(n, 1500.0 * aero.fpm), aphi=bank25,
                ax=np.full(n, aero.kts), bank=bank25,
                coslat=np.cos(np.radians(lat))),
            # Child rows (reference create() of each TrafficArrays child)
            ap=dict(trk=hdg, tas=tas, alt=alt, vs=zeros,
                    dist2vs=np.full(n, -999.0)),
            actwp=dict(lat=np.full(n, 89.99), lon=zeros,
                       spd=np.full(n, -999.0), turndist=ones, flyby=ones,
                       next_qdr=np.full(n, -999.0), nextaltco=zeros,
                       xtoalt=zeros),
            asas=dict(trk=hdg, tas=tas, alt=alt, vs=zeros, active=false),
            adsb=dict(lat=lat, lon=lon, alt=alt, trk=hdg, tas=tas, gs=tas,
                      lastupdate=zeros),
            # Route tables: clear the slots
            route=dict(nwp=np.zeros(n, np.int32),
                       iactwp=np.full(n, -1, np.int32)),
            perf={})
        # Performance coefficients per type (perfoap.py:49-113)
        for k in range(n):
            vals = perf_coeffs.slot_values(self.coeffdb.get(types[k]))
            for name, v in vals.items():
                rows["perf"].setdefault(name, []).append(v)
        return slots, {(sub, field): (slots, v)
                       for sub, fields in rows.items()
                       for field, v in fields.items()}

    # ---------------------------------------------------------------- delete
    def delete(self, idx):
        """Deactivate slot(s); stable slot identity (cf.
        traffic.py:365-381).  Queued like every write: the host's record
        is cleared now (``forget``), the device's with the next write
        program, which also purges the slots from the partner tables
        before it writes a row, so a ``create`` later in the same pass
        may take a freed slot."""
        if np.isscalar(idx):
            idx = [int(idx)]
        idx = [int(i) for i in np.atleast_1d(np.asarray(idx))]
        if any(b["slots"] is not None and np.isin(idx, b["slots"]).any()
               for b in self._pending):
            # created and deleted in one pass: a program writes its
            # rows after its purge, so the creation goes first
            self._apply_queued()
        for i in idx:
            self.write("ac", "active", i, False)
            self.write("asas", "active", i, False)
        self._gone += idx
        self.forget(idx)
        return True

    def forget(self, idx):
        """Take slots out of the host's record (callsign, type, the
        id map) and hand them back for reuse; then the delete hooks.
        ``delete`` calls this; so does a plugin whose own device program
        deactivated the aircraft (with ``purge_tables``), once it has
        read which (plugins/area.py): a slot is given out again only
        when the host has seen it freed."""
        with self._timed(None, "sim_delete_ms"):
            free, n = self._free_slots(), 0
            for i in idx:
                if self.ids[i] is not None:
                    del self._id2slot[self.ids[i]]
                    self.ids[i] = None
                    self.types[i] = None
                    free.append(i)
                    n += 1
            for hook in self.delete_hooks:
                hook(idx)
        self._timed.obs.counter("sim_ac_deleted").inc(n)

    def reset(self):
        seed = int(self._rng.integers(0, 2**31 - 1))
        # without the pair matrix: the next flush allocates it if the
        # backend then in use needs it (a RESET returns the config to
        # its default, and the scenario's CDMETHOD line comes after)
        with self._timed("make_state", "sim_make_state_ms"):
            self.state = make_state(self.nmax, self.wmax, self.dtype, seed,
                                    False, self.k_partners)
        self.ids = [None] * self.nmax
        self.types = [None] * self.nmax
        self._id2slot = {}
        self._pending = []
        self._writes, self._nwrites = {}, 0
        self._gone = []
        self._autoid = 0
        self.epoch += 1
        self.trails.reset()

    # ------------------------------------------------------------- creconfs
    def creconfs(self, acid, actype, targetidx, dpsi, cpa, tlosh,
                 dh=None, tlosv=None, spd=None,
                 pzr_nm=5.0, pzh_ft=1000.0):
        """Create an aircraft on a synthetic conflict course with target
        (reference traffic.py:314-363)."""
        latref, lonref, altref, trkref, gsref, vsref = (
            float(v) for v in self.read_slot(
                "ac", ("lat", "lon", "alt", "trk", "gs", "vs"), targetidx))
        trkref = np.radians(trkref)
        cpa_m = cpa * aero.nm
        pzr = pzr_nm * aero.nm
        pzh = pzh_ft * aero.ft

        trk = trkref + np.radians(dpsi)
        gs = gsref if spd is None else spd
        if dh is None:
            acalt = altref
            acvs = 0.0
        else:
            acalt = altref + dh
            tlosv = tlosh if tlosv is None else tlosv
            acvs = vsref - np.sign(dh) * (abs(dh) - pzh) / tlosv

        gsn, gse = gs * np.cos(trk), gs * np.sin(trk)
        vreln = gsref * np.cos(trkref) - gsn
        vrele = gsref * np.sin(trkref) - gse
        vrel = np.sqrt(vreln * vreln + vrele * vrele)
        drelcpa = tlosh * vrel + (0 if cpa_m > pzr
                                  else np.sqrt(pzr * pzr - cpa_m * cpa_m))
        dist = np.sqrt(drelcpa * drelcpa + cpa_m * cpa_m)
        rd = drelcpa / dist
        rx = cpa_m / dist
        brn = np.degrees(np.arctan2(-rx * vreln + rd * vrele,
                                    rd * vreln + rx * vrele))
        from ..ops import geo as jgeo
        aclat, aclon = (float(x) for x in
                        jgeo.qdrpos(jnp.float64(latref) if self.dtype == jnp.float64
                                    else jnp.asarray(latref, self.dtype),
                                    jnp.asarray(lonref, self.dtype),
                                    jnp.asarray(brn, self.dtype),
                                    jnp.asarray(dist / aero.nm, self.dtype)))
        acspd = float(_np_vtas2cas(np.hypot(gsn, gse), acalt))
        achdg = float(np.degrees(np.arctan2(gse, gsn)))
        self.create(1, actype, acalt, acspd, None, aclat, aclon, achdg, acid)
        self.flush()
        s = self._id2slot[acid.upper()]
        self.write("ac", "vs", s, acvs)
        self.write("ac", "selalt", s, altref)
        self.write("ac", "selvs", s, acvs)


# --- Host-side NumPy twins of the aero conversions used at creation time ----
# (creation happens on host with float64; the device versions live in
# ops/aero.py — same formulas, reference aero.py:62-168)

def _np_vatmos(h):
    T = np.maximum(288.15 - 0.0065 * h, 216.65)
    rhotrop = 1.225 * (T / 288.15) ** 4.256848030018761
    dhstrat = np.maximum(0.0, h - 11000.0)
    rho = rhotrop * np.exp(-dhstrat / 6341.552161)
    return rho * 287.05287 * T, rho, T


def _np_vtas2cas(tas, h):
    p, rho, _ = _np_vatmos(h)
    qdyn = p * ((1.0 + rho * tas * tas / (7.0 * p)) ** 3.5 - 1.0)
    cas = np.sqrt(7.0 * aero.p0 / aero.rho0
                  * ((qdyn / aero.p0 + 1.0) ** (2.0 / 7.0) - 1.0))
    return np.where(tas < 0, -cas, cas)


def _np_vcas2tas(cas, h):
    p, rho, _ = _np_vatmos(h)
    qdyn = aero.p0 * ((1.0 + aero.rho0 * cas * cas / (7.0 * aero.p0)) ** 3.5 - 1.0)
    tas = np.sqrt(7.0 * p / rho * ((1.0 + qdyn / p) ** (2.0 / 7.0) - 1.0))
    return np.where(cas < 0, -tas, tas)


def _np_vcasormach(spd, h):
    a = np.sqrt(1.4 * 287.05287 * np.maximum(288.15 - 0.0065 * h, 216.65))
    ismach = (0.1 < spd) & (spd < 1.0)
    tas = np.where(ismach, spd * a, _np_vcas2tas(spd, h))
    cas = np.where(ismach, _np_vtas2cas(tas, h), spd)
    mach = np.where(ismach, spd, tas / a)
    return tas, cas, mach
