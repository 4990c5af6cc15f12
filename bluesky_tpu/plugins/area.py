"""Experiment-area plugin: delete aircraft leaving the area, FLST log.

Parity with the reference ``plugins/area.py:47-219``: an experiment area
(existing shape name or ad-hoc box) from which exiting aircraft are
deleted, per-flight efficiency accumulators (2D/3D distance, work done),
the FLST flight-statistics event log written at deletion, and the
AREA / TAXI stack commands.

TPU-first divergences:
* A tick is ONE device program (``Area._program``), enqueued behind the
  chunk whose edge it falls on and waited for by nobody: the three
  integrals (with the *actual* elapsed sim time since the previous tick;
  the reference multiplies by its nominal dt, plugins/area.py:118-125),
  the membership test (``Shape.contains`` with ``xp=jnp``) and the
  deletion itself, on the state of the tick's own time, so an aircraft
  stops being simulated at the tick that finds it outside.  The
  accumulators are ``[nmax]`` device arrays on stable slots.
* Only the leavers' rows come to the host (``ROWS`` a tick without a
  second transfer), at the first chunk edge retired after the tick
  (``collect``): there the FLST row is written and ``Traffic.forget``
  takes the slot back, so a slot is reused only once the host has seen
  it freed.  The hook reads nothing of the live state on the host
  (``reads_state`` False): the chunk pipeline holds through it.
"""
import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.traffic import purge_tables

FLST_HEADER = (
    "FLST log - flight statistics: "
    "deletion time [s], callsign, spawn time [s], flight time [s], "
    "2D distance [m], 3D distance [m], work done [J], "
    "lat [deg], lon [deg], alt [m], TAS [m/s], VS [m/s], HDG [deg], "
    "ASAS active [bool], pilot alt [m], pilot TAS [m/s], "
    "pilot VS [m/s], pilot HDG [deg]")


def init_plugin(sim):
    area = Area(sim)

    config = {
        "plugin_name": "AREA",
        "plugin_type": "sim",
        "update_interval": area.dt,
        "update": area.update,
        "collect": area.collect,
        "reads_state": False,
        "reset": area.reset,
    }
    stackfunctions = {
        "AREA": [
            "AREA Shapename/OFF or AREA lat,lon,lat,lon,[top,bottom]",
            "[float/txt,float,float,float,alt,alt]",
            area.set_area,
            "Define experiment area (area of interest)",
        ],
        "TAXI": [
            "TAXI ON/OFF [alt]: OFF auto deletes traffic below 1500 ft",
            "onoff,[alt]",
            area.set_taxi,
            "Ground/low-altitude mode: prevents auto-delete at 1500 ft",
        ],
    }
    return config, stackfunctions


ROWS = 256      # leavers a tick whose rows come in the tick's own pack

# an FLST row's columns that are read off the state, after the four the
# plugin keeps itself (spawn time, 2D and 3D distance, work)
_STATE_COLS = (("ac", "lat"), ("ac", "lon"), ("ac", "alt"), ("ac", "tas"),
               ("ac", "vs"), ("ac", "hdg"), ("asas", "active"),
               ("pilot", "alt"), ("pilot", "tas"), ("pilot", "vs"),
               ("pilot", "hdg"))


class Area:
    def __init__(self, sim):
        self.sim = sim
        traf = sim.traf
        self.active = False
        self.dt = 0.5                  # [s] area-check interval
        self.name = None
        self.swtaxi = True             # True = no low-altitude auto-delete
        self.swtaxialt = 1500.0 * 0.3048
        # per-slot record, on the device: inside at the last tick, the
        # altitude then, the integrals, the spawn time
        self.acc = dict(inside=jnp.zeros(traf.nmax, bool), **{
            k: jnp.zeros(traf.nmax, traf.dtype) for k in (
                "oldalt", "distance2d", "distance3d", "work",
                "create_time")})
        self._born = []                # (slots, simt) since the last tick
        # ticks whose leavers the host has not read yet: (sequence tag
        # of the chunk dispatched last before it, simt, the fleet's
        # epoch, what it left)
        self._left = collections.deque()
        self._programs = {}            # (shape, taxi) -> compiled tick
        self.last_t = float(sim.simt)
        self.logger = sim.datalog.define_event("FLSTLOG", FLST_HEADER)
        traf.create_hooks.append(self.on_create)

    # ---------------------------------------------------------- lifecycle
    def on_create(self, slots):
        """New aircraft start their record at the next tick: the
        program zeroes their rows before it integrates."""
        self._born.append((np.atleast_1d(np.asarray(slots)),
                           self.sim.simt_planned))

    def reset(self):
        self.active = False
        self.name = None
        self.acc = {k: jnp.zeros_like(v) for k, v in self.acc.items()}
        self._born.clear()
        self._left.clear()
        self.logger.stop()
        self.last_t = float(self.sim.simt)

    # ------------------------------------------------------------- update
    def _program(self, shape, taxi):
        """The tick for one area shape (or none) and taxi mode, compiled
        once: state and accumulators in and out, donated."""
        key = (shape, taxi)
        if key not in self._programs:
            self._programs[key] = functools.partial(
                jax.jit, donate_argnums=(0, 1))(
                    functools.partial(_tick, shape, taxi))
        return self._programs[key]

    def update(self):
        """Integrate efficiency metrics; delete aircraft that left the
        area (plugins/area.py:113-174), all in one device program whose
        leavers ``collect`` reads later."""
        sim = self.sim
        traf = sim.traf
        t = sim.simt_planned
        dt = max(0.0, t - self.last_t)
        self.last_t = t
        if not self.active and self.swtaxi:
            return
        shape = sim.areas.areas.get(self.name) if self.active else None
        born, self._born = self._born, []
        nborn = sum(len(s) for s, _ in born)
        # padded to a short ladder from 64 up, so a tick's spawns
        # compile nothing new
        rows = 64
        while rows < nborn:
            rows *= 4
        slots = np.full(min(rows, max(traf.nmax, 64)), traf.nmax, np.int32)
        times = np.zeros(len(slots), np.float32)
        if nborn:
            slots[:nborn] = np.concatenate([s for s, _ in born])
            times[:nborn] = np.concatenate(
                [np.full(len(s), tb) for s, tb in born])
        state, self.acc, left = self._program(shape, self.swtaxi)(
            traf.state, self.acc, slots, times, dt, self.swtaxialt)
        traf.state = state
        self._left.append((sim._chunk_seq, t, traf.epoch, left))

    def collect(self, upto=None):
        """The host's record of the leavers of every tick that ran
        before the chunk with sequence tag ``upto`` (every tick, if
        None): their FLST rows logged, their slots handed back.
        Returns how many aircraft left."""
        traf = self.sim.traf
        total = 0
        while self._left and (upto is None or self._left[0][0] < upto):
            _, t, epoch, (head, full) = self._left.popleft()
            n, slots, rows = jax.device_get(head)
            n = int(n)
            if n == 0 or epoch != traf.epoch:   # of a fleet since reset
                continue
            if n > ROWS:              # more than the pack holds: all
                slots, rows = jax.device_get(full)
            slots, rows = slots[:n], np.asarray(rows)[:, :n]
            state_cols = list(rows[4:])
            state_cols[6] = state_cols[6] > 0        # ASAS active [bool]
            self.logger.log(
                self.sim, [traf.ids[i] for i in slots],
                rows[0], t - rows[0], rows[1], rows[2], rows[3],
                *state_cols, simt=t)
            traf.forget([int(i) for i in slots])
            total += n
        return total

    # ------------------------------------------------------------ commands
    def set_area(self, *args):
        """AREA Shapename/OFF or AREA lat,lon,lat,lon,[top,bottom]
        (plugins/area.py:177-210)."""
        args = [a for a in args if a is not None]
        if not args:
            return True, ("Area is currently "
                          + ("ON" if self.active else "OFF")
                          + "\nCurrent Area name is: " + str(self.name))
        a0 = args[0]
        if isinstance(a0, str) and not _isfloat(a0) and len(args) == 1:
            name = a0.upper()
            if self.sim.areas.hasArea(name) or self.sim.areas.hasArea(a0):
                self.name = name if self.sim.areas.hasArea(name) else a0
                self.active = True
                self._outside()
                self.logger.start(self.sim)
                return True, f"Area is set to {self.name}"
            if name in ("OFF", "OF"):
                if self.name is not None:
                    self.sim.areas.deleteArea(self.name)
                self.logger.stop()
                self.active = False
                self.name = None
                return True, "Area is switched OFF"
            return False, ("Shapename unknown. Please create shapename "
                           "first or shapename is misspelled!")
        if len(args) >= 4:
            try:
                coords = [float(a) for a in args[:4]]
                bounds = [float(a) for a in args[4:6]]
            except (TypeError, ValueError):
                return False, ("Incorrect arguments\n"
                               "AREA Shapename/OFF or "
                               "AREA lat,lon,lat,lon,[top,bottom]")
            self.active = True
            self.name = "DELAREA"
            self.sim.areas.defineArea(self.name, "BOX", coords, *bounds)
            self._outside()
            self.logger.start(self.sim)
            return True, f"Area is ON. Area name is: {self.name}"
        return False, ("Incorrect arguments\nAREA Shapename/OFF or "
                       "AREA lat,lon,lat,lon,[top,bottom]")

    def set_taxi(self, flag, alt=None):
        """TAXI ON/OFF [alt] (plugins/area.py:212-215)."""
        self.swtaxi = bool(flag)
        if alt is not None:
            self.swtaxialt = float(alt)
        self.acc["oldalt"] = jnp.array(self.sim.traf.state.ac.alt)
        return True

    def _outside(self):
        """A new area: nobody has been seen inside it yet."""
        self.acc["inside"] = jnp.zeros_like(self.acc["inside"])


def _tick(shape, taxi, state, acc, born, born_t, dt, swtaxialt):
    """One AREA tick on the device (``Area._program`` compiles it for a
    shape and a taxi mode).  Returns the state with the leavers
    deactivated and purged from the partner tables
    (``traffic.purge_tables``, as a queued ``delete`` does), the
    accumulators, and what the host reads later: ``(n, the first ROWS
    leavers' slots, their FLST rows [15, ROWS])`` and, filled only when
    ``n > ROWS``, the same for all of them."""
    ac, asas = state.ac, state.asas
    nmax = ac.active.shape[0]
    # aircraft created since the last tick: a fresh record
    fresh = lambda a, v: a.at[born].set(v, mode="drop")
    inside = fresh(acc["inside"], False)
    oldalt = fresh(acc["oldalt"], ac.alt[jnp.minimum(born, nmax - 1)])
    create_time = fresh(acc["create_time"], born_t.astype(ac.alt.dtype))
    active = ac.active
    live = active.astype(ac.gs.dtype)
    spd = jnp.sqrt(ac.gs * ac.gs + ac.vs * ac.vs)
    d2 = fresh(acc["distance2d"], 0.0) + dt * ac.gs * live
    d3 = fresh(acc["distance3d"], 0.0) + dt * spd * live
    work = fresh(acc["work"], 0.0) + state.perf.thrust * dt * spd * live

    gone = jnp.zeros_like(active)
    if not taxi:
        # low-altitude auto-delete when taxi mode is off
        gone |= active & (oldalt >= swtaxialt) & (ac.alt < swtaxialt)
        oldalt = ac.alt
    if shape is not None:
        now = shape.contains(ac.lat, ac.lon, ac.alt, xp=jnp) & active
        gone |= inside & ~now & active
        inside = now
    inside &= ~gone

    # the leavers' slots, lowest first, and their rows
    n = jnp.sum(gone, dtype=jnp.int32)
    padded = -(-nmax // ROWS) * ROWS
    slots = jnp.nonzero(gone, size=padded, fill_value=nmax)[0] \
        .astype(jnp.int32)
    cols = [create_time, d2, d3, work] + [
        getattr(getattr(state, sub), f).astype(ac.alt.dtype)
        for sub, f in _STATE_COLS]
    rows_at = lambda at: jnp.stack(
        [c[jnp.minimum(at, nmax - 1)] for c in cols])
    head = (n, slots[:ROWS], rows_at(slots[:ROWS]))
    full = jax.lax.cond(
        n > ROWS, lambda: (slots, rows_at(slots)),
        lambda: (slots, jnp.zeros((len(cols), padded), ac.alt.dtype)))

    # out of the simulated state, ROWS at a time
    tables = jax.lax.fori_loop(
        0, -(-n // ROWS),
        lambda i, tb: purge_tables(
            *tb, asas.sort_perm,
            jax.lax.dynamic_slice(slots, (i * ROWS,), (ROWS,))),
        (asas.partners, asas.partners_s, asas.resopairs))
    state = state.replace(
        ac=ac.replace(active=active & ~gone),
        asas=asas.replace(active=asas.active & ~gone, partners=tables[0],
                          partners_s=tables[1], resopairs=tables[2]))
    acc = dict(inside=inside, oldalt=oldalt, distance2d=d2, distance3d=d3,
               work=work, create_time=create_time)
    return state, acc, (head, full)


def _isfloat(s):
    try:
        float(s)
        return True
    except (TypeError, ValueError):
        return False
