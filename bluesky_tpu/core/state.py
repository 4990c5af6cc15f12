"""The simulation state: a padded struct-of-arrays JAX pytree.

This replaces the reference's ``TrafficArrays`` registry
(``bluesky/tools/trafficarrays.py:19-138``), which grows NumPy arrays with
``np.append`` on every aircraft creation.  Dynamic shapes are poison for XLA
— every growth would recompile — so the single most consequential design
divergence from the reference is here:

* Every per-aircraft array has fixed shape ``[N_max]`` (pair matrices
  ``[N_max, N_max]``, waypoint tables ``[N_max, W_max]``).
* A boolean ``active`` mask marks live slots; create/delete are mask flips +
  slot writes (functional ``.at[].set``), never reshapes.
* Callsign/type strings and other host-only bookkeeping live OUTSIDE the
  pytree in the host-side ``Traffic`` facade (core/traffic.py), so the device
  never sees a Python object.

All sub-structures are `flax.struct` dataclasses => they are pytrees: they
jit, vmap, shard and donate cleanly.  Field groups mirror the reference's
state registration (traffic.py:91-164, activewpdata.py:12-20, autopilot
state autopilot.py:24-43, pilot.py:12-17, asas state) so every reference
variable has a home; dtype is configurable (float32 for TPU throughput,
float64 on CPU for golden tests).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..ops import aero


#: worst-case extra padded slots of the sparse backend's stripe-sorted
#: layout: 32 pad blocks of <= 256 slots plus block rounding
#: (ops/cd_sched.stripe_sort_dest with block <= 256, extra_blocks = 32).
SORT_PAD = 33 * 256


@struct.dataclass
class AircraftArrays:
    """Kinematic + autopilot-selection state, one row per aircraft slot.

    Mirrors reference traffic.py:91-164.
    """
    active: jnp.ndarray   # bool — live slot mask (replaces dynamic ntraf)
    # Position
    lat: jnp.ndarray      # [deg]
    lon: jnp.ndarray      # [deg]
    alt: jnp.ndarray      # [m]
    hdg: jnp.ndarray      # [deg] heading
    trk: jnp.ndarray      # [deg] ground track
    # Velocity
    tas: jnp.ndarray      # [m/s] true airspeed
    gs: jnp.ndarray       # [m/s] ground speed
    gsnorth: jnp.ndarray  # [m/s]
    gseast: jnp.ndarray   # [m/s]
    cas: jnp.ndarray      # [m/s] calibrated airspeed
    mach: jnp.ndarray     # [-]
    vs: jnp.ndarray       # [m/s] vertical speed
    # Atmosphere at current altitude
    p: jnp.ndarray        # [Pa]
    rho: jnp.ndarray      # [kg/m3]
    temp: jnp.ndarray     # [K]
    # Autopilot selections (the MCP panel)
    selspd: jnp.ndarray   # selected CAS [m/s] or Mach [-]
    selalt: jnp.ndarray   # [m]
    selvs: jnp.ndarray    # [m/s]
    # LNAV/VNAV mode switches
    swlnav: jnp.ndarray   # bool
    swvnav: jnp.ndarray   # bool
    # Performance-ish per-aircraft settings (traffic.py:140-149)
    apvsdef: jnp.ndarray  # [m/s] default AP vertical speed
    aphi: jnp.ndarray     # [rad] AP bank-angle setting
    ax: jnp.ndarray       # [m/s2] longitudinal acceleration (abs)
    bank: jnp.ndarray     # [rad] nominal bank angle
    swhdgsel: jnp.ndarray  # bool — currently turning
    swaltsel: jnp.ndarray  # bool — currently climbing/descending
    # Crossover altitude flags
    abco: jnp.ndarray     # bool — above crossover
    belco: jnp.ndarray    # bool — below crossover
    # Misc
    coslat: jnp.ndarray   # cos(lat) cache for flat-earth math


@struct.dataclass
class ActWpArrays:
    """Active-leg guidance state (reference activewpdata.py:12-20)."""
    lat: jnp.ndarray        # [deg] active waypoint latitude
    lon: jnp.ndarray        # [deg]
    nextaltco: jnp.ndarray  # [m] next altitude constraint
    xtoalt: jnp.ndarray     # [m] distance from next wp to that constraint
    spd: jnp.ndarray        # CAS [m/s] / Mach — active wp speed (-999 = none)
    vs: jnp.ndarray         # [m/s] VNAV vertical speed to use
    turndist: jnp.ndarray   # [m] turn-anticipation distance
    flyby: jnp.ndarray      # 1.0 fly-by / 0.0 fly-over
    next_qdr: jnp.ndarray   # [deg] track of next leg (-999 = unknown)


@struct.dataclass
class AutopilotArrays:
    """FMS guidance output state (reference autopilot.py:24-43)."""
    trk: jnp.ndarray       # [deg] commanded track
    tas: jnp.ndarray       # [m/s] commanded TAS
    alt: jnp.ndarray       # [m] commanded altitude
    vs: jnp.ndarray        # [m/s] commanded vertical speed
    dist2vs: jnp.ndarray   # [m] distance-to-waypoint where descent starts
    swvnavvs: jnp.ndarray  # bool — VNAV vertical guidance engaged
    vnavvs: jnp.ndarray    # [m/s] VNAV vertical speed


@struct.dataclass
class PilotArrays:
    """AP-vs-ASAS arbitrated targets (reference pilot.py:12-17)."""
    alt: jnp.ndarray
    hdg: jnp.ndarray
    trk: jnp.ndarray
    vs: jnp.ndarray
    tas: jnp.ndarray


@struct.dataclass
class AsasArrays:
    """Conflict detection & resolution state (reference asas.py + MVP).

    ``resopairs`` is the [N,N] pair matrix replacing the reference's Python
    set of callsign tuples (asas.py:417); ``active`` is the per-aircraft
    "follow ASAS, not AP" flag consumed by the pilot arbitration.

    For the tiled large-N backend (ops/cd_tiled.py) ``resopairs`` is
    allocated [0,0] (an [N,N] bool is 10 GB at N=100k) and the resume-nav
    pair memory lives in ``partners``: [N,K] intruder indices, -1 = empty.
    """
    trk: jnp.ndarray        # [deg] resolution track command
    tas: jnp.ndarray        # [m/s] resolution speed command
    vs: jnp.ndarray         # [m/s] resolution vertical-speed command
    alt: jnp.ndarray        # [m] resolution altitude command
    active: jnp.ndarray     # [N] bool
    inconf: jnp.ndarray     # [N] bool — in conflict right now
    tcpamax: jnp.ndarray    # [N] max tcpa over own conflicts
    resopairs: jnp.ndarray  # [N,N] bool — pairs still being resolved
    partners: jnp.ndarray   # [N,K] int32 — tiled-backend partner table
    asasn: jnp.ndarray      # [N] resolution-vector north (display/logs)
    asase: jnp.ndarray      # [N] resolution-vector east
    noreso: jnp.ndarray     # [N] bool — nobody avoids these aircraft
    resooff: jnp.ndarray    # [N] bool — these aircraft don't resolve
    # Cumulative counts (device-side; unique-pair sets stay host-side)
    nconf_cur: jnp.ndarray  # scalar int — current directional conflict pairs
    nlos_cur: jnp.ndarray   # scalar int — current LoS pairs
    # Cached spatial sort for the tiled/pallas/sparse backends (Morton
    # permutation, or padded stripe destinations for 'sparse').  Sorting
    # 100k keys on TPU costs more than the CD kernel itself, and ANY
    # layout is exact (results are mapped back; tile reachability is
    # recomputed from true positions every interval) — so the sort is
    # refreshed by the HOST at chunk boundaries
    # (core/asas.refresh_spatial_sort) and carried here.
    sort_perm: jnp.ndarray  # [N] int32 — slot permutation / stripe dest
    # Sorted-space partner table for the 'sparse' backend: rows are
    # PADDED-SORTED slots (layout of ops/cd_sched.stripe_sort_dest,
    # bounded by SORT_PAD extra slots), values are sorted-slot ids, -1
    # empty.  Lives in sorted space so the in-kernel resume-nav needs no
    # [N,K] gathers; remapped on host sort refreshes.  The other
    # backends keep using ``partners`` (caller-slot semantics).
    partners_s: jnp.ndarray  # [N + SORT_PAD, K] int32


@struct.dataclass
class RouteArrays:
    """Dense per-aircraft flight plans: [N_max, W_max] waypoint tables.

    Replaces the reference's per-aircraft Python ``Route`` objects
    (route.py:15-1109).  Route *editing* (stack commands) happens host-side
    in core/route.py, which writes these tables; the device only reads them.
    ``wptoalt``/``wpxtoalt`` carry the propagated altitude-constraint
    lookahead that the reference computes in ``Route.calcfp``
    (route.py:983-1041), so the jitted FMS never scans the route.
    """
    wplat: jnp.ndarray    # [N,W] deg
    wplon: jnp.ndarray    # [N,W] deg
    wpalt: jnp.ndarray    # [N,W] m      (-999 = no constraint)
    wpspd: jnp.ndarray    # [N,W] CAS/Mach (-999 = no constraint)
    wpflyby: jnp.ndarray  # [N,W] 1.0 fly-by / 0.0 fly-over
    wptoalt: jnp.ndarray  # [N,W] m   next alt constraint at/after this wp
    wpxtoalt: jnp.ndarray  # [N,W] m  distance from this wp to that constraint
    nwp: jnp.ndarray      # [N] int32 — number of valid waypoints
    iactwp: jnp.ndarray   # [N] int32 — index of active waypoint (-1 = none)


@struct.dataclass
class PerfArrays:
    """Vectorized OpenAP-style performance model state (core/perf.py).

    Per-aircraft coefficient columns are filled host-side at creation from
    the type tables (models/perf_coeffs.py); phase-dependent selection
    happens in the jitted update.  Mirrors reference perfoap.py:28-47.
    """
    mass: jnp.ndarray       # [kg]
    sref: jnp.ndarray       # [m2] wing area
    engthrust: jnp.ndarray  # [N] total static thrust (n_eng * per-engine)
    engbpr: jnp.ndarray     # engine bypass ratio
    ff_a: jnp.ndarray       # fuel-flow quadratic coefficients
    ff_b: jnp.ndarray
    ff_c: jnp.ndarray
    engnum: jnp.ndarray     # number of engines
    cd0_clean: jnp.ndarray
    cd0_gd: jnp.ndarray
    cd0_to: jnp.ndarray
    cd0_ic: jnp.ndarray
    cd0_ap: jnp.ndarray
    cd0_ld: jnp.ndarray
    k: jnp.ndarray          # induced-drag factor
    # Phase-dependent envelope columns [N] (vmin/vmax per phase group)
    vminto: jnp.ndarray     # CAS m/s
    vminic: jnp.ndarray
    vminer: jnp.ndarray
    vminap: jnp.ndarray
    vminld: jnp.ndarray
    vmaxto: jnp.ndarray
    vmaxic: jnp.ndarray
    vmaxer: jnp.ndarray
    vmaxap: jnp.ndarray
    vmaxld: jnp.ndarray
    vsmin: jnp.ndarray      # m/s
    vsmax: jnp.ndarray      # m/s
    hmax: jnp.ndarray       # m
    axmax: jnp.ndarray      # m/s2
    islifttype_rotor: jnp.ndarray  # bool
    # Outputs of the jitted perf update
    phase: jnp.ndarray      # int32 flight phase
    vmin: jnp.ndarray       # current phase envelope
    vmax: jnp.ndarray
    thrust: jnp.ndarray     # [N]
    drag: jnp.ndarray       # [N]
    fuelflow: jnp.ndarray   # [kg/s]


@struct.dataclass
class SimState:
    """Top-level simulation state — one pytree, jitted/donated whole."""
    ac: AircraftArrays
    actwp: ActWpArrays
    ap: AutopilotArrays
    pilot: PilotArrays
    asas: AsasArrays
    route: RouteArrays
    perf: PerfArrays
    adsb: "AdsbArrays"      # noise.AdsbArrays — surveillance broadcast state
    wind: "WindState"       # wind.WindState — point-defined wind field
    rng: jnp.ndarray        # PRNG key for turbulence/ADS-B noise
    nstep: jnp.ndarray      # int32 — steps taken: the simulation clock
    simt: jnp.ndarray       # [s] simulation time: ``time_of_count(nstep)``
    fms_t0: jnp.ndarray     # [s] last FMS update time (autopilot.py:17)
    asas_tnext: jnp.ndarray  # [s] next ASAS trigger time (asas.py:474-478)

    @property
    def nmax(self) -> int:
        return self.ac.lat.shape[0]


#: ``time_of_count`` corrects its float32 product with whole arithmetic
#: in int32: the steps a second times a 24-bit mantissa must fit.
_RATE_MAX = 127


def _steps_a_second(simdt: float):
    """The whole number of ``simdt`` steps in a simulated second where
    ``time_of_count`` can use it (1 to 127), else None."""
    k = int(round(1.0 / simdt))
    return k if 1 <= k <= _RATE_MAX and abs(k * simdt - 1.0) < 1e-12 \
        else None


def time_of_count(nstep, simdt: float, dtype):
    """The simulation time [s] of a step count, on the device: the
    count times ``simdt``, rounded to the state's float type once, so
    that it is ``time_as_held(n * simdt)`` bit for bit.  (A sum of ``simdt``
    steps rounds at each: in float32 a step of it is 0.1% long above
    1,024 s and 1.6% long above 16,384 s.)

    In float32 neither ``float32(n) * float32(0.05)`` nor ``float32(n)
    / 20`` is that for every n: ``float32(0.05)`` is a quarter of an
    ulp high, and the compiler turns a division by a constant into
    that product (one count in five is an ulp off on the CPU, PR 42).
    So where a second is a whole number ``q`` of steps (20 at the
    default 0.05 s) the product ``x = m * 2**-e`` is held against the
    count in whole arithmetic, ``q * m - (n << e)``, and moved to the
    neighbouring float32 where that one is nearer ``n / q``: exact
    whatever the float unit rounds like, to 2**24 steps (9.7 simulated
    days at 0.05 s), beyond which float32 no longer holds the count
    itself.  Any other ``simdt`` in float32 takes the plain product,
    an ulp from the host's at some counts."""
    dtype = jnp.dtype(dtype)
    q = _steps_a_second(simdt) if dtype == jnp.float32 else None
    if q is None:
        return nstep.astype(dtype) * jnp.asarray(simdt, dtype)
    x = nstep.astype(dtype) * jnp.asarray(1.0 / q, dtype)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    e = jnp.clip(150 - (bits >> 23), 0, 30)
    m = (bits & 0x7FFFFF) | 0x800000
    gap = q * m - (nstep << e)              # q * x - n, in units 2**-e
    move = jnp.where(2 * gap > q, -1, jnp.where(2 * gap < -q, 1, 0))
    near = jax.lax.bitcast_convert_type(bits + move, dtype)
    return jnp.where(nstep > 0, near, jnp.zeros((), dtype))


def time_as_held(simt: float, dtype) -> float:
    """A time of the host's clock (the product ``n * simdt``, formed in
    float64) as a state of float type ``dtype`` holds it, rounded once:
    ``time_of_count`` on the host, and the definition of every time
    that leaves a worker."""
    return float(np.asarray(simt).astype(dtype))


def count_of_time(simt: float, simdt: float) -> int:
    """The step count whose time is nearest ``simt`` (a clock set from a
    time: a snapshot written before the state counted, a new ``DT``)."""
    return int(round(float(simt) / float(simdt)))


def make_state(nmax: int = 64, wmax: int = 32,
               dtype=jnp.float32, rng_seed: int = 0,
               pair_matrix: bool = True, k_partners: int = 8) -> SimState:
    """Allocate an empty padded simulation state.

    Defaults mirror the reference's creation defaults where a slot is
    activated (traffic.py:287-308, activewpdata.py:22-29); padding slots hold
    benign values (eps speeds, lat 89.99 for waypoints) so jitted math stays
    NaN-free without branching.

    One compiled program per shape fills every leaf: every ``RESET``
    builds a fresh state, and filled eagerly each of the 132 leaves is
    a dispatch of its own.  The key is built here and passed in traced:
    a reset draws a new seed each time, and a static seed would compile
    at each.
    """
    return _empty_state(jax.random.PRNGKey(rng_seed), nmax, wmax,
                        np.dtype(dtype), pair_matrix, k_partners)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _empty_state(rng, nmax, wmax, dtype, pair_matrix, k_partners):
    """``make_state``'s fills.  Each leaf is an array op of its own, so
    the program returns each in a buffer of its own: the state is
    donated whole, and no two leaves may share one."""
    f = lambda: jnp.zeros((nmax,), dtype)
    b = lambda: jnp.zeros((nmax,), dtype=bool)
    i = lambda: jnp.zeros((nmax,), dtype=jnp.int32)

    ac = AircraftArrays(
        active=b(), lat=f(), lon=f(), alt=f(), hdg=f(), trk=f(),
        tas=f(), gs=f(), gsnorth=f(), gseast=f(), cas=f(), mach=f(), vs=f(),
        p=f(), rho=f(), temp=f(),
        selspd=f(), selalt=f(), selvs=f(),
        swlnav=b(), swvnav=b(),
        apvsdef=jnp.full((nmax,), 1500.0 * aero.fpm, dtype),
        aphi=jnp.full((nmax,), jnp.radians(25.0), dtype),
        ax=jnp.full((nmax,), aero.kts, dtype),
        bank=jnp.full((nmax,), jnp.radians(25.0), dtype),
        swhdgsel=b(), swaltsel=b(),
        abco=b(), belco=jnp.ones((nmax,), dtype=bool),
        coslat=jnp.ones((nmax,), dtype),
    )
    actwp = ActWpArrays(
        lat=jnp.full((nmax,), 89.99, dtype), lon=f(),
        nextaltco=f(), xtoalt=f(),
        spd=jnp.full((nmax,), -999.0, dtype), vs=f(),
        turndist=jnp.ones((nmax,), dtype),
        flyby=jnp.ones((nmax,), dtype),
        next_qdr=jnp.full((nmax,), -999.0, dtype),
    )
    ap = AutopilotArrays(
        trk=f(), tas=f(), alt=f(), vs=f(),
        dist2vs=jnp.full((nmax,), -999.0, dtype),
        swvnavvs=b(), vnavvs=f(),
    )
    pilot = PilotArrays(alt=f(), hdg=f(), trk=f(), vs=f(), tas=f())
    asas = AsasArrays(
        trk=f(), tas=f(), vs=f(), alt=f(),
        active=b(), inconf=b(), tcpamax=f(),
        resopairs=jnp.zeros((nmax, nmax) if pair_matrix else (0, 0),
                            dtype=bool),
        partners=jnp.full((nmax, k_partners), -1, jnp.int32),
        asasn=f(), asase=f(), noreso=b(), resooff=b(),
        nconf_cur=jnp.zeros((), jnp.int32), nlos_cur=jnp.zeros((), jnp.int32),
        sort_perm=jnp.arange(nmax, dtype=jnp.int32),
        partners_s=jnp.full((nmax + SORT_PAD, k_partners), -1, jnp.int32),
    )
    route = RouteArrays(
        wplat=jnp.full((nmax, wmax), 89.99, dtype),
        wplon=jnp.zeros((nmax, wmax), dtype),
        wpalt=jnp.full((nmax, wmax), -999.0, dtype),
        wpspd=jnp.full((nmax, wmax), -999.0, dtype),
        wpflyby=jnp.ones((nmax, wmax), dtype),
        wptoalt=jnp.full((nmax, wmax), -999.0, dtype),
        wpxtoalt=jnp.zeros((nmax, wmax), dtype),
        nwp=i(), iactwp=jnp.full((nmax,), -1, jnp.int32),
    )
    from ..models import perf_coeffs
    from . import noise, wind as windmod
    perf = perf_coeffs.empty_perf_arrays(nmax, dtype)
    return SimState(
        ac=ac, actwp=actwp, ap=ap, pilot=pilot, asas=asas, route=route,
        perf=perf,
        adsb=noise.make_adsb(nmax, dtype),
        wind=windmod.make_windstate(dtype=dtype),
        rng=rng,
        nstep=jnp.zeros((), jnp.int32),
        simt=jnp.zeros((), dtype),
        fms_t0=jnp.full((), -999.0, dtype),
        asas_tnext=jnp.zeros((), dtype),
    )
