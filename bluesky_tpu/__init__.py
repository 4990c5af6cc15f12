"""bluesky_tpu — a TPU-native air-traffic-simulation framework.

A ground-up redesign of the capabilities of BlueSky (the open ATM simulator,
reference: /root/reference) for TPU hardware: the N-aircraft simulation state
is a padded struct-of-arrays JAX pytree advanced by a jitted, `lax.scan`-
wrapped step function; the O(N^2) conflict detection and MVP resolution are
batched all-pairs kernels; geodesy/atmosphere primitives are jitted ops; the
aircraft axis shards over a `jax.sharding.Mesh` for large N, and Monte-Carlo
ensembles vmap over a replica axis.

Package layout:
  ops/        pure jitted math: geodesy, atmosphere, conflict detection
              (dense / lax-tiled / Pallas), MVP/Eby/Swarm/SSD resolvers,
              legacy+BADA performance kernels
  core/       simulation state pytree, traffic facade, kinematics,
              autopilot, pilot arbitration, ASAS coordinator, perf,
              wind, noise, routes, trails, conditionals, metrics, step
  parallel/   device-mesh sharding of the aircraft axis, ensemble axis
  stack/      the text-command stack (the universal user/API surface)
  simulation/ the fixed-dt simulation loop + node, streams, snapshots
  network/    zmq server/client/node fabric, GuiClient, telnet bridge
  plugins/    plugin system + the nine shipped plugins
  models/     OpenAP / BADA / BS coefficient databases, fwparser
  ui/         SVG radar renderer
  utils/      datalog, areafilter, plotter, profiler, timers
"""

import os as _os

__version__ = "0.1.0"

# The persistent XLA compilation cache.  JAX reads
# JAX_COMPILATION_CACHE_DIR itself when it is first imported: where the
# variable is set from outside, the cache lives there and nothing in
# this repository sets a directory.  Where it is not, the cache goes to
# one fixed directory in the checkout — no process id, worker id or
# time in the path, because a directory that moves never hits.  Every
# process of the served path (broker, the workers it spawns — they
# inherit the environment —, chip_smoke.py's children, bench.py)
# imports this package before it imports jax, so this is the one place.
_os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), ".jax_cache"))

# The chip a worker was given.  A broker with ``max_nnodes`` > 1 gives
# every worker it spawns one device slot, 0 to ``max_nnodes`` - 1, in
# this variable of the child's environment (``Server.addnodes``; an
# operator who starts an external worker on a chip of their choice sets
# it the same way).  libtpu reads its process variables when JAX first
# loads it, and every process passes here before it imports jax: this
# is the one place a slot is honoured.
DEVICE_SLOT_ENV = "BLUESKY_TPU_DEVICE_SLOT"


def device_slot(environ=None):
    """The device slot this process was given, or None where none is
    named (the one worker of a host: it owns every chip)."""
    named = (_os.environ if environ is None else environ).get(
        DEVICE_SLOT_ENV, "").strip()
    return int(named) if named else None


def cpu_by_name(platforms):
    """Was the CPU asked for by name?  ``platforms``: ``JAX_PLATFORMS``
    / ``jax_platforms``, whose first entry is the platform computed on
    (a chip machine may say ``tpu,cpu``: that names the chip)."""
    return (platforms or "").lower().split(",")[0].strip() == "cpu"


def slot_variables(slot):
    """What libtpu needs to give this process chip ``slot`` of its host
    alone: that one chip visible, as a process of one chip in a mesh of
    one process.  Bounds that are a subset of the host's chips also make
    libtpu skip its one-owner-a-host lock file (by ``TPU_VISIBLE_CHIPS``
    alone a process gets its chip too, libtpu 0.0.34 on a v5e 2x2 host,
    but the bounds are what says so); a second process on a chip that is
    held fails to open it (``/dev/vfio/<k>``: device or resource busy)."""
    return {"TPU_VISIBLE_CHIPS": str(slot),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def honour_device_slot(environ=None):
    """Restrict this process to the chip its slot names, before JAX is
    imported; returns what it set.  No slot named: nothing is set.  On a
    CPU asked for by name (``JAX_PLATFORMS=cpu``: every test launcher, a
    rehearsal) the slot is carried and reported and restricts nothing."""
    environ = _os.environ if environ is None else environ
    slot = device_slot(environ)
    if slot is None or cpu_by_name(environ.get("JAX_PLATFORMS")):
        return {}
    named = slot_variables(slot)
    environ.update(named)
    return named


honour_device_slot()
