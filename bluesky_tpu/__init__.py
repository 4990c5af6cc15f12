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
