#!/usr/bin/env python3
"""Environment capability probe (parity: /root/reference/check.py).

The reference script checks numpy/scipy/Qt/OpenGL/pygame availability
for its GUI stack; this framework's equivalent checks the TPU-native
stack: JAX and its backend devices, the optional acceleration pieces,
the network fabric deps, the compiled host-geodesy extension, and the
data mounts — then runs a one-aircraft smoke simulation.

Run: python check.py        (exit 0 = everything needed is present)
"""
import importlib
import os
import sys

FAIL = 0


def probe(name, what, detail="", optional=False):
    global FAIL
    pad = " " * max(1, 32 - len(what))
    try:
        out = name() if callable(name) else importlib.import_module(name)
        extra = detail(out) if callable(detail) else detail
        print(f"Checking {what}{pad}[OK] {extra}")
        return out
    except Exception as e:  # noqa: BLE001 — a probe must never crash
        if optional:
            # missing optional pieces degrade gracefully: report, but
            # keep exit 0 (the script's contract)
            print(f"Checking {what}{pad}[MISSING] {type(e).__name__}: {e}")
        else:
            print(f"Checking {what}{pad}[FAIL] {type(e).__name__}: {e}")
            FAIL += 1
        return None


print("bluesky_tpu environment check")
print()

probe("numpy", "numpy")
jax = probe("jax", "jax", detail=lambda m: m.__version__)
if jax is not None:
    probe(lambda: jax.devices(), "jax devices",
          detail=lambda d: f"{d[0].platform} ({d[0].device_kind}) "
                           f"x{len(d)}: {[str(x) for x in d]}")
    probe(lambda: __import__("jax.experimental.pallas", fromlist=["x"]),
          "pallas (TPU kernels)")
probe("flax", "flax (optional)", optional=True)
probe("optax", "optax (optional)", optional=True)
probe("zmq", "pyzmq (network fabric)")
probe("msgpack", "msgpack (wire codec)")

# the compiled host geodesy core (optional; NumPy fallback otherwise)
def _cgeo():
    from bluesky_tpu.ops import hostgeo
    if not hostgeo.compiled:
        raise RuntimeError(
            "not built (optional): cd bluesky_tpu/src_cpp && "
            "python setup.py build_ext --inplace")
    return hostgeo
probe(_cgeo, "cgeo C++ extension (optional)", optional=True)

# data mounts (everything degrades gracefully; see docs/DATA.md)
def _data():
    from bluesky_tpu import settings
    out = []
    for label, p in (("navdata", settings.navdata_path),
                     ("performance", settings.perf_path)):
        out.append(f"{label}: "
                   + (p if p and os.path.isdir(p) else "builtin fallback"))
    return ", ".join(out)
probe(_data, "data paths", detail=lambda s: s, optional=True)

# multi-chip decomposition surface (SHARD REPLICATE/SPATIAL): report
# the visible mesh size; the full 8-device parity matrix is the
# driver/CI dryrun (__graft_entry__.dryrun_multichip)
def _shard():
    import jax as _jax
    from bluesky_tpu.parallel import sharding as _shd
    nd = len(_jax.devices())
    assert _shd.prepare_spatial and _shd.make_mesh
    return f"{nd} device(s); modes: replicate, spatial"
probe(_shard, "multi-chip shard modes", detail=lambda s: s,
      optional=True)

# one-aircraft smoke sim on the backend JAX picked.  A CPU is fine when
# it was asked for by name (JAX_PLATFORMS=cpu, as the tests do); a CPU
# that nobody named means the accelerator this host should have was not
# found, and a worker started here would stop at its first kernel.
def _smoke():
    from bluesky_tpu.ops.cd_pallas import interpret_default
    interpret_default(None)     # the rule itself: raises on such a CPU
    from bluesky_tpu.simulation.sim import Simulation
    sim = Simulation(nmax=8)
    sim.stack.stack("CRE CHK B744 52 4 90 FL200 250; OP; FF 2")
    sim.stack.process()
    sim.run(until_simt=2.0)
    assert sim.traf.ntraf == 1 and float(sim.simt) >= 2.0 - 0.06, \
        f"ntraf={sim.traf.ntraf} simt={float(sim.simt)}"
    return sim
probe(_smoke, "smoke simulation (2 sim-s)",
      detail=lambda s: f"simt={float(s.simt):.2f}s on "
                       f"{jax.devices()[0].device_kind}")

print()
if FAIL:
    print(f"{FAIL} probe(s) failed — required pieces are jax, numpy, "
          "pyzmq, msgpack; the rest degrade gracefully.")
print("Result:", "OK" if FAIL == 0 else "INCOMPLETE")
sys.exit(1 if FAIL else 0)
