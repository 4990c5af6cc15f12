"""TRAFGEN-driven density sweep: the reference benchmark config #3.

Spins up circle traffic with the TRAFGEN plugin (12 edge segments, inward
flows) until a target aircraft count is reached, then measures sustained
full-pipeline throughput (FMS + CD&R + perf + kinematics) at that density.

Usage:  python scripts/density_sweep.py [N ...]     (default: 1000 10000)

Prints one JSON line per density with aircraft-steps/s and wall time.
Mirrors BASELINE.md config #3 (plugins/trafgen.py 10k/50k/100k circle
sweep); the spawn phase exercises the batched create path, the measure
phase the scanned step.
"""
import json
import sys
import time

sys.path.insert(0, ".")


def sweep(n_target, spawn_circle_nm=230.0):
    import jax
    import jax.numpy as jnp
    from bluesky_tpu.simulation.sim import Simulation

    nmax = int(n_target * 1.25)
    sim = Simulation(nmax=nmax, dtype=jnp.float32)
    st = sim.stack
    st.stack("PLUGINS LOAD TRAFGEN")
    st.stack(f"TRAFGEN CIRCLE 52.6 5.4 {spawn_circle_nm}")
    # 12 segments, even inbound flows sized to reach n_target quickly
    flow = max(3600.0, n_target * 3600.0 / (12 * 120.0))  # fill in ~2 min
    for brg in range(0, 360, 30):
        st.stack(f"TRAFGEN SRC SEGM{brg} FLOW {flow}")
        st.stack(f"TRAFGEN SRC SEGM{brg} DEST SEGM{(brg + 180) % 360}")
    st.process()
    sim.op()
    sim.fastforward()

    t0 = time.perf_counter()
    while sim.traf.ntraf < n_target:
        sim.step()
        if time.perf_counter() - t0 > 600.0:
            break
    spawn_wall = time.perf_counter() - t0
    n_reached = sim.traf.ntraf

    # Freeze population for the measurement: drop the generator plugin
    # entirely so its 0.1 s hook interval stops clamping the device chunk.
    st.stack("PLUGINS REMOVE TRAFGEN")
    st.process()
    sim.step()

    # Sustained throughput at this density
    nsteps = 0
    simt0 = sim.simt
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 10.0:
        sim.step()
        nsteps += 1
    wall = time.perf_counter() - t0
    sim_advanced = sim.simt - simt0
    steps = sim_advanced / sim.simdt
    result = {
        "metric": f"density-sweep N={n_reached}",
        "value": round(n_reached * steps / wall, 1),
        "unit": "aircraft-steps/s",
        "n": n_reached,
        "spawn_wall_s": round(spawn_wall, 1),
        "xrealtime": round(sim_advanced / wall, 1),
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    targets = [int(a) for a in sys.argv[1:]] or [1000, 10000]
    for n in targets:
        sweep(n)
