"""CI perf-smoke: the pipelined chunk loop must be bit-identical to
synchronous stepping, and the fused edge-telemetry pack must round-trip
through the ACDATA stream schema.

Tiny N, CPU, seconds of wall time — run non-blocking in CI so a flaky
runner can't gate merges, but a real divergence is loud on every PR.

Exit 0 on success, 1 with a diagnostic on any mismatch.

Usage: python scripts/pipeline_smoke.py
"""
import hashlib
import os
import sys

sys.path.insert(0, ".")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def state_hash(sim):
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(jax.tree.map(np.asarray, sim.traf.state)):
        h.update(np.ascontiguousarray(leaf).tobytes())
    h.update(repr([sim.traf.ids, sim.traf.types]).encode())
    return h.hexdigest()


def build_and_run(pipeline: bool):
    from bluesky_tpu.simulation.sim import Simulation
    sim = Simulation(nmax=32)
    sim.pipeline_enabled = pipeline
    for cmd in (
            "CRE KL1 B744 52 4 90 FL200 250",
            "CRE KL2 B744 52.2 4.3 270 FL210 250",
            "SCHEDULE 00:00:03 ALT KL1 FL300",
            "SCHEDULE 00:00:06 CRE KL3 B744 53 5 180 FL100 200",
            "SCHEDULE 00:00:09 DEL KL2",
            "FF"):
        sim.stack.stack(cmd)
    sim.stack.process()
    sim.op()
    sim.run(until_simt=15.0, max_iters=1000)
    return sim


def check_parity():
    a = build_and_run(True)
    b = build_and_run(False)
    ha, hb = state_hash(a), state_hash(b)
    assert a.pipe_stats["pipelined_chunks"] > 0, \
        "pipelined run never actually pipelined"
    assert b.pipe_stats["pipelined_chunks"] == 0, \
        "sync run pipelined despite the toggle"
    assert ha == hb, (f"pipelined vs sync state hash diverged:\n"
                      f"  pipelined {ha}\n  sync      {hb}\n"
                      f"  simt {a.simt} vs {b.simt}")
    print(f"parity OK: hash {ha[:16]}..., simt {a.simt:.2f}, "
          f"{a.pipe_stats['pipelined_chunks']} pipelined chunks")
    return a


def check_telemetry_schema(sim):
    """The edge pack must cover every per-aircraft ACDATA field the
    stream schema test checks (test_stream_schema.py), and survive the
    network serializer round-trip."""
    edge = sim._last_edge
    assert edge is not None, "no retired edge after a pipelined run"
    idx, data = edge.acdata_arrays()
    data["simt"] = edge.simt
    data["id"] = [sim.traf.ids[i] for i in idx]
    data["nconf_cur"] = int(np.asarray(edge.nconf_cur)) // 2
    data["nlos_cur"] = int(np.asarray(edge.nlos_cur)) // 2
    required = {"lat", "lon", "alt", "trk", "tas", "gs", "cas", "vs",
                "inconf", "tcpamax", "asasn", "asase"}
    missing = required - set(data)
    assert not missing, f"edge pack missing ACDATA fields: {missing}"
    n = len(data["id"])
    for key in sorted(required):
        assert np.asarray(data[key]).shape == (n,), \
            f"{key}: shape {np.asarray(data[key]).shape} != ({n},)"
    # round-trip through the wire serializer the streams use
    try:
        from bluesky_tpu.network.npcodec import packb, unpackb
        raw = packb(data)
        back = unpackb(raw)
        for key in sorted(required):
            assert np.allclose(np.asarray(back[key]),
                               np.asarray(data[key])), key
        print(f"telemetry pack round-trips the stream codec "
              f"({len(raw)} bytes, {n} aircraft)")
    except ImportError:
        print("msgpack not installed — schema check ran, codec "
              "round-trip skipped")


def main():
    sim = check_parity()
    check_telemetry_schema(sim)
    print("pipeline smoke OK")


if __name__ == "__main__":
    try:
        main()
    except AssertionError as e:
        print(f"PIPELINE SMOKE FAILED: {e}", file=sys.stderr)
        sys.exit(1)
