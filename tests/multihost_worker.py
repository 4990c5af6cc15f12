"""Worker process for the 2-process jax.distributed test
(tests/test_multihost.py) — VERDICT r4 #2: execute
``parallel.sharding.init_multihost`` for real.

Each of the two processes owns 4 virtual CPU devices; after
``init_multihost`` the job-wide mesh has 8 devices spanning both
processes, and the sharded SPARSE step (shard_map row split + GSPMD
collectives, here over the gloo DCN-analogue transport) runs as one
SPMD program.  Process 0 writes the gathered results to ``--out`` for
the parent to compare against its single-process run.

Usage: python multihost_worker.py <pid> <coord_port> <out.npz> [mode]

``mode`` (default "replicate") selects the multi-chip decomposition:
"spatial" runs the ISSUE-5 latitude-stripe mode — every process
executes the identical spatial refresh (stripe sort + caller-slot
re-bucketing) on its host copy, places the re-bucketed state and the
device-divisible partner table shard-by-shard, and the halo exchange's
collective-permutes cross the process boundary over gloo.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
# cross-process CPU collectives ride the gloo transport
jax.config.update("jax_cpu_collectives_implementation", "gloo")


def main():
    pid, port, outfile = int(sys.argv[1]), sys.argv[2], sys.argv[3]
    mode = sys.argv[4] if len(sys.argv) > 4 else "replicate"
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

    from bluesky_tpu.parallel import sharding
    # The line under test: jax.distributed.initialize through the
    # framework's own entry point (SURVEY §5.8 scale-out role).
    sharding.init_multihost(coordinator_address=f"127.0.0.1:{port}",
                            num_processes=2, process_id=pid)
    assert len(jax.devices()) == 8, "job mesh must span both processes"
    assert len(jax.local_devices()) == 4

    import numpy as np
    from jax.experimental import multihost_utils

    from bluesky_tpu.core.step import SimConfig
    from test_sharding import make_mixed_scene  # noqa: F401

    nsteps = 25
    mesh = sharding.make_mesh()          # all 8 job devices
    if mode == "spatial":
        from test_spatial import make_scene
        cfg = SimConfig(cd_backend="sparse", cd_block=256,
                        cd_shard_mode="spatial")
        # deterministic refresh: every process computes the identical
        # re-bucketed state, then places only its own shards
        scene, _, sp_info = sharding.prepare_spatial(
            make_scene(), mesh, cfg.asas, put=False)
        cfg = cfg._replace(cd_halo_blocks=sp_info["halo_blocks"])
        shardings = sharding.spatial_state_shardings(scene, mesh)
    else:
        cfg = SimConfig(cd_backend="sparse", cd_block=256)
        scene = make_mixed_scene()
        shardings = sharding.state_shardings(scene, mesh)
    # Every process builds the identical host state; place it onto the
    # global mesh shard-by-shard (each process materialises only the
    # shards its local devices own).

    def put(leaf, sh):
        host = np.asarray(leaf)
        return jax.make_array_from_callback(host.shape, sh,
                                            lambda idx: host[idx])

    st = jax.tree.map(put, scene, shardings)
    out = jax.block_until_ready(
        sharding.sharded_step_fn(mesh, cfg, nsteps=nsteps)(st))

    gathered = {
        name: np.asarray(multihost_utils.process_allgather(
            getattr(out.ac, name), tiled=True))
        for name in ("lat", "lon", "alt", "hdg", "trk", "tas", "gs", "vs")
    }
    gathered["inconf"] = np.asarray(multihost_utils.process_allgather(
        out.asas.inconf, tiled=True))
    gathered["active"] = np.asarray(multihost_utils.process_allgather(
        out.asas.active, tiled=True))
    gathered["nconf"] = np.asarray(int(out.asas.nconf_cur))
    gathered["nlos"] = np.asarray(int(out.asas.nlos_cur))
    gathered["simt"] = np.asarray(float(out.simt))
    if pid == 0:
        np.savez(outfile, **gathered)
    # Keep both processes alive until the save completes (the job tears
    # down collectively).
    multihost_utils.sync_global_devices("done")
    print(f"worker {pid} done", flush=True)


if __name__ == "__main__":
    main()
