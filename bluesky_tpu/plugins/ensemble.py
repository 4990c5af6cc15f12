"""Device-side Monte-Carlo ensembles: ENSEMBLE n time [spread].

The reference parallelizes Monte-Carlo studies as a PROCESS farm (the
server's BATCH split, network/server.py) — one OS process per replica.
This plugin is the TPU-first counterpart with no reference equivalent:
the CURRENT traffic scene is replicated on-device with per-replica
initial-condition jitter and stepped as ONE vmapped SPMD program
(``parallel.sharding.ensemble_step_fn``), so a 64-replica study of a
500-aircraft scene costs one kernel launch per chunk instead of 64
processes.  On a multi-device mesh the replicas shard over the 'ens'
axis with zero cross-device traffic.

Usage from the stack:

    CRE ... / IC scenario.scn        # set up the scene
    ENSEMBLE 32 60 500               # 32 replicas, 60 sim-s, 500 m jitter

Reports conflict/LoS count statistics across the ensemble — the
uncertainty band the reference MC studies compute from BATCH logs.
"""
import numpy as np


def init_plugin(sim):
    ens = Ensemble(sim)
    config = {
        "plugin_name": "ENSEMBLE",
        "plugin_type": "sim",
    }
    stackfunctions = {
        "ENSEMBLE": [
            "ENSEMBLE nreps,time[,spread]",
            "int,float,[float]",
            ens.run,
            "Monte-Carlo the current scene on-device: nreps jittered "
            "replicas stepped as one vmapped program",
        ],
    }
    return config, stackfunctions


class Ensemble:
    MAX_SLOTS = 2_000_000        # nmax*nreps guard (device memory)

    def __init__(self, sim):
        self.sim = sim
        self.last = None         # stats dict of the last run
        self._runs = 0           # per-call entropy for the jitter keys
        self._cache = {}         # (cfg, nreps, nmax, nsteps) -> runner

    def run(self, nreps, tend, spread=500.0):
        import jax
        import jax.numpy as jnp
        from ..parallel import sharding

        sim = self.sim
        nreps = int(nreps)
        n = sim.traf.ntraf
        if n == 0:
            return False, "ENSEMBLE: no traffic in the scene"
        if nreps < 2:
            return False, "ENSEMBLE: need at least 2 replicas"
        nmax = sim.traf.state.nmax
        if nmax * nreps > self.MAX_SLOTS:
            return False, (f"ENSEMBLE: {nreps} x nmax {nmax} exceeds "
                           f"{self.MAX_SLOTS} slots — shrink one")
        # A state under the dense backend carries the [nmax, nmax] pair
        # matrix, which every replica would copy — bound that memory too.
        if sim.cfg.cd_backend == "dense" \
                and nmax * nmax * nreps > 256_000_000:
            return False, ("ENSEMBLE: the [N,N] pair matrix x nreps "
                           "would exceed device memory — switch to a "
                           "blockwise backend (CDMETHOD TILED) for "
                           "large ensembles")
        sim.traf.flush()
        base = sim.traf.state

        # Per-replica initial-condition jitter: gaussian position noise
        # of ``spread`` meters (and ~1 kt speed noise) on active slots —
        # the classic MC-over-uncertainty setup the reference runs as
        # BATCH process replicas.  A run counter folds into the key so
        # repeated ENSEMBLE calls draw fresh replicas.
        self._runs += 1
        key = jax.random.fold_in(
            jax.random.PRNGKey(int(np.asarray(base.rng)[-1])), self._runs)
        keys = jax.random.split(key, nreps)
        act = base.ac.active

        def jitter(state_key):
            # 5-way split: four noise draws + a FRESH stream for the
            # replica's in-sim rng (split is prefix-stable, so reusing
            # state_key would alias the first step's noise keys onto
            # the jitter draws)
            k1, k2, k3, k4, knew = jax.random.split(state_key, 5)
            dtype = base.ac.lat.dtype
            mlat = spread / 111_000.0
            mlon = mlat / jnp.maximum(
                jnp.cos(jnp.radians(base.ac.lat)), 0.2)
            noise = lambda k, s: jax.random.normal(
                k, base.ac.lat.shape, dtype) * s
            ac = base.ac.replace(
                lat=jnp.where(act, base.ac.lat + noise(k1, mlat),
                              base.ac.lat),
                lon=jnp.where(act, base.ac.lon + noise(k2, mlon),
                              base.ac.lon),
                tas=jnp.where(act, base.ac.tas + noise(k3, 0.5),
                              base.ac.tas),
                gs=jnp.where(act, base.ac.gs + noise(k4, 0.5),
                             base.ac.gs))
            return base.replace(ac=ac, rng=knew)

        states = jax.vmap(jitter)(keys)
        # Inherit the sim's FULL config (simdt, noise, ASAS settings);
        # only the replica-hostile pieces change: dense CD above a size
        # threshold becomes tiled, and any aircraft-axis mesh is
        # dropped (replicas shard on 'ens', not 'ac').
        backend = sim.cfg.cd_backend
        if backend == "dense" and nmax > 4096:
            backend = "tiled"
        cfg = sim.cfg._replace(cd_backend=backend, cd_mesh=None)

        # Step in CD-interval chunks, accumulating per-replica peak and
        # time-mean counts — sampling only the final step would miss
        # every conflict that resolves before tend.  The compiled chunk
        # runner is cached across calls (a fresh jit closure per call
        # would recompile the scan every time).
        chunk = max(1, int(round(cfg.asas.dtasas / cfg.simdt)))
        # Cover tend exactly: whole CD-interval chunks plus one
        # remainder chunk (rounding tend to whole chunks could silently
        # simulate up to half a CD interval more or less than asked).
        total = max(1, int(round(float(tend) / cfg.simdt)))
        nchunks, rem = divmod(total, chunk)
        plan = [chunk] * nchunks + ([rem] if rem else [])

        def get_runner(nsteps):
            ck = (cfg, nreps, nmax, nsteps)
            runner = self._cache.get(ck)
            if runner is None:
                mesh = sharding.make_ensemble_mesh(
                    min(nreps, len(jax.devices())))
                runner = sharding.ensemble_step_fn(mesh, cfg,
                                                   nsteps=nsteps)
                if len(self._cache) > 2:    # keep the latest plan only
                    self._cache = {}
                self._cache[ck] = runner
                self._ndev = mesh.devices.size
            return runner

        peak_conf = np.zeros(nreps)
        peak_los = np.zeros(nreps)
        sum_conf = np.zeros(nreps)
        sum_los = np.zeros(nreps)
        for nsteps in plan:
            states = get_runner(nsteps)(states)
            nconf = np.asarray(states.asas.nconf_cur) / 2.0  # pairs
            nlos = np.asarray(states.asas.nlos_cur) / 2.0
            peak_conf = np.maximum(peak_conf, nconf)
            peak_los = np.maximum(peak_los, nlos)
            sum_conf += nconf
            sum_los += nlos
        mean_conf = sum_conf / len(plan)
        mean_los = sum_los / len(plan)

        self.last = dict(nreps=nreps, tend=float(tend),
                         spread=float(spread),
                         peak_conf_mean=float(peak_conf.mean()),
                         peak_conf_std=float(peak_conf.std()),
                         mean_conf_mean=float(mean_conf.mean()),
                         peak_los_mean=float(peak_los.mean()),
                         mean_los_mean=float(mean_los.mean()))
        return True, (
            f"ENSEMBLE {nreps} x {float(tend):.0f}s (jitter "
            f"{float(spread):.0f} m) on {self._ndev} device(s), "
            f"conflict PAIRS sampled each CD interval:\n"
            f"  peak conflicts {peak_conf.mean():.1f} "
            f"+- {peak_conf.std():.1f} "
            f"(min {peak_conf.min():.0f}, max {peak_conf.max():.0f})\n"
            f"  mean conflicts {mean_conf.mean():.2f} "
            f"+- {mean_conf.std():.2f}\n"
            f"  peak LoS       {peak_los.mean():.1f} "
            f"+- {peak_los.std():.1f}")
