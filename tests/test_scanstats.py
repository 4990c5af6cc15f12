"""Fold-correctness oracle for in-scan telemetry (obs/scanstats.py).

The ISSUE-14 contract is that every ScanStats field is an EXACT fold:
int32 sums are associative, mins/maxes are order-free, and histogram
bucket counts add — so one 20-step chunk's accumulator pack must equal
the ``reduce_packs`` reduction of twenty 1-step-chunk edge packs on the
same scenario, bit for bit.  Pinned under all three runners:

* plain single-world chunk scan (``run_steps_edge``),
* world-batched W=3 (``run_steps_worlds_edge`` + ``world_slice`` demux),
* spatial 4-device stripes on the 8-device virtual CPU mesh
  (``sharding.sharded_step_fn`` — slow-marked, interpret-mode kernels),
  where the ``[P]`` per-device partials and the documented mesh
  limitations (min_sep +inf) are asserted too.

Also pins the device-histogram <-> host-registry bucket parity: the
``searchsorted(side='left')`` device bucketing must agree with the
``bisect_left`` the registry ``Histogram.observe`` uses, so drained
counts merge count-exactly.
"""
import bisect

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluesky_tpu.core.step import (SimConfig, run_steps_edge,
                                   run_steps_worlds_edge, stack_worlds,
                                   world_slice)
from bluesky_tpu.core.traffic import Traffic
from bluesky_tpu.obs import scanstats as ss

NSTEPS = 20


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _make_state(n=24, nmax=32, seed=0, lat0=52.0, pair_matrix=True):
    """Clustered scene: a tight box around ``lat0`` at mixed-but-close
    altitudes, so CD sees conflicts/LoS within the first interval and
    the folds accumulate non-trivial values."""
    rng = np.random.default_rng(seed)
    traf = Traffic(nmax=nmax, dtype=jnp.float32, pair_matrix=pair_matrix)
    traf.create(n, "B744",
                rng.uniform(9000.0, 9300.0, n),
                rng.uniform(140.0, 200.0, n), None,
                lat0 + rng.uniform(-0.15, 0.15, n),
                4.0 + rng.uniform(-0.2, 0.2, n),
                rng.uniform(0.0, 360.0, n))
    traf.flush()
    return traf.state


def _assert_packs_equal(got, want, where=""):
    for f in ss.ScanStats._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
            err_msg=f"{where}ScanStats.{f} fold is not exact")


def _trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y),
                              equal_nan=True) for x, y in zip(la, lb))


def _sanity(pack, nsteps=NSTEPS):
    """The scene must exercise the folds, and internal invariants must
    hold (each per-step histogram observes exactly one bucket/step)."""
    assert int(np.asarray(pack.steps)) == nsteps
    assert int(np.asarray(pack.conf_peak)) > 0, \
        "scene must produce conflicts or the oracle proves nothing"
    assert int(np.sum(np.asarray(pack.conf_hist))) == nsteps
    assert int(np.sum(np.asarray(pack.los_hist))) == nsteps
    assert int(np.asarray(pack.conf_sum)) \
        <= nsteps * int(np.asarray(pack.conf_peak))
    assert np.all(np.asarray(pack.live_rowsteps) >= 0)


def _oracle_plain(cfg, state):
    """One NSTEPS chunk vs NSTEPS 1-step chunks: states bit-equal AND
    stats packs reduce exactly."""
    big_state, _, big, _ = run_steps_edge(_copy(state), cfg, NSTEPS,
                                          checked=True)
    big = jax.device_get(big)

    s = _copy(state)
    packs = []
    for _ in range(NSTEPS):
        s, _, p, _ = run_steps_edge(s, cfg, 1, checked=True)
        packs.append(jax.device_get(p))
    assert _trees_equal(big_state, s), \
        "chunking changed the stepped state; stats oracle is moot"
    return big, ss.reduce_packs(packs)


def test_fold_oracle_plain_dense():
    big, small = _oracle_plain(SimConfig(scanstats=True),
                               _make_state())
    _sanity(big)
    _assert_packs_equal(small, big)
    # single-device: min_sep engages (finite) once pairs are tracked
    assert np.isfinite(np.asarray(big.min_sep_m)).all()
    assert np.isfinite(np.asarray(big.headroom_min_m)).all()


def test_fold_oracle_plain_tiled():
    cfg = SimConfig(cd_backend="tiled", cd_block=32, scanstats=True)
    big, small = _oracle_plain(cfg, _make_state(pair_matrix=False))
    _sanity(big)
    _assert_packs_equal(small, big)


def test_fold_oracle_worlds():
    """W=3 different scenarios: the [W]-leading stats demux per world
    and each world's fold is exact — and equals the same world run
    unbatched (no cross-world leakage through the stats carry)."""
    cfg = SimConfig(scanstats=True)
    states = [_make_state(n=16 + 4 * w, seed=w, lat0=50.0 + w)
              for w in range(3)]

    wstate, _, wbig, _ = run_steps_worlds_edge(
        stack_worlds([_copy(s) for s in states]), cfg, NSTEPS,
        checked=True)
    wbig = jax.device_get(wbig)
    assert np.asarray(wbig.steps).shape == (3,)

    ws = stack_worlds([_copy(s) for s in states])
    packs = []
    for _ in range(NSTEPS):
        ws, _, p, _ = run_steps_worlds_edge(ws, cfg, 1, checked=True)
        packs.append(jax.device_get(p))
    assert _trees_equal(wstate, ws)

    for w in range(3):
        big_w = world_slice(wbig, w)
        small_w = ss.reduce_packs([world_slice(p, w) for p in packs])
        _assert_packs_equal(small_w, big_w, where=f"world {w}: ")
        # no leakage: world w batched == world w alone
        solo, _, solo_pack, _ = run_steps_edge(_copy(states[w]), cfg,
                                               NSTEPS, checked=True)
        _assert_packs_equal(jax.device_get(solo_pack), big_w,
                            where=f"world {w} solo-vs-batched: ")
    _sanity(world_slice(wbig, 0))


def test_summarize_merge_consistency():
    """``merge_summaries`` over per-chunk summaries must agree with
    ``summarize(reduce_packs(...))`` on every worst-case field (peaks,
    minima, ratios are fold-order-free; the mean is steps-weighted)."""
    cfg = SimConfig(scanstats=True)
    s = _copy(_make_state())
    packs = []
    for _ in range(4):
        s, _, p, _ = run_steps_edge(s, cfg, 5, checked=True)
        packs.append(jax.device_get(p))
    merged = ss.merge_summaries([ss.summarize(p) for p in packs])
    whole = ss.summarize(ss.reduce_packs(packs))
    assert merged["steps"] == whole["steps"] == 20
    for key in ("conf_peak", "los_peak", "min_sep_m",
                "alt_headroom_min_m", "occ_peak"):
        assert merged[key] == whole[key], key
    # the steps-weighted mean re-derives the global mean up to the
    # per-chunk rounding summarize applies
    assert merged["conf_mean"] == pytest.approx(whole["conf_mean"],
                                                abs=2e-3)


def test_device_bucketing_matches_host_histogram():
    """Device ``searchsorted(side='left')`` == host ``bisect_left``:
    the exact per-value bucket parity that makes ``drain`` merge the
    device histogram into the registry count-exactly (incl. the edges:
    a count equal to an upper bound lands in that bucket on both)."""
    bounds = list(ss.COUNT_BUCKETS)
    dev = jnp.searchsorted(jnp.asarray(bounds, jnp.float32),
                           jnp.arange(0, 5200, dtype=jnp.float32),
                           side="left")
    host = [bisect.bisect_left(bounds, float(v)) for v in range(0, 5200)]
    np.testing.assert_array_equal(np.asarray(dev), host)


# --------------------------------------------------------------- spatial
# Interpret-mode sparse kernels over the virtual mesh are multi-minute:
# slow lane only, like tests/test_spatial.py.

@pytest.mark.slow
def test_fold_oracle_spatial():
    """Spatial stripes on a 4-device mesh: the [P]=4 per-device partial
    folds reduce exactly across chunk splits, occupancy partials match
    the stripe populations, and the documented mesh limitation holds
    (min_sep_m reports +inf — no pair gathers are added in-scan)."""
    from bluesky_tpu.parallel import sharding

    assert len(jax.devices()) >= 8, "conftest must provision 8 devices"
    mesh = sharding.make_mesh(4)
    nmax, n = 1024, 400
    rng = np.random.default_rng(7)
    traf = Traffic(nmax=nmax, dtype=jnp.float32, pair_matrix=False)
    traf.create(n, "B744",
                rng.uniform(4900.0, 5100.0, n),
                rng.uniform(140.0, 180.0, n), None,
                rng.uniform(35.0, 60.0, n),
                rng.uniform(-10.0, 30.0, n),
                rng.uniform(0.0, 360.0, n))
    traf.flush()
    cfg = SimConfig(cd_backend="sparse", cd_block=256,
                    cd_shard_mode="spatial", scanstats=True)
    st, _, info = sharding.prepare_spatial(traf.state, mesh, cfg.asas)
    cfg = cfg._replace(cd_halo_blocks=info["halo_blocks"])
    # host master copy: each run below gets a fresh placement so the
    # donated buffers of one run cannot alias the other's input
    host = jax.tree_util.tree_map(np.asarray, st)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda x, sh: jax.device_put(np.copy(x), sh), tree,
            sharding.spatial_state_shardings(st, mesh))

    big_state, big = sharding.sharded_step_fn(mesh, cfg,
                                              nsteps=NSTEPS)(place(host))
    big = jax.device_get(big)

    one = sharding.sharded_step_fn(mesh, cfg, nsteps=1)
    s = place(host)
    packs = []
    for _ in range(NSTEPS):
        s, p = one(s)
        packs.append(jax.device_get(p))
    assert _trees_equal(big_state, s)
    _assert_packs_equal(ss.reduce_packs(packs), big,
                        where="spatial: ")
    _sanity(big)

    # [P] partials: one row-split partial per mesh device
    assert np.asarray(big.occ_peak).shape == (4,)
    # occupancy peak per stripe == that device's caller population
    # (populations are constant here: nothing is created or deleted)
    counts = np.asarray(host.ac.active).reshape(4, -1).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(big.occ_peak), counts)
    np.testing.assert_array_equal(
        np.asarray(big.live_rowsteps), counts * NSTEPS)
    # documented limitation: pair-gather stats are +inf under a mesh
    assert np.all(np.isinf(np.asarray(big.min_sep_m)))
    # headroom is a pure row fold: stays finite per partial
    assert np.isfinite(np.asarray(big.headroom_min_m)).all()
