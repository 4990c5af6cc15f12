"""Multi-world batched stepping: correctness of the world axis.

Pins the three contracts the packing layer builds on
(docs/PERF_ANALYSIS.md §multi-world):

* W=1 batched stepping is BIT-identical to the unbatched scan (the
  vmap+hoisted-gate formulation changes no value, acceptance
  criterion of ISSUE 6);
* W worlds with different scenarios step exactly like W independent
  runs (no cross-world leakage through the stacked carry);
* the in-scan integrity guard pins a (world, step) pair, and the
  WorldBatch runner quarantines ONLY the faulty world.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluesky_tpu.core.step import (SimConfig, run_steps,
                                   run_steps_worlds_edge, stack_worlds,
                                   unstack_worlds, world_slice,
                                   pack_telemetry, unpack_telemetry)
from bluesky_tpu.core.traffic import Traffic


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _make_state(n=24, nmax=32, seed=0, lat0=45.0):
    rng = np.random.default_rng(seed)
    traf = Traffic(nmax=nmax, dtype=jnp.float32)
    traf.create(n, "B744",
                rng.uniform(3000.0, 11000.0, n),
                rng.uniform(130.0, 240.0, n), None,
                lat0 + rng.uniform(-2.0, 2.0, n),
                rng.uniform(-10.0, 30.0, n),
                rng.uniform(0.0, 360.0, n))
    traf.flush()
    return traf.state


def _trees_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y),
                              equal_nan=True) for x, y in zip(la, lb))


def test_w1_bit_parity():
    """A W=1 world-batch steps bit-identically to the unbatched scan."""
    state = _make_state()
    cfg = SimConfig()
    ref = run_steps(_copy(state), cfg, 60)
    got = world_slice(
        run_steps_worlds_edge(stack_worlds([state]), cfg, 60)[0], 0)
    assert _trees_equal(ref, got)


def test_w4_independent_scenarios():
    """4 different worlds batched == 4 independent unbatched runs."""
    cfg = SimConfig()
    states = [_make_state(n=8 + 4 * i, seed=i, lat0=40.0 + 5 * i)
              for i in range(4)]
    refs = [run_steps(_copy(s), cfg, 40) for s in states]
    worlds = unstack_worlds(
        run_steps_worlds_edge(stack_worlds(states), cfg, 40)[0])
    for ref, got in zip(refs, worlds):
        assert _trees_equal(ref, got)


def test_checked_pins_world_and_step():
    """The guard word is per-world: a NaN injected into one world
    reports (that world, step 0) and leaves the others clean AND
    bit-identical to clean independent runs."""
    cfg = SimConfig()
    states = [_make_state(seed=i) for i in range(3)]
    poisoned = states[1].replace(ac=states[1].ac.replace(
        lat=states[1].ac.lat.at[2].set(jnp.nan)))
    refs = [run_steps(_copy(states[0]), cfg, 20),
            run_steps(_copy(states[2]), cfg, 20)]
    wstate, telem, _, _ = run_steps_worlds_edge(
        stack_worlds([states[0], poisoned, states[2]]), cfg, 20,
        checked=True)
    bad = np.asarray(unpack_telemetry(telem).bad)
    assert bad[1] >= 0, "poisoned world must trip"
    assert bad[0] == -1 and bad[2] == -1, "clean worlds must not trip"
    assert _trees_equal(refs[0], world_slice(wstate, 0))
    assert _trees_equal(refs[1], world_slice(wstate, 2))


def test_worlds_edge_telemetry_demux():
    """The stacked EdgeTelemetry's world slices equal each world's own
    pack (the serving demux contract)."""
    cfg = SimConfig()
    states = [_make_state(seed=i) for i in range(2)]
    refs = [run_steps(_copy(s), cfg, 10) for s in states]
    wstate, telem, _, _ = run_steps_worlds_edge(
        stack_worlds(states), cfg, 10, checked=True)
    fields = unpack_telemetry(telem)
    assert fields.simt.shape == (2,)
    assert fields.bad.shape == (2,)
    for w, ref in enumerate(refs):
        sl = unpack_telemetry(world_slice(telem, w))
        expect = unpack_telemetry(pack_telemetry(ref))
        for name in ("simt", "lat", "lon", "alt", "nconf_cur"):
            assert np.array_equal(np.asarray(getattr(sl, name)),
                                  np.asarray(getattr(expect, name)),
                                  equal_nan=True), name
        assert int(sl.bad) == -1


def test_worlds_refuse_sharded_cfg():
    """The world axis composes with single-device configs only."""
    state = _make_state()
    with pytest.raises(ValueError, match="single-device"):
        run_steps_worlds_edge(stack_worlds([state]),
                              SimConfig(cd_backend="sparse",
                                        cd_shard_mode="spatial"), 5)


# --------------------------------------------------------------- runner
def _piece(acid, lat, ff=20.0):
    return ([0.0, 0.0, 0.0],
            [f"SCEN {acid}",
             f"CRE {acid} B744 {lat} 4 90 FL200 250",
             f"FF {ff}"])


def _run_solo(piece, nmax=16):
    from bluesky_tpu.simulation.sim import Simulation, OP
    sim = Simulation(nmax=nmax)
    sim.pipeline_enabled = False
    sim.stack.set_scendata(list(piece[0]), list(piece[1]))
    sim.op()
    it = 0
    while sim.state_flag == OP and it < 5000:
        sim.step()
        it += 1
    return sim


def test_worldbatch_runner_parity():
    """WorldBatch joint dispatch == independent Simulation runs,
    bit-exactly, with the device work actually batched."""
    from bluesky_tpu.simulation.worlds import WorldBatch
    pieces = [_piece("AAA1", 52.0), _piece("BBB2", 48.0),
              _piece("CCC3", 44.0)]
    wb = WorldBatch(pieces, simkw=dict(nmax=16))
    status = wb.run(max_iters=5000)
    assert status == ["completed"] * 3
    assert wb.stats["joint_dispatches"] > 0
    assert wb.stats["max_group"] == 3
    for piece, wsim in zip(pieces, wb.sims):
        ref = _run_solo(piece)
        assert ref.simt == wsim.simt
        assert _trees_equal(ref.traf.state, wsim.traf.state)


def test_worldbatch_quarantines_only_faulty_world():
    """A NaN injected into one world mid-run trips only that world's
    guard; the other world completes bit-identically to a solo run."""
    from bluesky_tpu.simulation.worlds import WorldBatch
    pieces = [_piece("GOOD1", 52.0), _piece("BAD1", 30.0)]
    wb = WorldBatch(pieces, simkw=dict(nmax=16))
    # let the scenario set up, then poison world 1's aircraft
    assert wb.step()
    bad = wb.sims[1]
    st = bad.traf.state
    bad.traf.state = st.replace(ac=st.ac.replace(
        tas=st.ac.tas.at[0].set(jnp.nan)))
    wb.run(max_iters=5000)
    assert wb.status[0] == "completed"
    # world 1's guard quarantined its poisoned aircraft, world 0 never
    # saw a trip
    assert len(bad.guard.trips) >= 1
    assert bad.traf.ntraf == 0
    assert not wb.sims[0].guard.trips
    assert wb.sims[0].traf.ntraf == 1


def test_worldbatch_progress_payload():
    from bluesky_tpu.simulation.worlds import WorldBatch
    wb = WorldBatch([_piece("AAA1", 52.0), _piece("BBB2", 48.0)],
                    simkw=dict(nmax=16))
    p = wb.progress()
    assert p["worlds"] == 2 and p["worlds_done"] == 0
    wb.run(max_iters=5000)
    p = wb.progress()
    assert p["worlds_done"] == 2
