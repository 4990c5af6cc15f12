"""The dense CD&R interval over the slots a fleet occupies (PR 36).

``SimConfig.cd_rows`` makes ``core/asas.update`` run on the leading rows
of the state and pad its outputs back; ``core/step.cd_dense_rows`` is the
rule that fills it in at every dispatch from ``Traffic.slot_bound``.
``nmax`` is 256 here so that the second rung exists and the programs
stay small.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluesky_tpu.core.step import (SimConfig, cd_dense_rows, run_steps,
                                   run_steps_edge)
from bluesky_tpu.simulation.sim import Simulation

NMAX = 256


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _wall(nmax=NMAX):
    """``SYN WALL``'s fleet: one ownship against a wall of twenty."""
    sim = Simulation(nmax=nmax)
    sim.stack.stack("SEED 1; SYN WALL")
    sim.stack.process()
    assert sim.traf.ntraf == 21 and sim.traf.slot_bound == 21
    return sim


@pytest.mark.parametrize("bound,nmax,rows,field", [
    (0, 1024, 128, 128), (21, 1024, 128, 128), (128, 1024, 128, 128),
    (129, 1024, 256, 256), (513, 1024, 1024, 0), (600, 1000, 1000, 0),
    (21, 64, 64, 0), (21, 128, 128, 0), (200, 256, 256, 0)])
def test_ladder(bound, nmax, rows, field):
    """128, 256, 512, ... up to ``nmax``; the whole-fleet program keeps
    the one key it had (``cd_rows`` 0), so ``nmax`` <= 128 never
    changes."""
    cfg, got = cd_dense_rows(SimConfig(), nmax, bound)
    assert got == rows and cfg.cd_rows == field
    assert cfg._replace(cd_rows=0) == SimConfig()


@pytest.mark.parametrize("method", ["MVP", "EBY", "SWARM", "SSD"])
def test_rows_equal_whole_fleet(method):
    """Forty CD intervals of the wall with the interval on 128 rows and
    on all 256: flags, counts, pair matrix and engagement bit-equal at
    every tenth interval, the resolution to float32 round-off."""
    sim = _wall()
    cfg = sim.cfg._replace(asas=sim.cfg.asas._replace(reso_method=method))
    whole, head = _copy(sim.traf.state), _copy(sim.traf.state)
    seen = 0
    for _ in range(4):
        whole = run_steps(whole, cfg, 200)
        head = run_steps(head, cfg._replace(cd_rows=128), 200)
        for f in ("inconf", "active", "resopairs", "nconf_cur",
                  "nlos_cur"):
            assert np.array_equal(np.asarray(getattr(whole.asas, f)),
                                  np.asarray(getattr(head.asas, f))), f
        for f in ("trk", "tas", "vs", "alt", "tcpamax"):
            np.testing.assert_allclose(
                np.asarray(getattr(head.asas, f)),
                np.asarray(getattr(whole.asas, f)),
                rtol=1e-6, atol=1e-5, err_msg=f)
        seen += int(whole.asas.nconf_cur)
    assert seen > 0, "the wall never came into conflict"
    assert float(whole.simt) == float(head.simt)
    assert not np.asarray(head.asas.resopairs)[128:].any()


def _hist(sim):
    h = sim.obs.get("sim_cd_dense_rows")
    return h.sum, h.count


def test_creation_past_the_rung_raises_it():
    """A fleet that fills the first rung runs on 128 rows; two aircraft
    created head-on into slots 128 and 129 raise the next dispatch to
    256, and both are in conflict after their first interval."""
    sim = Simulation(nmax=NMAX)
    n = 128
    sim.traf.create(n, "B744", 6000.0, 150.0, None,
                    np.linspace(-60.0, 60.0, n),
                    np.linspace(-170.0, 170.0, n), 90.0)
    sim.op()
    assert sim.traf.slot_bound == 128 and sim.chunk_cfg()[1] == 128
    sim.run(until_simt=2.0)
    s0, c0 = _hist(sim)
    assert c0 > 0 and s0 == 128 * c0
    assert int(sim.traf.state.asas.nconf_cur) == 0
    sim.stack.stack("CRE OWN B744 10 10 90 FL200 250")
    sim.stack.stack("CRE INT B744 10 10.3 270 FL200 250")
    sim.stack.process()
    assert [sim.traf.id2idx(a) for a in ("OWN", "INT")] == [128, 129]
    assert sim.chunk_cfg()[1] == 256
    sim.run(until_simt=float(sim.simt) + 1.05)
    s1, c1 = _hist(sim)
    assert c1 > c0 and s1 - s0 == 256 * (c1 - c0)
    asas = sim.traf.state.asas
    assert np.asarray(asas.inconf)[[128, 129]].all()
    assert int(asas.nconf_cur) == 2
    # and back down once they are gone
    sim.stack.stack("DEL OWN; DEL INT")
    sim.stack.process()
    assert sim.chunk_cfg()[1] == 128
    sim.run(until_simt=float(sim.simt) + 1.05)
    assert not np.asarray(sim.traf.state.asas.resopairs).any()


def test_world_pack_takes_its_largest_bound():
    """One program a pack, so one row count: the largest of its
    worlds', observed once a joint dispatch."""
    from bluesky_tpu.simulation.worlds import WorldBatch
    big = [f"CRE B{k:03d} B744 {-60 + k * 0.9:.2f} {-170 + k * 2.6:.2f} "
           "90 FL200 250" for k in range(130)]
    pieces = [([0.0] * 3, ["SCEN SMALL", "CRE A1 B744 52 4 90 FL200 250",
                           "FF 5"]),
              ([0.0] * (len(big) + 2), ["SCEN BIG"] + big + ["FF 5"])]
    wb = WorldBatch(pieces, simkw=dict(nmax=NMAX))
    assert wb.run(max_iters=2000) == ["completed"] * 2
    assert wb.stats["joint_dispatches"] > 0
    assert [s.traf.slot_bound for s in wb.sims] == [1, 130]
    sums, counts = zip(*(_hist(s) for s in wb.sims))
    assert sum(counts) >= wb.stats["joint_dispatches"]
    assert sum(sums) == 256 * sum(counts)


def test_smooth_and_mesh_stand_down():
    """The differentiable mode and a device mesh on the dense path run
    the whole-fleet program, and the counter says so."""
    from bluesky_tpu.diff.smooth import SmoothConfig
    base = SimConfig()
    for cfg in (base._replace(smooth=SmoothConfig()),
                base._replace(cd_mesh=object())):
        got, rows = cd_dense_rows(cfg, NMAX, 21)
        assert rows == NMAX and got == cfg
    sim = _wall()
    assert sim.chunk_cfg()[1] == 128
    sim.set_shard("replicate", 2)
    cfg, rows = sim.chunk_cfg()
    assert rows == NMAX and cfg.cd_rows == 0
    sim.set_shard("off")
    assert sim.chunk_cfg()[1] == 128


def test_sparse_program_does_not_read_the_field():
    """The other backends get no row count, and their lowered chunk
    program is the same text whatever the field holds."""
    sim = _wall(nmax=64)
    sim.stack.stack("CDMETHOD SPARSE")
    sim.stack.process()
    sim.traf.flush()
    cfg, rows = sim.chunk_cfg()
    assert rows is None and cfg is sim.cfg and cfg.cd_rows == 0
    state = sim.traf.state
    texts = [run_steps_edge.lower(state, cfg._replace(cd_rows=r), 20,
                                  checked=True).as_text()
             for r in (0, 32)]
    assert texts[0] == texts[1]
