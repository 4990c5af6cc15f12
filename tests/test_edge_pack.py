"""The edge pack (core/step.py ``EdgePack``): the chunk program returns
the edge's telemetry as one pack of four buffers, ``unpack_telemetry``
gives every ``EdgeTelemetry`` field back bit for bit, the pack outlives
the next chunk's donation, and ``ChunkEdge`` (simulation/pipeline.py)
reads its scalars without the bulk pull."""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bluesky_tpu.core.step import (EdgeTelemetry, PACK_ROWS, SimConfig,
                                   run_steps_edge, run_steps_edge_keep,
                                   unpack_telemetry)
from bluesky_tpu.core.traffic import Traffic
from bluesky_tpu.simulation.pipeline import ChunkEdge

CHUNK = 25      # past the first CD interval, so the ASAS fields are set


def _scene(dtype=jnp.float32, nmax=16):
    """Two aircraft head on (in conflict from the first CD interval)
    and a bystander; the rest of the slots padding."""
    traf = Traffic(nmax=nmax, dtype=dtype)
    traf.create(1, "B744", 5000.0, 150.0, None, 50.0, 4.0, 90.0, "AC0")
    traf.create(1, "B744", 5000.0, 150.0, None, 50.0, 4.3, 270.0, "AC1")
    traf.create(1, "A320", 8000.0, 200.0, None, 55.0, 9.0, 10.0, "AC2")
    traf.flush()
    return traf.state


def _fields_of(state) -> EdgeTelemetry:
    """Every telemetry field as the post-chunk state holds it, on the
    host (a clean chunk's guard word is -1)."""
    ac, asas = state.ac, state.asas
    return jax.device_get(EdgeTelemetry(
        simt=state.simt, nstep=state.nstep, bad=np.int32(-1),
        nconf_cur=asas.nconf_cur, nlos_cur=asas.nlos_cur,
        active=ac.active, lat=ac.lat, lon=ac.lon, alt=ac.alt,
        hdg=ac.hdg, trk=ac.trk, tas=ac.tas, gs=ac.gs, cas=ac.cas,
        vs=ac.vs, inconf=asas.inconf, tcpamax=asas.tcpamax,
        asasn=asas.asasn, asase=asas.asase))


def _same(got: EdgeTelemetry, want: EdgeTelemetry):
    for name in EdgeTelemetry._fields:
        g, w = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        assert g.dtype == w.dtype, name
        assert g.shape == w.shape, name
        assert np.array_equal(g, w, equal_nan=True), name


def test_every_field_has_one_row():
    rows = PACK_ROWS.ints + PACK_ROWS.cols + PACK_ROWS.masks + ("simt",)
    assert sorted(rows) == sorted(EdgeTelemetry._fields)


@pytest.mark.parametrize("checked", [False, True],
                         ids=["unchecked", "checked"])
def test_lowered_program_returns_at_most_four_pack_buffers(checked):
    state = _scene()
    out = run_steps_edge.lower(state, SimConfig(), CHUNK,
                               checked=checked).out_info
    leaves = jax.tree_util.tree_leaves
    assert len(leaves(out[1])) <= 4
    # the stepped state and the pack are all the program returns
    assert len(leaves(out)) == len(leaves(state)) + len(leaves(out[1]))


@pytest.mark.parametrize("where", ["device", "host"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                         ids=["float32", "float64"])
def test_unpack_is_bit_identical_to_the_state(dtype, where):
    state, pack, _, _ = run_steps_edge_keep(_scene(dtype), SimConfig(),
                                            CHUNK, checked=True)
    want = _fields_of(state)
    assert want.lat.dtype == np.dtype(dtype)
    assert int(want.nconf_cur) == 2 and want.inconf.sum() == 2
    if where == "host":
        pack = jax.device_get(pack)
    _same(unpack_telemetry(pack), want)


def test_pack_outlives_the_next_donated_chunk():
    """The pipelining contract: an edge's pack is read after the next
    chunk was dispatched with the state donated."""
    state, pack, _, _ = run_steps_edge(_scene(), SimConfig(), CHUNK,
                                       checked=True)
    want = _fields_of(state)
    state, later, _, _ = run_steps_edge(state, SimConfig(), CHUNK,
                                        checked=True)
    jax.block_until_ready(state)
    assert not any(b.is_deleted() for b in pack)
    _same(unpack_telemetry(jax.device_get(pack)), want)
    assert int(unpack_telemetry(later).nstep) == 2 * CHUNK


@pytest.fixture
def edge_and_fields():
    state, pack, _, _ = run_steps_edge_keep(_scene(), SimConfig(), CHUNK,
                                            checked=True)
    return (ChunkEdge(pack, CHUNK, lambda n: n * 0.05),
            _fields_of(state))


@pytest.mark.parametrize("prop,of", [
    ("bad_step", lambda f: f.bad), ("simt_device", lambda f: f.simt),
    ("nstep_device", lambda f: f.nstep),
    ("conf_pairs", lambda f: f.nconf_cur // 2)],
    ids=lambda v: v if isinstance(v, str) else "")
def test_chunk_edge_reads_a_scalar_without_the_bulk_fetch(
        edge_and_fields, prop, of):
    edge, want = edge_and_fields
    assert getattr(edge, prop) == of(want)
    assert not edge.fetched
    # ... and the same once the bulk fetch has come
    edge.fetch()
    assert getattr(edge, prop) == of(want)


def test_chunk_edge_fields_and_acdata_are_the_states(edge_and_fields):
    edge, want = edge_and_fields
    idx, data = edge.acdata_arrays()
    assert edge.fetched
    _same(edge.fetch(), want)
    assert np.array_equal(edge.lat, want.lat)      # __getattr__
    assert np.array_equal(idx, np.flatnonzero(want.active))
    assert list(data) == ["lat", "lon", "alt", "trk", "tas", "gs", "cas",
                          "vs", "inconf", "tcpamax", "asasn", "asase"]
    for name, col in data.items():
        assert col.dtype == getattr(want, name).dtype, name
        assert np.array_equal(col, getattr(want, name)[idx]), name
    with pytest.raises(AttributeError, match="no field 'nope'"):
        edge.nope


# --------------------------------------------------------------- the gauge
def _dump_gauge(sim):
    sim.scr.echobuf.clear()
    sim.stack.stack("METRICS DUMP")
    sim.stack.process()
    m = re.search(r"^sim_edge_pack_buffers: (\d+) \(gauge\)$",
                  "\n".join(sim.scr.echobuf), re.M)
    assert m, "sim_edge_pack_buffers is not in METRICS DUMP"
    return int(m.group(1))


def _piece(acid, lat):
    return ([0.0, 0.0, 0.0],
            [f"SCEN {acid}", f"CRE {acid} B744 {lat} 4 90 FL200 250",
             "FF 10"])


def _stepped_solo():
    from bluesky_tpu.simulation.sim import Simulation, OP
    sim = Simulation(nmax=16)
    sim.stack.set_scendata(*map(list, _piece("AAA1", 52.0)))
    sim.op()
    for _ in range(5000):
        if sim.state_flag != OP:
            break
        sim.step()
    return [sim]


def _stepped_pack():
    from bluesky_tpu.simulation.worlds import WorldBatch
    wb = WorldBatch([_piece("AAA1", 52.0), _piece("BBB2", 48.0)],
                    simkw=dict(nmax=16))
    assert wb.run(max_iters=5000) == ["completed"] * 2
    assert wb.stats["joint_dispatches"] > 0
    return wb.sims


@pytest.mark.parametrize("stepped", [_stepped_solo, _stepped_pack],
                         ids=["one_world", "world_pack"])
def test_gauge_reads_at_most_four_in_metrics_dump(stepped):
    for sim in stepped():
        assert 1 <= _dump_gauge(sim) <= 4


def test_gauge_is_set_again_after_a_reset():
    sim, = _stepped_solo()
    sim.obs.get("sim_edge_pack_buffers").set(19)
    sim.reset()
    sim.stack.set_scendata(*map(list, _piece("CCC3", 44.0)))
    sim.op()
    sim.step()
    sim.step()
    assert _dump_gauge(sim) == 4
