"""The empty state as one compiled program (ISSUE-39).

``make_state`` fills its 132 leaves in one ``jax.jit`` program per
shape.  Pinned here:

* Leaf for leaf — value, dtype, weak type, shape, tree structure — it
  is the eager body it replaced (kept below as the reference), for
  float32 and x64, three capacities, both ``pair_matrix``, two
  ``k_partners`` and seeds up to above 2**31.
* Every leaf is a buffer of its own: the state is donated whole, and
  XLA must not hand one buffer out under two leaves.
* The seed is traced: later calls with new seeds compile nothing.
* A state from it goes through a donated chunk and a donated write
  program.
* A two-piece ``BATCH`` of ``SYN WALL`` pieces through a detached node:
  the span ``make_state`` under ``piece_reset`` and under ``stack_run``,
  two observations of ``sim_make_state_ms`` a piece, no compilation in
  the second piece, and the stepped end state bit-equal to the one the
  eager body gives.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluesky_tpu.core import noise, state as statemod, traffic, wind
from bluesky_tpu.core.state import (SORT_PAD, ActWpArrays, AircraftArrays,
                                    AsasArrays, AutopilotArrays,
                                    PilotArrays, RouteArrays, SimState,
                                    make_state)
from bluesky_tpu.core.step import SimConfig, run_steps
from bluesky_tpu.models import perf_coeffs
from bluesky_tpu.obs.trace import get_recorder
from bluesky_tpu.ops import aero


def eager_make_state(nmax=64, wmax=32, dtype=jnp.float32, rng_seed=0,
                     pair_matrix=True, k_partners=8):
    """``make_state`` as it stood before ISSUE-39: one eager dispatch
    and one allocation a leaf.  The reference; change it only with the
    defaults themselves."""
    f = lambda: jnp.zeros((nmax,), dtype=dtype)
    b = lambda: jnp.zeros((nmax,), dtype=bool)
    i = lambda: jnp.zeros((nmax,), dtype=jnp.int32)
    ac = AircraftArrays(
        active=b(), lat=f(), lon=f(), alt=f(), hdg=f(), trk=f(),
        tas=f(), gs=f(), gsnorth=f(), gseast=f(), cas=f(), mach=f(), vs=f(),
        p=f(), rho=f(), temp=f(),
        selspd=f(), selalt=f(), selvs=f(),
        swlnav=b(), swvnav=b(),
        apvsdef=jnp.full((nmax,), 1500.0 * aero.fpm, dtype),
        aphi=jnp.full((nmax,), jnp.radians(25.0), dtype),
        ax=jnp.full((nmax,), aero.kts, dtype),
        bank=jnp.full((nmax,), jnp.radians(25.0), dtype),
        swhdgsel=b(), swaltsel=b(),
        abco=b(), belco=jnp.ones((nmax,), dtype=bool),
        coslat=jnp.ones((nmax,), dtype),
    )
    actwp = ActWpArrays(
        lat=jnp.full((nmax,), 89.99, dtype), lon=f(),
        nextaltco=f(), xtoalt=f(),
        spd=jnp.full((nmax,), -999.0, dtype), vs=f(),
        turndist=jnp.ones((nmax,), dtype),
        flyby=jnp.ones((nmax,), dtype),
        next_qdr=jnp.full((nmax,), -999.0, dtype),
    )
    ap = AutopilotArrays(
        trk=f(), tas=f(), alt=f(), vs=f(),
        dist2vs=jnp.full((nmax,), -999.0, dtype),
        swvnavvs=b(), vnavvs=f(),
    )
    pilot = PilotArrays(alt=f(), hdg=f(), trk=f(), vs=f(), tas=f())
    asas = AsasArrays(
        trk=f(), tas=f(), vs=f(), alt=f(),
        active=b(), inconf=b(), tcpamax=f(),
        resopairs=jnp.zeros((nmax, nmax) if pair_matrix else (0, 0),
                            dtype=bool),
        partners=jnp.full((nmax, k_partners), -1, jnp.int32),
        asasn=f(), asase=f(), noreso=b(), resooff=b(),
        nconf_cur=jnp.zeros((), jnp.int32), nlos_cur=jnp.zeros((), jnp.int32),
        sort_perm=jnp.arange(nmax, dtype=jnp.int32),
        partners_s=jnp.full((nmax + SORT_PAD, k_partners), -1, jnp.int32),
    )
    route = RouteArrays(
        wplat=jnp.full((nmax, wmax), 89.99, dtype),
        wplon=jnp.zeros((nmax, wmax), dtype),
        wpalt=jnp.full((nmax, wmax), -999.0, dtype),
        wpspd=jnp.full((nmax, wmax), -999.0, dtype),
        wpflyby=jnp.ones((nmax, wmax), dtype),
        wptoalt=jnp.full((nmax, wmax), -999.0, dtype),
        wpxtoalt=jnp.zeros((nmax, wmax), dtype),
        nwp=i(), iactwp=jnp.full((nmax,), -1, jnp.int32),
    )
    return SimState(
        ac=ac, actwp=actwp, ap=ap, pilot=pilot, asas=asas, route=route,
        perf=perf_coeffs.empty_perf_arrays(nmax, dtype),
        adsb=noise.make_adsb(nmax, dtype),
        wind=wind.make_windstate(dtype=dtype),
        rng=jax.random.PRNGKey(rng_seed),
        nstep=jnp.zeros((), jnp.int32),
        simt=jnp.zeros((), dtype),
        fms_t0=jnp.full((), -999.0, dtype),
        asas_tnext=jnp.zeros((), dtype),
    )


def assert_same_state(got, want):
    gl, gt = jax.tree_util.tree_flatten_with_path(got)
    wl, wt = jax.tree_util.tree_flatten_with_path(want)
    assert gt == wt
    assert len(gl) == len(wl) == 132
    for (path, g), (_, w) in zip(gl, wl):
        name = jax.tree_util.keystr(path)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert g.weak_type == w.weak_type, name
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes(), name


SHAPES = [(dtype, nmax, pair_matrix, k)
          for dtype in (jnp.float32, jnp.float64)
          for nmax in (64, 1024, 100)
          for pair_matrix in (True, False)
          for k in (8, 4)]


@pytest.mark.parametrize(
    "dtype,nmax,pair_matrix,k", SHAPES,
    ids=[f"{np.dtype(d).name}-n{n}-{'pairs' if p else 'nopairs'}-k{k}"
         for d, n, p, k in SHAPES])
def test_compiled_equals_eager_over_shapes(dtype, nmax, pair_matrix, k):
    args = (nmax, 8, dtype, 7, pair_matrix, k)
    assert_same_state(make_state(*args), eager_make_state(*args))


@pytest.mark.parametrize("dtype", (jnp.float32, jnp.float64),
                         ids=("float32", "float64"))
@pytest.mark.parametrize("seed", (0, 1, 2147483646, 2**31 + 5))
def test_compiled_equals_eager_over_seeds(dtype, seed):
    got = make_state(64, 8, dtype, seed, False)
    assert_same_state(got, eager_make_state(64, 8, dtype, seed, False))
    # bit for bit the key the parent's call gives
    assert np.array_equal(np.asarray(got.rng),
                          np.asarray(jax.random.PRNGKey(seed)))


def test_defaults_are_the_eager_body_s():
    assert_same_state(make_state(), eager_make_state())


@pytest.mark.parametrize("pair_matrix", (True, False),
                         ids=("pairs", "nopairs"))
def test_every_leaf_is_a_buffer_of_its_own(pair_matrix):
    leaves = jax.tree.leaves(make_state(1024, 32, jnp.float32, 3,
                                        pair_matrix))
    assert len(leaves) == 132
    ptrs = [leaf.unsafe_buffer_pointer() for leaf in leaves]
    assert len(set(ptrs)) == len(ptrs)
    # nor do two calls share one
    again = jax.tree.leaves(make_state(1024, 32, jnp.float32, 3,
                                       pair_matrix))
    assert not set(ptrs) & {leaf.unsafe_buffer_pointer() for leaf in again}


def _compiles():
    """A counter of this process's backend compilations, as
    ``devprof_backend_compiles`` counts them."""
    from bluesky_tpu.obs import devprof
    from bluesky_tpu.obs.metrics import Registry
    reg = Registry()
    devprof.install_compile_listener(reg)
    return reg, reg.counter("devprof_backend_compiles")


def test_new_seeds_compile_nothing():
    reg, compiles = _compiles()
    # a shape no other test of this file uses: the first call compiles
    make_state(48, 8, jnp.float32, 11, False)
    n0, size0 = compiles.value, statemod._empty_state._cache_size()
    assert n0 >= 1
    for seed in (12, 2147483646):
        st = make_state(48, 8, jnp.float32, seed, False)
        assert np.array_equal(np.asarray(st.rng),
                              np.asarray(jax.random.PRNGKey(seed)))
    assert compiles.value == n0
    assert statemod._empty_state._cache_size() == size0
    # the other spelling of a dtype is the same program
    make_state(48, 8, "float32", 13, False)
    make_state(48, 8, np.float32, 14, False)
    assert compiles.value == n0
    assert statemod._empty_state._cache_size() == size0


def test_state_goes_through_donated_chunk_and_write_program():
    st = make_state(16, 8, jnp.float32, 5, True)
    leaves = jax.tree.leaves(st)
    out = run_steps(st, SimConfig(), 2)          # donates every leaf
    jax.block_until_ready(out)
    assert all(leaf.is_deleted() for leaf in leaves)
    traf = traffic.Traffic(nmax=16, wmax=8)
    traf.reset()
    before = traf.state
    traf.create(1, "B744", 6000.0, 150.0, None, 52.0, 4.0, 90.0, "KL1")
    traf.flush()        # the write program, donated; the pair matrix
    after = traf.state
    jax.block_until_ready(after)
    assert before.ac.lat.is_deleted() and bool(after.ac.active[0])
    jax.block_until_ready(run_steps(after, SimConfig(), 2))


# ------------------------------------------------------------ the farm path
def _wall_piece(name, hold_at=2.0):
    return {"scentime": [0.0, 0.0, 0.0, 0.0, 0.0, hold_at],
            "scencmd": [f"SCEN {name}", "SEED 1", "ASAS ON", "SYN WALL",
                        "FF", "HOLD"]}


def _two_wall_pieces():
    """A detached node after a two-piece BATCH of SYN WALL pieces: the
    node, the compilations its second piece made, the recorder's spans,
    and the stepped end state."""
    from bluesky_tpu.simulation.sim import OP
    from bluesky_tpu.simulation.simnode import DetachedSimNode
    rec = get_recorder()
    rec.clear()
    rec.enable()
    try:
        node = DetachedSimNode(nmax=32)
        compiles = node.sim.obs.counter("devprof_backend_compiles")
        counts = []
        for name in ("WALL_A", "WALL_B"):
            n0 = compiles.value
            node.event(b"BATCH", _wall_piece(name), [])
            for _ in range(400):
                node.step()
                if node.sim.state_flag != OP and node._piece_span is None:
                    break
            assert node._piece_span is None, f"piece {name} never ended"
            counts.append(compiles.value - n0)
        spans = [e for e in rec._ring if e["ph"] == "X"]
    finally:
        rec.disable()
        rec.clear()
    end = jax.tree.map(np.asarray, node.sim.traf.state)
    return node, counts, spans, end


def test_two_wall_pieces_through_a_detached_node(monkeypatch):
    node, counts, spans, end = _two_wall_pieces()
    assert node.sim.traf.ntraf == 21 and float(end.simt) >= 2.0
    by_id = {e["id"]: e for e in spans}
    made = [e for e in spans if e["name"] == "make_state"]
    # RESET's and SYN WALL's reset_traffic: twice a piece
    assert [by_id[e["parent"]]["name"] for e in made] \
        == ["piece_reset", "stack_run"] * 2
    assert [e["args"]["piece"] for e in made] \
        == ["WALL_A"] * 2 + ["WALL_B"] * 2
    hist = node.sim.obs.get("sim_make_state_ms")
    assert hist.count == 4
    assert hist.sum == pytest.approx(sum(e["dur"] for e in made) * 1e-3,
                                     abs=0.05 * 4 + 0.02 * hist.sum)
    # a warm worker compiles nothing: not at a reset, not in the piece
    assert counts[0] >= 1 and counts[1] == 0
    # the same two pieces over the eager body: the same end state
    monkeypatch.setattr(traffic, "make_state", eager_make_state)
    _, _, spans_e, end_e = _two_wall_pieces()
    assert sum(e["name"] == "make_state" for e in spans_e) == 4
    for g, w in zip(jax.tree.leaves(end), jax.tree.leaves(end_e)):
        assert g.dtype == w.dtype and g.tobytes() == w.tobytes()
