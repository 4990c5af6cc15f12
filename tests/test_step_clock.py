"""The simulation clock counts its steps (ISSUE-42, ROADMAP C10).

The state carries a step count and ``simt`` is derived from it, rounded
once.  Pinned here, on the CPU:

* What leaves the worker: every ``simt`` of an ACDATA or SIMINFO frame,
  a heartbeat, a reply and an edge pack is ``float32(n * 0.05)`` as the
  benchmark's plain reference defines it (``counted``), and the device's
  derivation is that for every n to 2**22.  The host's own clock
  (``sim.simt``) is the exact product, for its timers.
* When the program detects: on the steps whose exact time is a whole
  second (20, 40, ... and 400 for a mark at 20 s), never a step later,
  at counts near 20 times 1, 1,000, 5,000, 40,000 and 300,000.
* The planned clock is the device's after chunks of 1, 20, 1000 and
  12,000 steps from counts 0, 327,680 and 1,310,720.
* A snapshot written before the state counted its steps loads.
* The counter ``sim_steps`` and the gauge ``sim_step_count`` agree at
  every edge, and ``sim_clock_s`` is the time those steps took.
"""
import os
import pickle
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluesky_tpu.core.state import (SimState, time_as_held,
                                    time_of_count)
from bluesky_tpu.core.step import step_jit
from bluesky_tpu.simulation import snapshot
from bluesky_tpu.simulation.sim import OP, Simulation

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "benchmark"))
from reference import plain  # noqa: E402

F32 = np.float32


def _sim(nmax=16, n_ac=2):
    sim = Simulation(nmax=nmax)
    for k in range(n_ac):
        sim.stack.stack(f"CRE KL{k} B744 52.{k} 4 90 FL200 250")
    sim.stack.stack("ASAS ON; RESO OFF; HOLD")
    sim.stack.process()
    return sim


# ---------------------------------------------------- what leaves the worker
def test_the_devices_time_is_the_counted_product_for_every_count():
    n = np.arange(1 << 22, dtype=np.int64)
    want = (n * plain.SIMDT).astype(F32)
    got = np.asarray(jax.jit(
        lambda c: time_of_count(c, plain.SIMDT, jnp.float32))(
            jnp.asarray(n, jnp.int32)))
    assert np.array_equal(got, want)
    # the product and the quotient the issue weighs are not: the
    # constant 0.05 is a quarter of an ulp high in float32
    assert (F32(9) * F32(0.05)) != want[9]
    # the host's is the same definition, and the reference reads it
    for k in (0, 1, 9, 400, 327_680, 1_310_720, (1 << 22) - 1):
        t = time_as_held(k * plain.SIMDT, np.float32)
        assert t == float(want[k]) and plain.counted(t) == k


@pytest.mark.parametrize("simdt", (0.1, 0.025, 1.0, 0.5))
def test_other_step_lengths_that_divide_a_second(simdt):
    n = np.arange(1 << 20, dtype=np.int64)
    got = np.asarray(jax.jit(
        lambda c: time_of_count(c, simdt, jnp.float32))(
            jnp.asarray(n, jnp.int32)))
    assert np.array_equal(got, (n * simdt).astype(F32))


def test_float64_states_take_the_plain_product():
    n = np.asarray([0, 1, 9, 327_680, 1_310_720])
    got = np.asarray(time_of_count(jnp.asarray(n, jnp.int32), 0.05,
                                   jnp.float64))
    assert got.dtype == np.float64 and np.array_equal(got, n * 0.05)
    assert time_as_held(9 * 0.05, np.float64) == 9 * 0.05


@pytest.mark.parametrize("n0", (0, 327_680, 1_310_720))
def test_every_time_that_leaves_a_worker_is_a_counted_product(n0):
    """Frames, heartbeats and the edge pack of a free-running worker,
    started where a float32 sum of 0.05 s steps would be 0.4% and 1.6%
    off: each ``simt`` is float32(n * 0.05) of a whole n on a chunk's
    edge, and the reference reads the frames' clock as "count"."""
    from bluesky_tpu.simulation.simnode import DetachedSimNode
    node = DetachedSimNode(nmax=16)
    sim = node.sim
    for k in range(2):
        sim.stack.stack(f"CRE KL{k} B744 52.{k} 4 90 FL200 250")
    sim.stack.stack("HOLD")
    sim.stack.process()
    sim.set_clock(n0)
    sim.stack.stack("DTMULT 100; OP")
    seen = {"ACDATA": [], "SIMINFO": [], "heartbeat": [], "edge": [],
            "reply": []}
    for turn in range(12):
        node.step()
        if sim._last_edge is not None:
            seen["edge"].append(sim._last_edge.simt_device)
        seen["heartbeat"].append(node.heartbeat_payload(0)["simt"])
        if turn % 3 == 2:
            sim.scr.send_aircraft_data()
            sim.scr.send_siminfo()
    sim.drain_pipeline()
    replies = []
    node.send_event = lambda name, data, route=None: replies.append(data)
    node.event(b"GETSIMSTATE", None, [])
    seen["reply"].append(replies[0]["simt"])
    seen["edge"].append(float(sim.traf.state.simt))
    for name, data in node.streams:
        if name in (b"ACDATA", b"SIMINFO"):
            seen[name.decode()].append(data["simt"])
    for what, simts in seen.items():
        assert simts, what
        for t in simts:
            n = plain.counted(t)
            assert n is not None, (what, t)
            assert n >= n0 and (n - n0) % sim.chunk_steps == 0, (what, t)
    assert sim.nstep > n0
    assert plain.counted(seen["reply"][0]) == sim.nstep
    assert sim.simt == sim.nstep * 0.05       # the host's: the product
    assert seen["reply"][0] == sim.sent(sim.simt) \
        == float(sim.traf.state.simt)
    if n0:       # from nought the first frames are values of the sum too
        assert plain.clock_of(seen["ACDATA"]) == "count"


# ------------------------------------------------- when the program detects
@pytest.mark.parametrize("second", (1, 20, 1_000, 5_000, 40_000, 300_000))
def test_detection_runs_on_the_step_of_the_whole_second(second):
    """CD runs in the step that starts at count 20 * second, whose exact
    time has reached the whole second, and not in the one before or the
    one after (``asas_tnext`` moves on when an interval ran)."""
    sim = _sim()
    cfg = sim.cfg
    n = 20 * second
    sim.set_clock(n - 2)
    st = sim.traf.state
    st = st.replace(asas_tnext=jnp.asarray(second, st.asas_tnext.dtype))
    ran = []
    for _ in range(4):                    # starts n-2, n-1, n, n+1
        before = float(st.asas_tnext)
        st = step_jit(st, cfg)
        ran.append(float(st.asas_tnext) != before)
    assert ran == [False, False, True, False]
    assert int(st.nstep) == n + 2
    assert float(st.simt) == float(F32((n + 2) * 0.05))


def test_detection_from_nought_runs_on_steps_0_20_40():
    sim = _sim()
    st, cfg, ran = sim.traf.state, sim.cfg, []
    for k in range(61):
        before = float(st.asas_tnext)
        st = step_jit(st, cfg)
        if float(st.asas_tnext) != before:
            ran.append(k)
    assert ran == [0, 20, 40, 60]


# --------------------------------------------------------- the planned clock
@pytest.mark.parametrize("n0", (0, 327_680, 1_310_720))
def test_the_planned_clock_is_the_devices(n0):
    sim = _sim(n_ac=1)
    sim.set_clock(n0)
    sim.setdtmult(100.0)          # no pacing: a chunk takes what it takes
    sim.op()
    n = n0
    for chunk in (1, 20, 1000, 12_000):
        sim.chunk_steps = chunk
        assert sim.step()
        n += chunk
        # planned, with the chunk in flight: no read of the device
        assert sim._inflight
        assert sim.nstep_planned == n
        assert sim.simt_planned == n * 0.05
        edge = sim._inflight[-1]
        assert edge.nstep == n and edge.simt == n * 0.05
        sim.drain_pipeline()
        assert edge.nstep_device == n == sim.nstep
        assert sim.simt == n * 0.05
        # and as the device holds it, and as it is sent
        assert edge.simt_device == float(sim.traf.state.simt) \
            == sim.sent(sim.simt) == float(F32(n * 0.05))


def test_planning_counts_steps_to_a_trigger_and_to_the_ff_stop():
    sim = _sim(n_ac=1)
    sim.set_clock(327_680)                      # 16,384 s
    assert sim.steps_until(16_385.0, sim.nstep) == 20
    assert sim.steps_until(16_384.0, sim.nstep) == 0
    assert sim.steps_until(16_384.01, sim.nstep) == 1
    assert sim.steps_until(16_384.05, sim.nstep) == 1    # no float32 is
    assert sim.steps_until(16_384.3, sim.nstep) == 6     # 16,384.3 s
    sim.op()
    sim.fastforward(60.0)
    while sim.state_flag == OP and sim.ffmode:
        sim.step()
    sim.drain_pipeline()
    assert sim.nstep == 327_680 + 1200 and sim.simt == 16_444.0


def test_reset_zeroes_the_count_and_dt_restarts_it():
    sim = _sim(n_ac=1)
    sim.op()
    sim.run(until_simt=3.0)
    assert sim.nstep == 60
    sim.setdt(0.1)
    assert sim.nstep == 30 and sim.simt == 3.0
    sim.op()
    sim.run(until_simt=4.0)
    assert sim.nstep == 40 and sim.simt == 4.0
    sim.reset()
    assert sim.nstep == 0 and sim.simt == 0.0 and sim.simdt == 0.05


# ------------------------------------------------------------------ snapshot
def test_a_snapshot_written_before_the_state_counted_loads(tmp_path):
    sim = _sim()
    sim.op()
    sim.run(until_simt=5.0)
    blob = snapshot.state_blob(sim)
    # the parent's format: a pickled SimState with no ``nstep``, its
    # clock a float32 sum that had drifted off the product
    old = object.__new__(SimState)
    held = {k: v for k, v in vars(blob["state"]).items() if k != "nstep"}
    held["simt"] = np.asarray(4.9999, np.float32)
    for k, v in held.items():
        object.__setattr__(old, k, v)
    assert not hasattr(old, "nstep")
    blob["state"] = old
    fname = snapshot.write_blob(pickle.loads(pickle.dumps(blob)),
                                str(tmp_path / "parent.snap"))
    other = _sim()
    ok, msg = snapshot.load(other, fname)
    assert ok, msg
    assert other.nstep == 100 and other.simt == 5.0
    assert float(other.traf.state.simt) == 5.0
    other.op()
    other.run(until_simt=6.0)
    assert other.nstep == 120
    # and today's round trip keeps the count
    ok, msg = snapshot.restore_blob(sim, snapshot.state_blob(other))
    assert ok and sim.nstep == 120


# -------------------------------------------------------- counters and gauge
def test_counter_and_gauge_agree_at_every_edge():
    from bluesky_tpu.obs.trace import get_recorder
    rec = get_recorder()
    rec.clear()
    rec.enable()
    try:
        sim = _sim()
        steps, clock, gauge = (sim.obs.get(k) for k in (
            "sim_steps", "sim_clock_s", "sim_step_count"))
        sim.op()
        for chunk in (20, 20, 5, 200, 1, 20):
            sim.chunk_steps = chunk
            sim.step()
            sim.drain_pipeline()
            assert steps.value == gauge.value == sim.nstep
            assert clock.value == pytest.approx(sim.simt, abs=1e-4)
        tags = [e["args"]["n"] for e in rec._ring
                if e["ph"] == "X" and e["name"] == "chunk_edge"]
        assert tags == [20, 40, 45, 245, 246, 266]
        # a RESET restarts the device's count; the counters count on
        sim.reset()
        sim.stack.stack("CRE KL9 B744 52 4 90 FL200 250")
        sim.stack.process()
        sim.chunk_steps = 20
        sim.op()
        sim.step()
        sim.drain_pipeline()
        assert gauge.value == 20 and steps.value == 286
        assert clock.value == pytest.approx(14.3, abs=1e-4)
    finally:
        rec.disable()
        rec.clear()
