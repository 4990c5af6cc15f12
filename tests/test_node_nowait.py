"""A stepping worker looks at its socket and does not wait on it
(ISSUE-43).

A turn of a worker's loop may skip its wait for an event only if its
``step`` blocks or works by itself.  ``SimNode`` says so while its sim
steps (``event_wait_ms`` 0: the chunk in flight, the pacing sleep, a
straggle sleep, the few dispatches that fill an empty pipeline) and
waits the idle loop's pace while it does not; a node that says nothing
keeps its millisecond a turn.  Every turn still looks at the socket
once, timed (``node_poll``, ``sim_node_poll_ms``), and a turn that did
not wait is counted (``sim_node_turns_nowait``).  One contract for the
three worker flavours: ``SimNode`` and a ``SimNode`` over ``MTNode``
behind a real broker, ``DetachedSimNode`` with no socket at all.
"""
import contextlib
import statistics
import threading
import time

import pytest

zmq = pytest.importorskip("zmq")

from bluesky_tpu.network import detached
from bluesky_tpu.network.common import IDLE_WAIT_MS
from bluesky_tpu.network.node import Node
from bluesky_tpu.network.node_mt import MTNode
from bluesky_tpu.obs.trace import get_recorder
from bluesky_tpu.simulation import sim as simmod
from bluesky_tpu.simulation.sim import HOLD, OP, Simulation
from bluesky_tpu.simulation.simnode import (DetachedSimNode, SimNode,
                                            _make_simnode_class)
from tests.test_network import free_ports, wait_for
from tests.test_simnode import fabric

MTSimNode = _make_simnode_class(MTNode)
CHUNK = 20                       # steps of an unclamped OP chunk
FLEET = ("HOLD", "CRE KL1 B744 52 4 90 FL200 250",
         "CRE KL2 B744 52.5 4 270 FL200 250")


class Worker:
    """A worker whose loop runs in a thread, and how to talk to it."""

    def __init__(self, node, client=None):
        self.node, self.client = node, client
        self.sim, self.obs = node.sim, node.sim.obs

    def command(self, *lines):
        for line in lines:
            if self.client is not None:
                self.client.stack(line)
            else:
                self.sim.stack.stack(line)

    def step_chunks(self, dtmult):
        """OP at ``dtmult``, its chunk program compiled and running."""
        self.command(*FLEET, f"DTMULT {dtmult}", "OP")
        n = self.sim._step_count
        assert wait_for(lambda: self.sim.state_flag == OP
                        and self.sim._step_count >= n + 10 * CHUNK,
                        timeout=120)

    def watch(self, turns=None, seconds=None, timeout=60):
        """What the loop did over ``turns`` turns, or over the turns
        that took ``seconds``: the registry, the clocks and the
        ``node_poll`` spans, all read in the worker's own thread at the
        top of a ``step``, so a turn's one look at the socket lies
        wholly inside or outside."""
        node, obs = self.node, self.obs
        rec = get_recorder()
        marks, done, step = [], threading.Event(), node.step

        def mark():
            return {"turn": 0, "wall": time.perf_counter(),
                    "cpu": time.thread_time(),
                    "nowait": obs.get("sim_node_turns_nowait").value,
                    "polls": obs.get("sim_node_poll_ms").count,
                    "steps": obs.get("sim_steps").value,
                    "idle_ended": obs.get("sim_node_idle_woken").value
                    + obs.get("sim_node_idle_timed_out").value}

        def watched_step():
            if not marks:
                rec.clear()
                rec.enable()
                marks.append(mark())
            elif not done.is_set():
                marks[0]["turn"] += 1
                over = marks[0]["turn"] >= turns if turns is not None \
                    else time.perf_counter() - marks[0]["wall"] >= seconds
                if over:
                    rec.disable()
                    marks.append(mark())
                    done.set()
            return step()
        node.step = watched_step
        try:
            assert done.wait(timeout), "the loop stopped turning"
        finally:
            node.step = step
            rec.disable()
        a, b = marks
        seen = {k: b[k] - a[k] for k in b if k != "turn"}
        seen["turns"] = a["turn"]
        seen["poll_ms"] = [e["dur"] * 1e-3 for e in rec._ring
                           if e["name"] == "node_poll"]
        return seen


@contextlib.contextmanager
def _served(node_cls):
    with fabric(node_cls) as (server, node, client):
        yield Worker(node, client)


@contextlib.contextmanager
def _detached():
    node = DetachedSimNode(nmax=32)
    thread = threading.Thread(target=node.run, daemon=True)
    thread.start()
    try:
        yield Worker(node)
    finally:
        node.quit()
        thread.join(timeout=5)
    assert not thread.is_alive()


FLAVOURS = {"node": lambda: _served(SimNode),
            "node_mt": lambda: _served(MTSimNode),
            "detached": _detached}


@pytest.fixture(params=list(FLAVOURS))
def worker(request):
    with FLAVOURS[request.param]() as w:
        yield w


def test_a_stepping_worker_looks_at_its_socket_and_does_not_wait(worker):
    """OP with the clock turned up: one timed look a turn that takes no
    millisecond, every turn counted as one that did not wait; on HOLD
    the idle loop's pace again, and the counter stands still."""
    worker.step_chunks(dtmult=100)
    seen = worker.watch(turns=60)
    assert seen["turns"] == 60 == seen["nowait"]
    assert seen["polls"] == 60 == len(seen["poll_ms"])
    assert statistics.median(seen["poll_ms"]) < 0.5, sorted(seen["poll_ms"])
    assert seen["idle_ended"] == 0
    # one turn a chunk: the counter over sim_steps says how often the
    # mechanism engages (a retirement trails its dispatch by a chunk)
    assert abs(seen["steps"] / CHUNK - 60) <= 2, seen["steps"]
    worker.command("HOLD")
    assert wait_for(lambda: worker.sim.state_flag == HOLD, timeout=30)
    time.sleep(0.1)
    seen = worker.watch(turns=10)
    assert seen["nowait"] == 0 and seen["steps"] == 0
    assert seen["polls"] == 10 == seen["idle_ended"] == len(seen["poll_ms"])
    # ten waits of 20 ms, less what a broker's PING cut short
    assert seen["wall"] > 8 * IDLE_WAIT_MS * 1e-3, seen["wall"]
    assert seen["cpu"] < 0.25 * seen["wall"]


def test_a_paced_worker_does_not_spin(worker):
    """OP at the wall clock's pace: a turn's step sleeps until its
    chunk is due (``_plan_chunk``), so a loop that does not wait turns
    once a chunk and its thread stays off the processor."""
    worker.step_chunks(dtmult=100)
    worker.command("DTMULT 1", "OP")
    time.sleep(0.3)
    seen = worker.watch(seconds=1.2)
    chunks = max(1.0, seen["wall"] / (CHUNK * worker.sim.simdt))
    assert seen["turns"] == seen["nowait"] == seen["polls"]
    assert 1 <= seen["turns"] <= 3 * chunks + 3, seen
    assert seen["cpu"] < 0.25 * seen["wall"], seen


@pytest.mark.parametrize("worker", ["node", "node_mt"], indirect=True)
def test_a_stepping_worker_answers_within_a_chunk(worker):
    """GETSIMSTATE to a worker in OP: handled by the turn after the
    step it arrived in, so the reply takes the hops and at most about
    one chunk's wall time, and no millisecond more a turn."""
    w, client = worker, worker.client
    w.step_chunks(dtmult=100)
    seen = w.watch(turns=40)
    chunk_ms = seen["wall"] / seen["turns"] * 1e3
    states = []
    client.event_received.connect(
        lambda n, d, s: states.append(d) if n == b"SIMSTATE" else None)
    took = []
    for i in range(20):
        time.sleep(0.003 + 0.001 * (i % 7))   # anywhere in a chunk
        n = len(states)
        t0 = time.perf_counter()
        client.send_event(b"GETSIMSTATE")
        assert wait_for(lambda: (client.receive(1), len(states) > n)[1],
                        timeout=10, step=0.0)
        took.append((time.perf_counter() - t0) * 1e3)
    assert states[-1]["state"] == OP
    assert states[-1]["simt"] > states[0]["simt"]
    assert statistics.median(took) < chunk_ms + 6.0, (chunk_ms,
                                                     sorted(took))


# ------------------------------------ when may a turn skip its wait
def test_a_node_that_does_not_say_its_step_blocks_yields():
    """The base node's step is a no-op: its loop keeps the millisecond
    a turn (a thousand turns a second at the most, its thread mostly
    off the processor); the detached base has no socket to wait on."""
    assert detached.Node().event_wait_ms() == 0
    wev, wst = free_ports(2)
    node = Node(event_port=wev, stream_port=wst)   # nobody listens
    assert node.event_wait_ms() == 1
    calls, cpu = [], []
    node.step = lambda: (calls.append(time.perf_counter()),
                         cpu.append(time.thread_time()))
    thread = threading.Thread(target=node.run, daemon=True)
    thread.start()
    time.sleep(0.5)
    node.quit()
    thread.join(timeout=5)
    assert not thread.is_alive()
    wall = calls[-1] - calls[0]
    assert 50 <= len(calls) <= wall * 1e3 + 5, len(calls)
    assert cpu[-1] - cpu[0] < 0.5 * wall


def test_a_turn_skips_its_wait_only_if_its_step_worked_or_blocked(
        monkeypatch):
    """Turn a detached worker's loop by hand through INIT, OP, an FF
    that reaches its horizon, HOLD, OP again and a straggle stall:
    whenever the coming turn may not wait (``event_wait_ms`` 0), the
    step just made dispatched a chunk, waited for the device or slept;
    every other step opened ``node_idle`` and waits its 20 ms."""
    naps = []
    monkeypatch.setattr(simmod.time, "sleep", naps.append)
    node = DetachedSimNode(nmax=16)
    sim, obs = node.sim, node.sim.obs
    script = {3: FLEET + ("DTMULT 100", "OP"), 30: ("FF 3",),
              60: ("OP",), 80: ("HOLD",), 90: ("DTMULT 100", "OP")}
    nowait = dispatched_in_op = 0
    for turn in range(140):
        for line in script.get(turn, ()):
            sim.stack.stack(line)
        if turn == 110:
            sim.straggle_stall = True
        if turn == 120:
            sim.straggle_stall = False
        before = (obs.get("sim_dispatch_ms").count,
                  obs.get("sim_device_wait_ms").count, len(naps))
        node.step()
        after = (obs.get("sim_dispatch_ms").count,
                 obs.get("sim_device_wait_ms").count, len(naps))
        if node.event_wait_ms() == 0:
            nowait += 1
            assert sim.state_flag == OP
            assert after != before, f"turn {turn} did nothing in OP"
            dispatched_in_op += after[0] - before[0]
        else:
            assert node.event_wait_ms() == IDLE_WAIT_MS
            assert sim.state_flag != OP and node._idle_span is not None
    assert nowait >= 80 and dispatched_in_op >= 60
    assert naps.count(0.02) == 10        # the stall's turns slept


def test_an_empty_pipeline_fills_in_a_chunks_worth_of_dispatches():
    """The turns that neither wait for the device nor sleep are the
    dispatches that fill an empty pipeline, and the pipeline holds one
    unclamped chunk's worth of steps (``_step_pipelined``): four
    5-step chunks here (ten 2-step ones in ``circleflow100k-ff``),
    then every turn retires one."""
    sim = Simulation(nmax=16)
    for line in FLEET[1:] + ("DTMULT 100", "OP"):
        sim.stack.stack(line)
    sim.stack.process()
    waits = sim.obs.get("sim_device_wait_ms")
    fill = sim.chunk_steps // 5
    turns = 0
    while waits.count == 0:
        sim.step(max_chunk=5)
        turns += 1
        assert turns <= fill + 1
    assert turns == fill + 1 == len(sim._inflight) + 1
    for _ in range(5):
        n = waits.count
        sim.step(max_chunk=5)
        assert waits.count == n + 1
    sim.drain_pipeline()
