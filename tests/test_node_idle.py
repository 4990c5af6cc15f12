"""An idle worker waits on its socket, not on a clock (ISSUE-41).

While its sim is not stepping a worker's loop waits for the next event
for up to the idle loop's pace (``network/common.py`` ``IDLE_WAIT_MS``)
and wakes the moment one arrives; ``SimNode.step`` does not sleep, and a
state change goes out in the turn in which the state changed.  Over the
real fabric as tests/test_batch.py builds it (``simfabric``: a ``Server``
that spawns no worker, one ``SimNode`` thread, a ``Client``).
"""
import statistics
import threading
import time

import pytest

zmq = pytest.importorskip("zmq")

from bluesky_tpu.network.common import IDLE_WAIT_MS
from bluesky_tpu.obs.trace import get_recorder
from bluesky_tpu.simulation.sim import HOLD, OP
from bluesky_tpu.simulation.simnode import DetachedSimNode
from bluesky_tpu.utils.timer import Timer
from tests.test_network import wait_for
from tests.test_simnode import simfabric      # noqa: F401 (the fixture)


def _count_steps(node):
    """Count the turns of ``node``'s loop from now on."""
    calls = []
    step = node.step
    node.step = lambda: (calls.append(time.perf_counter()), step())[1]
    return calls


def test_an_idle_worker_answers_at_once(simfabric):
    """GETSIMSTATE on HOLD: client, broker, worker and back in the
    time the hops take, not the rest of a 20 ms sleep."""
    server, node, client = simfabric
    states = []
    client.event_received.connect(
        lambda n, d, s: states.append(d) if n == b"SIMSTATE" else None)
    client.stack("HOLD")
    assert wait_for(lambda: node.sim.state_flag == HOLD, timeout=30)
    took = []
    for i in range(20):
        time.sleep(0.003 + 0.001 * (i % 7))   # anywhere in the wait
        n = len(states)
        t0 = time.perf_counter()
        client.send_event(b"GETSIMSTATE")
        assert wait_for(lambda: (client.receive(1), len(states) > n)[1],
                        timeout=10, step=0.0)
        took.append((time.perf_counter() - t0) * 1e3)
    assert states[-1]["state"] == HOLD
    assert statistics.median(took) < 6.0, sorted(took)


def _batch_file(tmp_path, names):
    scn = tmp_path / "mc.scn"
    scn.write_text("".join(
        f"00:00:00.00>SCEN {name}\n"
        "00:00:00.00>CRE KL1 B744 52 4 90 FL200 250\n"
        "00:00:00.00>CRE KL2 B744 52.5 4 270 FL200 250\n"
        "00:00:00.00>FF\n"
        "00:00:03.00>HOLD\n" for name in names))
    return scn


def test_four_pieces_back_to_back_turn_around_in_a_round_trip(
        simfabric, tmp_path):
    """A farm piece has one idle stretch, between pieces, that the next
    BATCH ends: none inside the piece, and no 20 ms in the turnaround."""
    server, node, client = simfabric
    obs = node.sim.obs
    rec = get_recorder()
    rec.clear()
    rec.enable()
    try:
        client.stack(f"BATCH {_batch_file(tmp_path, 'ABCD')}")
        assert wait_for(lambda: (client.receive(10),
                                 obs.get("sim_piece_ms").count == 4)[1],
                        timeout=120)
        # let the stretch behind the last piece time out and close
        time.sleep(3 * IDLE_WAIT_MS * 1e-3)
    finally:
        rec.disable()
    turn = obs.get("sim_piece_turnaround_ms")
    assert turn.count == 3
    assert turn.percentile(0.5) < 10.0
    spans = [e for e in rec._ring if e["ph"] == "X"]
    by_id = {e["id"]: e for e in spans}
    pieces = sorted((e for e in spans if e["name"] == "piece"),
                    key=lambda e: e["ts"])
    assert [e["args"]["piece"] for e in pieces] == list("ABCD")
    idles = [e for e in spans if e["name"] == "node_idle"]
    assert idles and all(e["parent"] is None for e in idles)
    assert {by_id[e["parent"]]["name"] for e in spans
            if e["name"] == "node_poll"} == {"piece", "node_idle"}
    # between two pieces: one stretch, which the next BATCH ended (a
    # broker that took over 20 ms about it leaves timed-out ones before)
    for a, b in zip(pieces, pieces[1:]):
        gap = [e["args"].get("cause") for e in idles
               if a["ts"] + a["dur"] <= e["ts"] < b["ts"]]
        assert gap.count("woken") == 1 and gap[-1] == "woken", gap
        assert set(gap) <= {"woken", "timed_out"}
    # and every stretch is one observation
    assert obs.get("sim_node_idle_ms").count >= len(idles)


def test_a_piece_ends_in_the_turn_its_state_changed():
    """The last mark's echo, SDCFP, STATECHANGE: in that order, and in
    the call of step() in which sim.step() left OP."""
    node = DetachedSimNode(nmax=16)
    sim = node.sim
    sim.set_fingerprint(True)
    sent = []
    turn = [0]
    loop_back = node.send_event

    def send_event(name, data=None, route=None):
        sent.append((turn[0], name, data))
        loop_back(name, data, route)
    node.send_event = send_event
    node.event(b"BATCH", {
        "scentime": [0.0, 0.0, 0.0, 0.0, 3.0, 3.0],
        "scencmd": ["SCEN MARKS", "CRE KL1 B744 52 4 90 FL200 250",
                    "CRE KL2 B744 52.5 4 270 FL200 250", "FF",
                    "ECHO last mark", "HOLD"]}, [])
    left_op = None
    for turn[0] in range(1, 400):
        was = sim.state_flag
        node.step()
        if was == OP and sim.state_flag != OP:
            left_op = turn[0]
            break
    assert left_op is not None and node._piece_span is None
    tail = [(t, n) for t, n, d in sent
            if n in (b"ECHO", b"SDCFP", b"STATECHANGE")][-3:]
    assert tail == [(left_op, b"ECHO"), (left_op, b"SDCFP"),
                    (left_op, b"STATECHANGE")]
    assert sent[-1][1:] == (b"STATECHANGE", HOLD)
    echo = [d for t, n, d in sent if n == b"ECHO"][-1]
    assert echo["text"] == "last mark"
    fp = [d for t, n, d in sent if n == b"SDCFP"]
    assert len(fp) == 1 and fp[0] == sim.fp_summary()
    assert sim.obs.get("sim_piece_ms").count == 1


def _ten_hertz_timer():
    fired = []
    timer = Timer(0.1)
    timer.connect(lambda: fired.append(time.perf_counter()))
    return timer, fired


def test_an_idle_worker_turns_its_loop_fifty_times_a_second(simfabric):
    """Nothing arriving: the loop neither spins nor stalls, and the
    wall-clock timers keep their pace."""
    server, node, client = simfabric
    client.stack("HOLD")
    assert wait_for(lambda: node.sim.state_flag == HOLD, timeout=30)
    time.sleep(0.1)
    timer, fired = _ten_hertz_timer()
    try:
        calls = _count_steps(node)
        time.sleep(1.0)
        n, nfired = len(calls), len(fired)
    finally:
        timer.remove()
    assert 35 <= n <= 60, n
    assert 7 <= nfired <= 10, nfired


def test_a_detached_node_idles_at_the_same_pace():
    """detached.Node has no socket and nothing can wake it: its run
    sleeps the idle loop's pace out, and its step does not sleep."""
    node = DetachedSimNode(nmax=16)
    t0 = time.perf_counter()
    for _ in range(10):
        node.step()
    assert (time.perf_counter() - t0) * 1e3 < 10 * IDLE_WAIT_MS / 2
    timer, fired = _ten_hertz_timer()
    calls = _count_steps(node)
    thread = threading.Thread(target=node.run, daemon=True)
    try:
        thread.start()
        time.sleep(1.0)
        n, nfired = len(calls), len(fired)
    finally:
        node.quit()
        thread.join(timeout=5)
        timer.remove()
    assert not thread.is_alive()
    assert 35 <= n <= 60, n
    assert 7 <= nfired <= 10, nfired
    assert node.sim.obs.get("sim_node_idle_timed_out").value >= n - 2
    assert node.sim.obs.get("sim_node_idle_woken").value == 0


def test_the_counter_says_how_an_idle_wait_ended(simfabric):
    """``woken`` for a wait an event ended, ``timed_out`` for one that
    ran its bound out."""
    server, node, client = simfabric
    obs = node.sim.obs
    woken, timed_out = (obs.get("sim_node_idle_woken"),
                        obs.get("sim_node_idle_timed_out"))
    client.stack("HOLD")
    assert wait_for(lambda: node.sim.state_flag == HOLD, timeout=30)
    time.sleep(0.1)
    # nobody talks to it (but the broker's PING, every other second)
    w0, t0 = woken.value, timed_out.value
    time.sleep(0.5)
    assert timed_out.value - t0 >= 15
    assert woken.value - w0 <= 2
    # ten requests, each behind the last one's reply: ten waits woken
    states = []
    client.event_received.connect(
        lambda n, d, s: states.append(d) if n == b"SIMSTATE" else None)
    w0, t0 = woken.value, timed_out.value
    for i in range(10):
        client.send_event(b"GETSIMSTATE")
        assert wait_for(lambda: (client.receive(1), len(states) > i)[1],
                        timeout=10, step=0.0)
    assert 10 <= woken.value - w0 <= 12
    assert timed_out.value - t0 <= 2
