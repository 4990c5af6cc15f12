"""End-to-end: TPU sim worker on the fabric, driven from a Client
(reference §4.2/§4.3 style: real processes-in-threads over localhost ZMQ)."""
import contextlib
import threading
import time

import numpy as np
import pytest

zmq = pytest.importorskip("zmq")

from bluesky_tpu.network.client import Client
from bluesky_tpu.network.server import Server
from bluesky_tpu.simulation.simnode import SimNode, DetachedSimNode
from tests.test_network import free_ports, wait_for


@contextlib.contextmanager
def fabric(node_cls=SimNode):
    """A ``Server`` that spawns no worker, one ``node_cls`` worker whose
    loop runs in a thread, and a ``Client`` that has seen it."""
    ev, st, wev, wst = free_ports(4)
    server = Server(headless=True,
                    ports=dict(event=ev, stream=st, wevent=wev,
                               wstream=wst),
                    spawn_workers=False)
    server.start()
    time.sleep(0.2)
    node = node_cls(event_port=wev, stream_port=wst, nmax=32)
    thread = threading.Thread(target=node.run, daemon=True)
    thread.start()
    client = Client()
    try:
        client.connect(event_port=ev, stream_port=st, timeout=5.0)
        assert wait_for(lambda: (client.receive(10),
                                 len(client.nodes) > 0)[1])
        yield server, node, client
    finally:
        node.quit()
        thread.join(timeout=5)
        server.stop()
        server.join(timeout=5)
        client.close()


@pytest.fixture
def simfabric():
    with fabric() as made:
        yield made


def test_stackcmd_echo_and_acdata(simfabric):
    server, node, client = simfabric
    echoes, acdata = [], []
    client.event_received.connect(
        lambda n, d, s: echoes.append(d) if n == b"ECHO" else None)
    client.stream_received.connect(
        lambda n, d, s: acdata.append(d) if n == b"ACDATA" else None)
    client.subscribe(b"ACDATA")
    time.sleep(0.3)

    client.stack("CRE KL204 B744 52 4 90 FL200 250")
    client.stack("POS KL204")
    assert wait_for(lambda: (client.receive(10), len(echoes) >= 1)[1],
                    timeout=60)
    assert any("KL204" in e["text"] for e in echoes if e.get("text"))

    client.stack("OP")
    assert wait_for(
        lambda: (client.receive(10),
                 any(f["id"] for f in acdata))[1], timeout=60)
    frame = next(f for f in reversed(acdata) if f["id"])
    assert frame["id"] == ["KL204"]
    assert frame["lat"].shape == (1,)
    assert abs(frame["lat"][0] - 52.0) < 0.5


def test_getsimstate(simfabric):
    server, node, client = simfabric
    states = []
    client.event_received.connect(
        lambda n, d, s: states.append(d) if n == b"SIMSTATE" else None)
    client.send_event(b"GETSIMSTATE")
    assert wait_for(lambda: (client.receive(10), len(states) > 0)[1],
                    timeout=30)
    assert states[0]["ntraf"] == 0
    assert states[0]["simt"] == 0.0


def test_register_and_health_name_the_device(simfabric):
    """The worker's REGISTER says which devices it computes on, and
    HEALTH shows them per worker — the only place the served path
    names its platform (chip_smoke.py reads it there)."""
    import jax
    server, node, client = simfabric
    d = jax.devices()[0]
    assert node.register_payload()["device"] == {
        "platform": d.platform, "device_kind": d.device_kind,
        "count": len(jax.devices())}
    client.request_health()
    assert wait_for(lambda: (client.receive(10),
                             client.last_health is not None)[1], timeout=30)
    (w,) = client.last_health["workers"].values()
    assert w["device"]["platform"] == "cpu"
    assert w["device"]["device_kind"] == d.device_kind
    assert f"x cpu ({d.device_kind})" in client.last_health["text"]


def test_detached_simnode_runs():
    node = DetachedSimNode(nmax=16)
    node.sim.stack.stack("CRE AB1 B744 52 4 90 FL100 200")
    node.sim.stack.process()
    node.sim.op()
    for _ in range(3):
        node.step()
    assert node.sim.traf.ntraf == 1
    assert node.sim.simt > 0.0
