"""A chip for each worker (ISSUE 38).  With ``max_nnodes`` > 1 the broker
gives every worker it spawns one device slot, the child honours it before
JAX is imported, says so in REGISTER and ``HEALTH`` shows it; with
``max_nnodes`` 1 nothing is named and the worker is spawned as it always
was.  On the CPU, with a recording stand-in for ``Popen`` wherever no
process is needed; one served test runs a real broker and two workers on
a ``wall_batch`` BATCH and holds the echoed states to the benchmark's
plain reference.  Nothing here compiles the step at more than toy size."""
import json
import os
import subprocess
import sys
import time

import pytest

import bluesky_tpu
from bluesky_tpu import settings
from bluesky_tpu.network import server as srv
from bluesky_tpu.network.npcodec import packb

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(REPO, "benchmark"))

SLOT = bluesky_tpu.DEVICE_SLOT_ENV


class FakeProc:
    """What ``addnodes`` keeps of a ``Popen``: the command line, the
    environment it was given, and whether the process has exited."""

    def __init__(self, argv, env=None, **kw):
        self.argv, self.env, self.kw = argv, env, kw
        self.returncode = None

    def poll(self):
        return self.returncode

    def die(self, code=-9):
        self.returncode = code


@pytest.fixture
def broker(monkeypatch):
    """``broker(max_nnodes)``: a Server that never runs its loop and
    whose spawns are recorded, not started."""
    made = []
    monkeypatch.setattr(srv.subprocess, "Popen", FakeProc)

    def make(max_nnodes, **kw):
        server = srv.Server(headless=True, spawn_workers=True,
                            journal_path="", max_nnodes=max_nnodes, **kw)
        made.append(server)
        return server
    yield make
    for server in made:
        for sock in (server.fe_event, server.fe_stream, server.be_event,
                     server.be_stream):
            sock.close()


def slots_of(server):
    return {wid: slot for wid, (slot, _) in server.worker_slot.items()}


def register(server, wid, device=None):
    server._handle_server_event(
        server.be_event, wid, b"REGISTER",
        packb({"device": device or {"platform": "cpu",
                                    "device_kind": "cpu", "count": 1}}))


# ------------------------------------------------------------ the broker
def test_four_workers_are_given_four_different_slots(broker):
    server = broker(4)
    server.addnodes(4)
    procs = list(server.spawned.values())
    assert [p.env[SLOT] for p in procs] == ["0", "1", "2", "3"]
    assert sorted(slots_of(server).values()) == [0, 1, 2, 3]
    for wid, p in server.spawned.items():
        # the command line is what it always was: the slot travels in
        # the child's environment alone, beside the broker's own
        assert p.argv[:4] == [sys.executable, "-m", "bluesky_tpu", "--sim"]
        assert p.argv[p.argv.index("--node-id") + 1] == wid.hex()
        assert not any("slot" in a.lower() for a in p.argv)
        assert {k: v for k, v in p.env.items() if k != SLOT} \
            == {k: v for k, v in os.environ.items() if k != SLOT}
    # a fifth finds no slot: it is not spawned, and asked for again on
    # a heartbeat tick
    server.addnodes(1)
    assert len(server.spawned) == 4 and server._slot_wanted
    assert server._pending_spawns == 4


def test_one_worker_is_spawned_as_the_parent_spawned_it(broker, monkeypatch):
    monkeypatch.setattr(settings, "config_file", "")
    server = broker(1)
    server.addnodes(1)
    (wid, p), = server.spawned.items()
    assert p.argv == [sys.executable, "-m", "bluesky_tpu", "--sim",
                      "--event-port", str(server.ports["wevent"]),
                      "--stream-port", str(server.ports["wstream"]),
                      "--node-id", wid.hex()]
    assert p.env is None and p.kw == {}      # the broker's own, inherited
    assert server.worker_slot == {}
    register(server, wid)
    assert "slot" not in server.health_payload()["workers"][wid.hex()][
        "device"]


@pytest.mark.parametrize("registered", [True, False],
                         ids=["died_after_registering",
                              "died_before_registering"])
def test_a_dead_worker_frees_its_slot_for_its_replacement(broker,
                                                          registered):
    server = broker(4)
    server.addnodes(4)
    wids = list(server.spawned)
    victim = wids[1]
    for wid in wids:
        if registered or wid != victim:
            register(server, wid)
    assert slots_of(server)[victim] == 1
    # a backlog, so that the broker replaces what it loses
    server.scenarios.push(([0.0], ["SCEN A"]), b"")
    server.spawned[victim].die()
    server._reap_dead_workers()
    assert victim not in server.worker_slot and victim not in server.workers
    (new,) = set(server.spawned) - set(wids)
    assert slots_of(server)[new] == 1
    assert server.spawned[new].env[SLOT] == "1"
    live = slots_of(server)
    assert sorted(live.values()) == [0, 1, 2, 3] and len(live) == 4


def test_a_worker_that_said_goodbye_keeps_its_slot_while_it_lives(broker):
    server = broker(2)
    server.addnodes(2)
    first, second = server.spawned
    register(server, first)
    register(server, second)
    proc = server.spawned[first]
    server._handle_server_event(server.be_event, first, b"STATECHANGE",
                                packb(-1))
    assert first not in server.workers
    server.addnodes(1)                    # its process still runs
    assert len(server.worker_slot) == 2 and server._slot_wanted
    proc.die(0)
    server.addnodes(1)
    assert first not in server.worker_slot
    (new,) = set(server.spawned) - {second}
    assert slots_of(server) == {second: 1, new: 0}


def test_spawn_span_histograms_and_the_gauge(broker):
    server = broker(2)
    server.recorder.enable()
    try:
        server.addnodes(2)
        first, second = server.spawned
        register(server, first, {"platform": "cpu", "device_kind": "cpu",
                                 "count": 1, "slot": 0})
        assert server.obs.get("server_worker_spawn_ms").count == 1
        assert server.obs.get("server_workers_live").value == 1
        server.spawned[second].die(1)     # never registers
        server._reap_dead_workers()
        assert server.obs.get("server_worker_spawn_ms").count == 1
        spans = {ev["args"]["worker"]: ev for ev in list(server.recorder._ring)
                 if ev["name"] == "worker_spawn"}
    finally:
        server.recorder.disable()
        server.recorder.clear()
    assert spans[first.hex()]["cat"] == "server"
    assert spans[first.hex()]["args"]["slot"] == 0
    assert "died" not in spans[first.hex()]["args"]
    assert spans[second.hex()]["args"] == {
        "worker": second.hex(), "slot": 1, "died": True}
    assert spans[first.hex()]["dur"] >= 0
    # REGISTER's device reaches HEALTH, slot and all
    health = server.health_payload()
    assert health["workers"][first.hex()]["device"]["slot"] == 0
    assert "on 1 x cpu (cpu), device slot 0" in health["text"]


def test_the_loop_observes_its_busy_turns():
    from tests.test_network import free_ports, wait_for
    from bluesky_tpu.network.client import Client
    ev, st, wev, wst = free_ports(4)
    server = srv.Server(headless=True, spawn_workers=False, journal_path="",
                        ports=dict(event=ev, stream=st, wevent=wev,
                                   wstream=wst), hb_interval=0.1)
    server.start()
    client = Client()
    try:
        client.connect(event_port=ev, stream_port=st, timeout=30.0)
        busy = server.obs.get("server_loop_busy_ms")
        assert wait_for(lambda: busy.count >= 3, timeout=10.0)
        n = busy.count
        client.request_health()
        assert wait_for(lambda: (client.receive(10),
                                 client.last_health is not None)[1],
                        timeout=10.0)
        # the turn that answered is observed when it ends
        assert wait_for(lambda: busy.count > n, timeout=10.0)
        assert busy.sum >= 0.0
        assert client.last_health["workers"] == {}
    finally:
        server.stop()
        server.join(timeout=10)
        client.close()


# ------------------------------------------------------------- the worker
@pytest.mark.parametrize("slot", [0, 3])
def test_honouring_a_slot_names_that_chip_and_no_other(slot):
    env = {SLOT: str(slot), "HOME": "/nowhere"}
    named = bluesky_tpu.honour_device_slot(env)
    assert named == bluesky_tpu.slot_variables(slot)
    assert env["TPU_VISIBLE_CHIPS"] == str(slot)
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["HOME"] == "/nowhere" and env[SLOT] == str(slot)
    # a chip machine that lists the CPU behind its chip names the chip
    listed = {SLOT: str(slot), "JAX_PLATFORMS": "tpu,cpu"}
    assert bluesky_tpu.honour_device_slot(listed) == named
    assert listed["TPU_VISIBLE_CHIPS"] == str(slot)


@pytest.mark.parametrize("env", [{}, {SLOT: ""}, {SLOT: "2",
                                                 "JAX_PLATFORMS": "cpu"}],
                         ids=["no_slot", "empty", "a_named_cpu"])
def test_nothing_is_set_where_nothing_is_to_restrict(env):
    before = dict(env)
    assert bluesky_tpu.honour_device_slot(env) == {}
    assert env == before
    assert bluesky_tpu.device_slot(env) == (2 if env.get(SLOT) else None)


@pytest.mark.parametrize("platforms, chip", [
    (None, "2"), ("tpu,cpu", "2"), ("cpu", None)],
    ids=["nothing_named", "the_chip_then_the_cpu", "the_cpu_by_name"])
def test_the_slot_is_honoured_before_jax_is_imported(platforms, chip):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    if platforms:
        env["JAX_PLATFORMS"] = platforms
    env[SLOT] = "2"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, "-c",
         "import os, sys, json, bluesky_tpu; print(json.dumps("
         "['jax' in sys.modules, os.environ.get('TPU_VISIBLE_CHIPS'), "
         "os.environ.get('TPU_CHIPS_PER_PROCESS_BOUNDS'), "
         "bluesky_tpu.device_slot()]))"],
        env=env, capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.loads(p.stdout) == [False, chip, chip and "1,1,1", 2]


def test_register_says_which_slot(monkeypatch):
    from bluesky_tpu.obs.devprof import device_info
    monkeypatch.delenv(SLOT, raising=False)
    assert "slot" not in device_info()
    monkeypatch.setenv(SLOT, "3")
    info = device_info()
    assert info["slot"] == 3
    assert info["count"] >= 1 and info["platform"] == "cpu"   # JAX's own


class FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("found, ends", [
    ([FakeDevice("tpu", "TPU v5 lite")], None),
    ([FakeDevice("tpu", "TPU v5 lite")] * 4, "4 x tpu"),
    ([FakeDevice("cpu", "cpu")], "1 x cpu"),
    (RuntimeError("Unable to initialize backend 'tpu'"), "no device"),
], ids=["its_chip", "every_chip", "the_cpu", "nothing"])
def test_a_worker_without_its_chip_ends_at_start_up(monkeypatch, found,
                                                    ends):
    import jax
    from bluesky_tpu.obs.devprof import require_slot_device

    def devices():
        if isinstance(found, Exception):
            raise found
        return found
    monkeypatch.setattr(jax, "devices", devices)
    monkeypatch.setenv(SLOT, "2")
    require_slot_device()            # a CPU by name: nothing is held
    try:
        # "asked for nothing", and a chip machine's "the chip, then the CPU"
        for platforms in (None, "tpu,cpu"):
            jax.config.update("jax_platforms", platforms)
            if ends is None:
                require_slot_device()
            else:
                with pytest.raises(SystemExit) as e:
                    require_slot_device()
                assert "device slot 2" in str(e.value) \
                    and ends in str(e.value)
        monkeypatch.delenv(SLOT)
        require_slot_device()        # no slot named: nothing is asked
    finally:
        jax.config.update("jax_platforms", "cpu")


# ------------------------------------- served: the system and the reference
def test_two_workers_farm_a_wall_batch_to_the_plain_reference(tmp_path,
                                                              monkeypatch):
    """A real broker with ``max_nnodes`` 2 and the two workers it spawns,
    on a named CPU: both complete pieces of one ``wall_batch`` BATCH, each
    on a slot of its own, the journal has every piece once, and the states
    they echoed agree with ``reference/plain.py`` inside ``wallmc``'s
    limits."""
    from tests.test_network import free_ports, wait_for
    from bluesky_tpu.network.client import Client
    import check
    from generators import wall_batch
    from windows.backlog import read_marks

    with open(os.path.join(REPO, "benchmark", "configs",
                           "wallmc4.json")) as f:
        cfg = json.load(f)
    params = dict(cfg["generator"]["params"],
                  **cfg["rehearsal_size"]["params"])
    # what the workers read: toy capacity (the broker hands the file on)
    cfgfile = tmp_path / "settings.cfg"
    cfgfile.write_text("nmax = 64\ntelnet_port = 0\n")
    monkeypatch.setattr(settings, "config_file", str(cfgfile))
    monkeypatch.delenv("XLA_FLAGS", raising=False)   # conftest's eight
    monkeypatch.setenv("PYTHONPATH", REPO + os.pathsep
                       + os.environ.get("PYTHONPATH", ""))
    ev, st, wev, wst = free_ports(4)
    jpath = str(tmp_path / "batch.jsonl")
    server = srv.Server(headless=True, spawn_workers=True, max_nnodes=2,
                        ports=dict(event=ev, stream=st, wevent=wev,
                                   wstream=wst),
                        hb_interval=0.5, journal_path=jpath)
    server.start()
    client = Client()
    echoes, got = [], {}
    client.event_received.connect(
        lambda n, d, s: echoes.append(
            (time.perf_counter(), str((d or {}).get("text", "")), s))
        if n == b"ECHO" else None)
    client.stream_received.connect(
        lambda n, d, s: got.update(ids=list(d["id"]))
        if n == b"ACDATA" and len(d["id"]) else None)

    def pump(cond, timeout, what):
        assert wait_for(lambda: (client.receive(10), cond())[1],
                        timeout=timeout), what

    def journal():
        if not os.path.exists(jpath):
            return []
        with open(jpath) as f:
            return [json.loads(ln) for ln in f if ln.strip()]
    try:
        client.connect(event_port=ev, stream_port=st, timeout=30.0)
        server.addnodes(2)               # both up before the batch is in
        pump(lambda: len(client.nodes) == 2, 120.0, "two workers registered")
        client.subscribe(b"ACDATA")
        client.stack("; ".join(["HOLD"] + wall_batch.discover(params)))
        pump(lambda: "ids" in got, 120.0, "no ACDATA frame with ids")
        client.unsubscribe(b"ACDATA")
        main = wall_batch.pieces(dict(params, stream=2), 2147484038, 8, "P",
                                 got["ids"])
        client.send_event(b"BATCH", {
            "scentime": [t for p in main for t in p["scentime"]],
            "scencmd": [c for p in main for c in p["scencmd"]]}, target=b"")
        pump(lambda: sum(r.get("rec") == "completed" for r in journal())
             >= 8, 600.0, "the batch did not drain")
        client.last_health = None
        client.request_health()
        pump(lambda: client.last_health is not None, 30.0, "no HEALTH")
    finally:
        server.stop()
        server.join(timeout=15)
        client.close()
        for proc in server.processes:
            if proc.poll() is None:
                proc.kill()
    workers = client.last_health["workers"]
    assert sorted(w["device"]["slot"] for w in workers.values()) == [0, 1]
    assert all(w["device"]["count"] == 1 for w in workers.values())
    assert server.obs.get("server_worker_spawn_ms").count == 2
    recs = journal()
    done = [r for r in recs if r.get("rec") == "completed"]
    keys = [r["key"] for r in recs if r.get("rec") == "queued"]
    assert len(keys) == 8 and sorted(r["key"] for r in done) == sorted(keys)
    assert len({r["worker"] for r in done}) == 2, \
        [r["worker"] for r in done]
    states = {}
    read_marks(echoes, {p["name"] for p in main}, {}, states)
    spec = check.spec_of(cfg, None)
    correct, numbers, _ = check.decide(
        spec, dict(pieces=main, states=states, duplicates=0), 2147484038)
    assert correct, numbers
    assert numbers["mark_states_missing"]["value"] == 0
    assert numbers["pieces_not_once"]["value"] == 0
