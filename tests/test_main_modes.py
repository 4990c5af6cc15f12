"""The console entry point's mode dispatch, driven as a real user
would: ``python -m bluesky_tpu --detached --scenfile ...`` must run a
scenario to completion and exit cleanly (the reference BlueSky.py
headless workflow)."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow    # spawns a fresh JAX process


def test_detached_scenfile_runs_to_quit(tmp_path):
    scn = tmp_path / "run.scn"
    # the SCREENSHOT at t=10 proves the scenario actually ran to its
    # end (exit code alone would pass even if --scenfile were ignored)
    scn.write_text(
        "00:00:00.00>CRE KL1 B744 52 4 90 FL200 250\n"
        "00:00:00.00>FF\n"
        "00:00:10.00>SCREENSHOT finished.svg\n"
        "00:00:10.00>QUIT\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", BLUESKY_TPU_NO_REF="1",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    out = subprocess.run(
        [sys.executable, "-m", "bluesky_tpu", "--detached",
         "--scenfile", str(scn)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    marker = tmp_path / "finished.svg"
    assert marker.exists() and b"KL1" in marker.read_bytes(), \
        "scenario did not run to its t=10s SCREENSHOT"


def test_attach_requires_web():
    """--attach without --web is a usage error, not a silently-started
    stray server."""
    out = subprocess.run(
        [sys.executable, "-m", "bluesky_tpu", "--attach"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 2
    assert "--attach only applies to --web" in out.stderr


def test_help_lists_all_modes():
    out = subprocess.run(
        [sys.executable, "-m", "bluesky_tpu", "--help"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0
    for mode in ("--headless", "--sim", "--detached", "--client",
                 "--web", "--upstream", "--node-id"):
        assert mode in out.stdout


REF_NAVDATA = "/root/reference/data/navdata"


@pytest.mark.skipif(not os.path.isdir(REF_NAVDATA),
                    reason="reference navdata mount absent")
def test_import_navdata_cli(tmp_path):
    """`bluesky-tpu --import-navdata <dir>` (VERDICT r4 #9): the full
    reference navdata tree imports into a local destination, the pickle
    cache is warmed, and a Navdatabase on the imported tree resolves
    real-world waypoints/airports."""
    dest = tmp_path / "navdata"
    out = subprocess.run(
        [sys.executable, "-m", "bluesky_tpu",
         "--import-navdata", REF_NAVDATA, "--dest", str(dest)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 HOME=str(tmp_path)),      # cache under tmp, not ~/
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr
    assert "imported navdata" in out.stdout
    for name in ("fix.dat", "nav.dat", "airports.dat"):
        assert (dest / name).is_file()

    from bluesky_tpu.navdb.navdatabase import Navdatabase
    db = Navdatabase(navdata_path=str(dest),
                     cache_path=str(tmp_path / "cache"))
    # full-world scale, not the 237-airport builtin
    assert len(db.wpid) > 10000
    assert len(db.aptid) > 2000
    i = db.getaptidx("EHAM")            # Schiphol exists in the import
    assert i >= 0
    assert abs(db.aptlat[i] - 52.3) < 0.2


def test_headless_config_file_capacity_serves_mcre(tmp_path):
    """`python -m bluesky_tpu --headless --config-file <nmax = 2048>`:
    the capacity key reaches the worker the broker spawns, which then
    serves `MCRE 2000` from a client (the default 1024 slots answer
    'traffic full')."""
    import time

    from bluesky_tpu.network.client import Client
    from tests.test_network import free_ports, wait_for

    cfg = tmp_path / "settings.cfg"
    cfg.write_text("nmax = 2048\ntelnet_port = 0\n"
                   f"log_path = {str(tmp_path / 'output')!r}\n")
    ev, st = free_ports(2)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    broker = subprocess.Popen(
        [sys.executable, "-m", "bluesky_tpu", "--headless",
         "--config-file", str(cfg), "--event-port", str(ev),
         "--stream-port", str(st)],
        cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu",
                           BLUESKY_TPU_NO_REF="1"))
    client = Client()
    echoes, states = [], []
    client.event_received.connect(
        lambda n, d, s: (echoes if n == b"ECHO" else states).append(d)
        if n in (b"ECHO", b"SIMSTATE") else None)
    try:
        client.connect(event_port=ev, stream_port=st, timeout=60.0)
        assert wait_for(lambda: (client.receive(10), bool(client.nodes))[1],
                        timeout=120)
        client.stack("HOLD; MCRE 2000")

        def ntraf():
            client.send_event(b"GETSIMSTATE")
            time.sleep(0.2)
            client.receive(10)
            return states and states[-1]["ntraf"]
        assert wait_for(lambda: ntraf() == 2000, timeout=120)
        assert not any("traffic full" in (e or {}).get("text", "")
                       for e in echoes)
    finally:
        client.close()
        broker.terminate()
        try:
            broker.wait(timeout=20)
        except subprocess.TimeoutExpired:
            broker.kill()
