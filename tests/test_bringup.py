"""What a first run on a chip depends on, checked on the CPU at toy
size (ISSUE 21): capacity reaches a spawned worker, the [N,N] pair
matrix exists only under the dense backend, a Pallas backend is
interpreted only on a CPU that was asked for by name, the compile cache
can be placed from outside, and chip_smoke.py's parent stays off JAX.
Nothing here compiles the step."""
import importlib
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bluesky_tpu import settings
from bluesky_tpu.simulation.sim import Simulation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def capacity_file(tmp_path, monkeypatch):
    """A settings file with the capacity key, loaded the way
    ``--config-file`` loads it (and unloaded afterwards)."""
    monkeypatch.setattr(settings, "nmax", settings.nmax)
    monkeypatch.setattr(settings, "config_file", "")
    monkeypatch.setattr(settings, "_overrides", dict(settings._overrides))
    cfg = tmp_path / "settings.cfg"
    cfg.write_text("nmax = 2048\n")
    assert settings.init(str(cfg))
    return str(cfg)


def test_capacity_key_reaches_a_spawned_worker(capacity_file, monkeypatch):
    from bluesky_tpu.network import server as srv
    spawned = []
    monkeypatch.setattr(srv.subprocess, "Popen",
                        lambda argv, **kw: spawned.append(argv) or object())
    server = srv.Server(headless=True, spawn_workers=True, journal_path="")
    try:
        assert server.max_nnodes == 1          # one process per chip
        server.addnodes(1)
    finally:
        for sock in (server.fe_event, server.fe_stream, server.be_event,
                     server.be_stream):
            sock.close()
    argv = spawned[0]
    assert argv[argv.index("--config-file") + 1] == capacity_file


def test_simulation_takes_its_capacity_from_settings(capacity_file):
    sim = Simulation()
    assert sim.traf.nmax == 2048
    sim.stack.stack("MCRE 2000")
    sim.stack.process()
    assert sim.traf.ntraf == 2000
    assert not any("traffic full" in e for e in sim.scr.echobuf)


def test_pair_matrix_only_under_the_dense_backend():
    def flushed_shape(sim, *cmds):
        for c in cmds:
            sim.stack.stack(c)
        sim.stack.process()
        sim.traf.flush()               # what precedes every dispatch
        return sim.traf.state.asas.resopairs.shape

    sim = Simulation(nmax=64)
    assert sim.traf.state.asas.resopairs.shape == (0, 0)   # not yet run
    assert flushed_shape(sim, "CRE A1 B744 52 4 90 FL200 250") == (64, 64)
    assert flushed_shape(sim, "CDMETHOD SPARSE") == (0, 0)
    assert flushed_shape(sim, "CDMETHOD DENSE") == (64, 64)
    assert flushed_shape(sim, "RESET", "CDMETHOD TILED",
                         "CRE A1 B744 52 4 90 FL200 250") == (0, 0)

    # a 100k-slot sim under a blockwise backend never holds [N,N]
    # (10 GB): CDMETHOD comes before the first flush
    big = Simulation(nmax=100_000)
    big.stack.stack("RESET")       # back to the (dense) default config
    big.stack.process()
    assert big.traf.state.asas.resopairs.shape == (0, 0)
    assert flushed_shape(big, "CDMETHOD SPARSE", "MCRE 10") == (0, 0)
    assert max(a.size for a in jax.tree.leaves(big.traf.state)) \
        < 100_000 * 100


def test_interpreter_only_on_a_cpu_asked_for_by_name():
    from bluesky_tpu.ops.cd_pallas import interpret_default
    assert jax.config.jax_platforms == "cpu"     # conftest, by name
    assert interpret_default(None) is True
    assert interpret_default(False) is False     # explicit wins
    jax.config.update("jax_platforms", None)     # "asked for nothing"
    try:
        with pytest.raises(RuntimeError, match="TFRT_CPU|cpu"):
            interpret_default(None)
    finally:
        jax.config.update("jax_platforms", "cpu")


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    import bluesky_tpu
    var = "JAX_COMPILATION_CACHE_DIR"
    monkeypatch.setenv(var, str(tmp_path))
    importlib.reload(bluesky_tpu)
    assert os.environ[var] == str(tmp_path)
    monkeypatch.delenv(var)
    importlib.reload(bluesky_tpu)
    assert os.environ[var] == os.path.join(REPO, ".jax_cache")
    monkeypatch.delenv(var)
    # and no code names a directory to jax: the variable is the one way
    key = "jax_compilation" + "_cache_dir"
    for root in ("bluesky_tpu", "scripts", "tests"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            for f in files:
                if f.endswith(".py"):
                    with open(os.path.join(dirpath, f)) as fh:
                        assert key not in fh.read(), f
    for f in ("bench.py", "chip_smoke.py", "check.py", "__graft_entry__.py"):
        with open(os.path.join(REPO, f)) as fh:
            assert key not in fh.read(), f


def test_chip_smoke_alone_fails_and_its_parent_stays_off_jax(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo the script fails and prints no result; the parent process has
    imported neither jax nor the package when it ends."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    code = (
        "import runpy, sys\n"
        "sys.argv = ['chip_smoke.py']\n"
        "try:\n"
        "    runpy.run_path('chip_smoke.py', run_name='__main__')\n"
        "    rc = 0\n"
        "except SystemExit as e:\n"
        "    rc = e.code\n"
        "assert 'jax' not in sys.modules, 'parent imported jax'\n"
        "assert 'bluesky_tpu' not in sys.modules\n"
        "sys.exit(rc)\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "phase served failed" in out.stdout
    assert '"ok"' not in out.stdout
