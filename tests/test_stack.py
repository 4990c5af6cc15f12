"""Stack tests: command parsing, dispatch, scenario replay, route editing.

Models the reference's TCP end-to-end tests (test/tcp/test_simple.py: send
command text, assert echoed responses) but in-process against the Simulation
object — no sockets needed for command semantics.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bluesky_tpu.simulation.sim import Simulation
from bluesky_tpu.ops import aero


@pytest.fixture()
def sim():
    return Simulation(nmax=32, dtype=jnp.float64)


def do(sim, *lines):
    for line in lines:
        sim.stack.stack(line)
    sim.stack.process()
    out = "\n".join(sim.scr.echobuf)
    sim.scr.echobuf.clear()
    return out


def test_cre_and_pos(sim):
    out = do(sim, "CRE KL204 B744 52 4 90 FL200 250", "POS KL204")
    assert "KL204" in out and "20000 ft" in out
    assert sim.traf.ntraf == 1
    i = sim.traf.id2idx("KL204")
    assert float(sim.traf.state.ac.alt[i]) == pytest.approx(20000 * aero.ft)
    assert float(sim.traf.state.ac.cas[i]) == pytest.approx(250 * aero.kts,
                                                            rel=1e-6)


def test_cre_duplicate_and_syntax_error(sim):
    do(sim, "CRE KL204 B744 52 4 90 FL200 250")
    out = do(sim, "CRE KL204 B744 52 4 90 FL200 250")
    assert "exists" in out
    out = do(sim, "CRE")
    assert "Usage" in out or "missing" in out
    out = do(sim, "FOO BAR")
    assert "Unknown command" in out


def test_acid_first_syntax(sim):
    do(sim, "CRE KL204 B744 52 4 90 FL200 250")
    do(sim, "KL204 ALT FL300")
    i = sim.traf.id2idx("KL204")
    assert float(sim.traf.state.ac.selalt[i]) == pytest.approx(30000 * aero.ft)


def test_alt_spd_hdg_vs(sim):
    do(sim, "CRE KL204 B744 52 4 90 FL200 250")
    i = sim.traf.id2idx("KL204")
    do(sim, "ALT KL204 FL300")
    assert float(sim.traf.state.ac.selalt[i]) == pytest.approx(30000 * aero.ft)
    do(sim, "SPD KL204 280")
    assert float(sim.traf.state.ac.selspd[i]) == pytest.approx(280 * aero.kts)
    do(sim, "SPD KL204 M.82")
    assert float(sim.traf.state.ac.selspd[i]) == pytest.approx(0.82)
    do(sim, "HDG KL204 180")
    assert float(sim.traf.state.ap.trk[i]) == pytest.approx(180.0)
    assert not bool(sim.traf.state.ac.swlnav[i])
    do(sim, "VS KL204 1000")
    assert float(sim.traf.state.ac.selvs[i]) == pytest.approx(1000 * aero.fpm)


def test_del_and_delall(sim):
    do(sim, "CRE A1 B744 52 4 90 FL200 250", "CRE A2 B744 53 4 90 FL200 250")
    assert sim.traf.ntraf == 2
    do(sim, "DEL A1")
    assert sim.traf.ntraf == 1 and sim.traf.id2idx("A1") == -1
    do(sim, "DELALL")
    assert sim.traf.ntraf == 0


def test_move(sim):
    do(sim, "CRE KL204 B744 52 4 90 FL200 250")
    do(sim, "MOVE KL204 30 5 FL100")
    i = sim.traf.id2idx("KL204")
    st = sim.traf.state
    assert float(st.ac.lat[i]) == pytest.approx(30.0)
    assert float(st.ac.lon[i]) == pytest.approx(5.0)
    assert float(st.ac.alt[i]) == pytest.approx(10000 * aero.ft)


def test_route_editing(sim):
    do(sim, "CRE KL204 B744 52 4 90 FL200 250",
       "ADDWPT KL204 52.2 4.5 FL220",
       "ADDWPT KL204 52.4 5.0")
    out = do(sim, "LISTRTE KL204")
    assert "WP001" in out and "WP002" in out
    i = sim.traf.id2idx("KL204")
    assert int(sim.traf.state.route.nwp[i]) == 2
    # delete one
    do(sim, "DELWPT KL204 WP002")
    assert int(sim.traf.state.route.nwp[i]) == 1
    # direct to remaining
    out = do(sim, "DIRECT KL204 WP001")
    assert int(sim.traf.state.route.iactwp[i]) == 0
    assert bool(sim.traf.state.ac.swlnav[i])


def test_dest_engages_lnav_vnav(sim):
    do(sim, "CRE KL204 B744 52 4 90 FL200 250", "DEST KL204 52.5 6.0")
    i = sim.traf.id2idx("KL204")
    assert bool(sim.traf.state.ac.swlnav[i])
    assert bool(sim.traf.state.ac.swvnav[i])
    r = sim.routes.route(i)
    assert r.nwp == 1 and r.name[0] == "DEST"


def test_zoom_shorthand(sim):
    """'+++'/'--' lines zoom by sqrt(2)^(n+ - n-), '=' counts as '+'
    (reference stack.py:1436-1443) — used all over the scenario
    library (CIRCLE12.SCN, EHAM-TAXI.SCN...)."""
    z0 = sim.scr.scrzoom
    sim.stack.stack("+++")
    sim.stack.process()
    assert sim.scr.scrzoom == pytest.approx(z0 * 2.0 ** 1.5)
    sim.stack.stack("--")
    sim.stack.process()
    assert sim.scr.scrzoom == pytest.approx(z0 * 2.0 ** 0.5)
    sim.stack.stack("=")                     # same key as '+'
    sim.stack.process()
    assert sim.scr.scrzoom == pytest.approx(z0 * 2.0)
    assert not any("Unknown" in l for l in sim.scr.echobuf)


def test_asas_settings(sim):
    do(sim, "ZONER 3")
    assert sim.cfg.asas.rpz == pytest.approx(3 * aero.nm)
    do(sim, "ZONEDH 800")
    assert sim.cfg.asas.hpz == pytest.approx(800 * aero.ft)
    do(sim, "DTLOOK 120")
    assert sim.cfg.asas.dtlookahead == pytest.approx(120.0)
    do(sim, "RESO OFF")
    assert not sim.cfg.asas.reso_on
    do(sim, "RESO MVP")
    assert sim.cfg.asas.reso_on
    do(sim, "ASAS OFF")
    assert not sim.cfg.asas.swasas
    out = do(sim, "ASAS")
    assert "OFF" in out


def test_noreso_resooff_toggle(sim):
    do(sim, "CRE KL204 B744 52 4 90 FL200 250")
    i = sim.traf.id2idx("KL204")
    do(sim, "NORESO KL204")
    assert bool(sim.traf.state.asas.noreso[i])
    do(sim, "NORESO KL204")
    assert not bool(sim.traf.state.asas.noreso[i])
    do(sim, "RESOOFF KL204")
    assert bool(sim.traf.state.asas.resooff[i])


def test_syn_super_and_matrix(sim):
    do(sim, "SYN SUPER 8")
    assert sim.traf.ntraf == 8
    do(sim, "SYN MATRIX 3")
    assert sim.traf.ntraf == 12
    do(sim, "SYN WALL")
    assert sim.traf.ntraf == 21


def test_scenario_file_roundtrip(sim, tmp_path):
    scn = tmp_path / "test.scn"
    scn.write_text(
        "# comment\n"
        "00:00:00.00>CRE KL204 B744 52 4 90 FL200 250\n"
        "00:00:05.00>ALT KL204 FL300\n"
        "00:00:10.00>ECHO scenario done\n")
    ok, _ = sim.stack.openfile(str(scn))
    assert ok
    assert sim.stack.next_trigger_time() == 0.0
    sim.run(until_simt=12.0, max_iters=300)
    assert sim.traf.ntraf == 1
    i = sim.traf.id2idx("KL204")
    assert float(sim.traf.state.ac.selalt[i]) == pytest.approx(30000 * aero.ft)
    assert any("scenario done" in e for e in sim.scr.echobuf)


def test_pcall_argument_substitution(sim, tmp_path):
    scn = tmp_path / "param.scn"
    scn.write_text("00:00:00.00>CRE %0 B744 52 4 90 FL200 250\n")
    do(sim, f"PCALL {scn} ACX")
    sim.run(until_simt=1.0, max_iters=50)
    assert sim.traf.id2idx("ACX") >= 0


def test_delay_and_schedule(sim):
    do(sim, "CRE KL204 B744 52 4 90 FL200 250",
       "DELAY 2 ECHO later", "SCHEDULE 00:00:04 ECHO at4")
    sim.run(until_simt=5.0, max_iters=200)
    joined = "\n".join(sim.scr.echobuf)
    assert "later" in joined and "at4" in joined


def test_saveic_writes_reconstruction(sim, tmp_path):
    sim.stack.scenario_path = str(tmp_path)
    do(sim, "CRE KL204 B744 52 4 90 FL200 250",
       "ADDWPT KL204 52.2 4.5 FL220",
       "SAVEIC mysave")
    do(sim, "ALT KL204 FL300")
    sim.stack.saveclose()
    content = (tmp_path / "mysave.scn").read_text()
    assert "CRE KL204" in content
    assert "ADDWPT KL204" in content
    assert "ALT KL204" in content


def test_wind_command(sim):
    do(sim, "CRE KL204 B744 52 4 90 FL200 250")
    do(sim, "WIND 52 4 270 30")
    assert sim.cfg.use_wind
    assert int(sim.traf.state.wind.winddim) >= 1


def test_dtmult_and_dt(sim):
    do(sim, "DTMULT 5")
    assert sim.dtmult == 5.0
    do(sim, "DT 0.1")
    assert sim.cfg.simdt == pytest.approx(0.1)


def test_calc_and_dist(sim):
    out = do(sim, "CALC 2 + 3")
    assert "5" in out
    out = do(sim, "DIST 0 0 1 0")
    assert "60" in out.split("=")[-1]  # ~60 nm


def test_benchmark_command(sim, tmp_path):
    sim.stack.scenario_path = str(tmp_path)
    (tmp_path / "bench.scn").write_text(
        "00:00:00.00>CRE KL204 B744 52 4 90 FL200 250\n")
    do(sim, "BENCHMARK bench 5")
    sim.run(until_simt=6.0, max_iters=500)
    joined = "\n".join(sim.scr.echobuf)
    assert "Benchmark complete" in joined


def test_snaplog_logger(sim, tmp_path, monkeypatch):
    from bluesky_tpu import settings
    monkeypatch.setattr(settings, "log_path", str(tmp_path))
    do(sim, "CRE KL204 B744 52 4 90 FL200 250", "SNAPLOG ON 1")
    sim.run(until_simt=3.0, max_iters=100)
    do(sim, "SNAPLOG OFF")
    files = list(tmp_path.glob("SNAPLOG*"))
    assert files
    content = files[0].read_text()
    assert "KL204" in content


def test_snaplog_selectvars(sim, tmp_path, monkeypatch):
    """SELECTVARS restricts the logged columns (reference
    datalog.py:216-242); unknown variables are rejected."""
    from bluesky_tpu import settings
    monkeypatch.setattr(settings, "log_path", str(tmp_path))
    do(sim, "CRE KL204 B744 52 4 90 FL200 250")
    out = do(sim, "SNAPLOG SELECTVARS id alt bogus")
    assert "unknown variable" in out and "BOGUS" in out
    do(sim, "SNAPLOG SELECTVARS id alt", "SNAPLOG ON 1")
    sim.run(until_simt=2.0, max_iters=100)
    do(sim, "SNAPLOG OFF")
    content = list(tmp_path.glob("SNAPLOG*"))[0].read_text()
    assert "# simt, id, alt" in content
    datarow = content.splitlines()[2]          # first sample row
    assert len(datarow.split(", ")) == 3       # simt, id, alt only
    assert "KL204" in datarow
    out = do(sim, "SNAPLOG SELECTVARS")
    assert "id, alt" in out
    # selection is locked while the file is open
    do(sim, "SNAPLOG ON 1")
    out = do(sim, "SNAPLOG SELECTVARS id")
    assert "OFF first" in out
    do(sim, "SNAPLOG OFF")


def test_seed_reproducibility(sim):
    do(sim, "SEED 42", "MCRE 3")
    lats1 = np.asarray(sim.traf.state.ac.lat)[:3].copy()
    sim2 = Simulation(nmax=32, dtype=jnp.float64)
    sim2.stack.stack("SEED 42")
    sim2.stack.stack("MCRE 3")
    sim2.stack.process()
    lats2 = np.asarray(sim2.traf.state.ac.lat)[:3]
    np.testing.assert_array_equal(lats1, lats2)


# ------------------------------------------------- the stack's write queue
# A pass of the stack queues its slot writes and creations on the host
# (Traffic.write / Traffic.create) and the next read of traf.state
# applies them as one compiled program (core/traffic.py).  The oracle
# below applies the SAME writes one at a time, in order, in NumPy.

def _np_tree(state):
    return jax.tree.map(lambda x: np.array(x), state)


class _WriteLog:
    """Record what a pass asks of the state, in call order: slot writes,
    creation rows (one write per field and slot), deletions, a RESET's
    fresh state, and the pair matrix allocated for the dense backend."""

    def __init__(self, traf):
        self.ops = []
        write, rows = traf.write, traf._creation_rows
        delete, reset = traf.delete, traf.reset

        def logged_write(sub, field, slot, value):
            self.ops.append(("write", sub, field, int(slot), value))
            write(sub, field, slot, value)

        def logged_rows(batch):
            slots, cols = rows(batch)
            for k, slot in enumerate(slots):
                for (sub, field), (_, vals) in cols.items():
                    self.ops.append(("write", sub, field, int(slot),
                                     vals[k]))
            return slots, cols

        def logged_delete(idx):
            # the queue is applied before the slot is cleared, so what
            # is queued now comes first in the oracle too
            traf.flush()
            self.ops.append(("delete", [int(i) for i in np.atleast_1d(idx)]))
            return delete(idx)

        def logged_reset():
            reset()
            self.ops.append(("assign", _np_tree(traf._state)))

        def logged_sync():
            sync()
            self.ops.append(("pairs", traf._state.asas.resopairs.shape))

        sync, traf._sync_pair_matrix = traf._sync_pair_matrix, logged_sync
        traf.write, traf._creation_rows = logged_write, logged_rows
        traf.delete, traf.reset = logged_delete, logged_reset

    def replay(self, tree):
        """The oracle: every recorded write applied alone."""
        for op in self.ops:
            if op[0] == "assign":
                tree = op[1]
            elif op[0] == "write":
                _, sub, field, slot, value = op
                getattr(getattr(tree, sub), field)[slot] = value
            elif op[0] == "pairs":
                if tree.asas.resopairs.shape != op[1]:
                    tree = tree.replace(asas=tree.asas.replace(
                        resopairs=np.zeros(op[1], bool)))
            else:
                idx = np.asarray(op[1])
                asas = tree.asas
                tree.ac.active[idx] = False
                asas.active[idx] = False
                if asas.resopairs.size:
                    asas.resopairs[idx, :] = False
                    asas.resopairs[:, idx] = False
                asas.partners[idx, :] = -1
                asas.partners[np.isin(asas.partners, idx)] = -1
                sidx = asas.sort_perm[idx]
                asas.partners_s[sidx, :] = -1
                asas.partners_s[np.isin(asas.partners_s, sidx)] = -1
        return tree


def _wall_pass(head=("SCEN P000", "ASAS ON")):
    """A wallmc piece's first pass (benchmark/generators/wall_batch.py):
    SYN WALL, a HDG and a SPD line for each of its 21 aircraft, FF.
    The wall's callsigns are drawn from the host's generator: a fresh
    Simulation repeats them, a reset one only after a ``SEED`` line."""
    probe = Simulation(nmax=32)
    do(probe, *head, "SYN WALL")
    ids = [i for i in probe.traf.ids if i is not None]
    assert len(ids) == 21
    lines = [*head, "SYN WALL"]
    for k, acid in enumerate(ids):
        lines += [f"HDG {acid} {90.0 + 0.37 * k:.2f}",
                  f"SPD {acid} {200.0 + 1.3 * k:.1f}"]
    return lines + ["FF"]


_TWO = ["CRE KL1 B744 52 4 90 FL200 250", "CRE KL2 A320 52.1 4.2 180 FL300 0.78"]
WRITE_PASSES = {
    "wall_first_pass": ([], _wall_pass),
    "move_alt_vs_hdg": (_TWO, ["MOVE KL1 51 3 FL250 270 300 1000",
                               "ALT KL1 FL100", "VS KL1 -1500",
                               "HDG KL1 123"]),
    "same_slot_and_field_twice": (_TWO, ["HDG KL1 123", "SPD KL2 280",
                                         "HDG KL1 124.5", "HDG KL2 10",
                                         "HDG KL1 125.25"]),
    "alt_reads_queued_altitude": (_TWO + ["VS KL1 1500"],
                                  ["MOVE KL1 52 4 FL400",
                                   "ALT KL1 FL300"]),
    "del_then_cre_reuses_slot": (_TWO, ["SPD KL1 230", "DEL KL1",
                                        "CRE KL3 B738 50 2 10 FL150 220",
                                        "HDG KL3 33"]),
    "pos_after_queued_spd": (_TWO, ["SPD KL2 280", "MOVE KL2 48.5 7.25",
                                    "POS KL2", "VS KL2 500"]),
}


@pytest.mark.parametrize("name", sorted(WRITE_PASSES))
def test_write_queue_equals_one_at_a_time_oracle(name):
    setup, lines = WRITE_PASSES[name]
    if callable(lines):
        lines = lines()
    # float32, as the served path runs; free slots are given out
    # longest free first, so a fleet with no slot to spare is the one
    # whose next creation takes the slot just freed
    sim = Simulation(nmax=2 if "reuses_slot" in name else 32)
    do(sim, *setup)
    before = _np_tree(sim.traf.state)
    log = _WriteLog(sim.traf)
    programs = sim.obs.get("sim_state_write_programs")
    p0 = programs.value
    out = do(sim, *lines)
    assert "failed" not in out and "Usage" not in out, out
    got = jax.tree.leaves(_np_tree(sim.traf.state))
    want = jax.tree.leaves(log.replay(before))
    assert not sim.traf.dirty
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    nwrites = sum(op[0] == "write" for op in log.ops)
    assert nwrites > 0 and programs.value > p0
    # the cases' own points, beyond the state
    i = {a: sim.traf.id2idx(a) for a in ("KL1", "KL2", "KL3")}
    ac = sim.traf.state.ac
    if name == "same_slot_and_field_twice":
        assert float(sim.traf.state.ap.trk[i["KL1"]]) == 125.25
    elif name == "alt_reads_queued_altitude":
        # from FL400 down to FL300 with a climb selected: ALT saw the
        # MOVE's altitude and zeroed the selected vertical speed
        assert float(ac.selvs[i["KL1"]]) == 0.0
    elif name == "del_then_cre_reuses_slot":
        assert i["KL1"] == -1 and i["KL3"] == 0
        assert float(ac.selspd[0]) == pytest.approx(220 * aero.kts)
    elif name == "pos_after_queued_spd":
        assert "Pos: 48.5000, 7.2500" in out


def test_second_wall_pass_compiles_nothing_and_counts_its_writes():
    lines = _wall_pass(("SCEN P000", "SEED 1", "ASAS ON"))
    sim = Simulation(nmax=32)
    reg = sim.obs
    do(sim, *lines)
    sim.traf.flush()                   # as the dispatch does
    snap = {k: reg.counter(k).value for k in
            ("devprof_backend_compiles", "sim_state_writes",
             "sim_state_write_programs")}
    h0 = reg.get("sim_state_write_ms").count
    sim.reset()
    assert "not found" not in do(sim, *lines)
    sim.traf.flush()
    assert reg.get("devprof_backend_compiles").value \
        == snap["devprof_backend_compiles"]
    writes = reg.get("sim_state_writes").value - snap["sim_state_writes"]
    programs = reg.get("sim_state_write_programs").value \
        - snap["sim_state_write_programs"]
    assert writes >= 84 and 1 <= programs <= 3
    assert reg.get("sim_state_write_ms").count - h0 == programs


@pytest.mark.parametrize("reader", ["alt", "pos", "acdata", "saveic",
                                    "dispatch", "snapshot"])
def test_no_reader_sees_a_state_without_the_queued_writes(reader, tmp_path):
    from bluesky_tpu.simulation import snapshot
    from bluesky_tpu.simulation.screenio import ScreenIO

    sim = Simulation(nmax=16)
    do(sim, "CRE KL1 B744 52 4 90 FL200 250")
    sim.traf.write("ac", "lat", 0, 48.5)
    sim.traf.write("ac", "alt", 0, 9000.0)
    sim.traf.write("ac", "selvs", 0, 7.5)
    assert sim.traf.dirty
    if reader == "alt":                # reads ac.alt and ac.selvs
        do(sim, "ALT KL1 FL100")
        assert float(sim.traf.state.ac.selvs[0]) == 0.0
    elif reader == "pos":
        assert "Pos: 48.5000, 4.0000" in do(sim, "POS KL1")
    elif reader == "acdata":
        class Node:
            def send_stream(self, name, data):
                self.frame = data
        node = Node()
        ScreenIO(sim, node).send_aircraft_data()
        assert node.frame["lat"][0] == pytest.approx(48.5)
        assert node.frame["alt"][0] == pytest.approx(9000.0)
    elif reader == "saveic":
        sim.stack.scenario_path = str(tmp_path)
        do(sim, "SAVEIC queued")
        sim.stack.saveclose()
        text = (tmp_path / "queued.scn").read_text()
        assert "CRE KL1 B744 48.500000 4.000000" in text
    elif reader == "dispatch":
        sim.op()
        sim.step()
        sim.drain_pipeline()
        assert abs(float(sim.traf.state.ac.lat[0]) - 48.5) < 0.01
    else:
        ac = snapshot.state_blob(sim)["state"].ac
        assert ac.lat[0] == pytest.approx(48.5)
        assert ac.selvs[0] == pytest.approx(7.5)
    assert not sim.traf.dirty
