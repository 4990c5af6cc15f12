"""The harness takes the workers a configuration states.  A copy of this
directory whose ``wallmc`` says ``workers`` 2 and ``max_nnodes`` 2 runs a
whole rehearsal of ``wallmc-farm`` on two CPU workers: both complete
pieces inside the window, ``correct`` is true, the device counts 2 over
2 workers and every ``.farm`` metric that needs no device is in the
line.  The same copy with the cell asking for 4 chips ends before the
window and names both workers.  Two workers whose echoes interleave,
told apart by one shared mark, come out **not** correct; by a mark each,
correct: the per-sender mark is what keeps a fleet's states apart."""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import check                                         # noqa: E402
from generators import wall_batch                    # noqa: E402
from checks import pieces as pieces_check            # noqa: E402
from reference import plain                          # noqa: E402
from served import HarnessFailure, fleet_device, require_device  # noqa: E402
from windows import backlog                          # noqa: E402

SEED = 2147484001


def _copy(tmp_path, chips):
    """This directory with a two-worker ``wallmc`` and a ``wallmc-farm``
    that asks for ``chips``."""
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata", "tests"))
    path = bench / "configs" / "wallmc.json"
    cfg = json.loads(path.read_text())
    cfg["deployment"]["workers"] = 2
    cfg["settings"]["max_nnodes"] = 2
    cfg["rehearsal_size"]["pieces"] = 16
    path.write_text(json.dumps(cfg))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for w in doc["workloads"]:
        if w["name"] == "wallmc-farm":
            w["chips"] = chips
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return bench, doc


def _run(bench, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload", "wallmc-farm",
         "--seed", str(SEED), "--seconds", "3", "--trace", str(trace),
         "--rehearsal"],
        env=env, capture_output=True, text=True, timeout=1200)


def test_a_two_worker_rehearsal_is_correct_and_counts_its_fleet(tmp_path):
    bench, doc = _copy(tmp_path, chips=1)
    p = _run(bench, trace=1)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["compared"]["pieces_not_once"]["value"] == 0
    assert line["compared"]["mark_states_missing"]["value"] == 0
    assert line["device"]["count"] == 2 and line["device"]["workers"] == 2
    assert line["device"]["memory_peak_bytes"] > 0
    # every worker is listed with its device before the window opens
    assert p.stderr.count(" on platform cpu, device_kind cpu, count 1") == 3
    farm = [m["name"] for m in doc["per_layer"]
            if "wallmc-farm" in m.get("workloads", ["wallmc-farm"])
            and m["source"] != "device_trace"]
    assert len(farm) >= 9 and not set(farm) - set(line["metrics"]), \
        set(farm) - set(line["metrics"])
    with open(tmp_path / "benchmark_out" / "wallmc-farm"
              / f"seed{SEED}_trace1" / "pieces.jsonl") as f:
        rows = [json.loads(ln) for ln in f]
    done = [r for r in rows if r["completed_s"] is not None]
    # (the traced worker's piece may still run when the window closes)
    assert len(rows) == line["attempted"] and len(done) >= 8
    by = {r["worker"] for r in done}
    assert len(by) == 2 and None not in by, by


def test_a_cell_that_asks_for_more_chips_than_its_fleet_has(tmp_path):
    bench, _ = _copy(tmp_path, chips=4)
    p = _run(bench, trace=0)
    assert p.returncode == 1 and not p.stdout.strip(), p.stdout[-500:]
    said = [ln for ln in p.stderr.splitlines()
            if ln.startswith("benchmark: the cell asks for 4 chip(s)")]
    assert len(said) == 1, p.stderr[-3000:]
    assert "2 device(s) over 2 workers" in said[0]
    assert said[0].count("platform 'cpu', device_kind 'cpu', 1 device(s)") \
        == 2
    assert "broker/worker log" in p.stderr
    assert "warm-up pieces done" not in p.stderr     # before the window


def _worker(k, count=1, kind="cpu"):
    return {"worker": f"0{k}aa", "platform": "cpu", "device_kind": kind,
            "count": count}


def test_require_device_names_every_worker_of_a_fleet():
    fleet = fleet_device([_worker(1), _worker(2)])
    assert fleet["count"] == 2 and fleet["workers"] == 2
    require_device(fleet, 2, True)
    require_device(fleet, None, True)        # a fleet still growing
    with pytest.raises(HarnessFailure) as e:
        require_device(fleet, 4, True)
    assert "asks for 4 chip(s)" in str(e.value)
    assert "worker 01aa: platform 'cpu'" in str(e.value) \
        and "worker 02aa: platform 'cpu'" in str(e.value)
    with pytest.raises(HarnessFailure, match="no accelerator"):
        require_device(fleet, 2, False)
    with pytest.raises(HarnessFailure, match="different devices"):
        require_device(fleet_device([_worker(1), _worker(2, kind="other")]),
                       2, True)
    one = fleet_device([_worker(1)])
    with pytest.raises(HarnessFailure) as e:     # one worker: as it was
        require_device(one, 4, True)
    assert str(e.value) == "the cell asks for 4 chip(s): platform 'cpu', " \
        "device_kind 'cpu', 1 device(s)"


# ---- two workers' echoes, interleaved -------------------------------
def _echoes(piece, lat, lon, sender):
    """What a piece echoes at its first mark, as the worker ``sender``
    sends it: the mark, then POS of the echoed aircraft."""
    out = [(0.0, f"{piece['name']} MARK0", sender)]
    for k, a in enumerate(piece["aircraft"]):
        if a["echoed"][0]:
            out.append((0.0, f"Info on {a['id']} B744 index = {k}\n"
                        f"Pos: {lat[k]:.4f}, {lon[k]:.4f}\nHdg: 90", sender))
    return out


def _decided(shared):
    """``correct`` and the numbers of two pieces on two workers whose
    echoes arrive in turns, told apart by a mark for each sender or
    (``shared``) by one mark for all."""
    with open(os.path.join(BENCH, "configs", "wallmc.json")) as f:
        cfg = json.load(f)
    params = dict(cfg["generator"]["params"], stream=2, marks_s=[20.0])
    params["echo_aircraft"] = params["echo_aircraft"][:1]
    ids = ["OWNSHIP"] + [f"AC{k:02d}" for k in range(20)]
    two = wall_batch.pieces(params, SEED, 2, "P", ids)
    lat, lon = pieces_check.step_pieces(two, plain.Precision(), 1, plain)[0]
    n = len(two[0]["aircraft"])
    a = _echoes(two[0], lat[:n], lon[:n], b"\x00wkrA")
    b = _echoes(two[1], lat[n:], lon[n:], b"\x00wkrB")
    turns = [e for pair in zip(a, b) for e in pair]
    if shared:
        turns = [(t, text, None) for t, text, _ in turns]
    states = {}
    backlog.read_marks(turns, {p["name"] for p in two}, {}, states)
    return check.decide(cfg["check"], dict(pieces=two, states=states,
                                           duplicates=0), SEED)


def test_interleaved_echoes_need_a_mark_for_each_sender():
    ok, numbers, _ = _decided(shared=False)
    assert ok is True, numbers
    ok, numbers, _ = _decided(shared=True)
    assert ok is False
    assert numbers["mark_states_missing"]["value"] > 0
