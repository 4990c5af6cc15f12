"""The cell ``wallmc-farm4`` as committed: a whole ``--rehearsal`` on four
CPU workers, each on a device slot of its own, comes out correct with the
device counting 4 over 4 workers; a traced toy line carries the three
metrics the cell brings (``broker_busy_ms.farm4``, ``broker_turn_ms.farm4``,
``worker_balance.farm4``) beside every ``.farm`` metric that needs no
device.  ``readers/worker_balance.py`` on made-up registries: an even
fleet reads 1.0, a fleet with an idle worker 0.0, one worker nothing."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from readers import worker_balance                   # noqa: E402

SEED = 2147484004
CELL = "wallmc-farm4"


def _run(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(SEED), "--seconds", "3", "--trace", str(trace),
         "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_the_committed_cell_rehearses_on_four_workers(trace):
    line, err = _run(trace)
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["rehearsal"] is True
    assert line["device"]["count"] == 4 and line["device"]["workers"] == 4
    assert err.count(" on platform cpu, device_kind cpu, count 1") == 5
    rundir = os.path.join(ROOT, "benchmark_out", CELL,
                          f"seed{SEED}_trace{trace}")
    with open(os.path.join(rundir, "broker.log")) as f:
        log = f.read()
    # four workers, four slots, each said once at the spawn
    assert sorted(ln.rsplit(" ", 1)[1] for ln in log.splitlines()
                  if " spawned on device slot " in ln) == list("0123")
    with open(os.path.join(rundir, "pieces.jsonl")) as f:
        rows = [json.loads(ln) for ln in f]
    by = {r["worker"] for r in rows if r["completed_s"] is not None}
    assert len(by) == 4 and None not in by, by
    if not trace:
        assert set(line["metrics"]) == {"setup_s", "pieces_rate"}
        return
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    mine = [m["name"] for m in doc["per_layer"]
            if CELL in m["workloads"] and m["source"] != "device_trace"]
    assert {"broker_busy_ms.farm4", "broker_turn_ms.farm4",
            "worker_balance.farm4"} <= set(mine) and len(mine) == 12
    assert not set(mine) - set(line["metrics"]), \
        set(mine) - set(line["metrics"])
    assert 0.0 < line["metrics"]["worker_balance.farm4"]["value"] <= 1.0
    assert line["metrics"]["broker_busy_ms.farm4"]["value"] \
        >= line["metrics"]["broker_turn_ms.farm4"]["value"] > 0.0


def _dump(chunks):
    return ("sim registry:\nsim_chunk_latency_ms: n=%d sum=1.0 p50=1\n"
            "sim_other: n=999\n" % chunks)


@pytest.mark.parametrize("before, after, reads", [
    ([4, 4], [22, 22], 1.0),
    ([4, 4, 4, 4], [40, 40, 40, 22], 4 * 18 / (3 * 36 + 18)),
    ([4, 8], [40, 8], 0.0),                 # one worker idle
    ([4], [22], None),                      # no fleet
    ([4, 4], [4, 4], None),                 # nothing retired
], ids=["even", "one_short", "one_idle", "one_worker", "no_work"])
def test_worker_balance_on_made_up_registries(before, after, reads):
    ctx = dict(m0={f"0{k}": _dump(n) for k, n in enumerate(before)},
               m1={f"0{k}": _dump(n) for k, n in enumerate(after)})
    got = worker_balance.read(ctx, {"hist": "sim_chunk_latency_ms"})
    assert got == pytest.approx(reads) if reads is not None else got is None


def test_a_worker_that_joined_inside_the_window_counts_from_nought():
    ctx = dict(m0={"01": _dump(10)}, m1={"01": _dump(28), "02": _dump(9)})
    assert worker_balance.read(ctx, {"hist": "sim_chunk_latency_ms"}) \
        == pytest.approx(9 / 13.5)


def test_the_traced_worker_is_left_out_of_the_balance():
    """The harness keys the dumps by the workers' ids as bytes and names
    the traced worker in hex; its shortfall is the profiler's."""
    ids = [bytes.fromhex(h) for h in ("00aa", "00bb", "00cc", "00dd")]
    ctx = dict(m0={w: _dump(4) for w in ids},
               m1=dict(zip(ids, map(_dump, (6, 40, 40, 31)))),
               traced_worker="00aa")
    assert worker_balance.read(ctx, {"hist": "sim_chunk_latency_ms"}) \
        == pytest.approx(27 * 3 / (36 + 36 + 27))
    ctx["traced_worker"] = "00dd"
    assert worker_balance.read(ctx, {"hist": "sim_chunk_latency_ms"}) \
        == pytest.approx(2 * 3 / (2 + 36 + 36))
    ctx["m1"] = {w: ctx["m1"][w] for w in ids[2:]}     # one left: no fleet
    assert worker_balance.read(ctx, {"hist": "sim_chunk_latency_ms"}) is None
