"""The configuration ``ff1000`` and its cell ``ff1000-op`` as committed.

A ``--rehearsal`` of the cell comes out correct, its traced toy line
carries the metrics the cell brings, and ``steps_per_sim_s.op`` reads 20
on a clock that counts; the bfloat16 control and a run whose worker skips
detection come out not correct.  ``generators/cre_flow.py`` gives exactly
``n`` unique callsigns, the same lines for the same seed, and a fleet that
stays where the configuration says it does: south of 80N and west of 180E
for 60,000 s of straight flight, with two ownships in a hundred and more
in conflict at 12,000 s, by rhumb-line flight and the plain reference's
detection.  ``readers/counter_ratio.py`` on made-up registries.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from generators import cre_flow                      # noqa: E402
from readers import counter_ratio                    # noqa: E402
from reference import plain                          # noqa: E402

CELL = "ff1000-op"
SEED = 2147484042

with open(os.path.join(BENCH, "configs", "ff1000.json")) as _f:
    CFG = json.load(_f)
PARAMS = CFG["generator"]["params"]


def _run(trace=0, control=0, env=None, seed=SEED):
    # the cell asks for four chips (for steadiness: one worker on one of
    # them), and the harness holds a rehearsal to the count too
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               **(env or {}))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(HERE, "breaker_cd"), ROOT, env.get("PYTHONPATH", "")])
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(seed), "--seconds", "6", "--trace", str(trace),
         "--control", str(control), "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


# ------------------------------------------------------------------ the cell
def test_the_committed_cell_rehearses_and_its_control_is_not_correct():
    line, err = _run(control=1)
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["rehearsal"] is True
    assert set(line["metrics"]) == {"setup_s", "sim_rate"}
    assert set(line["compared"]) == {"interval_flag_mismatch_share",
                                     "interval_position_gap_p99_m"}
    # nobody turns: the fly-back is exact and the whole fleet is steady
    also = json.loads(err.split("also read ")[-1].splitlines()[0])
    assert also["interval_unsteady_share"] == 0.0
    ctl = next(ln for ln in err.splitlines() if ln.startswith("control "))
    assert "correct=False" in ctl, ctl
    numbers = json.loads(ctl.split("correct=False ", 1)[1].split(" also ")[0])
    assert any(v["value"] > v["limit"] for v in numbers.values())


def test_the_traced_toy_line_carries_the_cells_metrics():
    line, _ = _run(trace=1)
    assert line["correct"] is True, line["compared"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    mine = {m["name"] for m in doc["per_layer"]
            if CELL in m.get("workloads", ())
            and m["source"] not in ("device_trace", "host_clock")}
    assert {"dispatch_ms.op", "node_poll_ms.op", "cd_dense_rows.op",
            "steps_per_sim_s.op"} <= mine
    assert not mine - set(line["metrics"]), mine - set(line["metrics"])
    assert line["metrics"]["steps_per_sim_s.op"]["value"] \
        == pytest.approx(20.0, abs=2e-3)
    assert line["metrics"]["cd_dense_rows.op"]["value"] == 128.0


def test_a_worker_that_skips_detection_is_not_correct():
    line, _ = _run(env={"BENCHMARK_SKIP_DETECTION": "1"})
    assert line["correct"] is False, line
    gap = line["compared"]["interval_flag_mismatch_share"]
    assert gap["value"] > gap["limit"]
    # and nothing else is wrong with it: it flies as it should
    assert line["compared"]["interval_position_gap_p99_m"]["value"] == 0.0


# ------------------------------------------------------------- the generator
@pytest.mark.parametrize("n", (1, 100, 1000))
def test_cre_flow_gives_n_lines_and_unique_callsigns(n):
    lines = cre_flow.commands(PARAMS, SEED, n)
    assert len(lines) == n
    assert all(ln.startswith("CRE ") and ln.count(",") == 6 for ln in lines)
    assert len({ln.split(",")[0] for ln in lines}) == n
    assert lines == cre_flow.commands(PARAMS, SEED, n)
    assert n == 1 or lines != cre_flow.commands(PARAMS, SEED + 1, n)
    for ln in lines:
        acid, typ, lat, lon, hdg, alt, spd = ln[4:].split(",")
        assert typ in PARAMS["types"]
        assert PARAMS["box"][0] <= float(lat) <= PARAMS["box"][1]
        assert PARAMS["box"][2] <= float(lon) <= PARAMS["box"][3]
        assert 85.0 <= float(hdg) <= 95.0
        assert alt.startswith("FL") and int(alt[2:]) % 10 == 0 \
            and 300 <= int(alt[2:]) <= 400
        assert 0.78 <= float(spd) <= 0.84


def _flown(f, t, dt=10.0):
    """The fleet ``t`` seconds on: rhumb-line flight at the drawn Mach
    number in steps of ``dt`` (BlueSky's position update), as a frame
    the plain reference detects on."""
    lat, lon = f["lat"].copy(), f["lon"].copy()
    h = (f["fl"] * 100 * plain.FT).astype(np.float32)
    gs = np.asarray(plain.vmach2tas(f["mach"].astype(np.float32), h),
                    np.float64)
    hr = np.radians(f["hdg"])
    for _ in range(int(t / dt)):
        lat = lat + np.degrees(dt * gs * np.cos(hr) / plain.REARTH)
        lon = lon + np.degrees(dt * gs * np.sin(hr)
                               / np.cos(np.radians(lat)) / plain.REARTH)
    f32 = np.float32
    return dict(lat=lat.astype(f32), lon=lon.astype(f32), alt=h,
                trk=f["hdg"].astype(f32), gs=gs.astype(f32),
                vs=np.zeros(len(lat), f32))


@pytest.mark.parametrize("seed", (1, 4200000001, 2**31 + 5))
def test_the_flow_keeps_its_conflicts_and_stays_on_the_map(seed):
    n = 1000
    f = cre_flow.fleet(PARAMS, seed, n)
    flagged = {}
    for t in (0, 12_000, 24_000):
        inconf, _, _ = plain.interval_of_sample(np.arange(n), _flown(f, t))
        flagged[t] = int(inconf.sum())
    # a third of the fleet at the start, a tenth at the probe of a chip
    # run, two in a hundred after twice that: never under 2%
    assert flagged[0] > 300 and flagged[12_000] >= 100 \
        and flagged[24_000] >= 20, flagged
    far = _flown(f, 60_000)
    assert far["lat"].max() < 80.0 and far["lat"].min() > 0.0
    assert far["lon"].max() < 180.0, float(far["lon"].max())


# ---------------------------------------------------------------- the reader
def _dump(**series):
    return "sim registry:\n" + "".join(f"{k}: {v:g}\n"
                                       for k, v in series.items())


@pytest.mark.parametrize("m0, m1, reads", [
    (dict(sim_steps=12000, sim_clock_s=600), dict(sim_steps=216000,
                                                  sim_clock_s=10800), 20.0),
    (dict(), dict(sim_steps=400, sim_clock_s=20), 20.0),    # from nought
    (dict(sim_steps=12000, sim_clock_s=600),
     dict(sim_steps=12000, sim_clock_s=600), None),         # nothing ran
    (dict(sim_steps=1), dict(sim_steps=2), None),           # no such counter
    (dict(), dict(), None),                                 # the parent
], ids=["window", "from_nought", "held", "one_missing", "neither"])
def test_counter_ratio_on_made_up_registries(m0, m1, reads):
    ctx = dict(m0={"01": _dump(**m0)}, m1={"01": _dump(**m1)})
    got = counter_ratio.read(ctx, {"num": ["sim_steps"],
                                   "den": ["sim_clock_s"]})
    assert got == pytest.approx(reads) if reads is not None else got is None
