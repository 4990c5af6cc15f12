"""A window, a check and a reference are each one new file and a name in
a mix or a configuration: a copy of this directory gets a dummy of each,
a mix, a configuration and a cell that name them, and no edit; a whole
rehearsal run of that cell goes through all three, and ``check.py`` reads
the run's evidence back off the chip with the same three."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

WINDOW = '''"""A window that measures nothing: one aircraft, so that the worker
has compiled something, and its registry twice."""
import time


def run(sv, cfg, mix, size, args, rundir):
    sv.client.stack("HOLD; CRE KL204 B744 52 4 90 FL200 250")
    sv.s.wait_state(lambda r: r["ntraf"] == 1, 300.0, "ntraf == 1")
    m = sv.worker_metrics()
    q = {"setup_s": time.perf_counter() - args.t_process,
         "dummy_quantity": 7.0}
    ctx = dict(window_s=1.0, units=1.0, m0=m, m1=m, f0={}, f1={},
               tracedir=rundir)
    return dict(q=q, ctx=ctx, attempted=1, failed=0, note="a dummy window",
                evidence=dict(answer=mix["answer"],
                              compares=mix["probe"]["compares"],
                              check=mix["probe"]["check"]))
'''
CHECK = '''"""A check that compares one answer with the reference's."""


def numbers(spec, evidence, seed, reference):
    return {"dummy_gap": abs(evidence["answer"] - reference.ANSWER),
            "dummy_seed": float(seed)}


def control_evidence(spec, evidence, seed, reference):
    return dict(evidence, answer=reference.ANSWER + reference.CONTROL_OFF)
'''
REFERENCE = '''"""A reference that knows one answer."""
ANSWER = 42.0
CONTROL_OFF = 3.0
'''


def _copy(tmp_path):
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata", "tests"))
    (bench / "windows" / "dummy.py").write_text(WINDOW)
    (bench / "checks" / "dummy.py").write_text(CHECK)
    (bench / "reference" / "dummy.py").write_text(REFERENCE)
    (bench / "traffic" / "dummy.json").write_text(json.dumps({
        "window": "dummy", "answer": 42.25,
        "reports": {"dummy_rate": "dummy_quantity"},
        "probe": {"check": "dummy", "compares": ["dummy_gap"]}}))
    cfg = json.loads((bench / "configs" / "eu100k.json").read_text())
    cfg["checks"]["dummy"] = {"reference": "dummy",
                              "limits": {"dummy_gap": 0.5}}
    (bench / "configs" / "dummy.json").write_text(json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "dummy", "file": "benchmark/configs/dummy.json"}],
        "workloads": [{"name": "dummy-cell", "config": "dummy",
                       "traffic": "dummy", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "dummy_rate", "unit": "things/s"}],
        "per_layer": []}))
    return bench


def test_a_dummy_window_check_and_reference_run_by_name_alone(tmp_path):
    bench = _copy(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload", "dummy-cell",
         "--seed", "2147483999", "--seconds", "1", "--trace", "0",
         "--rehearsal", "--control", "1"],
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["dummy_rate"] == {"value": 7.0,
                                             "unit": "things/s"}
    assert line["compared"] == {"dummy_gap": {"value": 0.25, "limit": 0.5}}
    assert "control (bfloat16 reference in the program's place): " \
        "correct=False" in p.stderr
    # the off-chip reader finds the same check and reference by the
    # evidence's word and the configuration's
    ev = tmp_path / "benchmark_out" / "dummy-cell" \
        / "seed2147483999_trace0" / "evidence.npz"
    p = subprocess.run(
        [sys.executable, str(bench / "check.py"),
         str(bench / "configs" / "dummy.json"), "5", str(ev)],
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-3000:]
    program, control = p.stdout.strip().splitlines()
    assert "program correct=True" in program and '"dummy_gap": 0.25' in program
    assert "control correct=False" in control and '"dummy_gap": 3.0' in control
