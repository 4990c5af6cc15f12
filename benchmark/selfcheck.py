#!/usr/bin/env python3
"""Rehearsal checks, run by hand (``python3 benchmark/selfcheck.py``; not
collected by tier-1, ~10 minutes on the CPU):

1. the trace reduction gives the recorded idle share, program time and
   kernel time on ``testdata/small_trace.json.gz``;
2. a run with no chip and no ``--rehearsal`` exits non-zero, prints no
   result and names the platform;
3. every cell of BENCHMARK.json at toy size on a named CPU prints a last
   line with exactly the contract's keys, every end-to-end metric the
   cell reports with its unit, and, traced, the per-layer metrics that
   need no device.
"""
import gzip
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from trace_reduce import DeviceTrace            # noqa: E402

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def check(cond, msg):
    if not cond:
        raise SystemExit(f"selfcheck FAILED: {msg}")
    print(f"  ok: {msg}", flush=True)


def run_cell(cell, trace, rehearsal=True, seconds=8):
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
           "--seed", "3000000019", "--seconds", str(seconds),
           "--trace", str(trace)] + (["--rehearsal"] if rehearsal else [])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=3000)


def trace_reduction():
    with gzip.open(os.path.join(HERE, "testdata",
                                "small_trace.json.gz"), "rt") as f:
        tr = DeviceTrace.from_chrome(json.load(f))
    with open(os.path.join(HERE, "testdata",
                           "small_trace.expected.json")) as f:
        want = json.load(f)
    s = tr.summary()
    idle = 100.0 * (1.0 - s["busy_s"] / s["window_s"])
    kern, nk = tr.time_of(want["kernel_patterns"])
    for name, got in (("idle_share_pct", idle),
                      ("module_ms", 1e3 * s["module_s"]),
                      ("kernel_ms", 1e3 * kern), ("kernel_events", nk)):
        check(abs(got - want[name]) <= 1e-6 * max(1.0, abs(want[name])),
              f"recorded trace: {name} = {got:.6f} (recorded "
              f"{want[name]:.6f})")
    bd = tr.breakdown()
    check(bd["device_ops"] and len(bd["device_ops"]) <= 10
          and len(bd["idle_gaps"]) <= 10, "breakdown has at most ten "
          "operations and gaps")


def main():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    print("trace reduction", flush=True)
    trace_reduction()
    print("no chip, no --rehearsal", flush=True)
    first = bench["workloads"][0]["name"]
    p = run_cell(first, 0, rehearsal=False)
    check(p.returncode != 0 and not p.stdout.strip()
          and "platform 'cpu'" in p.stderr,
          f"{first} without a chip: exit {p.returncode}, no result, "
          "names platform 'cpu'")
    for w in bench["workloads"]:
        for trace in (0, 1):
            print(f"{w['name']} --trace {trace} at toy size", flush=True)
            p = run_cell(w["name"], trace)
            check(p.returncode == 0, f"exit 0 ({p.stderr[-400:]!r})")
            line = json.loads(p.stdout.strip().splitlines()[-1])
            check(KEYS <= set(line) and DEVICE_KEYS <= set(line["device"])
                  and line.get("rehearsal") is True
                  and list(line)[-1] == "compared",
                  "the line has the contract's keys, says rehearsal, "
                  "and ends with the numbers compared")
            check(line["device"]["platform"] == "cpu"
                  and "busy_s" not in line["device"],
                  "the device is the CPU and no device time is printed")
            check(line["correct"] is True, f"correct ({line['compared']})")
            group = "per_layer" if trace else "end_to_end"
            for m in bench[group]:
                if "workloads" in m and w["name"] not in m["workloads"]:
                    continue
                if trace and m["source"] == "device_trace":
                    check(m["name"] not in line["metrics"],
                          f"{m['name']}: no device trace on the CPU, so "
                          "it is left out, not zero")
                    continue
                got = line["metrics"].get(m["name"])
                # a count can be nought (six blocks of 256 cannot
                # overflow a row); a metric that is missing cannot
                check(got is not None and got["unit"] == m["unit"]
                      and got["value"] >= 0,
                      f"{m['name']} = {got and got['value']} {m['unit']}")
    print("selfcheck passed")


if __name__ == "__main__":
    main()
