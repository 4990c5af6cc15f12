#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, on the machine it is started on.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearsal]

The cell names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<mix>.json``); the mix names its window kind
(``windows/<kind>.py``), the configuration its generator and its check
(``checks/<kind>.py``, ``reference/<name>.py``).  Nothing here names a
cell, a configuration, a metric, a window or a check: see README.md.

The window is driven through ``python -m bluesky_tpu --headless``, the
workers it spawns (``deployment.workers`` of the configuration, one
unless it says more) and a ``network.client.Client`` in this process,
which never imports JAX.  The last line of standard output is the result.
"""
import argparse
import importlib
import json
import os
import shutil
import signal
import sys
import time

T_PROCESS = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import check                                             # noqa: E402
from served import HarnessFailure, Served, metric, require_device  # noqa: E402
from trace_reduce import DeviceTrace                     # noqa: E402
from windows._common import stage                        # noqa: E402


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# =====================================================================
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also put the bfloat16 reference in the "
                         "program's place and print what the comparison "
                         "makes of it (the builder's control runs)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes on a named CPU: debugs the harness, "
                         "prints no metric under a device's name")
    args = ap.parse_args(argv)
    args.t_process = T_PROCESS

    def stop(signum, frame):      # ended from outside: still end the
        raise SystemExit(128 + signum)   # broker and the worker (finally)
    signal.signal(signal.SIGTERM, stop)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"no cell {args.workload!r} in BENCHMARK.json")
    centry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT, centry["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    window = importlib.import_module("windows." + mix["window"])
    spec = check.spec_of(cfg, mix.get("probe", {}).get("check"))
    size = cfg["rehearsal_size"] if args.rehearsal else cfg["size"]
    rundir = os.path.join(ROOT, "benchmark_out", args.workload,
                          f"seed{args.seed}_trace{args.trace}")
    shutil.rmtree(rundir, ignore_errors=True)
    os.makedirs(rundir)

    settings = dict(cfg["settings"], **size.get("settings", {}))
    workers = int(cfg.get("deployment", {}).get("workers", 1))
    chips = int(cell["chips"])
    sv = None
    try:
        sv = Served(ROOT, rundir, settings, args.rehearsal, workers, chips)
        dev = sv.device
        # a fleet grows after the first registration: its count is held
        # on the sum, by the window's ``expect_workers``
        require_device(dev, chips if workers == 1 else None, args.rehearsal)
        stage(args, "worker registered")
        print(f"run: {args.workload} seed {args.seed} on platform "
              f"{dev['platform']}, device_kind {dev['device_kind']}, "
              f"count {dev['count']}", file=sys.stderr, flush=True)
        out = window.run(sv, cfg, mix, size, args, rundir)
        dev = sv.device
        if dev["workers"] != workers:
            raise HarnessFailure(
                f"the configuration states {workers} workers and the "
                f"window gathered {dev['workers']} (expect_workers)")
        # m0, m1: {worker id: METRICS DUMP}; ``metric`` of a counter is
        # the fleet's sum, so one compile or trip on any worker shows
        m0, m1 = out["ctx"]["m0"], out["ctx"]["m1"]
        c0 = metric(m0, "devprof_backend_compiles")
        c1 = metric(m1, "devprof_backend_compiles")
        if c0 is None or c0 != c1:
            raise HarnessFailure(
                f"compilation inside the window: "
                f"devprof_backend_compiles {c0} -> {c1}")
        if (metric(m1, "sim_guard_trips") or 0) != 0:
            raise HarnessFailure("the guard word tripped")
        failed_cmds = sv.s.failed_commands()
        if failed_cmds:
            raise HarnessFailure(f"a command failed: {failed_cmds[:3]}")
        # the fullest device: the largest worker's peak
        peak = max((metric(m, "devprof_peak_bytes_dev0") or 0
                    for m in m1.values()), default=0)
        print("setup: compile cache at the window's start: "
              f"{metric(m0, 'devprof_persistent_cache_hits')} hits, "
              f"{metric(m0, 'devprof_persistent_cache_misses')} misses, "
              f"{c0} compiles", file=sys.stderr, flush=True)
    except HarnessFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        if sv is not None:
            print("---- broker/worker log (tail) ----\n" + sv.log_tail(),
                  file=sys.stderr)
        return 1
    finally:
        if sv is not None:
            sv.close()

    check.save_evidence(os.path.join(rundir, "evidence.npz"),
                         out["evidence"])
    # the worker is gone and the chip is free: the plain reference runs
    # now, on the host, and is no part of set-up or of the window
    t_ref = time.perf_counter()
    correct, numbers, also = check.decide(spec, out["evidence"], args.seed)
    t_ref = time.perf_counter() - t_ref
    if args.control:
        ctl = check.decide(spec, check.control_evidence(
            spec, out["evidence"], args.seed), args.seed)
        print("control (bfloat16 reference in the program's place): "
              f"correct={ctl[0]} " + json.dumps(ctl[1]) + " also "
              + json.dumps(ctl[2]), file=sys.stderr, flush=True)

    q, ctx = out["q"], out["ctx"]
    ctx["q"] = q
    metrics = {}
    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": dev["count"],
              "memory_peak_bytes": int(peak) if peak else 0,
              "workers": dev["workers"]}
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.rehearsal:
        result["rehearsal"] = True
    if not args.trace:
        for m in bench["end_to_end"]:
            src = "setup_s" if m["name"] == "setup_s" \
                else mix["reports"].get(m["name"])
            if src is not None and src in q:
                metrics[m["name"]] = {"value": q[src], "unit": m["unit"]}
    else:
        trace = DeviceTrace.from_dir(ctx["tracedir"])
        shutil.rmtree(ctx["tracedir"], ignore_errors=True)   # 35-140 MB
        ctx["trace"] = trace
        summ = trace.summary() if trace is not None else None
        ctx["trace_summary"] = summ
        if summ is not None:
            device["busy_s"], device["window_s"] = \
                summ["busy_s"], summ["window_s"]
            result["breakdown"] = trace.breakdown()
        for m in bench["per_layer"]:
            if "workloads" in m and args.workload not in m["workloads"]:
                continue
            mspec = load_json(HERE, "metrics", m["name"] + ".json")
            reader = importlib.import_module("readers." + mspec["reader"])
            v = reader.read(ctx, mspec.get("params", {}))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    print(f"run: {out['note']}; reference and comparison {t_ref:.1f} s; "
          f"also read {json.dumps(also)}", file=sys.stderr)
    result["compared"] = numbers
    print("compared (value, limit): " + json.dumps(numbers),
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
