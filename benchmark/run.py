#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, on the machine it is started on.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearsal]

The cell names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<mix>.json``); the mix names one of the two window kinds
below, the configuration its generator and its check.  Nothing here
names a cell, a configuration or a metric: see README.md.

The window is driven through ``python -m bluesky_tpu --headless``, the
worker it spawns and a ``network.client.Client`` in this process, which
never imports JAX.  The last line of standard output is the result.
"""
import argparse
import importlib
import json
import os
import re
import shutil
import signal
import statistics
import sys
import time

T_PROCESS = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import check as checks                                   # noqa: E402
from served import HarnessFailure, Served, metric, require_device  # noqa: E402
from trace_reduce import DeviceTrace                     # noqa: E402


def stage(what):
    """The set-up's timeline, on standard error: where its seconds go."""
    print(f"setup: {what} at {time.perf_counter() - T_PROCESS:.1f} s",
          file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def generator(name):
    return importlib.import_module(f"generators.{name}")


# =====================================================================
# window kind "advance": one long run of one world; the window opens and
# closes on an advance of simulated time as the client sees it
# =====================================================================
def window_advance(sv, cfg, mix, size, args, rundir):
    s, client = sv.s, sv.client
    n = int(size["aircraft"])
    gen = generator(cfg["generator"]["name"])
    params = dict(cfg["generator"]["params"], **size.get("params", {}))
    for name in mix["consumers"]:
        client.subscribe(name.encode())
    client.stack("; ".join(["HOLD"] + cfg["setup_commands"]
                           + gen.commands(params, args.seed, n)))
    s.wait_state(lambda r: r["ntraf"] == n, 900.0, f"ntraf == {n}")
    stage(f"{n} aircraft created")
    client.stack("; ".join(mix["start"]))

    def advances():
        """Distinct values of simulated time seen so far, with the stamp
        of the first frame that carried each."""
        out = []
        for t, simt in s.siminfo:
            if not out or simt > out[-1][1] + 1e-6:
                out.append((t, simt))
        return out

    # warm-up: every program of this mix has run once (first chunk from
    # the compile cache or the compiler, first host re-sort)
    warm = float(mix["warm_sim_s"])
    s.wait(lambda: len(advances()) >= 3
           and advances()[-1][1] >= advances()[0][1] + warm,
           1500.0, f"{warm:g} s of simulated time to warm up")
    stage("warmed up")
    m0 = sv.worker_metrics()
    f0 = sv.fleet_metrics()
    k0 = len(advances())
    s.wait(lambda: len(advances()) > k0, 600.0, "the window to open")
    t_open, sim_open = advances()[k0]
    setup_s = t_open - T_PROCESS
    tracedir = os.path.join(rundir, "devprof")
    if args.trace:
        client.stack(f"PROFILE DEVICE {int(mix['trace_chunks'])} {tracedir}")
    s.wait(lambda: advances()[-1][0] >= t_open + args.seconds,
           args.seconds + 600.0, "the window to close")
    t_close, sim_close = next(a for a in advances()
                              if a[0] >= t_open + args.seconds)
    m1 = sv.worker_metrics()
    f1 = sv.fleet_metrics()
    q = {"setup_s": setup_s,
         "advance_rate": (sim_close - sim_open) / (t_close - t_open)}
    inside = [a for a in advances() if t_open <= a[0] <= t_close]
    with open(os.path.join(rundir, "advances.jsonl"), "w") as f:
        for t, simt in advances():          # for the builder's reading
            f.write(json.dumps([t - t_open, simt]) + "\n")
    frames = probe_frames(sv, mix["probe"])
    # a chunk as the client saw it: the step of simulated time between
    # two advances of the window, not the mix's word for it
    chunk_seen = statistics.median(
        b[1] - a[1] for a, b in zip(inside[:-1], inside[1:]))
    ctx = dict(window_s=t_close - t_open, units=sim_close - sim_open,
               m0=m0, m1=m1, f0=f0, f1=f1, tracedir=tracedir,
               chunks_per_unit=1.0 / chunk_seen,
               cd_interval_s=float(cfg["cd_interval_s"]))
    nadv = len(inside) - 1
    return dict(q=q, ctx=ctx, attempted=nadv, failed=0,
                evidence=dict(frames=frames, compares=mix["probe"]["compares"],
                              chunk_sim_s=float(mix["probe"]["collect_sim_s"])),
                note=f"{nadv} advances of {chunk_seen:g} sim-s in "
                     f"{t_close - t_open:.3f} s")


def probe_frames(sv, probe):
    """After the window: hold, then drive the mix's own programs a
    little further with an ACDATA consumer attached, and keep the
    frames.  Returns them with distinct simulated times, in order."""
    s, client = sv.s, sv.client
    client.stack("HOLD")
    _, held = s.wait_state(lambda r: r["state"] == 1, 300.0, "HOLD")
    client.subscribe(b"ACDATA")
    n0 = len(s.acdata_t)
    s.wait(lambda: len(s.acdata_t) >= n0 + 2, 120.0,
           "frames of the held state")
    s.keep_frames = [s.acdata]
    t0 = s.acdata["simt"]
    client.stack("; ".join(probe["commands"]))
    s.wait(lambda: s.keep_frames[-1]["simt"] >= t0
           + float(probe["collect_sim_s"]) - 0.25, 900.0,
           "the probe's frames")
    client.stack("HOLD")
    frames, s.keep_frames = s.keep_frames, None
    return frames


# =====================================================================
# window kind "backlog": a batch of small worlds through the broker's
# queue; the window opens at submission and closes on a completion
# =====================================================================
def window_backlog(sv, cfg, mix, size, args, rundir):
    s, client = sv.s, sv.client
    gen = generator(cfg["generator"]["name"])
    params = dict(cfg["generator"]["params"], **size.get("params", {}))
    nwarm, nmain = int(mix["warm_pieces"]), int(size["pieces"])
    # the callsigns the generator's lines will address
    client.subscribe(b"ACDATA")
    client.stack("; ".join(["HOLD"] + gen.discover(params)))
    s.wait(lambda: s.acdata is not None and len(s.acdata["id"]) > 0, 300.0,
           "the ids of a piece's aircraft")
    ids = list(s.acdata["id"])
    client.unsubscribe(b"ACDATA")
    stage("callsigns read")
    warm = gen.pieces(dict(params, stream=1), args.seed, nwarm, "W", ids)
    main = gen.pieces(dict(params, stream=2), args.seed, nmain, "P", ids)
    names = [p["name"] for p in main]
    known = set(names) | {p["name"] for p in warm}
    jstate, key2name, seen, states = {}, {}, {}, {}
    cur, npos = [None], [0]

    def submit(batch):
        client.send_event(b"BATCH", {
            "scentime": [t for p in batch for t in p["scentime"]],
            "scencmd": [c for p in batch for c in p["scencmd"]]},
            target=b"")

    def absorb():
        """New journal records and echoes, each with the stamp at which
        this client saw it.  A piece announces each mark by name, then
        echoes POS of its aircraft: one worker, so no two interleave."""
        for t, rec in sv.journal_lines(jstate):
            kind = rec.get("rec")
            if kind == "queued":
                key2name[rec["key"]] = next(
                    (c.split()[1] for c in rec["scencmd"]
                     if c.upper().startswith("SCEN")), rec["key"])
            elif "key" in rec:
                seen.setdefault(key2name.get(rec["key"], rec["key"]),
                                []).append((kind, t))
        for t, text in s.echo[npos[0]:]:
            w = text.split()
            if len(w) == 2 and w[0] in known and w[1].startswith("MARK"):
                cur[0] = (w[0], int(w[1][4:]))
                states[cur[0]] = {}
            elif text.startswith("Info on ") and cur[0] is not None:
                lat, lon = text.splitlines()[1].split(":")[1].split(",")
                states[cur[0]][w[2]] = (float(lat), float(lon))
        npos[0] = len(s.echo)

    def completions(of):
        return sorted(t for n in of for k, t in seen.get(n, [])
                      if k == "completed")

    submit(warm)
    s.wait(lambda: len(completions(known - set(names))) == nwarm, 1500.0,
           "the warm-up pieces", each=absorb)
    stage(f"{nwarm} warm-up pieces done")
    m0 = sv.worker_metrics()
    f0 = sv.fleet_metrics()
    t_open = time.perf_counter()
    submit(main)
    setup_s = t_open - T_PROCESS
    tracedir = os.path.join(rundir, "devprof")
    traced = [not args.trace]

    def tick():
        absorb()
        if not traced[0] and len(completions(names)) >= 2:
            client.stack(f"PROFILE DEVICE {int(mix['trace_chunks'])} "
                         f"{tracedir}")
            traced[0] = True

    s.wait(lambda: any(t >= t_open + args.seconds
                       for t in completions(names))
           or len(completions(names)) == nmain,
           args.seconds + 900.0, "the window to close", each=tick)
    comp = completions(names)
    t_close = next((t for t in comp if t >= t_open + args.seconds), comp[-1])
    ndone = sum(1 for t in comp if t <= t_close)
    m1 = sv.worker_metrics()
    f1 = sv.fleet_metrics()
    absorb()
    # one line per piece: what the client saw of it, on its own clock
    rows, bad = [], 0
    for k, n in enumerate(names):
        ev = seen.get(n, [])
        disp = [t for kd, t in ev if kd == "dispatched"]
        cmpl = [t for kd, t in ev if kd == "completed" and t <= t_close]
        other = sorted({kd for kd, _ in ev} - {"dispatched", "completed"})
        if not disp:
            continue
        rows.append(dict(index=k, name=n,
                         dispatched_s=disp[0] - t_open,
                         completed_s=(cmpl[0] - t_open) if cmpl else None,
                         ndispatched=len(disp), ncompleted=len(cmpl),
                         other=other))
        if len(cmpl) > 1 or any(o in ("crashed", "quarantined")
                                for o in other):
            bad += 1
    with open(os.path.join(rundir, "pieces.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    piece_s = [r["completed_s"] - r["dispatched_s"] for r in rows
               if r["completed_s"] is not None]
    q = {"setup_s": setup_s,
         "completion_rate": ndone / (t_close - t_open)}
    # a piece's chunks, by the worker's own count of the chunks it
    # retired over the pieces the window completed
    nchunks = [int(re.search(mix["chunk_counter"] + r": n=(\d+)", m)[1])
               for m in (m0, m1)]
    ctx = dict(window_s=t_close - t_open, units=ndone, piece_s=piece_s,
               m0=m0, m1=m1, f0=f0, f1=f1, tracedir=tracedir,
               chunks_per_unit=(nchunks[1] - nchunks[0]) / ndone)
    finished = {r["name"] for r in rows if r["completed_s"] is not None}
    return dict(q=q, ctx=ctx, attempted=len(rows), failed=bad,
                evidence=dict(pieces=[p for p in main
                                      if p["name"] in finished],
                              states=states, duplicates=bad),
                note=f"{ndone} pieces in {t_close - t_open:.3f} s, "
                     f"median {1e3 * statistics.median(piece_s):.1f} ms, "
                     f"{ctx['chunks_per_unit']:.2f} chunks a piece")


WINDOWS = {"advance": window_advance, "backlog": window_backlog}


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
    size = cfg["rehearsal_size"] if args.rehearsal else cfg["size"]
    rundir = os.path.join(ROOT, "benchmark_out", args.workload,
                          f"seed{args.seed}_trace{args.trace}")
    shutil.rmtree(rundir, ignore_errors=True)
    os.makedirs(rundir)

    settings = dict(cfg["settings"], **size.get("settings", {}))
    sv = None
    try:
        sv = Served(ROOT, rundir, settings, args.rehearsal)
        dev = sv.device
        require_device(dev, int(cell["chips"]), args.rehearsal)
        stage("worker registered")
        print(f"run: {args.workload} seed {args.seed} on platform "
              f"{dev['platform']}, device_kind {dev['device_kind']}, "
              f"count {dev['count']}", file=sys.stderr, flush=True)
        out = WINDOWS[mix["window"]](sv, cfg, mix, size, args, rundir)
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
        peak = metric(m1, "devprof_peak_bytes_dev0")
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

    checks.save_evidence(os.path.join(rundir, "evidence.npz"),
                         out["evidence"])
    # the worker is gone and the chip is free: the plain reference runs
    # now, on the host, and is no part of set-up or of the window
    t_ref = time.perf_counter()
    correct, numbers, also = checks.decide(cfg["check"], out["evidence"],
                                           args.seed)
    t_ref = time.perf_counter() - t_ref
    if args.control:
        ctl = checks.decide(cfg["check"], checks.control_evidence(
            cfg["check"], out["evidence"], args.seed), args.seed)
        print("control (bfloat16 reference in the program's place): "
              f"correct={ctl[0]} " + json.dumps(ctl[1]) + " also "
              + json.dumps(ctl[2]), file=sys.stderr, flush=True)

    q, ctx = out["q"], out["ctx"]
    metrics = {}
    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": dev["count"],
              "memory_peak_bytes": int(peak) if peak else 0}
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
            spec = load_json(HERE, "metrics", m["name"] + ".json")
            reader = importlib.import_module("readers." + spec["reader"])
            v = reader.read(ctx, spec.get("params", {}))
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
