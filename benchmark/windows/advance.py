"""Window kind "advance": one long run of one world; the window opens and
closes on an advance of simulated time as the client sees it."""
import json
import os
import statistics

from ._common import generator, probe_frames, stage


def run(sv, cfg, mix, size, args, rundir):
    s, client = sv.s, sv.client
    n = int(size["aircraft"])
    gen = generator(cfg["generator"]["name"])
    params = dict(cfg["generator"]["params"], **size.get("params", {}))
    for name in mix["consumers"]:
        client.subscribe(name.encode())
    client.stack("; ".join(["HOLD"] + cfg["setup_commands"]
                           + gen.commands(params, args.seed, n)))
    s.wait_state(lambda r: r["ntraf"] == n, 900.0, f"ntraf == {n}")
    stage(args, f"{n} aircraft created")
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
    stage(args, "warmed up")
    m0 = sv.worker_metrics()
    f0 = sv.fleet_metrics()
    k0 = len(advances())
    s.wait(lambda: len(advances()) > k0, 600.0, "the window to open")
    t_open, sim_open = advances()[k0]
    setup_s = t_open - args.t_process
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
               t_open=t_open, t_close=t_close,
               m0=m0, m1=m1, f0=f0, f1=f1, tracedir=tracedir,
               chunks_per_unit=1.0 / chunk_seen,
               cd_interval_s=float(cfg["cd_interval_s"]))
    nadv = len(inside) - 1
    probe = mix["probe"]
    evidence = dict(frames=frames, compares=probe["compares"],
                    # the simulated time of one chunk of the probe's
                    # programs: all it collects, unless the mix says
                    chunk_sim_s=float(probe.get("chunk_sim_s",
                                                probe["collect_sim_s"])))
    if "check" in probe:       # not the configuration's own check
        evidence["check"] = probe["check"]
    return dict(q=q, ctx=ctx, attempted=nadv, failed=0, evidence=evidence,
                note=f"{nadv} advances of {chunk_seen:g} sim-s in "
                     f"{t_close - t_open:.3f} s")
