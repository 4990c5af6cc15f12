"""Window kind "backlog": a batch of small worlds through the broker's
queue; the window opens at submission and closes on a completion."""
import json
import os
import statistics
import time

from ._common import chunk_count, generator, stage


def run(sv, cfg, mix, size, args, rundir):
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
    stage(args, "callsigns read")
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
    stage(args, f"{nwarm} warm-up pieces done")
    m0 = sv.worker_metrics()
    f0 = sv.fleet_metrics()
    t_open = time.perf_counter()
    submit(main)
    setup_s = t_open - args.t_process
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
    nchunks = [chunk_count(mix, m) for m in (m0, m1)]
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
