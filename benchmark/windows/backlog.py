"""Window kind "backlog": a batch of small worlds through the broker's
queue; the window opens at submission and closes on a completion.

The fleet is the configuration's (``deployment.workers``, ``sv.workers``):
the backlog makes the broker spawn it, every worker runs ``warm_pieces``
of the warm-up before the window opens, a piece's echoes are told apart
by the worker that sent them, each worker is asked for its own registry,
and a traced run traces the first worker by id."""
import json
import os
import statistics
import time

from served import HarnessFailure

from ._common import chunk_count, generator, stage


def read_marks(echoes, known, cur, states):
    """What the pieces echoed, ``states[(piece, mark)][acid] = (lat,
    lon)``, from ``echoes`` (``Session.echo`` entries: stamp, text,
    sender).  A piece announces each mark by name, then echoes POS of
    its aircraft.  A worker runs one piece at a time, so the echoes of
    one sender never interleave, and two senders' may: each sender has
    a mark of its own in ``cur``."""
    for _, text, sender in echoes:
        w = text.split()
        if len(w) == 2 and w[0] in known and w[1].startswith("MARK"):
            cur[sender] = (w[0], int(w[1][4:]))
            states[cur[sender]] = {}
        elif text.startswith("Info on ") and sender in cur:
            lat, lon = text.splitlines()[1].split(":")[1].split(",")
            states[cur[sender]][w[2]] = (float(lat), float(lon))


def run(sv, cfg, mix, size, args, rundir):
    s, client = sv.s, sv.client
    gen = generator(cfg["generator"]["name"])
    params = dict(cfg["generator"]["params"], **size.get("params", {}))
    nwarm, nmain = int(mix["warm_pieces"]), int(size["pieces"])
    fleet = sv.workers
    # the callsigns the generator's lines will address
    client.subscribe(b"ACDATA")
    client.stack("; ".join(["HOLD"] + gen.discover(params)))
    s.wait(lambda: s.acdata is not None and len(s.acdata["id"]) > 0, 300.0,
           "the ids of a piece's aircraft")
    ids = list(s.acdata["id"])
    client.unsubscribe(b"ACDATA")
    stage(args, "callsigns read")
    # the warm-up's pool: ``warm_pieces`` a worker, and for a fleet as
    # much again for each round in which a worker came up short
    warm = gen.pieces(dict(params, stream=1), args.seed,
                      nwarm * fleet * (1 if fleet == 1 else 1 + fleet),
                      "W", ids)
    main = gen.pieces(dict(params, stream=2), args.seed, nmain, "P", ids)
    names = [p["name"] for p in main]
    known = set(names) | {p["name"] for p in warm}
    jstate, key2name, seen, states, ran = {}, {}, {}, {}, {}
    cur, npos = {}, [0]

    def submit(batch):
        client.send_event(b"BATCH", {
            "scentime": [t for p in batch for t in p["scentime"]],
            "scencmd": [c for p in batch for c in p["scencmd"]]},
            target=b"")

    def absorb():
        """New journal records and echoes, each with the stamp at which
        this client saw it."""
        for t, rec in sv.journal_lines(jstate):
            kind = rec.get("rec")
            if kind == "queued":
                key2name[rec["key"]] = next(
                    (c.split()[1] for c in rec["scencmd"]
                     if c.upper().startswith("SCEN")), rec["key"])
            elif "key" in rec:
                name = key2name.get(rec["key"], rec["key"])
                seen.setdefault(name, []).append((kind, t))
                if kind == "completed":
                    ran.setdefault(rec.get("worker"), []).append(name)
        read_marks(s.echo[npos[0]:], known, cur, states)
        npos[0] = len(s.echo)

    def completions(of):
        return sorted(t for n in of for k, t in seen.get(n, [])
                      if k == "completed")

    # every worker has run ``warm_pieces`` before the window opens: a
    # round of them for each, then for a fleet (which grows while the
    # first worker drains the round) as many rounds more as it takes
    sent, due = 0, nwarm * fleet
    while due:
        if sent + due > len(warm):
            raise HarnessFailure(
                f"{sent} warm-up pieces and a worker still short of "
                f"{nwarm}: " + json.dumps({w: len(v) for w, v in
                                           ran.items()}))
        submit(warm[sent:sent + due])
        sent += due
        if fleet > 1 and sv.device["workers"] != fleet:
            sv.expect_workers()
        s.wait(lambda: len(completions(known - set(names))) == sent, 1500.0,
               "the warm-up pieces", each=absorb)
        due = 0 if fleet == 1 else fleet * max(
            max(0, nwarm - len(ran.get(w["worker"], [])))
            for w in sv.device["per_worker"])
    stage(args, f"{sent} warm-up pieces done")
    m0 = sv.worker_metrics()
    f0 = sv.fleet_metrics()
    t_open = time.perf_counter()
    submit(main)
    setup_s = t_open - args.t_process
    tracedir = os.path.join(rundir, "devprof")
    traced = [not args.trace]
    first = sv.device["per_worker"][0]["worker"]   # the worker traced

    def tick():
        absorb()
        if not traced[0] and len(completions(names)) >= 2 * fleet:
            client.stack(f"PROFILE DEVICE {int(mix['trace_chunks'])} "
                         f"{tracedir}", bytes.fromhex(first))
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
    by = {n: w for w, ns in ran.items() for n in ns}
    rows, bad = [], 0
    for k, n in enumerate(names):
        ev = seen.get(n, [])
        disp = [t for kd, t in ev if kd == "dispatched"]
        cmpl = [t for kd, t in ev if kd == "completed" and t <= t_close]
        other = sorted({kd for kd, _ in ev} - {"dispatched", "completed"})
        if not disp:
            continue
        rows.append(dict(index=k, name=n, worker=by.get(n),
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
    # a piece's chunks, by the workers' own count of the chunks they
    # retired over the pieces the window completed
    nchunks = [chunk_count(mix, m) for m in (m0, m1)]
    ctx = dict(window_s=t_close - t_open, units=ndone, piece_s=piece_s,
               m0=m0, m1=m1, f0=f0, f1=f1, tracedir=tracedir,
               workers=fleet, traced_worker=first,
               chunks_per_unit=(nchunks[1] - nchunks[0]) / ndone)
    finished = {r["name"] for r in rows if r["completed_s"] is not None}
    return dict(q=q, ctx=ctx, attempted=len(rows), failed=bad,
                evidence=dict(pieces=[p for p in main
                                      if p["name"] in finished],
                              states=states, duplicates=bad),
                note=f"{ndone} pieces in {t_close - t_open:.3f} s, "
                     f"median {1e3 * statistics.median(piece_s):.1f} ms, "
                     f"{ctx['chunks_per_unit']:.2f} chunks a piece")
