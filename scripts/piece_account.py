#!/usr/bin/env python3
"""A farm piece's account, by hand: a ``wallmc`` backlog through the
benchmark's own ``Served`` (broker, one worker, a client) and generator,
with the recorder on, then what the worker booked of it.

    python3 scripts/piece_account.py --pieces 96 [--worlds 4] [--rehearsal]

Prints, for the warm-up and for the batch, each series of a piece from
the broker's fleet aggregate (count, sum, ms a piece: the counts are
exact, the script waits a heartbeat before it reads), and from the
worker's ``TRACE DUMP`` each piece's own account: the mean over the
batch's ``piece`` spans of their ``own_ms`` and ``parts``
(docs/OBSERVABILITY.md, "Timed scopes"), which add up to the piece.
``--worlds W`` turns ``world_pack`` on with ``world_batch_max`` W, so a
``piece`` is a pack.  Needs the chip unless ``--rehearsal`` (toy marks on
a named CPU: debugs the script, measures nothing).  PERF.md section 5
has the reading of PR 40.
"""
import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SERIES = ("sim_piece_ms", "sim_piece_own_ms", "sim_piece_turnaround_ms",
          "sim_piece_reset_ms", "sim_pack_build_ms", "sim_stack_ms",
          "sim_make_state_ms", "sim_state_write_ms", "sim_dispatch_ms",
          "sim_device_wait_ms", "sim_edge_work_ms", "sim_frame_ms",
          "sim_node_idle_ms", "sim_node_poll_ms", "sim_pipeline_empty_ms")
# how a worker's idle waits ended: by an event, or by their bound; and
# the turns of its loop that did not wait at all (the sim was stepping:
# 16 a piece, one a chunk)
COUNTERS = ("sim_node_idle_woken", "sim_node_idle_timed_out",
            "sim_node_turns_nowait")


def series_lines(f0, f1, npieces):
    """One line a series: its increase between two METRICS payloads."""
    for name in SERIES:
        h0 = (f0.get("fleet") or {}).get(name) or {"sum": 0.0, "count": 0}
        h1 = (f1.get("fleet") or {}).get(name)
        if not h1 or "sum" not in h1:
            yield f"  {name}: none"
            continue
        dsum, dcount = h1["sum"] - h0["sum"], h1["count"] - h0["count"]
        yield (f"  {name}: count {dcount}, sum {dsum:.3f} ms, a piece "
               f"{dsum / npieces:.3f} ms")
    for name in COUNTERS:
        c0 = ((f0.get("fleet") or {}).get(name) or {}).get("value", 0.0)
        c1 = ((f1.get("fleet") or {}).get(name) or {}).get("value")
        yield (f"  {name}: none" if c1 is None
               else f"  {name}: {c1 - c0:.0f}")


def account_lines(events, prefix):
    """The mean account of the ``piece`` spans whose piece is named
    ``prefix``..., and under what the frames, polls and idle stretches
    lay."""
    pieces = [e for e in events if e["name"] == "piece"
              and str(e["args"].get("piece", "")).startswith(prefix)
              and "parts" in e["args"]]
    yield (f"TRACE DUMP: {len(events)} events, {len(pieces)} pieces of "
           "the batch with their account")
    if not pieces:
        return
    n = len(pieces)
    own = sum(e["args"]["own_ms"] for e in pieces) / n
    yield (f"  piece span (recorder's clock) "
           f"{sum(e['dur'] for e in pieces) * 1e-3 / n:.3f} ms a piece")
    total = own
    for key in sorted({k for e in pieces for k in e["args"]["parts"]}):
        ms = sum(e["args"]["parts"].get(key, 0.0) for e in pieces) / n
        total += ms
        yield f"  part {key}: {ms:.3f} ms a piece"
    yield f"  own_ms: {own:.3f} ms a piece"
    yield f"  parts + own: {total:.3f} ms a piece"
    by_id = {e["id"]: e["name"] for e in events if "id" in e}
    under = {}
    for e in events:
        if e["name"] in ("node_poll", "node_idle", "acdata_frame",
                         "state_write") and "id" in e:
            c = under.setdefault((e["name"], by_id.get(e["parent"])),
                                 [0, 0.0])
            c[0] += 1
            c[1] += e["dur"] * 1e-3
    for (name, parent), (count, ms) in sorted(under.items(), key=str):
        yield f"  span {name} under {parent}: {count}, {ms:.1f} ms"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pieces", type=int, default=96)
    ap.add_argument("--worlds", type=int, default=0,
                    help="world_batch_max with world_pack on (0: solo)")
    ap.add_argument("--seed", type=int, default=4000000141)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    sys.path.insert(1, ROOT)
    from served import Served
    from windows._common import generator

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "wallmc.json")) as f:
        cfg = json.load(f)
    settings = dict(cfg["settings"], trace_enabled=True,
                    trace_ring_size=200000, world_pack=args.worlds > 0,
                    world_batch_max=max(args.worlds, 1))
    rundir = os.path.join(ROOT, "benchmark_out", "piece_account")
    shutil.rmtree(rundir, ignore_errors=True)   # no earlier run's journal
    os.makedirs(rundir)
    sv = Served(ROOT, rundir, settings, args.rehearsal, 1, 1)
    try:
        s, client = sv.s, sv.client
        print("device:", sv.device["platform"], sv.device["device_kind"],
              flush=True)
        gen = generator(cfg["generator"]["name"])
        params = dict(cfg["generator"]["params"])
        if args.rehearsal:
            params.update(cfg["rehearsal_size"]["params"])
        client.subscribe(b"ACDATA")
        client.stack("; ".join(["HOLD"] + gen.discover(params)))
        s.wait(lambda: s.acdata is not None and len(s.acdata["id"]) > 0,
               300.0, "the ids of a piece's aircraft")
        ids = list(s.acdata["id"])
        client.unsubscribe(b"ACDATA")
        jstate, done = {}, []

        def absorb():
            done.extend(t for t, rec in sv.journal_lines(jstate)
                        if rec.get("rec") == "completed")

        def run(batch, label):
            n0, f0, t0 = len(done), sv.fleet_metrics(), time.perf_counter()
            client.send_event(b"BATCH", {
                "scentime": [t for p in batch for t in p["scentime"]],
                "scencmd": [c for p in batch for c in p["scencmd"]]},
                target=b"")
            s.wait(lambda: len(done) >= n0 + len(batch), 1500.0,
                   "the pieces", each=absorb)
            dt = done[-1] - t0
            s.pump(3.0)      # a heartbeat: the aggregate holds them all
            print(f"{label}: {len(batch)} pieces in {dt:.3f} s, "
                  f"{len(batch) / dt:.4f} pieces/s")
            print("\n".join(series_lines(f0, sv.fleet_metrics(),
                                         len(batch))), flush=True)

        run(gen.pieces(dict(params, stream=1), args.seed,
                       max(2 * args.worlds, 4), "W", ids), "warm-up")
        run(gen.pieces(dict(params, stream=2), args.seed, args.pieces,
                       "P", ids),
            f"the batch (world_batch_max {args.worlds})")
        echo = s.command("TRACE DUMP", "Trace written to")
        path = echo.split("Trace written to", 1)[1].split()[0]
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        print("\n".join(account_lines(events, "P")), flush=True)
    finally:
        sv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
