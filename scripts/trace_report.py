"""Merge flight-recorder dumps (obs/trace.py) into one Perfetto trace
and print a per-chunk breakdown table.

Each process dumps its own ring (TRACE DUMP on the sim, the b"TRACE"
event on the server, auto-dumps on guard/mesh trips) as a separate
``trace-<proc>-<pid>-<NNN>-<reason>.json`` file.  All events carry wall
timestamps from a shared epoch anchor (time.time() - perf_counter() at
import), so dumps from processes on ONE host line up on the same axis
and can simply be concatenated; the pid field keeps the tracks apart in
the Perfetto UI.

Run:
    python scripts/trace_report.py trace-*.json [-o merged.json]

The breakdown table groups "X" (complete) events by (pid, seq) — the
host-side chunk sequence number stamped at dispatch — and shows, per
chunk, the dispatch span, the edge-retire span and the reported device
pull latency, plus any instants (guard trips, voided chunks,
mesh_lost/resharded) that share the correlation id.  A second table
gives each span name its count, its total and its self time (a span's
duration less what its children cover, by the ``id``/``parent`` every
span carries): where a piece's or a chunk's host time went.

The spans file of a ``PROFILE DEVICE`` window (``<dir>_spans.json``)
loads like a dump.
"""
import argparse
import json
import sys
from collections import defaultdict


def load(paths):
    """Read + concatenate dumps, deduping events that appear in more
    than one (a dump does not clear the ring, so an incident auto-dump
    and a later manual dump from the same process overlap)."""
    events, seen = [], set()
    for p in paths:
        try:
            with open(p) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            print(f"skipping {p}: {e}", file=sys.stderr)
            continue
        evs = doc.get("traceEvents", doc.get("spans", [])) \
            if isinstance(doc, dict) else doc
        for ev in evs:
            if not (isinstance(ev, dict) and "ts" in ev):
                continue
            key = (ev.get("pid"), ev.get("tid"), ev["ts"],
                   ev.get("name"), ev.get("ph"))
            if key in seen:
                continue
            seen.add(key)
            events.append(ev)
    events.sort(key=lambda e: e["ts"])
    return events


def merge(events, meta=None):
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    if meta:
        doc["metadata"] = meta
    return doc


def chunk_table(events):
    """Rows keyed by (pid, seq): per-chunk span durations + instants."""
    rows = defaultdict(dict)
    loose = []                      # instants with no seq tag
    for ev in events:
        args = ev.get("args") or {}
        seq = args.get("seq")
        if seq is None:
            if ev.get("ph") == "i":
                loose.append(ev)
            continue
        row = rows[(ev.get("pid", 0), seq)]
        row.setdefault("t0", ev["ts"])
        row.setdefault("chunk", args.get("chunk"))
        row.setdefault("world", args.get("world"))
        name = ev.get("name", "?")
        if ev.get("ph") == "X":
            row[name] = ev.get("dur", 0) / 1000.0       # us -> ms
            if name == "chunk_edge" and "latency_ms" in args:
                row["latency_ms"] = args["latency_ms"]
        else:                                           # instant
            row.setdefault("events", []).append(name)
    return rows, loose


def self_times(events):
    """{span name: [count, total ms, self ms]} over the spans that carry
    an ``id``: a span's self time is its duration less the part of it
    that its children (the spans naming it as ``parent``) cover."""
    spans = [e for e in events
             if e.get("ph") == "X" and e.get("id") is not None]
    kids = defaultdict(list)
    for e in spans:
        kids[(e.get("pid"), e.get("parent"))].append(e)
    out = defaultdict(lambda: [0, 0.0, 0.0])
    for e in spans:
        t, end, covered = e["ts"], e["ts"] + e["dur"], 0.0
        for c in sorted(kids.get((e.get("pid"), e["id"]), ()),
                        key=lambda c: c["ts"]):
            a, b = max(c["ts"], t), min(c["ts"] + c["dur"], end)
            if b > a:
                covered += b - a
                t = b
        row = out[e["name"]]
        row[0] += 1
        row[1] += e["dur"] / 1000.0
        row[2] += (e["dur"] - covered) / 1000.0
    return dict(out)


def print_self_times(table, out=sys.stdout):
    head = f"{'span':>16} {'count':>6} {'total_ms':>11} {'self_ms':>11}"
    print("\n" + head, file=out)
    print("-" * len(head), file=out)
    for name, (n, tot, own) in sorted(table.items(),
                                      key=lambda kv: -kv[1][2]):
        print(f"{name:>16} {n:>6} {tot:>11.2f} {own:>11.2f}", file=out)


def fmt_ms(v):
    return f"{v:8.2f}" if isinstance(v, (int, float)) else " " * 8


def print_table(rows, loose, out=sys.stdout):
    cols = ("dispatch", "edge", "meshchk", "latency")
    head = (f"{'pid':>7} {'seq':>5} {'chunk':>6} {'world':>6} "
            + " ".join(f"{c:>8}" for c in cols) + "  events")
    print(head, file=out)
    print("-" * len(head), file=out)
    for (pid, seq), row in sorted(rows.items(),
                                  key=lambda kv: kv[1].get("t0", 0)):
        world = row.get("world")
        print(f"{pid:>7} {seq:>5} {str(row.get('chunk', '')):>6} "
              f"{('' if world is None else str(world)):>6} "
              f"{fmt_ms(row.get('chunk_dispatch'))} "
              f"{fmt_ms(row.get('chunk_edge'))} "
              f"{fmt_ms(row.get('mesh_check'))} "
              f"{fmt_ms(row.get('latency_ms'))}  "
              f"{','.join(row.get('events', []))}", file=out)
    if loose:
        print("\nuntagged instants:", file=out)
        for ev in loose:
            args = ev.get("args") or {}
            tag = " ".join(f"{k}={v}" for k, v in sorted(args.items()))
            print(f"  {ev['ts']/1e6:12.3f}s pid={ev.get('pid', '?')} "
                  f"{ev.get('name', '?')} {tag}", file=out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("dumps", nargs="+", help="trace-*.json dump files")
    ap.add_argument("-o", "--out", default=None,
                    help="write the merged Perfetto trace here")
    args = ap.parse_args(argv)

    events = load(args.dumps)
    if not events:
        print("no events found", file=sys.stderr)
        return 1
    if args.out:
        with open(args.out, "w") as f:
            json.dump(merge(events, {"sources": args.dumps}), f)
        print(f"merged {len(events)} events from {len(args.dumps)} "
              f"dump(s) -> {args.out}")

    rows, loose = chunk_table(events)
    print_table(rows, loose)
    print_self_times(self_times(events))
    return 0


if __name__ == "__main__":
    sys.exit(main())
