"""Print the per-chunk attribution table of a device-profile window
(obs/devprof.py).

``PROFILE DEVICE [n] [dir]`` wraps n chunk dispatches in a
``jax.profiler`` trace window.  The host's spans of the window are in
the profiler's own file already, as ``bs/<name>`` annotations on the
profiler's clock: open ``<dir>/plugins/profile/<ts>/*.trace.json.gz``
at https://ui.perfetto.dev to see them above the device operations.
This script only prints, from the ``devprof_chunk`` events of the
window's ``<dir>_spans.json`` or of a recorder dump:

    seq  chunk  compute_ms  halo_ms  edge_ms  device%

``compute_ms`` runs from the dispatch's return to the end of
``device_wait``, ``halo_ms`` is the ``sort_refresh`` span, ``edge_ms``
the self time of ``chunk_edge``.

Run:
    python scripts/devprof_report.py RUNDIR/devprof_spans.json
    python scripts/devprof_report.py trace-*.json
"""
import argparse
import os
import sys

# reuse the recorder-dump loader (shared dedupe semantics)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import trace_report


def attribution_rows(events):
    """Rows from devprof_chunk complete events (host recorder), sorted
    by seq.  Schema is pinned by tests/test_devprof.py."""
    rows = []
    for ev in events:
        if ev.get("name") != "devprof_chunk" or ev.get("ph") != "X":
            continue
        args = ev.get("args") or {}
        rows.append({
            "seq": args.get("seq"),
            "chunk": args.get("chunk"),
            "compute_ms": args.get("compute_ms"),
            "halo_ms": args.get("halo_ms"),
            "edge_ms": args.get("edge_ms"),
        })
    rows.sort(key=lambda r: (r["seq"] is None, r["seq"]))
    return rows


def print_table(rows, out=sys.stdout):
    head = (f"{'seq':>5} {'chunk':>6} {'compute_ms':>11} "
            f"{'halo_ms':>9} {'edge_ms':>9} {'device%':>8}")
    print(head, file=out)
    print("-" * len(head), file=out)
    for r in rows:
        c = r.get("compute_ms") or 0.0
        h = r.get("halo_ms") or 0.0
        e = r.get("edge_ms") or 0.0
        tot = c + h + e
        pct = (100.0 * c / tot) if tot else 0.0
        print(f"{str(r.get('seq', '')):>5} {str(r.get('chunk', '')):>6}"
              f" {c:>11.2f} {h:>9.2f} {e:>9.2f} {pct:>7.1f}%",
              file=out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("dumps", nargs="+",
                    help="<dir>_spans.json of a PROFILE DEVICE window, "
                         "or host recorder trace-*.json dumps")
    args = ap.parse_args(argv)

    rows = attribution_rows(trace_report.load(args.dumps))
    if not rows:
        print("no devprof_chunk events found "
              "(was a PROFILE DEVICE window active?)", file=sys.stderr)
        return 1
    print_table(rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
