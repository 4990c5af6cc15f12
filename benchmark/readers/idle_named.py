"""Share [%] of the traced window's idle time between programs that
lies under a host span other than ``piece``: the gaps of the device
trace, put on the host's clock, and sorted by the span of the sim
thread whose own time covers them.  The offset between the clocks is
the one the worker read back from its own ``bs/clock`` annotation
(``profiler_zero_us``), held to the bracket that the chunk programs
put around it (``_spans.bracket``, ``_spans.offset``).  On standard error: the idle seconds under each
span name and the error of the alignment.  None where the spans file or the alignment is missing
(a program that writes no such file, as the parent of the PR that added
this reader)."""
import sys

from . import _spans


def gaps_between_programs(trace):
    """[(start_us, end_us)] in the trace's clock: the gaps of the busiest
    device's operations (those that contain no other) that no program
    spans, as ``DeviceTrace.breakdown`` sorts them."""
    dev = max(trace.devices.values(), key=lambda d: len(d["ops"]),
              default=None)
    if not dev or not dev["ops"]:
        return []
    busy = trace._union((t, t + d) for _, t, d in trace._leaves(dev))
    mods = trace._union((t, t + d) for _, t, d in dev["modules"])
    out, k = [], 0
    for (_, e0), (s1, _) in zip(busy[:-1], busy[1:]):
        while k < len(mods) and mods[k][1] < s1:
            k += 1
        if not (k < len(mods) and mods[k][0] <= e0 and s1 <= mods[k][1]):
            out.append((1e6 * e0, 1e6 * s1))
    return out


def account(doc, trace, program):
    """(alignment, {span name: idle us under its own time}, idle us in
    all, number of gaps), or None where the two clocks cannot be
    aligned or nothing idles."""
    al = _spans.bracket(doc.get("chunks", []),
                        _spans.programs(trace, program))
    gaps = gaps_between_programs(trace)
    spans = [s for s in doc.get("spans", [])
             if s.get("ph") == "X" and s.get("id") is not None]
    tids = [s["tid"] for s in spans if s["name"] == "chunk_dispatch"]
    if al is None or not gaps or not tids:
        return None
    al["mark_us"] = doc.get("profiler_zero_us")
    offset = al["offset_us"] = _spans.offset(al, al["mark_us"])
    own = _spans.self_intervals([s for s in spans if s["tid"] == tids[0]])
    under = {name: sum(_spans.overlap(iv, a + offset, b + offset)
                       for a, b in gaps)
             for name, iv in own.items()}
    return al, under, sum(b - a for a, b in gaps), len(gaps)


def read(ctx, params):
    trace, doc = ctx.get("trace"), _spans.load(ctx.get("tracedir") or "")
    got = account(doc, trace, params["program"]) \
        if trace is not None and doc is not None else None
    if got is None:
        return None
    al, under, total, ngaps = got
    named = sum(v for k, v in under.items() if k != "piece")
    print(f"idle_named: {1e-3 * total:.3f} ms idle in {ngaps} gaps between "
          "programs: "
          + "; ".join(f"{k} {1e-3 * v:.3f}" for k, v in
                      sorted(under.items(), key=lambda kv: -kv[1]) if v > 0)
          + f"; under no span {1e-3 * (total - sum(under.values())):.3f}",
          file=sys.stderr)
    mark, offset = al["mark_us"], al["offset_us"]
    print("idle_named: alignment host - trace = %.1f us: %s; the bracket "
          "of %d chunk programs is %.1f us wide (lower bound from chunk "
          "seq %s, upper from seq %s)"
          % (offset,
             "the bracket's midpoint, no bs/clock mark" if mark is None
             else "the bs/clock mark, %.1f us above the lower and %.1f us "
             "below the upper bound" % (mark - al["lo_us"],
                                        al["hi_us"] - mark)
             + ("" if offset == mark else ", moved to the bound"),
             al["pairs"], al["hi_us"] - al["lo_us"], al["lo_seq"],
             al["hi_seq"]), file=sys.stderr, flush=True)
    return 100.0 * named / total
