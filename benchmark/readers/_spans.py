"""The host spans of a ``PROFILE DEVICE`` window on the device trace's
clock.

The worker writes ``<tracedir>_spans.json`` when the window closes: its
flight recorder's spans of the window, stamped on the host's clock
(wall-anchored ``perf_counter`` microseconds), and for each windowed
chunk the host stamps of its dispatch (``enqueue_us``: just before the
chunk program is handed to the runtime) and of the end of its
``device_wait`` (the blocking read of its outputs has returned).  The
profiler's trace counts from its own start.  A chunk program cannot
start on the device before the host enqueues it, nor end after the host
has read its outputs, so with ``offset = host - trace``

    max_k(enqueue_k - program_start_k) <= offset
                                       <= min_k(wait_end_k - program_end_k)

and the width of that bracket bounds the error of any alignment.  The
worker also reads back, from the profiler's own file, where its
``bs/clock`` annotation lies (``profiler_zero_us``): that is the
host's clock against the profiler's *host* track, exact to
microseconds, and as good for the device's track as the profiler's own
alignment of the two (on a v5e it has been seen 0.12 ms outside the
bracket).  So the offset used is that mark, moved into the bracket
where it falls outside (``offset``).
"""
import json
import os


def load(tracedir):
    """The spans file beside a PROFILE DEVICE directory, or None."""
    path = tracedir.rstrip("/\\") + "_spans.json"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def programs(trace, pattern):
    """[(start_us, end_us)] of the programs whose name contains
    ``pattern`` on the device that ran most of them, by start."""
    best = []
    for dev in trace.devices.values():
        hit = sorted((1e6 * t, 1e6 * (t + d)) for n, t, d in dev["modules"]
                     if pattern.lower() in n.lower())
        if len(hit) > len(best):
            best = hit
    return best


def bracket(chunks, progs):
    """Pair the windowed chunks (by ``seq``) with the chunk programs of
    the trace (by start) and bracket ``offset = host - trace`` [us].

    The profiler starts inside the window's first dispatch, so the
    first program of the trace belongs to the first chunk; but the
    trace may hold a program more than the window has chunks (one
    dispatched while the window's last edges drained) or fewer (the
    converter keeps a million events), so the pairings are tried from
    that one outwards, those with more pairs first, and the first whose
    bracket is not empty is kept.  Returns ``dict(lo_us, hi_us, pairs,
    lo_seq, hi_seq)`` or None."""
    chunks = sorted((c for c in chunks if "wait_end_us" in c),
                    key=lambda c: c["seq"])
    pairings = [[(c, progs[k + shift]) for k, c in enumerate(chunks)
                 if 0 <= k + shift < len(progs)]
                for shift in sorted(range(-len(chunks) + 1, len(progs)),
                                    key=abs)]
    for pairs in sorted(filter(None, pairings), key=lambda p: -len(p)):
        lo, lo_seq = max((c.get("enqueue_us", c["dispatch_start_us"])
                          - p[0], c["seq"]) for c, p in pairs)
        hi, hi_seq = min((c["wait_end_us"] - p[1], c["seq"])
                         for c, p in pairs)
        if lo <= hi:
            return dict(lo_us=lo, hi_us=hi, pairs=len(pairs),
                        lo_seq=lo_seq, hi_seq=hi_seq)
    return None


def offset(al, mark_us):
    """The offset to use [us]: the worker's mark held to the bracket
    ``al``, or the bracket's midpoint where there is no mark."""
    if mark_us is None:
        return 0.5 * (al["lo_us"] + al["hi_us"])
    return min(max(mark_us, al["lo_us"]), al["hi_us"])


def self_intervals(spans):
    """{span name: [(start_us, end_us)]}: each span's own time, its
    interval less what its children cover.  Spans of one thread nest,
    so these intervals are disjoint across all of them."""
    kids = {}
    for s in spans:
        kids.setdefault(s.get("parent"), []).append(s)
    out = {}
    for s in spans:
        t, end = s["ts"], s["ts"] + s["dur"]
        own = out.setdefault(s["name"], [])
        for c in sorted(kids.get(s["id"], []), key=lambda c: c["ts"]):
            if c["ts"] > t:
                own.append((t, min(c["ts"], end)))
            t = max(t, c["ts"] + c["dur"])
        if t < end:
            own.append((t, end))
    return out


def overlap(intervals, a, b):
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in intervals)
