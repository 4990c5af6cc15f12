"""How evenly a fleet shared the window's work: the least-loaded
worker's increase of a worker histogram's count over the fleet's mean
increase, from each worker's own ``METRICS DUMP`` before and after
(``m0``, ``m1``: ``{worker id: text}``).  1.0 is even, 0.0 a worker that
retired nothing inside the window; ``None`` with fewer than two workers
to compare, or where they retired nothing.

Per-layer metrics are read in traced runs, and there the traced worker
(``ctx["traced_worker"]``) spends most of the window inside the
profiler's ``stop_trace`` (48 s of 51 on a 14-chunk ``wallmc`` window):
its shortfall is the profiler's and not the farm's, so it is left out
and the balance is that of the workers the profiler did not stop."""
import re


def count(text, hist):
    found = re.search(re.escape(hist) + r": n=(\d+)", text or "")
    return int(found[1]) if found else 0


def name(worker):
    return worker.hex() if isinstance(worker, bytes) else str(worker)


def read(ctx, params):
    m0, m1 = ctx["m0"], ctx["m1"]
    if not isinstance(m1, dict):
        return None
    traced = ctx.get("traced_worker")   # a reader runs in traced runs
    grew = [count(text, params["hist"])
            - count((m0 or {}).get(w), params["hist"])
            for w, text in m1.items() if name(w) != traced]
    if len(grew) < 2 or sum(grew) <= 0:
        return None
    return min(grew) * len(grew) / sum(grew)
