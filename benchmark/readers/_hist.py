"""Increase of a worker histogram's sum and count over the window, from
the broker's fleet aggregate (METRICS payload) before and after."""


def delta(ctx, name):
    h0 = (ctx["f0"].get("fleet") or {}).get(name) or {"sum": 0.0, "count": 0}
    h1 = (ctx["f1"].get("fleet") or {}).get(name)
    if not h1 or h1.get("type") != "histogram":
        return None
    return h1["sum"] - h0["sum"], h1["count"] - h0["count"]
