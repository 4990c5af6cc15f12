"""Sum of a broker histogram's observations inside the window, per unit
of the window's work (pieces): the broker's own registry, which the
METRICS payload carries beside the fleet aggregate."""


def read(ctx, params):
    h0 = (ctx["f0"].get("server") or {}).get(params["hist"]) \
        or {"sum": 0.0, "count": 0}
    h1 = (ctx["f1"].get("server") or {}).get(params["hist"])
    if not h1 or h1.get("type") != "histogram" or not ctx["units"] \
            or h1["count"] <= h0["count"]:
        return None
    return (h1["sum"] - h0["sum"]) / ctx["units"]
