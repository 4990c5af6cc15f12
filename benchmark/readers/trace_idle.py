"""Share of the traced window in which no operation ran on the device."""


def read(ctx, params):
    s = ctx.get("trace_summary")
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
