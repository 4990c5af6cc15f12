"""Median dispatch-to-completion time of a piece [ms], both stamped on
the client's clock as the journal's records appeared."""
import statistics


def read(ctx, params):
    ps = ctx.get("piece_s") or []
    return 1e3 * statistics.median(ps) if ps else None
