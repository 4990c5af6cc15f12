"""How much work the traced chunk programs were, in the window's unit
(simulated seconds, or pieces): the programs are counted in the trace,
and a unit's chunks in the window (``chunks_per_unit``: from the advances
the client saw, or from the worker's own count of chunks over the
pieces completed)."""


def traced(ctx, program):
    """(seconds in the chunk programs, units of work they were)."""
    tr, per_unit = ctx.get("trace"), ctx.get("chunks_per_unit")
    if tr is None or not per_unit:
        return None
    secs, n = tr.program_time(program)
    return (secs, n / per_unit) if n else None
