"""A quantity the window kind measured itself (``q`` of its result: what
``reports`` of a mix may name, and what a kind reads beside that)."""


def read(ctx, params):
    return (ctx.get("q") or {}).get(params["quantity"])
