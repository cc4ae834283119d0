"""One `handle_one_iteration` on the warm state: median of 9 blocked calls
of a 24-iteration scan, per iteration."""


def read(ctx):
    p = ctx.pieces()
    return p["iter_ms"] if p else None
