"""One `flush_outbox` on the outbox the timed iterations filled: median of
9 blocked calls."""


def read(ctx):
    p = ctx.pieces()
    return p["flush_ms"] if p else None
