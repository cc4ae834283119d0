"""Drain-loop iterations in one unit (`ChunkProbe.iters` difference);
repeats exactly for a seed."""


def read(ctx):
    return ctx.iters_per_unit or None
