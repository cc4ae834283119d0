"""The flush's share of its roofline, memory-bound: the least time is the
bytes it has to move (roofline.flush_min_bytes) over the chip's HBM
bandwidth; the share is that over the measured `exchange.flush_ms`."""


def read(ctx):
    p = ctx.pieces()
    if not p or not p["flush_ms"] or "hbm_bytes_per_s" not in ctx.peaks:
        return None
    least_ms = p["flush_min_bytes"] / ctx.peaks["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / p["flush_ms"]
