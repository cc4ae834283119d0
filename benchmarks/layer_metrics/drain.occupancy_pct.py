"""Share of the rows a drain iteration passes over that hold an eligible
event, over one unit: `lanes_live` over iterations x the rows a shard
scans, as `ChunkProbe.occupancy` computes it, from the difference between
the newest chunk's probe and its entry's (`benchmarks/probe_delta.py`). An
iteration costs the same whatever this reads. None against a program that
keeps no probes."""


def read(ctx):
    import probe_delta

    d = probe_delta.per_unit(ctx)
    if not d or not d["iters"]:
        return None
    return 100.0 * d["lanes_live"] / (d["iters"] * (d["hosts"] // ctx.chips))
