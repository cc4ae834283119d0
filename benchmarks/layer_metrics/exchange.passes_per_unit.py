"""Passes of the landing's loop (`equeue.land_sorted`: LAND_LANES arrival
lanes of every destination a pass, `ceil(busiest destination's arrivals /
LAND_LANES)` passes a round) in one unit: the `ChunkProbe.land_passes`
difference (`benchmarks/exchange_counts.py`), summed over the chips as
`drain.iters_per_unit` is (a shard's loop runs to its own busiest
destination); counted with the tracker on or off; exact for a seed on one
plane. `exchange.pull_ms_per_unit` over this is what a pass costs. None
against a program that does not count it with the tracker off."""


def read(ctx):
    import exchange_counts

    d = exchange_counts.per_unit(ctx)
    return d["land_passes"] if d else None
