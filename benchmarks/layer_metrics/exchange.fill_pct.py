"""Share of what a unit's flushes flatten, sort, count and pack that holds
a packet: entries staged in the unit (the `ChunkProbe.packets_sent`
difference: every kept packet is staged) over live rounds x the outbox
slots of all shards (`benchmarks/exchange_counts.py`); the exchange's
`drain.occupancy_pct`. A flush costs the same whatever this reads. Exact
for a seed. None against a program that keeps no `outbox_slots`."""


def read(ctx):
    import exchange_counts

    d = exchange_counts.per_unit(ctx)
    if not d or not d["rounds_live"]:
        return None
    return 100.0 * d["packets_sent"] / (d["rounds_live"] * d["outbox_slots"])
