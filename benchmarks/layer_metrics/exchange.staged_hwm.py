"""Most entries one shard staged for one round's flush since t = 0
(`ChunkProbe.exch_hwm` of the newest chunk: the sum of the outbox's fill
right before the flush; a running mark, NOT a difference over the unit;
the largest over the chips): the figure
`sharded.auto_a2a_capacity(measured_hwm=)` sizes a peer's bucket from.
Counted with the tracker on or off. None against a program that does not
count it with the tracker off."""


def read(ctx):
    import exchange_counts

    d = exchange_counts.per_unit(ctx)
    return d["exch_hwm"] if d else None
