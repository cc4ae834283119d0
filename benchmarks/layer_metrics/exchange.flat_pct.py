"""Share of the outbox's capacity a unit's flushes flattened: the columns
each live round's flush took (`ChunkProbe.flush_cols`: whole blocks of an
eighth of the capacity, as many as hold the busiest row's fill, 0 for a
skipped flush; summed over the chips, each of which takes as many) over
chips x live rounds x the outbox capacity (`benchmarks/exchange_counts.py`
for the guards, the rounds and the slots). What the flush's flatten, sort,
counts and packing cost goes by this, where `exchange.fill_pct` says how
much of it held a packet. Exact for a seed. None against a program whose
probe has no `flush_cols` (the parent: every flush flattens the whole
outbox there)."""


def read(ctx):
    import exchange_counts

    d = exchange_counts.per_unit(ctx)
    if not d or not d["rounds_live"]:
        return None
    from shadow_tpu import scopes  # exchange_counts found it

    kept = scopes.last_probes
    if not hasattr(kept.chunk, "flush_cols"):
        return None
    cols = kept.chunk.flush_cols - kept.entry.flush_cols
    capacity = d["outbox_slots"] / kept.hosts
    return 100.0 * cols / (ctx.chips * d["rounds_live"] * capacity)
