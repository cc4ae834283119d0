"""What one unit did, by the program's own probes: the difference between
the probe of the newest chunk and the probe of the state its driver entry
started from, which the program keeps for whoever asks
(`shadow_tpu.scopes.last_probes`). The harness replays a unit from a warm
state whose counters are not zero and hands a reader only the iterations
and events of a unit, so rounds and occupancy are read here.

When the readers run, the newest entry is the window's last unit: nothing
after the window enters the driver (`host_stats` is a fetch, the pieces
call the handler and the flush themselves). That is checked, not assumed:
an entry whose drain iterations differ from `ctx.iters_per_unit` gives
None. So does a program that keeps no probes (the parent of the PR that
added this file)."""

FIELDS = ("rounds_live", "iters", "lanes_live")


def per_unit(ctx) -> "dict | None":
    from shadow_tpu import scopes  # the harness has imported the program, or refused

    kept = getattr(scopes, "last_probes", None)  # the parent's scopes has no such name
    if kept is None or kept.chunk is None:
        return None
    d = {k: getattr(kept.chunk, k) - getattr(kept.entry, k) for k in FIELDS}
    if d["iters"] != ctx.iters_per_unit:
        return None
    d["hosts"] = kept.hosts
    return d
