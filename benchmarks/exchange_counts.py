"""What the exchange carried in one unit, by the program's own probes: the
landing's passes, the entries staged, the live rounds that flushed them,
the two marks of the busiest round so far, and the outbox slots a round's
flushes flatten. `probe_delta.per_unit` hands out three fields and keeps
the guards (no kept probes; an entry whose drain iterations differ from
`ctx.iters_per_unit`); this file reads the exchange's lanes of the same
two probes (`shadow_tpu.scopes.last_probes`) under those guards.

The program counts `land_passes`, `land_hwm` and `exch_hwm` whatever
`cfg.tracker` says since the PR that added this file, and says so by
keeping `outbox_slots` beside the probes: against a program without that
field (the parent, whose three lanes read 0 with the tracker off) every
reader of this file gives None."""

DELTAS = ("land_passes", "packets_sent")  # chunk - entry: what the unit did
MARKS = ("land_hwm", "exch_hwm")  # running maxima since t = 0: the newest


def per_unit(ctx) -> "dict | None":
    import probe_delta

    base = probe_delta.per_unit(ctx)
    if base is None:
        return None
    from shadow_tpu import scopes  # probe_delta found it

    kept = scopes.last_probes
    slots = getattr(kept, "outbox_slots", None)  # the parent's EntryProbes has none
    if not slots:
        return None
    d = {k: getattr(kept.chunk, k) - getattr(kept.entry, k) for k in DELTAS}
    d.update({k: getattr(kept.chunk, k) for k in MARKS})
    d["rounds_live"] = base["rounds_live"]
    d["outbox_slots"] = slots
    return d
