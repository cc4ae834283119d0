"""Window wall time over the events handled by all completed units (the
probe's `events_handled` lane: an exact count)."""


def read(ctx):
    events = ctx.events_per_unit * len(ctx.unit_s)
    if not events:
        return None
    return ctx.window_s * 1e6 / events
