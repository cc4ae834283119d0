"""Device time under the chunk program's `window` scope (queue minimum,
staged-traffic test, next window end, a round's bookkeeping): self seconds
of its operations over the three traced units, per unit."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "window")
