"""Device time under the chunk program's `exchange` scope (flush_outbox:
bucketing, collectives, landing), from the chunk's own trace, per unit."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "exchange")
