"""Device time under `exchange/land/count` (equeue.run_bounds: each
destination's arrival count, a product of two one-hot matrices whose work
grows as entries x hosts, and the runs' starts), per unit. Part of
`exchange.land_ms_per_unit`. None against a program without that scope."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "exchange/land/count")
