"""Device time under `exchange/land/pack` (equeue.land_sorted's step G:
the payload of all M flattened entries packed where it lies as `[14, M]`
32-bit words, the 64-bit times and ties split in two), per unit. Part of
`exchange.land_ms_per_unit`. None against a program without that scope."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "exchange/land/pack")
