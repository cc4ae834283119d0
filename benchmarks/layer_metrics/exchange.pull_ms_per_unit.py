"""Device time under `exchange/land/pull` (equeue.land_sorted's while loop:
a pass pulls LAND_LANES arrival lanes of every destination through the
sort's permutation and selects them into the rows' free slots; as many
passes as the round's busiest destination needs), per unit. Part of
`exchange.land_ms_per_unit`. None against a program without that scope."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "exchange/land/pull")
