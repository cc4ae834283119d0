"""Device time under `exchange/land/sort` (equeue.land_sorted's step S but
the counts: the destination key, the positions and the one stable sort of
the M = rows x outbox capacity flattened entries, staged or not), per
unit. Part of `exchange.land_ms_per_unit`. None against a program without
that scope."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "exchange/land/sort")
