"""Device time under `exchange/land` (equeue.push_many_sorted: destination
sort, row gather, row scatter, and the delivery grid merged into the queue
rows below it as `exchange/land/push_self`), per unit."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "exchange/land")
