"""Device time under the chunk program's `drain` scope (run_round's while
loop: handler, pump, their queue merges, compaction), from the chunk's own
trace, per unit."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "drain")
