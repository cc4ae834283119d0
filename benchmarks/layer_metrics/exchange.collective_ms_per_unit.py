"""Device time under `exchange/collective` (the flush's all_to_all /
all_gather / ppermute ring between chips), per unit, averaged over the
chips as the reduced trace is. None on one chip: the scope is empty there."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "exchange/collective")
