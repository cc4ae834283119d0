"""Device time under `drain/handle/stage` (the handler's staging of the
packets that survived loss into the host's own outbox row: one select over
the whole [H, outbox_capacity, ...] grid per packet lane, whatever was
emitted), per unit. None against a program without that scope."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "drain/handle/stage")
