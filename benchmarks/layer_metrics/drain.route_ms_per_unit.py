"""Device time under `drain/handle/route` (the handler's routing lookup:
the source's and the destination's node, and one gather of the pair's
packed latency and reliability words for each of the [H, packet lanes]
indices, in every drain iteration whatever was emitted), per unit. None
against a program without that scope."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "drain/handle/route")
