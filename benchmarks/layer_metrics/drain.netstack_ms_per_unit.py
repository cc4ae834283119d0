"""Device time under `drain/handle/netstack` (netstack.py as the handler
calls it: the down relay's token bucket and CoDel at ingress, the up
relay's token buckets at emit time), per unit. None against a program
without that scope."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "drain/handle/netstack")
