"""Device time under `drain/handle/tcp` (transport/tcp.py: tcp_handle on
the fused slot view, the model's view_write / view_close, commit_slot's
one scatter), per unit. None against a program without that scope."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "drain/handle/tcp")
