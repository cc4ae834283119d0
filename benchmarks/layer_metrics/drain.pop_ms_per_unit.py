"""Device time under `drain/handle/pop` (equeue.peek_min + clear_slot as
the handler calls them: the reduction over the row's keys that gives the
slot and its tie, the gather of H indices that reads the slot's payload,
the select pass that tombstones the two key arrays; once a drain iteration
whatever was popped), per unit. Part of `drain.device_ms_per_unit`. None
against a program without that scope."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "drain/handle/pop")
