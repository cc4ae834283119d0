"""Device time under `exchange/bucket` (the sharded flush's all_to_all
mode: each entry's shard, the stable argsort by it, its rank in its
peer's bucket, and per array the `[peers, capacity]` buffer and the
scatter into it: all that stands in front of the collective), per unit,
averaged over the chips as the reduced trace is. Part of
`exchange.device_ms_per_unit`. None on one chip (the scope is empty
there) and against a program without that scope."""


def read(ctx):
    import scope_account

    return scope_account.ms_per_unit(ctx, "exchange/bucket")
