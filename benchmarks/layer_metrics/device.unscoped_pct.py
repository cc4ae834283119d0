"""Share of the device's busy seconds that no scope names: operations of
the chunk program under no scope, and operations of other programs
(`jit_copy`, `jit_convert_element_type`, `_peek_next_time`)."""


def read(ctx):
    import scope_account

    folded = scope_account.account(ctx)
    if folded is None or not ctx.trace["busy_s"]:
        return None
    nameless = sum(folded["by_scope"].get(k, 0.0)
                   for k in (scope_account.UNSCOPED, scope_account.OTHER))
    return 100.0 * nameless / ctx.trace["busy_s"]
