"""Share of the traced window that lies inside a running program while no
leaf operation runs: the self time of control-flow wrappers (`while`,
`conditional`, `call`)."""


def read(ctx):
    import scope_account

    folded = scope_account.account(ctx)
    if folded is None or not ctx.trace["window_s"]:
        return None
    return 100.0 * folded["wrappers"] / ctx.trace["window_s"]
