"""What the host spends per driver entry while the device may starve:
median over the window's `run` spans of the span's length less the time
inside its `probe_fetch` children (where the host is blocked by design)."""

import statistics


def read(ctx):
    import scope_account

    runs = scope_account.window_runs(ctx)
    if not runs:
        return None
    return statistics.median(
        (r[2] - r[1]) - sum(e - s for n, s, e in inside if n == "probe_fetch")
        for r, inside in runs) * 1e3
