"""95th percentile of the units' wall times. Wants some tens of units; with
fewer than 20 there is no sample beyond it and nothing is reported."""


def read(ctx):
    n = len(ctx.unit_s)
    if n < 20:
        return None
    ranked = sorted(ctx.unit_s)
    print(f"driver.unit_p95_ms: over {n} units", flush=True)
    return ranked[min(n - 1, int(0.95 * n))] * 1e3
