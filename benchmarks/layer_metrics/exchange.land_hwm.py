"""Most arrivals ONE destination landed in one round since t = 0
(`ChunkProbe.land_hwm` of the newest chunk): a running mark, NOT a
difference over the unit, so it holds the warm-up's rounds too. What
`equeue.LAND_LANES` and the landing's fan-in worst case (PERF.md section
7.7) are read against: a round makes `ceil(its mark / LAND_LANES)` passes.
Counted with the tracker on or off; the largest over the chips. None
against a program that does not count it with the tracker off."""


def read(ctx):
    import exchange_counts

    d = exchange_counts.per_unit(ctx)
    return d["land_hwm"] if d else None
