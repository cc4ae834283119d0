"""The landing's share of its roofline, memory-bound: the least time is the
bytes a unit's landings have to move (land_bytes.land_min_bytes times
land_bytes.rounds_per_unit, from the configuration's shapes) over the
chip's HBM bandwidth; the share is that over the measured
`exchange.land_ms_per_unit` (the run counts' scope included). One chip."""


def read(ctx):
    import land_bytes
    import scope_account

    land_ms = scope_account.ms_per_unit(ctx, "exchange/land")
    if not land_ms or ctx.chips != 1 or "hbm_bytes_per_s" not in ctx.peaks:
        return None
    doc = land_bytes.config_doc(ctx.cell)
    rounds = land_bytes.rounds_per_unit(doc, int(ctx.params["unit_sim_ms"]))
    least_bytes = rounds * land_bytes.land_min_bytes(*land_bytes.shapes(doc))
    least_ms = least_bytes / ctx.peaks["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / land_ms
