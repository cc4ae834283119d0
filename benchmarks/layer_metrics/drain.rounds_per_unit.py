"""Live rounds in one unit: rounds that ran a drain loop and a flush
(`ChunkProbe.rounds_live`, counted with the tracker on or off), as the
difference between the newest chunk's probe and the probe of the state
the window's last driver entry started from (`benchmarks/probe_delta.py`);
repeats exactly for a seed. A round's fixed cost (window agreement, sort,
counts, packing) is paid this many times a unit. None against a program
that keeps no probes."""


def read(ctx):
    import probe_delta

    d = probe_delta.per_unit(ctx)
    return d["rounds_live"] if d else None
