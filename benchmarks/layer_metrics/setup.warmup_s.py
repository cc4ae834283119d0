"""The rest of the run to the warm point, and the one untimed unit."""


def read(ctx):
    return ctx.phases.get("warmup_s")
