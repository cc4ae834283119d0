"""First `sched.run` up to the end of the driver's `compile+launch` span:
the chunk program compiled, or loaded from the persistent cache."""


def read(ctx):
    return ctx.phases.get("compile_load_s")
