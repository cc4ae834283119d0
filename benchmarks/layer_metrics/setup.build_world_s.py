"""Front door: configuration -> world -> scheduler -> initial state, seconds."""


def read(ctx):
    return ctx.phases.get("build_world_s")
