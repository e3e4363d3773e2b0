"""The steps the window completed (a count, so a CPU run may read it)."""


def read(ctx):
    return ctx.stats["steps"]
