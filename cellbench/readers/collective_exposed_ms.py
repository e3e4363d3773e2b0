"""Per step, the time a collective operation runs on chip 0 while no
compute operation does, in ms (device trace)."""


def read(ctx):
    tr = ctx.trace
    if not tr["steps"] or not tr["collective_s"]:
        return None
    return tr["collective_exposed_s"] / tr["steps"] * 1e3
