"""Mean of the program's own ``data.wait`` span per step
(``Updater.update``: the time ``next(iterator)`` took), in ms."""


def read(ctx):
    waits = (ctx.telemetry or {}).get("data.wait")
    if not waits:
        return None
    return sum(waits) / len(waits) * 1e3
