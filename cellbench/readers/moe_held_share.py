"""Share of a step's routes that landed on the experts held here:
``moe_rows_routed`` over positions x experts per token x layers, the
mean over the window's steps (the runner prints min and max).  Balanced
routing reads held / all experts.  A rehearsal leaves it out (the
runner's printed line carries the counters there): a CPU run's line
holds no share."""


def read(ctx):
    if ctx.spec.rehearse or not ctx.telemetry \
            or "counters" not in ctx.telemetry:
        return None
    routed = ctx.telemetry["counters"]["moe_rows_routed"]
    return 100.0 * float(routed.mean()) / ctx.telemetry["routes_per_step"]
