"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 100.0 * ctx.trace.idle_share
