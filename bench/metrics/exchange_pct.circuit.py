"""Device time in the bit exchanges (``repro_part="exchange"``: the
planar lane and sublane exchanges, the Pallas path's tile swaps) over
the device's busy time."""
from bench.scopes import busy_share_pct


def read(ctx):
    return busy_share_pct(ctx.trace, "part", "exchange")
