"""Device time in the result epilogue (``repro_kind="epilogue"``: the
expectation reductions after the last gate) over the device's busy
time."""
from bench.scopes import busy_share_pct


def read(ctx):
    return busy_share_pct(ctx.trace, "kind", "epilogue")
