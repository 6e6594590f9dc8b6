"""Pallas kernels' device time over the device's busy time; the rest is
the XLA tile exchanges around the calls."""
from bench.trace import is_pallas_call, union_seconds


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    calls = [(o.start, o.end) for o in ctx.trace.ops if is_pallas_call(o)]
    if not calls:
        return None
    return 100.0 * union_seconds(calls) / ctx.trace.busy_s
