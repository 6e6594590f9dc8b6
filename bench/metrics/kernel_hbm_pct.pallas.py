"""Pallas kernels against their roofline: the sum of the calls' bounds
over the sum of their device durations.

Each plan item is one Pallas call that reads and writes the whole state
(see bench.roofline for the scope of that count); within a circuit the
k-th call is the k-th plan item, and the traced window holds whole
circuits, so the calls are matched to items in order.
"""
from bench import roofline
from bench.trace import is_pallas_call


def read(ctx):
    c = ctx.counters
    if ctx.trace is None or not c.get("circuits"):
        return None
    calls = sorted((o for o in ctx.trace.ops if is_pallas_call(o)),
                   key=lambda o: o.start)
    items = c["plan_items"]
    if not calls or len(calls) != len(items) * c["circuits"]:
        return None
    bound = sum(roofline.item_bound_s(kind, k, ctl, c["n"], c["state_bytes"],
                                      ctx.peaks)
                for kind, k, ctl in items) * c["circuits"]
    return 100.0 * bound / sum(o.dur for o in calls)
