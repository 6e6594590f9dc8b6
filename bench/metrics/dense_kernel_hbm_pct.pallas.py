"""The dense Pallas kernel against its roofline: for the calls tagged
``repro_kind="dense"``, the sum of the bounds (bench.roofline) of the
plan items their ``repro_item`` names, over the sum of their device
durations.  Each call is matched to its item by that attribute, not by
order; if any Pallas call lacks it, there is no reading."""
from bench import roofline
from bench.scopes import attrs
from bench.trace import is_pallas_call


def read(ctx):
    c = ctx.counters
    if ctx.trace is None or not c.get("plan_items"):
        return None
    calls = [(o, attrs(o)) for o in ctx.trace.ops if is_pallas_call(o)]
    if not calls or any("item" not in t for _, t in calls):
        return None
    dense = [(o, t) for o, t in calls if t.get("kind") == "dense"]
    if not dense:
        return None
    items = c["plan_items"]
    bound = 0.0
    for _, t in dense:
        kind, k, controls = items[int(t["item"])]
        bound += roofline.item_bound_s(kind, k, controls, c["n"],
                                       c["state_bytes"], ctx.peaks)
    return 100.0 * bound / sum(o.dur for o, _ in dense)
