"""Items in the plan the window ran: one full-state sweep each."""


def read(ctx):
    items = ctx.counters.get("plan_items")
    return None if items is None else float(len(items))
