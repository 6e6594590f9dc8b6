"""Median wait from a request's lane append to its batch's dispatch, over
the requests dispatched in the traced window (scheduler span stamps)."""
import statistics


def read(ctx):
    waits = ctx.counters.get("queue_wait_s")
    if not waits:
        return None
    return 1e3 * statistics.median(waits)
