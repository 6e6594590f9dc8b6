"""Real rows over device rows (real + padding) of the batches dispatched
in the window, from the scheduler's counters."""


def read(ctx):
    rows = ctx.counters.get("batch_rows", 0)
    pad = ctx.counters.get("padded_slots", 0)
    if rows + pad == 0:
        return None
    return 100.0 * rows / (rows + pad)
