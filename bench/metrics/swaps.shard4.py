"""Qubit-block swaps (``all_to_all``s) per circuit of the sharded program,
restores at its end included (``CompiledPlan.sharded_swaps``)."""


def read(ctx):
    swaps = ctx.counters.get("swaps")
    return None if swaps is None else float(swaps)
