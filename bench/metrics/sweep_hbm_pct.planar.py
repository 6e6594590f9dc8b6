"""Planar gate application against its roofline: the sum of the plan
items' bounds (bench.roofline) over the device's busy time per circuit."""
from bench import roofline


def read(ctx):
    c = ctx.counters
    if ctx.trace is None or not ctx.trace.ops or not c.get("circuits"):
        return None
    bound = roofline.circuit_bound_s(c["plan_items"], c["n"],
                                     c["state_bytes"], ctx.peaks)
    busy_per_circuit = ctx.trace.busy_s / c["circuits"]
    return 100.0 * bound / busy_per_circuit
