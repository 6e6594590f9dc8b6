"""Device time in the qubit-block swaps (their ``all_to_all`` and the
views tagged ``repro_part="collective"``, bench.ici) over the device's
busy time, averaged over the chips.  None where no operation is a swap."""
from bench import ici


def read(ctx):
    seconds = ici.swap_seconds(ctx.trace)
    if seconds is None or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * seconds / ctx.trace.busy_s
