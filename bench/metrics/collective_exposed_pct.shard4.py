"""Time in which a qubit-block swap (bench.ici) runs on a chip and no
other operation of that chip does, over the busy time, averaged over the
chips: the part of the swaps that nothing hides."""
from bench import ici


def read(ctx):
    seconds = ici.swap_seconds(ctx.trace, exposed=True)
    if seconds is None or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * seconds / ctx.trace.busy_s
