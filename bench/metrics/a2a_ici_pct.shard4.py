"""The qubit-block swaps against their roofline: the bytes each chip sends
in the window's swaps (bench.ici: swaps per circuit x circuits x
(1 - 2**-s) x the local state bytes) over the chip's interconnect
bandwidth, over the device time of the swaps per chip."""
from bench import ici


def read(ctx):
    c = ctx.counters
    bw = ici.bandwidth(c.get("device_kind"))
    seconds = ici.swap_seconds(ctx.trace)
    if bw is None or not seconds or not (c.get("circuits")
                                         and c.get("swaps")):
        return None
    sent = (ici.swap_bytes_sent(c["local_state_bytes"], c["state_bits"])
            * c["swaps"] * c["circuits"])
    return 100.0 * sent / bw / seconds
