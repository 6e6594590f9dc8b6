"""The qubit-block swaps: which device operations they are, the bytes
each chip sends in one, and the chip's interconnect bandwidth.

A swap of ``s`` global qubits with a block of ``s`` local ones is one
``all_to_all`` over ``2**s`` chips: each chip keeps ``2**-s`` of its local
state and sends the rest, ``(1 - 2**-s) * local_state_bytes``, to the
others.  Only the bytes sent are counted, against the whole per-chip
bandwidth of ``bench/peaks_ici.json``, so a share computed from them can
only read low.

In the profile, a swap is its ``all-to-all`` instruction, which XLA's TPU
pipeline rebuilds without the program's attributes (compiled for a
described v5e), and the views around it that keep
``repro_part="collective"``.
"""
from __future__ import annotations

import json
import pathlib
import re

from bench.scopes import attrs
from bench.trace import union_seconds

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks_ici.json"
ALL_TO_ALL = re.compile(r"= \S+ all-to-all(?:-start|-done)?\(")


def swap_bytes_sent(local_state_bytes: int, state_bits: int) -> float:
    """Bytes one chip sends in one qubit-block swap."""
    return (1.0 - 2.0 ** -state_bits) * local_state_bytes


def bandwidth(device_kind) -> float | None:
    """Per-chip interconnect bytes per second of ``device_kind``; None for
    a device the table does not hold."""
    table = json.loads(PEAKS.read_text())["devices"]
    entry = table.get(device_kind)
    return None if entry is None else float(entry["ici_bytes_per_s"])


def is_swap(op) -> bool:
    """Whether a device operation belongs to a qubit-block swap."""
    return (ALL_TO_ALL.search(op.stats.get("hlo", "")) is not None
            or attrs(op).get("part") == "collective")


def swap_seconds(trace, exposed: bool = False) -> float | None:
    """Seconds a chip spends in swaps, averaged over the chips as
    ``busy_s`` is; with ``exposed``, only the time in which no other
    operation of that chip runs.  None where no operation is a swap."""
    if trace is None or not trace.ops:
        return None
    swap = [is_swap(o) for o in trace.ops]
    if not any(swap):
        return None
    every, other = {}, {}
    for o, sw in zip(trace.ops, swap):
        if exposed or sw:
            every.setdefault(o.device, []).append((o.start, o.end))
        if exposed and not sw:
            other.setdefault(o.device, []).append((o.start, o.end))
    # exposed: covered by a swap and by nothing else, all ops less the rest
    seconds = sum(union_seconds(iv) - union_seconds(other.get(d, []))
                  for d, iv in every.items())
    return seconds / trace.devices
