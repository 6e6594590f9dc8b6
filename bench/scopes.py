"""The program's device attributes, as a profile keeps them.

The program tags the operations it emits with ``repro_<key>="<value>"``
frontend attributes (``repro.core.scopes``): the plan item and its kind
and width, ``part`` (``apply`` or ``exchange``), the epilogue's terms.
A TPU profile names each device operation by its HLO instruction text,
attributes included, which :func:`bench.trace.load` keeps as
``op.stats["hlo"]``.  A program that tags nothing gives these helpers
nothing to find, and the readers built on them then read nothing: a
missing attribute is never a zero.
"""
from __future__ import annotations

import re

from bench.trace import union_seconds

ATTR = re.compile(r'\brepro_(\w+)="([^"]*)"')


def attrs(op) -> dict:
    """``{key: value}`` of the ``repro_`` attributes on one operation."""
    return dict(ATTR.findall(op.stats.get("hlo", "")))


def busy_share_pct(trace, key: str, value: str):
    """Device time in operations tagged ``repro_<key>="<value>"``, as a
    share of the busy time: the union of their intervals on each device,
    averaged over the devices as ``busy_s`` is.  None where no operation
    carries ``repro_<key>`` at all."""
    if trace is None or not trace.ops or trace.busy_s <= 0:
        return None
    tags = [attrs(o) for o in trace.ops]
    if not any(key in t for t in tags):
        return None
    per_device: dict = {}
    for o, t in zip(trace.ops, tags):
        if t.get(key) == value:
            per_device.setdefault(o.device, []).append((o.start, o.end))
    seconds = sum(union_seconds(iv) for iv in per_device.values())
    return 100.0 * seconds / trace.devices / trace.busy_s
