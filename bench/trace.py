"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` and keeps two kinds of interval, on one
clock: device operations (planes named ``/device:TPU:<i>``, line
``XLA Ops``, each named by its HLO instruction, ``fusion.99``) and the benchmark's own host spans (``jax.profiler.
TraceAnnotation`` events whose names start with ``bench.``).
:func:`summarize` is pure and works on such intervals from any source, so
tests feed it synthetic ones.

Busy time is the union of a device's op intervals inside the window,
averaged over the devices; idle share is one minus busy over the window.
An idle gap is labelled by the innermost benchmark span open at its
middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


@dataclasses.dataclass
class Interval:
    name: str
    start: float        # seconds on the trace's clock
    end: float
    device: int = 0
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                 # averaged over the devices seen
    devices: int
    ops: list                     # device op intervals inside the window
    spans: list                   # benchmark host spans
    op_seconds: dict              # op name -> summed device seconds
    op_counts: dict               # op name -> number of executions
    idle_gaps: list               # [(label, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union_seconds(intervals) -> float:
    """Length of the union of ``(start, end)`` pairs."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, t0: float, t1: float):
    """Idle ``(start, end)`` stretches of [t0, t1] between ``intervals``."""
    out, cursor = [], t0
    for s, e in sorted(intervals):
        if s > cursor:
            out.append((cursor, min(s, t1)))
        cursor = max(cursor, e)
        if cursor >= t1:
            break
    if cursor < t1:
        out.append((cursor, t1))
    return [(s, e) for s, e in out if e > s]


def _label(spans, t: float) -> str:
    """Innermost benchmark span open at ``t`` (the shortest one)."""
    open_ = [sp for sp in spans if sp.start <= t < sp.end
             and sp.name != WINDOW_SPAN]
    if not open_:
        return "no bench span"
    return min(open_, key=lambda sp: sp.dur).name


def summarize(ops, spans, window=None) -> TraceSummary:
    """Reduce device ops and host spans over ``window`` (``(t0, t1)``; by
    default the ``bench.window`` span)."""
    if window is None:
        wins = [sp for sp in spans if sp.name == WINDOW_SPAN]
        if not wins:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        window = (wins[0].start, wins[0].end)
    t0, t1 = window
    if not t1 > t0:
        raise ValueError(f"empty trace window {window}")
    inside = [Interval(o.name, max(o.start, t0), min(o.end, t1), o.device,
                       o.stats)
              for o in ops if o.end > t0 and o.start < t1]
    devices = sorted({o.device for o in ops}) or [0]
    busy = [union_seconds([(o.start, o.end) for o in inside
                           if o.device == d]) for d in devices]
    op_seconds: dict = {}
    op_counts: dict = {}
    for o in inside:
        op_seconds[o.name] = op_seconds.get(o.name, 0.0) + o.dur
        op_counts[o.name] = op_counts.get(o.name, 0) + 1
    gaps = []
    for d in devices:
        for s, e in _gaps([(o.start, o.end) for o in inside
                           if o.device == d], t0, t1):
            gaps.append((_label(spans, (s + e) / 2), e - s))
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=t1 - t0, busy_s=sum(busy) / len(busy),
                        devices=len(devices), ops=inside, spans=list(spans),
                        op_seconds=op_seconds, op_counts=op_counts,
                        idle_gaps=gaps)


def breakdown(summary: TraceSummary) -> dict:
    """The result line's ``breakdown``: the device ops that took most time
    and the longest idle gaps, at most :data:`TOP` each."""
    top = sorted(summary.op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps[:TOP]]}


def xplane_path(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir: str):
    """``(ops, spans)`` from the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path(trace_dir))
    ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops += [Interval(short_name(e.name), e.start_ns * 1e-9,
                                 e.end_ns * 1e-9, dev, {"hlo": e.name})
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Interval(e.name, e.start_ns * 1e-9,
                                   e.end_ns * 1e-9)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return ops, spans


def is_pallas_call(op: Interval) -> bool:
    """A Pallas kernel's execution: the TPU trace names a ``pallas_call``
    ``%program.<k>``, an HLO custom call whose target is
    ``tpu_custom_call`` (kept in ``stats["hlo"]``)."""
    return 'custom_call_target="tpu_custom_call"' in op.stats.get("hlo", "")


def short_name(hlo: str) -> str:
    """``fusion.99`` from ``%fusion.99 = (f32[64]...) fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%")
