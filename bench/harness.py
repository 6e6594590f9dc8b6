"""What every traffic loop shares: the run's record, compile counts,
the memory peak, the benchmark's profiler spans and the window's profile.

A loop is a module ``bench/loops/<loop>.py`` (see :mod:`bench.spec`) with
``run(cfg, traffic, family, *, seed, seconds, trace, devices, log)``,
which returns a :class:`RunRecord`, and ``control(cfg, traffic, family)``
and ``FAULTS`` for :mod:`bench.control`.  Host timestamps are
``time.perf_counter``; with ``trace`` on, the window is profiled and the
benchmark's calls into each layer carry ``bench.*`` spans.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import shutil
import tempfile
import threading

HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
DRAIN_GRACE_S = 60.0    # how long an answer due in the window may be late


@dataclasses.dataclass
class RunRecord:
    e2e: dict                      # end-to-end metric name -> value
    counters: dict                 # what the per-layer readers read
    checks: list                   # [(name, value, limit)]
    attempted: int
    failed: int
    memory_peak_bytes: int | None
    window_start: float            # perf_counter at the window's start
    trace_dir: str | None = None


class CompileCounter:
    """Persistent-cache hits and misses, and backend compiles with their
    seconds, from JAX's monitoring events while the context is open."""

    def __init__(self):
        self._lock = threading.Lock()
        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0

    def _event(self, event, **_):
        with self._lock:
            if event == HIT:
                self.hits += 1
            elif event == MISS:
                self.misses += 1

    def _duration(self, event, seconds, **_):
        if event == BACKEND_COMPILE:
            with self._lock:
                self.compiles += 1
                self.compile_s += seconds

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_listener(self._event)
        monitoring.unregister_event_duration_listener(self._duration)

    def snapshot(self) -> dict:
        with self._lock:
            return {"cache_hits": self.hits, "cache_misses": self.misses,
                    "compiles": self.compiles, "compile_s": self.compile_s}


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Spans:
    """``bench.<name>`` profiler annotations, only while tracing."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(f"bench.{name}")


class Profiler:
    """Profile the window into a temporary directory (under ``TMPDIR``)."""

    def __init__(self, on: bool):
        self.on, self.dir = on, None

    def start(self):
        if self.on:
            import jax
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.dir)

    def stop(self):
        if self.on and self.dir is not None:
            import jax
            jax.profiler.stop_trace()


def cleanup(record: RunRecord) -> None:
    if record.trace_dir:
        shutil.rmtree(record.trace_dir, ignore_errors=True)


def worse(a: float, b: float) -> float:
    """The larger of two errors, where NaN is worse than any number: a
    check folded with plain ``max`` from 0.0 would drop a NaN."""
    if math.isnan(a) or math.isnan(b):
        return math.nan
    return max(a, b)


@contextlib.contextmanager
def patched(obj, name, value):
    """``obj.name`` replaced by ``value`` while the context is open."""
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def fmt(d: dict) -> str:
    return " ".join(f"{k}={v!r}" for k, v in d.items())

