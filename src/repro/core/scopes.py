"""Names that reach the profiler: device attributes and host spans.

A TPU profile names each device operation by its HLO instruction text and
nothing else, so a ``jax.named_scope`` never reaches it.  What does reach
it is the instruction's ``frontend_attributes``, which
``jax.experimental.xla_metadata.set_xla_metadata`` puts on every
operation traced inside it (XLA keeps a fusion's root attributes on the
fusion).  Two helpers put the program's own names on the profiler's
timeline:

* :func:`device_scope` tags the operations traced inside it with
  ``repro_<key>="<value>"`` frontend attributes and opens a
  ``jax.named_scope`` of the same keys for human readers of a profile.
  Nested scopes merge their keys; the inner scope wins.  It acts at trace
  time only: the compiled program is the same operations with attributes
  attached, so it is always on.
* :func:`host_span` is a ``jax.profiler.TraceAnnotation``: a host span on
  the profiler's own clock, with integer attributes that are formatted
  only while a profile is being taken.

The keys the program uses (``docs/OBSERVABILITY.md`` lists each with the
metric that reads it): ``item``, ``kind``, ``width`` and ``part`` per plan
item, ``term`` per observable of the result epilogue.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.experimental.xla_metadata import set_xla_metadata

PREFIX = "repro_"

_local = threading.local()


def current_attrs() -> dict[str, str]:
    """The attributes of the innermost open :func:`device_scope` on this
    thread, keys without the prefix."""
    return dict(getattr(_local, "attrs", {}))


@contextlib.contextmanager
def device_scope(**attrs):
    """Tag every operation traced inside with ``repro_<key>="<value>"``."""
    outer = getattr(_local, "attrs", {})
    _local.attrs = {**outer, **{k: str(v) for k, v in attrs.items()}}
    name = ",".join(f"{k}={v}" for k, v in attrs.items())
    try:
        with jax.named_scope(name), set_xla_metadata(
                **{PREFIX + k: str(v) for k, v in attrs.items()}):
            yield
    finally:
        _local.attrs = outer


def kernel_metadata(**extra) -> dict[str, str]:
    """``pallas_call(metadata=...)`` for a kernel launched inside the
    current scope: its plan item and kind, and the kernel's own ``extra``
    keys (the phase kernel's ``steps`` and ``block_bytes``)."""
    attrs = current_attrs()
    return {"item": attrs.get("item", ""), "kind": attrs.get("kind", ""),
            **{k: str(v) for k, v in extra.items()}}


def host_span(name: str, **attrs: int) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (a constant) on the profiler's clock."""
    return jax.profiler.TraceAnnotation(name, **attrs)
