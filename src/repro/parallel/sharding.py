"""Mesh context + sharding-constraint helpers.

The launcher installs the active mesh here; model code calls ``shard`` to
constrain intermediate activations.  Without a mesh (unit tests, single
device) every helper degrades to the identity, so the same model code runs
anywhere — the LM-side echo of the paper's single-source portability claim.

Axis conventions (DESIGN.md §5):
  pod    — outermost data-parallel axis (crosses the DCI on the 2-pod mesh)
  data   — intra-pod data parallelism (+ ZeRO-1 optimizer-state sharding)
  model  — tensor/expert parallelism
"""
from __future__ import annotations

import contextlib
from typing import Iterable, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None

BATCH_AXES = ("pod", "data")
MODEL_AXIS = "model"


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _MESH
    _MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _MESH


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    prev = _MESH
    set_mesh(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        set_mesh(prev)


def _axes_in_mesh(spec: Iterable) -> bool:
    names = set(_MESH.axis_names)
    for s in spec:
        if s is None:
            continue
        ss = s if isinstance(s, tuple) else (s,)
        if not all(a in names for a in ss):
            return False
    return True


def axis_size(name: str) -> int:
    if _MESH is None or name not in _MESH.axis_names:
        return 1
    return _MESH.shape[name]


def batch_axes() -> tuple[str, ...]:
    if _MESH is None:
        return ()
    return tuple(a for a in BATCH_AXES if a in _MESH.axis_names)


def shard(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint when a mesh is active; identity otherwise.

    Spec entries that reference axes missing from the active mesh are
    silently dropped — the same model code serves 1-axis test meshes and the
    3-axis production mesh.
    """
    if _MESH is None:
        return x
    names = set(_MESH.axis_names)

    def keep(s):
        if s is None:
            return None
        ss = s if isinstance(s, tuple) else (s,)
        kept = tuple(a for a in ss if a in names)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]

    cleaned = tuple(keep(s) for s in spec)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_MESH, P(*cleaned)))


def named_sharding(*spec) -> Optional[NamedSharding]:
    if _MESH is None:
        return None
    return NamedSharding(_MESH, P(*spec))


def clean_spec(mesh: Mesh, spec: P) -> P:
    """Drop axes not present in ``mesh`` from a PartitionSpec."""
    names = set(mesh.axis_names)

    def keep(s):
        if s is None:
            return None
        ss = s if isinstance(s, tuple) else (s,)
        kept = tuple(a for a in ss if a in names)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]

    return P(*(keep(s) for s in spec))


def param_partition(path: str, shape: tuple[int, ...],
                    strategy: str = "tp") -> P:
    """Partition rule for a parameter leaf, by name convention.

    strategy="tp": column-parallel weights shard their output dim over
    ``model``; row-parallel weights their input dim; embeddings shard the
    vocab dim; expert weights shard the expert dim (EP).

    strategy="fsdp": every tensor shards its largest divisible dim over the
    combined (data, model) axes (ZeRO-3); experts still shard over model
    first (EP) with the remainder FSDP-sharded.
    """
    if _MESH is None:
        return P()
    tp = axis_size(MODEL_AXIS)
    last = path.rsplit("/", 1)[-1]
    nd = len(shape)

    if strategy == "fsdp":
        dp = axis_size("data")
        if last in ("experts_w1", "experts_w3", "experts_w2") \
                and tp > 1 and shape[0] % tp == 0:
            entries = [MODEL_AXIS] + [None] * (nd - 1)
            for i in range(1, nd):
                if shape[i] % dp == 0 and dp > 1:
                    entries[i] = "data"
                    break
            return P(*entries)
        world = dp * tp
        order = sorted(range(nd), key=lambda i: -shape[i])
        for i in order:
            if world > 1 and shape[i] % world == 0:
                return P(*[("data", MODEL_AXIS) if j == i else None
                           for j in range(nd)])
        for i in order:
            if tp > 1 and shape[i] % tp == 0:
                return P(*[MODEL_AXIS if j == i else None
                           for j in range(nd)])
        return P()

    def ok(dim_size):
        return tp > 1 and dim_size % tp == 0

    if last in ("experts_w1", "experts_w3", "experts_w2"):
        return P(*((MODEL_AXIS,) + (None,) * (nd - 1))) if ok(shape[0]) else P()
    # column-parallel (output dim over model).  NOTE: SSM/LSTM projections
    # deliberately stay replicated under "tp" — mamba's fused in_proj slices
    # its z|xBC|dt segments at non-shard-aligned boundaries, and sharding it
    # on either dim triggers GSPMD regather storms (measured: zamba2 train
    # collective 176 -> 431/752 GB/dev).  Memory-critical SSM cells (decode/
    # long-context) use strategy="fsdp", which shards every tensor on its
    # largest aligned dim without touching the activation layout.
    if last in ("wq", "w1", "w3") and nd >= 1 and ok(shape[-1]):
        return P(*((None,) * (nd - 1) + (MODEL_AXIS,)))
    # row-parallel (input dim over model): output projections
    if last in ("wo", "w2") and ok(shape[-2] if nd >= 2 else 0):
        return P(*((None,) * (nd - 2) + (MODEL_AXIS, None)))
    if last in ("embed", "lm_head") and ok(shape[-2] if nd >= 2 else 0):
        return P(*((None,) * (nd - 2) + (MODEL_AXIS, None)))
    return P()


def zero1_spec(spec: P, shape: tuple[int, ...], axis: str = "data") -> P:
    """ZeRO-1: additionally shard optimizer state over the data axis on the
    first divisible, not-yet-sharded dimension."""
    if _MESH is None or axis not in _MESH.axis_names:
        return spec
    d = _MESH.shape[axis]
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for s in entries:
        ss = s if isinstance(s, tuple) else (s,)
        if s is not None and axis in ss:
            return spec            # already sharded over the data axis
    for i, (s, dim) in enumerate(zip(entries, shape)):
        if s is None and dim % d == 0 and dim >= d:
            entries[i] = axis
            return P(*entries)
    return spec
