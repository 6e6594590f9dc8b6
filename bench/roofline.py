"""Operations and bytes of the program's work, from its plan items.

Scope: each plan item, and each Pallas call, reads and writes the whole
state once, so its bytes are ``2 * state_bytes``; that is a lower bound
only while that holds.  A change that makes an item touch part of the
state, or that fuses items into one sweep, needs this count corrected.

Flops per amplitude the item changes: a dense k-qubit item 8 * 2**k (one
complex multiply-add per matrix entry of its row), a diagonal item 6 (one
complex multiply), a permutation 0.  An item with c controls changes
2**-c of the amplitudes.  The compute bound uses the bfloat16 peak, so it
is optimistic for float32 work at ``Precision.HIGHEST``, which takes
several bfloat16 passes; a share computed from it can only read low.
"""
from __future__ import annotations


def item_flops(kind: str, k: int, controls: int, n: int) -> float:
    changed = float(1 << n) / (1 << controls)
    if kind == "dense":
        return 8.0 * (1 << k) * changed
    if kind == "diag":
        return 6.0 * changed
    if kind == "perm":
        return 0.0
    raise ValueError(f"no count for plan item kind {kind!r}")


def item_bound_s(kind: str, k: int, controls: int, n: int,
                 state_bytes: int, peaks: dict) -> float:
    """Least time the chip could take for one item: the larger of its
    bytes over HBM bandwidth and its flops over peak compute."""
    return max(2.0 * state_bytes / peaks["hbm_bytes_per_s"],
               item_flops(kind, k, controls, n) / peaks["peak_flops_bf16"])


def circuit_bound_s(items, n: int, state_bytes: int, peaks: dict) -> float:
    """Sum of the item bounds of one circuit; ``items`` are
    ``(kind, k, controls)``."""
    return sum(item_bound_s(kind, k, c, n, state_bytes, peaks)
               for kind, k, c in items)
