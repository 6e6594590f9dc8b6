"""Hardware target descriptors — the VLA "vector length query" analogue.

The paper resolves ``numVals = VLEN / ELEN`` at run time from the SVE register
width. JAX shapes are static, so the same decision is made at *trace* time from
a target descriptor: every kernel in this package is parameterized by
``target.lanes`` (the fp32 lane tile, numVals analogue) and the roofline
constants used by the fusion-degree chooser (machine balance adaptation,
paper §IV-D).  One kernel source serves every descriptor.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Target:
    """A vector-width + memory-hierarchy descriptor of one platform."""

    name: str
    lanes: int                 # fp32 elements per vector tile (numVals analogue)
    sublanes: int              # second-minor tile dim (TPU VREG sublanes)
    vmem_bytes: int            # fast scratch capacity (SVE: L1; TPU: VMEM)
    hbm_bw: float              # bytes/s main-memory bandwidth
    peak_flops_f32: float      # FLOP/s, fp32 vector units
    peak_flops_bf16: float     # FLOP/s, matrix units (0 if none)
    mxu_dim: int               # systolic tile (0 if no matrix unit)
    ici_bw: float              # bytes/s per interconnect link (0 = single chip)
    # device memory holds arrays in (sublanes, lanes) tiles, so a view whose
    # minor axes are narrower is padded: lowerings keep the tile whole
    tiled: bool = False

    @property
    def machine_balance_f32(self) -> float:
        """FLOPs per byte at which fp32 compute and HBM bandwidth balance."""
        return self.peak_flops_f32 / self.hbm_bw

    @property
    def machine_balance_bf16(self) -> float:
        return (self.peak_flops_bf16 or self.peak_flops_f32) / self.hbm_bw

    @property
    def lane_qubits(self) -> int:
        """log2(lanes): number of state qubits resident in the lane axis."""
        q = self.lanes.bit_length() - 1
        if (1 << q) != self.lanes:
            raise ValueError(f"lanes must be a power of two, got {self.lanes}")
        return q


def row_budget(n: int, target: Target) -> int:
    """Row-qubit budget of an ``n``-qubit lane-tiled state: ``max(2, n -
    target.lane_qubits)``.

    This is the canonical statement of the rule — every fused-cluster width
    cap derives from it.  The planar layout ``f32[2, R, V]`` keeps the bottom
    ``lane_qubits`` state qubits resident in the vector-lane axis, so only
    ``n - lane_qubits`` qubits live on addressable rows; a fused cluster wider
    than that would force lane reshuffles the block layout cannot express.
    The floor of 2 keeps two-qubit gates fusable even on tiny states (they
    then span lane qubits, which the planar/pallas applications handle as
    ordinary tensor axes, just without the wide-cluster fast paths).

    Callers (keep these in lockstep — they must all agree on one number):

    * :func:`repro.engine.plan.resolve_f` — general fused-cluster cap;
    * :func:`repro.engine.plan.resolve_diag_f` — wide-diagonal cluster cap
      handed to ``cluster_gates(diag_f=...)``;
    * :meth:`repro.core.distributed.DistributedSimulator.prepare` and the
      sharded plan path, which pass the *local* qubit count ``n -
      state_bits`` — the per-device sub-state a ``shard_map`` block sees —
      so sharded and planar plans can never drift apart.
    """
    return max(2, n - target.lane_qubits)


# TPU v5e: 197 TFLOP/s bf16 MXU, ~1/4 for fp32 via MXU passes, 819 GB/s HBM,
# 128 MiB VMEM (usable budget kept conservative), 50 GB/s/link ICI.
TPU_V5E = Target(
    name="tpu_v5e",
    lanes=128,
    sublanes=8,
    vmem_bytes=96 * 2**20,
    hbm_bw=819e9,
    peak_flops_f32=49.25e12,
    peak_flops_bf16=197e12,
    mxu_dim=128,
    ici_bw=50e9,
    tiled=True,
)

# TPU v5p-like descriptor (wider HBM): shows the VLA point — same source,
# different balance point, different chosen fusion degree.
TPU_V5P = Target(
    name="tpu_v5p",
    lanes=128,
    sublanes=8,
    vmem_bytes=128 * 2**20,
    hbm_bw=2765e9,
    peak_flops_f32=114.5e12,
    peak_flops_bf16=459e12,
    mxu_dim=128,
    ici_bw=100e9,
    tiled=True,
)

# Small descriptor for CPU tests: the same kernels lower with an 8-lane tile,
# which is the "short vector machine" end of the VLA sweep (SVE 128-bit / fp32
# = 4 lanes; we keep >=8 for TPU sublane alignment).  Balance calibrated to
# one busy core of this container (~50 GFLOP/s, ~20 GB/s): choose_f lands on
# f=3, matching the empirically best fusion degree of the Fig-10 benchmark —
# the same descriptor->optimum agreement the paper shows for its ARM CPUs.
CPU_TEST = Target(
    name="cpu_test",
    lanes=8,
    sublanes=8,
    vmem_bytes=1 * 2**20,
    hbm_bw=20e9,
    peak_flops_f32=0.05e12,
    peak_flops_bf16=0.0,
    mxu_dim=0,
    ici_bw=0.0,
)

# ARM descriptors used only for the paper-comparison projection benchmark
# (Fig 14/15 analogue): lanes = numVals from the paper's platforms; FLOP/s are
# *achievable* (not peak) throughputs, so that machine balance reflects the
# paper's measurements.  With these, ``choose_f`` lands on f=4 (Grace, 72
# threads), f=3 (Graviton), f=3 (A64FX) — the optima of the paper's Fig 10.
ARM_GRACE = Target("arm_grace", 4, 1, 64 * 2**10, 380e9, 2.0e12, 0.0, 0, 0.0)
ARM_GRAVITON3 = Target("arm_graviton3", 8, 1, 64 * 2**10, 307.2e9, 1.2e12, 0.0, 0, 0.0)
ARM_A64FX = Target("arm_a64fx", 16, 1, 64 * 2**10, 1024e9, 3.4e12, 0.0, 0, 0.0)

TARGETS = {
    t.name: t
    for t in (TPU_V5E, TPU_V5P, CPU_TEST, ARM_GRACE, ARM_GRAVITON3, ARM_A64FX)
}


def get_target(name: str) -> Target:
    try:
        return TARGETS[name]
    except KeyError:
        raise KeyError(f"unknown target {name!r}; have {sorted(TARGETS)}") from None


# device_kind strings JAX reports for the TPU generations described above
_TPU_KINDS = {"TPU v5 lite": TPU_V5E}


def device_target(device=None) -> Target:
    """The :class:`Target` of ``device`` (default: ``jax.devices()[0]``).

    The CPU maps to ``CPU_TEST`` and a TPU v5e to ``TPU_V5E``; any other
    device raises, so that no chip is ever run with another chip's lane
    tiling and fusion caps.
    """
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return CPU_TEST
    if device.platform == "tpu" and device.device_kind in _TPU_KINDS:
        return _TPU_KINDS[device.device_kind]
    raise ValueError(f"no target for device {device.platform!r} "
                     f"kind {device.device_kind!r}; known TPU kinds: "
                     f"{sorted(_TPU_KINDS)}")


def resolve_interpret(interpret: bool | None = None, device=None) -> bool:
    """Pallas interpret mode: ``None`` follows the platform (interpreted
    everywhere but on a TPU); asking to interpret on a TPU is an error."""
    if interpret is False:
        return False
    if device is None:
        import jax
        device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU: Pallas kernels compile "
                         "there; leave interpret unset")
    return not on_tpu
