"""Target-hardware constants (TPU v5e) used by the roofline analysis.

These describe the TARGET platform that the dry-run artifacts are
analyzed against (``benchmarks/roofline.py``).
"""

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops_bf16: float  # FLOP/s per chip
    hbm_bw: float           # bytes/s per chip
    ici_bw_per_link: float  # bytes/s per ICI link (one direction)
    ici_links: int          # links per chip participating in collectives
    hbm_bytes: int          # HBM capacity per chip
    vmem_bytes: int         # VMEM per core


TPU_V5E = ChipSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bw=819e9,
    ici_bw_per_link=50e9,
    ici_links=4,
    hbm_bytes=16 * 2**30,
    vmem_bytes=128 * 2**20,
)

# MXU / VPU native tile granularities — BlockSpec shapes in kernels/ are
# multiples of these.
MXU_DIM = 128
SUBLANE = 8
LANE = 128
