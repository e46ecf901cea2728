"""Hardware constants for the analytical latency/energy model and roofline.

Target hardware is a TPU v5e-class chip (the runtime here is CPU; the chip
is the *model*). Every constant is either given by the task spec or carries
a public citation so the energy model is auditable.

Roofline constants (task spec):
  * 197 TFLOP/s bf16 per chip
  * 819 GB/s HBM bandwidth per chip
  * ~50 GB/s per ICI link (we assume 3 usable links per chip on a 2-D/3-D
    torus slice and fold that into ``ICI_BYTES_PER_S_PER_CHIP``)

Energy constants (per byte / per flop):
  * HBM access energy: ~3.9 pJ/bit for HBM2-class stacks
    [O'Connor et al., "Fine-Grained DRAM", MICRO 2017; Micron HBM2 data]
    => 31.2 pJ/B.
  * Near-compute SRAM (VMEM-class, the "Sidebar"): large banked SRAM access
    is ~1-2 orders of magnitude cheaper than DRAM [Horowitz, ISSCC 2014:
    8KB SRAM 64b access ~10pJ => ~1.25 pJ/B; scaled bank-local]. We use
    1.2 pJ/B, a ~26x advantage over HBM — deliberately conservative vs the
    paper's L1-level scratchpad (which would be nearer 100x).
  * MXU bf16 MAC: ~0.3 pJ/flop [Horowitz ISSCC'14 fp16 mult 0.34 pJ scaled].
  * VPU vector op: ~1.5 pJ/flop (general-purpose lane, higher control
    overhead — this is the "host CPU computes the activation" cost).

Latency protocol constants:
  * Kernel-launch / DMA-descriptor overhead: ~2 us per launch (typical
    accelerator dispatch cost; the paper's DMA additionally pays cache
    flush+invalidate which we model as ``DMA_FLUSH_S``).
  * Sidebar handshake: flag write + poll observe, VMEM-latency scale —
    tens of ns. We use 100 ns per handshake (two per flexible call:
    invoke + return), faithful to the paper's "quick communication
    invisible to the rest of the memory system".
"""

from __future__ import annotations

import dataclasses

# ----------------------------------------------------------------------------
# Roofline (task-spec) constants — per chip.
# ----------------------------------------------------------------------------
PEAK_FLOPS_BF16: float = 197e12          # FLOP/s
HBM_BYTES_PER_S: float = 819e9           # B/s
ICI_BYTES_PER_S_PER_LINK: float = 50e9   # B/s per link
ICI_LINKS_PER_CHIP: int = 3              # usable links on a torus slice
ICI_BYTES_PER_S_PER_CHIP: float = ICI_BYTES_PER_S_PER_LINK * ICI_LINKS_PER_CHIP
HBM_BYTES_PER_CHIP: int = 16 * 1024**3   # 16 GiB (v5e)
VMEM_BYTES_PER_CHIP: int = 128 * 1024**2 # 128 MiB VMEM
# What one pallas_call may hold in VMEM unless it asks for more: the
# Mosaic compiler's default scoped-VMEM limit on v5e, which refuses a
# kernel past it ("Scoped allocation ... exceeded scoped vmem limit").
SCOPED_VMEM_BYTES: int = 16 * 1024**2

# ----------------------------------------------------------------------------
# Energy model constants.
# ----------------------------------------------------------------------------
E_HBM_PER_BYTE: float = 31.2e-12     # J/B   (HBM2 ~3.9 pJ/bit)
E_SIDEBAR_PER_BYTE: float = 1.2e-12  # J/B   (VMEM-class banked SRAM)
E_MXU_PER_FLOP: float = 0.3e-12     # J/flop (systolic bf16 MAC)
E_VPU_PER_FLOP: float = 1.5e-12     # J/flop (general vector lane = "host")
E_STATIC_W: float = 75.0             # static+leakage power proxy (W/chip)

# ----------------------------------------------------------------------------
# Protocol latency constants.
# ----------------------------------------------------------------------------
KERNEL_LAUNCH_S: float = 2.0e-6      # per accelerator invocation (DMA descr.)
DMA_FLUSH_S: float = 3.0e-6          # cache flush + invalidate before DMA
                                     # (paper §5.3.1; zero for Sidebar mode)
SIDEBAR_HANDSHAKE_S: float = 20e-9   # flag write + poll observe (one way) —
                                     # L1-latency scale, paper §3
VPU_BYTES_PER_S: float = 22e12       # host<->sidebar streaming bandwidth
                                     # (VMEM-class banked SRAM, full rate —
                                     # "with prefetching reach cache level
                                     # latency", paper §5.2.2)

# VPU cost (vector-ops per element) of each flexible function. This encodes
# the paper's observation that softplus is far more expensive than relu.
FLEXIBLE_OP_COST: dict[str, float] = {
    "identity": 0.0,
    "heaviside": 1.0,
    "relu": 1.0,
    "leaky_relu": 2.0,
    "squared_relu": 2.0,
    "abs": 1.0,
    "elu": 8.0,
    "silu": 11.0,
    "sigmoid": 10.0,
    "tanh": 12.0,
    "gelu": 14.0,
    "softplus": 15.0,
    "softmax": 12.0,
    "rmsnorm": 6.0,
    "layernorm": 8.0,
    "exp_decay": 10.0,   # RWKV6 data-dependent decay exp(-exp(w))
    "router_topk": 16.0, # MoE router softmax + top-k select
    "max_pool": 1.0,
    "avg_pool": 1.0,
    "qk_rmsnorm": 6.0,
}
DEFAULT_FLEXIBLE_OP_COST: float = 8.0


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """A parameterizable chip model (defaults = TPU v5e-class target)."""

    peak_flops: float = PEAK_FLOPS_BF16
    hbm_bytes_per_s: float = HBM_BYTES_PER_S
    ici_bytes_per_s: float = ICI_BYTES_PER_S_PER_CHIP
    hbm_bytes: int = HBM_BYTES_PER_CHIP
    vmem_bytes: int = VMEM_BYTES_PER_CHIP
    e_hbm_per_byte: float = E_HBM_PER_BYTE
    e_sidebar_per_byte: float = E_SIDEBAR_PER_BYTE
    e_mxu_per_flop: float = E_MXU_PER_FLOP
    e_vpu_per_flop: float = E_VPU_PER_FLOP
    static_w: float = E_STATIC_W
    kernel_launch_s: float = KERNEL_LAUNCH_S
    dma_flush_s: float = DMA_FLUSH_S
    sidebar_handshake_s: float = SIDEBAR_HANDSHAKE_S
    vpu_bytes_per_s: float = VPU_BYTES_PER_S


V5E = ChipSpec()
