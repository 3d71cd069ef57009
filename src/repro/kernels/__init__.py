"""Pallas TPU kernels for the paper's compute hot spots.

Each kernel ships three artifacts:
  <name>.py — pl.pallas_call + explicit BlockSpec VMEM tiling
  ops.py    — jit'd public wrappers (interpret=True on CPU hosts)
  ref.py    — pure-jnp oracles the tests assert against

Kernels:
  matmul_tiled     — f32-accumulator tiled matmul (general building block)
  lowrank          — FUSED (x R^T) L^T (paper Eq. 8): rank-K intermediate
                     lives in VMEM across both contractions; every factored
                     linear (training and serving) routes through it.
                     Training adds a sketch-saving single-launch backward
                     (dx, dL, dR with dh = dy L VMEM-resident)
  quant            — FUSED int8 variant for deployment: int8 L/R factors
                     stay VMEM-resident, per-channel scales fold into the
                     f32 accumulator, no dequantized weight materialized
  gram             — tall-skinny Y^T Y reduction (CholeskyQR stage of WSI/ASI)
  qr               — FUSED CholeskyQR: Gram -> in-kernel Cholesky/triangular
                     inverse -> apply, plus the Q^T Y mix matrix, one launch
                     (the WSI factored-refresh hot path)
  flash_attention  — causal/sliding-window grouped-query online-softmax
                     attention with its own backward (dq and dkv
                     kernels): the train step's attention core on TPU
  ssd_scan         — Mamba-2 SSD chunked scan with on-chip state carry

See docs/kernels.md for grid/BlockSpec conventions and the interpret-mode
(CPU) caveats.
"""

from repro.kernels.ops import (
    cholesky_qr_mix,
    choleskyqr_fused,
    dense_matmul_q8,
    flash_attention,
    gram,
    lowrank_bwd_fused,
    lowrank_matmul,
    lowrank_matmul_fused,
    lowrank_matmul_q8,
    lowrank_matmul_q8_fused,
    lowrank_matmul_unfused,
    matmul,
)
from repro.kernels.ssd_scan import ssd_scan_tiled
