"""Pallas TPU kernels for the paper's compute hot-spot: the distance scan.

<name>.py hold pl.pallas_call kernels with explicit BlockSpec VMEM tiling;
ops.py are the jit'd public wrappers (padding, tile selection, the
pallas-vs-jnp body knob); ref.py are the pure-jnp oracles every kernel is
tested against.  _backend.py says how they run: compiled on a TPU,
interpreted on the CPU (the correctness gate), refused anywhere else.

Design notes
============

**Layout.**  A PDX partition tile is ``(D, V)`` with vectors on the 128-wide
lane axis: the running distances array of the paper's Algorithm 1 is one
VMEM row per partition, there is no horizontal reduction, and a dimension
slice is exactly the contiguous stretch a ``BlockSpec((d_tile, V))`` DMA
fetches.  The batched kernels exploit that the same tile is already K-major
for the MXU — ``(B, d) @ (d, V)`` with no relayout (paper Section 7's
transposition cost, avoided by construction).

**Grid order.**  Accumulating kernels put the dimension tile innermost so
one output block stays resident in VMEM across all its d-tiles; the
megakernel ``pdx_prune_scan_multi_pallas`` adds the partition as the outer
grid axis, so one ``pallas_call`` covers the whole store and per-partition
state (accumulator + keep-mask) never round-trips to HBM.

**Pruning.**  The ADSampling hypothesis test is fused per d-tile: after each
``(d_tile, V)`` accumulation the keep-mask is re-evaluated in place, and a
``pl.when(any_alive)`` guard skips the *entire* remaining VPU work of a
partition once every lane is dead (the PRUNE phase at tile granularity).
In ``pdx_prune_scan_multi_pallas`` the HBM->VMEM fetch of later tiles still
streams under the automatic pipeline; the later cascade stages run
``pdx_prune_scan_multi_prefetch_pallas``, whose scalar-prefetched
(partition, d-tile) schedule and conditional manual DMA stop a partition's
fetches at the d-tile where its last lane dies.

**TPU tiling.**  A block's last two dims must be multiples of (8, 128) or
equal the array's.  Per-partition rows (ids in; dists, alive, streamed out)
therefore travel as ``(P, 1, V)`` with the partition axis squeezed, never as
``(1, V)`` blocks of a ``(P, V)`` array.

**Quantized mirrors.**  The scan is bandwidth-bound (paper Section 7), so
the executors stream reduced-precision device mirrors (bf16/int8, see
``repro.core.layout.device_mirror``) and dequantize **in-register**:
``x * scale_d + offset_d`` right after the VMEM load, accumulating in f32.
Each stored byte is touched exactly once, at mirror width; exactness is
restored by the planner's f32 re-rank against the master tiles.  PAD lanes
cannot be represented monotonically in int8, so every quantized kernel
seeds its keep-mask from ``ids >= 0`` instead of relying on the PAD_VALUE
sentinel.

**Masking contract.**  Kernels keep the alive mask as f32 internally (VPU
select-friendly, and bool outputs have no stable TPU layout story); the
``ops`` wrappers convert to bool at the boundary so callers never see the
representation.
"""
from .ops import (  # noqa: F401
    batched_distance_op,
    batched_distance_quant_op,
    nary_distance_op,
    pdx_distance_op,
    pdx_prune_scan_multi_op,
    pdx_prune_scan_op,
)
