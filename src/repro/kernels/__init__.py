"""Pallas TPU kernels for the MP (Margin Propagation) hot spots.

Each kernel ships three layers:
  <name>.py  - pl.pallas_call + BlockSpec VMEM tiling (TPU target)
  ops.py     - jit'd public wrappers (padding, interpret-mode fallback, vjp)
  ref.py     - pure-jnp oracles the tests assert against

Kernels:
  mp_waterfill - row-wise reverse water-filling z = MP(L, gamma) by bisection
  mp_linear    - fused multiplierless MVM: y = mpabs(w+x) - mpabs(w-x)
  fir_mp       - in-filter MP FIR: sliding windows formed in VMEM (no HBM
                 window matrix), both MP states solved in one pass, optional
                 fused HWR+accumulate (the paper's s_p readout)
  fir_mp_bank  - multi-filter fir_mp: grid (batch_tile, filter) with the
                 filter axis innermost so one VMEM-resident signal block
                 serves a whole octave's filter set in a single pallas_call
  fir_mp_bank_q - the INTEGER twin of fir_mp_bank: the bit-true
                 fixed-point datapath (integer MP bisection,
                 shift/add/compare only) on the same grid
  fir_mp_stream / fir_mp_stream_q - one stateful session-step kernel:
                 grid (slot, chunk_block, filter) carrying each slot's FIR
                 delay line, per-band accumulators and running amax in
                 VMEM scratch across grid steps, with a float and an
                 integer datapath (the step()-shaped streaming hot path;
                 bit-identical to the XLA session step of either numerics)

The integer kernels are bit-for-bit equal to the fxp_* XLA kernels and
censused multiplier-free by benchmarks/hardware_cost.py.

Default block shapes come from the committed autotune table
(stream_shapes.json, refreshed by benchmarks/kernel_sweep.py).
"""

from repro.kernels.ops import (  # noqa: F401
    mp_waterfill,
    mp_linear,
    fir_mp,
    fir_mp_accumulate,
    fir_mp_bank,
    fir_mp_bank_accumulate,
    fir_mp_bank_q,
    fir_mp_bank_q_accumulate,
    fir_mp_stream,
    fir_mp_stream_q,
)
