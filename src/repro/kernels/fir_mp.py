"""Pallas kernels: in-filter MP FIR (paper eq. 8 + 9, Fig. 5).

y[b, n] = mpabs(h + x[b, n-M+1..n]) - mpabs(h - x[b, n-M+1..n])

TPU adaptation of the FPGA's register-bank streaming: instead of
materializing the (N, M) sliding-window matrix in HBM (M-fold read
amplification) the raw signal row lives in VMEM and the M tap-shifted views
are formed in-register with static slices (M is a small compile-time
constant, 16 in the paper), unrolled. Both MP bisection states advance
together as in mp_linear.

Optionally fuses the paper's entire in-filter readout
    s[b] = sum_n max(0, y[b, n])        (HWR + accumulate, Appendix A)
so one HBM read of the signal produces the scalar kernel feature directly —
the TPU analogue of the FPGA's per-band accumulator register.

Two grid layouts live here:

* one-shot (``fir_mp_pallas`` / ``fir_mp_bank_pallas``): grid over
  (batch_tile,) or (batch_tile, filter) — the whole signal row is resident
  per step; block holds (block_b, N) rows in VMEM (1 s @ 16 kHz f32 =
  64 KiB/row; block_b=8 -> 0.5 MiB). The bank kernel has an integer twin,
  ``fir_mp_bank_q_pallas``.
* streaming (``fir_mp_stream_octave``): grid (slot_tile, chunk_block,
  filter) — per-slot FIR delay lines, partial accumulators and running
  amax carried in VMEM scratch across the chunk_block axis. One kernel
  body serves both numerics; its datapath (``FloatPath`` or
  ``FixedPath``) holds what differs.

The integer kernels execute ``repro.core.fixed``'s bit-true fixed-point
datapath — integer bisection, shift/add/compare only — bit-for-bit equal
to the ``fxp_*`` XLA kernels on either carrier (int32, or f32-carried
integer codes).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fixed as fx
from repro.core import mp as mp_mod
from repro.core.filterbank import accumulate_block_len

DEFAULT_ITERS = 26


def _fir_mp_body(x, h_ref, gamma, *, iters: int, M: int):
    """x: (bb, N) raw signal rows — NO upstream left-padding is assumed;
    windows clamp at the left edge by zero-shifting (streaming from zeroed
    registers, as the FPGA does)."""
    bb, N = x.shape

    def shifted(k):
        # x[n-k] with zeros for n < k: shift right by k.
        if k == 0:
            return x
        return jnp.concatenate(
            [jnp.zeros((bb, k), x.dtype), x[:, : N - k]], axis=1)

    xs = [shifted(k) for k in range(M)]  # unrolled; M is static & small

    # per-n bisection bounds
    hi_u = xs[0] * 0.0 - jnp.inf
    hi_v = hi_u
    for k in range(M):
        hk = h_ref[0, k]
        hi_u = jnp.maximum(hi_u, jnp.abs(xs[k] + hk))
        hi_v = jnp.maximum(hi_v, jnp.abs(xs[k] - hk))
    lo_u, lo_v = hi_u - gamma, hi_v - gamma

    def body(_, state):
        lo_u, hi_u, lo_v, hi_v = state
        mid_u = (lo_u + hi_u) * 0.5
        mid_v = (lo_v + hi_v) * 0.5
        hu = jnp.zeros_like(mid_u)
        hv = jnp.zeros_like(mid_v)
        for k in range(M):
            hk = h_ref[0, k]
            u = xs[k] + hk
            v = xs[k] - hk
            hu = hu + jnp.maximum(u - mid_u, 0) + jnp.maximum(-u - mid_u, 0)
            hv = hv + jnp.maximum(v - mid_v, 0) + jnp.maximum(-v - mid_v, 0)
        tu = hu > gamma
        tv = hv > gamma
        lo_u = jnp.where(tu, mid_u, lo_u)
        hi_u = jnp.where(tu, hi_u, mid_u)
        lo_v = jnp.where(tv, mid_v, lo_v)
        hi_v = jnp.where(tv, hi_v, mid_v)
        return lo_u, hi_u, lo_v, hi_v

    lo_u, hi_u, lo_v, hi_v = jax.lax.fori_loop(
        0, iters, body, (lo_u, hi_u, lo_v, hi_v))
    return (lo_u + hi_u) * 0.5 - (lo_v + hi_v) * 0.5


def _fir_mp_kernel(gamma_ref, x_ref, h_ref, out_ref, *, iters, M, accumulate,
                   valid_n):
    y = _fir_mp_body(x_ref[...], h_ref, gamma_ref[0, 0], iters=iters, M=M)
    if accumulate:
        # mask the padded tail: positions >= valid_n see partial windows of
        # real data and would otherwise contribute spurious HWR terms.
        n_idx = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
        y = jnp.where(n_idx < valid_n, y, 0.0)
        out_ref[...] = jnp.sum(jnp.maximum(y, 0.0), axis=-1, keepdims=True)
    else:
        out_ref[...] = y


def fir_mp_bank_pallas(
    x: jax.Array,
    H: jax.Array,
    gamma: jax.Array,
    *,
    accumulate: bool = False,
    iters: int = DEFAULT_ITERS,
    block_b: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Multi-filter variant: x (B, N), H (F, M) -> (F, B, N) or (B, F).

    Grid covers (batch_tile, filter) with the filter axis innermost, so the
    (block_b, N) signal block's index map is constant across the F inner
    steps: Pallas keeps it VMEM-resident and only the (1, M) tap row is
    re-fetched per filter. The per-filter path re-reads the signal from HBM
    F times; here one read serves the whole octave.
    """
    B, N = x.shape
    F, M = H.shape
    b_pad = (-B) % block_b
    n_pad = (-N) % 128
    xp = jnp.pad(x, ((0, b_pad), (0, n_pad)))
    Bp, Np = xp.shape
    H = H.astype(x.dtype)
    gamma_arr = jnp.asarray(gamma, dtype=x.dtype).reshape(1, 1)

    if accumulate:
        out_spec = pl.BlockSpec((block_b, 1), lambda i, j: (i, j))
        out_shape = jax.ShapeDtypeStruct((Bp, F), x.dtype)
    else:
        out_spec = pl.BlockSpec((1, block_b, Np), lambda i, j: (j, i, 0))
        out_shape = jax.ShapeDtypeStruct((F, Bp, Np), x.dtype)

    out = pl.pallas_call(
        functools.partial(_fir_mp_bank_kernel, iters=iters, M=M,
                          accumulate=accumulate, valid_n=N),
        name="fir_mp_bank",
        grid=(Bp // block_b, F),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((block_b, Np), lambda i, j: (i, 0)),
            pl.BlockSpec((1, M), lambda i, j: (j, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(gamma_arr, xp, H)

    if accumulate:
        return out[:B, :]
    return out[:, :B, :N]


def _fir_mp_bank_kernel(gamma_ref, x_ref, h_ref, out_ref, *, iters, M,
                        accumulate, valid_n):
    y = _fir_mp_body(x_ref[...], h_ref, gamma_ref[0, 0], iters=iters, M=M)
    if accumulate:
        n_idx = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
        y = jnp.where(n_idx < valid_n, y, 0.0)
        out_ref[...] = jnp.sum(jnp.maximum(y, 0.0), axis=-1, keepdims=True)
    else:
        out_ref[...] = y[None]


# ---------------------------------------------------------------------------
# lane data movement shared by the streaming kernels
# ---------------------------------------------------------------------------
#
# Mosaic (the TPU's Pallas compiler) lowers static lane slices,
# concatenation, iota and selects, but no lane gather and no strided lane
# slice. The streaming kernels therefore build every FIR window, the ÷2
# decimator's windows and the per-slot delay-line slide from static lane
# shifts plus per-row selects. These move exactly the values the XLA
# session step gathers, so results stay bit-for-bit the XLA path's, and
# the integer kernel's datapath stays shift/add/compare only.


def _shl(x, s: int):
    """``out[:, j] = x[:, j + s]``, zero-filled past the end (static s)."""
    if s == 0:
        return x
    rows, w = x.shape
    if s >= w:
        return jnp.zeros_like(x)
    return jnp.concatenate([x[:, s:], jnp.zeros((rows, s), x.dtype)], axis=1)


def _lane_tree_sum(h, n: int):
    """``mp.tree_sum`` of lanes ``[0, n)`` of ``h`` (n a power of two) as a
    (rows, 1) column. Step t adds lane ``i + 2**t`` into lane ``i``, so lane
    0 accumulates exactly the pairwise halving tree of ``tree_sum``."""
    s = 1
    while s < n:
        h = h + _shl(h, s)
        s <<= 1
    return h[:, :1]


def _compact_even(x, n: int):
    """``out[:, i] = x[:, 2 * i]`` for ``i < n``: a log-depth compaction.

    Before stage b, element i sits at lane ``2i - (i mod 2**b)``; stage b
    moves it left by ``2**b`` when bit b of i is set. Positions stay
    strictly increasing, so no two elements ever meet, and whether the
    element at lane p exists and moves is a function of p alone."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    b = 0
    while (1 << b) < n:
        s = 1 << b
        src = lane + s
        moves = (jnp.bitwise_and(src, 2 * s - 1) < s) & (
            jnp.bitwise_and(jnp.right_shift(src, b + 1), 1) == 1)
        x = jnp.where(moves, _shl(x, s), x)
        b += 1
    return x


def _slide(x, v, vmax: int):
    """Per-row left shift ``out[r, j] = x[r, j + v[r]]`` for ``v`` (rows, 1)
    in ``[0, vmax]``: a barrel shifter of static shifts and selects."""
    b = 0
    while (1 << b) <= vmax:
        bit = jnp.bitwise_and(jnp.right_shift(v, b), 1) == 1
        x = jnp.where(bit, _shl(x, 1 << b), x)
        b += 1
    return x


def _splice(delay, blk, M: int):
    """An M-tap FIR's input over a block: the delay line's last M - 1
    samples, then the block."""
    return jnp.concatenate([delay[:, delay.shape[1] - (M - 1):], blk],
                           axis=1)


def _fir_windows(buf, M: int, n: int) -> list:
    """FIR windows as M lane slices: ``w[k][:, j] = buf[:, j + k]``."""
    return [buf[:, k:k + n] for k in range(M)]


def _decim_windows(buf, start, M: int, n: int) -> list:
    """÷2 decimator windows: ``w[m][:, j] = buf[:, start + 2j + m]`` for
    ``j < n``, with the per-row phase ``start`` (rows, 1) in {0, 1}.

    The phase is a select between the buffer and its 1-lane shift; the
    stride-2 pick becomes two compactions (even and odd lanes), after which
    tap m is a static slice of one of them."""
    x = jnp.where(start == 1, _shl(buf, 1), buf)
    need = n + (M - 1) // 2
    even = _compact_even(x, need)
    odd = _compact_even(_shl(x, 1), need)
    return [(odd if m & 1 else even)[:, m >> 1:(m >> 1) + n]
            for m in range(M)]


def _slide_delay(delay, blk, v, T1: int, LB: int):
    """The delay line after a block: the last T1 of ``[delay, blk[:v]]``,
    i.e. ``[delay, blk][v:v + T1]`` per row (v = 0 keeps it bit-identical)."""
    return _slide(jnp.concatenate([delay, blk], axis=1), v, LB)[:, :T1]


def _add_column(part, f, s):
    """``part[:, f] += s`` for a dynamic column f: a lane-masked select, so
    every other column keeps its bits."""
    col = jax.lax.broadcasted_iota(jnp.int32, part.shape, 1) == f
    return jnp.where(col, part + s, part)


def fir_mp_pallas(
    x: jax.Array,
    h: jax.Array,
    gamma: jax.Array,
    *,
    accumulate: bool = False,
    iters: int = DEFAULT_ITERS,
    block_b: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """x: (B, N) signal, h: (M,) taps -> y: (B, N), or s: (B,) if accumulate.

    The kernel pairs x-shift k with tap h(k) directly, implementing eq. 8's
    sum_k h(k) x(n-k) operand multiset without reordering the taps.
    """
    B, N = x.shape
    (M,) = h.shape
    b_pad = (-B) % block_b
    n_pad = (-N) % 128
    xp = jnp.pad(x, ((0, b_pad), (0, n_pad)))
    Bp, Np = xp.shape
    h_row = h.reshape(1, M).astype(x.dtype)
    gamma_arr = jnp.asarray(gamma, dtype=x.dtype).reshape(1, 1)

    if accumulate:
        out_spec = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((Bp, 1), x.dtype)
    else:
        out_spec = pl.BlockSpec((block_b, Np), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((Bp, Np), x.dtype)

    out = pl.pallas_call(
        functools.partial(_fir_mp_kernel, iters=iters, M=M,
                          accumulate=accumulate, valid_n=N),
        name="fir_mp",
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block_b, Np), lambda i: (i, 0)),
            pl.BlockSpec((1, M), lambda i: (0, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(gamma_arr, xp, h_row)

    if accumulate:
        return out[:B, 0]
    return out[:B, :N]


# ---------------------------------------------------------------------------
# integer (fixed-point) solves: the bit-true hardware twin, VMEM-resident
# ---------------------------------------------------------------------------
#
# The bank kernel below and the streaming kernel's integer datapath
# (``FixedPath``) run repro.core.fixed's datapath INSIDE the pallas_call:
# integer bisection (arithmetic-shift midpoints, exact integer constraint
# sums), saturating clamps onto static spec bounds, and integer HWR
# accumulation. They are carrier-generic like every fxp_* kernel: on int32
# they are the hardware path (benchmarks/hardware_cost.py censuses the
# Pallas-lowered jaxpr to zero multiplies/divides); on f32-carried integer
# codes they are the fake-quant twin, bit-identical below 2**24.
#
# Parity with the XLA fxp_* kernels is by construction: every output value
# is one LSB-deterministic bisection over the SAME operand multiset
# {h_k +- x(n-k)} (integer max and adds are order-independent), so the
# Pallas and XLA paths agree bit-for-bit — no blocked-reduction ordering
# machinery needed (the float kernels' tree_sum/accumulate_block_len dance
# exists only because float addition is not associative).


def _fxp_mpabs_ops(ops, gamma_q, iters: int, ref=None):
    """fixed.fxp_mpabs over an unrolled operand list (each (bb, N)): the
    per-position integer bisection, shift/add/compare only. Given ``ref``,
    a (K, bb, N) VMEM ref that holds ``ops``, the loop body loads them from
    it, aligned, instead of carrying the values into the loop."""
    g = fx._c(gamma_q, ops[0])
    hi = jnp.abs(ops[0])
    for t in ops[1:]:
        hi = jnp.maximum(hi, jnp.abs(t))
    lo = hi - g

    def body(_, state):
        lo, hi = state
        mid = fx.shift_right(lo + hi, 1)
        neg_mid = -mid                     # -t - mid == neg_mid - t, exactly
        loaded = None if ref is None else ref[...]
        h = jnp.zeros_like(mid)
        for k in range(len(ops)):
            t = ops[k] if ref is None else loaded[k]
            h = h + fx._relu(t - mid) + fx._relu(neg_mid - t)
        too_low = h > g
        lo = jnp.where(too_low, mid, lo)
        hi = jnp.where(too_low, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return hi


def _fxp_fir_mp_body(x, h_ref, *, gamma_q, iters, qmin, qmax, M):
    """Integer twin of ``_fir_mp_body``: x (bb, N) signal codes (already on
    the stage's internal grid), h_ref (1, M) tap codes. Pairs x-shift k with
    tap h(k), forming the same operand multiset as ``fixed.fxp_fir_bank``'s
    reversed-tap windows; operand sums saturate onto [qmin, qmax] (the
    10-bit internal path) before the solve, exactly like ``fxp_mp_dot``."""
    bb, N = x.shape

    def shifted(k):
        if k == 0:
            return x
        return jnp.concatenate(
            [jnp.zeros((bb, k), x.dtype), x[:, : N - k]], axis=1)

    us, vs = [], []
    for k in range(M):
        hk = h_ref[0, k]
        xk = shifted(k)
        us.append(jnp.clip(hk + xk, qmin, qmax))
        vs.append(jnp.clip(hk - xk, qmin, qmax))
    return (_fxp_mpabs_ops(us, gamma_q, iters)
            - _fxp_mpabs_ops(vs, gamma_q, iters))


def _fir_mp_bank_q_kernel(x_ref, h_ref, out_ref, *, gamma_q, iters, qmin,
                          qmax, M, accumulate, valid_n):
    y = _fxp_fir_mp_body(x_ref[...], h_ref, gamma_q=gamma_q, iters=iters,
                         qmin=qmin, qmax=qmax, M=M)
    if accumulate:
        # integer HWR + accumulate: mask the padded tail (positions >=
        # valid_n see partial windows of real data), then a plain sum —
        # integer adds are associative, any order reproduces the XLA bits
        n_idx = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
        y = jnp.where(n_idx < valid_n, fx._relu(y), 0)
        out_ref[...] = jnp.sum(y, axis=-1, keepdims=True)
    else:
        out_ref[...] = y[None]


def fir_mp_bank_q_pallas(
    xq: jax.Array,
    H_q: jax.Array,
    *,
    gamma_q: int,
    iters: int,
    qmin: int,
    qmax: int,
    accumulate: bool = False,
    block_b: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """One-shot integer bank kernel: xq (B, N) signal codes already on the
    stage's internal grid, H_q (F, M) tap codes -> (F, B, N) band codes, or
    (B, F) integer HWR sums (at the stage grid — the caller applies
    ``acc_shift``).

    Same grid as the float ``fir_mp_bank_pallas``: (batch_tile, filter)
    with filter INNERMOST, so the (block_b, N) signal block stays
    VMEM-resident across the whole octave's filter set and only the (1, M)
    tap row re-fetches per filter. ``gamma_q``/``iters``/``qmin``/``qmax``
    are STATIC program constants (ROM contents), not kernel operands.
    Output positions match ``fixed.fxp_fir_bank(pad=True)`` bit-for-bit.
    """
    B, N = xq.shape
    F, M = H_q.shape
    b_pad = (-B) % block_b
    n_pad = (-N) % 128
    xp = jnp.pad(xq, ((0, b_pad), (0, n_pad)))
    Bp, Np = xp.shape
    H_q = H_q.astype(xq.dtype)

    if accumulate:
        out_spec = pl.BlockSpec((block_b, 1), lambda i, j: (i, j))
        out_shape = jax.ShapeDtypeStruct((Bp, F), xq.dtype)
    else:
        out_spec = pl.BlockSpec((1, block_b, Np), lambda i, j: (j, i, 0))
        out_shape = jax.ShapeDtypeStruct((F, Bp, Np), xq.dtype)

    out = pl.pallas_call(
        functools.partial(_fir_mp_bank_q_kernel, gamma_q=int(gamma_q),
                          iters=int(iters), qmin=int(qmin), qmax=int(qmax),
                          M=M, accumulate=accumulate, valid_n=N),
        name="fir_mp_bank_q",
        grid=(Bp // block_b, F),
        in_specs=[
            pl.BlockSpec((block_b, Np), lambda i, j: (i, 0)),
            pl.BlockSpec((1, M), lambda i, j: (j, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(xp, H_q)

    if accumulate:
        return out[:B, :]
    return out[:, :B, :N]


def _fxp_mp_dot_ops(xs, ws, *, gamma_q, iters, spec, into=None):
    """``fixed.fxp_mp_dot`` over unrolled operands (``xs[k]`` the k-th
    window column, ``ws[k]`` its tap): operand sums saturate onto ``spec``,
    then the two integer bisections. Integer max and adds are
    order-free, so this is bit-for-bit the array form.

    ``into`` = (u_ref, v_ref), two (K, bb, N) VMEM refs: the saturated
    operands are stored there and each bisection loop loads its own, so
    no operand is carried (and realigned) through a loop."""
    us = [jnp.clip(w + x, spec.qmin, spec.qmax) for x, w in zip(xs, ws)]
    vs = [jnp.clip(w - x, spec.qmin, spec.qmax) for x, w in zip(xs, ws)]
    u_ref = v_ref = None
    if into is not None:
        u_ref, v_ref = into
        u_ref[...] = jnp.stack(us)
        v_ref[...] = jnp.stack(vs)
    return (_fxp_mpabs_ops(us, gamma_q, iters, u_ref)
            - _fxp_mpabs_ops(vs, gamma_q, iters, v_ref))


# ---------------------------------------------------------------------------
# fir_mp_stream: the stateful session-step kernel, one body, two datapaths
# ---------------------------------------------------------------------------
#
# A grid step does the same in both numerics: the slot state machine, the
# valid mask and per-filter column add, the ÷2 decimator windows, the
# delay-line slide and the flush. What differs by numerics is the
# datapath, one object per octave: its SMEM operands (``roms``), extra
# scratch, the band solve and its column sum, the LP/÷2 emit, the flush
# scale and the kernel's name.


class FloatPath(NamedTuple):
    """The float32 datapath: ``mp.mp_dot_fast_terms`` (the tap-unrolled
    ``_mp_dot_fast``) on in-register window slices, and each band's HWR sum
    by ``tree_sum``'s lane tree.

    Bit-parity with the XLA session step is by construction: the same
    solver runs on the same window values, and the HWR sums use the shared
    ``accumulate_block_len`` blocking with ``tree_sum``'s tree, added in
    ascending block order exactly like ``filterbank.hwr_accumulate``.

    SMEM holds gamma, the (F, M) band-pass taps ``bp`` and the (M_lp,)
    anti-aliasing taps ``lp`` (None on the last octave). The flush scales
    the partial sums by ``scale`` (the octave's ``2**o`` renorm)."""
    bp: jax.Array
    lp: jax.Array | None
    gamma: jax.Array
    solver: str = "newton"
    scale: float = 1.0

    name = "fir_mp_stream"

    def roms(self, dt):
        lp = jnp.zeros((1,), dt) if self.lp is None else self.lp
        return [jnp.asarray(self.gamma, dtype=dt).reshape(1),
                self.bp.astype(dt), lp.astype(dt)]

    def scratch(self, M, bs, LB, dt):
        return []

    def band(self, h_ref, scratch, delay, blk, f, gamma):
        M = h_ref.shape[1]
        bufv = _splice(delay, blk, M)
        taps = [h_ref[f, M - 1 - k] for k in range(M)]   # conv tap order
        return mp_mod.mp_dot_fast_terms(_fir_windows(bufv, M, blk.shape[1]),
                                        taps, gamma, self.solver)

    def colsum(self, hwr):
        return _lane_tree_sum(hwr, hwr.shape[1])

    def emit(self, lp_ref, winl, gamma):
        M_lp = lp_ref.shape[0]
        lp = [lp_ref[M_lp - 1 - k] for k in range(M_lp)]
        return mp_mod.mp_dot_fast_terms(winl, lp, gamma, self.solver)

    def flush(self, part):
        return part * self.scale


class FixedPath(NamedTuple):
    """The integer datapath: ``repro.core.fixed``'s bit-true fixed-point
    solve, shift/add/compare only, on either carrier (int32, or f32-carried
    integer codes):

    * window codes rescale onto the band grid by ``stage.sig_shift`` (a
      static shift), once a block, into scratch that the block's filter
      steps share; operand sums clamp onto the 10-bit internal specs, and
      each position solves by integer bisection (``fixed.fxp_mp_dot``) —
      LSB-deterministic, so a band's HWR sum is a plain sum;
    * the decimator tail emits NEXT-OCTAVE register codes directly:
      ``clamp(rescale(kept, lp_out_shift))`` onto ``next_spec`` happens
      in-kernel, so y_next needs no post-processing;
    * the flush applies ``stage.acc_shift`` as a left shift (the int mirror
      of the float ``2**octave`` renorm — shifts distribute over the partial
      sums, so flush-time shifting equals the XLA session step's per-chunk
      shift bit-for-bit).

    ``stage`` is the compiled :class:`repro.core.fixed.OctaveStage`: its
    gammas, iteration counts, shifts and clamp bounds are static ROM
    constants, never kernel operands; SMEM holds only its tap ROMs.
    ``next_spec`` is the next octave's register spec (None on the last)."""
    stage: fx.OctaveStage
    next_spec: fx.FixedPointSpec | None = None

    name = "fir_mp_stream_q"

    def roms(self, dt):
        lp = (jnp.zeros((1,), dt) if self.next_spec is None
              else self.stage.lp_q[0].astype(dt))
        return [self.stage.bp_q.astype(dt), lp]

    def scratch(self, M, bs, LB, dt):
        # the tap windows, carried across the block's filter steps, and
        # the band-pass operands h + x and h - x
        return [pltpu.VMEM((M, bs, LB), dt)] * 3

    def band(self, h_ref, scratch, delay, blk, f):
        # The M tap windows are unaligned lane slices of [delay tail, blk]
        # and do not depend on the filter: build them once a block into
        # scratch, lane-aligned. The solve reads them aligned and stores
        # its operands for the loops to load, so no lane rotate runs inside
        # either loop.
        st = self.stage
        win_s, u_s, v_s = scratch
        M, LB = h_ref.shape[1], blk.shape[1]

        @pl.when(f == 0)
        def _windows():
            bufv = _splice(delay, blk, M)
            win_s[...] = jnp.stack([fx.rescale(w, st.sig_shift)
                                    for w in _fir_windows(bufv, M, LB)])

        win = win_s[...]
        taps = [h_ref[f, M - 1 - k] for k in range(M)]   # conv tap order
        return _fxp_mp_dot_ops([win[k] for k in range(M)], taps,
                               gamma_q=st.gamma_bp, iters=st.iters_bp,
                               spec=st.band_spec, into=(u_s, v_s))

    def colsum(self, hwr):
        return jnp.sum(hwr, axis=-1, keepdims=True)

    def emit(self, lp_ref, winl):
        st = self.stage
        winl = [fx.rescale(w, st.lp_sig_shift) for w in winl]
        M_lp = lp_ref.shape[0]
        lp = [lp_ref[M_lp - 1 - k] for k in range(M_lp)]
        kept = _fxp_mp_dot_ops(winl, lp, gamma_q=st.gamma_lp,
                               iters=st.iters_lp, spec=st.lp_spec)
        return jnp.clip(fx.rescale(kept, st.lp_out_shift),
                        int(self.next_spec.qmin), int(self.next_spec.qmax))

    def flush(self, part):
        return fx.shift_left(part, self.stage.acc_shift)


def _fir_mp_stream_kernel(*refs, path, n_roms, emit_next, update_amax):
    """One grid step of the streaming octave kernel.

    Grid is (slot_block, chunk_block, filter) with filter INNERMOST: the
    (bs, LB) signal block's index map is constant across the F filter steps,
    so Pallas keeps it VMEM-resident while the kernel reads filter f's taps
    from the whole (F, M) tap ROM in SMEM. The slot state — FIR delay line,
    per-band partial accumulators, running amax — lives in VMEM scratch and
    is carried across the chunk_block axis: the chunk streams through VMEM
    block by block with NO per-block HBM state round-trip; state is read
    once at grid start and written once at the final step.

    ``refs``: the ``n_roms`` SMEM operands (scalars, then the band-pass and
    LP tap ROMs), the six input blocks, the three or four outputs, then the
    scratch (delay line, partial sums, running amax, then ``path``'s own).
    """
    roms, refs = refs[:n_roms], refs[n_roms:]
    *scalar_refs, h_ref, lp_ref = roms
    x_ref, n_ref, start_ref, delay_ref, acc_ref, amax_ref = refs[:6]
    out_acc_ref, out_delay_ref, out_amax_ref = refs[6:9]
    out_next_ref = refs[9] if emit_next else None
    delay_s, part_s, amax_s, *scratch = refs[9 + emit_next:]

    b = pl.program_id(1)
    f = pl.program_id(2)
    NB = pl.num_programs(1)
    F = pl.num_programs(2)

    @pl.when((b == 0) & (f == 0))
    def _init():
        delay_s[...] = delay_ref[...]
        part_s[...] = jnp.zeros_like(part_s)
        amax_s[...] = amax_ref[...]

    blk = x_ref[...]                              # (bs, LB)
    nv = n_ref[...]                               # (bs, 1) valid counts
    scalars = [r[0] for r in scalar_refs]         # gamma, in float
    delay = delay_s[...]                          # (bs, T1)
    T1, LB = delay.shape[1], blk.shape[1]

    if update_amax:
        # running amax: invalid tails were zeroed upstream, and the padded
        # tail block is zeros, so blockwise max == whole-row max (max is
        # exactly associative; all operands >= 0).
        @pl.when(f == 0)
        def _amax():
            amax_s[...] = jnp.maximum(
                amax_s[...],
                jnp.max(jnp.abs(blk), axis=-1, keepdims=True))

    # --- band-pass filter f over this block -------------------------------
    y = path.band(h_ref, scratch, delay, blk, f, *scalars)
    pos = b * LB + jax.lax.broadcasted_iota(jnp.int32, (1, LB), 1)
    # a Python zero of y's kind: an int 0 against float y would lower to
    # an int constant and an in-kernel convert
    zero = 0.0 if jnp.issubdtype(y.dtype, jnp.floating) else 0
    hwr = jnp.where(pos < nv, jnp.maximum(y, zero), zero)
    part_s[...] = _add_column(part_s[...], f, path.colsum(hwr))

    @pl.when(f == F - 1)
    def _block_tail():
        # LP + ÷2 decimation for the next octave: solve ONLY the kept
        # positions. LB is even, so each slot's keep-parity (its decimator
        # phase) is constant across blocks; kept j of block b lands at
        # out position b*LB/2 + j.
        if emit_next:
            M_lp = lp_ref.shape[0]
            winl = _decim_windows(_splice(delay, blk, M_lp), start_ref[...],
                                  M_lp, LB // 2)
            out_next_ref[...] = path.emit(lp_ref, winl, *scalars)
        # slide the delay line by this block's VALID sample count; a
        # zero-valid (masked/inert) slot slides by 0 and keeps its
        # registers bit-identical.
        v = jnp.clip(nv - b * LB, 0, LB)
        delay_s[...] = _slide_delay(delay, blk, v, T1, LB)

    @pl.when((b == NB - 1) & (f == F - 1))
    def _flush():
        out_acc_ref[...] = acc_ref[...] + path.flush(part_s[...])
        out_delay_ref[...] = delay_s[...]
        out_amax_ref[...] = amax_s[...]


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)

# scratch is carried across grid steps -> every axis must iterate
# sequentially on TPU (no parallel partitioning of the grid)
_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary"))


def fir_mp_stream_octave(
    x: jax.Array,
    n: jax.Array,
    start: jax.Array,
    delay: jax.Array,
    acc: jax.Array,
    amax: jax.Array,
    path: FloatPath | FixedPath,
    *,
    emit_next: bool = True,
    update_amax: bool = False,
    block_s: int = 8,
    interpret: bool = False,
    octave: int = 0,
):
    """One octave of the stateful streaming step, as a single pallas_call
    named ``<path.name>_o<octave>``: ``fir_mp_stream_o<octave>`` on the
    float datapath, ``fir_mp_stream_q_o<octave>`` on the integer one.

    x (S, L): this octave's chunk, samples or register codes (invalid tails
    already zeroed upstream); n (S,): per-slot valid counts; start (S,):
    per-slot decimator phase (``consumed & 1``); delay (S, T1): FIR delay
    line registers; acc (S, F): this octave's accumulator columns; amax
    (S,): running max |x| (updated in-kernel only when ``update_amax``).
    ``path`` is the octave's datapath; with ``emit_next`` it has LP taps.

    Returns ``(acc', delay', amax', y_next | None)`` where ``y_next`` is
    (S, ceil(L/LB) * LB//2) — slice to ``(L+1)//2`` for the next octave.
    """
    S, L = x.shape
    T1 = delay.shape[1]
    LB = accumulate_block_len(L)
    NB = -(-L // LB)
    bs = min(block_s, S)
    s_pad = (-S) % bs
    Sp = S + s_pad
    dt = x.dtype
    roms = path.roms(dt)
    F, M = roms[-2].shape

    xp = jnp.pad(x, ((0, s_pad), (0, NB * LB - L)))
    pad1 = lambda a: jnp.pad(a, ((0, s_pad),))
    n2 = pad1(n.astype(jnp.int32))[:, None]
    start2 = pad1(start.astype(jnp.int32))[:, None]
    delay_p = jnp.pad(delay, ((0, s_pad), (0, 0)))
    acc_p = jnp.pad(acc, ((0, s_pad), (0, 0)))
    amax2 = pad1(amax.astype(dt))[:, None]

    # slot-block rows, the signal block per chunk block, whole-row state
    row = lambda w: pl.BlockSpec((bs, w), lambda i, b, f: (i, 0))
    in_specs = [
        pl.BlockSpec((bs, LB), lambda i, b, f: (i, b)),   # signal
        row(1),                                           # valid counts
        row(1),                                           # decim phase
        row(T1),                                          # delay line
        row(F),                                           # accumulators
        row(1),                                           # running amax
    ]
    out_specs = [row(F), row(T1), row(1)]
    out_shape = [
        jax.ShapeDtypeStruct((Sp, F), dt),             # acc'
        jax.ShapeDtypeStruct((Sp, T1), dt),            # delay'
        jax.ShapeDtypeStruct((Sp, 1), dt),             # amax'
    ]
    if emit_next:
        out_specs.append(pl.BlockSpec((bs, LB // 2), lambda i, b, f: (i, b)))
        out_shape.append(jax.ShapeDtypeStruct((Sp, NB * (LB // 2)), dt))

    outs = pl.pallas_call(
        functools.partial(_fir_mp_stream_kernel, path=path, n_roms=len(roms),
                          emit_next=emit_next, update_amax=update_amax),
        name=f"{path.name}_o{octave}",
        grid=(Sp // bs, NB, F),
        in_specs=[_SMEM] * len(roms) + in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bs, T1), dt),    # delay line, carried across blocks
            pltpu.VMEM((bs, F), dt),     # per-band partial accumulators
            pltpu.VMEM((bs, 1), dt),     # running amax
        ] + path.scratch(M, bs, LB, dt),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(*roms, xp, n2, start2, delay_p, acc_p, amax2)

    acc_o = outs[0][:S]
    delay_o = outs[1][:S]
    amax_o = outs[2][:S, 0]
    y_next = outs[3][:S] if emit_next else None
    return acc_o, delay_o, amax_o, y_next
