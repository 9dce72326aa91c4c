"""Bit-true fixed-point "hardware twin" of the in-filter pipeline.

The paper's headline (§III-A, §V, Tables I/II) is that the whole in-filter
kernel machine runs MULTIPLIERLESS: 8-bit fixed-point signals/weights, a
10-bit internal path, and a datapath built from adders, shifters and
comparators only. ``repro.core.quant`` simulates that with float tensors
carrying quantized values (the QAT proxy); this module EXECUTES it: every
stage — signal quantization, the multirate MP FIR bank, HWR + accumulate,
standardization, and the MP kernel-machine readout — runs on int32 arrays
using only add/subtract/compare/shift, the paper's primitive set.

Design rules that make the integer path provably equal to a float
simulation of the same datapath (the parity contract tested in
tests/test_fixed.py and pinned by the int golden fixtures):

* Every format is a :class:`repro.core.quant.FixedPointSpec` — a POWER-OF-
  TWO scale — so converting between formats is a bit shift: left shifts are
  exact, right shifts are floor rounding, identically in int32 and in a
  float carrier (``floor(ldexp(q, -k))``).
* The MP solve is integer bisection (:func:`fxp_mp_bisect`): halving is an
  arithmetic right shift, the constraint sum is an exact integer sum, and
  the result is the smallest grid point z with ``sum [L - z]_+ <= gamma`` —
  a deterministic LSB-exact answer, not an approximation to tolerance.
* Integer addition is associative, so HWR accumulation needs none of the
  fixed-tree ordering machinery the float path carries
  (``filterbank.hwr_accumulate``): any reduction order gives the same bits.

Carriers: all ``fxp_*`` kernels are dtype-generic. Called on int32 they run
the real integer datapath (what ``benchmarks/hardware_cost.py`` censuses);
called on float32 arrays carrying integer values they run the fake-quant
float twin, and the two agree BIT-FOR-BIT as long as magnitudes stay below
2**24 (f32's exact-integer range; the esc10-mp accumulators peak around
2**23 at 1 s of audio).

The deployment preview is driven through ``FilterBankConfig``:
``numerics="fixed"`` routes ``InFilterPipeline.apply``/``predict`` and
``FilterBank.accumulate`` through :func:`compile_pipeline` /
:func:`compile_bank` programs (static int32 taps, ROMs and shift tables
derived from the float pipeline plus a calibrated ADC full-scale
``fixed_amax``). Session streaming runs the same program chunk-by-chunk
through :func:`session_step_q` — every ``SessionState`` register carried as
an integer in the fixed-point grid, with chunked decisions bit-for-bit
equal to one-shot :func:`infer_q` (docs/numerics.md has the argument).
With ``stream_impl="pallas"`` the identical step runs through the
VMEM-resident integer kernel (``repro.kernels.fir_mp_stream_q``) —
bit-for-bit the same registers and decisions.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import FixedPointSpec, pow2_spec_for

__all__ = [
    "FixedBankProgram",
    "FixedClassifier",
    "FixedPointProgram",
    "OctaveStage",
    "calibrate_octave_gains",
    "compile_bank",
    "compile_pipeline",
    "fxp_fir_bank",
    "fxp_fir_shift_add",
    "fxp_hwr_accumulate",
    "fxp_mp_bisect",
    "fxp_mp_dot",
    "fxp_mpabs",
    "bank_accumulate_q",
    "cascade_q",
    "standardize_q",
    "classifier_q",
    "infer_q",
    "quantize_signal",
    "predict",
    "readout_q",
    "session_step_q",
    "shift_left",
    "shift_right",
    "rescale",
]


# ---------------------------------------------------------------------------
# carrier-generic shift/add/compare primitives
# ---------------------------------------------------------------------------


def _floatp(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


def _c(a, like):
    """Coerce a program constant onto the carrier dtype of ``like``."""
    a = jnp.asarray(a)
    return a.astype(jnp.float32) if _floatp(like) else a.astype(jnp.int32)


def shift_right(q, k):
    """Arithmetic (floor) shift right by ``k`` >= 0 (static int or int
    array — shift amounts are always integers, never carrier values).

    Int carrier: ``q >> k``. Float carrier: ``floor(ldexp(q, -k))`` — ldexp
    scales by an exact power of two and floor matches the arithmetic
    shift's round-toward-minus-infinity on negatives.
    """
    if _floatp(q):
        return jnp.floor(jnp.ldexp(q, -jnp.asarray(k, jnp.int32)))
    return jnp.right_shift(q, k)


def shift_left(q, k):
    """Shift left by ``k`` >= 0 (exact in both carriers)."""
    if _floatp(q):
        return jnp.ldexp(q, jnp.asarray(k, jnp.int32))
    return jnp.left_shift(q, k)


def rescale(q, k):
    """Multiply a q-array by 2**k: left shift for k >= 0, floor right shift
    for k < 0 — the format-conversion primitive (pow2 scales only)."""
    if isinstance(k, (int, np.integer)):
        k = int(k)
        return shift_left(q, k) if k >= 0 else shift_right(q, -k)
    k = jnp.asarray(k)
    return jnp.where(k >= 0, shift_left(q, jnp.maximum(k, 0)),
                     shift_right(q, jnp.maximum(-k, 0)))


def _clamp(q, spec: FixedPointSpec):
    """Saturating clamp onto a spec's representable range (compare/select)."""
    return jnp.clip(q, spec.qmin, spec.qmax)


def _relu(q):
    return jnp.maximum(q, 0)


# ---------------------------------------------------------------------------
# integer MP solve (bisection: add/compare/shift only)
# ---------------------------------------------------------------------------


def bisect_iters(gamma_q: int) -> int:
    """Iterations until the integer bisection interval collapses to one LSB:
    the initial width is gamma_q, halving each step."""
    return max(2, int(gamma_q).bit_length() + 2)


def fxp_mp_bisect(L, gamma_q, iters: int):
    """z = MP(L, gamma) on the fixed-point grid, along the last axis.

    Identical structure to :func:`repro.core.mp.mp_bisect`, but the midpoint
    is an arithmetic right shift (floor) and the constraint sum is an exact
    integer sum, so the loop is LSB-deterministic. Returns the smallest grid
    point ``z`` reached with ``sum_i [L_i - z]_+ <= gamma_q`` — within one
    LSB above the real-valued root.
    """
    gamma_q = _c(gamma_q, L)
    hi = jnp.max(L, axis=-1)
    lo = hi - gamma_q

    def body(_, state):
        lo, hi = state
        mid = shift_right(lo + hi, 1)
        h = jnp.sum(_relu(L - mid[..., None]), axis=-1)
        too_low = h > gamma_q
        lo = jnp.where(too_low, mid, lo)
        hi = jnp.where(too_low, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return hi


def fxp_mpabs(u, gamma_q, iters: int):
    """MP([u; -u], gamma) without materializing the concatenation (the
    eq. 9 operand form): the constraint splits into the u branch plus the
    -u branch. |u| = max(u, -u) is a compare/select, an allowed primitive."""
    gamma_q = _c(gamma_q, u)
    a = jnp.abs(u)
    hi = jnp.max(a, axis=-1)
    lo = hi - gamma_q

    def body(_, state):
        lo, hi = state
        mid = shift_right(lo + hi, 1)
        h = (jnp.sum(_relu(u - mid[..., None]), axis=-1)
             + jnp.sum(_relu(-u - mid[..., None]), axis=-1))
        too_low = h > gamma_q
        lo = jnp.where(too_low, mid, lo)
        hi = jnp.where(too_low, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return hi


def fxp_mp_dot(win, w, gamma_q, iters: int, spec: FixedPointSpec):
    """Multiplierless inner product (eq. 9) on the fixed-point grid:
    <w, win> ~= mpabs(w + win) - mpabs(w - win). Operand sums saturate onto
    ``spec`` (the 10-bit internal path) before the solve."""
    u = _clamp(w + win, spec)
    v = _clamp(w - win, spec)
    return fxp_mpabs(u, gamma_q, iters) - fxp_mpabs(v, gamma_q, iters)


# ---------------------------------------------------------------------------
# integer FIR primitives
# ---------------------------------------------------------------------------


def fxp_fir_bank(x, H, gamma_q, iters: int, spec: FixedPointSpec,
                 chunk_n: Optional[int] = 1024, pad: bool = True):
    """Multi-filter MP FIR on the integer grid: x (..., N), H (F, M) ->
    (..., F, N). Causal zero-padded form (matches the one-shot float path's
    ``mp_conv1d_bank(pad=True)`` window contents); long signals solve in
    ``chunk_n``-position blocks exactly like the float bank.

    ``pad=False`` computes ONLY the fully-covered positions — output p's
    window is ``x[p .. p+M-1]``, shape (..., F, N-M+1). The integer session
    step splices its delay-line registers in front of the chunk and uses
    this form; every window solve is an independent LSB-deterministic
    bisection, so shared positions match the padded form bit-for-bit."""
    H = _c(H, x)
    F, M = H.shape
    lead = x.shape[:-1]
    N = x.shape[-1] if pad else x.shape[-1] - M + 1
    x2 = x.reshape(-1, x.shape[-1])
    hr = H[:, ::-1].reshape(F, 1, 1, M)

    def solve(win):  # (B, Q, M) -> (F, B, Q)
        return fxp_mp_dot(win[None], hr, gamma_q, iters, spec)

    xp = jnp.pad(x2, ((0, 0), (M - 1, 0))) if pad else x2
    if chunk_n is None or N <= chunk_n:
        idx = jnp.arange(N)[:, None] + jnp.arange(M)[None, :]
        y = solve(xp[:, idx])
    else:
        Q = chunk_n
        n_blocks = -(-N // Q)
        xp = jnp.pad(xp, ((0, 0), (0, n_blocks * Q + M - 1 - xp.shape[1])))
        idx = jnp.arange(Q)[:, None] + jnp.arange(M)[None, :]

        def one(start):
            seg = jax.lax.dynamic_slice_in_dim(xp, start, Q + M - 1, axis=1)
            return solve(seg[:, idx])

        ys = jax.lax.map(one, jnp.arange(n_blocks) * Q)  # (nc, F, B, Q)
        y = jnp.moveaxis(ys, 0, 2).reshape(F, x2.shape[0], n_blocks * Q)
        y = y[..., :N]
    return jnp.moveaxis(y, 0, 1).reshape(*lead, F, N)


def _csd(v: int) -> list:
    """Canonical signed-digit decomposition: v == sum(sign << bit) with no
    two adjacent nonzero digits — the minimal shift/add realization of a
    constant multiplier."""
    v = int(v)
    terms = []
    k = 0
    while v != 0:
        if v & 1:
            r = 2 - (v & 3)  # +1 when v % 4 == 1, -1 when v % 4 == 3
            terms.append((r, k))
            v -= r
        v >>= 1
        k += 1
    return terms


def fxp_fir_shift_add(x, h_q: np.ndarray, pad: bool = True):
    """Constant-coefficient FIR as trace-time-unrolled CSD shift/adds:
    y(n) = sum_k h[k] x(n-k) with every tap expanded into signed powers of
    two — the classic multiplierless realization of a MAC FIR. ``h_q`` must
    be STATIC host integers (the ROM contents). Output q-values carry scale
    2**(x.exp + h.exp). ``pad=False`` keeps only the fully-covered positions
    (shape ``(..., N-M+1)``) — the session step's delay-splice form."""
    h_q = np.asarray(h_q)
    assert h_q.ndim == 1
    M = h_q.shape[0]
    N = x.shape[-1] if pad else x.shape[-1] - M + 1
    xp = x if not pad else \
        jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(M - 1, 0)])
    y = jnp.zeros(x.shape[:-1] + (N,), x.dtype)
    for k_tap in range(M):
        sk = jax.lax.slice_in_dim(xp, M - 1 - k_tap, M - 1 - k_tap + N,
                                  axis=x.ndim - 1)
        for sign, bit in _csd(int(h_q[k_tap])):
            t = shift_left(sk, bit)
            y = y + t if sign > 0 else y - t
    return y


def fxp_hwr_accumulate(y, valid=None):
    """s = sum_n [y_n]_+ over the last axis. Integer adds are associative,
    so no blocked-reduction ordering is needed for bit parity (unlike the
    float path's ``filterbank.hwr_accumulate``) — and chunked streaming
    accumulation is EXACTLY one-shot accumulation, not merely close.

    ``valid`` (broadcastable to ``y.shape[:-1]``, trailing axis dropped —
    e.g. ``n[:, None]`` for a (S, F, l) bank output) zeroes positions >=
    valid before the sum, so padded slots contribute no-op terms."""
    h = _relu(y)
    if valid is not None:
        pos = jax.lax.broadcasted_iota(jnp.int32, y.shape, y.ndim - 1)
        h = jnp.where(pos < jnp.asarray(valid)[..., None], h, 0)
    return jnp.sum(h, axis=-1)


# ---------------------------------------------------------------------------
# compiled programs: static taps/ROMs/shift tables + per-stage specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OctaveStage:
    """One octave's static datapath: band-pass taps + the anti-aliasing
    low-pass feeding the next octave, with their internal-path formats.

    ``in_spec`` is this octave's 8-bit signal register format. Its exp may
    sit below the ADC's by a calibrated static pre-gain (a left shift baked
    into the design, see ``calibrate_octave_gains``): deeper octaves carry
    progressively smaller signals, and without the per-octave gain their
    content drowns in the shared full-scale grid."""
    in_spec: FixedPointSpec    # 8-bit octave signal register format
    bp_q: jax.Array            # (F, M) int32 taps, pre-aligned to band_spec
    band_spec: FixedPointSpec  # 10-bit internal format of the BP MP stage
    sig_shift: int             # in_spec.exp - band_spec.exp (align x; a
    #                            negative value floors input LSBs away —
    #                            the 10-bit adder path's width limit)
    gamma_bp: int              # gamma_f on the band grid
    iters_bp: int
    acc_shift: int             # (band exp + octave renorm) -> acc exp, >= 0
    lp_q: Optional[jax.Array]  # (1, M_lp) int32, None for the last octave
    lp_spec: Optional[FixedPointSpec]
    lp_sig_shift: int          # in_spec.exp - lp_spec.exp
    gamma_lp: int
    iters_lp: int
    lp_out_shift: int          # lp_spec.exp -> next octave's register exp
    # MAC (shift-add) mode extras: raw ROM taps + product-grid rescales
    bp_rom: Optional[np.ndarray] = None   # (F, M) host ints at rom exp
    lp_rom: Optional[np.ndarray] = None
    bp_prod_shift: int = 0     # (in+rom exp) -> band exp
    lp_prod_shift: int = 0     # (in+rom exp) -> lp_spec exp


@dataclasses.dataclass(frozen=True)
class FixedBankProgram:
    """Integer multirate filter bank: quantized signal in, 32-bit per-band
    accumulators out. Built once from static taps by :func:`compile_bank`."""
    mode: str                  # "mp" | "mac"
    signal: FixedPointSpec     # 8-bit ADC format (exp from fixed_amax)
    acc: FixedPointSpec        # 32-bit accumulator format
    octaves: tuple             # OctaveStage per octave

    @property
    def num_filters(self) -> int:
        return sum(int(o.bp_q.shape[0]) for o in self.octaves)


@dataclasses.dataclass(frozen=True)
class FixedClassifier:
    """MP kernel machine ROMs on the classifier operand grid."""
    wp_q: jax.Array            # (P, C) int32 at spec.exp
    wn_q: jax.Array
    bpos_q: jax.Array          # (C,)
    bneg_q: jax.Array
    spec: FixedPointSpec       # 10-bit operand/output format
    phi_shift: int             # phi.exp - spec.exp (align K, usually >= 0)
    gamma1_q: int
    gamman_q: int
    iters1: int
    iters_n: int


@dataclasses.dataclass(frozen=True)
class FixedPointProgram:
    """The full audio -> decision integer program: bank + standardization
    shift table + classifier. ``infer_q`` executes it.

    Standardization is shift-add: 1/sigma (folded with the acc->phi grid
    change) is approximated per band by a two-term CSD reciprocal
    ``2**k1 + sign * 2**k2`` (<= ~9% relative error vs ~41% for a single
    power of two), so ``phi = (s - mu) / sigma`` costs two shifts and one
    add/select per band — no divider on the FPGA."""
    bank: FixedBankProgram
    mu_q: jax.Array            # (P,) int32 at bank.acc.exp
    phi_shift_q: jax.Array     # (P,) int32: leading CSD shift per band
    phi_shift2_q: jax.Array    # (P,) int32: second CSD term's shift
    phi_sign2_q: jax.Array     # (P,) int32 in {-1, 0, +1}: second term sign
    phi: FixedPointSpec        # 8-bit standardized-feature format
    clf: FixedClassifier

    @property
    def signal(self) -> FixedPointSpec:
        return self.bank.signal

    @property
    def out_spec(self) -> FixedPointSpec:
        return self.clf.spec


def _plan_bits(cfg):
    """Per-stage bitwidth plan from a FilterBankConfig: 8-bit signals and
    weights, a (bits+2)-bit internal path — the paper's 8/10-bit split."""
    signal_bits = cfg.quant_bits if cfg.quant_bits is not None else 8
    return signal_bits, signal_bits, signal_bits + 2


def calibrate_octave_gains(cfg, lp_taps, audio,
                           max_gain: int = 8) -> tuple:
    """Static per-octave pre-gains (left shifts) from calibration audio.

    The multirate cascade halves bandwidth per octave, so deep-octave
    signals are usually far below the ADC full-scale; a fixed-point design
    bakes a power-of-two gain into each octave's register format to recover
    the lost resolution (block-format calibration — still shift-only).
    Runs the FLOAT LP cascade on ``audio`` and returns
    ``g_o = clip(floor(log2(full_scale / peak_o)), 0, max_gain)`` per
    octave, with ``g_0 = 0`` (the ADC grid is the ADC grid).
    """
    from repro.core import filterbank as fbm
    fcfg = cfg._replace(numerics="float", quant_bits=None)
    amax = float(cfg.fixed_amax)
    x_o = jnp.asarray(np.atleast_2d(np.asarray(audio, np.float32)))
    gains = [0]
    for o in range(cfg.num_octaves - 1):
        x_o = fbm.single_fir(x_o, jnp.asarray(lp_taps[o]), fcfg)[..., ::2]
        peak = float(jnp.max(jnp.abs(x_o)))
        g = 0 if peak <= 0 else math.floor(math.log2(amax / peak))
        gains.append(int(np.clip(g, 0, max_gain)))
    return tuple(gains)


def compile_bank(cfg, bp_taps, lp_taps, *, amax: float | None = None,
                 signal_bits: int | None = None,
                 internal_bits: int | None = None,
                 octave_gains=None) -> FixedBankProgram:
    """Lower a float filter bank (per-octave (F, M) bp taps + per-stage lp
    taps) to the integer program. ``amax`` is the ADC full-scale
    (default ``cfg.fixed_amax``): a STATIC calibration, like real hardware —
    inputs beyond it saturate. ``octave_gains`` (from
    :func:`calibrate_octave_gains`) bakes a left-shift pre-gain into each
    octave's register format; default all-zero (flat full-scale grids)."""
    if cfg.mode not in ("mp", "mac"):
        raise ValueError(f"numerics='fixed' supports mode 'mp' or 'mac', "
                         f"got {cfg.mode!r}")
    sb, tb, ib = _plan_bits(cfg)
    if signal_bits is not None:
        sb = tb = signal_bits
        ib = signal_bits + 2
    if internal_bits is not None:
        ib = internal_bits
    amax = float(cfg.fixed_amax if amax is None else amax)
    signal = pow2_spec_for(None, sb, amax=amax)
    num_oct = cfg.num_octaves
    if octave_gains is None:
        octave_gains = (0,) * num_oct
    octave_gains = tuple(int(g) for g in octave_gains)
    if len(octave_gains) != num_oct or octave_gains[0] != 0 \
            or any(g < 0 for g in octave_gains):
        raise ValueError(f"octave_gains must be {num_oct} ints >= 0 with "
                         f"gains[0] == 0, got {octave_gains}")
    # octave signal registers: the ADC format shifted down by the pre-gain
    in_specs = [FixedPointSpec(bits=sb, exp=signal.exp - g)
                for g in octave_gains]

    def stage_for(h: np.ndarray, in_spec: FixedPointSpec):
        """(taps ROM ints + exp, internal spec) for one FIR stage. The
        internal exp covers |h|max + the octave register range (the MP
        operand range u = h +- x) at ``ib`` bits; ROM taps align onto it by
        shift."""
        h = np.asarray(h, np.float64)
        rom_spec = pow2_spec_for(h, tb)
        rom = np.clip(np.round(h / rom_spec.scale),
                      rom_spec.qmin, rom_spec.qmax).astype(np.int64)
        if cfg.mode == "mp":
            cover = float(np.max(np.abs(h))) + in_spec.amax
        else:
            # shift-add MAC: output range is the l1 gain times the signal
            cover = max(float(np.sum(np.abs(h), axis=-1).max()), 1.0) \
                * in_spec.amax
        spec = pow2_spec_for(None, ib, amax=cover)
        # align ROM onto the internal grid (host-side floor shift)
        k = rom_spec.exp - spec.exp
        aligned = rom * (1 << k) if k >= 0 else rom >> (-k)
        return rom, rom_spec, spec, np.asarray(aligned, np.int32)

    pre = []
    for o in range(num_oct):
        bp_rom, bp_rom_spec, band_spec, bp_q = stage_for(bp_taps[o],
                                                         in_specs[o])
        if o < num_oct - 1:
            lp_rom, lp_rom_spec, lp_spec, lp_q = stage_for(
                np.asarray(lp_taps[o])[None, :], in_specs[o])
        else:
            lp_rom = lp_rom_spec = lp_spec = lp_q = None
        pre.append((bp_rom, bp_rom_spec, band_spec, bp_q,
                    lp_rom, lp_rom_spec, lp_spec, lp_q))
    # accumulator grid: the finest (band exp + octave renorm) across octaves
    acc_exp = min(p[2].exp + o for o, p in enumerate(pre))
    acc = FixedPointSpec(bits=32, exp=acc_exp)
    stages = []
    for o, (bp_rom, bp_rom_spec, band_spec, bp_q,
            lp_rom, lp_rom_spec, lp_spec, lp_q) in enumerate(pre):
        in_spec = in_specs[o]
        gamma_bp = max(1, int(round(cfg.gamma_f / band_spec.scale)))
        if lp_spec is not None:
            gamma_lp = max(1, int(round(cfg.gamma_f / lp_spec.scale)))
            lp_sig_shift = in_spec.exp - lp_spec.exp
            lp_out_shift = lp_spec.exp - in_specs[o + 1].exp
            lp_prod_shift = (in_spec.exp + lp_rom_spec.exp) - lp_spec.exp
        else:
            gamma_lp = 1
            lp_sig_shift = lp_out_shift = lp_prod_shift = 0
        stages.append(OctaveStage(
            in_spec=in_spec, bp_q=bp_q, band_spec=band_spec,
            sig_shift=in_spec.exp - band_spec.exp,
            gamma_bp=gamma_bp, iters_bp=bisect_iters(gamma_bp),
            acc_shift=band_spec.exp + o - acc_exp,
            lp_q=lp_q, lp_spec=lp_spec, lp_sig_shift=lp_sig_shift,
            gamma_lp=gamma_lp, iters_lp=bisect_iters(gamma_lp),
            lp_out_shift=lp_out_shift,
            bp_rom=bp_rom, lp_rom=lp_rom,
            bp_prod_shift=(in_spec.exp + bp_rom_spec.exp) - band_spec.exp,
            lp_prod_shift=lp_prod_shift,
        ))
    return FixedBankProgram(mode=cfg.mode, signal=signal, acc=acc,
                            octaves=tuple(stages))


def compile_pipeline(pipe, *, amax: float | None = None,
                     signal_bits: int | None = None,
                     internal_bits: int | None = None,
                     phi_amax: float = 4.0,
                     octave_gains=None,
                     calibration_audio=None) -> FixedPointProgram:
    """Lower a trained ``InFilterPipeline`` to the full integer program.

    Standardization becomes subtract-and-shift (two-term CSD reciprocal
    sigma — exact standardization would need a true divider); mu and the
    classifier ROMs quantize onto their stage grids. ``calibration_audio``
    (host array) derives the ADC full-scale (when ``amax`` is None) and the
    per-octave register pre-gains; or pass ``octave_gains`` directly. Must
    be called with CONCRETE (non-traced) pipeline arrays.

    The one program serves BOTH execution shapes with one parity contract:
    one-shot :func:`infer_q` and chunked :func:`session_step_q` produce
    identical integer codes (any chunking, from the first chunk), and each
    runs bit-identically on int32 or float-carried integers.
    """
    from repro.core import kernel_machine as km

    cfg = pipe.config
    if any(isinstance(leaf, jax.core.Tracer) for leaf in jax.tree.leaves(
            (pipe.bp_taps, pipe.lp_taps, pipe.mu, pipe.sigma, pipe.clf))):
        raise TypeError(
            "compile_pipeline needs CONCRETE pipeline arrays — it bakes the "
            "ROMs and shift tables host-side. Do not jit "
            "InFilterPipeline.apply/predict/features with numerics='fixed' "
            "directly (the pipeline pytree's leaves become tracers); "
            "precompile instead:  prog = pipe.fixed_program(); "
            "jax.jit(lambda x: fixed.predict(prog, x))")
    if calibration_audio is not None:
        cal = np.asarray(calibration_audio, np.float32)
        if amax is None:
            amax = float(np.max(np.abs(cal))) or 1.0
        if octave_gains is None:
            octave_gains = calibrate_octave_gains(
                cfg._replace(fixed_amax=amax), pipe.lp_taps, cal)
    bank = compile_bank(cfg, [np.asarray(t) for t in pipe.bp_taps],
                        [np.asarray(t) for t in pipe.lp_taps],
                        amax=amax, signal_bits=signal_bits,
                        internal_bits=internal_bits,
                        octave_gains=octave_gains)
    _, tb, ib = _plan_bits(cfg)
    if signal_bits is not None:
        tb, ib = signal_bits, signal_bits + 2
    if internal_bits is not None:
        ib = internal_bits

    mu = np.asarray(pipe.mu, np.float64)
    sigma = np.asarray(pipe.sigma, np.float64)
    mu_q = np.asarray(np.round(mu / bank.acc.scale), np.int32)
    # phi = (s - mu) * g with g = 2**(acc.exp - phi.exp) / sigma, realized
    # as the best two-term CSD approximation g ~= 2**k1 + sign * 2**k2
    phi = pow2_spec_for(None, tb, amax=phi_amax)
    g = math.ldexp(1.0, bank.acc.exp - phi.exp) / np.maximum(sigma, 1e-30)
    k1s, k2s, s2s = [], [], []
    for gi in g:
        best = (math.inf, 0, 0, 0)
        for k1 in (math.floor(math.log2(gi)), math.ceil(math.log2(gi))):
            for sign, k2 in [(0, k1 - 1)] + [(s, k1 - d)
                                             for s in (-1, 1)
                                             for d in range(1, 7)]:
                approx = math.ldexp(1.0, k1) + sign * math.ldexp(1.0, k2)
                err = abs(approx - gi) / gi
                if err < best[0]:
                    best = (err, k1, k2, sign)
        k1s.append(best[1]); k2s.append(best[2]); s2s.append(best[3])
    phi_shift_q = np.asarray(k1s, np.int32)
    phi_shift2_q = np.asarray(k2s, np.int32)
    phi_sign2_q = np.asarray(s2s, np.int32)

    # classifier operand grid: cover |w|max + |phi|max at internal bits
    wp = np.maximum(np.asarray(pipe.clf.w_pos, np.float64), 0.0)
    wn = np.maximum(np.asarray(pipe.clf.w_neg, np.float64), 0.0)
    bias_amax = float(max(np.max(np.abs(np.asarray(pipe.clf.b_pos))),
                          np.max(np.abs(np.asarray(pipe.clf.b_neg))), 0.0))
    wmax = float(max(wp.max(), wn.max(), 1e-6))
    cover = max(wmax + phi.amax, bias_amax, 1.0)
    cspec = pow2_spec_for(None, ib, amax=cover)
    rom_spec = pow2_spec_for(None, tb, amax=max(wmax, bias_amax, 1e-6))
    wp_q, wn_q, bpos_q, bneg_q = km.quantize_params(pipe.clf, rom_spec,
                                                    cspec)
    gamma1 = float(np.exp(np.asarray(pipe.clf.log_gamma1)))
    gamma1_q = max(1, int(round(gamma1 / cspec.scale)))
    gamman_q = max(1, int(round(1.0 / cspec.scale)))
    clf = FixedClassifier(
        wp_q=wp_q, wn_q=wn_q, bpos_q=bpos_q, bneg_q=bneg_q, spec=cspec,
        phi_shift=phi.exp - cspec.exp,
        gamma1_q=gamma1_q, gamman_q=gamman_q,
        iters1=bisect_iters(gamma1_q), iters_n=bisect_iters(gamman_q))
    if clf.phi_shift < 0:
        raise ValueError("classifier operand grid coarser than phi grid "
                         f"(phi exp {phi.exp} < operand exp {cspec.exp})")
    return FixedPointProgram(bank=bank, mu_q=mu_q, phi_shift_q=phi_shift_q,
                             phi_shift2_q=phi_shift2_q,
                             phi_sign2_q=phi_sign2_q, phi=phi, clf=clf)


# ---------------------------------------------------------------------------
# program execution (int32 carrier = the hardware twin; float carrier =
# the fake-quant simulation — bit-identical by construction)
# ---------------------------------------------------------------------------


def quantize_signal(prog, x, carrier: str = "int"):
    """ADC: float audio -> signal-format codes. ``carrier="int"`` gives the
    int32 hardware path; ``carrier="float"`` gives float-carried codes for
    the fake-quant twin."""
    signal = prog.signal if isinstance(prog, FixedBankProgram) \
        else prog.bank.signal
    dtype = jnp.int32 if carrier == "int" else jnp.float32
    if carrier not in ("int", "float"):
        raise ValueError(f"carrier must be 'int' or 'float', got {carrier!r}")
    return signal.quantize(x, dtype=dtype)


def bank_accumulate_q(bank: FixedBankProgram, xq, *,
                      use_pallas: bool = False):
    """Quantized signal (B, N) -> 32-bit accumulators (B, P) at
    ``bank.acc``. The integer mirror of ``filterbank.multirate_accumulate``
    (renormalization by 2**octave is folded into ``acc_shift``).

    ``use_pallas`` routes the MP band solves + HWR accumulation through the
    fused integer Pallas kernels (``kernels.fir_mp_bank_q*`` — one
    VMEM-resident signal block per octave), bit-for-bit equal to the XLA
    ``fxp_*`` path; MAC mode always runs the XLA shift-add FIR."""
    if use_pallas and bank.mode == "mp":
        from repro.kernels import fir_mp_bank_q, fir_mp_bank_q_accumulate
    x_o = xq
    parts = []
    for o, st in enumerate(bank.octaves):
        if bank.mode == "mp":
            x_op = rescale(x_o, st.sig_shift)
            if use_pallas:
                parts.append(shift_left(fir_mp_bank_q_accumulate(
                    x_op, st.bp_q, gamma_q=st.gamma_bp, iters=st.iters_bp,
                    qmin=int(st.band_spec.qmin),
                    qmax=int(st.band_spec.qmax)), st.acc_shift))
            else:
                band = fxp_fir_bank(x_op, st.bp_q, st.gamma_bp, st.iters_bp,
                                    st.band_spec)
                parts.append(shift_left(fxp_hwr_accumulate(band),
                                        st.acc_shift))
        else:
            bands = [rescale(fxp_fir_shift_add(x_o, st.bp_rom[f]),
                             st.bp_prod_shift)
                     for f in range(st.bp_rom.shape[0])]
            band = _clamp(jnp.stack(bands, axis=-2), st.band_spec)
            parts.append(shift_left(fxp_hwr_accumulate(band), st.acc_shift))
        if st.lp_q is not None:
            if bank.mode == "mp":
                x_lp = rescale(x_o, st.lp_sig_shift)
                if use_pallas:
                    y_lp = fir_mp_bank_q(
                        x_lp, st.lp_q, gamma_q=st.gamma_lp,
                        iters=st.iters_lp, qmin=int(st.lp_spec.qmin),
                        qmax=int(st.lp_spec.qmax))[..., 0, :]
                else:
                    y_lp = fxp_fir_bank(x_lp, st.lp_q, st.gamma_lp,
                                        st.iters_lp, st.lp_spec)[..., 0, :]
            else:
                y_lp = _clamp(rescale(fxp_fir_shift_add(x_o, st.lp_rom[0]),
                                      st.lp_prod_shift), st.lp_spec)
            # requantize onto the NEXT octave's 8-bit register bank (its
            # exp carries that octave's calibrated pre-gain), then ÷2
            x_o = _clamp(rescale(y_lp, st.lp_out_shift),
                         bank.octaves[o + 1].in_spec)[..., ::2]
    return jnp.concatenate(parts, axis=-1)


def standardize_q(prog: FixedPointProgram, s_q):
    """32-bit accumulators -> 8-bit standardized kernel vector: subtract
    the mu ROM, then the per-band two-term CSD reciprocal-sigma (two
    shifts + one add/select per band)."""
    diff = s_q - _c(prog.mu_q, s_q)
    t1 = rescale(diff, jnp.asarray(prog.phi_shift_q, jnp.int32))
    t2 = rescale(diff, jnp.asarray(prog.phi_shift2_q, jnp.int32))
    s2 = jnp.asarray(prog.phi_sign2_q, jnp.int32)
    phi = jnp.where(s2 > 0, t1 + t2, jnp.where(s2 < 0, t1 - t2, t1))
    return _clamp(phi, prog.phi)


def classifier_q(clf: FixedClassifier, K_q):
    """Integer MP kernel machine (paper eq. 2-7): the same operand layout
    as ``kernel_machine.forward``, solved by integer bisection."""
    K = shift_left(K_q, clf.phi_shift)          # phi grid -> operand grid
    Kp = K[:, :, None]
    Kn = -K[:, :, None]
    wp = _c(clf.wp_q, K_q)
    wn = _c(clf.wn_q, K_q)

    def z_of(a, b, bias):
        ops = jnp.concatenate([_clamp(a[None] + Kp, clf.spec),
                               _clamp(b[None] + Kn, clf.spec)], axis=1)
        bias_col = jnp.broadcast_to(_c(bias, K_q)[None, None, :],
                                    (ops.shape[0], 1, ops.shape[2]))
        ops = jnp.concatenate([ops, bias_col], axis=1)   # (B, 2P+1, C)
        return fxp_mp_bisect(jnp.moveaxis(ops, 1, -1), clf.gamma1_q,
                             clf.iters1)

    z_pos = z_of(wp, wn, clf.bpos_q)
    z_neg = z_of(wn, wp, clf.bneg_q)
    z = fxp_mp_bisect(jnp.stack([z_pos, z_neg], axis=-1), clf.gamman_q,
                      clf.iters_n)
    return _relu(z_pos - z) - _relu(z_neg - z)


def infer_q(prog: FixedPointProgram, xq, *, use_pallas: bool = False):
    """The pure-integer inference program: quantized signal codes in,
    (p_q, phi_q, s_q) codes out. This is the function
    ``benchmarks/hardware_cost.py`` censuses — its jaxpr must contain no
    multiply and no divide (with or without ``use_pallas``, which swaps the
    MP bank solves onto the fused integer Pallas kernels bit-for-bit)."""
    s_q = bank_accumulate_q(prog.bank, xq, use_pallas=use_pallas)
    phi_q = standardize_q(prog, s_q)
    p_q = classifier_q(prog.clf, phi_q)
    return p_q, phi_q, s_q


def predict(prog: FixedPointProgram, x, carrier: str = "int", *,
            use_pallas: bool = False):
    """Float audio (B, N) -> dequantized (p, phi): the deployment-preview
    surface. ``p`` carries scale ``2**clf.spec.exp`` (the [-1, 1] signed
    confidence on the operand grid)."""
    xq = quantize_signal(prog, x, carrier=carrier)
    p_q, phi_q, _ = infer_q(prog, xq, use_pallas=use_pallas)
    return prog.out_spec.dequantize(p_q), prog.phi.dequantize(phi_q)


# ---------------------------------------------------------------------------
# integer session streaming: every SessionState register is an int in the
# fixed-point grid, and chunked execution is bit-for-bit the one-shot
# program (see docs/numerics.md for the exactness argument)
# ---------------------------------------------------------------------------


def readout_q(prog: FixedPointProgram, acc_q):
    """Pure readout from 32-bit accumulator registers: (p_q, phi_q).
    The decision from all evidence so far — what a zero-length session
    chunk (and every chunk's trailing readout) computes. Its ops carry
    the named scope ``readout``."""
    with jax.named_scope("readout"):
        phi_q = standardize_q(prog, acc_q)
        return classifier_q(prog.clf, phi_q), phi_q


def session_step_q(prog: FixedPointProgram, state, chunk_q, n):
    """One slot-batched INTEGER session step: signal codes in, codes out.

    The int32 mirror of the pipeline's XLA session cascade. ``state`` is a
    ``SessionState``-shaped namedtuple whose registers are carried on the
    fixed-point grid: per-octave delay lines hold that octave's 8-bit
    signal-register codes (``OctaveStage.in_spec``), ``acc`` is the 32-bit
    accumulator at ``prog.bank.acc``, and ``amax`` is the running max
    |signal code| (pure calibration telemetry — the ADC grid is STATIC, so
    unlike the float path no quantization scale depends on it). ``chunk_q``
    is (S, L) ADC codes with positions >= ``n`` already zeroed; ``n`` is
    (S,) int32 effective valid counts (active mask applied by the caller).

    Exactness: every band value at a global stream position is one
    LSB-deterministic integer bisection over a window of octave-register
    codes, the delay lines carry those codes losslessly across chunk
    boundaries (zero-initialized registers == the one-shot path's zero
    padding), and integer accumulator addition is associative — so ANY
    chunk partition reproduces the one-shot :func:`infer_q` codes
    bit-for-bit, from the FIRST chunk (no peak-seen caveat). Returns
    ``(state', p_q, phi_q)``.

    Carrier-generic like every ``fxp_*`` kernel: int32 registers run the
    hardware path (what ``benchmarks/hardware_cost.py`` censuses — zero
    multiplies/divides per chunk); float-carried registers run the
    fake-quant twin bit-identically.
    """
    state = cascade_q(prog, state, chunk_q, n)
    p_q, phi_q = readout_q(prog, state.acc)
    return state, p_q, phi_q


def cascade_q(prog: FixedPointProgram, state, chunk_q, n):
    """The register update of :func:`session_step_q` without its readout:
    the integer octave cascade over one (S, L) chunk. Returns ``state'``
    (unchanged for L == 0)."""
    bank = prog.bank
    S, L = chunk_q.shape
    if L == 0:
        return state
    T1 = state.delays[0].shape[1]
    # running amax telemetry: invalid positions are zero codes, so they
    # never raise the max (|code| >= 0 and the register starts at 0)
    amax = jnp.maximum(state.amax, jnp.max(jnp.abs(chunk_q), axis=-1))
    x_o, n_o = chunk_q, n
    l_max = L
    delays, consumed, parts = [], [], []
    for o, st in enumerate(bank.octaves):
        M_bp = st.bp_q.shape[-1]
        # splice the delay registers in front of the chunk: in-chunk
        # position p sits at buf[T1 + p] with its full FIR history
        buf = jnp.concatenate([state.delays[o], x_o], axis=1)
        buf_bp = buf[:, T1 - (M_bp - 1):]
        if bank.mode == "mp":
            band = fxp_fir_bank(rescale(buf_bp, st.sig_shift), st.bp_q,
                                st.gamma_bp, st.iters_bp, st.band_spec,
                                pad=False)                     # (S, F, l_max)
        else:
            bands = [rescale(fxp_fir_shift_add(buf_bp, st.bp_rom[f],
                                               pad=False), st.bp_prod_shift)
                     for f in range(st.bp_rom.shape[0])]
            band = _clamp(jnp.stack(bands, axis=-2), st.band_spec)
        parts.append(shift_left(fxp_hwr_accumulate(band, n_o[:, None]),
                                st.acc_shift))
        # register update: the last T1 *valid* samples become the new delay
        # line (slots with n_o == 0 re-read their old registers: inert)
        delays.append(jax.vmap(
            lambda b, s: jax.lax.dynamic_slice_in_dim(b, s, T1, axis=0)
        )(buf, n_o))
        consumed.append(state.consumed[o] + n_o)
        if st.lp_q is not None:
            M_lp = st.lp_q.shape[-1]
            # ÷2 decimator keeps even GLOBAL positions; each slot's phase
            # is its octave-sample parity (bit-and, not a divider)
            start = jnp.bitwise_and(state.consumed[o], 1)          # (S,)
            l_next = (l_max + 1) // 2
            buf_lp = buf[:, T1 - (M_lp - 1):]
            if bank.mode == "mp":
                # solve ONLY the kept positions: stride-2 window gather
                # (kept sample k of slot s ends at start_s + 2k + M_lp - 1)
                xw = jnp.pad(rescale(buf_lp, st.lp_sig_shift),
                             ((0, 0), (0, 1)))
                widx = ((jnp.arange(l_next) << 1)[:, None]
                        + jnp.arange(M_lp)[None, :])       # (l_next, M_lp)
                win = jax.vmap(lambda r, s: r[s + widx])(xw, start)
                kept = fxp_mp_dot(win, _c(st.lp_q[0, ::-1], xw),
                                  st.gamma_lp, st.iters_lp, st.lp_spec)
            else:
                y_lp = _clamp(rescale(fxp_fir_shift_add(buf_lp, st.lp_rom[0],
                                                        pad=False),
                                      st.lp_prod_shift), st.lp_spec)
                y_pad = jnp.pad(y_lp, ((0, 0), (0, 2 * l_next + 1 - l_max)))
                kept = jax.vmap(
                    lambda r, s: jax.lax.dynamic_slice_in_dim(
                        r, s, 2 * l_next, axis=0)
                )(y_pad, start)[:, ::2]
            # requantize onto the next octave's 8-bit register bank (its
            # exp carries that octave's calibrated pre-gain)
            x_o = _clamp(rescale(kept, st.lp_out_shift),
                         bank.octaves[o + 1].in_spec)
            # kept-count update: arithmetic shift, not an integer divide
            # (the census must stay divider-free)
            n_o = jnp.right_shift(jnp.maximum(n_o - start + 1, 0), 1)
            l_max = l_next
    acc = state.acc + jnp.concatenate(parts, axis=-1)
    return state._replace(delays=tuple(delays), consumed=tuple(consumed),
                          acc=acc, amax=amax, count=state.count + n)
