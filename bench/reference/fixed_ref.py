"""Plain reference of the int32 datapath (``numerics: "fixed"``).

The configuration states the paper's fixed-point plan (§V): every format
is a signed integer with a power-of-two LSB; signals and ROM weights have
``bits`` bits, the MP adders ``bits + 2``, the accumulators 32. Converting
between formats is a shift: left is exact, right floors. Sums that feed an
MP solve saturate onto the adder format. Quantising rounds half to even
and saturates.

On that grid MP(L, gamma) is the smallest integer z with
sum_i [L_i - z]_+ <= gamma. Here it is found by a bisection that keeps
h(lo) > gamma >= h(hi) from its first step and runs until hi - lo == 1.

The bank is computed one-shot over each stream's whole audio, from zeroed
registers, in blocks of positions so that it fits. Streams of different
lengths share one call: positions past a stream's length are left out of
its sums. The decision is argmax of the class codes, first on ties, and
its value.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import design

BLOCK = 4096            # output positions solved per block
PHI_AMAX = 4.0          # the standardised feature's range: four sigmas,
#                         a constant of the datapath, not a setting


def fmt_exp(amax: float, bits: int) -> int:
    """Exponent of the finest power-of-two LSB whose largest code
    (2**(bits-1) - 1) still reaches ``amax``."""
    qmax = (1 << (bits - 1)) - 1
    e = math.ceil(math.log2(amax / qmax) - 1e-12)
    while math.ldexp(qmax, e) < amax:
        e += 1
    return e


def _shift(q, k: int):
    """q * 2**k on integer codes: left shift, or floor right shift."""
    return q << k if k >= 0 else q >> (-k)


def _quant(x: np.ndarray, e: int, bits: int) -> np.ndarray:
    lim = 1 << (bits - 1)
    q = np.round(np.asarray(x, np.float64) / math.ldexp(1.0, e))
    return np.clip(q, -lim, lim - 1).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Stage:
    taps: np.ndarray        # (F, M) int codes on the adder grid
    exp: int                # adder-grid exponent
    sig_shift: int          # register grid -> adder grid
    gamma: int


@dataclasses.dataclass(frozen=True)
class Plan:
    bits: int
    sig_exp: int            # ADC (and every octave register) exponent
    bp: tuple               # Stage per octave
    lp: tuple               # Stage per /2 stage
    acc_exp: int
    acc_shift: tuple        # per octave: band grid (+ octave renorm) -> acc
    lp_out_shift: tuple     # per /2 stage: low-pass grid -> register grid
    phi_exp: int
    mu_q: np.ndarray        # (P,) on the accumulator grid
    phi_shift: int          # (s - mu) * 2**(acc_exp - phi_exp) / sigma
    c_exp: int              # classifier adder grid
    wp: np.ndarray          # (P, C) ROM codes on the classifier grid
    wn: np.ndarray
    bpos: np.ndarray        # (C,)
    bneg: np.ndarray
    k_shift: int            # phi grid -> classifier grid
    gamma1: int
    gamman: int


def plan(cfg: dict, weights: dict, bits: int = 8) -> Plan:
    """The integer program's formats and ROMs, from the configuration and
    the float weights. ``bits`` is the signal and weight width."""
    fb = cfg["filterbank"]
    ib = bits + 2
    bp_f, lp_f = design.taps(fb)
    sig_exp = fmt_exp(float(cfg["fixed_amax"]), bits)
    reg_amax = ((1 << (bits - 1)) - 1) * math.ldexp(1.0, sig_exp)

    def stage(h):
        h = np.asarray(h, np.float64)
        hmax = float(np.max(np.abs(h))) or 1.0
        rom_exp = fmt_exp(hmax, bits)
        exp = fmt_exp(hmax + reg_amax, ib)
        codes = _shift(_quant(h, rom_exp, bits), rom_exp - exp)
        gamma = max(1, int(round(float(fb["gamma_f"]) / math.ldexp(1.0, exp))))
        return Stage(taps=codes.astype(np.int32), exp=exp,
                     sig_shift=sig_exp - exp, gamma=gamma)

    bp = tuple(stage(h) for h in bp_f)
    lp = tuple(stage(h[None, :]) for h in lp_f)
    acc_exp = min(s.exp + o for o, s in enumerate(bp))
    acc_shift = tuple(s.exp + o - acc_exp for o, s in enumerate(bp))
    lp_out_shift = tuple(s.exp - sig_exp for s in lp)

    phi_exp = fmt_exp(PHI_AMAX, bits)
    mu = np.asarray(weights["mu"], np.float64)
    sigma = float(np.asarray(weights["sigma"]).reshape(-1)[0])
    if not np.all(np.asarray(weights["sigma"]) == sigma) \
            or math.frexp(sigma)[0] != 0.5:
        raise ValueError("the fixed reference takes one power-of-two sigma "
                         "for every band")
    mu_q = np.round(mu / math.ldexp(1.0, acc_exp)).astype(np.int64)
    phi_shift = acc_exp - phi_exp - (math.frexp(sigma)[1] - 1)

    wp = np.maximum(np.asarray(weights["w_pos"], np.float64), 0.0)
    wn = np.maximum(np.asarray(weights["w_neg"], np.float64), 0.0)
    b_amax = float(max(np.max(np.abs(weights["b_pos"])),
                       np.max(np.abs(weights["b_neg"])), 0.0))
    wmax = float(max(wp.max(), wn.max(), 1e-6))
    phi_amax = ((1 << (bits - 1)) - 1) * math.ldexp(1.0, phi_exp)
    c_exp = fmt_exp(max(wmax + phi_amax, b_amax, 1.0), ib)
    rom_exp = fmt_exp(max(wmax, b_amax, 1e-6), bits)
    rom = lambda w: _shift(_quant(w, rom_exp, bits), rom_exp - c_exp)
    gamma1 = float(cfg["classifier"]["gamma1"])
    return Plan(
        bits=bits, sig_exp=sig_exp, bp=bp, lp=lp, acc_exp=acc_exp,
        acc_shift=acc_shift, lp_out_shift=lp_out_shift, phi_exp=phi_exp,
        mu_q=mu_q, phi_shift=phi_shift, c_exp=c_exp,
        wp=rom(wp).astype(np.int32), wn=rom(wn).astype(np.int32),
        bpos=_quant(weights["b_pos"], c_exp, ib).astype(np.int32),
        bneg=_quant(weights["b_neg"], c_exp, ib).astype(np.int32),
        k_shift=phi_exp - c_exp,
        gamma1=max(1, int(round(gamma1 / math.ldexp(1.0, c_exp)))),
        gamman=max(1, int(round(1.0 / math.ldexp(1.0, c_exp)))))


# -- the datapath ----------------------------------------------------------


def _ishift(q, k: int):
    return jnp.left_shift(q, k) if k >= 0 else jnp.right_shift(q, -k)


def mp(ops: list, gamma: int):
    """Smallest integer z with sum_i [ops_i - z]_+ <= gamma (elementwise
    over the operands' shape)."""
    top = ops[0]
    for o in ops[1:]:
        top = jnp.maximum(top, o)
    h = lambda z: sum(jnp.maximum(o - z, 0) for o in ops)
    lo, hi = top - gamma - 1, top      # h(lo) >= gamma + 1, h(hi) == 0

    def body(_, b):
        lo, hi = b
        mid = jnp.right_shift(lo + hi, 1)
        over = h(mid) > gamma
        return jnp.where(over, mid, lo), jnp.where(over, hi, mid)

    lo, hi = jax.lax.fori_loop(0, int(gamma + 1).bit_length() + 1, body,
                               (lo, hi))
    return hi


def _mp_dot(xs: list, taps, gamma: int, bits: int):
    """Multiplierless inner product (paper eq. 9) of ``taps`` (M,) with the
    window ``xs`` (M arrays), operand sums saturating onto ``bits``."""
    lim = 1 << (bits - 1)
    u = [jnp.clip(taps[k] + x, -lim, lim - 1) for k, x in enumerate(xs)]
    v = [jnp.clip(taps[k] - x, -lim, lim - 1) for k, x in enumerate(xs)]
    return (mp(u + [-a for a in u], gamma)
            - mp(v + [-a for a in v], gamma))


def _fir(x, taps, gamma: int, bits: int):
    """Causal MP FIR from zeroed registers, y[n] = MPdot(h, x[n], x[n-1],
    ...): x (B, N) codes, taps (F, M) -> (F, B, N), solved in blocks."""
    F, M = taps.shape
    B, N = x.shape
    nb = -(-N // BLOCK)
    xp = jnp.pad(x, ((0, 0), (M - 1, nb * BLOCK - N)))

    def block(start):
        seg = jax.lax.dynamic_slice_in_dim(xp, start, BLOCK + M - 1, axis=1)
        xs = [seg[:, M - 1 - j:M - 1 - j + BLOCK] for j in range(M)]
        return jnp.stack([_mp_dot(xs, taps[f], gamma, bits)
                          for f in range(F)])

    y = jax.lax.map(block, jnp.arange(nb) * BLOCK)      # (nb, F, B, BLOCK)
    return jnp.moveaxis(y, 0, 2).reshape(F, B, nb * BLOCK)[..., :N]


@jax.jit
def _adc(audio, sig_scale_inv, lim):
    q = jnp.round(audio * sig_scale_inv)
    return jnp.clip(q, -lim, lim - 1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "sig_shift", "gamma", "acc_shift", "lp_shift", "lp_gamma", "out_shift",
    "bits", "last"))
def _octave(x, n, bp_taps, lp_taps, *, sig_shift: int, gamma: int,
            acc_shift: int, lp_shift: int, lp_gamma: int, out_shift: int,
            bits: int, last: bool):
    """One octave: band accumulators (B, F) and the next octave's codes."""
    lim = 1 << (bits - 1)
    band = _fir(_ishift(x, sig_shift), bp_taps, gamma, bits + 2)
    pos = jnp.arange(x.shape[1])[None, None, :]
    keep = pos < n[None, :, None]
    acc = jnp.sum(jnp.where(keep, jnp.maximum(band, 0), 0), axis=-1)
    acc = _ishift(acc.T, acc_shift)
    if last:
        return acc, x
    y = _fir(_ishift(x, lp_shift), lp_taps, lp_gamma, bits + 2)[0]
    return acc, jnp.clip(_ishift(y, out_shift), -lim, lim - 1)[:, ::2]


def _readout(p: Plan):
    ib = p.bits + 2
    lim, clim = 1 << (p.bits - 1), 1 << (ib - 1)

    @jax.jit
    def run(acc, mu_q, wp, wn, bpos, bneg):
        phi = jnp.clip(_ishift(acc - mu_q, p.phi_shift), -lim, lim - 1)
        k = _ishift(phi, p.k_shift)                          # (B, P)
        sat = lambda a: jnp.clip(a, -clim, clim - 1)

        def z(a, b, bias):                                   # -> (B, C)
            ops = ([sat(a[i][None, :] + k[:, i:i + 1])
                    for i in range(a.shape[0])]
                   + [sat(b[i][None, :] - k[:, i:i + 1])
                      for i in range(b.shape[0])]
                   + [jnp.broadcast_to(bias[None, :],
                                       (k.shape[0], bias.shape[0]))])
            return mp(ops, p.gamma1)

        zp, zn = z(wp, wn, bpos), z(wn, wp, bneg)
        zz = mp([zp, zn], p.gamman)
        return jnp.maximum(zp - zz, 0) - jnp.maximum(zn - zz, 0)

    return run


def run(p: Plan, audio: np.ndarray, lengths: np.ndarray) -> dict:
    """audio (B, N) float32, zero past each row's ``lengths`` -> the
    accumulator codes ``acc`` (B, P), the class codes ``p`` (B, C) and
    their exponent ``p_exp``."""
    lim = 1 << (p.bits - 1)
    x = _adc(jnp.asarray(audio, jnp.float32),
             jnp.float32(math.ldexp(1.0, -p.sig_exp)), lim)
    n = jnp.asarray(lengths, jnp.int32)
    accs = []
    for o, st in enumerate(p.bp):
        last = o == len(p.lp)
        lp = p.lp[0 if last else o]
        acc, x = _octave(
            x, n, jnp.asarray(st.taps), jnp.asarray(lp.taps),
            sig_shift=st.sig_shift, gamma=st.gamma,
            acc_shift=p.acc_shift[o], lp_shift=lp.sig_shift,
            lp_gamma=lp.gamma, out_shift=p.lp_out_shift[0 if last else o],
            bits=p.bits, last=last)
        accs.append(acc)
        n = (n + 1) >> 1
    acc = jnp.concatenate(accs, axis=1)
    codes = _readout(p)(acc, jnp.asarray(p.mu_q, jnp.int32),
                        jnp.asarray(p.wp), jnp.asarray(p.wn),
                        jnp.asarray(p.bpos), jnp.asarray(p.bneg))
    return {"acc": np.asarray(acc), "p": np.asarray(codes),
            "p_exp": p.c_exp}
