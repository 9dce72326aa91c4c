"""Plain reference of the float datapath (``numerics: "float"``).

The paper's pipeline as its equations state it (§III): per octave, each
band-pass output is the multiplierless inner product of eq. 9,
MPdot(h, w) = MP([h + w; -(h + w)], gamma_f) - MP([h - w; -(h - w)],
gamma_f) over the window w of the last M samples, from zeroed registers.
Its half-wave rectified values are summed over the stream and scaled by
2**o. The low-pass output, kept at even positions, feeds the next octave.
The kernel vector (s - mu) / sigma drives the MP kernel machine
(eq. 2-7).

MP(L, gamma) is the root z of sum_i [L_i - z]_+ = gamma, found by
bisection on [max L - gamma, max L] until the interval stops shrinking.
Everything is computed in ``dtype``: float32 is the configuration's
precision, bfloat16 the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import design

BLOCK = 4096            # output positions solved per block
ITERS = 40              # bisection steps: float32 stops shrinking by ~30


def mp(ops: list, gamma):
    """Root z of sum_i [ops_i - z]_+ = gamma, elementwise."""
    top = ops[0]
    for o in ops[1:]:
        top = jnp.maximum(top, o)
    gamma = jnp.asarray(gamma, top.dtype)
    h = lambda z: sum(jnp.maximum(o - z, 0) for o in ops)
    half = jnp.asarray(0.5, top.dtype)

    def body(_, b):
        lo, hi = b
        mid = (lo + hi) * half
        over = h(mid) > gamma
        return jnp.where(over, mid, lo), jnp.where(over, hi, mid)

    lo, hi = jax.lax.fori_loop(0, ITERS, body, (top - gamma, top))
    return (lo + hi) * half


def _mp_dot(xs: list, taps, gamma):
    u = [taps[k] + x for k, x in enumerate(xs)]
    v = [taps[k] - x for k, x in enumerate(xs)]
    return mp(u + [-a for a in u], gamma) - mp(v + [-a for a in v], gamma)


def _fir(x, taps, gamma):
    """Causal MP FIR: x (B, N), taps (F, M) -> (F, B, N), in blocks."""
    F, M = taps.shape
    B, N = x.shape
    nb = -(-N // BLOCK)
    xp = jnp.pad(x, ((0, 0), (M - 1, nb * BLOCK - N)))

    def block(start):
        seg = jax.lax.dynamic_slice_in_dim(xp, start, BLOCK + M - 1, axis=1)
        xs = [seg[:, M - 1 - j:M - 1 - j + BLOCK] for j in range(M)]
        return jnp.stack([_mp_dot(xs, taps[f], gamma) for f in range(F)])

    y = jax.lax.map(block, jnp.arange(nb) * BLOCK)      # (nb, F, B, BLOCK)
    return jnp.moveaxis(y, 0, 2).reshape(F, B, nb * BLOCK)[..., :N]


@functools.partial(jax.jit, static_argnames=("octave", "last"))
def _octave(x, n, bp, lp, gamma, *, octave: int, last: bool):
    band = _fir(x, bp, gamma)                            # (F, B, N)
    pos = jnp.arange(x.shape[1])[None, None, :]
    h = jnp.where(pos < n[None, :, None], jnp.maximum(band, 0), 0)
    nb = -(-x.shape[1] // BLOCK)
    h = jnp.pad(h, ((0, 0), (0, 0), (0, nb * BLOCK - x.shape[1])))
    parts = jnp.sum(h.reshape(*h.shape[:2], nb, BLOCK), axis=-1)
    acc = parts[..., 0]
    for k in range(1, nb):                               # running total
        acc = acc + parts[..., k]
    acc = (acc * jnp.asarray(2.0 ** octave, x.dtype)).T  # (B, F)
    if last:
        return acc, x
    return acc, _fir(x, lp[None, :], gamma)[0][:, ::2]


@jax.jit
def _readout(acc, mu, sigma, w_pos, w_neg, b_pos, b_neg, gamma1):
    k = (acc - mu) / sigma                               # (B, P)
    wp, wn = jnp.maximum(w_pos, 0), jnp.maximum(w_neg, 0)

    def z(a, b, bias):
        ops = ([a[i][None, :] + k[:, i:i + 1] for i in range(a.shape[0])]
               + [b[i][None, :] - k[:, i:i + 1] for i in range(b.shape[0])]
               + [jnp.broadcast_to(bias[None, :], (k.shape[0],
                                                   bias.shape[0]))])
        return mp(ops, gamma1)

    zp, zn = z(wp, wn, b_pos), z(wn, wp, b_neg)
    zz = mp([zp, zn], 1.0)
    return jnp.maximum(zp - zz, 0) - jnp.maximum(zn - zz, 0)


def run(cfg: dict, weights: dict, audio: np.ndarray, lengths: np.ndarray,
        dtype=jnp.float32) -> dict:
    """audio (B, N), zero past each row's ``lengths`` -> the accumulators
    ``acc`` (B, P) and the class scores ``p`` (B, C), as float32."""
    fb = cfg["filterbank"]
    bp, lp = design.taps(fb)
    cast = lambda a: jnp.asarray(np.asarray(a, np.float32), dtype)
    x = cast(audio)
    n = jnp.asarray(lengths, jnp.int32)
    gamma = jnp.asarray(float(fb["gamma_f"]), dtype)
    accs = []
    for o in range(len(bp)):
        last = o == len(bp) - 1
        acc, x = _octave(x, n, cast(bp[o]), cast(lp[o] if not last
                                                  else lp[0]),
                         gamma, octave=o, last=last)
        accs.append(acc)
        n = (n + 1) >> 1
    acc = jnp.concatenate(accs, axis=1)
    w = {k: cast(weights[k]) for k in ("mu", "sigma", "w_pos", "w_neg",
                                        "b_pos", "b_neg")}
    p = _readout(acc, w["mu"], w["sigma"], w["w_pos"], w["w_neg"],
                 w["b_pos"], w["b_neg"],
                 jnp.asarray(float(cfg["classifier"]["gamma1"]), dtype))
    return {"acc": np.asarray(acc.astype(jnp.float32)),
            "p": np.asarray(p.astype(jnp.float32))}
