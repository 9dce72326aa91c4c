"""Margin Propagation (MP) primitives — the paper's core contribution.

The MP function ``z = MP(L, gamma)`` is defined implicitly by the *reverse
water-filling* constraint (Gu [40], Chakrabartty & Cauwenberghs [26]):

    sum_i [L_i - z]_+  =  gamma,        gamma > 0

Two solvers are provided:

* :func:`mp_exact` — closed form via sort/cumsum/threshold-count. This is the
  mathematically exact solution (identical to the threshold in a simplex
  projection of ``L`` onto the scaled simplex ``{p >= 0, sum p = gamma}``).
  Differentiable through a ``custom_vjp`` using the known subgradient
  ``dz/dL_i = 1{L_i > z} / |support|``, ``dz/dgamma = -1/|support|``.
  Used for training (the paper trains *through* the MP approximation).

* :func:`mp_bisect` — the hardware-faithful iterative solver: bisection on
  ``z`` inside ``[max(L) - gamma, max(L)]`` using only add/subtract/compare
  and halving (a shift in fixed point). A fixed iteration count makes it a
  static ``fori_loop`` — this is what the Pallas TPU kernels implement
  (no sort needed; sorts are expensive on the TPU VPU, compares are cheap).

Multiplierless inner products (paper eq. 9): for ``u = w + x``, ``v = w - x``
(elementwise),

    w.x  ~=  mpabs(u, gamma) - mpabs(v, gamma),
    mpabs(u, gamma) := MP([u; -u], gamma)

since ``[w+ + x+, w- + x-] = [u; -u]`` and ``[w+ + x-, w- + x+] = [v; -v]``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "tree_sum",
    "mp_exact",
    "mp",
    "mp_bisect",
    "mp_newton",
    "mpabs",
    "mpabs_newton",
    "mp_dot",
    "mp_linear",
    "mp_conv1d",
    "mp_conv1d_bank",
    "DEFAULT_BISECT_ITERS",
    "DEFAULT_NEWTON_ITERS",
]

DEFAULT_BISECT_ITERS = 26  # |interval| * 2^-26 < 1e-7 * gamma: fp32-parity
DEFAULT_NEWTON_ITERS = 12  # monotone Newton: lands exactly on the root
                           # segment; 12 steps beat bisect-26 empirically


def tree_sum(h: jax.Array) -> jax.Array:
    """Sum over the last axis as a FIXED pairwise halving tree.

    ``jnp.sum`` lowers to a reduce HLO whose internal association order is a
    codegen detail — it can change with the surrounding fusion context, so
    two graphs computing "the same" f32 sum of identical operands may differ
    by ulps. The streaming-parity contract (XLA session step == Pallas
    streaming kernel, bit for bit; single-chunk streaming == one-shot)
    needs every float reduction on that path to be an explicit add DAG that
    XLA must evaluate as written. Zero-padding to a power of two is exact:
    every operand fed in is >= +0.0 or the pad lanes only ever add +0.0.

    Cost: log2(n) strided vector adds — on par with a reduce, and the
    fixed-iteration solvers were already bandwidth-bound on the operands.
    """
    n = h.shape[-1]
    if n == 0:
        return jnp.zeros(h.shape[:-1], h.dtype)
    p = 1
    while p < n:
        p <<= 1
    if p != n:
        h = jnp.pad(h, [(0, 0)] * (h.ndim - 1) + [(0, p - n)])
    while h.shape[-1] > 1:
        h = h[..., 0::2] + h[..., 1::2]
    return h[..., 0]


# ---------------------------------------------------------------------------
# Exact solver (sort based) with custom VJP
# ---------------------------------------------------------------------------


def _mp_exact_fwd_impl(L: jax.Array, gamma: jax.Array) -> jax.Array:
    """Exact reverse water-filling along the last axis.

    L: (..., m); gamma: broadcastable to (...,). Returns z: (...,).
    """
    m = L.shape[-1]
    # sort descending
    s = jnp.flip(jnp.sort(L, axis=-1), axis=-1)
    cs = jnp.cumsum(s, axis=-1)
    k = jnp.arange(1, m + 1, dtype=L.dtype)
    gamma_b = jnp.asarray(gamma, dtype=L.dtype)[..., None]
    z_k = (cs - gamma_b) / k
    # support size k* = #{k : s_k > z_k}; monotone as in simplex projection.
    valid = s > z_k
    k_star = jnp.maximum(jnp.sum(valid, axis=-1), 1)
    cs_sel = jnp.take_along_axis(cs, (k_star - 1)[..., None], axis=-1)[..., 0]
    z = (cs_sel - jnp.asarray(gamma, dtype=L.dtype)) / k_star.astype(L.dtype)
    return z


@jax.custom_vjp
def mp_exact(L: jax.Array, gamma: jax.Array) -> jax.Array:
    """z = MP(L, gamma) along the last axis (exact, differentiable)."""
    return _mp_exact_fwd_impl(L, gamma)


def _mp_exact_fwd(L, gamma):
    z = _mp_exact_fwd_impl(L, gamma)
    return z, (L, z)


def _mp_exact_bwd(res, g):
    L, z = res
    support = (L > z[..., None]).astype(L.dtype)
    k = jnp.maximum(jnp.sum(support, axis=-1), 1.0)
    dL = g[..., None] * support / k[..., None]
    # dz/dgamma = -1/k ; reduce to gamma's shape via broadcasting rules.
    dgamma_full = -g / k
    dgamma = dgamma_full.sum()  # gamma is scalar in all our uses
    return dL, jnp.asarray(dgamma, dtype=jnp.result_type(dgamma_full))


mp_exact.defvjp(_mp_exact_fwd, _mp_exact_bwd)

# Public alias: `mp` is the trainable exact form.
mp = mp_exact


def mp_bisect(
    L: jax.Array,
    gamma: jax.Array,
    iters: int = DEFAULT_BISECT_ITERS,
) -> jax.Array:
    """Hardware-faithful MP via bisection (add/compare/shift only).

    The constraint function h(z) = sum_i [L_i - z]_+ is continuous, strictly
    decreasing where positive. h(max L) = 0 <= gamma and at
    z = max(L) - gamma the max element alone contributes gamma, so the root
    lies in [max(L) - gamma, max(L)].
    """
    gamma = jnp.asarray(gamma, dtype=L.dtype)
    hi = jnp.max(L, axis=-1)
    lo = hi - gamma

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) * jnp.asarray(0.5, L.dtype)  # shift in fixed point
        h = tree_sum(jnp.maximum(L - mid[..., None], 0))
        too_low = h > gamma  # z too small -> move lo up
        lo = jnp.where(too_low, mid, lo)
        hi = jnp.where(too_low, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return (lo + hi) * jnp.asarray(0.5, L.dtype)


def mp_newton(
    L: jax.Array,
    gamma: jax.Array,
    iters: int = DEFAULT_NEWTON_ITERS,
) -> jax.Array:
    """MP via monotone Newton on the water-filling constraint.

    h(z) = sum_i [L_i - z]_+ is convex, piecewise linear, decreasing with
    slope -k(z) where k = |{i : L_i > z}|. Starting LEFT of the root
    (z0 = max L - gamma, where h >= gamma) every Newton step
    ``z += (h(z) - gamma)/k`` jumps to its tangent's root: the tangent
    under-estimates h (convexity), so the iterate never overshoots and is
    monotone increasing; once it reaches the root's linear segment the
    tangent IS h and it lands exactly. ~12 fixed steps beat 26 bisections
    both in accuracy and wall time — at the price of a divide, so this is
    the fast SOFTWARE solver; ``mp_bisect`` remains the hardware-faithful
    add/compare/shift reference.
    """
    gamma = jnp.asarray(gamma, dtype=L.dtype)
    z = jnp.max(L, axis=-1) - gamma

    def body(_, z):
        zc = z[..., None]
        s = tree_sum(jnp.maximum(L - zc, 0))
        k = jnp.sum(L > zc, axis=-1).astype(L.dtype)  # int count: exact
        return z + (s - gamma) / jnp.maximum(k, 1.0)

    return jax.lax.fori_loop(0, iters, body, z)


def mpabs_newton(
    u: jax.Array,
    gamma: jax.Array,
    iters: int = DEFAULT_NEWTON_ITERS,
) -> jax.Array:
    """MP([u; -u], gamma) via monotone Newton (see ``mp_newton``), without
    materializing the concatenation: h(z) over [u; -u] splits into the
    |u| branch plus the -|u| branch (active only when z < -min|u|)."""
    gamma = jnp.asarray(gamma, dtype=u.dtype)
    a = jnp.abs(u)
    z = jnp.max(a, axis=-1) - gamma

    def body(_, z):
        zc = z[..., None]
        s = (tree_sum(jnp.maximum(a - zc, 0))
             + tree_sum(jnp.maximum(-a - zc, 0)))
        k = (jnp.sum(a > zc, axis=-1)
             + jnp.sum(-a > zc, axis=-1)).astype(u.dtype)  # int counts
        return z + (s - gamma) / jnp.maximum(k, 1.0)

    return jax.lax.fori_loop(0, iters, body, z)


# ---------------------------------------------------------------------------
# Multiplierless inner products
# ---------------------------------------------------------------------------


def mpabs(u: jax.Array, gamma: jax.Array, exact: bool = True,
          iters: int = DEFAULT_BISECT_ITERS) -> jax.Array:
    """MP([u; -u], gamma) along the last axis, without materializing [u;-u].

    Materialization-free for the bisect path: h(z) over [u;-u] equals
    sum [u - z]_+ + sum [-u - z]_+. For the exact path we concatenate (the
    training path; XLA fuses it).
    """
    if exact:
        return mp_exact(jnp.concatenate([u, -u], axis=-1), gamma)
    gamma = jnp.asarray(gamma, dtype=u.dtype)
    a = jnp.abs(u)  # |u| = max(u, -u): compare/select, allowed primitive
    hi = jnp.max(a, axis=-1)
    lo = hi - gamma

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) * jnp.asarray(0.5, u.dtype)
        h = (tree_sum(jnp.maximum(u - mid[..., None], 0))
             + tree_sum(jnp.maximum(-u - mid[..., None], 0)))
        too_low = h > gamma
        lo = jnp.where(too_low, mid, lo)
        hi = jnp.where(too_low, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return (lo + hi) * jnp.asarray(0.5, u.dtype)


def mp_dot(x: jax.Array, w: jax.Array, gamma: jax.Array,
           exact: bool = True) -> jax.Array:
    """Multiplierless approximation of the inner product <x, w> (eq. 9).

    x, w: (..., d) broadcast-compatible. Returns (...,).
    """
    u = w + x
    v = w - x
    return mpabs(u, gamma, exact=exact) - mpabs(v, gamma, exact=exact)


def mp_linear(
    x: jax.Array,
    w: jax.Array,
    gamma: jax.Array,
    b: Optional[jax.Array] = None,
    exact: bool = True,
    block_out: int = 128,
) -> jax.Array:
    """Multiplierless matrix-vector/matrix product: (..., d) @ (d, out).

    Each output scalar y[..., o] = mpabs(w[:,o] + x) - mpabs(w[:,o] - x).
    Blocks over the output dim to bound the (..., block_out, d) intermediate.
    This is the pure-jnp reference path; the Pallas kernel
    (repro.kernels.mp_linear) is the TPU production path.
    """
    d, out = w.shape
    assert x.shape[-1] == d, (x.shape, w.shape)

    def block(wb):  # wb: (d, bo)
        u = wb.T + x[..., None, :]  # (..., bo, d)
        v = wb.T - x[..., None, :]
        return mpabs(u, gamma, exact=exact) - mpabs(v, gamma, exact=exact)

    if out <= block_out:
        y = block(w)
    else:
        pad = (-out) % block_out
        wp = jnp.pad(w, ((0, 0), (0, pad)))
        nb = wp.shape[1] // block_out
        wblocks = wp.reshape(d, nb, block_out).transpose(1, 0, 2)
        y = jax.lax.map(lambda wb: block(wb), wblocks)  # (nb, ..., bo)
        y = jnp.moveaxis(y, 0, -2).reshape(*x.shape[:-1], nb * block_out)
        y = y[..., :out]
    if b is not None:
        y = y + b
    return y


def mp_conv1d(
    x: jax.Array,
    h: jax.Array,
    gamma: jax.Array,
    exact: bool = True,
    solver: str = "newton",
    pad: bool = True,
) -> jax.Array:
    """Multiplierless FIR filtering (paper eq. 8 + 9): y(n) = MP-dot(h, x[n-M+1..n]).

    x: (..., N) signal; h: (M,) taps. 'Valid' part is y[M-1:]; we left-pad
    with zeros so y has the same length as x (matches streaming hardware that
    starts from zeroed register banks). ``pad=False`` computes ONLY the
    valid positions ((..., N-M+1) output, window n = x[n..n+M-1]) — the
    streaming hot path, whose delay-line splice already supplies the
    history, uses this to skip solves that would be sliced away. Window
    contents are identical either way, so the shared positions match
    bitwise. With exact=False, ``solver`` picks the fixed-iteration scheme:
    "newton" (fast software default) or "bisect" (the hardware's
    add/compare/shift loop).
    """
    M = h.shape[0]
    if pad:
        xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(M - 1, 0)])
        n_out = x.shape[-1]
    else:
        xp = x
        n_out = x.shape[-1] - M + 1
    # windows: (..., n_out, M) — window n holds x[n-M+1..n] with taps
    # reversed to implement the convolution sum h(k) x(n-k).
    idx = jnp.arange(n_out)[:, None] + jnp.arange(M)[None, :]
    win = xp[..., idx]  # gather windows
    hr = h[::-1]
    if exact:
        return mp_dot(win, hr, gamma, exact=True)
    return _mp_dot_fast(win, hr, gamma, solver)


def _mp_dot_fast(x: jax.Array, w: jax.Array, gamma, solver: str) -> jax.Array:
    """Fast-solver mp_dot for the (non-differentiable) feature-extraction
    hot path: same eq. 9 operand pairing, fixed-iteration solver."""
    if solver == "newton":
        return mpabs_newton(w + x, gamma) - mpabs_newton(w - x, gamma)
    if solver == "bisect":
        return (mpabs(w + x, gamma, exact=False)
                - mpabs(w - x, gamma, exact=False))
    raise ValueError(f"unknown MP solver: {solver!r}")


# ---------------------------------------------------------------------------
# the same solvers over an unrolled operand list (Pallas kernel bodies)
# ---------------------------------------------------------------------------
#
# A TPU kernel cannot hold a (rows, n, M) window tensor with the tap axis
# minor (Mosaic refuses the gather that builds it), so the streaming
# kernels keep one (rows, n) array per tap. The functions below run the
# SAME per-element operations as ``mpabs_newton`` / ``mpabs`` /
# ``_mp_dot_fast`` over such a list — max chains for ``jnp.max``, integer
# counts for the comparison sums and :func:`tree_sum_terms` for the
# fixed-tree sums — so every output is bit-for-bit the array form's.


def tree_sum_terms(terms: list) -> jax.Array:
    """:func:`tree_sum` over a list of equally shaped operands (the list is
    the summed axis): the same zero-padding to a power of two and the same
    pairwise halving tree, so the result is bitwise ``tree_sum``'s."""
    terms = list(terms)
    p = 1
    while p < len(terms):
        p <<= 1
    terms += [jnp.zeros_like(terms[0])] * (p - len(terms))
    while len(terms) > 1:
        terms = [terms[i] + terms[i + 1] for i in range(0, len(terms), 2)]
    return terms[0]


def _count_terms(masks: list) -> jax.Array:
    """Integer count of true entries across a list of boolean arrays."""
    k = masks[0].astype(jnp.int32)
    for m in masks[1:]:
        k = k + m.astype(jnp.int32)
    return k


def _mpabs_newton_terms(us: list, gamma, iters: int = DEFAULT_NEWTON_ITERS):
    """``mpabs_newton`` with the operand axis unrolled into ``us``."""
    dt = us[0].dtype
    gamma = jnp.asarray(gamma, dtype=dt)
    a = [jnp.abs(u) for u in us]
    z = a[0]
    for t in a[1:]:
        z = jnp.maximum(z, t)
    z = z - gamma

    def body(_, z):
        s = (tree_sum_terms([jnp.maximum(t - z, 0) for t in a])
             + tree_sum_terms([jnp.maximum(-t - z, 0) for t in a]))
        k = (_count_terms([t > z for t in a])
             + _count_terms([-t > z for t in a])).astype(dt)
        return z + (s - gamma) / jnp.maximum(k, 1.0)

    return jax.lax.fori_loop(0, iters, body, z)


def _mpabs_bisect_terms(us: list, gamma,
                        iters: int = DEFAULT_BISECT_ITERS) -> jax.Array:
    """``mpabs(exact=False)`` with the operand axis unrolled into ``us``."""
    dt = us[0].dtype
    gamma = jnp.asarray(gamma, dtype=dt)
    hi = jnp.abs(us[0])
    for u in us[1:]:
        hi = jnp.maximum(hi, jnp.abs(u))
    lo = hi - gamma

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) * jnp.asarray(0.5, dt)
        h = (tree_sum_terms([jnp.maximum(u - mid, 0) for u in us])
             + tree_sum_terms([jnp.maximum(-u - mid, 0) for u in us]))
        too_low = h > gamma
        lo = jnp.where(too_low, mid, lo)
        hi = jnp.where(too_low, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return (lo + hi) * jnp.asarray(0.5, dt)


def mp_dot_fast_terms(xs: list, ws: list, gamma, solver: str) -> jax.Array:
    """``_mp_dot_fast`` over unrolled operands: ``xs[k]`` is the k-th
    window column (any shape), ``ws[k]`` its tap (scalar or broadcastable).
    Bitwise equal to ``_mp_dot_fast(stack(xs, -1), stack(ws), ...)``."""
    us = [w + x for x, w in zip(xs, ws)]
    vs = [w - x for x, w in zip(xs, ws)]
    if solver == "newton":
        return _mpabs_newton_terms(us, gamma) - _mpabs_newton_terms(vs, gamma)
    if solver == "bisect":
        return _mpabs_bisect_terms(us, gamma) - _mpabs_bisect_terms(vs, gamma)
    raise ValueError(f"unknown MP solver: {solver!r}")


def mp_conv1d_bank(
    x: jax.Array,
    H: jax.Array,
    gamma: jax.Array,
    exact: bool = True,
    chunk_n: Optional[int] = 1024,
    solver: str = "newton",
    pad: bool = True,
) -> jax.Array:
    """Multi-filter MP FIR: x (..., N), H (F, M) -> y (..., F, N).

    The (N, M) window gather is built ONCE and broadcast against all F tap
    rows (filter axis leading: (F, B, N, M) keeps the MP solve operands in
    the same layout a per-filter vmap produces, which XLA:CPU vectorizes
    measurably better than a (B, F, N, M) broadcast). Long signals are
    solved in ``chunk_n``-sample blocks via lax.map so the fixed-iteration
    solve re-reads cache-resident operands instead of streaming the full
    (F, B, N, M) tensor from DRAM each iteration. Window contents are
    unchanged by chunking, so results match ``mp_conv1d(x, H[f], gamma)``
    exactly per band. ``pad=False``: valid positions only, (..., F, N-M+1)
    (see ``mp_conv1d``).
    """
    F, M = H.shape
    lead = x.shape[:-1]
    N = x.shape[-1]
    x2 = x.reshape(-1, N)
    B = x2.shape[0]
    hr = H[:, ::-1].reshape(F, 1, 1, M)
    n_out = N if pad else N - M + 1

    def solve(win):  # (B, Q, M) -> (F, B, Q)
        if exact:
            return mp_dot(win[None], hr, gamma, exact=True)
        return _mp_dot_fast(win[None], hr, gamma, solver)

    if chunk_n is None or n_out <= chunk_n:
        xp = jnp.pad(x2, ((0, 0), (M - 1, 0))) if pad else x2
        idx = jnp.arange(n_out)[:, None] + jnp.arange(M)[None, :]
        y = solve(xp[:, idx])                          # (F, B, n_out)
    else:
        Q = chunk_n
        xp = jnp.pad(x2, ((0, 0), (M - 1, 0))) if pad else x2
        # right-pad so every Q-block of output positions has a full segment
        n_blocks = -(-n_out // Q)
        xp = jnp.pad(xp, ((0, 0), (0, n_blocks * Q + M - 1 - xp.shape[1])))
        idx = jnp.arange(Q)[:, None] + jnp.arange(M)[None, :]

        def one(start):  # windows for output positions [start, start+Q)
            seg = jax.lax.dynamic_slice_in_dim(xp, start, Q + M - 1, axis=1)
            return solve(seg[:, idx])

        ys = jax.lax.map(one, jnp.arange(n_blocks) * Q)  # (nc, F, B, Q)
        y = jnp.moveaxis(ys, 0, 2).reshape(F, B, n_blocks * Q)[..., :n_out]
    return jnp.moveaxis(y, 0, 1).reshape(*lead, F, n_out)
