"""Public jit'd wrappers around the Pallas MP kernels.

Responsibilities:
  * compiled on the TPU, interpret mode on the CPU, an error elsewhere;
  * shape canonicalization (leading batch dims flattened);
  * default block shapes from the committed autotune table
    (``stream_shapes.best_block_s``, refreshed by
    ``benchmarks/kernel_sweep.py --update-table``);
  * a custom VJP for `mp_linear` so the multiplierless layer is trainable
    end-to-end: forward runs the fused Pallas kernel, backward applies the
    water-filling subgradient (support-set masks recomputed from z — the
    same trick as softmax-recompute in flash attention: cheaper to rebuild
    the mask than to store it).

The integer wrappers (``fir_mp_bank_q*``, ``fir_mp_stream_q``) drive the
fixed-point datapaths; ``fir_mp_stream`` and ``fir_mp_stream_q`` share one
streaming kernel and one per-octave cascade. ``fir_mp_stream_q`` is NOT
itself jitted: it takes the compiled ``fixed.FixedPointProgram``
(host-side ROMs and shift tables), so — exactly like
``fixed.session_step_q`` — callers jit a closure over a concrete program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fir_mp as _fir
from repro.kernels import mp_linear as _lin
from repro.kernels import mp_waterfill as _wf
from repro.kernels.stream_shapes import best_block_s


def _interpret() -> bool:
    """Compiled on the TPU, interpret mode on the CPU (tests and rehearsal
    runs). Any other backend is an error: timing the Pallas interpreter on
    an accelerator would report a device that never ran the kernel."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile only for the TPU (interpret mode on the "
        f"CPU); the default backend is {backend!r}")


# ---------------------------------------------------------------------------
# mp_waterfill
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("iters",))
def mp_waterfill(L: jax.Array, gamma, *, iters: int = _wf.DEFAULT_ITERS):
    """z = MP(L, gamma) along the last axis; any leading batch shape."""
    lead = L.shape[:-1]
    L2 = L.reshape(-1, L.shape[-1])
    z = _wf.mp_waterfill_pallas(L2, gamma, iters=iters, interpret=_interpret())
    return z.reshape(lead)


# ---------------------------------------------------------------------------
# mp_linear with custom VJP
# ---------------------------------------------------------------------------


def _mp_linear_fwd_impl(x2, w, gamma, iters):
    return _lin.mp_linear_pallas(x2, w, gamma, iters=iters,
                                 interpret=_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _mp_linear_core(x2, w, gamma, iters):
    return _mp_linear_fwd_impl(x2, w, gamma, iters)


def _mp_linear_vjp_fwd(x2, w, gamma, iters):
    y = _mp_linear_fwd_impl(x2, w, gamma, iters)
    return y, (x2, w, gamma)


def _mp_linear_vjp_bwd(iters, res, g):
    x2, w, gamma = res
    gamma = jnp.asarray(gamma, x2.dtype)
    # Recompute the two water-fill levels exactly (small: sort over d per
    # (b, o) pair) and form support masks.
    u = x2[:, None, :] + w.T[None, :, :]          # (B, O, d)
    v = x2[:, None, :] - w.T[None, :, :]

    def z_and_masks(t):
        L = jnp.concatenate([t, -t], axis=-1)
        from repro.core.mp import mp_exact
        z = mp_exact(L, gamma)
        s_pos = (t > z[..., None]).astype(x2.dtype)     # d/dt_i of z over +t
        s_neg = (-t > z[..., None]).astype(x2.dtype)    # over -t branch
        k = jnp.maximum(jnp.sum(s_pos + s_neg, -1), 1.0)
        return (s_pos - s_neg) / k[..., None]           # dz/dt_i

    du = z_and_masks(u)       # dz_u/du_i
    dv = z_and_masks(v)       # dz_v/dv_i
    # y = z_u - z_v;  du/dx=+1, du/dw=+1, dv/dx=+1, dv/dw=-1 (v = x - w?) --
    # NOTE: kernel uses u = x + w, v = x - w (see mp_linear kernel).
    gy = g[..., None]                                  # (B, O, 1)
    dx = jnp.sum(gy * (du - dv), axis=1)               # (B, d)
    dw = jnp.sum(gy * (du + dv), axis=0).T             # (d, O)
    # dz/dgamma = -1/k for each solve
    dgamma = jnp.zeros((), x2.dtype)  # gamma non-trained in the kernel path
    return dx, dw, dgamma


_mp_linear_core.defvjp(_mp_linear_vjp_fwd, _mp_linear_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("iters",))
def mp_linear(x: jax.Array, w: jax.Array, gamma,
              *, iters: int = _lin.DEFAULT_ITERS):
    """Multiplierless (..., d) @ (d, O) via the fused Pallas kernel."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = _mp_linear_core(x2, w, jnp.asarray(gamma, x.dtype), iters)
    return y.reshape(*lead, w.shape[1])


# ---------------------------------------------------------------------------
# fir_mp
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("iters",))
def fir_mp(x: jax.Array, h: jax.Array, gamma, *, iters: int = _fir.DEFAULT_ITERS):
    """In-filter MP FIR: x (..., N), h (M,) -> y (..., N)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = _fir.fir_mp_pallas(x2, h, gamma, iters=iters, interpret=_interpret())
    return y.reshape(*lead, x.shape[-1])


@functools.partial(jax.jit, static_argnames=("iters",))
def fir_mp_accumulate(x: jax.Array, h: jax.Array, gamma,
                      *, iters: int = _fir.DEFAULT_ITERS):
    """Fused FIR + HWR + accumulate: x (..., N), h (M,) -> s (...)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    s = _fir.fir_mp_pallas(x2, h, gamma, accumulate=True, iters=iters,
                           interpret=_interpret())
    return s.reshape(lead)


@functools.partial(jax.jit, static_argnames=("iters",))
def fir_mp_bank(x: jax.Array, H: jax.Array, gamma,
                *, iters: int = _fir.DEFAULT_ITERS):
    """Multi-filter in-filter MP FIR: x (..., N), H (F, M) -> y (..., F, N).

    One pallas_call covers the whole bank; the signal block is read from HBM
    once and shared by all F filters (vs F reads with per-filter fir_mp)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = _fir.fir_mp_bank_pallas(x2, H, gamma, iters=iters,
                                interpret=_interpret())      # (F, B, N)
    y = jnp.moveaxis(y, 0, 1)                                # (B, F, N)
    return y.reshape(*lead, H.shape[0], x.shape[-1])


@functools.partial(jax.jit, static_argnames=("iters",))
def fir_mp_bank_accumulate(x: jax.Array, H: jax.Array, gamma,
                           *, iters: int = _fir.DEFAULT_ITERS):
    """Fused bank FIR + HWR + accumulate: x (..., N), H (F, M) -> s (..., F).

    The paper's per-band accumulator readout for a full octave in a single
    kernel invocation: one HBM read of the signal -> F scalar features."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    s = _fir.fir_mp_bank_pallas(x2, H, gamma, accumulate=True, iters=iters,
                                interpret=_interpret())      # (B, F)
    return s.reshape(*lead, H.shape[0])


# ---------------------------------------------------------------------------
# fir_mp_stream / fir_mp_stream_q: the session-shaped streaming step
# ---------------------------------------------------------------------------


def _stream_cascade(chunk, n, delays, consumed, acc, amax, paths, *,
                    update_amax: bool, block_s: int | None):
    """The per-octave loop of both streaming entry points: one
    ``fir_mp_stream_octave`` pallas_call per octave, ``paths[o]`` its
    datapath. Each call carries that octave's delay line, per-band
    accumulator partials and (octave 0) running amax in VMEM scratch across
    its chunk-block grid steps — the per-chunk state never round-trips
    through HBM inside the step, and the [delay, chunk] splice happens in
    VMEM rather than as an XLA concatenation. The decimated signal hops
    octaves through HBM exactly once, like the XLA path's octave cascade.

    The decimator phase is a bit-AND and the kept count a shift, not a
    remainder and a divide (the counts are non-negative), so the integer
    census stays divider-free, as in ``fixed.session_step_q``.
    ``block_s=None`` takes the slot tile from ``stream_shapes`` under the
    kernel's name."""
    S, L = chunk.shape
    if block_s is None:
        block_s = best_block_s(paths[0].name, S)
    x_o = chunk
    n_o = jnp.asarray(n, jnp.int32)
    l_o = L
    new_delays, new_consumed, acc_cols = [], [], []
    amax_out = amax
    interpret = _interpret()
    F = acc.shape[1] // len(paths)          # every octave has F bands
    for o, path in enumerate(paths):
        emit = o < len(paths) - 1
        start_o = jnp.bitwise_and(consumed[o], 1).astype(jnp.int32)
        acc_o = jax.lax.slice_in_dim(acc, o * F, (o + 1) * F, axis=1)
        amax_in = amax if o == 0 else jnp.zeros((S,), chunk.dtype)
        acc_new, delay_new, amax_new, y_next = _fir.fir_mp_stream_octave(
            x_o, n_o, start_o, delays[o], acc_o, amax_in, path,
            emit_next=emit, update_amax=(update_amax and o == 0),
            block_s=block_s, interpret=interpret, octave=o)
        if o == 0 and update_amax:
            amax_out = amax_new
        new_delays.append(delay_new)
        new_consumed.append(consumed[o] + n_o)
        acc_cols.append(acc_new)
        if emit:
            l_next = (l_o + 1) // 2
            x_o = y_next[:, :l_next]
            n_o = jnp.right_shift(jnp.maximum(n_o - start_o + 1, 0), 1)
            l_o = l_next
    return (tuple(new_delays), tuple(new_consumed),
            jnp.concatenate(acc_cols, axis=1), amax_out)


@functools.partial(jax.jit,
                   static_argnames=("solver", "update_amax", "block_s"))
def fir_mp_stream(chunk: jax.Array, n: jax.Array, delays: tuple,
                  consumed: tuple, acc: jax.Array, amax: jax.Array,
                  bp_taps: tuple, lp_taps: tuple, gamma, *,
                  solver: str = "newton", update_amax: bool = True,
                  block_s: int | None = None):
    """Stateful multirate session step through the Pallas streaming kernel
    on its float datapath (kernels ``fir_mp_stream_o<k>``).

    chunk (S, L): one slot-batched chunk, invalid tails already zeroed (and
    quantized, if deployed quantized — in which case pass the pre-updated
    running amax and ``update_amax=False``; without quantization the octave-0
    kernel updates the running amax in VMEM scratch itself). ``n`` (S,) are
    per-slot valid counts (0 for masked/inert slots), ``delays``/``consumed``
    per-octave register tuples, ``acc`` (S, P) the concatenated per-band
    accumulators, ``bp_taps[o]`` (F, M) / ``lp_taps[o]`` (M_lp,) the
    precomputed filters.

    Returns ``(delays', consumed', acc', amax')``. Masked slots (n == 0)
    are inert: their registers come back bit-identical (delay slides by 0,
    accumulator contributions are exactly +0.0). ``block_s=None`` (default)
    consults the committed autotune table (``stream_shapes``) for the
    best-known slot tile at this capacity — shape choice never changes
    values, only VMEM tiling.
    """
    last = len(delays) - 1
    paths = [_fir.FloatPath(bp_taps[o], lp_taps[o] if o < last else None,
                            gamma, solver, 2.0 ** o)
             for o in range(len(delays))]
    return _stream_cascade(chunk, n, delays, consumed, acc, amax, paths,
                           update_amax=update_amax, block_s=block_s)


def fir_mp_stream_q(prog, chunk_q: jax.Array, n: jax.Array, delays: tuple,
                    consumed: tuple, acc: jax.Array, amax: jax.Array, *,
                    block_s: int | None = None):
    """Stateful INTEGER multirate session step through the Pallas streaming
    kernel on its integer datapath (kernels ``fir_mp_stream_q_o<k>``): the
    VMEM-resident twin of ``fixed.session_step_q``'s octave cascade.

    ``prog`` is the compiled ``fixed.FixedPointProgram`` (static ROMs/shift
    tables — which is why this wrapper is not itself jitted: jit a closure
    over a concrete program, exactly like ``session_step_q``). ``chunk_q``
    (S, L) is ADC codes with invalid tails already zeroed; ``n`` (S,)
    effective valid counts; ``delays``/``consumed``/``acc``/``amax`` the
    integer session registers. Requires mode "mp" and L >= 1 (the caller
    handles the L == 0 pure-readout step).

    Every in-kernel op is shift/add/compare, and the result registers are
    bit-for-bit ``session_step_q``'s (and therefore bit-for-bit one-shot
    ``infer_q`` under any chunking — the fixed-grid exactness argument in
    docs/numerics.md). Returns ``(delays', consumed', acc', amax')``.
    """
    octaves = prog.bank.octaves
    paths = [_fir.FixedPath(st, nxt.in_spec if nxt is not None else None)
             for st, nxt in zip(octaves, octaves[1:] + (None,))]
    return _stream_cascade(chunk_q, n, delays, consumed, acc, amax, paths,
                           update_amax=True, block_s=block_s)


# ---------------------------------------------------------------------------
# integer (fixed-point) bank wrappers: the VMEM-resident hardware twin
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("gamma_q", "iters", "qmin", "qmax"))
def fir_mp_bank_q(xq: jax.Array, H_q: jax.Array, *, gamma_q: int,
                  iters: int, qmin: int, qmax: int):
    """Integer bank FIR through the fused Pallas kernel: xq (..., N) signal
    codes already on the stage's internal grid, H_q (F, M) tap codes ->
    (..., F, N) band codes, bit-for-bit ``fixed.fxp_fir_bank(pad=True)``.
    ``gamma_q``/``iters``/``qmin``/``qmax`` are static program constants."""
    lead = xq.shape[:-1]
    x2 = xq.reshape(-1, xq.shape[-1])
    y = _fir.fir_mp_bank_q_pallas(x2, H_q, gamma_q=gamma_q, iters=iters,
                                  qmin=qmin, qmax=qmax,
                                  interpret=_interpret())      # (F, B, N)
    y = jnp.moveaxis(y, 0, 1)                                  # (B, F, N)
    return y.reshape(*lead, H_q.shape[0], xq.shape[-1])


@functools.partial(jax.jit,
                   static_argnames=("gamma_q", "iters", "qmin", "qmax"))
def fir_mp_bank_q_accumulate(xq: jax.Array, H_q: jax.Array, *, gamma_q: int,
                             iters: int, qmin: int, qmax: int):
    """Fused integer bank FIR + HWR + accumulate: xq (..., N) -> (..., F)
    integer sums at the stage grid (the caller applies ``acc_shift``).
    One HBM read of the signal codes serves the whole octave's filter set
    AND the paper's per-band accumulator readout."""
    lead = xq.shape[:-1]
    x2 = xq.reshape(-1, xq.shape[-1])
    s = _fir.fir_mp_bank_q_pallas(x2, H_q, gamma_q=gamma_q, iters=iters,
                                  qmin=qmin, qmax=qmax, accumulate=True,
                                  interpret=_interpret())      # (B, F)
    return s.reshape(*lead, H_q.shape[0])
