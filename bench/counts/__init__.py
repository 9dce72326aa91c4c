"""Operations and bytes of the session step, from the configuration's
arithmetic and the call shapes, never from an implementation's jaxpr.

An operation is one add, subtract, compare, select or shift on one
element (a max or a clamp bound counts as one compare). Terms, per output
position of one filter (M taps, ``it`` solver iterations):

* MPdot (paper eq. 9) builds the operands h_k + x_k and h_k - x_k
  (fixed: each then clamped to the adder format, 2 compares), then solves
  MP([u; -u]) for u and for v and subtracts the two.
* one MP([u; -u]) solve: |u| and the max to start (2M); then per step,
  fixed (bisection): midpoint add and shift (2), the two branch sums of
  [+-u - z]_+ (2 x (M subtracts, M maxes, M - 1 adds) + 1 join), the
  compare and two selects (3): 6M + 4. Float (monotone Newton): the same
  two branch sums (6M - 1), the two branch support counts
  (2 x (M compares, M - 1 adds) + 1 = 4M - 1) and the update
  (subtract, max, divide, add = 4): 10M + 2.
* half-wave rectify and accumulate: a max, a mask select, an add (3).

Per octave o the band-pass bank solves F filters at every position the
octave receives, and the low-pass (M_lp taps) solves only the positions
the /2 decimator keeps, each then requantised (fixed: shift and clamp,
3). The readout solves the kernel machine (eq. 2-7) once per decision.

Solver steps: float runs the configuration's Newton steps; fixed counts
the least bisection steps that shrink an interval of gamma codes to one
code, ceil(log2(gamma)), so steps an implementation spends beyond that
are not counted as work.

Bytes of one kernel call are its HBM traffic at the call shape, 4 bytes
an element: the chunk, the per-slot valid counts and decimator phases,
the delay line, accumulator and running-amax registers read and written
back, and the next octave's signal written.
"""

from __future__ import annotations

import functools
import json
import math

from bench.reference import fixed_ref

WORD = 4


@functools.lru_cache(maxsize=8)
def _plan(key: str):
    cfg = json.loads(key)
    return fixed_ref.plan(cfg, _unit_weights(cfg))


def plan(cfg: dict):
    """The fixed plan's solver gammas (they depend on no seeded weight)."""
    return _plan(json.dumps(cfg, sort_keys=True))


def _iters(cfg: dict) -> tuple:
    """(band-pass, low-pass) solver steps per octave."""
    fb = cfg["filterbank"]
    if cfg["numerics"] == "fixed":
        p = plan(cfg)
        need = lambda g: max(1, math.ceil(math.log2(g)))
        lp = list(p.lp) + [p.lp[-1]]
        return [(need(b.gamma), need(l.gamma)) for b, l in zip(p.bp, lp)]
    n = int(fb["solver_iters"])
    return [(n, n)] * int(fb["num_octaves"])


def _unit_weights(cfg: dict) -> dict:
    import numpy as np
    fb, c = cfg["filterbank"], cfg["classifier"]
    P = int(fb["num_octaves"]) * int(fb["filters_per_octave"])
    C = int(c["num_classes"])
    w = float(c["weight_scale"])
    return {"w_pos": np.full((P, C), w / 2), "w_neg": np.full((P, C), w / 2),
            "b_pos": np.zeros(C), "b_neg": np.zeros(C),
            "mu": np.zeros(P), "sigma": np.full(P, 1.0)}


def mpabs(m: int, it: int, fixed: bool) -> int:
    return 2 * m + it * ((6 * m + 4) if fixed else (10 * m + 2))


def mp_dot(m: int, it: int, fixed: bool) -> int:
    return (6 * m if fixed else 2 * m) + 2 * mpabs(m, it, fixed) + 1


def octave_lengths(n: int, octaves: int) -> list:
    out = []
    for _ in range(octaves):
        out.append(n)
        n = (n + 1) // 2
    return out


def octave_ops(cfg: dict, o: int, positions: int) -> int:
    """Operations of octave ``o`` over ``positions`` of its own signal."""
    fb = cfg["filterbank"]
    fixed = cfg["numerics"] == "fixed"
    F, M, M_lp = (int(fb["filters_per_octave"]), int(fb["bp_taps"]),
                  int(fb["lp_taps"]))
    it_bp, it_lp = _iters(cfg)[o]
    ops = positions * F * (mp_dot(M, it_bp, fixed) + 3)
    if o < int(fb["num_octaves"]) - 1:
        kept = (positions + 1) // 2
        ops += kept * (mp_dot(M_lp, it_lp, fixed) + (3 if fixed else 0))
    return ops


def octave_bytes(cfg: dict, o: int, slots: int, length: int) -> int:
    fb = cfg["filterbank"]
    F = int(fb["filters_per_octave"])
    T1 = max(int(fb["bp_taps"]), int(fb["lp_taps"])) - 1
    elems = slots * (length + 2 + 2 * T1 + 2 * F + 2)
    if o < int(fb["num_octaves"]) - 1:
        elems += slots * ((length + 1) // 2)
    return elems * WORD


def kernel_calls(cfg: dict, slots: int, length: int) -> list:
    """``(ops, bytes)`` of each octave's kernel call in one session step
    over ``slots`` slots at the padded chunk length ``length``: the calls
    of ``fir_mp_stream_q`` for a fixed configuration, of
    ``fir_mp_stream`` for a float one."""
    octaves = int(cfg["filterbank"]["num_octaves"])
    return [(slots * octave_ops(cfg, o, n), octave_bytes(cfg, o, slots, n))
            for o, n in enumerate(octave_lengths(length, octaves))]


def readout_ops(cfg: dict) -> int:
    """One decision: standardise the P accumulators, build the operands
    w+ + K, w- - K (and the swapped pair) for each of C classes, solve the
    two class-wise MPs over 2P + 1 operands and the normalising MP over
    two, and form p = [z+ - z]_+ - [z- - z]_+ (4 per class)."""
    fb, c = cfg["filterbank"], cfg["classifier"]
    fixed = cfg["numerics"] == "fixed"
    P = int(fb["num_octaves"]) * int(fb["filters_per_octave"])
    C = int(c["num_classes"])
    if fixed:
        p = plan(cfg)
        it1 = max(1, math.ceil(math.log2(p.gamma1)))
        itn = max(1, math.ceil(math.log2(p.gamman)))
        step = lambda k: 3 * k + 5        # midpoint, branch sum, selects
    else:
        it1 = itn = int(fb["solver_iters"])
        step = lambda k: 5 * k + 4        # branch sum, count, update
    solve = lambda k, it: 2 * k + it * step(k)
    std = P * (4 if fixed else 2)
    build = 2 * C * 2 * P * (3 if fixed else 1)
    return (std + build + 2 * C * solve(2 * P + 1, it1) + C * solve(2, itn)
            + 4 * C)


def step_ops(cfg: dict, samples: dict) -> int:
    """Required operations of advancing streams and deciding each:
    ``samples`` maps a request's sample count to how many requests had
    it."""
    octaves = int(cfg["filterbank"]["num_octaves"])
    per = lambda n: (sum(octave_ops(cfg, o, m) for o, m in
                         enumerate(octave_lengths(int(n), octaves)))
                     + readout_ops(cfg))
    return sum(k * per(n) for n, k in samples.items())
