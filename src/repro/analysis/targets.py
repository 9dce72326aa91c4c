"""The standard analysis targets: the deployed integer programs, traced,
with their documented worst-case input assumptions.

Each :class:`Target` pairs a traced ``ClosedJaxpr`` with one
:class:`~repro.analysis.intervals.Interval` per flattened program input.
The assumptions are the deployment contract, not guesses:

* **ADC codes** are ``FixedPointSpec.qmin..qmax`` by construction — the
  quantizer clamps (``quantize_signal``), exactly like the hardware ADC
  saturates. The proof covers EVERY signal, not sampled audio.
* **Delay-line registers** hold each octave's 8-bit signal-register codes
  (``OctaveStage.in_spec``) — written only by the clamped requantizers.
* **Session accumulators** are bounded by the 1-second one-shot envelope:
  per octave, (octave samples in 1 s) x (band full-scale) x 2^acc_shift.
  Integer accumulation grows without bound in an endless session, so the
  proof is explicitly "sessions totalling <= 1 s of audio" — the paper's
  per-utterance deployment. :func:`session_envelope` also reports the
  closed-form maximum session length before any int32 accumulator can
  overflow, which the analyze report and benchmarks surface.
* **Sample counters** (``consumed``/``count``) are bounded by
  ``SESSION_BOUND`` (2^30 samples ~ 18 h at 16 kHz) — far past the
  accumulator-safe envelope, so the counters are never the binding
  constraint.
"""

from __future__ import annotations

import dataclasses
from fractions import Fraction

from repro.analysis.intervals import BOOL, Interval

# counter registers: 2^30 octave samples (~18 h @ 16 kHz) — generous
# headroom past any accumulator-safe session length
SESSION_BOUND = 1 << 30

INT32_MAX = (1 << 31) - 1

# one 10 ms sensor packet at 16 kHz — the deployment chunk the FPGA (and
# benchmarks/hardware_cost.py) processes per step
CHUNK_LEN = 160


@dataclasses.dataclass
class Target:
    """One traced program plus its analysis contract."""
    name: str
    jaxpr: object                     # ClosedJaxpr
    numerics: str                     # "fixed" | "float"
    n_samples: int                    # input samples per call (for rates)
    in_intervals: list | None         # None: skip the interval pass
    assumptions: dict                 # input name -> contract, for the report
    gate: bool                        # violations fail scripts/analyze.py


def _fixed_pipeline(smoke: bool, *, stream_impl: str = "xla",
                    numerics: str = "fixed", seed: int = 0):
    from repro.configs.esc10_mp import make_pipeline
    return make_pipeline(smoke=smoke, seed=seed, stream_impl=stream_impl,
                         numerics=numerics)


def _signal_iv(prog) -> Interval:
    s = prog.signal
    return Interval(int(s.qmin), int(s.qmax))


def _shift_int(v: int, k: int) -> int:
    return v << k if k >= 0 else v >> (-k)


def session_envelope(prog, n_envelope: int) -> dict:
    """Closed-form session accumulator bounds.

    Per octave ``o`` the accumulator gains at most
    ``band_spec.qmax << acc_shift`` per octave sample (HWR output is
    nonnegative and clamped), and a length-``N`` session delivers
    ``ceil(N / 2**o)`` octave samples. Returns the worst-case ``acc``
    interval for sessions totalling ``n_envelope`` input samples, plus the
    maximum session length (in input samples) before ANY band's int32
    accumulator can overflow.
    """
    acc_hi = 0
    max_safe = None
    for o, st in enumerate(prog.bank.octaves):
        qmax = int(st.band_spec.qmax)
        shift = int(st.acc_shift)
        n_o = -(-n_envelope // (1 << o))          # ceil
        acc_hi = max(acc_hi, _shift_int(n_o * qmax, shift))
        # growth per INPUT sample for this octave's bands
        g = Fraction(qmax * 2 ** max(shift, 0),
                     2 ** (o + max(-shift, 0)))
        safe_o = int(Fraction(INT32_MAX) / g) if g > 0 else None
        if safe_o is not None:
            max_safe = safe_o if max_safe is None else min(max_safe, safe_o)
    return {
        "acc_interval": Interval(0, acc_hi),
        "envelope_samples": n_envelope,
        "max_safe_session_samples": max_safe,
    }


def _session_inputs(prog, state, chunk_len: int, acc_iv: Interval):
    """Interval pytree matching ``(state, chunk_q, n)`` and flatten it in
    jax's leaf order (what the traced jaxpr's invars use)."""
    import jax

    sig = _signal_iv(prog)
    amax_hi = max(abs(sig.lo), sig.hi)
    counter = Interval(0, SESSION_BOUND)
    ivs_state = state._replace(
        delays=tuple(Interval(int(prog.bank.octaves[o].in_spec.qmin),
                              int(prog.bank.octaves[o].in_spec.qmax))
                     for o in range(len(state.delays))),
        consumed=tuple(counter for _ in state.consumed),
        acc=acc_iv,
        amax=Interval(0, amax_hi),
        count=counter,
        active=BOOL,
    )
    tree = (ivs_state, sig, Interval(0, chunk_len))
    return jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, Interval))


def build_targets(smoke: bool = False) -> tuple:
    """Build the standard target set. Returns ``(targets, meta)`` where
    ``meta`` carries the session envelope figures for the report."""
    import jax
    import jax.numpy as jnp

    from repro.core import fixed

    n = 1600 if smoke else 16000               # 1 s of audio (0.4 s smoke)
    pipe = _fixed_pipeline(smoke)
    prog = pipe.fixed_program()
    sig = _signal_iv(prog)
    env = session_envelope(prog, n)
    acc_iv = env["acc_interval"]

    targets = []

    # -- one-shot integer program (the compiled esc10_mp fixed path) ------
    xq = jnp.zeros((1, n), jnp.int32)
    jaxpr_oneshot = jax.make_jaxpr(lambda q: fixed.infer_q(prog, q))(xq)
    adc = (f"ADC codes in [{sig.lo}, {sig.hi}] "
           f"(FixedPointSpec {prog.signal.bits}-bit, clamped quantizer)")
    targets.append(Target(
        name="oneshot_q", jaxpr=jaxpr_oneshot, numerics="fixed",
        n_samples=n, in_intervals=[sig], assumptions={"xq": adc},
        gate=True))

    # -- one-shot through the fused int Pallas bank kernels ---------------
    jaxpr_pl = jax.make_jaxpr(
        lambda q: fixed.infer_q(prog, q, use_pallas=True))(xq)
    targets.append(Target(
        name="oneshot_q_pallas", jaxpr=jaxpr_pl, numerics="fixed",
        n_samples=n, in_intervals=[sig], assumptions={"xq": adc},
        gate=True))

    # -- per-chunk integer session step (the deployed datapath) -----------
    session_assumptions = {
        "chunk_q": adc,
        "delays[o]": "octave signal-register codes (OctaveStage.in_spec, "
                     "written only by the clamped requantizers)",
        "consumed/count": f"<= {SESSION_BOUND} octave samples "
                          "(~18 h @ 16 kHz)",
        "acc": f"within the {n}-sample one-shot envelope "
               f"{acc_iv!r}; max int32-safe session = "
               f"{env['max_safe_session_samples']} input samples",
        "amax": "running max |ADC code| (telemetry)",
        "n": f"valid counts in [0, {CHUNK_LEN}]",
    }
    state = pipe.init_session(1)
    chunk = jnp.zeros((1, CHUNK_LEN), jnp.int32)
    nv = jnp.zeros((1,), jnp.int32)
    jaxpr_step = jax.make_jaxpr(
        lambda st, q, v: fixed.session_step_q(prog, st, q, v))(
            state, chunk, nv)
    targets.append(Target(
        name="session_step_q", jaxpr=jaxpr_step, numerics="fixed",
        n_samples=CHUNK_LEN,
        in_intervals=_session_inputs(prog, state, CHUNK_LEN, acc_iv),
        assumptions=session_assumptions, gate=True))

    # -- per-chunk step through the stateful int Pallas kernel ------------
    pipe_pl = _fixed_pipeline(smoke, stream_impl="pallas")
    prog_pl = pipe_pl.fixed_program()
    state_pl = pipe_pl.init_session(1)
    jaxpr_spl = jax.make_jaxpr(pipe_pl._session_step_q)(state_pl, chunk, nv)
    targets.append(Target(
        name="stream_pallas", jaxpr=jaxpr_spl, numerics="fixed",
        n_samples=CHUNK_LEN,
        in_intervals=_session_inputs(prog_pl, state_pl, CHUNK_LEN, acc_iv),
        assumptions=session_assumptions, gate=True))

    # -- float reference path: determinism lint only (informational) ------
    pipe_f = _fixed_pipeline(smoke, numerics="float")
    x = jnp.zeros((1, n), jnp.float32)
    jaxpr_f = jax.make_jaxpr(pipe_f.apply)(x)
    targets.append(Target(
        name="float_oneshot", jaxpr=jaxpr_f, numerics="float",
        n_samples=n, in_intervals=None,
        assumptions={"x": "float32 audio (reference path — lint only)"},
        gate=False))

    meta = {
        "config": "smoke" if smoke else "full",
        "envelope_samples": env["envelope_samples"],
        "acc_envelope": [int(acc_iv.lo), int(acc_iv.hi)],
        "max_safe_session_samples": env["max_safe_session_samples"],
        "session_bound_counter": SESSION_BOUND,
        "chunk_len": CHUNK_LEN,
    }
    return targets, meta
