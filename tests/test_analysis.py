"""The static-analysis framework (repro.analysis): op-legality /
census-compat edge cases, the worst-case interval pass (including a
deliberately-seeded overflow it must reject by name), the determinism
lint, and the standard targets' int32-safety proof on a reduced config."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    Interval,
    analyze_intervals,
    census,
    check_legality,
    lint_determinism,
    literal_pow2_multiplicand,
)
from repro.analysis.legality import assert_legal


# ---------------------------------------------------------------------------
# pow2-literal classification (the fixed _literal_pow2 semantics)
# ---------------------------------------------------------------------------


def test_pow2_literal_mul_counts_as_shift():
    c = census(lambda x: x * 4.0, jnp.zeros((8,), jnp.float32))
    assert c["shift"] == 8 and c["multiply"] == 0


def test_non_pow2_literal_mul_is_a_multiply():
    c = census(lambda x: x * 3.0, jnp.zeros((8,), jnp.float32))
    assert c["multiply"] == 8 and c["shift"] == 0


def _literal(val):
    from jax._src import core
    arr = np.asarray(val)
    return core.Literal(arr, core.get_aval(arr))


def test_mixed_pow2_array_literal_is_not_a_shift():
    """The pre-refactor classifier looked at the FIRST element only: a
    [4.0, 3.0] multiplier would have been miscounted as a pure shift."""
    eqn = types.SimpleNamespace(
        primitive=types.SimpleNamespace(name="mul"),
        invars=[_literal([4.0, 3.0]), types.SimpleNamespace()])
    assert not literal_pow2_multiplicand(eqn)
    eqn.invars[0] = _literal([4.0, 2.0])  # all-pow2 vector IS a shift bank
    assert literal_pow2_multiplicand(eqn)


def test_two_literal_operands_are_not_a_shift():
    """'Exactly one literal operand' — with both operands literal there is
    no runtime multiplicand for a shifter to act on."""
    eqn = types.SimpleNamespace(
        primitive=types.SimpleNamespace(name="mul"),
        invars=[_literal(4.0), _literal(8.0)])
    assert not literal_pow2_multiplicand(eqn)


def test_zero_literal_is_not_a_shift():
    eqn = types.SimpleNamespace(
        primitive=types.SimpleNamespace(name="mul"),
        invars=[_literal(0.0), types.SimpleNamespace()])
    assert not literal_pow2_multiplicand(eqn)


def test_legality_names_the_offending_mul():
    jx = jax.make_jaxpr(lambda x: x * x)(jnp.zeros((4,), jnp.int32))
    r = check_legality(jx)
    assert not r.ok
    assert r.violations[0].primitive == "mul"
    with pytest.raises(AssertionError, match="mul"):
        assert_legal(jx, "test")


# ---------------------------------------------------------------------------
# grid-product scaling inside pallas_call
# ---------------------------------------------------------------------------


def test_census_scales_by_pallas_grid_product():
    from repro.kernels.fir_mp import fir_mp_bank_q_pallas

    def bank(b):
        # batch is a static shape: close over it so the census traces a
        # (b, N) program with grid (b // block_b, F)
        def run():
            x = jnp.zeros((b, 64), jnp.int32)
            h = jnp.ones((2, 8), jnp.int32)
            return fir_mp_bank_q_pallas(x, h, gamma_q=4, iters=5, qmin=-512,
                                        qmax=511, block_b=8, interpret=True)
        return run

    c8 = census(bank(8))    # grid (1, F)
    c16 = census(bank(16))  # grid (2, F): per-block kernel ops run twice
    assert c8["add"] > 0
    assert c16["add"] == 2 * c8["add"]
    assert c16["compare"] == 2 * c8["compare"]


# ---------------------------------------------------------------------------
# interval pass: arithmetic, seeded overflow, zero-length chunks
# ---------------------------------------------------------------------------


def test_interval_arithmetic_is_tight():
    def f(x):
        return (x << 2) + x - jnp.max(x)
    jx = jax.make_jaxpr(f)(jnp.zeros((4,), jnp.int32))
    r = analyze_intervals(jx, [Interval(-128, 127)])
    assert r.ok
    # x<<2 in [-512, 508]; +x -> [-640, 635]; -max(x) -> [-767, 763]
    assert r.out_intervals[0] == Interval(-767, 763)
    assert r.min_headroom_bits == 21  # 32 - 11 bits required


def test_interval_pass_rejects_seeded_overflow_by_name():
    """(q << 24) + (q << 24) with q in [-128, 127] peaks at 2^32 — one bit
    past int32. The violation must name the offending add."""
    def f(q):
        return (q << 24) + (q << 24)
    jx = jax.make_jaxpr(f)(jnp.zeros((4,), jnp.int32))
    r = analyze_intervals(jx, [Interval(-128, 127)])
    assert not r.ok
    v = r.violations[0]
    assert v.primitive == "add"
    assert v.required_bits == 33
    assert "add@" in v.name  # named eqn with source location


def test_interval_pass_rejects_overflowing_program_variant():
    """Program-level seeding: widen one octave's accumulator shift in a
    compiled program until the interval pass must reject the register."""
    import dataclasses

    from repro.analysis.targets import _fixed_pipeline, _signal_iv

    pipe = _fixed_pipeline(True)
    prog = pipe.fixed_program()
    from repro.core import fixed
    st0 = prog.bank.octaves[0]
    bank = dataclasses.replace(
        prog.bank,
        octaves=(dataclasses.replace(st0, acc_shift=st0.acc_shift + 24),)
        + prog.bank.octaves[1:])
    bad_prog = dataclasses.replace(prog, bank=bank)
    n = 1600
    jx = jax.make_jaxpr(
        lambda q: fixed.infer_q(bad_prog, q))(jnp.zeros((1, n), jnp.int32))
    r = analyze_intervals(jx, [_signal_iv(prog)])
    assert not r.ok
    assert any(v.primitive in ("shift_left", "add") for v in r.violations)


def test_zero_length_chunk_jaxpr_analyzes_clean():
    """L == 0 session step is the pure-readout path; the analysis must
    traverse it (no FIR eqns, no crash, no violations)."""
    from repro.analysis import report as rp
    from repro.analysis.targets import (_fixed_pipeline, _session_inputs,
                                        session_envelope)
    from repro.core import fixed

    pipe = _fixed_pipeline(True)
    prog = pipe.fixed_program()
    state = pipe.init_session(1)
    chunk = jnp.zeros((1, 0), jnp.int32)
    nv = jnp.zeros((1,), jnp.int32)
    jx = jax.make_jaxpr(
        lambda st, q, v: fixed.session_step_q(prog, st, q, v))(
            state, chunk, nv)
    env = session_envelope(prog, 1600)
    ivs = _session_inputs(prog, state, 0, env["acc_interval"])
    r = analyze_intervals(jx, ivs)
    assert r.ok, r.violations
    c = census(lambda st, q, v: fixed.session_step_q(prog, st, q, v),
               state, chunk, nv)
    assert c["multiply"] == 0
    t = types.SimpleNamespace(name="zero_chunk", jaxpr=jx, numerics="fixed",
                              n_samples=1, in_intervals=ivs,
                              assumptions={}, gate=True)
    assert rp.target_ok(rp.analyze_target(t))


def test_zero_length_scan_keeps_initial_carry():
    """length=0 must NOT analyze one body iteration: the true carry out is
    the initial carry (a step(init) result like [1000, 1005] would exclude
    every real output — unsound, not just loose)."""
    def f(c):
        out, _ = jax.lax.scan(lambda c, _: (c + 1000, c), c, None, length=0)
        return out
    jx = jax.make_jaxpr(f)(jnp.zeros((), jnp.int32))
    r = analyze_intervals(jx, [Interval(0, 5)])
    assert r.ok
    assert r.out_intervals[0] == Interval(0, 5)
    # census: the body executes zero times, so it contributes zero ops
    assert census(f, jnp.zeros((), jnp.int32))["add"] == 0


def test_pallas_fixpoint_nonconvergence_widens_to_top():
    """A grid past grid_unroll_limit whose ref state never stabilizes in
    fixpoint_iters must widen to TOP and FAIL — exiting with the partial
    state would certify e.g. [1, 64] for a 8192-step accumulator and claim
    'PROVEN int32-safe' for an overflowing program."""
    from jax.experimental import pallas as pl

    def k(o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)
        o_ref[...] += 1

    jx = jax.make_jaxpr(
        lambda: pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((8,), jnp.int32),
            grid=(8192,), interpret=True)())()
    r = analyze_intervals(jx, [])
    assert not r.ok
    assert r.out_intervals[0].hi == float("inf")


def test_pallas_fixpoint_convergent_large_grid_stays_tight():
    """The widening fallback must only fire on non-convergence: a
    per-block copy kernel over the same huge grid stabilizes immediately
    and keeps the input bound."""
    from jax.experimental import pallas as pl

    def k(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    jx = jax.make_jaxpr(
        lambda x: pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((8192, 8), jnp.int32),
            grid=(8192,),
            in_specs=[pl.BlockSpec((1, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 8), lambda i: (i, 0)),
            interpret=True)(x))(jnp.zeros((8192, 8), jnp.int32))
    r = analyze_intervals(jx, [Interval(-128, 127)])
    assert r.ok
    assert r.out_intervals[0] == Interval(-128, 127)


class _Var:
    """Hashable jaxpr-var stand-in (SimpleNamespace defines __eq__ and so
    can't key the interpreter's env dict)."""

    def __init__(self, aval=None):
        self.aval = aval


def _swap_eqn(outvars):
    return types.SimpleNamespace(
        primitive=types.SimpleNamespace(name="swap"),
        invars=[_Var(), _Var()], outvars=outvars, params={"tree": None})


def test_swap_of_unwritten_ref_flags_read_before_write():
    """swap whose old value is USED must report the same read-before-write
    violation as get and return the dtype range, not the newly written
    value (optimistic)."""
    from jax._src import core
    from repro.analysis.intervals import RefCell, _Analyzer, _dtype_range

    a = _Analyzer()
    cell = RefCell((8,), np.int32, None)
    eqn = _swap_eqn([_Var(core.ShapedArray((8,), np.int32))])
    env = {eqn.invars[0]: cell, eqn.invars[1]: Interval(5, 5)}
    out = a._eval_swap(eqn, env, "t")
    assert out == _dtype_range(np.int32)
    assert len(a.violations) == 1
    assert "(read-before-write)" in a.violations[0].name


def test_first_store_to_unwritten_ref_is_clean():
    """Plain stores lower to swap with a DropVar result: the first write
    to an output/scratch ref reads nothing and must not be flagged."""
    from jax._src import core
    from repro.analysis.intervals import RefCell, _Analyzer

    a = _Analyzer()
    cell = RefCell((8,), np.int32, None)
    eqn = _swap_eqn([core.DropVar(core.ShapedArray((8,), np.int32))])
    env = {eqn.invars[0]: cell, eqn.invars[1]: Interval(5, 5)}
    assert a._eval_swap(eqn, env, "t") == Interval(5, 5)
    assert not a.violations
    assert cell.hull() == Interval(5, 5)


def test_unsigned_registers_use_unsigned_carrier_bits():
    """uint32 holding [0, 2^32-1] needs 32 unsigned bits (headroom 0), not
    the 33 two's-complement bits that would distort the report with
    negative headroom for a value that fits."""
    from repro.analysis.intervals import INF, carrier_bits, signed_bits

    full = Interval(2**31, 2**32 - 1)
    assert signed_bits(full) == 33
    assert carrier_bits(full, unsigned=True) == 32
    assert carrier_bits(Interval(-1, 3), unsigned=True) == INF

    jx = jax.make_jaxpr(lambda x: x + jnp.uint32(0))(
        jnp.zeros((4,), jnp.uint32))
    r = analyze_intervals(jx, [Interval(0, 2**32 - 1)])
    assert r.ok
    assert r.max_required_bits == 32
    assert r.min_headroom_bits == 0


# ---------------------------------------------------------------------------
# determinism lint
# ---------------------------------------------------------------------------


def test_float_reduce_sum_is_flagged_as_free_tree():
    jx = jax.make_jaxpr(lambda x: jnp.sum(x))(jnp.zeros((16,), jnp.float32))
    r = lint_determinism(jx, numerics="float")
    assert any(f.kind == "free_tree_reduction" and f.primitive == "reduce_sum"
               for f in r.findings)
    assert r.ok  # informational on the float path


def test_integer_reduce_sum_is_exact_and_clean():
    jx = jax.make_jaxpr(lambda x: jnp.sum(x))(jnp.zeros((16,), jnp.int32))
    r = lint_determinism(jx, numerics="fixed")
    assert r.ok and not r.findings


def test_fixed_tree_sum_is_clean():
    from repro.core import mp
    jx = jax.make_jaxpr(mp.tree_sum)(jnp.zeros((2, 16), jnp.float32))
    r = lint_determinism(jx, numerics="float")
    assert not r.findings


def test_float_op_in_fixed_program_gates():
    jx = jax.make_jaxpr(
        lambda x: (x.astype(jnp.float32) * 0.5).astype(jnp.int32))(
            jnp.zeros((4,), jnp.int32))
    r = lint_determinism(jx, numerics="fixed")
    assert not r.ok
    assert any(f.kind == "float_in_fixed" for f in r.findings)


# ---------------------------------------------------------------------------
# the deployed programs, proven on the reduced config (the full config is
# the scripts/analyze.py tier-1 gate)
# ---------------------------------------------------------------------------


def test_smoke_targets_prove_int32_safe():
    from repro.analysis import report as rp
    from repro.analysis.targets import build_targets

    targets, meta = build_targets(smoke=True)
    names = {t.name for t in targets}
    assert {"oneshot_q", "oneshot_q_pallas", "session_step_q",
            "stream_pallas"} <= names
    report = rp.build_report(targets, meta, top_registers=5)
    assert report["ok"], report
    for name in ("oneshot_q", "session_step_q", "stream_pallas"):
        s = report["targets"][name]
        assert s["legality"]["ok"]
        assert s["intervals"]["ok"]
        assert s["intervals"]["min_headroom_bits"] >= 0
        assert s["determinism"]["ok"]
        # every register was actually bounded (no TOP escapes)
        assert s["intervals"]["max_required_bits"] is not None
    assert meta["max_safe_session_samples"] > meta["envelope_samples"]


def test_census_smoke_numbers_pinned():
    """The refactor onto the shared traversal must not move the committed
    benchmark numbers: pin the smoke-config fixed one-shot census exactly
    (verified identical to the pre-refactor walk when the refactor landed).
    Also exercises the compat re-export surface in benchmarks."""
    from benchmarks.hardware_cost import assert_multiplierless
    from repro.analysis.targets import _fixed_pipeline
    from repro.core import fixed

    pipe = _fixed_pipeline(True)
    prog = pipe.fixed_program()
    c = census(lambda q: fixed.infer_q(prog, q),
               jnp.zeros((1, 1600), jnp.int32))
    assert_multiplierless(c, "pin")
    assert c["add"] == 21_277_331
    assert c["compare"] == 10_726_788
    assert c["shift"] == 311_366
