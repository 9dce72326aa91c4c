"""Table I/II analogue: hardware operation census.

The paper's headline is resource count: the MP design uses 0 DSPs and <1K
slices because it is multiplierless. We can't synthesize Verilog here, but
we can count the primitive operations each inference performs by walking
the traced jaxpr and convert multiplier counts to LUT-equivalents with the
paper's own figures (8x8 signed Baugh-Wooley multiplier = 72 LUTs;
adds/compares = ~8 LUTs at 8 bit).

Since the fixed-point refactor the census has a REAL target: the integer
hardware twin (``repro.core.fixed``, numerics="fixed") executes the whole
audio -> decision path in int32. Its jaxpr is walked here with a HARD
assertion that no multiply and no divide survives — the multiplierless
claim as an executable regression gate, not prose. The float MP/MAC paths
are kept for comparison (the float MP census still counts the pow2
bisection halvings as shifts, exactly as the FPGA implements them).

Both the one-shot program AND the per-chunk integer streaming step
(``fixed.session_step_q`` — what a deployed FPGA executes per sensor
packet) are censused and asserted multiplierless, and so is the int
Pallas streaming kernel (``kernels.fir_mp_stream_q``): the census
recurses into ``pallas_call`` kernel jaxprs scaled by the grid product,
so the gate covers the VMEM-resident datapath as lowered.

Since the IR refactor the integer censuses are computed by lowering each
program to the typed fixed-point IR (``repro.ir``) and counting with the
IR census pass — the same lowering the interpreter, the XLA re-emitter and
the C/ROM generator consume — with a runtime assertion that the counts are
EXACTLY the legacy jaxpr-walk numbers (``repro.analysis.legality``, which
still backs the float rows and the op-legality verifier). The committed
``hw.*`` rows are therefore pinned byte-identical across the rebase, and
the benchmark, ``scripts/analyze.py`` and the hardware artifacts under
``artifacts/ir/`` can never disagree about what a program contains. This module also surfaces the analysis summary (bitwidth
headroom per target, the session-accumulator safety envelope) as bench
rows so headroom is tracked across PRs alongside the op counts.

Run with ``--smoke`` (used by scripts/bench_smoke.sh) for a reduced config
that still exercises the assertions.
"""

from __future__ import annotations

import argparse
from collections import Counter

import jax
import jax.numpy as jnp

from benchmarks.common import row
from repro.analysis import assert_multiplierless, census  # noqa: F401
from repro.analysis.intervals import Interval
from repro.analysis.legality import census_jaxpr
from repro.core.filterbank import FilterBank, FilterBankConfig
from repro.core import fixed
from repro.core import kernel_machine as km
from repro.core.pipeline import InFilterPipeline
from repro.ir import build_program, census_program

FS = 16000.0
N = 16000  # 1 s


def census_ir(fn, *args, tag: str, in_intervals=None):
    """Census an integer program THROUGH the typed IR: trace, lower with
    ``repro.ir.build`` (which rejects anything outside the multiplierless
    contract), and count with the IR census pass. Pinned at runtime
    against the legacy jaxpr walk — if the lowering ever re-associates or
    drops an op, the committed ``hw.*`` rows can't silently move; the
    bench fails instead. Returns ``(census, program)`` — the typed
    program also feeds the allocator cost rows; passing ``in_intervals``
    runs the interval pass so register widths are the proven minima."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    prog = build_program(jaxpr, name=tag, in_intervals=in_intervals)
    c_ir = census_program(prog)
    c_jx = census_jaxpr(jaxpr)
    if dict(c_ir) != dict(c_jx):
        raise AssertionError(
            f"{tag}: IR census {dict(c_ir)} != jaxpr census {dict(c_jx)} "
            "— the IR lowering moved the pinned hw.* numbers")
    return c_ir, prog


def lut_estimate(c: Counter) -> float:
    """8-bit LUT-equivalents using the paper's conversion factors."""
    return (c["multiply"] * 72          # 8x8 Baugh-Wooley (paper: 72 LUTs)
            + c["add"] * 8
            + c["compare"] * 8
            + c["shift"] * 0            # wiring on FPGA
            + c["transcendental_or_div"] * 200)


def _fixed_pipeline(cfg, seed: int = 0) -> InFilterPipeline:
    fb = FilterBank(cfg)
    P = cfg.num_filters
    params = km.init_params(jax.random.PRNGKey(seed), P, 10)
    mu = jnp.zeros((P,))
    sigma = jnp.ones((P,))
    return InFilterPipeline.from_filterbank(fb, params, mu, sigma)


def emit_rows(tag: str, c: Counter, n_samples: int) -> None:
    per = {k: v / n_samples for k, v in c.items()}  # per input sample
    row(f"hw.{tag}.mult_per_sample", None, f"{per.get('multiply', 0):.1f}")
    row(f"hw.{tag}.add_per_sample", None, f"{per.get('add', 0):.1f}")
    row(f"hw.{tag}.cmp_per_sample", None, f"{per.get('compare', 0):.1f}")
    row(f"hw.{tag}.shift_per_sample", None, f"{per.get('shift', 0):.1f}")
    row(f"hw.{tag}.lut_weighted_ops_per_sample", None,
        f"{lut_estimate(c) / n_samples:.0f} (ops-weighted; the FPGA time-"
        f"multiplexes 3 MP modules so unit count is far lower)")


def emit_alloc_rows(tag: str, prog) -> None:
    """Allocator-derived hardware totals — the repo's slice-count proxy
    (paper Table I: 0 DSP, <1K slices). Register/adder/ROM totals come
    from the same allocation the committed ``program.v`` declares; with
    typed inputs the register bits are the interval-proven minima, and
    the carrier-saving row says how much the interval pass buys over a
    uniform int32 register file."""
    from repro.ir.alloc import allocate

    rep = allocate(prog).report
    regs, dp, roms = rep["registers"], rep["datapath"], rep["roms"]
    row(f"hw.{tag}.alloc_registers", None, f"{regs['count']}")
    row(f"hw.{tag}.alloc_register_bits", None,
        f"{regs['bits_allocated']} "
        f"(int32 carrier: {regs['bits_carrier']}, saving "
        f"{100 * regs['carrier_saving']:.1f}%)")
    row(f"hw.{tag}.alloc_rom_bits", None,
        f"{roms['bits_stored']} ({roms['count']} ROMs, "
        f"width-trimmed minimum {roms['bits_minimal']})")
    row(f"hw.{tag}.alloc_adder_sites", None,
        f"{dp['adder_sites']} (+{dp['comparator_sites']} comparators, "
        f"{dp['dyn_shifter_sites']} barrel shifters; time-multiplexed "
        f"over {rep['time_multiplexed']['element_ops_per_inference']} "
        f"element-ops/inference)")


def emit_analysis_rows(smoke: bool) -> None:
    """Static-analysis summary rows: per-target bitwidth headroom and the
    session accumulator envelope (see docs/analysis.md). Tracked across
    PRs so a register-growth regression shows up in the bench diff."""
    from repro.analysis import report as rp
    from repro.analysis.targets import build_targets

    targets, meta = build_targets(smoke=smoke)
    for t in targets:
        s = rp.analyze_target(t, top_registers=0)
        leg = s["legality"]
        row(f"analysis.{t.name}.legal_ops_per_sample", None,
            f"{sum(leg['legal_ops'].values()) / t.n_samples:.1f} "
            f"(legality {'ok' if leg['ok'] else 'FAIL'})")
        if "intervals" in s:
            iv = s["intervals"]
            row(f"analysis.{t.name}.min_headroom_bits", None,
                f"{iv['min_headroom_bits']} over {iv['num_registers']} "
                f"registers (max required {iv['max_required_bits']} bits)")
            row(f"analysis.{t.name}.int32_safe", None,
                "PROVEN for any ADC input" if iv["ok"]
                else f"FAIL: {len(iv['violations'])} possible overflow(s)")
    row("analysis.session.max_safe_session_samples", None,
        f"{meta['max_safe_session_samples']} input samples before any "
        f"int32 accumulator can overflow (acc <= "
        f"{meta['acc_envelope'][1]} within the "
        f"{meta['envelope_samples']}-sample envelope)")


def main(argv=()):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (3 octaves, 0.1 s) — still runs "
                         "the multiplierless assertion on the integer path")
    args = ap.parse_args(argv)

    if args.smoke:
        n = 1600
        base = FilterBankConfig(fs=4000.0, num_octaves=3,
                                filters_per_octave=3, mode="mp",
                                gamma_f=4.0, solver="bisect")
    else:
        n = N
        base = FilterBankConfig(fs=FS, num_octaves=6, mode="mp",
                                gamma_f=4.0, solver="bisect")
    x = jnp.zeros((1, n), jnp.float32)
    P = base.num_filters

    # --- MP in-filter path (bisection filtering + MP classifier) ---
    # solver="bisect": the census models the FPGA, whose MP modules run the
    # add/compare/shift bisection — not the software-fast Newton path
    fb_mp = FilterBank(base)
    params = km.init_params(jax.random.PRNGKey(0), P, 10)

    def mp_infer(x):
        s = fb_mp.accumulate(x)
        return km.forward(params, s)

    # --- MAC baseline (conv filtering + linear classifier) ---
    fb_mac = FilterBank(base._replace(mode="mac"))
    w = jnp.zeros((P, 10))
    b = jnp.zeros((10,))

    def mac_infer(x):
        s = fb_mac.accumulate(x)
        return km.forward_baseline(w, b, s)

    for tag, fn in [("mp_infilter", mp_infer), ("mac_baseline", mac_infer)]:
        emit_rows(tag, census(fn, x), n)

    # --- the integer hardware twin: census the REAL int32 jaxpr ----------
    # (from quantized codes onward — the ADC rounding at the boundary is
    # analog-side; everything after it must be add/sub/shift/compare)
    for tag, mode in [("fixed_mp", "mp"), ("fixed_mac_shift_add", "mac")]:
        pipe = _fixed_pipeline(base._replace(mode=mode, numerics="fixed"))
        prog = pipe.fixed_program()
        xq = fixed.quantize_signal(prog, x)
        sig = Interval(int(prog.signal.qmin), int(prog.signal.qmax))
        c, prog_ir = census_ir(lambda q: fixed.infer_q(prog, q), xq,
                               tag=tag, in_intervals=[sig])
        assert_multiplierless(c, tag)
        emit_rows(tag, c, n)
        emit_alloc_rows(tag, prog_ir)
        row(f"hw.{tag}.multiplierless_assert", None,
            "PASS (0 multiplies, 0 divides in the integer IR, counts "
            "pinned == jaxpr census)")

    # --- the integer STREAMING step: what a deployed FPGA actually runs --
    # per sensor packet (delay-line splice, kept-only decimation, readout
    # every chunk). Censused per chunk and asserted multiplierless — the
    # per-chunk step, not the one-shot program, is the deployment datapath.
    chunk_len = 160  # one 10 ms packet at 16 kHz (smoke: same length)
    for tag, mode in [("fixed_mp_stream", "mp"),
                      ("fixed_mac_stream", "mac")]:
        pipe = _fixed_pipeline(base._replace(mode=mode, numerics="fixed"))
        prog = pipe.fixed_program()
        state = pipe.init_session(1)
        xq = fixed.quantize_signal(prog, jnp.zeros((1, chunk_len)))
        nv = jnp.full((1,), chunk_len, jnp.int32)
        c, prog_ir = census_ir(
            lambda st, q, v: fixed.session_step_q(prog, st, q, v),
            state, xq, nv, tag=tag)
        assert_multiplierless(c, tag)
        emit_rows(tag, c, chunk_len)
        emit_alloc_rows(tag, prog_ir)
        row(f"hw.{tag}.multiplierless_assert", None,
            f"PASS (0 mul/div in the per-chunk int32 streaming IR, "
            f"chunk={chunk_len}, counts pinned == jaxpr census)")

    # --- the int PALLAS streaming step: the census recurses into the
    # pallas_call kernel jaxpr (scaled by the grid product), so the hard
    # gate covers the VMEM-resident datapath too — what actually lowers,
    # not just the XLA twin it mirrors.
    tag = "fixed_mp_stream_pallas"
    pipe = _fixed_pipeline(base._replace(mode="mp", numerics="fixed",
                                         stream_impl="pallas"))
    prog = pipe.fixed_program()
    state = pipe.init_session(1)
    xq = fixed.quantize_signal(prog, jnp.zeros((1, chunk_len)))
    nv = jnp.full((1,), chunk_len, jnp.int32)
    c, prog_ir = census_ir(pipe._session_step_q, state, xq, nv, tag=tag)
    assert_multiplierless(c, tag)
    emit_rows(tag, c, chunk_len)
    emit_alloc_rows(tag, prog_ir)
    row(f"hw.{tag}.multiplierless_assert", None,
        f"PASS (0 mul/div in the Pallas-lowered per-chunk int32 IR, "
        f"chunk={chunk_len}, counts pinned == jaxpr census)")

    emit_analysis_rows(args.smoke)

    row("hw.reference", None,
        "paper Table I: 0 DSP, 1503 LUT, 2376 FF, 17mW@50MHz; "
        "[6] CAR-IHC uses 4 DSPs (~890 LUT-equiv). Key check: fixed_mp "
        "multiplies/sample == 0 ENFORCED on the int32 jaxpr (was a float "
        "proxy before the fixed-point refactor), MAC baseline > 0")


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
