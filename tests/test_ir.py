"""The typed fixed-point IR (src/repro/ir): round-trip parity on the
golden fixture, census pinning, register typing, and the multiplierless
type-error contract.

The load-bearing checks: lowering the golden ``esc_mp_bisect`` integer
programs (one-shot ``fixed.infer_q`` AND the per-chunk
``fixed.session_step_q``) to the IR and executing them through all three
backends — the pure-Python interpreter, the IR->XLA re-emitter, and the
compiled C reference — must land on EXACTLY the integer codes the jax
program (and the committed golden .npz) produces. Integer arithmetic
either reproduces or it drifted; there is no tolerance anywhere here.
"""

import shutil
import struct
import subprocess
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fixed
from repro.ir import (BuildError, build_program, census_program)
from repro.ir import interp as ir_interp
from repro.ir import xla as ir_xla
from repro.ir.cgen import emit_c, emit_rom_mem
from repro.analysis.legality import census_jaxpr

from golden_cases import CASES, GOLDEN_DIR, build_pipeline, make_audio

CASE = CASES["esc_mp_bisect"]
CHUNK = CASE["chunk"]


# ---------------------------------------------------------------------------
# fixtures: lower the golden case's integer programs once per module
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def oneshot():
    """The golden one-shot integer program, lowered to the IR."""
    pipe = build_pipeline(CASE)
    x = make_audio(CASE)
    prog = fixed.compile_pipeline(pipe, calibration_audio=x)
    xq = fixed.quantize_signal(prog, jnp.asarray(x))

    def fn(q):
        return fixed.infer_q(prog, q)

    jaxpr = jax.make_jaxpr(fn)(xq)
    expected = [np.asarray(v) for v in fn(xq)]   # (p_q, phi_q, s_q)
    ir = build_program(jaxpr, name="oneshot_q")
    return SimpleNamespace(jaxpr=jaxpr, ir=ir, xq=np.asarray(xq),
                           expected=expected)


@pytest.fixture(scope="module")
def session():
    """One golden-chunking step of the int32 session datapath, lowered to
    the IR. Inputs/outputs are the flattened state leaves + chunk + n."""
    pipe = build_pipeline(
        dict(CASE, cfg=dict(CASE["cfg"], numerics="fixed")))
    x = make_audio(CASE)
    pipe.calibrate_fixed(x)
    prog = pipe.fixed_program()
    state = pipe.init_session(x.shape[0])
    leaves, treedef = jax.tree_util.tree_flatten(state)
    n_state = len(leaves)
    xq = fixed.quantize_signal(prog, jnp.asarray(x[:, :CHUNK]))
    nv = jnp.full((x.shape[0],), CHUNK, jnp.int32)

    def fn(*flat):
        st = jax.tree_util.tree_unflatten(treedef, flat[:n_state])
        st2, p_q, phi_q = fixed.session_step_q(prog, st, flat[n_state],
                                               flat[n_state + 1])
        return tuple(jax.tree_util.tree_leaves(st2)) + (p_q, phi_q)

    args = tuple(leaves) + (xq, nv)
    jaxpr = jax.make_jaxpr(fn)(*args)
    expected = [np.asarray(v) for v in fn(*args)]
    ir = build_program(jaxpr, name="session_step_q")
    return SimpleNamespace(jaxpr=jaxpr, ir=ir,
                           args=[np.asarray(a) for a in args],
                           expected=expected)


def _assert_all_equal(got, expected):
    assert len(got) == len(expected)
    for g, e in zip(got, expected):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))


# ---------------------------------------------------------------------------
# backend parity: interpreter / XLA re-emitter / compiled C, all exact
# ---------------------------------------------------------------------------


def test_interpreter_matches_infer_q(oneshot):
    _assert_all_equal(ir_interp.run(oneshot.ir, [oneshot.xq]),
                      oneshot.expected)


def test_interpreter_matches_golden_fixture(oneshot):
    """The IR interpreter lands on the COMMITTED golden integer codes —
    not just on what today's jax produces."""
    golden = np.load(f"{GOLDEN_DIR}/esc_mp_bisect.npz")
    p_q, phi_q, s_q = ir_interp.run(oneshot.ir, [oneshot.xq])
    np.testing.assert_array_equal(np.asarray(p_q), golden["p_fixed_q"])
    np.testing.assert_array_equal(np.asarray(phi_q), golden["phi_fixed_q"])
    np.testing.assert_array_equal(np.asarray(s_q), golden["acc_fixed_q"])


def test_interpreter_matches_session_step(session):
    _assert_all_equal(ir_interp.run(session.ir, session.args),
                      session.expected)


def test_xla_emitter_matches_infer_q(oneshot):
    fn = jax.jit(ir_xla.emit(oneshot.ir))
    _assert_all_equal(fn(oneshot.xq), oneshot.expected)


def test_xla_emitter_matches_session_step(session):
    fn = jax.jit(ir_xla.emit(session.ir))
    _assert_all_equal(fn(*session.args), session.expected)


def _run_c(prog, inputs, tmpdir):
    cc = shutil.which("gcc") or shutil.which("cc")
    if cc is None:
        pytest.skip("no C compiler on PATH")
    src = tmpdir / "program.c"
    src.write_text(emit_c(prog))
    exe = tmpdir / "program"
    subprocess.run([cc, "-std=c99", "-O1", "-o", str(exe), str(src)],
                   check=True)
    blob = b""
    for r, v in zip((prog.regs[i] for i in prog.inputs), inputs):
        v = np.asarray(v)
        blob += (v.astype(np.uint8) if r.dtype == "i1"
                 else v.astype("<i4")).tobytes()
    inp, outp = tmpdir / "in.bin", tmpdir / "out.bin"
    inp.write_bytes(blob)
    subprocess.run([str(exe), str(inp), str(outp)], check=True)
    raw = outp.read_bytes()
    outs, off = [], 0
    for i in prog.outputs:
        r = prog.regs[i]
        if r.dtype == "i1":
            n = r.size
            outs.append(np.frombuffer(raw, np.uint8, n, off)
                        .astype(bool).reshape(r.shape))
            off += n
        else:
            n = r.size
            outs.append(np.frombuffer(raw, "<i4", n, off)
                        .reshape(r.shape))
            off += 4 * n
    assert off == len(raw)
    return outs


def test_c_reference_matches_infer_q(oneshot, tmp_path):
    _assert_all_equal(_run_c(oneshot.ir, [oneshot.xq], tmp_path),
                      oneshot.expected)


def test_c_reference_matches_session_step(session, tmp_path):
    _assert_all_equal(_run_c(session.ir, session.args, tmp_path),
                      session.expected)


# ---------------------------------------------------------------------------
# census pinning: the IR census IS the jaxpr-walk census, number for number
# ---------------------------------------------------------------------------


def test_census_pinned_oneshot(oneshot):
    c = census_program(oneshot.ir)
    assert dict(c) == dict(census_jaxpr(oneshot.jaxpr))
    assert c["multiply"] == 0 and c["transcendental_or_div"] == 0
    assert c["add"] > 0 and c["shift"] > 0


def test_census_pinned_session(session):
    assert dict(census_program(session.ir)) == \
        dict(census_jaxpr(session.jaxpr))


def test_census_pinned_pallas_stream():
    """Grid programs lower too (executable=False) and their census —
    including the pallas_call body scaled by the grid product and the
    skipped ``cond`` branches from ``pl.when`` — matches the jaxpr walk."""
    pipe = build_pipeline(
        dict(CASE, cfg=dict(CASE["cfg"], numerics="fixed")), "pallas")
    x = make_audio(CASE)
    pipe.calibrate_fixed(x)
    prog = pipe.fixed_program()
    state = pipe.init_session(x.shape[0])
    xq = fixed.quantize_signal(prog, jnp.asarray(x[:, :CHUNK]))
    nv = jnp.full((x.shape[0],), CHUNK, jnp.int32)
    jaxpr = jax.make_jaxpr(pipe._session_step_q)(state, xq, nv)
    ir = build_program(jaxpr, name="stream_pallas")
    assert not ir.executable
    assert dict(census_program(ir)) == dict(census_jaxpr(jaxpr))
    with pytest.raises(NotImplementedError):
        ir_interp.run(ir, [])
    with pytest.raises(NotImplementedError):
        ir_xla.emit(ir)


# ---------------------------------------------------------------------------
# register typing from the interval pass
# ---------------------------------------------------------------------------


def test_register_typing_from_intervals(oneshot):
    from repro.analysis.intervals import Interval
    pipe = build_pipeline(CASE)
    x = make_audio(CASE)
    prog = fixed.compile_pipeline(pipe, calibration_audio=x)
    sig = Interval(int(prog.signal.qmin), int(prog.signal.qmax))
    ir = build_program(oneshot.jaxpr, name="oneshot_q", in_intervals=[sig])
    typed = [r for r in ir.regs if r.interval is not None]
    assert typed, "intervals did not propagate into the register table"
    for r in typed:
        assert r.required_bits is not None and r.required_bits <= 32
        assert r.interval[0] <= r.interval[1]
    # the table the artifacts serialize is complete and deterministic
    table = ir.register_table()
    assert [row["reg"] for row in table] == list(range(len(ir.regs)))


# ---------------------------------------------------------------------------
# the multiplierless contract is a TYPE ERROR, not a census result
# ---------------------------------------------------------------------------


def test_general_multiply_is_a_build_error():
    a = jnp.arange(8, dtype=jnp.int32)
    with pytest.raises(BuildError, match="mul"):
        build_program(jax.make_jaxpr(lambda u, v: u * v)(a, a),
                      name="bad_mul")


def test_pow2_literal_multiply_folds_to_shift():
    a = jnp.arange(8, dtype=jnp.int32)
    ir = build_program(jax.make_jaxpr(lambda u: u * 8)(a), name="p2")
    shifts = [i for i in ir.body if i.op == "shl"]
    assert len(shifts) == 1 and shifts[0].attrs["imm"] == 3
    assert dict(census_program(ir)).get("shift", 0) >= 1
    np.testing.assert_array_equal(
        np.asarray(ir_interp.run(ir, [np.arange(8, dtype=np.int32)])[0]),
        np.arange(8, dtype=np.int32) * 8)


def test_float_program_is_a_build_error():
    a = jnp.arange(8, dtype=jnp.float32)
    with pytest.raises(BuildError):
        build_program(jax.make_jaxpr(lambda u, v: u / v)(a, a),
                      name="bad_div")


# ---------------------------------------------------------------------------
# ROM artifacts
# ---------------------------------------------------------------------------


def test_rom_mem_files_round_trip(oneshot):
    """Every ROM serializes to a $readmemh file whose words parse back to
    the exact int32 contents (two's complement, 8 hex digits per word)."""
    mems = emit_rom_mem(oneshot.ir)
    assert len(mems) == len(oneshot.ir.roms)
    by_name = {f"{r.name}.mem": r for r in oneshot.ir.roms}
    for fname, text in mems.items():
        rom = by_name[fname]
        words = [w for line in text.splitlines()
                 for w in line.split() if not w.startswith("//")]
        got = np.asarray(
            [struct.unpack(">i", bytes.fromhex(w))[0] for w in words],
            np.int32)
        np.testing.assert_array_equal(
            got, np.asarray(rom.data, np.int32).ravel())
