"""Compile the serving hot path for a described TPU v5e, with no chip.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described rather than attached. These tests compile, at the paper's
full widths, what ``chip_smoke.py`` and the benchmark run on the chip:
the streaming kernel on both datapaths, the batched session step in both
numerics under both impls (and at the backlog cells' 4096-sample wave),
the slot-sharded fixed step on a four-chip mesh, and the slot reset of
``open()`` and ``close()``; and they count the lane rotates Mosaic emits
in the kernel's band-pass solve loops on both datapaths. Mosaic refuses
shapes and ops that interpret mode accepts (unaligned blocks, lane
gathers, strided lane slices), so these catch on the CPU what would
otherwise fail on the chip. Nothing runs: a pass says the programs compile, not what they
compute or how fast.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
every test file.
"""

from __future__ import annotations

import importlib
import json
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs.esc10_mp import FILTERBANK, make_pipeline
from repro.kernels import stream_shapes

fir_mp = importlib.import_module("repro.kernels.fir_mp")

S = 256                # session capacity served by chip_smoke.py
BUCKET = 256           # a 160-sample packet pads to this pow2 bucket
WAVE = 4096            # a backlog request: one 4096-sample chunk, one wave


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the ops wrappers to the compiled kernels: on this CPU host
    ``_interpret()`` would pick interpret mode."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fixed_program():
    return make_pipeline(smoke=False, numerics="fixed",
                         fixed_amax=4.0).fixed_program()


@pytest.mark.parametrize("numerics", ["fixed", "float"])
def test_int_stream_kernel_band_loops_rotate_free(tmp_path, numerics):
    """On both datapaths the kernel's band-pass solve loops (integer
    bisection, float Newton) load their operands aligned from VMEM
    scratch, so Mosaic emits no lane rotate in either loop body
    (``scripts/kernel_llo.py``). The script switches Mosaic's dump on
    through ``LIBTPU_INIT_ARGS``, which is read when the TPU library
    loads: hence a fresh process. Only one process at a time may load the
    library, so this test comes first in the file, before the ``topo``
    fixture loads it in this one."""
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(root / "src"), os.environ.get("PYTHONPATH", "")]))
    run = subprocess.run(
        [sys.executable, str(root / "scripts" / "kernel_llo.py"),
         "--numerics", numerics, "--dump", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    if run.returncode and "get_topology_desc" in run.stderr:
        pytest.skip("no v5e:2x2 topology can be described here: "
                    + run.stderr.strip().splitlines()[-1])
    assert run.returncode == 0, run.stderr[-4000:]
    loops = re.findall(r"loop \d+ \(one iteration\): \d+ ops, (\d+) lane "
                       r"rotates", run.stdout)
    # in program order: the band-pass solve's two loops (h + x, h - x),
    # then the LP/÷2 tail's two
    assert len(loops) == 4, run.stdout
    assert loops[:2] == ["0", "0"], run.stdout


@pytest.mark.parametrize("chunk", [160, 320])
def test_float_stream_kernel_compiles(one_chip, chunk):
    F, M = FILTERBANK.filters_per_octave, FILTERBANK.bp_taps
    T1 = max(FILTERBANK.bp_taps, FILTERBANK.lp_taps) - 1
    f32, i32 = jnp.float32, jnp.int32

    def octave(x, n, start, delay, acc, amax, H, lp, gamma):
        return fir_mp.fir_mp_stream_octave(
            x, n, start, delay, acc, amax, fir_mp.FloatPath(H, lp, gamma),
            update_amax=True,
            block_s=stream_shapes.best_block_s("fir_mp_stream", S))

    c = jax.jit(octave).lower(
        _sds((S, chunk), f32, one_chip), _sds((S,), i32, one_chip),
        _sds((S,), i32, one_chip), _sds((S, T1), f32, one_chip),
        _sds((S, F), f32, one_chip), _sds((S,), f32, one_chip),
        _sds((F, M), f32, one_chip), _sds((FILTERBANK.lp_taps,), f32,
                                          one_chip),
        _sds((), f32, one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("chunk", [160, 320])
def test_int_stream_kernel_compiles(one_chip, chunk):
    prog = _fixed_program()
    st = prog.bank.octaves[0]
    F = st.bp_q.shape[0]
    T1 = max(FILTERBANK.bp_taps, FILTERBANK.lp_taps) - 1
    i32 = jnp.int32

    path = fir_mp.FixedPath(st, prog.bank.octaves[1].in_spec)

    def octave(x, n, start, delay, acc, amax):
        return fir_mp.fir_mp_stream_octave(
            x, n, start, delay, acc, amax, path, update_amax=True,
            block_s=stream_shapes.best_block_s("fir_mp_stream_q", S))

    c = jax.jit(octave).lower(
        _sds((S, chunk), i32, one_chip), _sds((S,), i32, one_chip),
        _sds((S,), i32, one_chip), _sds((S, T1), i32, one_chip),
        _sds((S, F), i32, one_chip), _sds((S,), i32, one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()


def _lower_step(pipe, sharding, mesh=None, bucket=BUCKET):
    from repro.serving.server import make_batched_step
    state = pipe.init_session(S, active=np.ones((S,), bool))
    like = lambda a: _sds(a.shape, a.dtype, sharding)
    step = make_batched_step(pipe, mesh)
    return step.lower(
        jax.tree.map(like, pipe) if pipe.config.numerics == "float" else pipe,
        jax.tree.map(like, state), _sds((S, bucket), jnp.float32, sharding),
        _sds((S,), jnp.int32, sharding)).compile()


@pytest.mark.parametrize("impl,numerics,bucket", [
    pytest.param(impl, numerics, BUCKET, id=f"{impl}-{numerics}")
    for numerics in ("float", "fixed") for impl in ("xla", "pallas")] + [
    pytest.param("pallas", numerics, WAVE, id=f"pallas-{numerics}-{WAVE}")
    for numerics in ("float", "fixed")])
def test_session_step_compiles(one_chip, compiled_kernels, numerics, impl,
                               bucket):
    """At a live packet's bucket in both impls, and at a backlog wave in
    the Pallas step that the benchmark's backlog cells time."""
    pipe = make_pipeline(smoke=False, stream_impl=impl, numerics=numerics,
                         fixed_amax=4.0 if numerics == "fixed" else None)
    text = _lower_step(pipe, one_chip, bucket=bucket).as_text()
    assert ("tpu_custom_call" in text) == (impl == "pallas")
    # the profiler names each octave's kernel, and reports the step's
    # named scopes with every op
    kernel = "fir_mp_stream_q" if numerics == "fixed" else "fir_mp_stream"
    for o in range(FILTERBANK.num_octaves):
        assert (f"%{kernel}_o{o}." in text) == (impl == "pallas")
    for scope in ("octave_cascade", "readout"):
        assert f"/session_step/{scope}/" in text


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_slot_sharded_fixed_step_is_collective_free(topo, compiled_kernels,
                                                    impl):
    """On a 4-way data mesh each chip runs the step on its own 64 slots:
    Mosaic kernels cannot be partitioned automatically, so the step runs
    under shard_map, and nothing moves between chips."""
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    pipe = make_pipeline(smoke=False, stream_impl=impl,
                         numerics="fixed", fixed_amax=4.0)
    text = _lower_step(pipe, NamedSharding(mesh, P(("data",))),
                       mesh).as_text()
    assert ("tpu_custom_call" in text) == (impl == "pallas")
    for collective in ("all-gather", "all-reduce", "all-to-all"):
        assert collective not in text


@pytest.mark.parametrize("numerics", ["float", "fixed"])
def test_lifecycle_program_compiles(topo, numerics):
    """The donated slot reset of open() and close() compiles at full width
    with the slot and the flag traced, on one chip and on a 4-way slot mesh,
    where every leaf comes out with the sharding it went in with."""
    from repro.distributed.sharding import session_shardings
    from repro.serving.server import _reset_program
    pipe = make_pipeline(smoke=False, numerics=numerics,
                         fixed_amax=4.0 if numerics == "fixed" else None)
    state = pipe.init_session(S, active=np.zeros((S,), bool))
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    one_chip = SingleDeviceSharding(topo.devices[0])
    for program, layout, scalar in (
            (_reset_program(), jax.tree.map(lambda a: one_chip, state),
             one_chip),
            (_reset_program(session_shardings(state, mesh)),
             session_shardings(state, mesh), NamedSharding(mesh, P()))):
        c = program.lower(
            jax.tree.map(lambda a, s: _sds(a.shape, a.dtype, s), state,
                         layout),
            _sds((), jnp.int32, scalar), _sds((), jnp.bool_, scalar)
        ).compile()
        assert jax.tree.leaves(c.output_shardings) == jax.tree.leaves(layout)


def test_stream_shapes_table_is_tile_aligned():
    """Mosaic tiles f32/int32 rows in groups of 8: every slot block in the
    autotune table must be a multiple of 8."""
    with open(stream_shapes.TABLE_PATH) as f:
        table = json.load(f)
    blocks = [b for ent in table.values() for b in ent.values()]
    assert blocks and all(b % 8 == 0 for b in blocks), table
