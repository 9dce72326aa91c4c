#!/usr/bin/env python3
"""Count the LLO ops Mosaic emits for octave 0's streaming kernel, per loop.

Compiles octave 0 of ``fir_mp_stream_octave`` on its integer datapath
(``--numerics fixed``) or its float one (``float``) of the full-width
esc10-mp pipeline for a described TPU v5e, with no chip, at S slots x an
L-sample chunk and ``block_s`` 8, with Mosaic's dump on. Prints the ops
of every ``scf.for`` body (the MP solves' loops: one iteration each) and
of the code outside them, with the lane rotates (``llo.vrot.lane``),
selects and vector loads among them. Ops are counted before register
allocation: a count, not a time.

    PYTHONPATH=src JAX_PLATFORMS=cpu python scripts/kernel_llo.py \\
        [--numerics fixed|float] [--slots 256] [--chunk 4096] [--dump DIR]
"""

from __future__ import annotations

import argparse
import collections
import glob
import importlib
import os
import re
import sys
import tempfile

OP = re.compile(r"=\s+\"?([a-z_]+\.[a-z_.0-9]+)")


def loop_census(path: str) -> tuple[list, collections.Counter]:
    """Op counts of each ``scf.for`` body in a ``post-finalize-llo`` dump
    (innermost loop owns an op), and of everything outside the loops."""
    loops, outside, open_loops, depth = [], collections.Counter(), [], 0
    with open(path) as f:
        for line in f:
            s = line.strip()
            if s.startswith("}"):
                depth -= 1
                if open_loops and open_loops[-1][0] == depth:
                    loops.append(open_loops.pop()[1])
            m = OP.search(s)
            if m and m.group(1) != "scf.for":
                (open_loops[-1][1] if open_loops else outside)[m.group(1)] += 1
            if s.endswith("{"):
                if "scf.for" in s:
                    open_loops.append((depth, collections.Counter()))
                depth += 1
    return loops, outside


def _line(name: str, c: collections.Counter) -> str:
    loads = sum(v for k, v in c.items() if "load" in k)
    return (f"{name}: {sum(c.values())} ops, {c['llo.vrot.lane']} lane "
            f"rotates, {c['llo.vselect']} selects, {loads} vector loads")


def compile_octave0(numerics: str, S: int, L: int) -> str:
    """Compile octave 0's kernel for a described v5e; return its name."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.configs.esc10_mp import FILTERBANK, make_pipeline
    fir_mp = importlib.import_module("repro.kernels.fir_mp")

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    T1 = max(FILTERBANK.bp_taps, FILTERBANK.lp_taps) - 1
    F, M = FILTERBANK.filters_per_octave, FILTERBANK.bp_taps
    if numerics == "fixed":
        prog = make_pipeline(smoke=False, numerics="fixed",
                             fixed_amax=4.0).fixed_program()
        dt = jnp.int32
        path = fir_mp.FixedPath(prog.bank.octaves[0],
                                prog.bank.octaves[1].in_spec)

        def octave(x, n, start, delay, acc, amax):
            return fir_mp.fir_mp_stream_octave(x, n, start, delay, acc, amax,
                                               path, update_amax=True)
        extra = []
        name = "fir_mp_stream_q_o0"
    else:
        dt = jnp.float32

        def octave(x, n, start, delay, acc, amax, H, lp, gamma):
            return fir_mp.fir_mp_stream_octave(
                x, n, start, delay, acc, amax,
                fir_mp.FloatPath(H, lp, gamma),
                update_amax=True)
        extra = [((F, M), dt), ((FILTERBANK.lp_taps,), dt), ((), dt)]
        name = "fir_mp_stream_o0"
    shapes = [((S, L), dt), ((S,), jnp.int32), ((S,), jnp.int32),
              ((S, T1), dt), ((S, F), dt), ((S,), dt)] + extra
    jax.jit(octave).lower(*[jax.ShapeDtypeStruct(s, d, sharding=chip)
                            for s, d in shapes]).compile()
    return name


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--numerics", choices=("fixed", "float"),
                    default="fixed")
    ap.add_argument("--slots", type=int, default=256)
    ap.add_argument("--chunk", type=int, default=4096)
    ap.add_argument("--dump", help="keep Mosaic's dump in this directory")
    args = ap.parse_args(argv)
    dump = args.dump or tempfile.mkdtemp(prefix="mosaic_dump_")
    os.makedirs(dump, exist_ok=True)
    # read when the TPU library loads: add to what the variable holds
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "")
        + f" --xla_mosaic_dump_to={dump}").strip()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    name = compile_octave0(args.numerics, args.slots, args.chunk)
    paths = sorted(glob.glob(os.path.join(
        dump, f"*-mosaic-dump-{name}-post-finalize-llo.txt")))
    if not paths:
        print(f"no post-finalize-llo dump of {name} in {dump}",
              file=sys.stderr)
        return 1
    loops, outside = loop_census(paths[-1])
    print(f"{name}, {args.slots} x {args.chunk}: {len(loops)} loops")
    for i, c in enumerate(loops):
        print(_line(f"  loop {i} (one iteration)", c))
    print(_line("  outside the loops", outside))
    return 0


if __name__ == "__main__":
    sys.exit(main())
