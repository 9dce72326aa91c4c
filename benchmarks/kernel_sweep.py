"""Streaming-kernel shape sweep: slot_block x chunk length x capacity.

Times one jitted session step through the stateful Pallas streaming
kernels — float (``kernels.fir_mp_stream``) and integer
(``kernels.fir_mp_stream_q``) — across slot tiles (``block_s``), chunk
lengths, and session capacities (S in {64, 256} for the full run; the
ROADMAP's >=1.5x streams/sec target is stated at S=256). Rows land in the
``BENCH_pipeline.json`` trajectory like every other benchmark, so shape
regressions are visible across PRs, and ``--update-table`` persists each
(kernel, capacity) winner into the committed autotune table
(``src/repro/kernels/stream_shapes.json``) that ``ops.fir_mp_stream`` /
``ops.fir_mp_stream_q`` consult by default — re-tuning on real TPU
hardware is one command plus a one-line JSON diff.

Shape choice never changes VALUES (``block_s`` only tiles the
row-independent slot axis), so the sweep needs no parity checks — those
live in tests/test_streaming_parity.py. Off-TPU the kernels run in
interpret mode: CPU numbers track wiring overhead, not the VMEM-residency
win.

    PYTHONPATH=src python -m benchmarks.kernel_sweep [--smoke]
        [--update-table]

Emits ``name,us_per_call,derived`` CSV rows like every other benchmark.
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn
from repro.configs.esc10_mp import make_pipeline
from repro.core import fixed
from repro.kernels import fir_mp_stream, fir_mp_stream_q
from repro.kernels import stream_shapes


def _sweep_float(pipe, S, chunks, blocks, iters):
    """us per session step for each (chunk, block_s); returns
    {block_s: total_us} for the winner pick."""
    cfg = pipe.config
    totals: dict[int, float] = {}
    for ch in chunks:
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((S, ch)).astype(np.float32))
        n = jnp.full((S,), ch, jnp.int32)
        st = pipe.init_session(S)
        for bs in blocks:
            us = time_fn(
                lambda bs=bs, x=x, n=n, st=st: fir_mp_stream(
                    x, n, st.delays, st.consumed, st.acc, st.amax,
                    pipe.bp_taps, pipe.lp_taps, cfg.gamma_f,
                    solver=cfg.solver, block_s=bs),
                warmup=1, iters=iters)
            row(f"kernel_sweep.fir_mp_stream.S{S}xC{ch}.bs{bs}", us,
                f"{S / us * 1e6:.0f} chunks/s")
            totals[bs] = totals.get(bs, 0.0) + us
    return totals


def _sweep_int(pipe, S, chunks, blocks, iters):
    prog = pipe.fixed_program()
    totals: dict[int, float] = {}
    for ch in chunks:
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((S, ch)).astype(np.float32))
        xq = fixed.quantize_signal(prog, x)
        n = jnp.full((S,), ch, jnp.int32)
        st = pipe.init_session(S)
        for bs in blocks:
            # the program lowers host-side: jit a closure over it (the
            # same shape the server's donated fixed step uses)
            step = jax.jit(lambda q, nn, d, co, a, am, bs=bs:
                           fir_mp_stream_q(prog, q, nn, d, co, a, am,
                                           block_s=bs))
            us = time_fn(
                lambda: step(xq, n, st.delays, st.consumed, st.acc,
                             st.amax),
                warmup=1, iters=iters)
            row(f"kernel_sweep.fir_mp_stream_q.S{S}xC{ch}.bs{bs}", us,
                f"{S / us * 1e6:.0f} chunks/s")
            totals[bs] = totals.get(bs, 0.0) + us
    return totals


def main(argv=()):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep for CI bit-rot checks")
    ap.add_argument("--update-table", action="store_true",
                    help="persist each (kernel, capacity) winner into the "
                         "committed autotune table "
                         "(src/repro/kernels/stream_shapes.json)")
    args = ap.parse_args(argv)
    if args.smoke:
        caps, blocks, chunks, iters = (8,), (8,), (40,), 2
    else:
        # Mosaic tiles f32/int32 rows in groups of 8: a slot block that is
        # not a multiple of 8 (and not the whole capacity) cannot compile
        caps, blocks, chunks, iters = (64, 256), (8, 16, 32), \
            (40, 160), 3

    pipe_f = make_pipeline(smoke=True, stream_impl="pallas")
    pipe_q = make_pipeline(smoke=True, stream_impl="pallas",
                           numerics="fixed", fixed_amax=4.0)
    winners: dict[str, dict[str, int]] = {"fir_mp_stream": {},
                                          "fir_mp_stream_q": {}}
    for S in caps:
        bl = [b for b in blocks if b <= S] or [min(blocks)]
        for kernel, sweep, pipe in [
                ("fir_mp_stream", _sweep_float, pipe_f),
                ("fir_mp_stream_q", _sweep_int, pipe_q)]:
            totals = sweep(pipe, S, chunks, bl, iters)
            best = min(totals, key=totals.get)
            winners[kernel][str(S)] = best
            row(f"kernel_sweep.best.{kernel}.S{S}", None,
                f"block_s={best} (min total us over chunk lengths "
                f"{list(chunks)})")

    if args.update_table:
        current = stream_shapes.table()
        merged = {k: dict(current.get(k, {})) for k in
                  set(current) | set(winners)}
        for k, ent in winners.items():
            merged[k].update(ent)
        with open(stream_shapes.TABLE_PATH, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
            f.write("\n")
        stream_shapes.table.cache_clear()
        row("kernel_sweep.table_updated", None,
            f"wrote {stream_shapes.TABLE_PATH}")


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
