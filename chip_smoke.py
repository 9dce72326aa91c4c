#!/usr/bin/env python
"""Chip smoke test: serve full-width esc10-mp on a TPU through StreamRouter.

    python chip_smoke.py              # one chip: 4 phases
    python chip_smoke.py --chips 4    # four chips: slot-sharded fixed mode

One process, no child processes. It refuses to run unless JAX's first
device is a TPU, and it catches no phase failure: any failed phase exits
non-zero before the last line.

One chip runs four phases, {float, fixed} x {xla, pallas}. Each builds the
paper's pipeline at full width (16 kHz, 6 octaves x 5 filters, 16 band-pass
and 6 low-pass taps), opens 256 streams on a ``StreamRouter`` and feeds 8
rounds of 160-sample packets of seeded audio through ``submit()`` /
``drain()``. Each stream's final decision row is then compared with the
one-shot reference on the same audio: bitwise with ``fixed.infer_q`` in
fixed mode (and Pallas bitwise with XLA), within 1e-4 of ``predict`` in
float mode. A ``pallas`` phase must compile its step to a Mosaic kernel
(``tpu_custom_call`` in the compiled text).

``--chips 4`` runs only the scale-out path: fixed mode, both impls, the 256
slots sharded 4 ways over a ``data`` mesh, compared bitwise with the same
server on one chip, with 64 slots on each device.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

STREAMS = 256        # slots: the session capacity S
PACKET = 160         # samples per packet (10 ms at 16 kHz)
ROUNDS = 8           # packets per stream
CALLERS = 8          # independent submitters per round
FIXED_AMAX = 4.0     # ADC full-scale for N(0, 1) sensors (launch/serve.py)
FLOAT_ATOL = 1e-4    # multi-chunk streaming vs one-shot (f32 add order)
SEED = 0


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def make_audio(streams: int, samples: int, seed: int = SEED) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal((streams, samples)).astype(np.float32)


def serve(pipe, audio, *, mesh=None, label: str = ""):
    """Open one stream per audio row on a StreamRouter, feed the rows as
    ``ROUNDS`` packets through submit()/drain(), and return the server's
    view: final registers, decision rows per stream, the served results,
    the compiled step's text and timings."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import data_axes
    from repro.serving import StreamRouter
    from repro.serving.server import make_batched_step

    S, total = audio.shape
    packet = total // ROUNDS
    step = make_batched_step(pipe, mesh)
    router = StreamRouter(pipe, num_shards=1, capacity=S, step_fn=step,
                          mesh=mesh)
    server = router.shard(0)
    ids = [f"mic-{i:03d}" for i in range(S)]
    for sid in ids:
        router.open(sid)

    # AOT-compile the step the waves will run (bucket = next pow2 of the
    # packet) to time the compile and read its text
    bucket = 1 << (packet - 1).bit_length()
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=a.sharding)
    row = None if mesh is None else NamedSharding(mesh, P(data_axes(mesh)))
    t0 = time.perf_counter()
    compiled = step.lower(
        pipe, jax.tree.map(like, server.state),
        jax.ShapeDtypeStruct((S, bucket), jnp.float32, sharding=row),
        jax.ShapeDtypeStruct((S,), jnp.int32, sharding=row)).compile()
    compile_s = time.perf_counter() - t0

    round_s, results = [], {}
    for r in range(ROUNDS):
        sl = slice(r * packet, (r + 1) * packet)
        t0 = time.perf_counter()
        tickets = [router.submit([(ids[i], audio[i, sl])
                                  for i in range(g, S, CALLERS)])
                   for g in range(CALLERS)]
        router.drain()
        round_s.append(time.perf_counter() - t0)
        for t in tickets:
            _check(t.done, f"{label}: a ticket is unresolved after "
                           "drain()")
            for res in t.results:
                results[res.session_id] = res
    state = server.state
    jax.block_until_ready(state.acc)
    slots = np.asarray([router.session(sid).slot for sid in ids])
    return dict(state=state, slots=slots, ids=ids, results=results,
                text=compiled.as_text(), compile_s=compile_s,
                round_s=round_s, steps=server.steps_run)


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _rows(a, slots):
    return np.asarray(a)[slots]


def check_served(view, ref_rows, exact: bool, label: str) -> None:
    """Every served FeedResult is its stream's one-shot decision: the
    reference row's argmax and its value there — exactly in fixed mode; in
    float mode within ``FLOAT_ATOL`` (a label may differ only where the
    reference's top two classes are that close)."""
    for i, sid in enumerate(view["ids"]):
        res = view["results"].get(sid)
        _check(res is not None, f"{label}: no result for {sid}")
        _check(res.samples_seen == ROUNDS * PACKET,
               f"{label}: {sid} saw {res.samples_seen} samples")
        want = ref_rows[i]
        if exact:
            ok = (res.label == int(np.argmax(want))
                  and res.confidence == float(want[res.label]))
        else:
            ok = (float(want[res.label]) >= float(np.max(want)) - FLOAT_ATOL
                  and abs(res.confidence - float(want[res.label]))
                  <= FLOAT_ATOL)
        _check(ok, f"{label}: {sid} served (label {res.label}, confidence "
                   f"{res.confidence}); one-shot row {want.tolist()}")


def run_one_chip() -> None:
    """The four phases on one device, at full width."""
    import jax
    import jax.numpy as jnp

    from repro.configs.esc10_mp import make_pipeline
    from repro.core import fixed

    dev = jax.devices()[0]
    audio = make_audio(STREAMS, ROUNDS * PACKET)
    views = {}
    for numerics in ("float", "fixed"):
        ref = None
        for impl in ("xla", "pallas"):
            label = f"{numerics}/{impl}"
            pipe = make_pipeline(
                smoke=False, seed=SEED, stream_impl=impl, numerics=numerics,
                fixed_amax=FIXED_AMAX if numerics == "fixed" else None)
            cfg = pipe.config
            if ref is None:
                t0 = time.perf_counter()
                if numerics == "fixed":
                    prog = pipe.fixed_program()
                    p_q, _, s_q = fixed.infer_q(
                        prog, fixed.quantize_signal(prog, jnp.asarray(audio)))
                    ref = dict(p_q=np.asarray(p_q), acc=np.asarray(s_q))
                else:
                    ref = dict(p=np.asarray(pipe.predict(jnp.asarray(audio))))
                print(f"[{numerics}] one-shot reference over "
                      f"{audio.shape} in {time.perf_counter() - t0:.1f} s",
                      flush=True)
            view = serve(pipe, audio, label=label)
            state, slots = view["state"], view["slots"]
            print(f"[{label}] fs={cfg.fs:g} octaves={cfg.num_octaves} "
                  f"filters={cfg.num_filters} bp_taps={cfg.bp_taps} "
                  f"lp_taps={cfg.lp_taps} S={STREAMS} packet={PACKET} "
                  f"rounds={ROUNDS} steps={view['steps']} "
                  f"compile_s={view['compile_s']:.2f} "
                  f"first_round_s={view['round_s'][0]:.3f} "
                  f"steady_round_s={np.median(view['round_s'][1:]):.4f} "
                  f"peak_bytes={_peak_bytes(dev)}", flush=True)
            if impl == "pallas":
                _check("tpu_custom_call" in view["text"],
                       f"{label}: the compiled step holds no Mosaic kernel "
                       "(tpu_custom_call) — the kernel did not compile")
            if numerics == "fixed":
                prog = pipe.fixed_program()
                p_q, _ = fixed.readout_q(prog, state.acc)
                got = dict(p_q=_rows(p_q, slots), acc=_rows(state.acc, slots))
                for k in ("acc", "p_q"):
                    _check(np.array_equal(got[k], ref[k]),
                           f"{label}: {k} is not bitwise fixed.infer_q's "
                           f"({int(np.sum(got[k] != ref[k]))} entries "
                           "differ)")
                print(f"[{label}] acc and p codes bitwise == fixed.infer_q "
                      f"for all {STREAMS} streams", flush=True)
                check_served(view, np.asarray(prog.out_spec.dequantize(
                    jnp.asarray(ref["p_q"]))), True, label)
            else:
                p, _ = pipe.apply(jnp.zeros((STREAMS, 0), jnp.float32), state)
                p_rows = _rows(p, slots)
                err = float(np.max(np.abs(p_rows - ref["p"])))
                _check(err <= FLOAT_ATOL,
                       f"{label}: max |p - predict| = {err} > {FLOAT_ATOL}")
                got = dict(p=p_rows, acc=_rows(state.acc, slots))
                print(f"[{label}] max |p - predict| = {err:.3e} "
                      f"(limit {FLOAT_ATOL:g}) over {STREAMS} streams",
                      flush=True)
                check_served(view, ref["p"], False, label)
            views[label] = got

    for k in ("acc", "p_q"):
        same = np.array_equal(views["fixed/pallas"][k], views["fixed/xla"][k])
        _check(same, f"fixed: pallas {k} differs from xla")
    print("[fixed] pallas == xla bitwise (acc, p codes)", flush=True)
    fp, fx_ = views["float/pallas"], views["float/xla"]
    print(f"[float] pallas == xla bitwise: "
          f"acc {np.array_equal(fp['acc'], fx_['acc'])}, "
          f"p {np.array_equal(fp['p'], fx_['p'])} "
          f"(max |dp| = {float(np.max(np.abs(fp['p'] - fx_['p']))):.3e}, "
          f"reported, not required)", flush=True)


def run_four_chips(chips: int) -> None:
    """Fixed mode, both impls: the slot-sharded server on a ``chips``-way
    data mesh against the same server on one device, bitwise."""
    import jax

    from repro.configs.esc10_mp import make_pipeline
    from repro.launch.mesh import make_host_mesh

    devs = jax.devices()
    _check(len(devs) >= chips,
           f"--chips {chips} needs {chips} devices, found {len(devs)}")
    mesh = make_host_mesh(data=chips, model=1)
    audio = make_audio(STREAMS, ROUNDS * PACKET)
    per_device = STREAMS // chips
    for impl in ("xla", "pallas"):
        label = f"fixed/{impl}"
        pipe = make_pipeline(smoke=False, seed=SEED, stream_impl=impl,
                             numerics="fixed", fixed_amax=FIXED_AMAX)
        one = serve(pipe, audio, label=f"{label}/1chip")
        many = serve(pipe, audio, mesh=mesh, label=f"{label}/mesh")
        rows = sorted(s.data.shape[0]
                      for s in many["state"].acc.addressable_shards)
        _check(rows == [per_device] * chips,
               f"{label}: acc shard rows {rows}, expected {per_device} on "
               f"each of {chips} devices")
        if impl == "pallas":
            _check("tpu_custom_call" in many["text"],
                   f"{label}: the sharded step holds no Mosaic kernel")
        collectives = {c: many["text"].count(c)
                       for c in ("all-gather", "all-reduce", "all-to-all")}
        _check(not any(collectives.values()),
               f"{label}: the slot-sharded step moves data between chips "
               f"({collectives}); it should be collective-free")
        leaves = zip(jax.tree.leaves(one["state"]),
                     jax.tree.leaves(many["state"]))
        for k, (a, b) in enumerate(leaves):
            _check(np.array_equal(_rows(a, one["slots"]),
                                  _rows(b, many["slots"])),
                   f"{label}: sharded state leaf {k} differs from one chip")
        for sid in one["ids"]:
            r1, r2 = one["results"][sid], many["results"][sid]
            _check((r1.label, r1.confidence) == (r2.label, r2.confidence),
                   f"{label}: {sid} decision differs between 1 and "
                   f"{chips} chips")
        print(f"[{label}] {chips}-way slot mesh == one chip bitwise "
              f"(every register, every decision); acc rows per device "
              f"{rows}; collectives in step: none; "
              f"compile_s 1chip={one['compile_s']:.2f} "
              f"mesh={many['compile_s']:.2f}; steady_round_s 1chip="
              f"{np.median(one['round_s'][1:]):.4f} "
              f"mesh={np.median(many['round_s'][1:]):.4f}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the slot-sharded four-chip path")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']} jax={jax.__version__}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU (first device is "
              f"{device['platform']!r}); nothing was run", file=sys.stderr)
        return 1

    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    if args.chips > 1:
        run_four_chips(args.chips)
    else:
        run_one_chip()
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
