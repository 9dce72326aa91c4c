"""Serving parity gates: the decisions of the slot-batched StreamServer
along the paths that must agree.

* The async/coalescing front end: G callers' ``submit()``s resolved by
  one ``drain()`` against the same G callers' synchronous ``feed()``s,
  bit-for-bit in both numerics modes (a hard assert).
* ``--stream-impl both``: the stateful Pallas streaming kernel against
  the XLA session step on fresh servers, decisions and registers; a hard
  assert under ``--numerics fixed`` (int Pallas == int XLA).
* Streamed against one-shot decisions: exact equality under
  ``--numerics fixed`` (a hard assert, static ADC grid); the quantized
  float path's gap is reported, with the running amax seeded (a
  calibrated/held stream).

Off-TPU the Pallas kernels run in interpret mode. Speed is measured on the
chip by the benchmark in ``bench/`` (PERF.md), not here.

    PYTHONPATH=src python -m benchmarks.serve_streams [--slots 256] [--smoke]

Emits ``name,us_per_call,derived`` CSV rows like every other benchmark;
none of these rows is a timing.
"""

from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from benchmarks.common import row
from repro.configs.esc10_mp import make_pipeline
from repro.serving import StreamServer, make_batched_step

ROUNDS = 2  # chunks per stream


def _pow2_at_least(n: int) -> int:
    """Smallest power of two >= n: the server validates its chunk bounds
    as pow2 (the bucket-ladder contract), so an arbitrary packet length
    maps to the bucket it would pad into."""
    b = 1
    while b < n:
        b <<= 1
    return b


def main(argv=()):
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=256)
    ap.add_argument("--chunk", type=int, default=40,
                    help="sensor packet length in samples (default: 10 ms "
                         "at the smoke config's 4 kHz)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run for CI bit-rot checks")
    ap.add_argument("--stream-impl", choices=["xla", "pallas", "both"],
                    default="xla",
                    help="session-step hot path; 'both' also checks the "
                         "pallas-vs-xla decision parity")
    ap.add_argument("--numerics", choices=["float", "fixed"],
                    default="float",
                    help="serving engine; 'fixed' serves the bit-true "
                         "int32 hardware twin (parity rows become exact-"
                         "equality gates)")
    args = ap.parse_args(argv)
    S = 16 if args.smoke else args.slots
    CH = args.chunk
    primary_impl = "xla" if args.stream_impl == "both" else args.stream_impl
    nm = args.numerics
    tag = "" if nm == "float" else ".fixed"

    def _pipe(impl):
        # fixed: full-scale at ~4 sigma of the N(0,1) test audio so the
        # static ADC grid is exercised, not just saturated
        return make_pipeline(smoke=True, stream_impl=impl, numerics=nm,
                             fixed_amax=4.0 if nm == "fixed" else None)

    rng = np.random.default_rng(0)
    audio = rng.standard_normal((S, ROUNDS * CH)).astype(np.float32)
    ids = [f"s{i:04d}" for i in range(S)]

    # -- async/coalescing front end: G independent callers per round.
    # sync pays G full feed() calls; async coalesces the same G submits
    # into shared waves resolved by ONE drain. Decisions must stay
    # bit-for-bit identical — for BOTH numerics modes. --------------------
    G = 4 if args.smoke else 8
    L_ROUNDS = 2 if args.smoke else 4
    groups = [list(range(g, S, G)) for g in range(G)]
    # one pipeline + ONE shared compiled step across both passes, exactly
    # how the router shares it across shards
    pipe_c = _pipe(primary_impl)
    step_c = make_batched_step(pipe_c)

    def _caller_pass(async_path: bool) -> dict:
        srv = StreamServer(pipe_c, capacity=S,
                           max_chunk=_pow2_at_least(CH), step_fn=step_c)
        for sid in ids:
            srv.open(sid)
        dec = {}
        for r in range(L_ROUNDS):
            rr = r % ROUNDS
            parts = [[(ids[i], audio[i, rr * CH:(rr + 1) * CH]) for i in g]
                     for g in groups]
            if async_path:
                tickets = [srv.submit(part) for part in parts]
                srv.drain()
                results = [res for t in tickets for res in t.results]
            else:
                results = [res for part in parts for res in srv.feed(part)]
            for res in results:
                dec[(res.session_id, res.samples_seen)] = \
                    (res.label, res.confidence)
        return dec

    bitwise = _caller_pass(False) == _caller_pass(True)
    row(f"serve_streams.async_parity{tag}.S{S}.G{G}", None,
        f"bitwise={bitwise}")
    if not bitwise:
        raise AssertionError(
            "async/coalesced decisions != sync feed() decisions "
            f"({nm} numerics, {primary_impl}) — the bitwise serving "
            "contract is violated")

    # -- stateful Pallas streaming kernel vs the XLA session step -----------
    if args.stream_impl == "both":
        # decision parity on FRESH servers (history-free comparison);
        # registers are compared too — the server-parity gate covers the
        # full SessionState, not just the argmax
        fresh, regs = [], []
        for impl in ("xla", "pallas"):
            srv = StreamServer(_pipe(impl), capacity=S,
                               max_chunk=_pow2_at_least(CH))
            for sid in ids:
                srv.open(sid)
            res = None
            for r in range(ROUNDS):
                res = srv.feed([(sid, audio[i, r * CH:(r + 1) * CH])
                                for i, sid in enumerate(ids)])
            fresh.append(res)
            regs.append(np.asarray(srv.state.acc))
        bitwise = (all(a.label == b.label and a.confidence == b.confidence
                       for a, b in zip(*fresh))
                   and bool(np.array_equal(*regs)))
        if nm == "fixed" and not bitwise:
            # the int kernels carry an EXACT parity contract — a mismatch
            # is a correctness bug, not a benchmark footnote
            raise AssertionError(
                "fixed-numerics server parity violated: int Pallas != "
                "int XLA decisions/registers")
        row(f"serve_streams.pallas_parity{tag}.S{S}xC{CH}", None,
            f"bitwise={bitwise} (interpret mode off-TPU)")

    if nm == "fixed":
        # -- fixed streaming parity: chunked == one-shot at EXACT equality
        # (static ADC grid; docs/numerics.md) -------------------------------
        pipe_q = _pipe(primary_impl)
        xq = jnp.asarray(rng.standard_normal((4, 8 * CH)).astype(np.float32))
        p_one = pipe_q.apply(xq)
        state = pipe_q.init_session(4)
        p_s = None
        for i in range(0, xq.shape[1], CH):
            p_s, state = pipe_q.apply(xq[:, i:i + CH], state)
        exact = bool(np.array_equal(np.asarray(p_s), np.asarray(p_one)))
        row(f"serve_streams.fixed_parity.{primary_impl}", None,
            f"stream_vs_oneshot bitwise={exact}")
        if not exact:
            raise AssertionError(
                "fixed-numerics streaming parity violated: chunked apply "
                "!= one-shot apply")
    else:
        # -- quantized streaming parity (running amax, seeded = held
        # stream) -----------------------------------------------------------
        pipe_q = make_pipeline(smoke=True, quant_bits=8,
                               stream_impl=primary_impl)
        xq = jnp.asarray(rng.standard_normal((4, 8 * CH)).astype(np.float32))
        p_one = pipe_q.predict(xq)
        amax0 = jnp.max(jnp.abs(xq), axis=-1)
        state = pipe_q.init_session(4, amax=amax0)
        p_s = None
        for i in range(0, xq.shape[1], CH):
            p_s, state = pipe_q.apply(xq[:, i:i + CH], state)
        err = float(jnp.max(jnp.abs(p_s - p_one)))
        row("serve_streams.quant_parity", None,
            f"stream_vs_oneshot={err:.2e} bitwise={bool(err == 0.0)}")


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
