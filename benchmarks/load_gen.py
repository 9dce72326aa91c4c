"""Fleet parity gate: replay a churning fleet of logical sensor streams
through the serving tier along two paths that must decide alike.

This is the acoupi traffic shape (PAPERS.md): many long-lived edge
recorders phoning home with jittery, variable-length packets and churning
lifetimes. The generator builds a DETERMINISTIC schedule (seeded rng,
O(active-set) memory) and replays the SAME schedule through two paths
over an identically-configured ``StreamRouter``:

  sync   G independent callers per round, each paying a full synchronous
         ``feed()`` (dispatch + decision readback per caller);
  async  the same G callers ``submit()`` into the coalescing queue and
         one ``drain()`` resolves the round (shared waves, one readback).

Decisions must match bit-for-bit between the paths — under churn
(admission pressure auto-evicts LRU sessions to per-shard checkpoints;
evicted streams reopen losslessly when they next emit), under request
splitting, and under coalesced wave composition; a mismatch is a hard
assert. ``--smoke`` runs a small traffic sample through BOTH numerics
modes (wired into scripts/bench_smoke.sh -> tier1.sh). Speed is measured
on the chip by the benchmark in ``bench/`` (PERF.md), not here.

    PYTHONPATH=src python -m benchmarks.load_gen [--window 256] [--smoke]

Emits ``name,us_per_call,derived`` CSV rows like every other benchmark;
none of them is a timing.
"""

from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import row
from repro.serving import StreamRouter

POOL = 1 << 15  # shared sample pool; packets slice it at random offsets


def _traffic(seed, n_streams, window, rounds, chunk_lo, chunk_hi,
             life_lo, life_hi, emit_prob, evict_prob):
    """Yield (admits, burst, retires, evicts) per round. Deterministic for
    a given seed, so both replay paths see identical traffic; memory is
    O(window) no matter how many logical streams the fleet cycles
    through. ``evicts`` picks still-alive streams to park mid-lifetime —
    they reopen (losslessly, from their shard's checkpoint) when they next
    emit, which is what makes churn a PARITY test and not just load."""
    rng = np.random.default_rng(seed)
    active: dict = {}          # sid -> packets remaining in its lifetime
    next_id = 0
    for _ in range(rounds):
        admits = []
        while len(active) < window and next_id < n_streams:
            sid = f"st-{next_id:07d}"
            active[sid] = int(rng.integers(life_lo, life_hi + 1))
            admits.append(sid)
            next_id += 1
        burst, retires = [], []
        for sid in list(active):
            if rng.random() < emit_prob:
                ln = int(rng.integers(chunk_lo, chunk_hi + 1))
                off = int(rng.integers(0, POOL - ln))
                burst.append((sid, off, ln))
                active[sid] -= 1
                if active[sid] <= 0:
                    retires.append(sid)
                    del active[sid]
        evicts = [sid for sid in active if rng.random() < evict_prob]
        yield admits, burst, retires, evicts


def _replay(router: StreamRouter, schedule, pool, groups: int, mode: str):
    """Drive one schedule through the router. Returns (decisions, packets
    fed, reopens)."""
    decisions = {}
    n_pkts = 0
    reopens = 0

    def record(results):
        for r in results:
            decisions[(r.session_id, r.samples_seen)] = (r.label,
                                                         r.confidence)

    for admits, burst, retires, evicts in schedule:
        for sid in admits:
            router.open(sid)
        # parked streams reopen (losslessly, from their shard's
        # checkpoint) BEFORE the round's submits — open() flushes the
        # coalescing queue, so admissions mid-round would change wave
        # composition between the two paths
        for sid, _, _ in burst:
            if not router.is_open(sid):
                router.open(sid)
                reopens += 1
        reqs = [(sid, pool[off:off + ln]) for sid, off, ln in burst]
        n_pkts += len(reqs)
        parts = [reqs[g::groups] for g in range(groups)]
        if mode == "sync":
            for part in parts:
                if part:
                    record(router.feed(part))
        else:
            tickets = [router.submit(part) for part in parts if part]
            router.drain()
            for ticket in tickets:
                record(ticket.results)
        for sid in retires:
            if router.is_open(sid):
                router.close(sid)
        for sid in evicts:
            if router.is_open(sid):
                router.evict(sid)
    return decisions, n_pkts, reopens


def _run_fleet(args, numerics: str, tag: str, hard_churn: bool):
    import tempfile

    from repro.configs.esc10_mp import make_pipeline

    pipe = make_pipeline(smoke=True, stream_impl=args.stream_impl,
                         numerics=numerics,
                         fixed_amax=4.0 if numerics == "fixed" else None)
    rng = np.random.default_rng(args.seed)
    pool = rng.standard_normal(POOL).astype(np.float32)

    def make_router():
        # full-window capacity per shard: crc32 imbalance must never make
        # a shard unable to hold its share of one round's burst (churn
        # comes from the schedule's explicit evict events, not from
        # admission pressure)
        return StreamRouter(pipe, num_shards=args.shards,
                            capacity=args.window,
                            checkpoint_dir=tempfile.mkdtemp(
                                prefix="load_gen_ck_"),
                            max_chunk=args.max_chunk)

    def schedule():
        return _traffic(args.seed, args.streams, args.window, args.rounds,
                        args.chunk_lo, args.chunk_hi,
                        args.life_lo, args.life_hi, args.emit_prob,
                        args.evict_prob)

    out = {}
    for mode in ("sync", "async"):
        out[mode] = _replay(make_router(), schedule(), pool, args.groups,
                            mode)
    (dec_s, n_pkts, _), (dec_a, _, reopens) = out["sync"], out["async"]
    bitwise = dec_s == dec_a       # exact: labels, confidences, counts
    row(f"load_gen.async_parity{tag}.W{args.window}.G{args.groups}", None,
        f"bitwise={bitwise} ({n_pkts} packets, {reopens} evict-reopens)")
    if not bitwise:
        raise AssertionError(
            f"async/coalesced decisions != sync feed() decisions "
            f"({numerics} numerics, {args.stream_impl}) — the bitwise "
            "serving contract is violated")
    if hard_churn:
        # the parity claim must have covered churn: at least one evicted
        # stream must have come back through a checkpoint
        assert reopens > 0, "smoke schedule exercised no evict->reopen churn"


def main(argv=()):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small fleet, BOTH numerics modes, hard assert "
                         "async decisions == sync decisions (CI gate)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--streams", type=int, default=1024,
                    help="logical stream ids cycled through the window "
                         "(the schedule is O(window) memory)")
    ap.add_argument("--window", type=int, default=256,
                    help="max concurrently-active streams (= total slot "
                         "capacity across shards)")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--groups", type=int, default=8,
                    help="independent callers per round (sync pays one "
                         "feed() each; async coalesces them)")
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--max-chunk", type=int, default=256)
    ap.add_argument("--chunk-lo", type=int, default=20)
    ap.add_argument("--chunk-hi", type=int, default=200)
    ap.add_argument("--life-lo", type=int, default=2)
    ap.add_argument("--life-hi", type=int, default=6)
    ap.add_argument("--emit-prob", type=float, default=0.85)
    ap.add_argument("--evict-prob", type=float, default=0.1,
                    help="per-round chance an active stream is parked to "
                         "its shard's checkpoint (reopens on next emit)")
    ap.add_argument("--stream-impl", choices=["xla", "pallas"],
                    default="xla")
    ap.add_argument("--numerics", choices=["float", "fixed"],
                    default="float")
    args = ap.parse_args(argv)

    if args.smoke:
        # tiny fleet but real churn: window > capacity pressure comes from
        # crc32 shard imbalance, so evict/reopen paths ARE exercised
        args.streams, args.window, args.rounds = 40, 12, 8
        args.groups, args.shards, args.max_chunk = 3, 2, 128
        args.chunk_lo, args.chunk_hi = 10, 100
        args.evict_prob = 0.3   # make evict->reopen churn certain
        for nm in ("float", "fixed"):
            tag = "" if nm == "float" else ".fixed"
            _run_fleet(args, nm, f".smoke{tag}", hard_churn=True)
        print("load_gen --smoke: async == sync decisions (both numerics)",
              flush=True)
        return

    tag = "" if args.numerics == "float" else ".fixed"
    _run_fleet(args, args.numerics, tag, hard_churn=False)


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
