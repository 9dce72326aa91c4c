#!/usr/bin/env python3
"""Find the knee of an open-loop mix: the most sensors a configuration
keeps up with.

    python3 bench/knee.py --config esc10-mp-fixed --traffic live --seed 1 \
        --seconds 6 --sensors 32 64 96 128 160 192 224 256

One process on one chip. For each sensor count it serves the mix's open
loop (its traffic file, with ``sensors`` replaced) for ``--seconds`` and
prints, as one JSON line, the packets due but undecided at the middle and
at the end of the window, p50 and p99 latency from due time, and how late
the generator ran. A count is kept up with when the backlog at the end is
no larger than at the middle plus one period's packets. The mix's
traffic file then fixes about four fifths of the knee.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def backlog(res: dict, t: float) -> int:
    """Packets due by ``t`` (window seconds) and not decided by then."""
    return int((res["due"] <= t).sum() - (res["done"] <= t).sum())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a configuration's name in BENCHMARK.json")
    ap.add_argument("--traffic", required=True,
                    help="a mix's name under bench/traffic")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--sensors", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import spec, stats, system
    from bench.run import NoChip, chips_or_refuse, compile_cache

    cfg = spec.config(args.config)
    traffic = spec._json(spec.traffic_path(args.traffic))
    try:
        chips_or_refuse(1)
    except NoChip as e:
        print(f"bench/knee.py: {e}; nothing was run", file=sys.stderr)
        return 2
    compile_cache()
    w = system.weights(cfg, args.seed)
    pipe = system.pipeline(cfg, w)
    client = spec.loop(traffic["loop"])
    for n in args.sensors:
        mix = dict(traffic, sensors=n)
        t0 = time.perf_counter()
        router = system.router(cfg, pipe, client.capacity(mix), 1)
        loop = client.Loop(router, args.seed, mix)
        loop.open()
        loop.warm()
        setup = time.perf_counter() - t0
        res = loop.run(args.seconds)
        lat = (res["done"] - res["due"]) * 1e3
        period = float(mix["period_s"])
        mid = backlog(res, args.seconds / 2)
        end = backlog(res, args.seconds - 1e-9)
        row = {"sensors": n, "capacity": client.capacity(mix),
               "setup_s": setup, "packets": res["decided"],
               "backlog_mid": mid, "backlog_end": end,
               "steady": end <= mid + int(n * 1.0),
               "p50_ms": stats.percentile(lat, 50),
               "p99_ms": stats.percentile(lat, 99),
               "gen_late_p99_ms": stats.percentile(
                   (res["submit"] - res["due"]) * 1e3, 99),
               "drains": len(loop.spans["drain"]),
               "period_s": period}
        # admission cost once warm: close and reopen a few streams
        t1 = time.perf_counter()
        for sid in loop.ids[:16]:
            router.close(sid)
            router.open(sid)
        row["reopen_ms"] = (time.perf_counter() - t1) / 16 * 1e3
        print(json.dumps(row), flush=True)
        del router, loop
    return 0


if __name__ == "__main__":
    sys.exit(main())
