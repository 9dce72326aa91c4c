#!/usr/bin/env python3
"""Record ``data/serve_waves.xplane.pb.gz`` on a TPU.

About a second of the ``esc10-fixed.backlog`` cell's closed loop (a few
256 x 4096 waves and the stream rotations among them), set up and traced
as ``bench/run.py --trace 1`` traces its window, so the trace holds the
benchmark's ``bench.*`` spans, the server's ``serve.*`` spans and the
device's ops with their named scopes. The router's ``stats()`` before and
after the window go to ``data/serve_waves.stats.json``, and the split of
the window's device idle that ``bench/program_trace.py`` reads from both
is printed last, as one JSON object. With ``--seconds 20`` and ``--out``
elsewhere it records and splits a whole benchmark window.

    python3 bench/tests/record_waves.py [--seed N] [--seconds S] [--out DIR]
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CELL = "esc10-fixed.backlog"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20_240_417)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", default=os.path.join(ROOT, "bench", "tests",
                                                  "data"))
    args = ap.parse_args(argv)

    import jax

    from bench import program_trace, run, spec, system
    from bench import trace as tr

    cell = spec.cell(CELL)
    run.chips_or_refuse(cell["chips"])
    run.compile_cache()
    cfg, mix = cell["config"], cell["traffic"]
    pipe = system.pipeline(cfg, system.weights(cfg, args.seed))
    client = spec.loop(mix["loop"])
    router = system.router(cfg, pipe, client.capacity(mix), cell["chips"],
                           system.make_step)
    loop = client.Loop(router, args.seed, mix)
    loop.open()
    loop.warm()

    strip = lambda st: {k: v for k, v in st.items() if k != "shards"}
    before = strip(router.stats())
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    res = loop.run(args.seconds)
    jax.profiler.stop_trace()
    after = strip(router.stats())

    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "serve_waves.xplane.pb.gz")
    raw_path = tr.find(log_dir)
    with open(raw_path, "rb") as f:
        raw = f.read()
    with gzip.open(path, "wb") as g:
        g.write(raw)
    split = program_trace.split(tr.load(raw_path), program_trace.load(raw),
                                (before, after))
    shutil.rmtree(log_dir, ignore_errors=True)
    with open(os.path.join(args.out, "serve_waves.stats.json"), "w") as f:
        json.dump({"cell": CELL, "seed": args.seed, "window": res,
                   "before": before, "after": after}, f, indent=1,
                  sort_keys=True)
    print(f"{path}: {os.path.getsize(path)} bytes, {res}")
    print(json.dumps(split, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
