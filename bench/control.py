#!/usr/bin/env python3
"""Read a cell's compared numbers and its control's, seed after seed.

    python3 bench/control.py --workload esc10-fixed.backlog --seconds 5 \
        --seeds 11 12 13

One process on the chip. Per seed it serves the cell as ``bench/run.py``
does, for a short window at the cell's own load, then compares what the
window served with the plain reference, and compares the control (the
reference one precision step lower, put in the program's place: 4-bit
signals and weights for the int8 datapath, bfloat16 for float32) with the
same reference. It prints one JSON line per seed: the program's numbers
(the lower readings the limits rest on) and the control's (the upper
ones). The benchmark's own runs never run the control.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import run, spec
    cell = spec.cell(args.workload)
    try:
        devices = run.chips_or_refuse(cell["chips"])
    except run.NoChip as e:
        print(f"bench/control.py: {e}; nothing was run", file=sys.stderr)
        return 2
    run.compile_cache()
    for seed in args.seeds:
        out = run.run_cell(cell, seed, args.seconds, False,
                           time.perf_counter(), devices, control=True)
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "program": {k: v["value"] for k, v in out["check"].items()},
            "control": out["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
