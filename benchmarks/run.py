"""Benchmark driver — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [names...]

Emits ``name,us_per_call,derived`` CSV rows on stdout AND writes a
``BENCH_pipeline.json`` trajectory artifact (override the path with
``BENCH_OUT=...``): every row grouped per module plus run metadata, so
benchmark results are a diffable file instead of scrollback. Modules:
  accuracy_esc10       Table III  (ESC-10-like accuracy, 3 systems)
  accuracy_fsdd        Table IV   (speaker ID)
  bitwidth_sweep       Fig. 8     (accuracy vs bit width, QAT + true-int)
  filterbank_response  Fig. 4/6   (downsampling + MP distortion)
  hardware_cost        Table I/II (op census -> LUT equivalents; asserts
                       the int32 hardware twin is multiplierless, incl.
                       the Pallas-lowered streaming kernel)
  kernel_sweep         streaming-kernel shape sweep (block_s x chunk x
                       capacity, float + int; feeds the committed
                       autotune table)
  microbench           kernel reference timings
  pipeline_e2e         unified audio->decision pipeline: one-shot vs
                       streaming vs the seed per-filter path
  serve_streams        serving parity gates: async/coalesced vs sync
                       feed, Pallas vs XLA step, streamed vs one-shot
  load_gen             fleet parity gate: churning logical streams
                       through the sharded router, async vs sync paths
"""

from __future__ import annotations

import json
import os
import platform
import sys
import time
import traceback

from benchmarks.common import drain_rows

MODULES = [
    "microbench",
    "pipeline_e2e",
    "serve_streams",
    "load_gen",
    "kernel_sweep",
    "filterbank_response",
    "hardware_cost",
    "accuracy_fsdd",
    "bitwidth_sweep",
    "accuracy_esc10",
]

DEFAULT_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "..", "BENCH_pipeline.json")


def main() -> None:
    names = sys.argv[1:] or MODULES
    failures = []
    t_run = time.time()
    artifact = {
        "schema": "bench-trajectory-v1",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "platform": platform.platform(),
        "modules_requested": names,
        "modules": {},
        "failures": failures,
    }
    # every result names the device it ran on; an unreadable device is a
    # failed run, not an anonymous one
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devices = jax.devices()
    artifact["jax"] = jax.__version__
    artifact["device"] = {"platform": devices[0].platform,
                          "kind": devices[0].device_kind,
                          "count": len(devices)}
    artifact["devices"] = [str(d) for d in devices]
    print(f"# device: {artifact['device']}", flush=True)
    for name in names:
        print(f"# === benchmarks.{name} ===", flush=True)
        t0 = time.time()
        drain_rows()  # rows printed outside a module don't leak into it
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
            elapsed = time.time() - t0
            print(f"# {name} done in {elapsed:.1f}s", flush=True)
            artifact["modules"][name] = {
                "seconds": round(elapsed, 1),
                "rows": drain_rows(),
            }
        except Exception:  # noqa: BLE001
            failures.append(name)
            artifact["modules"][name] = {
                "seconds": round(time.time() - t0, 1),
                "error": traceback.format_exc(limit=5),
                "rows": drain_rows(),
            }
            print(f"# {name} FAILED:\n{traceback.format_exc()}", flush=True)
    artifact["total_seconds"] = round(time.time() - t_run, 1)
    # partial runs must not clobber the committed full-trajectory artifact:
    # only the full module list writes BENCH_pipeline.json by default
    # (BENCH_OUT always wins)
    default = DEFAULT_OUT if names == MODULES \
        else DEFAULT_OUT.replace(".json", ".partial.json")
    out = os.environ.get("BENCH_OUT", default)
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {os.path.normpath(out)} "
          f"({len(artifact['modules'])} modules)", flush=True)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
