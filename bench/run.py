#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process, no children. It refuses to run (exit 2, no result) unless
JAX's first device is a TPU and the cell's chips are there. It builds the
cell's configuration with weights from the seed, opens the traffic mix's
streams on a ``StreamRouter``, warms up the one chunk shape the mix uses
(all of that is ``setup_s``, from process start), measures for
``--seconds``, then checks a seeded sample of what the window served
against the plain reference and prints, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones, read from a profiler trace of the window), ``device``,
``breakdown`` (traced runs) and, last, ``check``: each number compared,
with its limit. The same numbers close standard error.

JAX's persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
where that is set, else at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The machine lacks the TPU chips the cell asks for."""


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_or_refuse(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"the first device is {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class Compiles:
    """Counts the backend compiles inside a ``with`` block."""

    def __enter__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._event)

    def _event(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1


def metrics(names: list, run) -> dict:
    """Each metric's reader (``bench/metrics/<name>.py``) applied to the
    run; a metric whose reader finds nothing is left out."""
    from bench import spec
    out = {}
    for m in names:
        v = spec.reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def breakdown(run) -> dict:
    from bench import trace
    dev = run.devices()[0]
    ops: dict = {}
    for name, ns in trace.op_ns(run.trace, dev).items():
        head = name.split(" = ")[0]
        if 'custom_call_target="' in name:
            head += " " + name.split('custom_call_target="')[1].split('"')[0]
        ops[head] = ops.get(head, 0.0) + ns
    top = lambda d: [[k, v * 1e-9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(ops),
            "idle_gaps": top(trace.idle_by_span(run.trace, dev))}


def run_cell(cell: dict, seed: int, seconds: float, traced: bool,
             t0: float, devices: list, step=None,
             control: bool = False) -> dict:
    """Set up, measure and check one cell on ``devices``; the result line
    as a dict. ``step`` replaces the program's session step (tests);
    ``control`` also reads the control's numbers (``bench/control.py``)."""
    import jax

    from bench import compare, record, spec, system
    from bench import trace as tr

    cfg, mix, chips = cell["config"], cell["traffic"], cell["chips"]
    marks = [("start", t0), ("init", time.perf_counter())]
    w = system.weights(cfg, seed)
    pipe = system.pipeline(cfg, w)
    marks.append(("weights", time.perf_counter()))
    client = spec.loop(mix["loop"])
    cap = client.capacity(mix)
    router = system.router(cfg, pipe, cap, chips,
                           step or system.make_step)
    loop = client.Loop(router, seed, mix)
    marks.append(("router", time.perf_counter()))
    loop.open()
    marks.append(("open", time.perf_counter()))
    loop.warm()
    marks.append(("warm", time.perf_counter()))
    setup_s = marks[-1][1] - t0
    parts = ", ".join(f"{b[0]} {b[1] - a[1]:.2f}"
                      for a, b in zip(marks, marks[1:]))

    before = dict(router.shard(0).bucket_counts)
    log_dir = None
    if traced:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    with Compiles() as compiles:
        res = loop.run(seconds)
    if traced:
        jax.profiler.stop_trace()
    after = router.shard(0).bucket_counts
    buckets = {L: n - before.get(L, 0) for L, n in after.items()
               if n - before.get(L, 0)}

    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                      0)) for d in devices)
    kind = devices[0].device_kind

    # what the window served, for the check; then free the program
    sids = compare.sample(loop, seed, int(mix["check_streams"]))
    slots = [router.session(s).slot for s in sids]
    acc_rows = jax.device_get(router.shard(0).state.acc)[slots]
    served = [loop.last[s] for s in sids]
    audio = [loop.audio_of(s) for s in sids]
    sizes = dict(loop.sizes)
    spans = loop.spans
    loop.router = None
    del router, pipe
    gc.collect()

    x, lengths = compare.batch(audio)
    t_ref = time.perf_counter()
    ref = compare.reference(cfg, w, x, lengths)
    t_ref = time.perf_counter() - t_ref
    nums = compare.numbers(cfg, compare.served_of(cfg, acc_rows, served, ref),
                           ref)
    lim = compare.limits(cfg)
    failed = int(res["attempted"] - res["decided"])
    correct = compare.verdict(nums, lim) and failed == 0

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": failed}
    run = record.Run(cfg=cfg, mix=mix, chips=chips, capacity=cap,
                     peak=spec.peaks(kind) if traced else {}, result=res,
                     spans=spans, buckets=buckets, sizes=sizes,
                     setup_s=setup_s)
    if traced:
        run.trace = tr.load(tr.find(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        out["metrics"] = metrics(cell["per_layer"], run)
        if run.trace.devices:
            device["busy_s"] = run.busy_s()
            device["window_s"] = run.window_s()
            out["breakdown"] = breakdown(run)
    else:
        out["metrics"] = metrics(cell["end_to_end"], run)
        missing = {m["name"] for m in cell["end_to_end"]} - set(out["metrics"])
        if missing:
            raise RuntimeError(f"end-to-end metrics with no reading: "
                               f"{sorted(missing)}")
    out["device"] = device
    if control:
        ctl = compare.reference(cfg, w, x, lengths, control=True)
        out["control"] = compare.numbers(cfg, compare.control_served(ctl),
                                         ref)
    out["check"] = {k: {"value": nums[k], "limit": lim[k]} for k in lim}
    out["check"]["failed"] = {"value": failed, "limit": 0}
    info = (f"cell {cell['name']} seed {seed}: setup_s {setup_s:.3f} "
            f"({parts}), "
            f"window_s {res['window_s']:.3f}, samples {res['samples']}, "
            f"requests {res['attempted']} ({failed} undecided), "
            f"compiles in window {compiles.n}, steps {run.steps()} "
            f"{dict(sorted(buckets.items()))}, check sample {len(sids)} "
            f"streams up to {int(lengths.max())} samples, reference "
            f"{t_ref:.2f} s")
    print(info, file=sys.stderr)
    for k, v in out["check"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    return out


def main(argv=None) -> int:
    args = parse(argv)
    from bench import spec
    cell = spec.cell(args.workload)
    try:
        devices = chips_or_refuse(cell["chips"])
    except NoChip as e:
        print(f"bench/run.py: {e}; nothing was run", file=sys.stderr)
        return 2
    spec.peaks(devices[0].device_kind)      # an unknown chip is an error
    compile_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T0,
                   devices)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
