"""What ``bench/program_trace.py`` reads of the program's own marks: idle
time intersected with the server's spans, span time, time under a named
scope and the split of the idle share; on hand-made intervals, on the
probe trace (no server spans: the benchmark's metrics read there as
before, and the split finds nothing of the server), and on a trace
recorded on the chip with the server's spans (``data/serve_waves.*``)."""

import gzip
import json
import os

import numpy as np
import pytest

from bench import program_trace as pt
from bench import record, spec, trace

from .conftest import DATA

DEV = "/device:TPU:0"
SPLIT = ("lifecycle_idle_share", "feed_idle_share", "rotate_ms",
         "readout_device_ms", "compiles_in_window")


def hand_made() -> tuple:
    """Ops a 10-20, b 15-30, k 40-60, c 90-120 and d 95-99 in a 0-100
    window, with server spans: the gap 60-90 straddles ``serve.close``,
    ``serve.open`` and ``serve.submit``; a ``serve.resolve`` runs inside
    the ``serve.close`` (a flush); and ops ``b`` and ``c`` (with ``d``
    nested in ``c``) ran under ``readout``."""
    names = ["a", "b", "k", "c", "d"]
    s = np.asarray([10.0, 15.0, 40.0, 90.0, 95.0])
    e = np.asarray([20.0, 30.0, 60.0, 120.0, 99.0])
    tr = trace.Trace(window=(0.0, 100.0), devices={DEV: (names, s, e)},
                     spans=[("bench.window", 0.0, 100.0)])
    prog = pt.Program(
        spans=[("serve.dispatch", 2.0, 12.0, {"waves": 1}),
               ("serve.resolve", 32.0, 36.0, {}),
               ("serve.close", 55.0, 70.0, {"slot": 1}),
               ("serve.resolve", 56.0, 65.0, {}),
               ("serve.open", 70.0, 80.0, {"slot": 1}),
               ("serve.submit", 80.0, 95.0, {"requests": 4})],
        scopes={"a": "jit(f)/session_step/octave_cascade/fir:",
                "b": "jit(f)/session_step/readout/add:",
                "c": "jit(f)/session_step/readout/while:",
                "d": "jit(f)/session_step/readout/while/body/add:"})
    return tr, prog


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10), (20, 30)], [(5, 25)], [(5, 10), (20, 25)]),
    ([(0, 10)], [(0, 2), (4, 6), (8, 12)], [(0, 2), (4, 6), (8, 10)]),
    ([(0, 1)], [(1, 2)], []),
    ([(0, 5)], [], []),
])
def test_intersect(a, b, want):
    seg = lambda iv: np.asarray(iv, np.float64).reshape(-1, 2)
    assert pt.intersect(seg(a), seg(b)).tolist() == \
        [list(map(float, w)) for w in want]


def test_idle_in_spans_charges_by_intersection():
    tr, prog = hand_made()
    # the gap 60-90 goes 10 to close, 10 to open and 10 to submit, where
    # the midpoint would charge all 30 to whatever is open at 75
    for name in ("serve.close", "serve.open", "serve.submit"):
        assert pt.idle_in_spans(tr, prog, DEV, (name,)) == 10.0, name
    assert pt.idle_in_spans(tr, prog, DEV, pt.LIFECYCLE) == 20.0
    # dispatch 2-10, resolve 32-36 and 60-65 (inside the close), submit
    assert pt.idle_in_spans(tr, prog, DEV, pt.FEED) == 8 + 4 + 5 + 10
    assert pt.idle_in_spans(tr, prog, DEV, pt.FEED,
                            outside=pt.LIFECYCLE) == 22.0
    idle = trace.gaps(tr, DEV)
    assert 20 + 22 <= float(np.sum(idle[:, 1] - idle[:, 0])) == 50.0
    assert pt.idle_in_spans(tr, prog, DEV, ("serve.park",)) == 0.0
    assert pt.span_union_ns(tr, prog, pt.LIFECYCLE) == 25.0
    # b 15-30 and c 90-100 (clipped), d inside c counted once
    assert pt.scope_busy_ns(tr, prog, DEV, "readout") == 25.0
    assert pt.scope_busy_ns(tr, prog, DEV, "octave_cascade") == 10.0
    assert pt.scope_busy_ns(tr, prog, DEV, "quantize") == 0.0


def test_split_on_hand_made_spans():
    tr, prog = hand_made()
    c = {"steps_run": 10, "compiles": {"launch": 2, "lifecycle": 1},
         "cache_loads": {"launch": 1, "lifecycle": 0}}
    c2 = {"steps_run": 15, "compiles": {"launch": 2, "lifecycle": 3},
          "cache_loads": {"launch": 1, "lifecycle": 1}}
    assert pt.split(tr, prog, (c, c2)) == pytest.approx({
        "idle_share": 50.0,                          # of a 100 ns window
        "lifecycle_idle_share": 20.0,
        "feed_idle_share": 22.0,
        "rotate_ms": 25e-6,                          # 25 ns over one open
        "readout_device_ms": 25e-6 / 5,
        "compiles_in_window": 3})
    # without the counters, what they give is left out
    assert set(pt.split(tr, prog)) == {"idle_share", "lifecycle_idle_share",
                                       "feed_idle_share", "rotate_ms"}
    # a program without spans, scopes or counters gives only the idle share
    bare = pt.split(tr, pt.Program(spans=[], scopes={}),
                    ({"steps_run": 0}, {"steps_run": 5}))
    assert bare == {"idle_share": 50.0}


@pytest.fixture(scope="module")
def probe(tmp_path_factory):
    """The probe trace of ``test_trace.py`` (three 256 x 4096 and ten
    256 x 160 waves, no ``bench.window``, no server spans), with the window
    from the first wave's start to the last one's end."""
    raw = pt.read(os.path.join(DATA, "probe_waves.xplane.pb.gz"))
    path = tmp_path_factory.mktemp("probe") / "probe.xplane.pb"
    path.write_bytes(raw)
    tr = trace.load(str(path), window=False)
    waves = [(s, e) for n, s, e in tr.spans if n.endswith("_wave")]
    tr.window = (min(s for s, _ in waves), max(e for _, e in waves))
    return tr, pt.load(raw)


# every per-layer metric and the breakdown on the probe, as the benchmark
# read them before the server had spans and the kernels had names
PROBE_READS = {
    "step_device_ms.backlog": 21.296114846153845,
    "step_mfu.backlog": 0.04261449697951993,
    "fir_mp_stream_q_roofline": 0.0565623407826621,
    "idle_share.backlog": 18.669240258443164,
}
PROBE_BREAKDOWN = {
    "device_ops": [["%_lambda_.6 tpu_custom_call", 0.128433465],
                   ["%_lambda_.7 tpu_custom_call", 0.06132818600000001],
                   ["%_lambda_.8 tpu_custom_call", 0.034385472],
                   ["%_lambda_.9 tpu_custom_call", 0.021764815],
                   ["%_lambda_.10 tpu_custom_call", 0.017450119],
                   ["%_lambda_.11 tpu_custom_call", 0.010668655],
                   ["%while", 0.001261942],
                   ["%while.1", 0.0012594750000000001],
                   ["%maximum_reduce_fusion.6", 0.0012290810000000001],
                   ["%maximum_reduce_fusion.7", 0.00122908]],
    "idle_gaps": [["bench.live_wave", 0.045500210000000006],
                  ["bench.backlog_wave", 0.018049789]]}


def test_probe_reads_as_before_and_splits_nothing(probe):
    from bench import run as bench_run
    tr, prog = probe
    run = record.Run(cfg=spec.config("esc10-mp-fixed"), mix={}, chips=1,
                     capacity=256, peak=spec.peaks("TPU v5 lite"),
                     result={"samples": 3 * 256 * 4096 + 10 * 256 * 160},
                     spans={}, buckets={4096: 3, 256: 10},
                     sizes={4096: 3 * 256, 160: 10 * 256}, trace=tr)
    got = {m["name"]: spec.reader(m["name"])(run)
           for m in spec.load()["per_layer"]}
    assert got == PROBE_READS
    b = bench_run.breakdown(run)
    assert [list(x) for x in b["device_ops"]] == PROBE_BREAKDOWN["device_ops"]
    assert [list(x) for x in b["idle_gaps"]] == PROBE_BREAKDOWN["idle_gaps"]
    assert prog.spans == []
    assert pt.split(tr, prog) == {"idle_share": pytest.approx(
        PROBE_READS["idle_share.backlog"], rel=1e-12)}


def test_probe_scopes_name_the_kernels(probe):
    tr, prog = probe
    names = tr.devices[DEV][0]
    kernels = {n for n in names if record.KERNEL_MARK in n}
    assert len(kernels) == 12             # six octaves, two wave shapes
    assert {prog.scopes[n] for n in kernels} == {"jit(<lambda>)/pallas_call:"}
    assert sum(n in prog.scopes for n in names) > 0.5 * len(names)


def test_traced_run_on_the_cpu_records_the_program(tiny_cell, monkeypatch):
    """A ``--trace 1`` run of ``bench/run.py`` at test size on the CPU:
    its trace holds the server's spans inside the window."""
    import time

    import jax

    from bench import run as bench_run

    monkeypatch.setattr(spec, "peaks", lambda kind: {})
    programs = []
    load = trace.load

    def keep(path, *a, **k):              # read the file before it goes
        programs.append(pt.load(pt.read(path)))
        return load(path, *a, **k)

    monkeypatch.setattr(trace, "load", keep)
    cell = tiny_cell("fixed", "backlog")
    cell["per_layer"] = spec.load()["per_layer"]
    out = bench_run.run_cell(cell, 987_654_321_012, 0.3, True,
                             time.perf_counter(), jax.devices()[:1])
    assert out["correct"], out["check"]
    (prog,) = programs
    names = {n for n, *_ in prog.spans}
    assert {"serve.open", "serve.close", "serve.submit", "serve.dispatch",
            "serve.stage", "serve.h2d", "serve.launch", "serve.resolve",
            "serve.readback", "serve.slot_reset"} <= names


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A trace recorded on a v5e (one chip) by ``record_waves.py``: a
    second of the ``esc10-fixed.backlog`` loop, eight 256 x 4096 waves and
    eight rotations in its ``bench.window``, with the server's spans, and
    the router's ``stats()`` around the window."""
    raw = pt.read(os.path.join(DATA, "serve_waves.xplane.pb.gz"))
    path = tmp_path_factory.mktemp("served") / "serve.xplane.pb"
    path.write_bytes(raw)
    with open(os.path.join(DATA, "serve_waves.stats.json")) as f:
        stats = json.load(f)
    return trace.load(str(path)), pt.load(raw), stats


def test_served_trace_names_the_kernels_and_scopes(served):
    tr, prog, _ = served
    (dev,) = tr.devices
    kernels = {n.split(" = ")[0] for n in tr.devices[dev][0]
               if record.KERNEL_MARK in n}
    assert kernels == {f"%fir_mp_stream_q_o{o}.1" for o in range(6)}
    assert all(prog.scopes[n].startswith("jit(<lambda>)/session_step/")
               for n in tr.devices[dev][0] if record.KERNEL_MARK in n)
    paths = set(prog.scopes.values())
    for scope in ("quantize", "octave_cascade", "readout"):
        assert any(f"/session_step/{scope}/" in p for p in paths), scope


def test_served_trace_spans_tie_each_wave_together(served):
    tr, prog, stats = served
    lo, hi = tr.window
    waves = range(stats["before"]["steps_run"], stats["after"]["steps_run"])
    for kind in ("serve.stage", "serve.h2d", "serve.launch",
                 "serve.readback"):
        got = sorted(m["wave"] for n, s, _, m in prog.spans
                     if n == kind and lo <= s < hi)
        assert got == list(waves), kind
    opens = [m["slot"] for n, s, _, m in prog.spans
             if n == "serve.open" and lo <= s < hi]
    closes = [m["slot"] for n, s, _, m in prog.spans
              if n == "serve.close" and lo <= s < hi]
    assert len(opens) == 8 and sorted(opens) == sorted(closes)
    delta = {k: stats["after"][k] - stats["before"][k]
             for k in ("drains", "readbacks", "slot_resets")}
    assert delta == {"drains": 8, "readbacks": 8, "slot_resets": 8 * 17}


# the benchmark's per-layer metrics and the split on the served trace, as
# first read
SERVED_READS = {
    "step_device_ms.backlog": 66.8763805,
    "step_mfu.backlog": 0.033179927528509026,
    "fir_mp_stream_q_roofline": 0.06408215952389558,
    "idle_share.backlog": 48.05147811258348,
}
SERVED_SPLIT = {
    "idle_share": 48.05147811258348,
    "lifecycle_idle_share": 42.659405868397336,
    "feed_idle_share": 4.372723366005427,
    "rotate_ms": 54.95493175,
    "readout_device_ms": 0.20005325000000002,
    "compiles_in_window": 0,
}


def test_served_trace_reads(served):
    tr, prog, stats = served
    steps = stats["after"]["steps_run"] - stats["before"]["steps_run"]
    run = record.Run(cfg=spec.config("esc10-mp-fixed"), mix={}, chips=1,
                     capacity=256, peak=spec.peaks("TPU v5 lite"),
                     result=stats["window"], spans={},
                     buckets={4096: steps}, sizes={4096: 256 * steps},
                     trace=tr)
    got = {m["name"]: spec.reader(m["name"])(run)
           for m in spec.load()["per_layer"]}
    assert got == pytest.approx(SERVED_READS, rel=1e-12)
    split = pt.split(tr, prog, (stats["before"], stats["after"]))
    assert split == pytest.approx(SERVED_SPLIT, rel=1e-12)
    assert split["lifecycle_idle_share"] + split["feed_idle_share"] <= \
        split["idle_share"]
    assert set(SPLIT) <= set(split)
