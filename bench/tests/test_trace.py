"""The reduction from a profiler trace to the metrics: the union of busy
intervals, idle gaps charged to the host span open at the time, and
operation time; on hand-made intervals, and on a trace recorded on the
chip (``data/probe_waves.xplane.pb.gz``)."""

import gzip
import os

import numpy as np
import pytest

from bench import record, trace

from .conftest import DATA


def hand_made() -> trace.Trace:
    names = ["a", "b", "k", "c"]
    starts = np.asarray([10.0, 15.0, 40.0, 90.0])
    ends = np.asarray([20.0, 30.0, 60.0, 120.0])
    spans = [("bench.window", 0.0, 100.0), ("bench.submit", 0.0, 35.0),
             ("bench.drain", 35.0, 70.0), ("bench.rotate", 80.0, 95.0)]
    return trace.Trace(window=(0.0, 100.0),
                       devices={"/device:TPU:0": (names, starts, ends)},
                       spans=spans)


@pytest.mark.parametrize("iv,want", [
    ([(0, 1), (2, 3)], [(0, 1), (2, 3)]),
    ([(0, 5), (1, 2), (4, 7)], [(0, 7)]),
    ([(3, 4), (0, 1), (1, 2)], [(0, 2), (3, 4)]),
    ([(-5, 2), (8, 20)], [(0, 2), (8, 10)]),
])
def test_merged_is_the_union(iv, want):
    s, e = zip(*iv)
    got = trace.merged(s, e, 0.0, 10.0)
    assert got.tolist() == [list(map(float, w)) for w in want]


def test_busy_gaps_and_spans_by_hand():
    tr = hand_made()
    dev = "/device:TPU:0"
    assert trace.busy_ns(tr, dev) == 10 + 10 + 20 + 10   # 10-30 40-60 90-100
    assert trace.gaps(tr, dev).tolist() == [[0, 10], [30, 40], [60, 90]]
    assert trace.op_ns(tr, dev) == {"a": 10.0, "b": 15.0, "k": 20.0,
                                    "c": 10.0}


def test_idle_by_span_charges_the_open_span():
    tr = hand_made()
    # gap 0-10 (mid 5, submit), 30-40 (mid 35, drain starts at 35),
    # 60-90 (mid 75, no span open)
    assert trace.idle_by_span(tr, "/device:TPU:0") == {
        "bench.submit": 10.0, "bench.drain": 10.0, "idle": 30.0}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace recorded on a v5e (one chip): three 256 x 4096 waves and ten
    256 x 160 waves of the fixed Pallas step, each in a ``bench.*`` span.
    It has no ``bench.window``; the window here runs from the first span's
    start to the last one's end."""
    path = tmp_path_factory.mktemp("trace") / "probe.xplane.pb"
    with gzip.open(os.path.join(DATA, "probe_waves.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    tr = trace.load(str(path), window=False)
    waves = [(s, e) for n, s, e in tr.spans if n.endswith("_wave")]
    tr.window = (min(s for s, _ in waves), max(e for _, e in waves))
    return tr


def test_recorded_trace_has_a_tpu_and_the_spans(recorded):
    assert sorted(recorded.devices) == ["/device:TPU:0"]
    names = [n for n, _, _ in recorded.spans]
    assert names.count("bench.backlog_wave") == 3
    assert names.count("bench.live_wave") == 10


def test_recorded_busy_gaps_and_spans_add_up(recorded):
    dev = "/device:TPU:0"
    lo, hi = recorded.window
    busy = trace.busy_ns(recorded, dev)
    idle = trace.gaps(recorded, dev)
    assert 0 < busy < hi - lo
    assert busy + float(np.sum(idle[:, 1] - idle[:, 0])) == \
        pytest.approx(hi - lo)
    charged = trace.idle_by_span(recorded, dev)
    assert sum(charged.values()) == pytest.approx(hi - lo - busy)
    assert set(charged) <= {"bench.backlog_wave", "bench.live_wave", "idle"}
    # overlapping ops count twice in the sum, never less than the union
    assert sum(trace.op_ns(recorded, dev).values()) >= busy - 1


def test_recorded_kernel_time(recorded):
    run = record.Run(cfg={}, mix={}, chips=1, capacity=256, peak={},
                     result={}, spans={}, buckets={}, sizes={},
                     trace=recorded)
    k, busy = run.kernel_s(), run.busy_s()
    assert 0 < k < busy
    # the octave kernels are most of the step's device time
    assert k > 0.8 * busy
