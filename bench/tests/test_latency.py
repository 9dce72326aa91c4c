"""Latency from due time, on a fake clock: the open loop's records and the
nearest-rank percentiles, with undecided packets counted as failed."""

import math
from types import SimpleNamespace

import numpy as np
import pytest

from bench import record, run, spec, stats

DRAIN_S = 0.006


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeRouter:
    """Decides every submitted packet at the next drain, which takes a
    fixed time on the fake clock."""

    def __init__(self, clock):
        self.clock, self.queued = clock, []

    def submit(self, reqs):
        ticket = SimpleNamespace(results=[
            SimpleNamespace(session_id=sid, label=0, confidence=0.0)
            for sid, _ in reqs])
        self.queued.extend(reqs)
        return ticket

    def drain(self):
        self.clock.t += DRAIN_S
        self.queued = []


def fake_run(seconds=0.05):
    clock = Clock()
    mix = {"loop": "open", "sensors": 2, "packet": 160, "period_s": 0.01,
           "pool": 4096}
    loop = spec.loop("open").Loop(FakeRouter(clock), 3, mix, clock=clock,
                                  sleep=clock.sleep)
    return loop.run(seconds)


def test_latency_runs_from_the_due_time():
    res = fake_run()
    # due every 5 ms from 12.5 ms; each drain takes 6 ms, so the queue
    # grows until two packets share the last drain
    assert np.allclose(res["due"], 0.0125 + 0.005 * np.arange(8))
    lat = np.round((res["done"] - res["due"]) * 1e3, 6)
    assert lat.tolist() == [6, 7, 8, 9, 10, 11, 12, 7]
    assert res["attempted"] == res["decided"] == 8


@pytest.mark.parametrize("q,missing,want", [
    (50, 0, 8.0), (99, 0, 12.0), (50, 1, 9.0), (99, 1, math.inf),
])
def test_percentile_counts_undecided_as_failed(q, missing, want):
    lat = [6, 7, 8, 9, 10, 11, 12, 7]
    assert stats.percentile(lat, q, missing=missing) == want


def test_end_to_end_reads_the_percentiles():
    res = fake_run()
    res["attempted"] += 1                       # one never decided
    names = [{"name": "decision_p50_ms", "unit": "ms"},
             {"name": "decision_p99_ms", "unit": "ms"}]
    m = run.metrics(names, record.Run(
        cfg={}, mix={}, chips=1, capacity=8, peak={}, result=res, spans={},
        buckets={}, sizes={}, setup_s=1.0))
    assert m["decision_p50_ms"]["value"] == pytest.approx(9.0)
    assert m["decision_p99_ms"]["value"] == math.inf
