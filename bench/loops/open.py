"""``loop: "open"``: always-on real-time sensors sending periodic packets.

Mix keys: ``sensors`` sensors, each sending a ``packet``-sample packet
every ``period_s`` seconds, whether or not earlier packets were decided.
Sensor i's packets fall due at phase_i + k * period_s (the packet's last
sample). The phases are spread evenly over the period and dealt to the
sensors in a seeded order, so every seed sends the same set of arrivals.
Each packet is a slice of the seeded audio pool at a seeded offset.

The client is one thread: it submits every packet that has come due, then
drains; with nothing due it sleeps until the next due time. Latency runs
from a packet's due time to the return of the ``drain()`` that decided it.
The router's capacity is ``sensors`` rounded up to a multiple of 8.
"""

from __future__ import annotations

import time
from collections import Counter

import numpy as np
from jax.profiler import TraceAnnotation

from bench import loadgen


def capacity(mix: dict) -> int:
    return -(-int(mix["sensors"]) // 8) * 8


class Schedule:
    """Due times and audio offsets of an open loop of periodic sensors."""

    def __init__(self, seed: int, mix: dict):
        self.sensors = int(mix["sensors"])
        self.packet = int(mix["packet"])
        self.period = float(mix["period_s"])
        self.pool_len = int(mix["pool"])
        g = loadgen.rng(seed, "phases")
        slots = (np.arange(self.sensors) + 0.5) / self.sensors * self.period
        self.phase = slots[g.permutation(self.sensors)]   # sensor -> phase
        self.order = np.argsort(self.phase, kind="stable")  # by phase
        self._off = loadgen.rng(seed, "offsets")
        self._offsets = np.zeros((self.sensors, 0), np.int64)

    def due(self, g: int) -> float:
        """Due time, from the schedule's origin, of the g-th packet in due
        order: cycle g // sensors, sensor order[g % sensors]."""
        c, j = divmod(g, self.sensors)
        return float(self.phase[self.order[j]]) + (c + 1) * self.period

    def sensor(self, g: int) -> int:
        return int(self.order[g % self.sensors])

    def offset(self, sensor: int, k: int) -> int:
        """Pool offset of sensor's k-th packet (drawn in cycles, so the
        same seed gives the same offsets however far a run goes)."""
        while k >= self._offsets.shape[1]:
            more = self._off.integers(0, self.pool_len - self.packet,
                                      (self.sensors, 512))
            self._offsets = np.concatenate([self._offsets, more], axis=1)
        return int(self._offsets[sensor, k])


class Loop:
    """The client of an open loop, on ``router``."""

    def __init__(self, router, seed: int, mix: dict,
                 clock=time.perf_counter, sleep=time.sleep):
        self.router = router
        self.sched = Schedule(seed, mix)
        self.pool = loadgen.pool(seed, mix)
        self.clock, self.sleep = clock, sleep
        n = self.sched.sensors
        self.ids = [f"sensor-{i:05d}" for i in range(n)]
        self.fed = np.zeros(n, np.int64)         # packets fed per sensor
        self.last = {}                           # sensor id -> FeedResult
        self.spans = {"submit": [], "drain": []}  # (seconds, requests)
        self.sizes = Counter()                   # request length -> count

    def open(self) -> None:
        for sid in self.ids:
            self.router.open(sid)

    def _requests(self, sensors):
        P, reqs = self.sched.packet, []
        for i in sensors:
            off = self.sched.offset(i, int(self.fed[i]))
            reqs.append((self.ids[i], self.pool[off:off + P]))
            self.fed[i] += 1
        return reqs

    def warm(self) -> None:
        """One packet from every sensor: the window's only shape."""
        t = self.router.submit(self._requests(range(self.sched.sensors)))
        self.router.drain()
        self._keep(t.results)

    def _keep(self, results) -> None:
        for r in results:
            self.last[r.session_id] = r

    def run(self, seconds: float) -> dict:
        s, clock = self.sched, self.clock
        due, sub, done = [], [], []
        g = 0
        with TraceAnnotation("bench.window"):
            t0 = clock()
            while True:
                now = clock() - t0
                batch = []
                while s.due(g) <= now and s.due(g) < seconds:
                    batch.append(g)
                    g += 1
                if not batch:
                    nxt = s.due(g)
                    if nxt >= seconds:
                        break
                    with TraceAnnotation("bench.wait"):
                        self.sleep(max(0.0, nxt - (clock() - t0)))
                    continue
                with TraceAnnotation("bench.client"):
                    reqs = self._requests([s.sensor(x) for x in batch])
                ts = clock()
                with TraceAnnotation("bench.submit"):
                    ticket = self.router.submit(reqs)
                tm = clock()
                with TraceAnnotation("bench.drain"):
                    self.router.drain()
                td = clock()
                self.spans["submit"].append((tm - ts, len(reqs)))
                self.spans["drain"].append((td - tm, len(reqs)))
                self._keep(ticket.results)
                for x in batch:
                    due.append(s.due(x))
                sub.extend([ts - t0] * len(batch))
                done.extend([td - t0] * len(batch))
        decided = len(done)
        self.sizes = Counter({s.packet: decided})
        return {"window_s": float(seconds), "due": np.asarray(due),
                "submit": np.asarray(sub), "done": np.asarray(done),
                "attempted": g, "decided": decided,
                "samples": decided * s.packet}

    def streams(self) -> list:
        """``(session id, sample count)`` of every open stream."""
        return [(sid, int(self.fed[i]) * self.sched.packet)
                for i, sid in enumerate(self.ids)]

    def audio_of(self, sid: str) -> np.ndarray:
        i, P = self.ids.index(sid), self.sched.packet
        return np.concatenate([
            self.pool[o:o + P] for o in
            (self.sched.offset(i, k) for k in range(int(self.fed[i])))])
