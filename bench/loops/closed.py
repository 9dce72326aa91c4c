"""``loop: "closed"``: resident streams uploading recorded clips.

Mix keys: ``streams`` resident streams upload clips of ``clip`` samples
in ``chunk``-sample pieces. Each request carries every stream's next
piece and is followed by one ``drain()``; the next request goes once the
last is decided. A finished clip closes its stream and a new stream opens
in its slot with the next clip. The first clips start at evenly spread
positions, dealt in a seeded order, so the rotations spread over the run
and every seed has the same set of them. A clip is a slice of the seeded
audio pool at a seeded offset. The router's capacity is ``streams``.
"""

from __future__ import annotations

import time
from collections import Counter

import numpy as np
from jax.profiler import TraceAnnotation

from bench import loadgen


def capacity(mix: dict) -> int:
    return int(mix["streams"])


class Schedule:
    """Clip offsets and first start positions of a closed loop."""

    def __init__(self, seed: int, mix: dict):
        self.streams = int(mix["streams"])
        self.chunk = int(mix["chunk"])
        self.clip = int(mix["clip"])
        self.pool_len = int(mix["pool"])
        g = loadgen.rng(seed, "starts")
        spread = (np.arange(self.streams) * self.clip) // self.streams
        self.start = spread[g.permutation(self.streams)]
        self._clips = loadgen.rng(seed, "clips")

    def next_clip(self) -> int:
        """Pool offset of the next clip to open, in opening order."""
        return int(self._clips.integers(0, self.pool_len - self.clip + 1))


class Loop:
    """The client of a closed loop, on ``router``."""

    def __init__(self, router, seed: int, mix: dict,
                 clock=time.perf_counter):
        self.router = router
        self.sched = Schedule(seed, mix)
        self.pool = loadgen.pool(seed, mix)
        self.clock = clock
        n = self.sched.streams
        self.opened = 0
        self.ids = [""] * n
        self.clip = np.zeros(n, np.int64)        # pool offset of the clip
        self.first = np.zeros(n, np.int64)       # session's first sample
        self.pos = np.zeros(n, np.int64)         # next sample in the clip
        self.last = {}
        self.spans = {"submit": [], "drain": []}
        self.sizes = Counter()               # request length -> count

    def _start(self, i: int, first: int) -> None:
        self.ids[i] = f"rec-{self.opened:07d}"
        self.opened += 1
        self.clip[i] = self.sched.next_clip()
        self.first[i] = self.pos[i] = first
        self.router.open(self.ids[i])

    def open(self) -> None:
        for i in range(self.sched.streams):
            self._start(i, int(self.sched.start[i]))

    def _wave(self) -> int:
        C, L = self.sched.chunk, self.sched.clip
        reqs, n = [], 0
        with TraceAnnotation("bench.client"):
            for i, sid in enumerate(self.ids):
                a = int(self.clip[i] + self.pos[i])
                m = min(C, L - int(self.pos[i]))
                reqs.append((sid, self.pool[a:a + m]))
                self.sizes[m] += 1
                self.pos[i] += m
                n += m
        ts = self.clock()
        with TraceAnnotation("bench.submit"):
            ticket = self.router.submit(reqs)
        tm = self.clock()
        with TraceAnnotation("bench.drain"):
            self.router.drain()
        td = self.clock()
        self.spans["submit"].append((tm - ts, len(reqs)))
        self.spans["drain"].append((td - tm, len(reqs)))
        for r in ticket.results:
            self.last[r.session_id] = r
        with TraceAnnotation("bench.rotate"):
            for i in np.flatnonzero(self.pos >= L):
                self.router.close(self.ids[i])
                self._start(int(i), 0)
        return n

    def warm(self) -> None:
        self._wave()

    def run(self, seconds: float) -> dict:
        samples = waves = 0
        self.sizes.clear()
        self.spans = {"submit": [], "drain": []}
        with TraceAnnotation("bench.window"):
            t0 = self.clock()
            while self.clock() - t0 < seconds:
                samples += self._wave()
                waves += 1
            window = self.clock() - t0
        n = waves * self.sched.streams
        return {"window_s": window, "samples": samples, "attempted": n,
                "decided": n}

    def streams(self) -> list:
        """``(session id, sample count)`` of every open stream."""
        return [(sid, int(self.pos[i] - self.first[i]))
                for i, sid in enumerate(self.ids)]

    def audio_of(self, sid: str) -> np.ndarray:
        i = self.ids.index(sid)
        a = int(self.clip[i])
        return self.pool[a + int(self.first[i]):a + int(self.pos[i])]
