"""What one run leaves for the per-layer metric readers.

A metric's reader (``bench/metrics/<metric>.py``) gets a :class:`Run` and returns
its number, or ``None`` where the run holds nothing for it to read: then
the metric is left out of the result line.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from bench import counts, trace

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Run:
    cfg: dict                      # the configuration file
    mix: dict                      # the traffic file
    chips: int
    capacity: int                  # slots of the router
    peak: dict                     # the device's row of bench/peaks.json
    result: dict                   # what the client loop measured
    spans: dict                    # host-clock spans: name -> [(s, n)]
    buckets: dict                  # chunk bucket -> steps in the window
    sizes: dict                    # request sample count -> requests
    setup_s: float = 0.0           # process start to window start
    trace: Optional[trace.Trace] = None

    # -- reads of the trace ------------------------------------------------

    def traced(self) -> bool:
        """A trace is there and shows device operations."""
        return self.trace is not None and bool(self.trace.devices)

    def devices(self) -> list:
        return sorted(self.trace.devices)[:self.chips]

    def window_s(self) -> float:
        lo, hi = self.trace.window
        return (hi - lo) * 1e-9

    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over the chips."""
        return float(np.mean([trace.busy_ns(self.trace, d)
                              for d in self.devices()])) * 1e-9

    def kernel_s(self) -> float:
        """Seconds in Mosaic kernels (every Pallas call on the TPU is a
        ``tpu_custom_call``), averaged over the chips."""
        per = []
        for d in self.devices():
            ops = trace.op_ns(self.trace, d)
            per.append(sum(v for k, v in ops.items() if KERNEL_MARK in k))
        return float(np.mean(per)) * 1e-9

    # -- reads of the counters ---------------------------------------------

    def steps(self) -> int:
        return int(sum(self.buckets.values()))

    def kernel_least_s(self) -> float:
        """The least time the step's kernel calls in the window could take
        on one chip: per call the larger of its operations over the peak
        and its bytes over the HBM bandwidth."""
        slots = self.capacity // self.chips
        op_peak = float(self.peak[self.cfg["peak"]])
        bw = float(self.peak["hbm_bytes_per_s"])
        total = 0.0
        for length, steps in self.buckets.items():
            calls = counts.kernel_calls(self.cfg, slots, int(length))
            total += steps * sum(max(o / op_peak, b / bw) for o, b in calls)
        return total
