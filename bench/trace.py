"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

The trace holds the device's operations and the benchmark's own host
spans (``bench.*`` ``TraceAnnotation``s) on one clock. The window is the
``bench.window`` span. From that:

* busy: the union of the intervals in which an operation ran on a device
  (the operations line of each device plane), clipped to the window;
* idle gaps: the rest of the window, each charged to the innermost
  ``bench.*`` span open at its midpoint (``idle`` where none is);
* operation time: the summed durations of each operation name, which is
  where a kernel's time is read, under whatever name the trace gives it.
"""

from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    window: tuple                  # (start_ns, end_ns) of bench.window
    devices: dict                  # plane name -> (names, starts, ends)
    spans: list                    # [(name, start_ns, end_ns)], bench.*


def find(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def load(path: str, window: bool = True) -> Trace:
    """The trace at ``path``. Its window is the one ``bench.window`` span;
    with ``window=False`` a trace without one loads with no window."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if _is_device(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get(OPS_LINE)
            if line is None:
                continue
            ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in line.events]
            if ev:
                names, s, e = zip(*ev)
                devices[plane.name] = (list(names),
                                       np.asarray(s, np.float64),
                                       np.asarray(e, np.float64))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if window and len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    return Trace(window=windows[0] if windows else None, devices=devices,
                 spans=spans)


def merged(starts, ends, lo: float, hi: float) -> np.ndarray:
    """The union of intervals clipped to [lo, hi], as sorted disjoint
    (k, 2) segments."""
    s = np.clip(np.asarray(starts, np.float64), lo, hi)
    e = np.clip(np.asarray(ends, np.float64), lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return np.zeros((0, 2))
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:], s.size) - 1
    return np.stack([s[first], reach[last]], axis=1)


def busy_ns(tr: Trace, device: str) -> float:
    _, s, e = tr.devices[device]
    seg = merged(s, e, *tr.window)
    return float(np.sum(seg[:, 1] - seg[:, 0]))


def gaps(tr: Trace, device: str) -> np.ndarray:
    """Idle (k, 2) intervals of one device inside the window."""
    _, s, e = tr.devices[device]
    lo, hi = tr.window
    seg = merged(s, e, lo, hi)
    edges = np.concatenate([[lo], seg.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def idle_by_span(tr: Trace, device: str) -> dict:
    """Idle nanoseconds per ``bench.*`` span open at each gap's midpoint.
    The benchmark's spans inside the window follow one another, so at
    most one is open at a time; ``idle`` where none is."""
    inner = sorted((s, e, n) for n, s, e in tr.spans if n != WINDOW)
    starts = np.asarray([s for s, _, _ in inner], np.float64)
    out: dict = {}
    for a, b in gaps(tr, device):
        mid = (a + b) / 2
        k = int(np.searchsorted(starts, mid, side="right")) - 1
        name = inner[k][2] if k >= 0 and mid < inner[k][1] else "idle"
        out[name] = out.get(name, 0.0) + float(b - a)
    return out


def op_ns(tr: Trace, device: str) -> dict:
    """Summed duration per operation name, clipped to the window."""
    names, s, e = tr.devices[device]
    lo, hi = tr.window
    d = np.clip(e, lo, hi) - np.clip(s, lo, hi)
    out: dict = {}
    for n, x in zip(names, d):
        if x > 0:
            out[n] = out.get(n, 0.0) + float(x)
    return out
