#!/usr/bin/env python3
"""What the program marks in a profiler trace, beside what ``bench/trace.py``
reads: the server's ``serve.*`` spans with their metadata, and the named
scope path each device operation was traced under (the ``tf_op`` stat of
the operation's metadata, which ``ProfileData`` does not expose and
:func:`op_scopes` reads from the file itself). From those, on the clock of
the device trace:

* idle in spans: the window's idle time that intersects a set of program
  spans, interval by interval (:func:`idle_in_spans`), where
  ``trace.idle_by_span`` charges each whole gap to the span open at its
  midpoint;
* span time: the union of a set of program spans in the window;
* scope time: the union of the intervals of the operations traced under a
  named scope (:func:`scope_busy_ns`).

:func:`split` reduces a trace and the router's ``stats()`` before and after
its window to the numbers that split the device's idle share by what the
server was doing. Run on a trace file (``record_waves.py`` writes one with
its stats):

    python3 bench/program_trace.py TRACE.xplane.pb[.gz] [STATS.json]

prints them as one JSON object.
"""

from __future__ import annotations

import argparse
import dataclasses
import gzip
import json
import os
import sys
import tempfile

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace  # noqa: E402

PROGRAM = "serve."                 # the server's own spans
SCOPE_STAT = "tf_op"               # an op's named-scope path, in its metadata
LIFECYCLE = ("serve.open", "serve.close")
FEED = ("serve.submit", "serve.dispatch", "serve.resolve")


@dataclasses.dataclass
class Program:
    spans: list                    # [(name, start_ns, end_ns, {metadata})]
    scopes: dict                   # device op name -> named-scope path


def read(path: str) -> bytes:
    """The serialized XSpace at ``path``, gzipped or not."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


def load(raw: bytes) -> Program:
    """The program's spans and the ops' scopes in a serialized XSpace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(raw)
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(PROGRAM):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, dict(e.stats)))
    return Program(spans=spans, scopes=op_scopes(raw))


# -- the ops' named scopes, from the XSpace protobuf ------------------------
# Field numbers of tsl/profiler/protobuf/xplane.proto: XSpace.planes 1;
# XPlane.name 2, event_metadata 4, stat_metadata 5 (maps: key 1, value 2);
# XEventMetadata.name 2, stats 5; XStat.metadata_id 1, str_value 5,
# ref_value 7 (a string kept as a stat metadata's name); XStatMetadata.name 2.

def _varint(buf: bytes, i: int) -> tuple:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: int = None):
    """``(field, value)`` of one message in ``buf[lo:hi]``; a
    length-delimited value is its ``(start, end)`` in ``buf``."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _text(buf: bytes, span: tuple) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, entry: tuple):
    for field, value in _fields(buf, *entry):
        if field == 2:
            return value
    return None


def op_scopes(raw: bytes) -> dict:
    """Device op name -> its named-scope path (the ``tf_op`` stat of the
    op's event metadata), over the device planes of a serialized XSpace."""
    out: dict = {}
    for field, plane in _fields(raw):
        if field != 1:
            continue
        name, events, stat_names = None, [], {}
        for f, v in _fields(raw, *plane):
            if f == 2:
                name = _text(raw, v)
                if not trace._is_device(name):
                    break
            elif f == 4:
                events.append(v)
            elif f == 5:
                sm = _map_value(raw, v)
                if sm is not None:
                    fs = dict(_fields(raw, *sm))
                    if 1 in fs and 2 in fs:
                        stat_names[fs[1]] = _text(raw, fs[2])
        if name is None or not trace._is_device(name):
            continue
        scope_id = [k for k, n in stat_names.items() if n == SCOPE_STAT]
        if not scope_id:
            continue
        for entry in events:
            md = _map_value(raw, entry)
            if md is None:
                continue
            op, scope = None, None
            for f, v in _fields(raw, *md):
                if f == 2:
                    op = _text(raw, v)
                elif f == 5:
                    stat = dict(_fields(raw, *v))
                    if stat.get(1) != scope_id[0]:
                        continue
                    if 5 in stat:
                        scope = _text(raw, stat[5])
                    elif 7 in stat:
                        scope = stat_names.get(stat[7])
            if op is not None and scope is not None:
                out[op] = scope
    return out


# -- intervals ----------------------------------------------------------------

def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The intersection of two sets of sorted disjoint (k, 2) segments."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.asarray(out, np.float64).reshape(-1, 2)


def _span_union(tr: trace.Trace, prog: Program, names) -> np.ndarray:
    iv = [(s, e) for n, s, e, _ in prog.spans if n in names]
    if not iv:
        return np.zeros((0, 2))
    s, e = zip(*iv)
    return trace.merged(s, e, *tr.window)


def _length(seg: np.ndarray) -> float:
    return float(np.sum(seg[:, 1] - seg[:, 0]))


def idle_in_spans(tr: trace.Trace, prog: Program, device: str, names,
                  outside=()) -> float:
    """Idle nanoseconds of one device inside the window that intersect
    the program spans named in ``names``, leaving out whatever intersects
    those named in ``outside``. A gap is charged for the time it overlaps
    the spans, so one gap may be shared among several spans and the rest
    of the gap stays uncharged."""
    idle = trace.gaps(tr, device)
    if outside:
        out = _span_union(tr, prog, outside)
        lo, hi = tr.window
        edges = np.concatenate([[lo], out.ravel(), [hi]]).reshape(-1, 2)
        idle = intersect(idle, edges[edges[:, 1] > edges[:, 0]])
    return _length(intersect(idle, _span_union(tr, prog, names)))


def span_union_ns(tr: trace.Trace, prog: Program, names) -> float:
    """Nanoseconds of the window covered by the program spans named in
    ``names``."""
    return _length(_span_union(tr, prog, names))


def scope_busy_ns(tr: trace.Trace, prog: Program, device: str,
                  scope: str) -> float:
    """Nanoseconds of the window in which an operation traced under the
    named scope ``scope`` ran on one device: the union of their intervals.
    XLA keeps no scope on a loop op, only on its body's ops, so a loop
    counts through its body (its own time between body ops is left out)."""
    names, s, e = tr.devices[device]
    keep = [k for k, n in enumerate(names)
            if scope in prog.scopes.get(n, "").split("/")]
    if not keep:
        return 0.0
    return _length(trace.merged(s[keep], e[keep], *tr.window))


# -- the split ----------------------------------------------------------------

def _compiles(stats: dict) -> int:
    return sum(stats["compiles"].values()) + \
        sum(stats["cache_loads"].values())


def split(tr: trace.Trace, prog: Program, stats=()) -> dict:
    """The device's idle share of the window split by what the server was
    doing, averaged over the trace's devices; ``stats`` is the router's
    ``stats()`` before and after the window, or empty. What the trace or
    the stats hold nothing for is left out:

    * ``idle_share``: 1 - busy / window, in % (as ``idle_share.backlog``);
    * ``lifecycle_idle_share``: idle intersecting ``serve.open`` /
      ``serve.close``, over the window, in %;
    * ``feed_idle_share``: idle intersecting ``serve.submit`` /
      ``serve.dispatch`` / ``serve.resolve`` and not the lifecycle spans
      (a flush inside a lifecycle call counts to the lifecycle), in %;
    * ``rotate_ms``: the window's ``serve.close`` + ``serve.open`` time
      over the ``serve.open`` spans that start in it, in ms;
    * ``readout_device_ms``: device time under the ``readout`` scope per
      step run in the window (``steps_run`` of the stats), in ms;
    * ``compiles_in_window``: the change in ``compiles`` + ``cache_loads``.
    """
    devs = sorted(tr.devices)
    lo, hi = tr.window
    win = hi - lo
    mean = lambda f: float(np.mean([f(d) for d in devs]))
    out: dict = {}
    if devs:
        out["idle_share"] = 100.0 * (1.0 - mean(
            lambda d: trace.busy_ns(tr, d)) / win)
    names = {n for n, *_ in prog.spans}
    if devs and names & set(LIFECYCLE + FEED):
        out["lifecycle_idle_share"] = 100.0 * mean(
            lambda d: idle_in_spans(tr, prog, d, LIFECYCLE)) / win
        out["feed_idle_share"] = 100.0 * mean(
            lambda d: idle_in_spans(tr, prog, d, FEED,
                                    outside=LIFECYCLE)) / win
    opens = sum(1 for n, s, _, _ in prog.spans
                if n == "serve.open" and lo <= s < hi)
    if opens:
        out["rotate_ms"] = span_union_ns(tr, prog, LIFECYCLE) * 1e-6 / opens
    if len(stats) == 2 and all("compiles" in st for st in stats):
        before, after = stats
        steps = after["steps_run"] - before["steps_run"]
        readout = mean(lambda d: scope_busy_ns(tr, prog, d, "readout")) \
            if devs else 0.0
        if steps and readout > 0:
            out["readout_device_ms"] = readout * 1e-6 / steps
        out["compiles_in_window"] = _compiles(after) - _compiles(before)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a .xplane.pb, gzipped or not")
    ap.add_argument("stats", nargs="?",
                    help="the stats() before and after its window "
                         "(record_waves.py's JSON)")
    args = ap.parse_args(argv)
    raw = read(args.trace)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.xplane.pb")
        with open(path, "wb") as f:
            f.write(raw)
        tr = trace.load(path)
    stats = ()
    if args.stats:
        with open(args.stats) as f:
            st = json.load(f)
        stats = (st["before"], st["after"])
    print(json.dumps(split(tr, load(raw), stats), sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
