"""``BENCHMARK.json`` and the files it names, found by name.

* a configuration: its entry's ``file`` (``bench/configs/<name>.json``);
* a traffic mix: ``bench/traffic/<traffic>.json``, whose ``loop`` names
  the client that runs it, ``bench/loops/<loop>.py``;
* a metric, end-to-end or per-layer: its reader
  ``bench/metrics/<metric>.py``.

A metric belongs to a cell when its ``workloads`` list names the cell, or
when it has no such list.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def traffic_path(name: str) -> str:
    return os.path.join(BENCH, "traffic", f"{name}.json")


def reader_path(metric: str) -> str:
    return os.path.join(BENCH, "metrics", f"{metric}.py")


def loop_path(loop: str) -> str:
    return os.path.join(BENCH, "loops", f"{loop}.py")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def config(name: str, root: str = ROOT) -> dict:
    """A configuration's file, by its name in ``BENCHMARK.json``."""
    configs = {c["name"]: c for c in load(root)["configs"]}
    return _json(os.path.join(root, configs[name]["file"]))


def cell(name: str, root: str = ROOT) -> dict:
    """Everything one cell needs, resolved from the files by name."""
    spec = load(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    return {
        "name": name,
        "chips": int(w["chips"]),
        "config": config(w["config"], root),
        "traffic": _json(traffic_path(w["traffic"])),
        "end_to_end": [m for m in spec["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in spec["per_layer"] if _applies(m, name)],
    }


def _module(kind: str, name: str, path: str):
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(run)`` function of a metric."""
    return _module("metric", metric, reader_path(metric)).read


def loop(name: str):
    """The loop module a mix's ``loop`` names: its ``capacity(mix)`` and
    its client class ``Loop(router, seed, mix)``."""
    return _module("loop", name, loop_path(name))


def peaks(kind: str) -> dict:
    table = _json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[kind]
