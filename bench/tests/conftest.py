"""Helpers for the benchmark's CPU tests: cells built from the small
configuration and traffic files in ``data/``."""

import json
import os
import sys

import pytest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
if SRC not in sys.path:       # the program, as bench/run.py finds it
    sys.path.insert(0, SRC)


def load(name: str) -> dict:
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.fixture
def tiny_cell():
    """``tiny_cell(config, traffic)``: a one-chip cell of the CPU-sized
    files, with the benchmark's end-to-end metrics."""
    from bench import spec

    e2e = spec.load()["end_to_end"]

    def make(config: str, traffic: str) -> dict:
        loop = load(f"tiny-{traffic}.json")
        names = ({"setup_s", "samples_per_s"} if loop["loop"] == "closed"
                 else {"setup_s", "decision_p99_ms", "decision_p50_ms"})
        return {"name": f"tiny-{config}.{traffic}", "chips": 1,
                "config": load(f"tiny-{config}.json"), "traffic": loop,
                "end_to_end": [m for m in e2e if m["name"] in names],
                "per_layer": []}

    return make
