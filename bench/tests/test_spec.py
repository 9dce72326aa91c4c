"""``BENCHMARK.json`` keeps to its contract, and every cell resolves by
name to its configuration, traffic and metric files."""

import os
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
B = spec.load()


@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_cell_resolves_by_name(cell):
    c = spec.cell(cell)
    assert c["config"]["name"] == next(
        w["config"] for w in B["workloads"] if w["name"] == cell)
    client = spec.loop(c["traffic"]["loop"])
    assert client.capacity(c["traffic"]) > 0 and callable(client.Loop)
    assert any(m["name"] == "setup_s" for m in c["end_to_end"])
    assert len(c["end_to_end"]) >= 2 and c["per_layer"]
    for m in c["end_to_end"] + c["per_layer"]:
        assert callable(spec.reader(m["name"]))


@pytest.mark.parametrize("mix", sorted(
    f[:-5] for f in os.listdir(os.path.join(spec.BENCH, "traffic"))))
def test_every_mix_resolves_to_its_loop(mix):
    m = spec._json(spec.traffic_path(mix))
    client = spec.loop(m["loop"])
    assert client.capacity(m) > 0
    assert client.Schedule(2 ** 40 + 1, m) is not None


def test_every_file_named_exists_under_paths():
    for c in B["configs"]:
        assert c["file"].startswith("bench/")
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
    for w in B["workloads"]:
        assert os.path.isfile(spec.traffic_path(w["traffic"]))
        loop = spec._json(spec.traffic_path(w["traffic"]))["loop"]
        assert os.path.isfile(spec.loop_path(loop))
    for m in B["end_to_end"] + B["per_layer"]:
        assert os.path.isfile(spec.reader_path(m["name"]))


def test_keys_names_and_units():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in B[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    metrics = B["end_to_end"] + B["per_layer"]
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
               for m in metrics)
    e2e = {m["name"] for m in B["end_to_end"]}
    assert all(m["moves"] in e2e for m in B["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in B["end_to_end"])
    four = [w for w in B["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(B["workloads"]) // 2)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")
    assert spec.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
