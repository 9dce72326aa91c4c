"""``chip_smoke.py`` refuses to run, and prints no result, without a TPU.

The phases themselves need the chip (``python chip_smoke.py`` on a TPU
host); here only the refusal is checked: on the CPU the script exits
non-zero before any phase and its output holds no ``ok`` line.
"""

from __future__ import annotations

import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_refuses_without_a_tpu(capsys, monkeypatch, argv):
    smoke = _load_chip_smoke()
    ran = []
    monkeypatch.setattr(smoke, "run_one_chip", lambda: ran.append(1))
    monkeypatch.setattr(smoke, "run_four_chips", lambda n: ran.append(n))
    assert smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert not ran
    assert "platform=cpu" in out.splitlines()[0]
    assert '"ok"' not in out
