"""``bench/run.py`` refuses to run without a TPU: exit 2, no result."""

from bench import run


def test_refuses_without_a_tpu(capsys, monkeypatch):
    ran = []
    monkeypatch.setattr(run, "run_cell", lambda *a, **k: ran.append(1))
    rc = run.main(["--workload", "esc10-fixed.backlog", "--seed",
                   str(2 ** 31 + 5), "--seconds", "20", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 2 and not ran
    assert out == ""
    assert "not a TPU" in err
