"""The command refuses to run without a TPU and prints no result."""
import os
import sys

import pytest


def test_refuses_a_non_tpu_platform(monkeypatch, capsys):
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    from chipbench import run
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "ckpt.save", "--seed", "3",
                  "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_refuses_too_few_chips():
    from chipbench import run
    with pytest.raises(SystemExit):
        run.require_chips("tpu", 1, 4)
    run.require_chips("tpu", 4, 4)


def test_unknown_cell_is_refused(monkeypatch):
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    from chipbench import harness, run
    with pytest.raises(harness.BenchError):
        run.main(["--workload", "no.such.cell", "--seed", "1",
                  "--seconds", "1"])
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
