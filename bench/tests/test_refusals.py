"""The harness refuses, with a non-zero exit and no result line, where it
cannot measure: off a TPU, on an unknown chip, or short of chips."""

import os
import subprocess
import sys

import pytest

import run

ROOT = run.ROOT


def test_cpu_backend_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper_n100_eval2", "--seed", "0",
         "--seconds", "10", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("devs,chips,why", [
    ([_Dev("tpu", "TPU v9 imaginary")], 1, "no peaks"),
    ([_Dev("tpu", "TPU v5 lite")], 4, "needs 4 chips"),
    ([_Dev("cpu", "cpu")], 1, "no TPU"),
])
def test_refusals(monkeypatch, capsys, devs, chips, why):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: devs)
    with pytest.raises(SystemExit) as e:
        run.check_devices(chips, {"TPU v5 lite": {}})
    assert e.value.code != 0
    assert why in capsys.readouterr().err


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit) as e:
        run.cell_files("no_such_cell")
    assert e.value.code != 0
