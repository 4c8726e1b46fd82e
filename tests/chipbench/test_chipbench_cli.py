"""The command refuses to run without a TPU, and in a directory that holds
only the benchmark's own files."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

from chipbench import run
from conftest import ROOT

ARGS = ["-m", "chipbench.run", "--workload", "granite2b.chat", "--seed",
        str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"]


def _command(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    shutil.copytree(ROOT / "tests" / "chipbench", tmp_path / "tests" / "chipbench")
    proc = _command(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_device_check_refuses_cpu():
    with pytest.raises(RuntimeError, match="no TPU"):
        run.chip_devices(1)
    assert jax.devices()[0].platform == "cpu"
