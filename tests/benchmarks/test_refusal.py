"""Off the chip the benchmark refuses: non-zero exit, nothing on stdout."""

import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmarks import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PEAKS = {"TPU v5 lite": {}}


def dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("devices,chips,refused", [
    ([dev("cpu", "cpu")], 1, True),
    ([dev("tpu", "TPU v9")], 1, True),  # a chip the peak table does not hold
    ([dev("tpu", "TPU v5 lite")], 4, True),  # fewer chips than the cell asks for
    ([dev("tpu", "TPU v5 lite")] * 4, 1, True),
    ([dev("tpu", "TPU v5 lite")], 1, False),
    ([dev("tpu", "TPU v5 lite")] * 4, 4, False),
])
def test_refusal(devices, chips, refused):
    assert (harness.refusal(devices, chips, PEAKS) is not None) == refused


def run_py(cwd, *extra):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"), "--workload",
         "qwen7-c1-s2k", "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        capture_output=True, text=True, timeout=300, cwd=cwd,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_run_py_refuses_the_cpu():
    proc = run_py(REPO)
    assert proc.returncode != 0 and proc.stdout.strip() == "", proc.stdout
    assert "peaks.json" in proc.stderr


def test_run_py_fails_where_only_the_benchmark_is(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under `paths`."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_py(str(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == "", proc.stdout
