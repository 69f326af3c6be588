"""The command refuses to run off the chip, and without the program."""

import os
import shutil
import subprocess
import sys

import pytest

from bench.peaks import PEAKS, peaks
from bench.tests.conftest import ROOT

ARGS = ["--workload", "tuner_narrow.shift_read_heavy", "--seed",
        str(2**31 + 5), "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_means_no_result():
    p = run(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_mean_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_peaks_by_device_kind():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
    assert all("hbm_bytes_per_s" in p for p in PEAKS.values())
