"""The peaks table and the refusal to run without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

from bench.harness import device
from bench.harness.spec import CHECKOUT

ARGS = ["--workload", "qwen3-8b-reason", "--seed", "2147483700",
        "--seconds", "1", "--trace", "0"]


def test_peaks_known_and_unknown_kind():
    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        device.peaks("cpu")


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(root / "bench" / "run.py")]
                          + ARGS, cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run(CHECKOUT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
