"""The command fails closed: no TPU, an unknown device kind, or a checkout
without the program gives a non-zero exit and no result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import harness

ARGS = ["--workload", "archive-1g.encode-rebuild", "--seed", str(2**31 + 9),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "benchmark/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_means_no_result():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_checkout_without_the_program_gives_no_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchFailure, match="not in"):
        harness.peaks_for("TPU v9 imaginary")
    with open(os.path.join(harness.BENCH, "peaks.json")) as f:
        table = json.load(f)
    assert table["source"] == "Google Cloud documentation, TPU v5e"
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops"] == 197e12


def test_unknown_workload_is_an_error():
    with pytest.raises(harness.BenchFailure, match="no workload"):
        harness.load_cell("no-such.cell")
