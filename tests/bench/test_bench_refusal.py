"""Runs refuse a backend other than TPU, an unknown device_kind, too few
chips, and a checkout without the program."""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

from conftest import REPO
from gfbench import bench, spec


def _dev(platform="tpu", kind="TPU v5 lite"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_bench_refuses_cpu():
    with pytest.raises(bench.Refused, match="no TPU"):
        bench.device_check([_dev("cpu", "cpu")], spec.peaks(), 1)


def test_bench_refuses_unknown_device_kind():
    with pytest.raises(bench.Refused, match="device_kind"):
        bench.device_check([_dev(kind="TPU v9 imaginary")], spec.peaks(), 1)


def test_bench_refuses_too_few_chips():
    with pytest.raises(bench.Refused, match="4 chips"):
        bench.device_check([_dev()], spec.peaks(), 4)


def test_bench_accepts_known_tpu():
    peak = bench.device_check([_dev()], spec.peaks(), 1)
    assert peak["flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "image-batch-1c",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_bench_run_on_cpu_exits_without_result(tmp_path):
    proc = _run(REPO, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode != 0 and _no_result(proc)
    assert "no TPU" in proc.stderr


def test_bench_run_without_program_exits_without_result(tmp_path):
    with open(REPO / "BENCHMARK.json") as f:
        paths = json.load(f)["paths"]
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in paths:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {})
    assert proc.returncode != 0 and _no_result(proc)
