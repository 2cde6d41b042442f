"""Dry-run harness checks on a tiny forced-device-count mesh (subprocess,
so the main test process keeps its single CPU device)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _run_cell(arch, shape, mesh="2,2"):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"       # forced host devices, never a chip
    env["REPRO_DEVICE_COUNT"] = "4"
    env["REPRO_DRYRUN_MESH"] = mesh
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--out", "/tmp/test_dryrun_cell.json"],
        env=env, capture_output=True, text=True, timeout=1200)
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert lines, proc.stdout + proc.stderr[-2000:]
    return json.loads(lines[-1])


@pytest.mark.slow
def test_dryrun_train_cell_small_mesh():
    r = _run_cell("yi-6b", "train_4k")
    assert r["ok"], r["error"]
    assert r["flops"] > 1e15              # extrapolated, not body-once
    assert r["collective_bytes"]          # TP/FSDP collectives present


@pytest.mark.slow
def test_dryrun_decode_cell_small_mesh():
    r = _run_cell("mixtral-8x7b", "decode_32k")
    assert r["ok"], r["error"]
    assert r["per_device_memory_bytes"] > 0


def test_cell_applicability_rules():
    from repro.configs import SHAPES, cell_is_applicable, get_config
    # pure full-attention archs skip long_500k
    for arch in ("mistral-large-123b", "yi-6b", "minitron-8b",
                 "deepseek-v2-236b", "paligemma-3b", "whisper-medium"):
        ok, why = cell_is_applicable(get_config(arch), "long_500k")
        assert not ok and "sub-quadratic" in why
    # SSM/hybrid/SWA/local-global run it
    for arch in ("mamba2-1.3b", "zamba2-7b", "gemma3-12b", "mixtral-8x7b"):
        ok, _ = cell_is_applicable(get_config(arch), "long_500k")
        assert ok
    # everything else is live everywhere
    from repro.configs import ASSIGNED_ARCHS
    live = sum(cell_is_applicable(get_config(a), s)[0]
               for a in ASSIGNED_ARCHS for s in SHAPES)
    assert live == 34


def test_depth_variants_linear():
    """Extrapolation units: cfg@1, cfg@2 differ by exactly one unit."""
    from repro.configs import get_config
    from repro.launch import dryrun
    for arch, expect_units in [("yi-6b", 32), ("gemma3-12b", 8),
                               ("zamba2-7b", 13), ("whisper-medium", 24),
                               ("deepseek-v2-236b", 59),
                               ("mamba2-1.3b", 48)]:
        c1, c2, units = dryrun.depth_variants(get_config(arch))
        assert units == expect_units, arch
        assert c1.scan_unroll and c2.scan_unroll
