"""chip_smoke.py at a reduced size on the CPU: the same phases and checks
the chip run makes, so the script cannot rot between chip runs; and its
refusals, which must print no result line."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_pass_at_reduced_size(smoke, capsys):
    from repro.configs.dit_models import DIT_IMAGE
    smoke.one_chip(DIT_IMAGE.reduced().with_(use_pallas=True), [],
                   small=64, large=128, steps=4)
    out = capsys.readouterr().out
    assert "FAILED" not in out
    assert "phase serve (4 requests)" in out


def test_failed_check_raises(smoke):
    with pytest.raises(smoke.SmokeFailure):
        smoke.check(False, "a failing check")


def test_refuses_pallas_override(smoke, monkeypatch, capsys):
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    assert smoke.main([]) == 2
    assert "ok" not in capsys.readouterr().out


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert not any(line.startswith("{") and json.loads(line).get("ok")
                   for line in proc.stdout.splitlines())
