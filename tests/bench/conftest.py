"""The benchmark's CPU tests: ``bench/`` on the import path, and the
fixture directory that holds a CPU-sized cell of its own."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixture"
if str(REPO / "bench") not in sys.path:
    sys.path.insert(0, str(REPO / "bench"))


@pytest.fixture
def fixture_root():
    return FIXTURE


@pytest.fixture
def peak():
    from gfbench import spec
    return spec.peaks()["TPU v5 lite"]
