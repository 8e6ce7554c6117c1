"""Tests of the benchmark's own arithmetic (perfbench/). They import the
harness as the benchmark's command does: with perfbench/ on the path."""

import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PERFBENCH = os.path.join(ROOT, "perfbench")
if PERFBENCH not in sys.path:
    sys.path.insert(0, PERFBENCH)


@pytest.fixture
def benchmark_copy(tmp_path) -> str:
    """A root holding a copy of ``BENCHMARK.json`` and ``perfbench/``, for
    the tests that add to the benchmark as a later PR would."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


@pytest.fixture
def one_chip_env(monkeypatch):
    """A one-chip cell on the 8-device test platform hides seven devices
    through TFD_DEVICE_MASK; give the variable back when the test ends."""
    monkeypatch.setenv("TFD_DEVICE_MASK", "0")
    yield
