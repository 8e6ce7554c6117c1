"""Tests of the benchmark's own arithmetic (perfbench/). They import the
harness as the benchmark's command does: with perfbench/ on the path."""

import json
import os
import shutil
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PERFBENCH = os.path.join(ROOT, "perfbench")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
SECOND_CELL = "tiny-ropegqa-chat"
SECOND_METRIC = "serve.decode_steps"
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


def _add_second_model(root: str, with_metric: bool = False):
    """Add to the benchmark copy at ``root`` what a ``model_config`` PR
    brings: a model file (``fixtures/ropegqa.py``), a configuration naming
    it, and at the END of their lists one ``configs`` entry, one
    ``workloads`` entry on the chat mix that is there and the cell's name
    in the two serve metrics' ``workloads``; ``with_metric``: also a
    reader and its ``per_layer`` entry. Nothing that was there is edited.
    Returns the names it added and ``config_file``, the added
    configuration's path."""
    bench_dir = os.path.join(root, "perfbench")
    shutil.copy(os.path.join(FIXTURES, "ropegqa.py"),
                os.path.join(bench_dir, "models"))
    config = os.path.join(bench_dir, "configs", "tiny-ropegqa-serve.json")
    shutil.copy(os.path.join(FIXTURES, "tiny-ropegqa-serve.json"), config)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-ropegqa-serve", "source": "test",
        "file": "perfbench/configs/tiny-ropegqa-serve.json",
        "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": SECOND_CELL, "config": "tiny-ropegqa-serve",
        "traffic": "chat-lognormal-0.8knee", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("serve_ttft_p50_ms", "serve_tpot_p95_ms"):
            m["workloads"].append(SECOND_CELL)
    if with_metric:
        shutil.copy(os.path.join(FIXTURES, SECOND_METRIC + ".py"),
                    os.path.join(bench_dir, "metrics"))
        bench["per_layer"].append({
            "name": SECOND_METRIC, "unit": "steps", "better": "higher",
            "source": "program_counter", "layer": "serve engine",
            "moves": "serve_tpot_p95_ms", "workloads": [SECOND_CELL]})
    with open(path, "w") as f:
        json.dump(bench, f)
    return types.SimpleNamespace(
        config="tiny-ropegqa-serve", config_file=config, cell=SECOND_CELL,
        metric=SECOND_METRIC if with_metric else None)


@pytest.fixture
def add_second_model():
    """``add(root, with_metric=False)``: a second architecture's cell,
    appended to a benchmark copy as a later PR appends one."""
    return _add_second_model


@pytest.fixture
def one_chip_env(monkeypatch):
    """A one-chip cell on the 8-device test platform hides seven devices
    through TFD_DEVICE_MASK; give the variable back when the test ends."""
    monkeypatch.setenv("TFD_DEVICE_MASK", "0")
    yield
