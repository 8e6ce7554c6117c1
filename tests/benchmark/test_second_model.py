"""A second architecture enters a copy of the benchmark by added files and
list entries alone: one model file (another tree than GPT-2's, another
forward pass, its sizes under other key names), one configuration naming
it, one ``configs`` and one ``workloads`` entry on the existing chat mix.
The cell rehearses ``correct``, fails when a token is altered underneath,
and no file the benchmark had is edited. With ``--serve.paged true`` the
probe finds the paged engine."""

import hashlib
import json
import os

import pytest

from harness import common, serve_runner
from harness.loader import ROOT, Cell

CELL = "tiny-ropegqa-chat"


def _hashes(top):
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture
def second_model_root(benchmark_copy, add_second_model):
    """A copy of the benchmark plus what a ``model_config`` PR would add
    (``conftest.py::add_second_model``). Yields (root, path of the added
    configuration); afterwards every file that was under the copied
    ``perfbench/`` must have the hash it had."""
    root = benchmark_copy
    bench_dir = os.path.join(root, "perfbench")
    before = _hashes(bench_dir)
    config = add_second_model(root).config_file
    yield root, config
    after = _hashes(bench_dir)
    for path, digest in before.items():
        assert after[path] == digest, f"{path} was edited"
    assert len(after) == len(before) + 2


def rehearse(root, fault=None, trace=False):
    cell = Cell(CELL, root=root)
    assert cell.model.__file__.startswith(root)
    return serve_runner.run(cell, seed=2 ** 31 + 23, seconds=2.0,
                            trace=trace, rehearse=True, fault=fault,
                            require_tpu=False)


def test_its_sizes_stand_under_its_own_keys(second_model_root):
    sizes = Cell(CELL, root=second_model_root[0]).sizes(rehearse=True)
    assert sizes["num_key_value_heads"] == 2
    assert sizes["n_positions"] == sizes["max_position_embeddings"] == 64
    assert "n_embd" not in sizes


def test_it_rehearses_correct(one_chip_env, second_model_root):
    res = rehearse(second_model_root[0])
    assert res["correct"] is True and res["failed"] == 0
    assert res["check"]["tokens"] > 30
    assert set(res["metrics"]) == {"serve_ttft_p50_ms",
                                   "serve_tpot_p95_ms", "setup_s"}


def test_an_altered_token_is_not_correct(one_chip_env, second_model_root):
    root, config = second_model_root
    res = rehearse(root, fault="altered_token")
    assert res["correct"] is False
    with open(config) as f:
        limits = json.load(f)["rehearsal"]["correct_limits"]
    assert res["check"]["max"] > limits["served_token_gap_max"]


def test_the_probe_follows_the_paged_engine(one_chip_env,
                                            second_model_root):
    root, config = second_model_root
    with open(config) as f:
        cfg = json.load(f)
    cfg["rehearsal"]["program_argv"] += ["--serve.paged", "true"]
    with open(config, "w") as f:
        json.dump(cfg, f)
    # run() reads the slots and the step counts off probe.engine: with no
    # engine caught by the seam it raises before it returns
    res = rehearse(root, trace=True)
    assert res["correct"] is True and res["failed"] == 0
    records = common.read_jsonl(os.path.join(
        ROOT, ".cache", "perfbench", CELL, "serve.jsonl"))
    summary = [r for r in records if r.get("event") == "serve_summary"]
    assert summary and summary[-1]["pages_peak"] > 0     # the paged engine's
    spans = {name for name, _, _ in common.read_capture(os.path.join(
        ROOT, ".cache", "perfbench", CELL, "trace")).host}
    assert {"bench.engine_step", "bench.engine_prefill"} <= spans
