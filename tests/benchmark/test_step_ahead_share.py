"""The reader of ``serve.step_ahead_share`` (PR 29): the program's own
count of decode steps launched from the previous step's device tokens,
over the steps it retired; nothing where the program has no such counter
(the parent of the PR that added it); and the steady cell's rehearsal
reports it through the real engine, given an entry."""

import json
import types

import pytest

from harness import serve_runner
from harness.loader import Cell, load_reader

NAME = "serve.step_ahead_share"


def _ctx(**summary):
    return types.SimpleNamespace(
        records=[{"event": "serve_request"},
                 {"event": "serve_summary", **summary}])


@pytest.mark.parametrize("summary, want", [
    ({"decode_steps": 200, "steps_ahead": 190}, 95.0),
    ({"decode_steps": 8, "steps_ahead": 0}, 0.0),       # synchronous run
    ({"decode_steps": 200}, None),                      # the parent
    ({"decode_steps": 0, "steps_ahead": 0}, None),      # nothing decoded
])
def test_reader_divides_the_programs_own_counts(summary, want):
    assert load_reader(NAME)(_ctx(**summary)) == want


def test_reader_without_a_summary_reads_nothing():
    assert load_reader(NAME)(types.SimpleNamespace(records=[])) is None


ENTRY = {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "serve engine",
         "moves": "serve_tpot_p95_ms",
         "workloads": ["gpt2l-serve-steady", "glm52-serve-longctx"]}


def test_steady_cell_reports_the_share_once_it_has_an_entry(
        benchmark_copy, one_chip_env):
    """``BENCHMARK.json`` has no entry for the reader yet (PERF.md section
    7: a test that is there pins the GLM cell's entries as the last).
    Added at the end of ``per_layer``, as a later PR adds one, the steady
    cell's rehearsal reports it from the real engine's counters."""
    path = f"{benchmark_copy}/BENCHMARK.json"
    with open(path) as f:
        bench = json.load(f)
    assert NAME not in {m["name"] for m in bench["per_layer"]}
    bench["per_layer"].append(ENTRY)
    with open(path, "w") as f:
        json.dump(bench, f)
    cell = Cell("gpt2l-serve-steady", root=benchmark_copy)
    assert cell.per_layer()[-1] == ENTRY
    res = serve_runner.run(cell, seed=2 ** 31 + 29, seconds=2.0,
                           trace=True, rehearse=True, require_tpu=False)
    assert res["correct"] is True and res["failed"] == 0
    assert 50.0 < res["metrics"][NAME]["value"] <= 100.0
