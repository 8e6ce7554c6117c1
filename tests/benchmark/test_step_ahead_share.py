"""The reader of ``serve.step_ahead_share`` (PR 29): the program's own
count of decode steps launched from the previous step's device tokens,
over the steps it retired; nothing where the program has no such counter
(the parent of the PR that added it); and, its entry being in
``BENCHMARK.json`` since PR 32, both serve configurations' cells name it
and the steady cell's rehearsal reports it through the real engine."""

import types

import pytest

from harness import serve_runner
from harness.loader import Cell, load_benchmark, load_reader

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


def test_steady_cell_reports_the_share_once_it_has_an_entry(one_chip_env):
    """``BENCHMARK.json`` as it stands has the entry (PR 32; the first
    placed after the GLM cell's): the cells of both serve configurations
    it lists name it, no other cell does, and the steady cell's rehearsal
    reports it from the real engine's counters."""
    bench = load_benchmark()
    assert [m for m in bench["per_layer"] if m["name"] == NAME] == [ENTRY]
    for w in bench["workloads"]:
        names = {m["name"] for m in Cell(w["name"]).per_layer()}
        assert (NAME in names) == (w["name"] in ENTRY["workloads"]), w["name"]
    res = serve_runner.run(Cell("gpt2l-serve-steady"), seed=2 ** 31 + 29,
                           seconds=2.0, trace=True, rehearse=True,
                           require_tpu=False)
    assert res["correct"] is True and res["failed"] == 0
    assert 50.0 < res["metrics"][NAME]["value"] <= 100.0
