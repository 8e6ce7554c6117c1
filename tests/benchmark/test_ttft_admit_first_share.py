"""The reader of ``serve.admit_first_share`` (PR 45): the program's own
count of admissions whose prefill found no decode step queued behind the
one running (``serve_summary.admits_first``), over its admissions;
nothing where the program has no such counter (the parent of the PR that
added it); the six cells that report ``serve_ttft_p50_ms`` list it and no
other; and the metric is one file and one appended entry over a
benchmark that lacks them. (The real engines' count is held by
``tests/test_serve_ahead.py``. This module rehearses no cell and its
name sorts last in the directory: a rehearsal writes under the cell's
name in ``.cache/perfbench/``, and a file that moves the order in which
the workers of a parallel run take this directory's modules puts two
rehearsals of one cell side by side.)"""

import json
import os
import types

import pytest
from test_glm_cell import _hashes, entries_added

from harness.loader import Cell, load_benchmark, load_reader

NAME = "serve.admit_first_share"
CELL = "gpt2l-serve-steady"
CELLS = [CELL, "glm52-serve-longctx", "axk1-serve-reasoning",
         "sala-serve-longdoc", "granite4h-serve-chat",
         "nemotron3s-serve-agentic"]
ENTRY = {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "serve engine",
         "moves": "serve_ttft_p50_ms", "workloads": CELLS}


def _ctx(**summary):
    return types.SimpleNamespace(
        records=[{"event": "serve_request"},
                 {"event": "serve_summary", **summary}])


@pytest.mark.parametrize("summary, want", [
    ({"admissions": 140, "admits_first": 119}, 85.0),
    # every arrival found the step ahead queued: the parent's order
    ({"admissions": 8, "admits_first": 0}, 0.0),
    ({"admissions": 3, "admits_first": 3}, 100.0),      # an idle engine's
    ({"admissions": 140}, None),                        # the parent
    ({"admissions": 0, "admits_first": 0}, None),       # nothing admitted
], ids=["most", "none", "all", "parent", "no_admission"])
def test_reader_divides_the_programs_own_counts(summary, want):
    assert load_reader(NAME)(_ctx(**summary)) == want


def test_reader_without_a_summary_reads_nothing():
    assert load_reader(NAME)(types.SimpleNamespace(records=[])) is None


def test_the_cells_with_a_median_first_token_list_it_and_it_stands_last():
    """The six serve cells below capacity report the end-to-end metric
    it moves; the saturated cell (tokens a second) and the train cells do
    not, and are not on its list."""
    bench = load_benchmark()
    assert bench["per_layer"][-1] == ENTRY
    for w in bench["workloads"]:
        cell = Cell(w["name"])
        names = {m["name"] for m in cell.per_layer()}
        assert (NAME in names) == (w["name"] in CELLS), w["name"]
        assert (w["name"] in CELLS) == (ENTRY["moves"] in {
            m["name"] for m in cell.end_to_end()}), w["name"]


def test_the_metric_is_one_file_and_one_entry_and_edits_no_file(
        benchmark_copy):
    """Taken OUT of a copy of the benchmark (its reader, its entry), the
    steady cell loads and names every other reader; added again as a
    ``perf_opt`` PR adds it, ``BENCHMARK.json`` differs by ONE appended
    ``per_layer`` entry and every file the copy had has the hash it had."""
    root = benchmark_copy
    bench_dir = os.path.join(root, "perfbench")
    reader = os.path.join(bench_dir, "metrics", NAME + ".py")
    with open(reader) as f:
        source = f.read()
    os.remove(reader)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        full = json.load(f)
    without = json.loads(json.dumps(full))
    without["per_layer"] = [m for m in full["per_layer"]
                            if m["name"] != NAME]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(without, f)
    before = _hashes(bench_dir)
    names = [m["name"] for m in Cell(CELL, root=root).per_layer()]
    assert NAME not in names and "serve.step_ahead_share" in names
    for name in names:
        assert load_reader(name, root=root) is not None
    with open(reader, "w") as f:
        f.write(source)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(full, f)
    assert entries_added(without, full, []) == {
        "configs": [], "workloads": [], "end_to_end": [],
        "per_layer": [NAME]}
    assert Cell(CELL, root=root).per_layer()[-1] == ENTRY
    after = _hashes(bench_dir)
    for path, digest in before.items():
        assert after[path] == digest, f"{path} was edited"
    assert len(after) == len(before) + 1
