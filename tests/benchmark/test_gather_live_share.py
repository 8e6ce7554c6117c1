"""The reader of ``serve.gather_live_share`` (PR 34): of the latent cache
rows the decode steps' gathers moved, the share a live slot's attend
needed, from the program's own counts; nothing where the program has no
such counter (the parent of the PR that added it); the GLM cell alone
lists it and its rehearsal reports it through the real engine; and the
metric is one file and one appended entry over a benchmark that lacks
them."""

import json
import os
import types

import pytest
from test_glm_cell import _hashes, entries_added

from harness import common, serve_runner
from harness.loader import Cell, load_benchmark, load_reader

NAME = "serve.gather_live_share"
CELL = "glm52-serve-longctx"
ENTRY = {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "kernels",
         "moves": "serve_tpot_p95_ms", "workloads": [CELL]}
# (mlp, indexer) a layer, as the GLM model file's sizes() gives them
FIVE = (("dense", "full"),) + (("sparse", "shared"),) * 3 + (
    ("sparse", "full"),)


def _ctx(layers=FIVE, **summary):
    return types.SimpleNamespace(
        sizes={"layers": layers},
        records=[{"event": "serve_request"},
                 {"event": "serve_summary", **summary}])


@pytest.mark.parametrize("layers, summary, want", [
    # the benchmark's rate: 7.2 live slots of 32, every one past index_topk
    (FIVE, {"select_keys_kept": 14_741, "select_rows_gathered": 5 * 14_741},
     100.0),
    # the same step had the gather moved all 32 slots' 2,048 rows
    (FIVE, {"select_keys_kept": 14_741, "select_rows_gathered": 5 * 65_536},
     100.0 * 14_741 / 65_536),
    # live slots shallower than index_topk keep fewer than they gather
    (FIVE, {"select_keys_kept": 1_200, "select_rows_gathered": 5 * 2_048},
     100.0 * 1_200 / 2_048),
    # a layer without a selection gathers nothing and counts for nothing
    (FIVE[:2] + (("sparse", "none"),), {"select_keys_kept": 300,
                                       "select_rows_gathered": 600}, 100.0),
    (FIVE, {"select_keys_kept": 14_741}, None),             # the parent
    (FIVE, {"select_keys_kept": 0, "select_rows_gathered": 0}, None),
], ids=["live_only", "every_slot", "shallow", "a_dense_layer", "parent",
        "nothing_decoded"])
def test_reader_divides_the_programs_own_counts(layers, summary, want):
    got = load_reader(NAME)(_ctx(layers, **summary))
    assert got == (want if want is None else pytest.approx(want))


def test_reader_without_a_summary_reads_nothing():
    assert load_reader(NAME)(types.SimpleNamespace(
        records=[], sizes={"layers": FIVE})) is None


def test_the_glm_cell_alone_reports_it_from_the_real_engine(
        one_chip_env, tmp_path, monkeypatch):
    """The entry stands LAST in ``per_layer``; of the cells only the GLM
    cell names it; and that cell's rehearsal (a traced run of the real
    engine at its tiny widths, in a work directory of its own) reports it:
    every gathered row is a live slot's, so the share is the keys the live
    slots kept of the ``index_topk`` each gathered, the run's own
    ``serve_summary`` counts again."""
    bench = load_benchmark()
    assert bench["per_layer"][-1] == ENTRY
    for w in bench["workloads"]:
        names = {m["name"] for m in Cell(w["name"]).per_layer()}
        assert (NAME in names) == (w["name"] == CELL), w["name"]
    monkeypatch.setattr(common, "ROOT", str(tmp_path))
    cell = Cell(CELL)
    res = serve_runner.run(cell, seed=2 ** 31 + 34, seconds=2.0, trace=True,
                           rehearse=True, require_tpu=False)
    assert res["correct"] is True and res["failed"] == 0
    s = [r for r in common.read_jsonl(os.path.join(
        str(tmp_path), ".cache", "perfbench", CELL, "serve.jsonl"))
        if r.get("event") == "serve_summary"][-1]
    layers = cell.sizes(rehearse=True)["layers"]
    topk = cell.config["rehearsal"]["sizes"]["index_topk"]
    assert s["select_rows_gathered"] == (
        s["decode_live_rows"] * topk * len(layers))
    assert 0 < s["select_keys_kept"] * len(layers) \
        <= s["select_rows_gathered"]
    assert res["metrics"][NAME]["value"] == pytest.approx(
        100.0 * s["select_keys_kept"] * len(layers)
        / s["select_rows_gathered"])
    assert 50.0 < res["metrics"][NAME]["value"] <= 100.0


def test_the_metric_is_one_file_and_one_entry_and_edits_no_file(
        benchmark_copy):
    """Taken OUT of a copy of the benchmark (its reader, its entry), the
    GLM cell loads and names every other reader; added again as a
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
    assert NAME not in names and "serve.index_keep_share" in names
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
