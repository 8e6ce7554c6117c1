"""The loader: names, and cells, mixes and per-layer metrics added as new
files plus entries, with no edit to a file that is there."""

import json
import os

import pytest

from harness import loader
from harness.loader import BenchmarkError, Cell, ROOT


@pytest.mark.parametrize("bad", [
    "has space", "a/b", "../x", "", "-leading", "x" * 65, "comma,", "µs"])
def test_bad_names_refused(bad):
    with pytest.raises(BenchmarkError):
        loader.check_name(bad)
    with pytest.raises(BenchmarkError):
        Cell(bad)


@pytest.mark.parametrize("good", [
    "gpt2m-train-dp1", "serve.decode_step_device_ms.sat", "_x", "0a"])
def test_good_names(good):
    assert loader.check_name(good) == good


def committed_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("name", committed_cells())
def test_every_committed_cell_resolves(name):
    cell = Cell(name)
    assert cell.kind in ("train", "serve")
    e2e = [m["name"] for m in cell.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell.per_layer()
    assert layer
    for m in layer:
        assert m["moves"] in e2e
        assert callable(loader.load_reader(m["name"]))


def test_unknown_cell_and_missing_reader():
    with pytest.raises(BenchmarkError, match="not in BENCHMARK.json"):
        Cell("no-such-cell")
    with pytest.raises(BenchmarkError, match="no reader"):
        loader.load_reader("no.such.metric")


@pytest.mark.parametrize("model_key,model_file,names", [
    # a configuration that names no model: there is no default
    (None, None, ["gpt2-large-serve.json", '"model"']),
    # it names a model whose file is not there
    ("absent", None, ["'absent'", "perfbench/models/absent.py"]),
    # a model that is only trained, named by a configuration that serves
    ("trained", "def sizes(src): ...\ndef make_params(key, sizes): ...\n"
     "def follow_training(*a): ...\nADAM_B1 = 0.9\n"
     "def train_flops_per_token(sizes, seq_len): ...\n",
     ["perfbench/models/trained.py", "served_token_gaps", "gaps_of",
      "reference_positions", "'serve'"]),
])
def test_a_configuration_without_its_model_is_refused(
        benchmark_copy, model_key, model_file, names):
    root = benchmark_copy
    path = os.path.join(root, "perfbench", "configs",
                        "gpt2-large-serve.json")
    with open(path) as f:
        cfg = json.load(f)
    del cfg["model"]
    if model_key:
        cfg["model"] = model_key
    with open(path, "w") as f:
        json.dump(cfg, f)
    if model_file:
        with open(os.path.join(root, "perfbench", "models",
                               model_key + ".py"), "w") as f:
            f.write(model_file)
    with pytest.raises(BenchmarkError) as e:
        Cell("gpt2l-serve-steady", root=root)
    for name in names:
        assert name in str(e.value)
    if model_file:          # what the file has is enough for a train cell
        assert loader.load_model(model_key, root, "train").ADAM_B1 == 0.9


def test_a_model_gives_the_sizes_every_runner_needs(benchmark_copy):
    root = benchmark_copy
    with open(os.path.join(root, "perfbench", "models", "gpt2.py"),
              "a") as f:
        f.write("\n\ndef sizes(src):\n    return {'vocab_size': 64}\n")
    with pytest.raises(BenchmarkError, match="n_positions"):
        Cell("gpt2l-serve-steady", root=root).sizes()
    assert Cell("gpt2l-serve-steady").sizes()["n_positions"] == 1024


def test_add_cell_mix_and_metric_as_new_files_only(benchmark_copy):
    root = benchmark_copy
    before = {}
    for d, _, files in os.walk(os.path.join(root, "perfbench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()
    # A later PR: one new traffic file, one new reader, three new entries.
    with open(os.path.join(root, "perfbench", "traffic",
                           "chat-bursty.json"), "w") as f:
        json.dump({"kind": "serve_open_loop", "rate_rps": 3.0,
                   "prompt_len": {"median": 64, "sigma": 0.5, "min": 16,
                                  "max": 256},
                   "output_len": {"median": 32, "sigma": 0.5, "min": 16,
                                  "max": 64}}, f)
    with open(os.path.join(root, "perfbench", "metrics",
                           "serve.new_counter.py"), "w") as f:
        f.write("def read(ctx):\n    return 42.0\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({
        "name": "gpt2l-serve-bursty", "config": "gpt2-large-serve",
        "traffic": "chat-bursty", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_ttft_p50_ms":
            m["workloads"].append("gpt2l-serve-bursty")
    bench["per_layer"].append({
        "name": "serve.new_counter", "unit": "steps", "better": "lower",
        "source": "program_counter", "layer": "serve driver",
        "moves": "serve_ttft_p50_ms", "workloads": ["gpt2l-serve-bursty"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = Cell("gpt2l-serve-bursty", root=root)
    assert cell.traffic["rate_rps"] == 3.0
    assert [m["name"] for m in cell.per_layer()] == ["serve.new_counter"]
    assert loader.load_reader("serve.new_counter", root=root)(None) == 42.0
    assert "serve_ttft_p50_ms" in [m["name"] for m in cell.end_to_end()]
    for p, content in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == content, f"{p} was edited"


def test_benchmark_json_meets_the_contracts_shape():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["name"] for w in b["workloads"]]:
        loader.check_name(n)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= 1
    layers = {m["layer"] for m in b["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert f"| {layer} " in perf, f"PERF.md lists no layer {layer!r}"
    for m in b["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells
            assert w in moved.get("workloads", cells)
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
