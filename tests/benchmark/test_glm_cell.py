"""The cell ``glm52-serve-longctx`` (GLM-5.2 as one chip's share, PR 28):
it rehearses on the CPU at its tiny widths, ``correct`` comes out false
when the timed path is broken underneath and under the lower-precision
control, the cell is files and entries over a benchmark that lacks them
(no other file edited), and every reader the cell brings returns a number
(the program's counters on a rehearsed run, the trace readers on a trace
with the names a v5e capture shows)."""

import hashlib
import json
import os
import shutil

import pytest

from harness import common, decode_parts, probes, serve_runner
from harness import trace as T
from harness.loader import ROOT, Cell, load_reader

CELL = "glm52-serve-longctx"
HERE = os.path.dirname(os.path.abspath(__file__))
NEW_READERS = (
    "serve.sparse_select_ms_per_step", "serve.latent_attend_ms_per_step",
    "serve.moe_expert_ms_per_step", "serve.prefill_ms_per_ktoken",
    "serve.index_keep_share", "serve.moe_pairs_per_expert_step",
    "mla_latent_attend_roofline", "dsa_index_scores_roofline",
    "serve.decode_bw_share.live")
# What the cell brings under perfbench/: data files, its model, its readers
# and the code they share, its tools.
CELL_FILES = (
    "configs/glm-5.2-serve.json", "traffic/longctx-lognormal-0.8knee.json",
    "models/glm_moe_dsa.py", "harness/decode_parts.py",
    "tools/selection_overlap.py", "tools/step_look.py",
) + tuple(f"metrics/{name}.py" for name in NEW_READERS)


def rehearse(fault=None, control=None, trace=False, seed=2 ** 31 + 19):
    return serve_runner.run(Cell(CELL), seed=seed, seconds=2.0, trace=trace,
                            rehearse=True, fault=fault, control=control,
                            require_tpu=False)


@pytest.fixture(scope="module")
def sound():
    os.environ["TFD_DEVICE_MASK"] = "0"
    try:
        yield rehearse(control="fp8", trace=True)
    finally:
        os.environ.pop("TFD_DEVICE_MASK", None)


def test_the_cell_names_its_files_and_metrics():
    cell = Cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.kind) == (
        "glm-5.2-serve", "longctx-lognormal-0.8knee", 1, "serve")
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_ttft_p50_ms", "serve_tpot_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW_READERS) <= names
    assert {"serve.decode_step_device_ms", "serve.device_idle_share",
            "serve.prefill_device_ms", "serve.idle_fetch_ms_per_step"} <= names
    # the generic share is told the slot count only (32 where 7 are live):
    # the cell reports the share from the program's counts instead
    assert "serve.decode_bw_share" not in names
    assert not any(n.endswith(".sat") for n in names)
    sizes = cell.sizes()
    assert sizes["layers"] == (
        ("dense", "full"), ("sparse", "shared"), ("sparse", "shared"),
        ("sparse", "shared"), ("sparse", "full"))
    assert (sizes["router_experts"], len(sizes["experts_held"])) == (256, 8)
    assert cell.model.param_count(sizes) == 2_673_557_504
    per = cell.model.cache_bytes_per_token(sizes)
    assert per == {"latent": 5 * 1152, "index_keys": 2 * 256}
    assert 32 * 16384 * sum(per.values()) == 3_288_334_336   # as needed
    # as stored: latent rows in whole lane tiles, 576 -> 640
    assert 32 * 16384 * (5 * 1280 + 2 * 256) == 3_623_878_656


def test_the_traffic_is_past_index_topk_and_fits_the_buckets():
    cell = Cell(CELL)
    mix, serve = cell.traffic, cell.config["serve"]
    buckets = [int(b) for b in serve["buckets"].split(",")]
    assert mix["prompt_len"]["min"] > cell.config["index_topk"]
    assert mix["prompt_len"]["max"] == max(buckets)
    assert (mix["prompt_len"]["max"] + mix["output_len"]["max"]
            <= cell.config["max_position_embeddings"])
    # at most a quarter of a bucket is padding
    lo = mix["prompt_len"]["min"]
    for b in buckets:
        assert (b - lo) / b <= 0.25 + 1e-9, (lo, b)
        lo = b + 1
    assert abs(mix["rate_rps"] - 0.8 * mix["knee_rps"]) \
        <= 0.011 * mix["knee_rps"]


def test_it_rehearses_correct(sound):
    assert sound["correct"] is True and sound["failed"] == 0
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    assert sound["check"]["max"] <= limits["served_token_gap_max"]
    assert sound["check"]["mean"] <= limits["served_token_gap_mean"]


def test_the_lower_precision_control_is_not_correct(sound):
    """What fp8 operands would have served fails the mean limit (at
    these tiny widths one swapped key in 70 tokens makes a sound run's
    MAX nearly the control's, so the mean is the limit that tells them
    apart; the configuration's ``correct_limits_why`` gives the chip's
    readings)."""
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    control = sound["check"]["control"]
    assert control["mean"] > 2 * limits["served_token_gap_mean"]
    assert control["max"] > limits["served_token_gap_max"]


@pytest.mark.parametrize("fault", [f for f in probes.FAULTS
                                   if f == "altered_token"])
def test_a_broken_timed_path_is_not_correct(one_chip_env, fault):
    """Of ``probes.FAULTS`` the serve seams implement ``altered_token``
    (the other two break the train step)."""
    res = rehearse(fault=fault)
    assert res["correct"] is False
    assert res["check"]["max"] > Cell(CELL).config["rehearsal"][
        "correct_limits"]["served_token_gap_max"]


def test_the_programs_counters_reach_their_readers(sound):
    m = sound["metrics"]
    assert 0 < m["serve.index_keep_share"]["value"] < 100
    assert m["serve.moe_pairs_per_expert_step"]["value"] > 0
    for name in ("serve.ttft_p95_ms", "serve.queue_steps_p95"):
        assert name in m
    # no device in a CPU capture: the trace readers find nothing
    assert "serve.latent_attend_ms_per_step" not in m


def _hashes(top):
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture
def cell_over_a_benchmark_without_it(benchmark_copy):
    """A copy of the benchmark with this cell taken OUT (its files, its
    entries, its name in other metrics' lists), then added again as a
    ``model_config`` PR adds it: files beside the others, entries at the
    end of their lists. Yields (root, the benchmark as it was without the
    cell); afterwards every file the copy had without the cell must have
    the hash it had."""
    root = benchmark_copy
    bench_dir = os.path.join(root, "perfbench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        full = json.load(f)
    held = os.path.join(root, "held")
    for rel in CELL_FILES:
        os.makedirs(os.path.dirname(os.path.join(held, rel)), exist_ok=True)
        shutil.move(os.path.join(bench_dir, rel), os.path.join(held, rel))
    without = json.loads(json.dumps(full))
    without["configs"] = [c for c in full["configs"]
                          if c["name"] != "glm-5.2-serve"]
    without["workloads"] = [w for w in full["workloads"]
                            if w["name"] != CELL]
    for key in ("end_to_end", "per_layer"):
        without[key] = [m for m in without[key]
                        if m.get("workloads") != [CELL]]
        for m in without[key]:
            if CELL in m.get("workloads", ()):
                m["workloads"].remove(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(without, f)
    before = _hashes(bench_dir)
    yield root, without
    after = _hashes(bench_dir)
    for path, digest in before.items():
        assert after[path] == digest, f"{path} was edited"


def _add_the_cell(root):
    for rel in CELL_FILES:
        shutil.move(os.path.join(root, "held", rel),
                    os.path.join(root, "perfbench", rel))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)


def test_the_benchmark_runs_without_the_cells_files(
        cell_over_a_benchmark_without_it):
    """Nothing the benchmark had reaches into what the cell brings: with
    the cell's files gone the other cells still name their files, models
    and readers, and the cell itself is unknown."""
    root, without = cell_over_a_benchmark_without_it
    for w in without["workloads"]:
        cell = Cell(w["name"], root=root)
        assert cell.model.__file__.endswith("gpt2.py")
        assert cell.sizes()
        for m in cell.per_layer():
            assert m["name"] not in NEW_READERS
            assert load_reader(m["name"], root=root) is not None
    with pytest.raises(Exception):
        Cell(CELL, root=root)


LISTS = ("configs", "workloads", "end_to_end", "per_layer")


def entries_added(without, new, cells):
    """Hold ``new`` to be ``without`` plus additions, entry by NAME and
    not by place: everything outside the four lists equal; every entry
    ``without`` has is in ``new`` under its name, in the order it had,
    equal but for its ``workloads``, which is the list it had with names
    of ``cells`` added at its end. Returns {list: names ``new`` adds}."""
    for key in set(without) | set(new):
        if key not in LISTS:
            assert new[key] == without[key], key
    added = {}
    for key in LISTS:
        was = {e["name"]: e for e in without[key]}
        names = [e["name"] for e in new[key]]
        assert len(set(names)) == len(names), f"{key}: a name twice"
        assert [n for n in names if n in was] == list(was), (
            f"{key}: an entry went, or the order changed")
        for now in new[key]:
            if now["name"] not in was:
                continue
            had, got = dict(was[now["name"]]), dict(now)
            had_cells = had.pop("workloads", None)
            got_cells = got.pop("workloads", None)
            assert got == had, f"{key} {now['name']} was edited"
            if had_cells is None:
                assert got_cells is None, now["name"]
                continue
            more = got_cells[len(had_cells):]
            assert got_cells[:len(had_cells)] == had_cells, now["name"]
            assert set(more) <= set(cells) and len(set(more)) == len(more), (
                now["name"], more)
        added[key] = [n for n in names if n not in was]
    return added


@pytest.mark.parametrize("further", [False, True],
                         ids=["the_cell", "and_a_further_cell_after_it"])
def test_the_cell_is_files_and_entries_and_edits_no_file(
        cell_over_a_benchmark_without_it, add_second_model, one_chip_env,
        further):
    """Added over that benchmark the cell loads from its own files, and
    BENCHMARK.json differs from the one without it by added entries and
    the cell's name at the end of other metrics' ``workloads`` (the
    fixture holds every other file to its hash). Entries are found by
    name: whatever a later PR appends AFTER the cell's (``further``: a
    second architecture's configuration, cell and per-layer metric) is
    held to the same rule, loads from its own files and rehearses."""
    root, without = cell_over_a_benchmark_without_it
    _add_the_cell(root)
    cells = [CELL]
    want = {"configs": ["glm-5.2-serve"], "workloads": [CELL],
            "end_to_end": [], "per_layer": list(NEW_READERS)}
    if further:
        more = add_second_model(root, with_metric=True)
        cells.append(more.cell)
        want["configs"].append(more.config)
        want["workloads"].append(more.cell)
        want["per_layer"].append(more.metric)
    cell = Cell(CELL, root=root)
    assert cell.model.__file__.startswith(root)
    assert set(NEW_READERS) <= {m["name"] for m in cell.per_layer()}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        new = json.load(f)
    added = entries_added(without, new, cells)
    assert {k: sorted(v) for k, v in added.items()} == {
        k: sorted(v) for k, v in want.items()}
    if further:
        second = Cell(more.cell, root=root)
        assert second.model.__file__.startswith(root)
        assert [m["name"] for m in second.per_layer()] == [more.metric]
        assert more.metric not in {m["name"] for m in cell.per_layer()}
        res = serve_runner.run(second, seed=2 ** 31 + 31, seconds=2.0,
                               trace=True, rehearse=True, require_tpu=False)
        assert res["correct"] is True and res["failed"] == 0
        assert res["metrics"][more.metric]["value"] > 0


def _step(start, names_us):
    ops, t = [], start
    for name, us in names_us:
        ops.append((name, t, int(us * 1e3)))
        t += int(us * 1e3) + 500
    return ops, t


def v5e_like_trace():
    """Two decode steps and one prefill with the op names a v5e capture
    of this model shows (tools/step_look.py, PERF.md section 3)."""
    layer_full = [("%fusion.1", 40), ("%dsa_index_scores.2", 100),
                  ("%fusion.9", 10), ("%sort.3", 200), ("%sort.4", 50),
                  ("%fusion.5", 900), ("%mla_latent_attend.6", 150)]
    layer_shared = [("%fusion.20", 40), ("%fusion.21", 900),
                    ("%mla_latent_attend.22", 150)]
    moe = [("%sort.30", 20), ("%gmm.31", 300), ("%gmm.32", 300),
           ("%gmm.33", 300), ("%convolution_fusion.34", 80)]
    step = (layer_full + [("%convolution_fusion.8", 500)] + layer_shared
            + moe + layer_full + moe)
    ops, modules, t = [], [], 1_000_000
    for _ in range(2):
        new, end = _step(t, step)
        ops += new
        modules.append(("jit_serve_decode_step(77)", t, end - t))
        t = end + 2_000_000
    new, end = _step(t, [("%fusion.50", 30_000), ("%gmm.51", 10_000)])
    ops += new
    modules.append(("jit_serve_prefill_b4096(5)", t, end - t))
    return T.Trace({0: {"ops": ops, "async": [], "modules": modules}}, [],
                   0, end + 1000)


def test_decode_parts_on_a_recorded_v5e_capture():
    """Three decode steps of the cell, recorded on the chip (PR 28, the
    first 4,000 device ops of a traced run: tools/trace_look.py)."""
    tr = T.load_json(os.path.join(HERE, "fixtures",
                                  "glm_serve_v5e.json.gz"))
    parts = decode_parts.decode_parts(tr)
    assert parts["steps"] == 3
    assert (parts["index_kernels_per_step"],
            parts["attend_kernels_per_step"],
            parts["experts_kernels_per_step"]) == (2, 5, 12)
    assert parts["select_ms"] == pytest.approx(0.7885, abs=1e-3)
    assert parts["attend_ms"] == pytest.approx(0.5699, abs=1e-3)
    assert parts["experts_ms"] == pytest.approx(0.3726, abs=1e-3)
    assert parts["step_ms"] == pytest.approx(11.339, abs=1e-2)
    names = {o[0].split(" ")[0].split(".")[0]
             for o in tr.devices[0]["ops"]}
    assert {"%dsa_index_scores", "%mla_latent_attend", "%gmm",
            "%latent_row_write", "%sort", "%fusion"} <= names
    assert not any("gather" in n for n in names)    # the gather is anonymous


def test_decode_parts_ties_sorts_by_order():
    parts = decode_parts.decode_parts(v5e_like_trace())
    assert parts["steps"] == 2
    # two full layers: index scores 100 + sorts 250; the MoE's sort, the
    # gather fusions and the fusion before the index scores are nobody's
    assert parts["select_ms"] == pytest.approx(2 * 0.350)
    assert parts["attend_ms"] == pytest.approx(3 * 0.150)
    assert parts["experts_ms"] == pytest.approx(6 * 0.300)
    assert parts["step_ms"] > (parts["select_ms"] + parts["attend_ms"]
                               + parts["experts_ms"])
    assert decode_parts.decode_parts(T.Trace({}, [], 0, 1)) is None
    gpt2 = T.Trace({0: {"ops": [("%fusion.1", 10, 5)], "async": [],
                        "modules": [("jit_serve_decode_step(1)", 0, 100)]}},
                   [], 0, 100)
    assert decode_parts.decode_parts(gpt2) is None     # another model


def test_every_new_reader_returns_a_number(sound):
    from harness import peaks
    cell = Cell(CELL)
    summary = decode_parts.summary_of(
        common.read_jsonl(os.path.join(ROOT, ".cache", "perfbench", CELL,
                                       "serve.jsonl")))
    assert summary is not None
    ctx = common.Ctx(cell=cell, model=cell.model, records=[summary],
                     trace=v5e_like_trace(), sizes=cell.sizes(), slots=32,
                     param_bytes=5_347_117_056,
                     peaks=peaks.peaks_for("TPU v5 lite"), chips=1,
                     say=lambda msg: None, cut_s=1.0,
                     ttft_ms_before_capture=[1.0])
    for name in NEW_READERS:
        value = load_reader(name)(ctx)
        assert isinstance(value, float) and value > 0, name
    assert load_reader("serve.prefill_ms_per_ktoken")(ctx) == \
        pytest.approx(1e3 * 40.0 / 4096)
    # on a program without the kernels and the counters: nothing, no raise
    empty = common.Ctx(cell=cell, model=cell.model, records=[],
                       trace=T.Trace({}, [], 0, 1), sizes=cell.sizes(),
                       slots=32, param_bytes=1, peaks=ctx.peaks, chips=1,
                       say=lambda msg: None, cut_s=1.0,
                       ttft_ms_before_capture=[])
    for name in NEW_READERS:
        assert load_reader(name)(empty) is None, name


def test_decode_step_bytes_counts_what_live_rows_need():
    """The experts no pair reached and the rows of free slots are not
    read: with the program's counts the bytes are the parameters less the
    embedding table and the unreached experts, plus the kept latent rows
    and the index keys seen; without them, what ``slots`` live rows need
    at the least under uniform routing."""
    cell = Cell(CELL)
    sizes, params = cell.sizes(), 5_347_117_056
    one_expert = 3 * 6144 * 2048 * 2
    rest = params - 19360 * 6144 * 2 - 4 * 8 * one_expert
    got = cell.model.decode_step_bytes(
        params, sizes, 6.5, experts_hit=5.25, keys_kept=13000.0,
        keys_available=45000.0)
    assert got == pytest.approx(rest + 6.5 * 6144 * 2 + 5.25 * one_expert
                                + 13000 * 5 * 1152 + 45000 * 2 * 256)
    full = cell.model.decode_step_bytes(params, sizes, 32)
    hit = 4 * 8 * (1 - (1 - 8 / 256) ** 32)            # 20.4 of 32
    assert full == pytest.approx(rest + 32 * 6144 * 2 + hit * one_expert
                                 + 32 * 2048 * (5 * 1152 + 2 * 256))
    # a fifth of the slots live: a third less than every slot live, far
    # below "every parameter once"
    assert got < 0.7 * full < 0.7 * params
