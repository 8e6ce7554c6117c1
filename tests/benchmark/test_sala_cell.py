"""The cell ``sala-serve-longdoc`` (MiniCPM-SALA as one 8-layer pipeline
stage, PR 35): a hybrid of linear layers with a fixed-size recurrent state
and block-sparse grouped-query layers. It names its files and metrics, its
sizes are the published ones and its cut is depth alone, its traffic is
past ``dense_len`` and fits its buckets, it rehearses on the CPU at its
tiny widths, ``correct`` comes out false when the timed path is broken
underneath and under the lower-precision control, every reader it brings
or shares returns a number (the program's counters on a rehearsed run, the
trace readers on a trace with the names a v5e capture shows), and the cell
is files and entries over a benchmark that lacks them."""

import hashlib
import json
import os
import shutil

import pytest
from test_glm_cell import entries_added

from harness import common, decode_parts, hybrid_parts, probes, serve_runner
from harness import trace as T
from harness.loader import ROOT, Cell, load_reader

CELL = "sala-serve-longdoc"
NEW_READERS = (
    "serve.lightning_state_ms_per_step", "serve.sparse_block_ms_per_step",
    "serve.lightning_scan_ms_per_ktoken", "lightning_state_step_roofline",
    "lightning_chunk_scan_roofline", "sparse_block_attend_roofline",
    "serve.decode_bw_share.hybrid", "serve.state_live_share",
    "sparse_block_scores_roofline")
# readers that were there and read this program too
SHARED_READERS = ("serve.prefill_ms_per_ktoken", "serve.index_keep_share")
GENERIC_READERS = (
    "serve.ttft_p95_ms", "serve.queue_steps_p95", "serve.prefill_device_ms",
    "serve.decode_step_device_ms", "serve.device_idle_share",
    "serve.idle_fetch_ms_per_step", "serve.idle_launch_ms_per_step",
    "serve.idle_sched_ms_per_step", "serve.idle_admit_ms_per_admission")
CELL_FILES = (
    "configs/minicpm-sala-serve.json",
    "traffic/longdoc-lognormal-0.8knee.json", "models/minicpm_sala.py",
    "harness/hybrid_parts.py",
) + tuple(f"metrics/{name}.py" for name in NEW_READERS)


def rehearse(fault=None, control=None, trace=False, seed=2 ** 31 + 35):
    return serve_runner.run(Cell(CELL), seed=seed, seconds=2.0, trace=trace,
                            rehearse=True, fault=fault, control=control,
                            require_tpu=False)


@pytest.fixture(scope="module")
def sound():
    os.environ["TFD_DEVICE_MASK"] = "0"
    try:
        res = rehearse(control="fp8", trace=True)
        # the run's own summary: a later rehearsal writes over the file
        res["summary"] = decode_parts.summary_of(common.read_jsonl(
            os.path.join(ROOT, ".cache", "perfbench", CELL, "serve.jsonl")))
        yield res
    finally:
        os.environ.pop("TFD_DEVICE_MASK", None)


def test_the_cell_names_its_files_and_metrics():
    cell = Cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.kind) == (
        "minicpm-sala-serve", "longdoc-lognormal-0.8knee", 1, "serve")
    assert cell.model.__file__.endswith("models/minicpm_sala.py")
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_ttft_p50_ms", "serve_tpot_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert names == set(NEW_READERS + SHARED_READERS + GENERIC_READERS)
    # no latent cache, no experts, no indexer: nothing of the other
    # family's kernels; and not on the step-ahead share's list, which its
    # own test pins
    assert not names & {
        "serve.latent_attend_ms_per_step", "serve.moe_expert_ms_per_step",
        "serve.decode_bw_share.live", "serve.decode_bw_share",
        "serve.step_ahead_share", "serve.gather_live_share"}
    for other in ("glm52-serve-longctx", "axk1-serve-reasoning",
                  "gpt2l-serve-steady"):
        assert not set(NEW_READERS) & {
            m["name"] for m in Cell(other).per_layer()}
    for m in cell.per_layer():
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
            if m["name"].endswith("_roofline"):
                assert (m["unit"], m["source"], m["layer"]) == (
                    "%", "device_trace", "kernels")


def test_the_configuration_holds_the_published_widths_and_states_the_cut():
    cell = Cell(CELL)
    cfg, sizes = cell.config, cell.sizes()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = [json.loads(line) for line in f]
    row = next(r for r in catalog if r["name"] == "MiniCPM-SALA")
    entry = [c for c in cell.bench["configs"]
             if c["name"] == "minicpm-sala-serve"][0]
    assert entry["source"] == row["source_url"]
    # every key of the catalog's config under the same name, unchanged
    # but for the two that are reduced
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == {"num_hidden_layers", "max_position_embeddings"}
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    assert set(cfg["changed"]) == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["max_position_embeddings"],
            cfg["first_layer_held"], cfg["num_hidden_layers_published"],
            cfg["max_position_embeddings_published"]) == (
        8, 25600, 9, 32, 524288)
    assert set(cfg["assumed"]) >= {"sparse_config", "decay", "qk_norm",
                                   "gate"}
    assert cfg["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "init_blocks": 1,
        "block_size": 64, "window_size": 2048, "topk": 64,
        "dense_len": 8192}
    assert "4 pipeline stages" in cfg["deployment"] and cfg["bytes"]
    assert sizes["mixers"] == ("minicpm4",) + ("lightning-attn",) * 6 + (
        "minicpm4",) == tuple(row["config"]["mixer_types"][9:17])
    assert sizes["published_layers"] == 32
    assert cell.model.residual_scale(sizes) == pytest.approx(
        1.4 / 32 ** 0.5)
    assert cell.model.param_count(sizes) == 2_820_569_088
    assert cell.model.state_bytes_per_slot(sizes) == 12_582_912
    assert cell.model.cache_bytes_per_token(sizes) == {
        "kv": 2048, "pooled_keys": 64.0}
    # a fixed-size state against the K and V the same six layers would
    # hold at 25,600 positions under the same 2-head grouping
    assert 6 * 1024 * 25600 == 157_286_400 > 12 * 12_582_912


def test_the_traffic_is_past_dense_len_and_fits_the_buckets():
    cell = Cell(CELL)
    mix, serve = cell.traffic, cell.config["serve"]
    buckets = [int(b) for b in serve["buckets"].split(",")]
    assert mix["prompt_len"] == {"median": 10240, "sigma": 0.4, "min": 8704,
                                 "max": 24576}
    assert mix["output_len"] == {"median": 192, "sigma": 0.6, "min": 48,
                                 "max": 768}
    assert (mix["stop_fraction"], mix["schedule_seed"], mix["kind"]) == (
        1.0, 1, "serve_open_loop")
    assert mix["prompt_len"]["min"] > cell.config["sparse_config"][
        "dense_len"]
    assert mix["prompt_len"]["max"] == max(buckets)
    assert (mix["prompt_len"]["max"] + mix["output_len"]["max"]
            <= cell.config["max_position_embeddings"])
    assert serve["num_slots"] == 32
    # every bucket a whole number of scan chunks, attend tiles and MLP
    # blocks
    assert all(b % 1024 == 0 for b in buckets)
    lo = mix["prompt_len"]["min"]
    for b in buckets:
        assert (b - lo) / b <= 1 / 5 + 1e-9, (lo, b)
        lo = b + 1
    assert abs(mix["rate_rps"] - 0.8 * mix["knee_rps"]) \
        <= 0.011 * mix["knee_rps"]


def test_it_rehearses_correct(sound):
    assert sound["correct"] is True and sound["failed"] == 0
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    assert sound["check"]["max"] <= limits["served_token_gap_max"]
    assert sound["check"]["mean"] <= limits["served_token_gap_mean"]
    assert sound["check"]["tokens"] > 30
    # the rehearsal is past ITS dense_len: the selection really drops
    # blocks
    s = sound["summary"]
    assert s["sparse_rows_dense"] == 0
    assert s["select_keys_kept"] < 0.5 * s["select_keys_available"]


def test_the_lower_precision_control_is_not_correct(sound):
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    control = sound["check"]["control"]
    assert control["mean"] > 2 * limits["served_token_gap_mean"]
    assert control["max"] > 2 * limits["served_token_gap_max"]


@pytest.mark.parametrize("fault", [f for f in probes.FAULTS
                                   if f == "altered_token"])
def test_a_broken_timed_path_is_not_correct(one_chip_env, fault):
    res = rehearse(fault=fault)
    assert res["correct"] is False
    assert res["check"]["max"] > Cell(CELL).config["rehearsal"][
        "correct_limits"]["served_token_gap_max"]


def test_the_programs_counters_reach_their_readers(sound):
    m, s = sound["metrics"], sound["summary"]
    for key in ("decode_live_rows", "select_keys_available",
                "select_keys_kept", "sparse_blocks_kept",
                "state_rows_stepped", "state_bytes_per_slot"):
        assert s[key], key
    assert set(s["cache_bytes_per_slot_by_kind"]) == {
        "kv", "pooled_keys", "state", "state_pos"}
    assert s["state_rows_stepped"] == 2 * s["decode_live_rows"]
    assert m["serve.state_live_share"]["value"] == pytest.approx(100.0)
    assert m["serve.index_keep_share"]["value"] == pytest.approx(
        100.0 * s["select_keys_kept"] / s["select_keys_available"])
    for name in ("serve.ttft_p95_ms", "serve.queue_steps_p95"):
        assert name in m
    # no device in a CPU capture: the trace readers find nothing
    for name in NEW_READERS:
        if name != "serve.state_live_share":
            assert name not in m, name


def _step(start, names_us):
    ops, t = [], start
    for name, us in names_us:
        ops.append((name, t, int(us * 1e3)))
        t += int(us * 1e3) + 500
    return ops, t


def v5e_like_trace():
    """Two decode steps and one prefill with the op names a v5e capture
    of this model shows (the described-chip compiles name the same
    kernels: tests/test_tpu_compile.py)."""
    mlp = [("%fusion.9", 700)]
    sparse = [("%fusion.1", 60), ("%latent_row_write.2", 8),
              ("%latent_row_write.3", 6), ("%sparse_block_scores.4", 30),
              ("%fusion.5", 12), ("%sort.6", 40), ("%fusion.7", 8),
              ("%sparse_block_attend.8", 100)] + mlp
    linear = [("%fusion.11", 90), ("%lightning_state_step.12", 80),
              ("%fusion.13", 30)] + mlp
    step = sparse + linear * 6 + sparse + [("%fusion.20", 800)]
    ops, modules, t = [], [], 1_000_000
    for _ in range(2):
        new, end = _step(t, step)
        ops += new
        modules.append(("jit_serve_decode_step(77)", t, end - t))
        t = end + 2_000_000
    new, end = _step(t, [("%fusion.50", 400_000)]
                     + [("%lightning_chunk_scan.51", 2_000)] * 6)
    ops += new
    modules.append(("jit_serve_prefill_b10240(5)", t, end - t))
    return T.Trace({0: {"ops": ops, "async": [], "modules": modules}}, [],
                   0, end + 1000)


def test_the_steps_kernels_are_read_by_name_and_the_selection_by_order():
    k = hybrid_parts.decode_kernels(v5e_like_trace())
    assert (k["steps"], k["state_calls"], k["scores_calls"],
            k["attend_calls"]) == (2, 12, 4, 4)
    assert k["state_s"] == pytest.approx(12 * 80e-6)
    # scores + the three ops between + the attend, two layers a step
    assert k["select_s"] == pytest.approx(4 * (30 + 12 + 40 + 8 + 100)
                                          * 1e-6)
    seconds, found = hybrid_parts.prefill_scans(v5e_like_trace())
    assert found == [(10240, 6)] and seconds == pytest.approx(6 * 2e-3)
    assert hybrid_parts.decode_kernels(T.Trace({}, [], 0, 1)) is None


def test_every_reader_of_the_cell_returns_a_number(sound):
    from harness import peaks
    cell = Cell(CELL)
    # the counts of a run at the cell's sizes: live rows at a mean depth
    # of 12,000, each keeping 4,064 positions a group
    summary = dict(sound["summary"], decode_steps=400,
                   decode_live_rows=400 * 8, state_rows_stepped=400 * 8 * 6,
                   select_keys_available=400 * 96_000,
                   select_keys_kept=400 * 8 * 4064)
    ctx = common.Ctx(cell=cell, model=cell.model, records=[summary],
                     trace=v5e_like_trace(), sizes=cell.sizes(), slots=32,
                     param_bytes=5_641_138_176,
                     peaks=peaks.peaks_for("TPU v5 lite"), chips=1,
                     say=lambda msg: None, cut_s=1.0,
                     ttft_ms_before_capture=[1.0],
                     # the capture's own steps: 8 live rows each (a real
                     # capture says so on its token_fetch spans)
                     capture_live_rows=8.0)
    for name in NEW_READERS + SHARED_READERS:
        value = load_reader(name)(ctx)
        assert isinstance(value, float) and value > 0, name
    read = lambda name: load_reader(name)(ctx)          # noqa: E731
    assert read("serve.lightning_state_ms_per_step") == pytest.approx(
        6 * 0.080)
    assert read("serve.sparse_block_ms_per_step") == pytest.approx(
        2 * 0.190)
    assert read("serve.lightning_scan_ms_per_ktoken") == pytest.approx(
        12.0 / 10.24)
    # 8 rows x 2.1 MB read and written: 33.6 MB is 41 us at 819 GB/s; the
    # kernel took 80 us
    assert read("lightning_state_step_roofline") == pytest.approx(
        100 * (8 * 2 * 32 * 128 * 128 * 4 / 819e9) / 80e-6)
    # 8 x 4,064 kept positions x 1,024 B (K and V, both groups)
    assert read("sparse_block_attend_roofline") == pytest.approx(
        100 * (8 * 4064 * 1024 / 819e9) / 100e-6)
    # 6,000 pooled windows x 512 B
    assert read("sparse_block_scores_roofline") == pytest.approx(
        100 * (6000 * 512 / 819e9) / 30e-6)
    ops, byts = cell.model.chunk_scan_cost(cell.sizes(), 10240, 256)
    assert read("lightning_chunk_scan_roofline") == pytest.approx(
        100 * max(ops / 197e12, byts / 819e9) / 2e-3)
    assert read("serve.state_live_share") == pytest.approx(100.0)
    assert 0 < read("serve.decode_bw_share.hybrid") < 100
    for name in NEW_READERS:
        if name.endswith("_roofline"):
            assert read(name) < 100, name
    # on a program without the kernels and the counters (the parent, any
    # other model): nothing, no raise
    empty = common.Ctx(cell=cell, model=cell.model, records=[],
                       trace=T.Trace({}, [], 0, 1), sizes=cell.sizes(),
                       slots=32, param_bytes=1, peaks=ctx.peaks, chips=1,
                       say=lambda msg: None, cut_s=1.0,
                       ttft_ms_before_capture=[])
    for name in NEW_READERS + SHARED_READERS:
        assert load_reader(name)(empty) is None, name
    # another family's summary (no state counters) under this trace: the
    # readers that divide by the program's counts have nothing to divide
    other = common.Ctx(
        cell=cell, model=cell.model, trace=v5e_like_trace(),
        records=[{"event": "serve_summary", "decode_steps": 9,
                  "decode_live_rows": 9, "select_keys_available": 9}],
        sizes=cell.sizes(), peaks=ctx.peaks, param_bytes=1, slots=32,
        chips=1, say=lambda msg: None, capture_live_rows=8.0)
    for name in ("lightning_state_step_roofline",
                 "sparse_block_attend_roofline",
                 "sparse_block_scores_roofline",
                 "serve.decode_bw_share.hybrid", "serve.state_live_share"):
        assert load_reader(name)(other) is None, name


def test_decode_step_bytes_counts_what_live_rows_need():
    cell = Cell(CELL)
    sizes, params = cell.sizes(), 5_641_138_176
    rest = params - 73448 * 4096 * 2
    state = 6 * 32 * 128 * 128 * 4
    got = cell.model.decode_step_bytes(
        params, sizes, 8.0, keys_kept=8 * 4064.0, keys_available=96_000.0)
    assert got == pytest.approx(rest + 8 * 4096 * 2 + 2 * 8 * state
                                + 8 * 4064 * 2048 + 96_000 * 64)
    full = cell.model.decode_step_bytes(params, sizes, 32)
    assert full == pytest.approx(rest + 32 * 4096 * 2 + 2 * 32 * state
                                 + 32 * 4096 * 2048 + 32 * 25600 * 64)
    assert rest < got < full
    # the layer weights and the head are most of a step
    assert rest / got > 0.9


def _hashes(top):
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_the_cell_is_files_and_entries_and_edits_no_file(benchmark_copy):
    """Taken OUT of a copy of the benchmark (its files, its entries, its
    name in other metrics' lists), every other cell still loads its
    files, model and readers; added again as a ``model_config`` PR adds
    it, ``BENCHMARK.json`` differs by appended entries and the cell's
    name at the end of ``workloads`` lists, and every file the copy had
    without the cell has the hash it had."""
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
                          if c["name"] != "minicpm-sala-serve"]
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
    for w in without["workloads"]:
        cell = Cell(w["name"], root=root)
        assert cell.sizes()
        for m in cell.per_layer():
            assert m["name"] not in NEW_READERS
            assert load_reader(m["name"], root=root) is not None
    with pytest.raises(Exception):
        Cell(CELL, root=root)
    for rel in CELL_FILES:
        shutil.move(os.path.join(held, rel), os.path.join(bench_dir, rel))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    cell = Cell(CELL, root=root)
    assert cell.model.__file__.startswith(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        new = json.load(f)
    assert entries_added(without, new, [CELL]) == {
        "configs": ["minicpm-sala-serve"], "workloads": [CELL],
        "end_to_end": [], "per_layer": list(NEW_READERS)}
    # its name went to the END of the lists of the readers it shares
    for m in new["end_to_end"] + new["per_layer"]:
        if m["name"] in SHARED_READERS + GENERIC_READERS + (
                "serve_ttft_p50_ms", "serve_tpot_p95_ms"):
            assert m["workloads"][-1] == CELL, m["name"]
    after = _hashes(bench_dir)
    for path, digest in before.items():
        assert after[path] == digest, f"{path} was edited"
    assert len(after) == len(before) + len(CELL_FILES)
