"""The cell ``axk1-serve-reasoning`` (A.X-K1 as one chip's share, PR 33):
a second model of the latent-attention family, with no selection. It
names its files and metrics, its sizes are the published ones, its
traffic fits its buckets, it rehearses on the CPU at its tiny widths,
``correct`` comes out false when the timed path is broken underneath and
under the lower-precision control, every reader it brings or shares
returns a number (the program's counters on a rehearsed run, the trace
readers on a trace with the names a v5e capture shows), and the cell is
files and entries over a benchmark that lacks them."""

import hashlib
import json
import os
import shutil

import pytest
from test_glm_cell import entries_added

from harness import common, decode_parts, probes, serve_runner
from harness import trace as T
from harness.loader import ROOT, Cell, load_reader

CELL = "axk1-serve-reasoning"
NEW_READERS = ("mla_dense_attend_roofline", "serve.attend_visit_share")
# readers that were there and read this program too
SHARED_READERS = (
    "serve.latent_attend_ms_per_step", "serve.moe_expert_ms_per_step",
    "serve.prefill_ms_per_ktoken", "serve.moe_pairs_per_expert_step",
    "serve.decode_bw_share.live")
GENERIC_READERS = (
    "serve.ttft_p95_ms", "serve.queue_steps_p95", "serve.prefill_device_ms",
    "serve.decode_step_device_ms", "serve.device_idle_share",
    "serve.idle_fetch_ms_per_step", "serve.idle_launch_ms_per_step",
    "serve.idle_sched_ms_per_step", "serve.idle_admit_ms_per_admission")
CELL_FILES = (
    "configs/ax-k1-serve.json", "traffic/reasoning-lognormal-0.8knee.json",
    "models/axk1.py",
) + tuple(f"metrics/{name}.py" for name in NEW_READERS)


def rehearse(fault=None, control=None, trace=False, seed=2 ** 31 + 33):
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
        "ax-k1-serve", "reasoning-lognormal-0.8knee", 1, "serve")
    assert cell.model.__file__.endswith("models/axk1.py")
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_ttft_p50_ms", "serve_tpot_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW_READERS + SHARED_READERS + GENERIC_READERS) <= names
    # no selection and no indexer: nothing of GLM's sparse path, and the
    # generic bandwidth share is told the slot count only
    assert not names & {
        "serve.index_keep_share", "serve.sparse_select_ms_per_step",
        "mla_latent_attend_roofline", "dsa_index_scores_roofline",
        "serve.decode_bw_share"}
    assert not any(n.endswith(".sat") for n in names)
    # the GLM cell reports neither of the readers this cell brings
    assert not set(NEW_READERS) & {
        m["name"] for m in Cell("glm52-serve-longctx").per_layer()}


def test_the_configuration_holds_the_published_widths_and_states_the_cut():
    cell = Cell(CELL)
    cfg, sizes = cell.config, cell.sizes()
    published = {
        "hidden_size": 7168, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "num_attention_heads": 64, "num_key_value_heads": 64,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "intermediate_size": 18432, "moe_intermediate_size": 2048,
        "n_group": 8, "topk_group": 4, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "first_k_dense_replace": 1, "moe_layer_freq": 1,
        "rms_norm_eps": 1e-6, "rope_theta": 10000, "topk_method": "none",
        "scoring_func": "sigmoid", "model_type": "axk1"}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "max_position_embeddings"]
    entry = [c for c in cell.bench["configs"]
             if c["name"] == "ax-k1-serve"][0]
    assert entry["reduced"] == cfg["reduced"] and set(cfg["changed"]) == set(
        cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["max_position_embeddings"]) == (
        5, 12, 20480, 10240)
    assert (cfg["num_hidden_layers_published"],
            cfg["n_routed_experts_published"], cfg["vocab_size_published"],
            cfg["max_position_embeddings_published"]) == (
        61, 192, 163840, 131072)
    assert "16 chips" in cfg["deployment"]
    assert sizes["layers"] == ("dense",) + ("sparse",) * 4
    assert (sizes["router_experts"], sizes["experts_held"]) == (
        192, tuple(range(12)))
    assert cell.model.param_count(sizes) == 3_491_258_112
    assert cell.model.cache_bytes_per_token(sizes) == {"latent": 5760}
    assert cell.model.cache_bytes_per_token_stored(sizes) == 6400
    assert cfg["serve"]["num_slots"] * sizes["n_positions"] * 6400 \
        == 3_145_728_000
    # a position of one layer costs every head's score and weighted sum
    # and its own numbers once
    assert cell.model.dense_attend_cost(sizes, 1.0) == (139_264.0, 1152.0)


def test_the_traffic_fits_the_buckets_and_the_cache():
    cell = Cell(CELL)
    mix, serve = cell.traffic, cell.config["serve"]
    buckets = [int(b) for b in serve["buckets"].split(",")]
    assert mix["prompt_len"] == {"median": 4096, "sigma": 0.5, "min": 2048,
                                 "max": 8192}
    assert mix["output_len"] == {"median": 768, "sigma": 0.5, "min": 256,
                                 "max": 1536}
    assert (mix["stop_fraction"], mix["schedule_seed"]) == (0.75, 1)
    assert mix["prompt_len"]["max"] == max(buckets)
    assert mix["prompt_len"]["min"] == min(buckets)
    assert (mix["prompt_len"]["max"] + mix["output_len"]["max"]
            <= cell.config["max_position_embeddings"])
    # at most a third of a bucket is padding
    lo = mix["prompt_len"]["min"]
    for b in buckets:
        assert (b - lo) / b <= 1 / 3 + 1e-9, (lo, b)
        lo = b + 1
    assert abs(mix["rate_rps"] - 0.8 * mix["knee_rps"]) \
        <= 0.011 * mix["knee_rps"]


def test_it_rehearses_correct(sound):
    assert sound["correct"] is True and sound["failed"] == 0
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    assert sound["check"]["max"] <= limits["served_token_gap_max"]
    assert sound["check"]["mean"] <= limits["served_token_gap_mean"]
    assert sound["check"]["tokens"] > 30


def test_the_lower_precision_control_is_not_correct(sound):
    """What fp8 operands would have served fails BOTH limits (no discrete
    selection here: a sound run's gaps are routing flips at the most)."""
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
                "select_keys_kept", "attend_positions_visited",
                "moe_held_pairs", "moe_held_pairs_by_expert",
                "moe_experts_hit", "moe_pairs_per_expert_step"):
        assert s[key], key
    assert s["select_keys_kept"] == s["select_keys_available"]
    assert set(s["cache_bytes_per_slot_by_kind"]) == {"latent"}
    # prompts of 600-1,536 in blocks of 1,024: a live row's last block is
    # half empty on average, a free slot's never visited
    assert s["attend_positions_visited"] % 1024 == 0
    assert 40 < m["serve.attend_visit_share"]["value"] < 100
    assert m["serve.attend_visit_share"]["value"] == pytest.approx(
        100.0 * s["select_keys_available"] / s["attend_positions_visited"])
    assert m["serve.moe_pairs_per_expert_step"]["value"] > 0
    for name in ("serve.ttft_p95_ms", "serve.queue_steps_p95"):
        assert name in m
    # no device in a CPU capture: the trace readers find nothing
    assert "mla_dense_attend_roofline" not in m
    assert "serve.latent_attend_ms_per_step" not in m


def _step(start, names_us):
    ops, t = [], start
    for name, us in names_us:
        ops.append((name, t, int(us * 1e3)))
        t += int(us * 1e3) + 500
    return ops, t


def v5e_like_trace():
    """Two decode steps and one prefill with the op names a v5e capture
    of this model shows (the described-chip compile of the decode step
    names the same kernels: tests/test_tpu_compile.py)."""
    attn = [("%fusion.1", 60), ("%latent_row_write.2", 8),
            ("%fusion.3", 40), ("%mla_latent_attend_dense.4", 500),
            ("%fusion.5", 70)]
    moe = [("%sort.30", 20), ("%gmm.31", 40), ("%gmm.32", 40),
           ("%gmm.33", 40), ("%convolution_fusion.34", 1200)]
    step = attn + [("%convolution_fusion.8", 2500)] + (attn + moe) * 4
    ops, modules, t = [], [], 1_000_000
    for _ in range(2):
        new, end = _step(t, step)
        ops += new
        modules.append(("jit_serve_decode_step(77)", t, end - t))
        t = end + 2_000_000
    new, end = _step(t, [("%fusion.50", 90_000), ("%gmm.51", 30_000)])
    ops += new
    modules.append(("jit_serve_prefill_b4096(5)", t, end - t))
    return T.Trace({0: {"ops": ops, "async": [], "modules": modules}}, [],
                   0, end + 1000)


def test_the_steps_split_reads_the_dense_kernel_by_its_prefix():
    parts = decode_parts.decode_parts(v5e_like_trace())
    assert parts["steps"] == 2
    assert (parts["index_kernels_per_step"],
            parts["attend_kernels_per_step"],
            parts["experts_kernels_per_step"]) == (0, 5, 12)
    assert parts["select_ms"] == 0
    assert parts["attend_ms"] == pytest.approx(5 * 0.500)
    assert parts["experts_ms"] == pytest.approx(12 * 0.040)
    assert parts["step_ms"] > parts["attend_ms"] + parts["experts_ms"]


def test_every_reader_of_the_cell_returns_a_number(sound):
    from harness import peaks
    cell = Cell(CELL)
    summary = sound["summary"]
    assert summary is not None
    # the counts of a run at the cell's sizes: 32 live rows at a mean
    # depth of 5,000 over 400 steps, blocks of 1,024
    summary = dict(summary, decode_steps=400, decode_live_rows=400 * 32,
                   select_keys_available=400 * 160_000,
                   select_keys_kept=400 * 160_000,
                   attend_positions_visited=400 * 176_000,
                   moe_experts_hit=400 * 36)
    ctx = common.Ctx(cell=cell, model=cell.model, records=[summary],
                     trace=v5e_like_trace(), sizes=cell.sizes(), slots=48,
                     param_bytes=6_982_517_760,
                     peaks=peaks.peaks_for("TPU v5 lite"), chips=1,
                     say=lambda msg: None, cut_s=1.0,
                     ttft_ms_before_capture=[1.0])
    for name in NEW_READERS + SHARED_READERS:
        value = load_reader(name)(ctx)
        assert isinstance(value, float) and value > 0, name
    # 160,000 positions a call: 22.3 GFLOP is 113 us at 197 TFLOP/s,
    # 184 MB is 225 us at 819 GB/s; the kernel took 500 us
    assert load_reader("mla_dense_attend_roofline")(ctx) == pytest.approx(
        100 * (160_000 * 1152 / 819e9) / 500e-6)
    assert load_reader("serve.attend_visit_share")(ctx) == pytest.approx(
        100 * 160 / 176)
    assert 0 < load_reader("serve.decode_bw_share.live")(ctx) < 100
    assert load_reader("serve.prefill_ms_per_ktoken")(ctx) == \
        pytest.approx(1e3 * 120.0 / 4096)
    # on a program without the kernel and the counters (the parent, any
    # other model): nothing, no raise
    empty = common.Ctx(cell=cell, model=cell.model, records=[],
                       trace=T.Trace({}, [], 0, 1), sizes=cell.sizes(),
                       slots=48, param_bytes=1, peaks=ctx.peaks, chips=1,
                       say=lambda msg: None, cut_s=1.0,
                       ttft_ms_before_capture=[])
    for name in NEW_READERS + SHARED_READERS:
        assert load_reader(name)(empty) is None, name
    glm = Cell("glm52-serve-longctx")
    for name in NEW_READERS:     # GLM's program: no such kernel or counter
        assert load_reader(name)(common.Ctx(
            cell=glm, model=glm.model, trace=v5e_like_trace(),
            records=[{"event": "serve_summary", "decode_steps": 9,
                      "select_keys_available": 9}], sizes=glm.sizes(),
            peaks=ctx.peaks, say=lambda msg: None)) is None, name


def test_decode_step_bytes_counts_what_live_rows_need():
    cell = Cell(CELL)
    sizes, params = cell.sizes(), 6_982_517_760
    one_expert = 3 * 7168 * 2048 * 2
    rest = params - 20480 * 7168 * 2 - 4 * 12 * one_expert
    got = cell.model.decode_step_bytes(
        params, sizes, 32.0, experts_hit=36.5, keys_kept=160_000.0,
        keys_available=160_000.0)
    assert got == pytest.approx(rest + 32 * 7168 * 2 + 36.5 * one_expert
                                + 160_000 * 5 * 1152)
    full = cell.model.decode_step_bytes(params, sizes, 48)
    hit = 4 * 12 * (1 - (1 - 8 / 192) ** 48)
    assert full == pytest.approx(rest + 48 * 7168 * 2 + hit * one_expert
                                 + 48 * 10240 * 5 * 1152)
    assert got < params < full


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
    files, model and readers, the GLM cell among them; added again as a
    ``model_config`` PR adds it, ``BENCHMARK.json`` differs by appended
    entries and the cell's name at the end of ``workloads`` lists, and
    every file the copy had without the cell has the hash it had."""
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
                          if c["name"] != "ax-k1-serve"]
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
        "configs": ["ax-k1-serve"], "workloads": [CELL], "end_to_end": [],
        "per_layer": list(NEW_READERS)}
    # its name went to the END of the lists of the readers it shares
    for m in new["per_layer"]:
        if m["name"] in SHARED_READERS + GENERIC_READERS:
            assert m["workloads"][-1] == CELL, m["name"]
    after = _hashes(bench_dir)
    for path, digest in before.items():
        assert after[path] == digest, f"{path} was edited"
    assert len(after) == len(before) + len(CELL_FILES)
