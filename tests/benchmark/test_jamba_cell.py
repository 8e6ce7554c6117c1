"""The cell ``jamba2-serve-highrate`` (AI21-Jamba2-3B whole on one chip,
PR 50): 26 Mamba-1 selective-scan layers with a per-channel state and
RMSNorms on dt, B and C, 2 multi-query attention layers without positions,
a dense SwiGLU feed-forward in every layer, the head tied to a 65,536-row
table. It names its files and metrics, its widths are the published ones
and nothing is cut but the declared context, its traffic fits its buckets
at four fifths of the swept knee, it rehearses on the CPU at its tiny
widths, ``correct`` comes out false under each of the six controls (the
reference at fp8, with the inner norms on B and C left out and with the
attention layers left out in the program's place; the state zeroed at the
hand-over, the state taken at the end of the padded bucket and a
convolution ring one tap off in the program) and when a served token is
altered, every reader it brings or shares returns a number (the program's
counters on a rehearsed run, the trace readers on a trace with the names a
v5e capture shows), the cost functions are the counts made by hand, and the
cell is files and entries over a benchmark that lacks them."""

import hashlib
import importlib.util
import json
import os
import shutil

import pytest
from test_glm_cell import entries_added

from harness import common, decode_parts, probes, serve_runner
from harness import trace as T
from harness.loader import ROOT, Cell, load_reader

CELL = "jamba2-serve-highrate"
CONFIG = "jamba2-3b-serve"
TRAFFIC = "highrate-lognormal-0.8knee"
NEW_READERS = ("serve.s6_state_ms_per_step", "s6_state_step_roofline",
               "serve.s6_scan_ms_per_ktoken", "s6_chunk_scan_roofline")
# readers that were there, read this program too and list the cell
SHARED_READERS = (
    "serve.state_live_share", "serve.decode_bw_share.ssm",
    "serve.full_attend_ms_per_step", "serve.attend_visit_share.gqa")
GENERIC_READERS = (
    "serve.decode_step_device_ms", "serve.device_idle_share",
    "serve.idle_fetch_ms_per_step", "serve.idle_launch_ms_per_step",
    "serve.idle_sched_ms_per_step", "serve.tpot_tail_admit_ms",
    "serve.tpot_tail_step_ms", "serve.admit_wall_share")
# The cell does not report ``serve_ttft_p50_ms``: 192 requests a window
# whose median first token lies a 17 ms decode step's phase wide do not
# hold its bound (PERF.md section 6, PR 50). The readers that move it
# read this program all the same and do not list the cell.
TTFT_READERS = (
    "serve.ttft_p95_ms", "serve.queue_steps_p95", "serve.prefill_device_ms",
    "serve.idle_admit_ms_per_admission", "serve.prefill_ms_per_ktoken",
    "serve.ttft_mid_wait_admit_ms", "serve.ttft_mid_wait_step_ms",
    "serve.ttft_mid_prefill_ms", "serve.admit_first_share")
CELL_FILES = (
    "configs/jamba2-3b-serve.json",
    "traffic/highrate-lognormal-0.8knee.json", "models/jamba.py",
    "tools/jamba_controls.py", "harness/s6_parts.py",
) + tuple(f"metrics/{name}.py" for name in NEW_READERS)
PARAMS = 3_029_337_472
# A_log, D, dt's bias and the norms' scales are float32
PARAM_BYTES = 2 * PARAMS + 2 * (26 * (16 * 5120 + 2 * 5120 + 192)
                                + 57 * 2560)
STATE, CONV, KV = 26 * 16 * 5120 * 4, 26 * 4 * 5120 * 2, 2 * 10240 * 512


def _controls():
    spec = importlib.util.spec_from_file_location(
        "jamba_controls",
        os.path.join(ROOT, "perfbench", "tools", "jamba_controls.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def rehearse(fault=None, control=None, trace=False, seed=2 ** 31 + 50):
    return serve_runner.run(Cell(CELL), seed=seed, seconds=2.0, trace=trace,
                            rehearse=True, fault=fault, control=control,
                            require_tpu=False)


@pytest.fixture(scope="module")
def sound():
    os.environ["TFD_DEVICE_MASK"] = "0"
    try:
        res = rehearse(control="fp8", trace=True)
        # the run's own summary: a later rehearsal writes over the file
        records = common.read_jsonl(os.path.join(
            ROOT, ".cache", "perfbench", CELL, "serve.jsonl"))
        res["summary"] = decode_parts.summary_of(records)
        yield res
    finally:
        os.environ.pop("TFD_DEVICE_MASK", None)


def test_the_cell_names_its_files_and_metrics():
    cell = Cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.kind) == (
        CONFIG, TRAFFIC, 1, "serve")
    assert cell.model.__file__.endswith("models/jamba.py")
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tpot_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert names == set(NEW_READERS + SHARED_READERS + GENERIC_READERS)
    assert not names & set(TTFT_READERS)
    for m in cell.bench["per_layer"]:
        if m["name"] in TTFT_READERS:
            assert m["moves"] == "serve_ttft_p50_ms"
    # nothing tied to another family's kernels, counters or sizes (the
    # Mamba-2 kernels' readers; the attend's roofline, whose reader takes
    # ``layer_counts``' first number for the full layers where this
    # model's is its state-space layers; anything of routed experts)
    assert not names & {
        "serve.ssm_state_ms_per_step", "serve.ssm_scan_ms_per_ktoken",
        "ssd_state_step_roofline", "ssd_chunk_scan_roofline",
        "gqa_dense_attend_roofline", "serve.decode_bw_share.gqa",
        "serve.moe_pairs_per_expert_step", "serve.moe_experts_hit_share",
        "serve.index_keep_share", "serve.step_ahead_share"}
    for other in ("glm52-serve-longctx", "axk1-serve-reasoning",
                  "sala-serve-longdoc", "granite4h-serve-chat",
                  "nemotron3s-serve-agentic", "kexaone-serve-mixedlen",
                  "gpt2l-serve-steady"):
        assert not set(NEW_READERS) & {
            m["name"] for m in Cell(other).per_layer()}
    moves = {"serve.s6_state_ms_per_step": "serve_tpot_p95_ms",
             "s6_state_step_roofline": "serve_tpot_p95_ms",
             # a prefill stands between two decode steps of every live
             # row: the scan's time is in the slowest rows' time a token
             "serve.s6_scan_ms_per_ktoken": "serve_tpot_p95_ms",
             "s6_chunk_scan_roofline": "serve_tpot_p95_ms"}
    for m in cell.per_layer():
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == moves[m["name"]]
            assert (m["source"], m["layer"]) == ("device_trace", "kernels")
            if m["name"].endswith("_roofline"):
                assert (m["unit"], m["better"]) == ("%", "higher")
    why = cell.entry["why"]
    slots = cell.config["serve"]["num_slots"]
    assert len(why) <= 200 and "whole" in why and f"{slots} slots" in why
    bench = cell.bench
    assert len(bench["workloads"]) == 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_configuration_holds_the_published_widths_and_cuts_only_context():
    cell = Cell(CELL)
    cfg, sizes = cell.config, cell.sizes()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = [json.loads(line) for line in f]
    row = next(r for r in catalog if r["name"] == "AI21-Jamba2-3B")
    entry = [c for c in cell.bench["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == row["source_url"]
    # every key of the catalog's config under the same name, unchanged but
    # for the declared context
    differ = {k for k, v in row["config"].items() if cfg.get(k, "∅") != v}
    assert differ == {"max_position_embeddings"}
    assert cfg["reduced"] == entry["reduced"] == ["max_position_embeddings"]
    assert set(cfg["changed"]) == {"max_position_embeddings"}
    assert (cfg["max_position_embeddings"],
            cfg["max_position_embeddings_published"]) == (10240, 262144)
    assert (cfg["hidden_size"], cfg["num_hidden_layers"],
            cfg["attn_layer_period"], cfg["attn_layer_offset"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_dt_rank"], cfg["mamba_expand"],
            cfg["vocab_size"], cfg["tie_word_embeddings"],
            cfg["num_experts"]) == (
        2560, 28, 14, 7, 20, 1, 8192, 16, 4, 160, 2, 65536, True, 1)
    assert set(cfg["assumed"]) >= {
        "norm_placement", "inner_norms", "dt", "D_skip", "A_log", "b_dt",
        "conv", "attention", "weights", "state_layout", "conv_ring",
        "buckets"}
    assert "whole model on one chip" in cfg["deployment"]
    assert cfg["bytes"] and cfg["precision"] and cfg["correct_limits_why"]
    assert [i for i, k in enumerate(sizes["layers"]) if k == "attention"] \
        == [7, 21]
    assert cfg["layers_as_run"] == list(sizes["layers"])
    assert cell.model.layer_counts(sizes) == (26, 2)
    # the published "3B", counted leaf by leaf
    assert cell.model.param_count(sizes) == PARAMS
    assert cell.model.param_bytes(sizes) == PARAM_BYTES
    assert cell.model.state_bytes_per_slot(sizes) == STATE
    assert cell.model.conv_bytes_per_slot(sizes) == CONV
    assert cell.model.cache_bytes_per_token(sizes) == {"kv": 1024}
    slots = cfg["serve"]["num_slots"]
    assert slots in (192, 128) and cfg["serve"]["num_slots_why"]
    # the fullest device holds well over a quarter of 16 GB
    assert (slots * (STATE + CONV + KV) + PARAM_BYTES) / 16e9 > 0.5
    # the rehearsal has every mechanism: two periods of 4 with the
    # attention layer third, a state of 4 numbers under a dt of rank 4,
    # one key-value head under 3 queries, a tied head, prompts over
    # several hundred positions
    small = cell.sizes(rehearse=True)
    assert small["layers"] == ("mamba", "mamba", "attention", "mamba") * 2
    assert (small["mamba_d_state"], small["mamba_dt_rank"],
            small["num_attention_heads"], small["num_key_value_heads"]) == (
        4, 4, 3, 1)
    assert cfg["rehearsal"]["traffic"]["prompt_len"]["min"] >= 300


def test_the_traffic_fits_the_buckets_at_four_fifths_of_the_knee():
    cell = Cell(CELL)
    mix, serve = cell.traffic, cell.config["serve"]
    buckets = [int(b) for b in serve["buckets"].split(",")]
    assert mix["prompt_len"] == {"median": 320, "sigma": 1.0, "min": 32,
                                 "max": 8192}
    assert mix["output_len"] == {"median": 768, "sigma": 0.6, "min": 128,
                                 "max": 2048}
    assert (mix["stop_fraction"], mix["schedule_seed"], mix["kind"],
            mix["arrivals"]) == (0.75, 1, "serve_open_loop", "poisson")
    assert buckets == [128, 256, 512, 1024, 2048, 4096, 8192]
    assert mix["prompt_len"]["max"] == max(buckets)
    assert (mix["prompt_len"]["max"] + mix["output_len"]["max"]
            == cell.config["max_position_embeddings"])
    # the median inside the 512 bucket, not on an edge
    assert 256 < mix["prompt_len"]["median"] < 512
    assert abs(mix["rate_rps"] - 0.8 * mix["knee_rps"]) \
        <= 0.011 * mix["knee_rps"]
    assert mix["knee_why"] and mix["why"]


def test_it_rehearses_correct(sound):
    assert sound["correct"] is True and sound["failed"] == 0
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    assert sound["check"]["max"] <= limits["served_token_gap_max"]
    assert sound["check"]["mean"] <= limits["served_token_gap_mean"]
    assert sound["check"]["tokens"] > 30
    assert sound["summary"]["decode_live_rows"] > 0


def test_the_lower_precision_control_is_not_correct(sound):
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    control = sound["check"]["control"]
    assert control["mean"] > 2 * limits["served_token_gap_mean"]
    assert control["max"] > 2 * limits["served_token_gap_max"]


@pytest.mark.parametrize("control", ["no_bc_norm", "no_attention"])
def test_a_mechanism_changed_in_the_reference_is_not_correct(one_chip_env,
                                                             control):
    """What the reference would have served with the inner norms on B and
    C left out, or with the attention layers left out, in the program's
    place: not ``correct`` by the cell's limits."""
    assert control in Cell(CELL).model.CONTROLS
    res = rehearse(control=control)
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    assert res["correct"] is True            # the program itself is sound
    got = res["check"]["control"]
    assert got["mean"] > 2 * limits["served_token_gap_mean"]
    assert got["max"] > 2 * limits["served_token_gap_max"]


@pytest.mark.parametrize("fault", ["altered_token", "state_zeroed",
                                   "state_at_bucket_end",
                                   "ring_one_tap_off"])
def test_a_broken_timed_path_is_not_correct(one_chip_env, fault):
    """One mechanism of the PROGRAM broken underneath
    (``tools/jamba_controls.py::broken``; a token altered: the probes' own
    fault), the cell rehearsed: ``correct`` is false, by at least one of
    the cell's limits."""
    controls = _controls()
    assert "altered_token" in probes.FAULTS
    with controls.broken(None if fault == "altered_token" else fault):
        res = rehearse(fault=fault if fault == "altered_token" else None)
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    assert res["correct"] is False
    assert res["check"]["max"] > limits["served_token_gap_max"] \
        or res["check"]["mean"] > limits["served_token_gap_mean"]


def test_the_programs_counters_reach_their_readers(sound):
    m, s = sound["metrics"], sound["summary"]
    for key in ("decode_live_rows", "state_rows_stepped",
                "state_rows_folded", "attend_keys", "select_keys_kept",
                "full_attend_keys", "attend_positions_visited",
                "s6_scan_positions", "s6_scan_positions_live"):
        assert s[key], key
    # three kinds of leaf in one tree, and the states' stamp
    assert s["cache_bytes_per_slot_by_kind"] == {
        "state": 6 * 4 * 96 * 4, "conv": 6 * 4 * 96 * 2,
        "kv": 2 * 1024 * 32 * 2, "state_pos": 4}
    assert s["state_rows_stepped"] == 6 * s["decode_live_rows"]
    assert s["full_attend_keys"] == s["select_keys_kept"] \
        == 2 * s["attend_keys"]
    assert s["s6_scan_positions"] > s["s6_scan_positions_live"]
    assert s["s6_scan_positions"] % (6 * 256) == 0     # buckets 512, 768
    assert m["serve.state_live_share"]["value"] == pytest.approx(100.0)
    assert m["serve.attend_visit_share.gqa"]["value"] == pytest.approx(
        100.0 * s["select_keys_kept"] / s["attend_positions_visited"])
    assert 0 < m["serve.attend_visit_share.gqa"]["value"] <= 100
    assert "serve.admit_wall_share" in m
    assert not set(m) & set(TTFT_READERS)
    # no device in a CPU capture: the trace readers find nothing
    for name in NEW_READERS + SHARED_READERS:
        if name not in ("serve.state_live_share",
                        "serve.attend_visit_share.gqa"):
            assert name not in m, name
    assert "moe_plan" not in s


def _step(start, names_us):
    ops, t = [], start
    for name, us in names_us:
        ops.append((name, t, int(us * 1e3)))
        t += int(us * 1e3) + 500
    return ops, t


def v5e_like_trace():
    """Two decode steps and one prefill with the op names a v5e capture of
    this model shows (the described-chip compiles name the same kernels:
    tests/test_tpu_compile.py): a Mamba layer is fusions around
    ``%s6_state_step``, an attention layer a row write and
    ``%gqa_dense_attend``; a prefill has ``%s6_chunk_scan`` and
    ``%mla_prefill_attend``."""
    mamba = [("%fusion.1", 80), ("%s6_state_step.2", 150),
             ("%fusion.3", 60), ("%fusion.4", 120)]
    attend = [("%fusion.5", 30), ("%latent_row_write.6", 8),
              ("%gqa_dense_attend.7", 200), ("%fusion.8", 120)]
    step = mamba * 7 + attend + mamba * 13 + attend + mamba * 6 \
        + [("%fusion.20", 500)]
    ops, modules, t = [], [], 1_000_000
    for _ in range(2):
        new, end = _step(t, step)
        ops += new
        modules.append(("jit_serve_decode_step(77)", t, end - t))
        t = end + 2_000_000
    new, end = _step(t, [("%fusion.50", 20_000)]
                     + [("%s6_chunk_scan.60", 400)] * 26
                     + [("%mla_prefill_attend.61", 300)] * 2)
    ops += new
    modules.append(("jit_serve_prefill_b2048(5)", t, end - t))
    return T.Trace({0: {"ops": ops, "async": [], "modules": modules}}, [],
                   0, end + 1000)


def _ctx(cell, summary, **kw):
    from harness import peaks
    base = dict(cell=cell, model=cell.model, records=[summary],
                trace=v5e_like_trace(), sizes=cell.sizes(), slots=192,
                param_bytes=PARAM_BYTES,
                peaks=peaks.peaks_for("TPU v5 lite"), chips=1,
                say=lambda msg: None, cut_s=1.0,
                ttft_ms_before_capture=[1.0], capture_live_rows=120.0)
    base.update(kw)
    return common.Ctx(**base)


def test_every_reader_of_the_cell_returns_a_number(sound):
    cell = Cell(CELL)
    sizes = cell.sizes()
    # the counts of a run at the cell's sizes: 120 live rows a step at a
    # mean depth of 900; the attends' blocks of 512 cover 1,024 a row
    steps, live = 1000, 1000 * 120
    keys = live * 900
    summary = dict(sound["summary"], decode_steps=steps,
                   decode_live_rows=live, state_rows_stepped=26 * live,
                   state_rows_folded=26 * live, state_rows_reread=0,
                   conv_bytes_per_slot=CONV, state_bytes_per_slot=STATE,
                   attend_keys=keys, select_keys_kept=2 * keys,
                   full_attend_keys=2 * keys,
                   attend_positions_visited=2 * live * 1024)
    ctx = _ctx(cell, summary)
    # the prefill's own reader too: it reads this program, listed or not
    on_fixture = NEW_READERS + SHARED_READERS + (
        "serve.prefill_ms_per_ktoken",)
    for name in on_fixture:
        value = load_reader(name)(ctx)
        assert isinstance(value, float) and value > 0, name
    read = lambda name: load_reader(name)(ctx)          # noqa: E731
    assert read("serve.s6_state_ms_per_step") == pytest.approx(26 * 0.150)
    assert read("serve.s6_scan_ms_per_ktoken") == pytest.approx(
        26 * 0.4 / 2.048)
    assert read("serve.full_attend_ms_per_step") == pytest.approx(0.400)
    assert read("serve.state_live_share") == pytest.approx(100.0)
    assert read("serve.attend_visit_share.gqa") == pytest.approx(
        100 * 900 / 1024)
    # 120 live rows' states, 327,680 B each, read and written: 78.6 MB is
    # 96 us at 819 GB/s (bytes bound: 7 operations a number are 0.35 us
    # at the MXU's peak); the kernel took 150 us in this made-up trace
    ops, byts = cell.model.state_step_cost(sizes, 120.0)
    assert (ops, byts) == (7.0 * 81920 * 120, 8.0 * 81920 * 120)
    assert read("s6_state_step_roofline") == pytest.approx(
        100 * (byts / 819e9) / 150e-6)
    assert read("s6_state_step_roofline") < 100
    # a capture with half the live rows needs half the bytes
    half = _ctx(cell, summary, capture_live_rows=60.0)
    assert load_reader("s6_state_step_roofline")(half) == pytest.approx(
        read("s6_state_step_roofline") / 2)
    # the scan at the 2,048 bucket: x, dt, z and y a channel a position
    # (14 B), B and C (128 B a position), the last state
    ops, byts = cell.model.s6_scan_cost(sizes, 2048)
    assert ops == 2048 * (7.0 * 81920 + 2 * 5120)
    assert byts == 2048 * (5120 * 14 + 128) + 81920 * 4
    assert read("s6_chunk_scan_roofline") == pytest.approx(
        100 * max(ops / 197e12, byts / 819e9) / 400e-6)
    assert byts / 819e9 > ops / 197e12       # bytes bound by the table
    assert read("s6_chunk_scan_roofline") < 100
    need = cell.model.decode_step_bytes(PARAM_BYTES, sizes, 120.0,
                                        keys_kept=120 * 900.0)
    step_ms = load_reader("serve.decode_step_device_ms")(ctx)
    assert read("serve.decode_bw_share.ssm") == pytest.approx(
        100 * (1e3 * need / 819e9) / step_ms)
    assert read("serve.decode_bw_share.ssm") < 100
    # on a program without the kernels and the counters (the parent, any
    # other model): nothing, no raise
    empty = _ctx(cell, {}, records=[], trace=T.Trace({}, [], 0, 1),
                 ttft_ms_before_capture=[], capture_live_rows=None)
    for name in on_fixture:
        assert load_reader(name)(empty) is None, name
    # a trace of another model's step (Mamba-2's kernels, not these)
    from test_nemotron_cell import v5e_like_trace as nemotron_trace
    theirs = _ctx(cell, summary, trace=nemotron_trace())
    for name in NEW_READERS:
        assert load_reader(name)(theirs) is None, name
    # a model file without the counts (another architecture's)
    other = _ctx(cell, summary, model=object())
    for name in ("s6_state_step_roofline", "s6_chunk_scan_roofline"):
        assert load_reader(name)(other) is None, name


def test_the_cost_functions_are_the_counts_made_by_hand():
    cell = Cell(CELL)
    sizes = cell.sizes()
    got = cell.model.decode_step_bytes(PARAM_BYTES, sizes, 120.0,
                                       keys_kept=120 * 900.0)
    assert got == pytest.approx(
        PARAM_BYTES + 120 * (2 * STATE + CONV) + 120 * 900 * 1024)
    full = cell.model.decode_step_bytes(PARAM_BYTES, sizes, 192)
    assert full == pytest.approx(
        PARAM_BYTES + 192 * (2 * STATE + CONV + 10240 * 1024))
    assert got < full
    # the live rows' states are about a quarter of such a step's bytes,
    # the weights most of the rest, K and V under a fiftieth
    assert 0.2 < 120 * 2 * STATE / got < 0.3
    assert 120 * 900 * 1024 / got < 0.02
    assert cell.model.state_numbers(sizes) == 81920


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
    without["configs"] = [c for c in full["configs"] if c["name"] != CONFIG]
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
        "configs": [CONFIG], "workloads": [CELL],
        "end_to_end": [], "per_layer": list(NEW_READERS)}
    # its name went to the END of the lists of the readers it shares
    for m in new["end_to_end"] + new["per_layer"]:
        if m["name"] in SHARED_READERS + GENERIC_READERS + (
                "serve_tpot_p95_ms",):
            assert m["workloads"][-1] == CELL, m["name"]
        elif m["name"] in TTFT_READERS + ("serve_ttft_p50_ms",):
            assert CELL not in m["workloads"], m["name"]
    assert new["configs"][-1]["name"] == CONFIG
    assert new["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in new["per_layer"]][-4:] == list(NEW_READERS)
    after = _hashes(bench_dir)
    for path, digest in before.items():
        assert after[path] == digest, f"{path} was edited"
    assert len(after) == len(before) + len(CELL_FILES)
