"""The cell ``kexaone-serve-mixedlen`` (K-EXAONE-236B-A23B as one chip of a
64-chip deployment, PR 47): three rotary 128-token sliding-window layers to
one full layer without positions, QK-norm, 16 of 128 sigmoid-routed gated
experts beside a shared expert, a ring of K and V beside the whole rows. It
names its files and metrics, its widths are the published ones and its cuts
are stated, its traffic fits its buckets at four fifths of the swept knee,
it rehearses on the CPU at its tiny widths, ``correct`` comes out false
under each of the five controls (the reference at fp8, with the window
ignored and with the rotation on the full layer too in the program's
place; a ring written one slot off and the routed part left out in the
program) and when a served token is altered, every reader it brings or
shares returns a number (the program's counters on a rehearsed run, the
trace readers on a trace with the names a v5e capture shows), the cost
functions are the counts made by hand, and the cell is files and entries
over a benchmark that lacks them."""

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

CELL = "kexaone-serve-mixedlen"
CONFIG = "k-exaone-236b-serve"
TRAFFIC = "mixedlen-lognormal-0.8knee"
NEW_READERS = ("serve.full_attend_ms_per_step", "gqa_dense_attend_roofline",
               "serve.attend_visit_share.gqa", "serve.decode_bw_share.gqa")
# readers that were there and read this program too
SHARED_READERS = (
    "serve.prefill_ms_per_ktoken", "serve.prefill_attend_ms_per_ktoken",
    "serve.moe_pairs_per_expert_step", "serve.moe_experts_hit_share",
    "serve.moe_combine_ms_per_ktoken", "serve.moe_gmm_ms_per_step",
    "moe_gmm_roofline", "serve.moe_held_pair_share",
    "serve.index_keep_share")
GENERIC_READERS = (
    "serve.ttft_p95_ms", "serve.queue_steps_p95", "serve.prefill_device_ms",
    "serve.decode_step_device_ms", "serve.device_idle_share",
    "serve.idle_fetch_ms_per_step", "serve.idle_launch_ms_per_step",
    "serve.idle_sched_ms_per_step", "serve.idle_admit_ms_per_admission",
    "serve.ttft_mid_wait_admit_ms", "serve.ttft_mid_wait_step_ms",
    "serve.ttft_mid_prefill_ms", "serve.tpot_tail_admit_ms",
    "serve.tpot_tail_step_ms", "serve.admit_wall_share",
    "serve.admit_first_share")
CELL_FILES = (
    "configs/k-exaone-236b-serve.json",
    "traffic/mixedlen-lognormal-0.8knee.json", "models/exaone_moe.py",
    "tools/kexaone_controls.py",
) + tuple(f"metrics/{name}.py" for name in NEW_READERS)
PARAM_BYTES = 7_424_057_856


def _controls():
    spec = importlib.util.spec_from_file_location(
        "kexaone_controls",
        os.path.join(ROOT, "perfbench", "tools", "kexaone_controls.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def rehearse(fault=None, control=None, trace=False, seed=2 ** 31 + 47):
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
        res["start"] = [r for r in records if r.get("event") == "start"][0]
        yield res
    finally:
        os.environ.pop("TFD_DEVICE_MASK", None)


def test_the_cell_names_its_files_and_metrics():
    cell = Cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.kind) == (
        CONFIG, TRAFFIC, 1, "serve")
    assert cell.model.__file__.endswith("models/exaone_moe.py")
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_ttft_p50_ms", "serve_tpot_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert names == set(NEW_READERS + SHARED_READERS + GENERIC_READERS)
    # nothing tied to another family's kernels, counters or sizes: the
    # older visit share divides other counts (section 3 of PERF.md)
    assert not names & {
        "serve.moe_expert_ms_per_step", "serve.latent_attend_ms_per_step",
        "serve.decode_bw_share.live", "serve.decode_bw_share.ssm",
        "serve.decode_bw_share.hybrid", "serve.attend_visit_share",
        "mla_dense_attend_roofline", "serve.state_live_share",
        "serve.step_ahead_share"}
    for other in ("glm52-serve-longctx", "axk1-serve-reasoning",
                  "sala-serve-longdoc", "granite4h-serve-chat",
                  "nemotron3s-serve-agentic", "gpt2l-serve-steady"):
        assert not set(NEW_READERS) & {
            m["name"] for m in Cell(other).per_layer()}
    for m in cell.per_layer():
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tpot_p95_ms"
            if m["name"].endswith("_roofline"):
                assert (m["unit"], m["source"], m["layer"]) == (
                    "%", "device_trace", "kernels")
    why = cell.entry["why"]
    assert len(why) <= 200 and "1/8" in why and "8x" in why
    bench = cell.bench
    assert len(bench["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_configuration_holds_the_published_widths_and_states_the_cuts():
    cell = Cell(CELL)
    cfg, sizes = cell.config, cell.sizes()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = [json.loads(line) for line in f]
    row = next(r for r in catalog if r["name"] == "K-EXAONE-236B-A23B")
    entry = [c for c in cell.bench["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == row["source_url"]
    # every key of the catalog's config under the same name, unchanged
    # but for the five that are reduced; none of them a width; the three
    # per-layer lists are kept whole and read from ``first_layer_held``
    differ = {k for k, v in row["config"].items() if cfg.get(k, "∅") != v}
    reduced = ["num_hidden_layers", "num_experts", "vocab_size",
               "max_position_embeddings", "num_nextn_predict_layers"]
    assert differ == set(reduced)
    assert cfg["reduced"] == entry["reduced"] == reduced
    assert set(cfg["changed"]) == set(reduced)
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in reduced)
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"],
            cfg["num_nextn_predict_layers"]) == (5, 16, 19200, 16384, 0)
    assert (cfg["num_hidden_layers_published"],
            cfg["num_experts_published"], cfg["vocab_size_published"],
            cfg["max_position_embeddings_published"],
            cfg["num_nextn_predict_layers_published"],
            cfg["first_layer_held"]) == (48, 128, 153600, 262144, 1, 0)
    assert cfg["experts_held"] == list(range(16))
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], cfg["moe_intermediate_size"],
            cfg["intermediate_size"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"]) == (
        6144, 64, 8, 128, 128, 2048, 18432, 8, 2.5)
    assert set(cfg["assumed"]) >= {
        "norm_placement", "qk_norm", "rotation", "window", "router",
        "shared_expert", "weights", "buckets"}
    assert "8 pipeline stages of 6 layers" in cfg["deployment"] \
        and "8 chips share each layer" in cfg["deployment"]
    assert cfg["bytes"] and cfg["precision"] and cfg["correct_limits_why"]
    assert sizes["layers"] == (
        ("sliding_attention", "dense"), ("sliding_attention", "sparse"),
        ("sliding_attention", "sparse"), ("full_attention", "sparse"),
        ("sliding_attention", "sparse"))
    # the published ratio, three window layers to one full, after the
    # dense layer: one whole period
    assert row["config"]["layer_types"].count("full_attention") * 3 == \
        row["config"]["layer_types"].count("sliding_attention")
    assert [a for a, _ in sizes["layers"][1:]].count("full_attention") == 1
    assert (sizes["router_experts"], len(sizes["experts_held"])) == (128, 16)
    assert cell.model.param_count(sizes) == 3_712_028_416
    assert cell.model.param_bytes(sizes) == PARAM_BYTES
    assert cell.model.kv_bytes_per_position(sizes) == 4096
    assert cell.model.cache_bytes_per_slot(sizes) == {
        "kv": 67_108_864, "kv_ring": 2_097_152}
    assert cell.model.expert_bytes(sizes) == 75_497_472
    slot = 67_108_864 + 2_097_152
    slots = cfg["serve"]["num_slots"]
    assert slots in (32, 24) and cfg["serve"]["num_slots_why"]
    # the fullest device holds well over a quarter of 16 GB
    assert (slots * slot + PARAM_BYTES) / 16e9 > 0.55
    # the whole model by the same count is the published 236B
    whole = dict(cfg, num_hidden_layers=48, num_experts=128,
                 experts_held=list(range(128)), vocab_size=153600)
    assert 236.0e9 < cell.model.param_count(cell.model.sizes(whole)) \
        < 237.0e9
    # the rehearsal has every mechanism: L L L G L with a dense layer 0, a
    # window of 16 under prompts several windows long, QK-norm and the
    # rotation in the model's file, 16 experts of which 4 held and 3 a
    # token, a sliced vocabulary
    small = cell.sizes(rehearse=True)
    assert small["layers"] == sizes["layers"]
    assert small["sliding_window"] == 16
    assert (small["router_experts"], len(small["experts_held"]),
            small["num_experts_per_tok"]) == (16, 4, 3)
    assert cfg["rehearsal"]["sizes"]["vocab_size_published"] \
        == 2 * small["vocab_size"]
    assert cfg["rehearsal"]["traffic"]["prompt_len"]["median"] \
        > 4 * small["sliding_window"]


def test_the_qk_norm_scales_are_seeded_where_the_configuration_says():
    """The cell seeds both QK-norm scales around 1.75 (softmax scores of
    deviation 3: at 1 ``correct`` did not see the full layer's attention
    on the chip, ``assumed.weights``); a configuration without the key,
    as the rehearsal's sizes, keeps 1; no other norm moves."""
    import jax
    import numpy as np

    cell = Cell(CELL)
    assert cell.config["qk_norm_scale"] == cell.sizes()["qk_norm_scale"] \
        == 1.75
    assert "qk_norm_scale" in cell.config["assumed"]["weights"]
    small = cell.sizes(rehearse=True)
    assert small["qk_norm_scale"] == 1.0
    for scale in (1.0, 1.75):
        params = cell.model.make_params(jax.random.PRNGKey(3),
                                        dict(small, qk_norm_scale=scale))
        mixer = params["layer_3"]["mixer"]
        for leaf in (mixer["q_norm"]["scale"], mixer["k_norm"]["scale"]):
            assert abs(float(np.mean(np.asarray(leaf, np.float32)))
                       - scale) < 0.03
        for leaf in (params["layer_3"]["attn_norm"]["scale"],
                     params["final_norm"]["scale"]):
            assert abs(float(np.mean(np.asarray(leaf, np.float32)))
                       - 1.0) < 0.03


def test_the_traffic_fits_the_buckets_at_four_fifths_of_the_knee():
    cell = Cell(CELL)
    mix, serve = cell.traffic, cell.config["serve"]
    buckets = [int(b) for b in serve["buckets"].split(",")]
    assert mix["prompt_len"] == {"median": 2560, "sigma": 1.0, "min": 128,
                                 "max": 12288}
    assert mix["output_len"] == {"median": 384, "sigma": 0.7, "min": 32,
                                 "max": 2048}
    assert (mix["stop_fraction"], mix["schedule_seed"], mix["kind"],
            mix["arrivals"]) == (0.75, 1, "serve_open_loop", "poisson")
    assert buckets == [256, 512, 1024, 2048, 3072, 4096, 6144, 8192, 12288]
    # whole 1,024-blocks from 1,024 up, as the fused attend wants
    assert all(b % 1024 == 0 for b in buckets if b >= 1024)
    assert mix["prompt_len"]["max"] == max(buckets)
    assert (mix["prompt_len"]["max"] + mix["output_len"]["max"]
            <= cell.config["max_position_embeddings"])
    # the median inside the 3,072 bucket, not on an edge; every prompt at
    # least a window long: every ring has wrapped before its first step
    assert 2048 < mix["prompt_len"]["median"] < 3072
    assert mix["prompt_len"]["min"] >= cell.config["sliding_window"]
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
    # the start record says what the banded attend computes
    plan = sound["start"]["prefill_attend_plan"]
    assert set(plan) == {"64", "128", "256"}
    assert plan["256"]["window"]["window"] == 16
    assert plan["256"]["window"]["tiles_computed"] <= \
        plan["256"]["full"]["tiles_computed"]


def test_the_lower_precision_control_is_not_correct(sound):
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    control = sound["check"]["control"]
    assert control["mean"] > 2 * limits["served_token_gap_mean"]
    assert control["max"] > 2 * limits["served_token_gap_max"]


@pytest.mark.parametrize("control", ["window_ignored", "rope_everywhere"])
def test_a_mechanism_changed_in_the_reference_is_not_correct(one_chip_env,
                                                             control):
    """What the reference would have served with the window ignored (the
    window layers attend the whole depth) or the rotation on the full
    layer too, in the program's place: not ``correct`` by the cell's
    limits."""
    assert control in Cell(CELL).model.CONTROLS
    res = rehearse(control=control)
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    assert res["correct"] is True            # the program itself is sound
    got = res["check"]["control"]
    assert got["mean"] > 2 * limits["served_token_gap_mean"]
    assert got["max"] > 2 * limits["served_token_gap_max"]


@pytest.mark.parametrize("fault", ["altered_token", "ring_one_off",
                                   "routed_part_left_out"])
def test_a_broken_timed_path_is_not_correct(one_chip_env, fault):
    """One mechanism of the PROGRAM broken underneath
    (``tools/kexaone_controls.py::broken``; a token altered: the probes'
    own fault), the cell rehearsed: ``correct`` is false, by at least one
    of the cell's limits."""
    controls = _controls()
    assert "altered_token" in probes.FAULTS
    assert set(controls.BREAKS) == {
        "ring_one_off", "ring_read_unmasked", "routed_part_left_out"}
    with controls.broken(None if fault == "altered_token" else fault, 16):
        res = rehearse(fault=fault if fault == "altered_token" else None)
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    assert res["correct"] is False
    assert res["check"]["max"] > limits["served_token_gap_max"] \
        or res["check"]["mean"] > limits["served_token_gap_mean"]


def test_the_programs_counters_reach_their_readers(sound):
    m, s = sound["metrics"], sound["summary"]
    for key in ("decode_live_rows", "select_keys_available",
                "select_keys_kept", "attend_positions_visited",
                "full_attend_keys", "moe_held_pairs", "moe_pairs_routed",
                "moe_experts_hit", "moe_layers"):
        assert s[key], key
    # two kinds of K and V leaf: four rings of 16 rows, one full row
    row = 2 * 2 * 8 * 2
    assert s["cache_bytes_per_slot_by_kind"] == {
        "kv": 512 * row, "kv_ring": 4 * 16 * row}
    assert s["select_keys_available"] == 5 * s["full_attend_keys"]
    assert s["full_attend_keys"] < s["select_keys_kept"] \
        < s["select_keys_available"]
    assert s["moe_pairs_routed"] == s["decode_live_rows"] * 3 * 4
    assert m["serve.index_keep_share"]["value"] == pytest.approx(
        100.0 * s["select_keys_kept"] / s["select_keys_available"])
    assert m["serve.attend_visit_share.gqa"]["value"] == pytest.approx(
        100.0 * s["select_keys_kept"] / s["attend_positions_visited"])
    assert 0 < m["serve.attend_visit_share.gqa"]["value"] <= 100
    assert m["serve.moe_pairs_per_expert_step"]["value"] == pytest.approx(
        s["moe_held_pairs"] / (4 * 4 * s["decode_steps"]), rel=1e-4)
    assert m["serve.moe_experts_hit_share"]["value"] == pytest.approx(
        100.0 * s["moe_experts_hit"] / (4 * 4 * s["decode_steps"]))
    assert m["serve.moe_held_pair_share"]["value"] == pytest.approx(
        100.0 * s["moe_held_pairs"] / s["moe_pairs_routed"])
    assert 10 < m["serve.moe_held_pair_share"]["value"] < 45
    for name in ("serve.ttft_p95_ms", "serve.queue_steps_p95",
                 "serve.admit_wall_share"):
        assert name in m
    # no device in a CPU capture: the trace readers find nothing
    for name in NEW_READERS + SHARED_READERS:
        if name not in ("serve.moe_held_pair_share",
                        "serve.moe_pairs_per_expert_step",
                        "serve.moe_experts_hit_share",
                        "serve.index_keep_share",
                        "serve.attend_visit_share.gqa"):
            assert name not in m, name
    assert s["moe_plan"]["decode"]["form"] == "one_hot"


def _step(start, names_us):
    ops, t = [], start
    for name, us in names_us:
        ops.append((name, t, int(us * 1e3)))
        t += int(us * 1e3) + 500
    return ops, t


def v5e_like_trace():
    """Two decode steps and one prefill with the op names a v5e capture of
    this model shows (the described-chip compiles name the same kernels:
    tests/test_tpu_compile.py): a window layer's attend is anonymous
    fusions around a row write, the full layer's is ``%gqa_dense_attend``,
    a routed layer three grouped matmuls."""
    ring = [("%fusion.1", 60), ("%latent_row_write.2", 8),
            ("%fusion.3", 40)]
    full = [("%fusion.4", 60), ("%latent_row_write.5", 8),
            ("%gqa_dense_attend.6", 900)]
    moe = [("%fusion.9", 40), ("%sort.3", 30), ("%gmm.1", 400),
           ("%gmm.2", 400), ("%gmm.3", 350), ("%fusion.10", 60)]
    dense = [("%fusion.11", 500)]
    step = ring + dense + (ring + moe) * 2 + full + moe + ring + moe \
        + [("%fusion.20", 300)]
    ops, modules, t = [], [], 1_000_000
    for _ in range(2):
        new, end = _step(t, step)
        ops += new
        modules.append(("jit_serve_decode_step(77)", t, end - t))
        t = end + 2_000_000
    new, end = _step(t, [("%fusion.50", 60_000)]
                     + [("%mla_prefill_attend.60", 700)] * 4
                     + [("%mla_prefill_attend.61", 2100)]
                     + [("%gmm.52", 1500)] * 12
                     + [("%moe_combine_held.53", 300)] * 4)
    ops += new
    modules.append(("jit_serve_prefill_b3072(5)", t, end - t))
    return T.Trace({0: {"ops": ops, "async": [], "modules": modules}}, [],
                   0, end + 1000)


def _ctx(cell, summary, **kw):
    from harness import peaks
    base = dict(cell=cell, model=cell.model, records=[summary],
                trace=v5e_like_trace(), sizes=cell.sizes(), slots=32,
                param_bytes=PARAM_BYTES,
                peaks=peaks.peaks_for("TPU v5 lite"), chips=1,
                say=lambda msg: None, cut_s=1.0,
                ttft_ms_before_capture=[1.0], capture_live_rows=25.0)
    base.update(kw)
    return common.Ctx(**base)


def test_every_reader_of_the_cell_returns_a_number(sound):
    cell = Cell(CELL)
    # the counts of a run at the cell's sizes: 25 live rows a step at a
    # mean depth of 4,000 (every ring wrapped), each routing 8 pairs in 4
    # layers of which an eighth land here, 51 of the 64 held experts
    # reached a step; the full layer's blocks of 512 cover 4,256 a row
    steps, live = 1000, 1000 * 25
    full = live * 4000
    kept = full + 4 * live * 128
    summary = dict(sound["summary"], decode_steps=steps,
                   decode_live_rows=live, full_attend_keys=full,
                   select_keys_available=5 * full, select_keys_kept=kept,
                   attend_positions_visited=live * 4256
                   + 4 * 32 * 128 * steps,
                   moe_layers=4, moe_pairs_routed=live * 8 * 4,
                   moe_held_pairs=live * 8 * 4 // 8,
                   moe_experts_hit=steps * 51)
    ctx = _ctx(cell, summary)
    for name in NEW_READERS + SHARED_READERS:
        value = load_reader(name)(ctx)
        assert isinstance(value, float) and value > 0, name
    read = lambda name: load_reader(name)(ctx)          # noqa: E731
    assert read("serve.full_attend_ms_per_step") == pytest.approx(0.900)
    assert read("serve.moe_gmm_ms_per_step") == pytest.approx(4 * 1.150)
    assert read("serve.moe_held_pair_share") == pytest.approx(12.5)
    assert read("serve.index_keep_share") == pytest.approx(
        100 * kept / (5 * full))
    assert read("serve.attend_visit_share.gqa") == pytest.approx(
        100 * kept / (live * 4256 + 4 * 32 * 128 * steps))
    assert read("serve.prefill_attend_ms_per_ktoken") == pytest.approx(
        (4 * 0.7 + 2.1) / 3.072)
    assert read("serve.moe_combine_ms_per_ktoken") == pytest.approx(
        4 * 0.3 / 3.072)
    # 25 rows 4,000 deep: 100,000 positions x 4,096 B is 0.41 GB, 500 us
    # at 819 GB/s (bytes bound: the operations are 17 us); the kernel took
    # 900 us in this made-up trace
    ops, byts = cell.model.gqa_attend_cost(cell.sizes(), 100_000.0)
    assert (ops, byts) == (4.0 * 64 * 128 * 100_000, 100_000 * 4096.0)
    assert read("gqa_dense_attend_roofline") == pytest.approx(
        100 * (byts / 819e9) / 900e-6)
    assert read("gqa_dense_attend_roofline") < 100
    # a capture with half the run's live rows needs half the positions
    half = _ctx(cell, summary, capture_live_rows=12.5)
    assert load_reader("gqa_dense_attend_roofline")(half) == pytest.approx(
        read("gqa_dense_attend_roofline") / 2)
    pairs = 25 * 8 * 4 / 8
    ops, byts = cell.model.expert_step_cost(cell.sizes(), pairs, 51.0)
    assert ops == 6.0 * 6144 * 2048 * pairs
    assert byts == 51 * 75_497_472 + pairs * 6144 * 6
    assert read("moe_gmm_roofline") == pytest.approx(
        100 * max(ops / 197e12, byts / 819e9) / 4.6e-3)
    need = cell.model.decode_step_bytes(
        PARAM_BYTES, cell.sizes(), 25.0, keys_kept=kept / steps,
        experts_hit=51.0)
    step_ms = load_reader("serve.decode_step_device_ms")(ctx)
    assert read("serve.decode_bw_share.gqa") == pytest.approx(
        100 * (1e3 * need / 819e9) / step_ms)
    # on a program without the kernels and the counters (the parent, any
    # other model): nothing, no raise
    empty = _ctx(cell, {}, records=[], trace=T.Trace({}, [], 0, 1),
                 ttft_ms_before_capture=[], capture_live_rows=None)
    for name in NEW_READERS + SHARED_READERS:
        assert load_reader(name)(empty) is None, name
    # Nemotron's summary (experts counted, no full_attend_keys) under this
    # trace: the new readers have nothing to divide
    other = _ctx(cell, {k: v for k, v in summary.items()
                        if k != "full_attend_keys"})
    for name in ("gqa_dense_attend_roofline", "serve.attend_visit_share.gqa",
                 "serve.decode_bw_share.gqa"):
        assert load_reader(name)(other) is None, name
    # a trace of another model's step (no %gqa_dense_attend)
    from test_nemotron_cell import v5e_like_trace as nemotron_trace
    theirs = _ctx(cell, summary, trace=nemotron_trace())
    for name in ("serve.full_attend_ms_per_step",
                 "gqa_dense_attend_roofline"):
        assert load_reader(name)(theirs) is None, name


def test_the_cost_functions_are_the_counts_made_by_hand():
    cell = Cell(CELL)
    sizes = cell.sizes()
    one_expert = 3 * 6144 * 2048 * 2
    got = cell.model.decode_step_bytes(
        PARAM_BYTES, sizes, 25.0, keys_kept=25 * (4000 + 4 * 128.0),
        experts_hit=51.0)
    # of the embedding only the live rows' rows; the head is whole; 13 of
    # the 64 held experts were not reached
    assert got == pytest.approx(
        PARAM_BYTES - 13 * one_expert - (19200 - 25) * 6144 * 2
        + 25 * 4512 * 4096)
    full = cell.model.decode_step_bytes(PARAM_BYTES, sizes, 32)
    assert full == pytest.approx(
        PARAM_BYTES - (19200 - 32) * 6144 * 2
        + 32 * (16384 + 4 * 128) * 4096)
    assert got < full
    # the held experts reached are over half of such a step's bytes, the
    # live rows' K and V under a tenth
    assert 0.5 < 51 * one_expert / got < 0.65
    assert 0.04 < 25 * 4512 * 4096 / got < 0.1
    assert cell.model.layer_counts(sizes) == (1, 4)
    assert cell.model.expert_layers(sizes) == 4


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
                "serve_ttft_p50_ms", "serve_tpot_p95_ms"):
            assert m["workloads"][-1] == CELL, m["name"]
    assert new["configs"][-1]["name"] == CONFIG
    assert new["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in new["per_layer"]][-4:] == list(NEW_READERS)
    after = _hashes(bench_dir)
    for path, digest in before.items():
        assert after[path] == digest, f"{path} was edited"
    assert len(after) == len(before) + len(CELL_FILES)
