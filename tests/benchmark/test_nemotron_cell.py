"""The cell ``nemotron3s-serve-agentic`` (Nemotron 3 Super as one chip of a
32-chip deployment, PR 43): single-part layers, Mamba-2 with 8 groups of B
and C, one NoPE grouped-query layer, 128 of 512 sigmoid-routed ungated
experts in a 1,024 latent beside a shared expert. It names its files and
metrics, its widths are the published ones and its cuts are stated, its
traffic fits its buckets at four fifths of the swept knee, it rehearses on
the CPU at its tiny widths, ``correct`` comes out false when one mechanism
of the timed path is broken underneath (a token altered; fp8 operands; a
state never read out; the held experts' routed part left out; group 0's B
and C read by every head) and under the lower-precision control, every
reader it brings or shares returns a number (the program's counters on a
rehearsed run, the trace readers on a trace with the names a v5e capture
shows), the cost functions are the counts made by hand, and the cell is
files and entries over a benchmark that lacks them."""

import hashlib
import importlib.util
import json
import os
import shutil

import pytest
from test_glm_cell import entries_added

from harness import common, decode_parts, nemotron_parts, probes
from harness import serve_runner, ssm_parts
from harness import trace as T
from harness.loader import ROOT, Cell, load_reader

CELL = "nemotron3s-serve-agentic"
CONFIG = "nemotron-3-super-serve"
NEW_READERS = ("serve.moe_gmm_ms_per_step", "moe_gmm_roofline",
               "serve.moe_held_pair_share")
# readers that were there and read this program too
SHARED_READERS = (
    "serve.prefill_ms_per_ktoken", "serve.moe_pairs_per_expert_step",
    "serve.moe_experts_hit_share", "serve.state_live_share",
    "serve.ssm_state_ms_per_step", "ssd_state_step_roofline",
    "serve.ssm_scan_ms_per_ktoken", "ssd_chunk_scan_roofline",
    "serve.decode_bw_share.ssm")
GENERIC_READERS = (
    "serve.ttft_p95_ms", "serve.queue_steps_p95", "serve.prefill_device_ms",
    "serve.decode_step_device_ms", "serve.device_idle_share",
    "serve.idle_fetch_ms_per_step", "serve.idle_launch_ms_per_step",
    "serve.idle_sched_ms_per_step", "serve.idle_admit_ms_per_admission",
    "serve.ttft_mid_wait_admit_ms", "serve.ttft_mid_wait_step_ms",
    "serve.ttft_mid_prefill_ms", "serve.tpot_tail_admit_ms",
    "serve.tpot_tail_step_ms", "serve.admit_wall_share")
CELL_FILES = (
    "configs/nemotron-3-super-serve.json",
    "traffic/agentic-lognormal-0.8knee.json", "models/nemotron_h.py",
    "harness/nemotron_parts.py", "tools/nemotron_controls.py",
) + tuple(f"metrics/{name}.py" for name in NEW_READERS)


def _controls():
    spec = importlib.util.spec_from_file_location(
        "nemotron_controls",
        os.path.join(ROOT, "perfbench", "tools", "nemotron_controls.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def rehearse(fault=None, control=None, trace=False, seed=2 ** 31 + 43):
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
        CONFIG, "agentic-lognormal-0.8knee", 1, "serve")
    assert cell.model.__file__.endswith("models/nemotron_h.py")
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_ttft_p50_ms", "serve_tpot_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert names == set(NEW_READERS + SHARED_READERS + GENERIC_READERS)
    # nothing tied to the latent family's attention kernels or another
    # family's sizes
    assert not names & {
        "serve.moe_expert_ms_per_step", "serve.latent_attend_ms_per_step",
        "serve.decode_bw_share.live", "serve.decode_bw_share.hybrid",
        "serve.lightning_state_ms_per_step", "serve.step_ahead_share",
        "serve.index_keep_share", "serve.prefill_attend_ms_per_ktoken"}
    for other in ("glm52-serve-longctx", "axk1-serve-reasoning",
                  "sala-serve-longdoc", "granite4h-serve-chat",
                  "gpt2l-serve-steady"):
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
    assert len(why) <= 200 and "quarter" in why and "4x" in why
    bench = cell.bench
    assert len(bench["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_configuration_holds_the_published_widths_and_states_the_cuts():
    cell = Cell(CELL)
    cfg, sizes = cell.config, cell.sizes()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = [json.loads(line) for line in f]
    row = next(r for r in catalog
               if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    entry = [c for c in cell.bench["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == row["source_url"]
    # every key of the catalog's config under the same name, unchanged
    # but for the five that are reduced; none of them a width
    differ = {k for k, v in row["config"].items() if cfg.get(k, "∅") != v}
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size",
               "max_position_embeddings", "num_nextn_predict_layers"]
    assert differ == set(reduced)
    assert cfg["reduced"] == entry["reduced"] == reduced
    assert set(cfg["changed"]) == set(reduced)
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in reduced)
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["max_position_embeddings"],
            cfg["num_nextn_predict_layers"]) == (11, 128, 32768, 6144, 0)
    assert (cfg["num_hidden_layers_published"],
            cfg["n_routed_experts_published"], cfg["vocab_size_published"],
            cfg["max_position_embeddings_published"],
            cfg["num_nextn_predict_layers_published"],
            cfg["first_layer_held"]) == (88, 512, 131072, 262144, 1, 0)
    assert cfg["experts_held"] == list(range(128))
    assert cfg["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert set(cfg["assumed"]) >= {
        "attention", "latent", "router", "conv_layout", "ssm_init",
        "time_step_limit", "gated_norm", "scan_chunk", "residual",
        "weights", "buckets"}
    assert "8 pipeline stages of 11 layers" in cfg["deployment"] \
        and "4 chips share each layer" in cfg["deployment"]
    assert cfg["bytes"] and cfg["precision"] and cfg["correct_limits_why"]
    assert sizes["layers"] == ("mamba", "moe") * 3 + (
        "mamba", "attention", "moe", "mamba", "moe")
    # the published ratio of the 88 layers, exactly
    pattern = row["config"]["hybrid_override_pattern"]
    assert [pattern.count(c) for c in "ME*"] == [40, 40, 8]
    assert [cfg["hybrid_override_pattern"][:11].count(c)
            for c in "ME*"] == [5, 5, 1]
    assert (sizes["router_experts"], len(sizes["experts_held"])) == (512,
                                                                     128)
    assert cell.model.param_count(sizes) == 4_648_163_712
    assert cell.model.param_bytes(sizes) == 9_296_332_544
    assert cell.model.state_bytes_per_slot(sizes) == 5 * 4_194_304
    assert cell.model.conv_bytes_per_slot(sizes) == 5 * 4 * 10240 * 2
    assert cell.model.cache_bytes_per_token(sizes) == {"kv": 1024}
    assert cell.model.expert_bytes(sizes) == 11_010_048
    slot = 5 * 4_194_304 + 5 * 4 * 10240 * 2 + 1024 * 6144 + 4
    assert slot == 27_672_580
    slots = cfg["serve"]["num_slots"]
    assert slots in (128, 96, 64) and cfg["serve"]["num_slots_why"]
    # the fullest device holds well over a quarter of 16 GB
    assert (slots * slot + 9_296_332_544) / 16e9 > 0.68
    # the whole model by the same count is the published 120B
    whole = dict(cfg, num_hidden_layers=88, n_routed_experts=512,
                 experts_held=list(range(512)), vocab_size=131072)
    assert 120.0e9 < cell.model.param_count(cell.model.sizes(whole)) \
        < 121.0e9
    # the rehearsal has every mechanism: the three kinds of layer, 2 groups
    # of B and C, 16 experts of which 4 held and 3 a token, a latent
    # narrower than the hidden size, a sliced vocabulary, prompts of
    # several chunks
    small = cell.sizes(rehearse=True)
    assert set(small["layers"]) == {"mamba", "attention", "moe"}
    assert small["n_groups"] == 2 and small["mamba_num_heads"] % 2 == 0
    assert small["moe_latent_size"] < small["hidden_size"]
    assert (small["router_experts"], len(small["experts_held"]),
            small["num_experts_per_tok"]) == (16, 4, 3)
    assert cfg["rehearsal"]["sizes"]["vocab_size_published"] \
        == 2 * small["vocab_size"]
    assert cfg["rehearsal"]["traffic"]["prompt_len"]["min"] \
        > cell.model.SCAN_CHUNK


def test_the_traffic_fits_the_buckets_at_four_fifths_of_the_knee():
    cell = Cell(CELL)
    mix, serve = cell.traffic, cell.config["serve"]
    buckets = [int(b) for b in serve["buckets"].split(",")]
    assert mix["prompt_len"] == {"median": 896, "sigma": 0.8, "min": 128,
                                 "max": 4096}
    assert mix["output_len"] == {"median": 512, "sigma": 0.6, "min": 64,
                                 "max": 1536}
    assert (mix["stop_fraction"], mix["schedule_seed"], mix["kind"],
            mix["arrivals"]) == (0.75, 1, "serve_open_loop", "poisson")
    assert buckets == [256, 512, 768, 1024, 1536, 2048, 3072, 4096]
    assert all(b % cell.model.SCAN_CHUNK == 0 for b in buckets)
    assert mix["prompt_len"]["max"] == max(buckets)
    assert (mix["prompt_len"]["max"] + mix["output_len"]["max"]
            <= cell.config["max_position_embeddings"])
    # the median inside the 1,024 bucket, not on an edge
    assert 768 < mix["prompt_len"]["median"] < 1024
    assert abs(mix["rate_rps"] - 0.8 * mix["knee_rps"]) \
        <= 0.011 * mix["knee_rps"]
    assert mix["knee_why"] and mix["why"]


def test_it_rehearses_correct(sound):
    assert sound["correct"] is True and sound["failed"] == 0
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    assert sound["check"]["max"] <= limits["served_token_gap_max"]
    assert sound["check"]["mean"] <= limits["served_token_gap_mean"]
    assert sound["check"]["tokens"] > 30
    s = sound["summary"]
    assert s["decode_live_rows"] > 0 and s["state_rows_reread"] >= 0


def test_the_lower_precision_control_is_not_correct(sound):
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    control = sound["check"]["control"]
    assert control["mean"] > 2 * limits["served_token_gap_mean"]
    assert control["max"] > 2 * limits["served_token_gap_max"]


@pytest.mark.parametrize("fault", [
    "altered_token", "fp8_operands", "state_never_read",
    "routed_part_left_out", "group0_for_every_head"])
def test_a_broken_timed_path_is_not_correct(one_chip_env, fault):
    """One mechanism of the PROGRAM broken underneath
    (``tools/nemotron_controls.py::broken``; a token altered: the probes'
    own fault), the cell rehearsed: ``correct`` is false, by at least one
    of the cell's limits."""
    controls = _controls()
    assert "altered_token" in probes.FAULTS
    assert set(controls.BREAKS) == {
        "fp8_operands", "state_never_read", "routed_part_left_out",
        "group0_for_every_head"}
    with controls.broken(None if fault == "altered_token" else fault):
        res = rehearse(fault=fault if fault == "altered_token" else None)
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    assert res["correct"] is False
    assert res["check"]["max"] > limits["served_token_gap_max"] \
        or res["check"]["mean"] > limits["served_token_gap_mean"]


def test_the_programs_counters_reach_their_readers(sound):
    m, s = sound["metrics"], sound["summary"]
    for key in ("decode_live_rows", "state_rows_stepped",
                "state_rows_folded", "state_bytes_per_slot",
                "conv_bytes_per_slot", "attend_keys", "moe_held_pairs",
                "moe_pairs_routed", "moe_experts_hit", "moe_layers"):
        assert s[key], key
    # only the sequence mixers keep anything
    assert set(s["cache_bytes_per_slot_by_kind"]) == {
        "kv", "state", "conv", "state_pos"}
    assert s["state_rows_stepped"] == 2 * s["decode_live_rows"]
    assert s["moe_pairs_routed"] == s["decode_live_rows"] * 3 * 2
    assert m["serve.state_live_share"]["value"] == pytest.approx(100.0)
    assert m["serve.moe_pairs_per_expert_step"]["value"] == pytest.approx(
        s["moe_held_pairs"] / (4 * 2 * s["decode_steps"]), rel=1e-4)
    assert m["serve.moe_experts_hit_share"]["value"] == pytest.approx(
        100.0 * s["moe_experts_hit"] / (4 * 2 * s["decode_steps"]))
    assert m["serve.moe_held_pair_share"]["value"] == pytest.approx(
        100.0 * s["moe_held_pairs"] / s["moe_pairs_routed"])
    # 4 of 16 held: a quarter of the pairs by the configuration, and what
    # the seeded router really sends is near it
    assert 10 < m["serve.moe_held_pair_share"]["value"] < 45
    for name in ("serve.ttft_p95_ms", "serve.queue_steps_p95",
                 "serve.admit_wall_share"):
        assert name in m
    # no device in a CPU capture: the trace readers find nothing
    for name in NEW_READERS + SHARED_READERS:
        if name not in ("serve.moe_held_pair_share",
                        "serve.moe_pairs_per_expert_step",
                        "serve.moe_experts_hit_share",
                        "serve.state_live_share"):
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
    tests/test_tpu_compile.py): an expert layer is two grouped matmuls
    between anonymous fusions and sorts, a state-space layer one state
    step."""
    moe = [("%fusion.9", 40), ("%sort.3", 30), ("%gmm.1", 500),
           ("%gmm.2", 450), ("%fusion.10", 60)]
    mamba = [("%fusion.11", 120), ("%fusion.12", 15),
             ("%ssd_state_step.12", 300), ("%fusion.13", 60)]
    attend = [("%fusion.1", 60), ("%latent_row_write.2", 8),
              ("%fusion.3", 300), ("%fusion.4", 30)]
    step = (mamba + moe) * 3 + mamba + attend + moe + mamba + moe \
        + [("%fusion.20", 300)]
    ops, modules, t = [], [], 1_000_000
    for _ in range(2):
        new, end = _step(t, step)
        ops += new
        modules.append(("jit_serve_decode_step(77)", t, end - t))
        t = end + 2_000_000
    new, end = _step(t, [("%fusion.50", 20_000)]
                     + [("%ssd_chunk_scan.51", 400)] * 5
                     + [("%gmm.52", 900)] * 10
                     + [("%mla_prefill_attend.60", 200)])
    ops += new
    modules.append(("jit_serve_prefill_b1024(5)", t, end - t))
    return T.Trace({0: {"ops": ops, "async": [], "modules": modules}}, [],
                   0, end + 1000)


def test_the_kernels_are_read_by_name():
    k = nemotron_parts.decode_gmm(v5e_like_trace())
    # the prefill's grouped matmuls are not a decode step's
    assert (k["steps"], k["gmm_calls"]) == (2, 20)
    assert k["gmm_s"] == pytest.approx(2 * 5 * 950e-6)
    s = ssm_parts.decode_kernels(v5e_like_trace())
    assert (s["steps"], s["state_calls"]) == (2, 10)
    seconds, found = ssm_parts.prefill_scans(v5e_like_trace())
    assert found == [(1024, 5)] and seconds == pytest.approx(5 * 400e-6)
    assert nemotron_parts.decode_gmm(T.Trace({}, [], 0, 1)) is None
    assert nemotron_parts.decode_gmm(None) is None
    # a decode step whose experts left megablox (or a model without
    # experts: the SALA cell's capture) has no %gmm to read
    from test_sala_cell import v5e_like_trace as sala_trace
    assert nemotron_parts.decode_gmm(sala_trace()) is None


def _ctx(cell, summary, **kw):
    from harness import peaks
    base = dict(cell=cell, model=cell.model, records=[summary],
                trace=v5e_like_trace(), sizes=cell.sizes(), slots=128,
                param_bytes=9_296_332_544,
                peaks=peaks.peaks_for("TPU v5 lite"), chips=1,
                say=lambda msg: None, cut_s=1.0,
                ttft_ms_before_capture=[1.0], capture_live_rows=50.0)
    base.update(kw)
    return common.Ctx(**base)


def test_every_reader_of_the_cell_returns_a_number(sound):
    cell = Cell(CELL)
    # the counts of a run at the cell's sizes: 50 live rows a step at a
    # mean depth of 1,200, each routing 22 pairs in 5 layers of which a
    # quarter land here, 570 of the 640 held experts reached a step
    steps, live = 1000, 1000 * 50
    summary = dict(sound["summary"], decode_steps=steps,
                   decode_live_rows=live, state_rows_stepped=live * 5,
                   state_rows_folded=live * 5, attend_keys=live * 1200,
                   moe_layers=5, moe_pairs_routed=live * 22 * 5,
                   moe_held_pairs=live * 22 * 5 // 4,
                   moe_experts_hit=steps * 570,
                   conv_bytes_per_slot=409_600)
    ctx = _ctx(cell, summary)
    for name in NEW_READERS + SHARED_READERS:
        value = load_reader(name)(ctx)
        assert isinstance(value, float) and value > 0, name
    read = lambda name: load_reader(name)(ctx)          # noqa: E731
    assert read("serve.moe_gmm_ms_per_step") == pytest.approx(5 * 0.950)
    assert read("serve.moe_held_pair_share") == pytest.approx(25.0)
    assert read("serve.ssm_state_ms_per_step") == pytest.approx(5 * 0.300)
    # the capture's steps had the run's mean rows: 1,375 held pairs on 570
    # experts: 6.28 GB is 7.66 ms at 819 GB/s (bytes bound: the operations
    # are 0.08 ms); the kernels took 4.75 ms a step in this made-up trace
    pairs = 50 * 22 * 5 / 4
    ops, byts = cell.model.expert_step_cost(cell.sizes(), pairs, 570.0)
    assert ops == 4.0 * 1024 * 2688 * pairs
    assert byts == 570 * 11_010_048 + pairs * 1024 * 6
    assert read("moe_gmm_roofline") == pytest.approx(
        100 * max(ops / 197e12, byts / 819e9) / 4.75e-3)
    # a capture with fewer live rows than the run's mean reaches fewer
    # experts and lands fewer pairs: the least time falls with both
    fewer = nemotron_parts.step_counts(
        _ctx(cell, summary, capture_live_rows=25.0))
    same = nemotron_parts.step_counts(ctx)
    assert same["experts_hit"] == pytest.approx(570.0)
    assert same["held_pairs"] == pytest.approx(pairs)
    assert fewer["held_pairs"] == pytest.approx(pairs / 2)
    miss = 1 - 22 / 512
    assert fewer["experts_hit"] == pytest.approx(
        570.0 * (1 - miss ** 25) / (1 - miss ** 50))
    # 50 rows x 4.19 MB read and written: 419 MB is 512 us at 819 GB/s;
    # the kernel took 300 us in this made-up trace, so only its form holds
    assert read("ssd_state_step_roofline") == pytest.approx(
        100 * (50 * 2 * 128 * 8192 * 4 / 819e9) / 300e-6)
    ops, byts = cell.model.chunk_scan_cost(cell.sizes(), 1024, 256)
    assert read("ssd_chunk_scan_roofline") == pytest.approx(
        100 * max(ops / 197e12, byts / 819e9) / 400e-6)
    assert read("serve.moe_experts_hit_share") == pytest.approx(
        100 * 570 / 640)
    assert read("serve.state_live_share") == pytest.approx(100.0)
    assert read("serve.decode_bw_share.ssm") > 0
    # on a program without the kernels and the counters (the parent, any
    # other model): nothing, no raise
    empty = _ctx(cell, {}, records=[], trace=T.Trace({}, [], 0, 1),
                 ttft_ms_before_capture=[], capture_live_rows=None)
    for name in NEW_READERS + SHARED_READERS:
        assert load_reader(name)(empty) is None, name
    # granite's summary (experts counted, no moe_pairs_routed) under this
    # trace: the new readers that divide by this program's counts have
    # nothing to divide
    other = _ctx(cell, dict(summary, moe_pairs_routed=None))
    for name in ("moe_gmm_roofline", "serve.moe_held_pair_share"):
        assert load_reader(name)(other) is None, name


def test_the_cost_functions_are_the_counts_made_by_hand():
    cell = Cell(CELL)
    sizes, params = cell.sizes(), 9_296_332_544
    n = 128 * 64 * 128                       # one layer's state, a row
    assert cell.model.state_numbers(sizes) == n == 1_048_576
    assert cell.model.state_step_cost(sizes, 50.0) == (
        6.0 * n * 50, 8.0 * n * 50)
    ops, byts = cell.model.chunk_scan_cost(sizes, 1024, 256)
    # a token a head: 256 x 64 decayed scores times dt x, the carried
    # state read and updated (2 x 128 x 64); C B^T once a token a GROUP
    assert ops == 2.0 * 1024 * (128 * (256 * 64 + 2 * 128 * 64)
                                + 8 * 256 * 128)
    # x in bfloat16 and y in float32, B and C of 8 groups in bfloat16, 12 B
    # of decays a head; the last state in float32
    assert byts == 1024 * (8192 * 6 + 2 * 8 * 128 * 2 + 128 * 12) + 4 * n
    state = 5 * 4 * n
    ring = 5 * 4 * 10240 * 2
    one_expert = 2 * 1024 * 2688 * 2
    got = cell.model.decode_step_bytes(
        params, sizes, 50.0, keys_kept=50 * 1200.0, experts_hit=570.0)
    # of the embedding only the live rows' rows; the head is whole
    assert got == pytest.approx(
        params - 70 * one_expert - (32768 - 50) * 4096 * 2
        + 2 * 50 * state + 50 * ring + 50 * 1200 * 1024)
    full = cell.model.decode_step_bytes(params, sizes, 128)
    assert full == pytest.approx(
        params - (32768 - 128) * 4096 * 2 + 2 * 128 * state + 128 * ring
        + 128 * 6144 * 1024)
    assert got < full
    # the held experts reached are over half of such a step's bytes, the
    # states a sixth
    assert 0.5 < 570 * one_expert / got < 0.65
    assert 0.12 < 2 * 50 * state / got < 0.22
    assert cell.model.layer_counts(sizes) == (5, 1)
    assert cell.model.expert_layers(sizes) == 5


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
    assert [m["name"] for m in new["per_layer"]][-3:] == list(NEW_READERS)
    after = _hashes(bench_dir)
    for path, digest in before.items():
        assert after[path] == digest, f"{path} was edited"
    assert len(after) == len(before) + len(CELL_FILES)
