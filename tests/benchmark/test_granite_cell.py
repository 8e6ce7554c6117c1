"""The cell ``granite4h-serve-chat`` (granite-4.0-h-small as one chip of an
8-chip deployment, PR 41): Mamba-2 state-space layers with a convolution
ring and a fixed-size state a slot, one NoPE grouped-query layer, 36 of 72
softmax-routed experts beside a shared one. It names its files and metrics,
its widths are the published ones and its cuts are stated, its traffic fits
its buckets at four fifths of the swept knee, it rehearses on the CPU at
its tiny widths, ``correct`` comes out false when the timed path is broken
underneath (a token altered; fp8 operands; a state never read out) and
under the lower-precision control, one token folded twice at every step
stays below what ``correct`` sees (and the test says so), every reader it
brings or shares returns a number (the program's counters on a rehearsed
run, the trace readers on a trace with the names a v5e capture shows), the
cost functions are the counts made by hand, and the cell is files and
entries over a benchmark that lacks them."""

import hashlib
import json
import os
import shutil

import pytest
from test_glm_cell import entries_added

from harness import common, decode_parts, probes, serve_runner, ssm_parts
from harness import trace as T
from harness.loader import ROOT, Cell, load_reader

CELL = "granite4h-serve-chat"
CONFIG = "granite-4.0-h-small-serve"
NEW_READERS = (
    "serve.ssm_state_ms_per_step", "serve.ssm_scan_ms_per_ktoken",
    "ssd_state_step_roofline", "ssd_chunk_scan_roofline",
    "serve.decode_bw_share.ssm", "serve.moe_experts_hit_share")
# readers that were there and read this program too
SHARED_READERS = ("serve.prefill_ms_per_ktoken",
                  "serve.moe_pairs_per_expert_step", "serve.state_live_share")
GENERIC_READERS = (
    "serve.ttft_p95_ms", "serve.queue_steps_p95", "serve.prefill_device_ms",
    "serve.decode_step_device_ms", "serve.device_idle_share",
    "serve.idle_fetch_ms_per_step", "serve.idle_launch_ms_per_step",
    "serve.idle_sched_ms_per_step", "serve.idle_admit_ms_per_admission",
    "serve.ttft_mid_wait_admit_ms", "serve.ttft_mid_wait_step_ms",
    "serve.ttft_mid_prefill_ms", "serve.tpot_tail_admit_ms",
    "serve.tpot_tail_step_ms", "serve.admit_wall_share")
CELL_FILES = (
    "configs/granite-4.0-h-small-serve.json",
    "traffic/assist-lognormal-0.8knee.json", "models/granitemoehybrid.py",
    "harness/ssm_parts.py",
) + tuple(f"metrics/{name}.py" for name in NEW_READERS)


def rehearse(fault=None, control=None, trace=False, seed=2 ** 31 + 41):
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
        CONFIG, "assist-lognormal-0.8knee", 1, "serve")
    assert cell.model.__file__.endswith("models/granitemoehybrid.py")
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_ttft_p50_ms", "serve_tpot_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert names == set(NEW_READERS + SHARED_READERS + GENERIC_READERS)
    # nothing tied to another family's kernel names or sizes: the experts'
    # device time is read through the latent family's step split, the
    # live-bytes share through its selection's counts
    assert not names & {
        "serve.moe_expert_ms_per_step", "serve.latent_attend_ms_per_step",
        "serve.decode_bw_share.live", "serve.decode_bw_share.hybrid",
        "serve.lightning_state_ms_per_step", "serve.step_ahead_share",
        "serve.index_keep_share", "serve.prefill_attend_ms_per_ktoken"}
    for other in ("glm52-serve-longctx", "axk1-serve-reasoning",
                  "sala-serve-longdoc", "gpt2l-serve-steady"):
        assert not set(NEW_READERS) & {
            m["name"] for m in Cell(other).per_layer()}
    for m in cell.per_layer():
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
            if m["name"].endswith("_roofline"):
                assert (m["unit"], m["source"], m["layer"]) == (
                    "%", "device_trace", "kernels")
    assert len(cell.entry["why"]) <= 200 and "poisson only" in \
        cell.entry["why"]


def test_the_configuration_holds_the_published_widths_and_states_the_cuts():
    cell = Cell(CELL)
    cfg, sizes = cell.config, cell.sizes()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = [json.loads(line) for line in f]
    row = next(r for r in catalog if r["name"] == "granite-4.0-h-small")
    entry = [c for c in cell.bench["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == row["source_url"]
    # every key of the catalog's config under the same name, unchanged
    # but for the four that are reduced; none of them a width
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == {"num_hidden_layers", "num_local_experts",
                      "vocab_size", "max_position_embeddings"}
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_local_experts", "vocab_size",
        "max_position_embeddings"]
    assert set(cfg["changed"]) == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"], cfg["max_position_embeddings"]) == (
        10, 36, 50176, 4096)
    assert (cfg["num_hidden_layers_published"],
            cfg["num_local_experts_published"], cfg["vocab_size_published"],
            cfg["max_position_embeddings_published"],
            cfg["first_layer_held"]) == (40, 72, 100352, 131072, 0)
    assert cfg["experts_held"] == list(range(36))
    assert set(cfg["assumed"]) >= {
        "expert_width", "expert_layout", "conv_layout", "ssm_init",
        "time_step_limit", "gated_norm", "router", "weights", "buckets"}
    assert "4 pipeline stages of 10 layers" in cfg["deployment"] \
        and "2 chips share each layer" in cfg["deployment"]
    assert cfg["bytes"] and cfg["precision"]
    assert sizes["layers"] == ("mamba",) * 5 + ("attention",) + (
        "mamba",) * 4 == tuple(row["config"]["layer_types"][:10])
    assert (sizes["router_experts"], len(sizes["experts_held"])) == (72, 36)
    assert cell.model.param_count(sizes) == 4_757_211_776
    assert cell.model.state_bytes_per_slot(sizes) == 9 * 4_194_304
    assert cell.model.conv_bytes_per_slot(sizes) == 9 * 4 * 8448 * 2
    assert cell.model.cache_bytes_per_token(sizes) == {"kv": 4096}
    slot = 9 * 4_194_304 + 9 * 4 * 8448 * 2 + 4096 * 4096 + 4
    assert slot == 55_134_212
    assert 0.8 < (64 * slot + 2 * 4_757_211_776) / 16e9 < 0.82
    # a fixed state against the K and V the same nine layers would hold
    assert 9 * 4096 * 4096 > 3.9 * 9 * 4_194_304
    # the rehearsal has every mechanism: both mixer kinds, 2 queries a
    # key-value head, 8 experts of which 4 held and 3 a token, a sliced
    # vocabulary, prompts of several chunks
    small = cell.sizes(rehearse=True)
    assert set(small["layers"]) == {"mamba", "attention"}
    assert small["num_attention_heads"] == 2 * small["num_key_value_heads"]
    assert (small["router_experts"], len(small["experts_held"]),
            small["num_experts_per_tok"]) == (8, 4, 3)
    assert cfg["rehearsal"]["sizes"]["vocab_size_published"] \
        == 2 * small["vocab_size"]
    assert cfg["rehearsal"]["traffic"]["prompt_len"]["min"] \
        > cell.model.SCAN_CHUNK


def test_the_traffic_fits_the_buckets_at_four_fifths_of_the_knee():
    cell = Cell(CELL)
    mix, serve = cell.traffic, cell.config["serve"]
    buckets = [int(b) for b in serve["buckets"].split(",")]
    assert mix["prompt_len"] == {"median": 384, "sigma": 0.9, "min": 32,
                                 "max": 3072}
    assert mix["output_len"] == {"median": 256, "sigma": 0.6, "min": 32,
                                 "max": 1024}
    assert (mix["stop_fraction"], mix["schedule_seed"], mix["kind"],
            mix["arrivals"]) == (1.0, 1, "serve_open_loop", "poisson")
    assert buckets == [256, 512, 768, 1024, 1536, 2048, 3072]
    assert all(b % cell.model.SCAN_CHUNK == 0 for b in buckets)
    assert mix["prompt_len"]["max"] == max(buckets)
    assert (mix["prompt_len"]["max"] + mix["output_len"]["max"]
            <= cell.config["max_position_embeddings"])
    assert serve["num_slots"] == 64
    # the median in the middle of the 512 bucket, not on an edge, and about
    # a third of the prompts in the first bucket
    assert 256 < 0.8 * mix["prompt_len"]["median"] \
        and 1.2 * mix["prompt_len"]["median"] < 512
    from harness import traffic
    lens = traffic.lognormal_quantiles(200, 384, 0.9, 32, 3072)
    assert 0.28 < sum(n <= 256 for n in lens) / 200 < 0.38
    assert abs(mix["rate_rps"] - 0.8 * mix["knee_rps"]) \
        <= 0.011 * mix["knee_rps"]


def test_it_rehearses_correct(sound):
    assert sound["correct"] is True and sound["failed"] == 0
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    assert sound["check"]["max"] <= limits["served_token_gap_max"]
    assert sound["check"]["mean"] <= limits["served_token_gap_mean"]
    assert sound["check"]["tokens"] > 30
    # the served tokens are not the prompt's last token repeated (a tied
    # head under N(0, 0.02) rows would serve nothing else)
    s = sound["summary"]
    assert s["decode_live_rows"] > 0 and s["state_rows_reread"] >= 0


def test_the_lower_precision_control_is_not_correct(sound):
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    control = sound["check"]["control"]
    assert control["mean"] > 2 * limits["served_token_gap_mean"]
    assert control["max"] > 2 * limits["served_token_gap_max"]


@pytest.fixture
def fresh_programs():
    """The engine keeps its compiled programs by model VALUE: a test that
    breaks the model underneath must not be handed the sound programs of
    an earlier rehearsal, nor leave its broken ones behind."""
    from tensorflow_distributed_tpu.serve import engine

    def clear():
        for name in ("_compiled_prefill", "_compiled_step",
                     "_compiled_verify"):
            getattr(engine, name).cache_clear()
    clear()
    yield
    clear()


def _fp8_operands(monkeypatch):
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models import granitemoehybrid as M
    real = M._mm

    def rounded(x, dtype):
        x = x.astype(jnp.float32)
        s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
        return ((x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                * s).astype(dtype)

    monkeypatch.setattr(M, "_mm", lambda spec, a, w, dtype: real(
        spec, rounded(a, dtype), rounded(w, dtype), dtype))


def _state_step(monkeypatch, broken):
    from tensorflow_distributed_tpu.ops import state_space as ops
    real = ops.ssd_state_step
    monkeypatch.setattr(ops, "ssd_state_step",
                        lambda S, *args, **kw: broken(real, S, *args, **kw))


def _never_read(real, S, *args, **kw):
    """A state step that moves the state and reads nothing out of it."""
    S, y = real(S, *args, **kw)
    return S, 0.0 * y


def _folded_twice(real, S, *args, **kw):
    S, _ = real(S, *args, **kw)
    return real(S, *args, **kw)


@pytest.mark.parametrize("fault", ["altered_token", "fp8_operands",
                                   "state_never_read"])
def test_a_broken_timed_path_is_not_correct(one_chip_env, fresh_programs,
                                            monkeypatch, fault):
    assert "altered_token" in probes.FAULTS
    if fault == "fp8_operands":
        _fp8_operands(monkeypatch)
    elif fault == "state_never_read":
        _state_step(monkeypatch, _never_read)
    res = rehearse(fault=fault if fault == "altered_token" else None)
    limits = Cell(CELL).config["rehearsal"]["correct_limits"]
    assert res["correct"] is False
    assert res["check"]["max"] > limits["served_token_gap_max"] \
        or res["check"]["mean"] > limits["served_token_gap_mean"]


def test_one_token_folded_twice_is_below_what_correct_sees(
        one_chip_env, fresh_programs, monkeypatch, sound):
    """EVERY decode step folding its token twice (a stamp that never
    holds) moves the served tokens' gaps, but by less than the
    lower-precision control does and not reliably past the limits: one
    token's double count is a few percent of a state that holds many.
    ``correct`` compares served tokens and is blind to it (PERF.md section
    7); what holds a state to a single fold is tests/test_granitemoehybrid
    .py, bit for bit, through the engine's drain."""
    _state_step(monkeypatch, _folded_twice)
    res = rehearse()
    control = sound["check"]["control"]
    assert res["failed"] == 0
    assert res["check"]["max"] < control["max"]
    assert res["check"]["mean"] < 0.2 * control["mean"]
    # and it is not nothing: the tokens served are not the sound run's
    assert (res["check"]["max"], res["check"]["mean"]) != (
        sound["check"]["max"], sound["check"]["mean"])


def test_the_programs_counters_reach_their_readers(sound):
    m, s = sound["metrics"], sound["summary"]
    for key in ("decode_live_rows", "state_rows_stepped",
                "state_rows_folded", "state_bytes_per_slot",
                "conv_bytes_per_slot", "attend_keys", "moe_held_pairs",
                "moe_experts_hit", "moe_layers"):
        assert s[key], key
    assert set(s["cache_bytes_per_slot_by_kind"]) == {
        "kv", "state", "conv", "state_pos"}
    assert s["state_rows_stepped"] == 3 * s["decode_live_rows"]
    assert s["state_rows_reread"] == \
        s["state_rows_stepped"] - s["state_rows_folded"]
    assert m["serve.state_live_share"]["value"] == pytest.approx(100.0)
    assert m["serve.moe_pairs_per_expert_step"]["value"] == pytest.approx(
        s["moe_held_pairs"] / (4 * 4 * s["decode_steps"]), rel=1e-4)
    assert m["serve.moe_experts_hit_share"]["value"] == pytest.approx(
        100.0 * s["moe_experts_hit"] / (4 * 4 * s["decode_steps"]))
    assert 0 < m["serve.moe_experts_hit_share"]["value"] <= 100
    for name in ("serve.ttft_p95_ms", "serve.queue_steps_p95",
                 "serve.admit_wall_share"):
        assert name in m
    # no device in a CPU capture: the trace readers find nothing
    for name in NEW_READERS:
        if name != "serve.moe_experts_hit_share":
            assert name not in m, name


def _step(start, names_us):
    ops, t = [], start
    for name, us in names_us:
        ops.append((name, t, int(us * 1e3)))
        t += int(us * 1e3) + 500
    return ops, t


def v5e_like_trace():
    """Two decode steps and one prefill with the op names a v5e capture
    of this model shows (my chip run, PR 41; the described-chip compiles
    name the same kernels: tests/test_tpu_compile.py)."""
    moe = [("%fusion.9", 40), ("%gmm.1", 300), ("%gmm.2", 300),
           ("%gmm.3", 300), ("%fusion.10", 60)]
    mamba = [("%fusion.11", 120), ("%fusion.12", 15),
             ("%ssd_state_step.12", 400), ("%fusion.13", 60)] + moe
    attend = [("%fusion.1", 60), ("%latent_row_write.2", 8),
              ("%fusion.3", 900), ("%fusion.4", 30)] + moe
    step = mamba * 5 + attend + mamba * 4 + [("%fusion.20", 300)]
    ops, modules, t = [], [], 1_000_000
    for _ in range(2):
        new, end = _step(t, step)
        ops += new
        modules.append(("jit_serve_decode_step(77)", t, end - t))
        t = end + 2_000_000
    new, end = _step(t, [("%fusion.50", 20_000)]
                     + [("%ssd_chunk_scan.51", 150)] * 9
                     + [("%mla_prefill_attend.60", 200)])
    ops += new
    modules.append(("jit_serve_prefill_b768(5)", t, end - t))
    return T.Trace({0: {"ops": ops, "async": [], "modules": modules}}, [],
                   0, end + 1000)


def test_the_kernels_are_read_by_name():
    k = ssm_parts.decode_kernels(v5e_like_trace())
    assert (k["steps"], k["state_calls"]) == (2, 18)
    assert k["state_s"] == pytest.approx(18 * 400e-6)
    seconds, found = ssm_parts.prefill_scans(v5e_like_trace())
    assert found == [(768, 9)] and seconds == pytest.approx(9 * 150e-6)
    assert ssm_parts.decode_kernels(T.Trace({}, [], 0, 1)) is None
    assert ssm_parts.prefill_scans(T.Trace({}, [], 0, 1)) == (0.0, [])
    # the SALA cell's capture runs none of these kernels
    from test_sala_cell import v5e_like_trace as sala_trace
    assert ssm_parts.decode_kernels(sala_trace()) is None
    assert ssm_parts.prefill_scans(sala_trace())[1] == []


def test_every_reader_of_the_cell_returns_a_number(sound):
    from harness import peaks
    cell = Cell(CELL)
    # the counts of a run at the cell's sizes: 32 live rows a step at a
    # mean depth of 700, 300 of the 360 held experts reached a step
    summary = dict(sound["summary"], decode_steps=1000,
                   decode_live_rows=1000 * 32,
                   state_rows_stepped=1000 * 32 * 9,
                   state_rows_folded=1000 * 32 * 9,
                   attend_keys=1000 * 32 * 700, moe_layers=10,
                   moe_experts_hit=1000 * 300,
                   conv_bytes_per_slot=608_256)
    ctx = common.Ctx(cell=cell, model=cell.model, records=[summary],
                     trace=v5e_like_trace(), sizes=cell.sizes(), slots=64,
                     param_bytes=9_514_423_552,
                     peaks=peaks.peaks_for("TPU v5 lite"), chips=1,
                     say=lambda msg: None, cut_s=1.0,
                     ttft_ms_before_capture=[1.0],
                     # the capture's own steps: 32 live rows each (a real
                     # capture says so on its token_fetch spans)
                     capture_live_rows=32.0)
    for name in NEW_READERS + SHARED_READERS:
        value = load_reader(name)(ctx)
        assert isinstance(value, float) and value > 0, name
    read = lambda name: load_reader(name)(ctx)          # noqa: E731
    assert read("serve.ssm_state_ms_per_step") == pytest.approx(9 * 0.400)
    assert read("serve.ssm_scan_ms_per_ktoken") == pytest.approx(
        9 * 0.150 / 0.768)
    # 32 rows x 4.19 MB read and written: 268 MB is 328 us at 819 GB/s;
    # the kernel took 400 us
    assert read("ssd_state_step_roofline") == pytest.approx(
        100 * (32 * 2 * 128 * 8192 * 4 / 819e9) / 400e-6)
    ops, byts = cell.model.chunk_scan_cost(cell.sizes(), 768, 256)
    assert read("ssd_chunk_scan_roofline") == pytest.approx(
        100 * max(ops / 197e12, byts / 819e9) / 150e-6)
    assert read("serve.moe_experts_hit_share") == pytest.approx(
        100 * 300 / 360)
    assert read("serve.state_live_share") == pytest.approx(100.0)
    assert 0 < read("serve.decode_bw_share.ssm") < 100
    for name in NEW_READERS:
        if name.endswith("_roofline"):
            assert read(name) < 100, name
    # on a program without the kernels and the counters (the parent, any
    # other model): nothing, no raise
    empty = common.Ctx(cell=cell, model=cell.model, records=[],
                       trace=T.Trace({}, [], 0, 1), sizes=cell.sizes(),
                       slots=64, param_bytes=1, peaks=ctx.peaks, chips=1,
                       say=lambda msg: None, cut_s=1.0,
                       ttft_ms_before_capture=[])
    for name in NEW_READERS + SHARED_READERS:
        assert load_reader(name)(empty) is None, name
    # another family's summary (SALA's: a state, no ring, no experts)
    # under this trace: the readers that divide by this program's counts
    # have nothing to divide
    other = common.Ctx(
        cell=cell, model=cell.model, trace=v5e_like_trace(),
        records=[{"event": "serve_summary", "decode_steps": 9,
                  "decode_live_rows": 9, "state_rows_stepped": 54}],
        sizes=cell.sizes(), peaks=ctx.peaks, param_bytes=1, slots=64,
        chips=1, say=lambda msg: None, capture_live_rows=8.0)
    for name in ("ssd_state_step_roofline", "serve.decode_bw_share.ssm",
                 "serve.moe_experts_hit_share"):
        assert load_reader(name)(other) is None, name


def test_the_cost_functions_are_the_counts_made_by_hand():
    cell = Cell(CELL)
    sizes, params = cell.sizes(), 9_514_423_552
    n = 128 * 64 * 128                       # one layer's state, a row
    assert cell.model.state_numbers(sizes) == n == 1_048_576
    assert cell.model.state_step_cost(sizes, 35.0) == (
        6.0 * n * 35, 8.0 * n * 35)
    ops, byts = cell.model.chunk_scan_cost(sizes, 1024, 256)
    # a token a head: 256 x 64 decayed scores times dt x, the carried
    # state read and updated (2 x 128 x 64); C B^T once a token
    assert ops == 2.0 * 1024 * (128 * (256 * 64 + 2 * 128 * 64)
                                + 256 * 128)
    # x, B, C in bfloat16, y in float32, 12 B of decays a head; the last
    # state in float32
    assert byts == 1024 * (8192 * 6 + 512 + 128 * 12) + 4 * n
    state = 9 * 4 * n
    ring = 9 * 4 * 8448 * 2
    one_expert = 3 * 4096 * 768 * 2
    got = cell.model.decode_step_bytes(
        params, sizes, 35.0, keys_kept=35 * 700.0, experts_hit=300.0)
    assert got == pytest.approx(params - 60 * one_expert + 2 * 35 * state
                                + 35 * ring + 35 * 700 * 4096)
    full = cell.model.decode_step_bytes(params, sizes, 64)
    assert full == pytest.approx(params + 2 * 64 * state + 64 * ring
                                 + 64 * 4096 * 4096)
    assert got < full
    # the states are a fifth of such a step's bytes (SALA's: 7%)
    assert 0.15 < 2 * 35 * state / got < 0.30
    assert cell.model.layer_counts(sizes) == (9, 1)


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
    assert [m["name"] for m in new["per_layer"]][-6:] == list(NEW_READERS)
    after = _hashes(bench_dir)
    for path, digest in before.items():
        assert after[path] == digest, f"{path} was edited"
    assert len(after) == len(before) + len(CELL_FILES)
