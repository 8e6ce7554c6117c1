"""The two readers PR 48 brought for the dense slot engine's decode attends
(``ops/kv_attend.py``): ``serve.attend_visit_share.kv`` divides the
program's own counts (``serve_summary.kv_attend_positions_seen /
kv_attend_positions_visited``), ``serve.kv_attend_ms_per_step`` sums
``%kv_decode_attend`` inside the decode program's executions of a trace;
both read nothing on a program that lacks what they read (the parent of
the PR that added them, another family); ``gpt2l-serve-steady`` lists
them and no other cell does; and the two are two files and two appended
entries over a benchmark that lacks them. (The real engine's counts are
held by ``tests/test_kv_attend.py``. Like ``test_ttft_admit_first_share``
this module rehearses no cell and its name sorts after every module that
does: a rehearsal writes under the cell's name in ``.cache/perfbench/``.)"""

import json
import os
import types

import pytest
from test_glm_cell import _hashes, entries_added
from test_kexaone_cell import _step

from harness import trace as T
from harness.loader import Cell, load_benchmark, load_reader

SHARE, KERNEL_MS = "serve.attend_visit_share.kv", "serve.kv_attend_ms_per_step"
CELL = "gpt2l-serve-steady"
# by name: tests/conftest.py shows this module the benchmark as this PR left
# it, these two standing last
ENTRIES = {
    SHARE: {"name": SHARE, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "serve engine",
            "moves": "serve_tpot_p95_ms", "workloads": [CELL]},
    KERNEL_MS: {"name": KERNEL_MS, "unit": "ms", "better": "lower",
                "source": "device_trace", "layer": "kernels",
                "moves": "serve_tpot_p95_ms", "workloads": [CELL]}}


def _ctx(trace=None, **summary):
    return types.SimpleNamespace(
        trace=trace, records=[{"event": "serve_request"},
                              {"event": "serve_summary", **summary}])


@pytest.mark.parametrize("summary, want", [
    # 36 layers, 4.8 live rows 244 deep a launch in 512-position blocks
    ({"kv_attend_positions_visited": 36 * 2560 * 3400,
      "kv_attend_positions_seen": 36 * 1176 * 3400}, 100 * 1176 / 2560),
    # attends that stop at each row's depth
    ({"kv_attend_positions_visited": 7200,
      "kv_attend_positions_seen": 7200}, 100.0),
    # a leaf the kernel does not take: every slot's whole row a launch
    ({"kv_attend_positions_visited": 2 * 4 * 64 * 100,
      "kv_attend_positions_seen": 2 * 21 * 100}, 100 * 21 / 256),
    ({"decode_steps": 3400}, None),                     # the parent
    # nothing launched
    ({"kv_attend_positions_visited": 0,
      "kv_attend_positions_seen": 0}, None),
    # another family's counters (exaone_moe's) are not this metric's
    ({"attend_positions_visited": 900, "select_keys_kept": 300}, None),
], ids=["steady", "exact", "slot_blind", "parent", "no_launch",
        "other_family"])
def test_share_divides_the_programs_own_counts(summary, want):
    got = load_reader(SHARE)(_ctx(**summary))
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("name", [SHARE, KERNEL_MS])
def test_readers_without_a_summary_or_a_trace_read_nothing(name):
    assert load_reader(name)(types.SimpleNamespace(
        records=[], trace=None)) is None


def v5e_like_trace(kernel="%kv_decode_attend"):
    """Two decode steps and a prefill with the op names a v5e capture of
    the GPT-2-large step shows (tests/test_tpu_compile.py names the same
    custom calls): a layer is fusions around two ``%kv_token_write`` and
    one attend; the prefill has no such kernel."""
    layer = [("%fusion.1", 130), ("%kv_token_write.2", 16),
             ("%kv_token_write.3", 16), (kernel + ".4", 30),
             ("%fusion.5", 30)]
    ops, modules, t = [], [], 1_000_000
    for _ in range(2):
        new, end = _step(t, layer * 36)
        ops += new
        modules.append(("jit_serve_decode_step(77)", t, end - t))
        t = end + 500_000
    new, end = _step(t, [("%fusion.50", 5_800), (kernel + ".9", 30)])
    ops += new
    modules.append(("jit_serve_prefill_b256(5)", t, end - t))
    return T.Trace({0: {"ops": ops, "async": [], "modules": modules}}, [],
                   0, end + 1000)


def test_kernel_ms_is_the_kernels_time_a_decode_step():
    read = load_reader(KERNEL_MS)
    # 36 calls of 30 us in each of the two steps; the prefill's op of the
    # same name is outside the decode program
    assert read(_ctx(v5e_like_trace())) == pytest.approx(36 * 0.030)
    # the parent's step: anonymous reductions, no such kernel
    assert read(_ctx(v5e_like_trace("%reduce_fusion"))) is None
    # K-EXAONE's kernel is another metric's
    assert read(_ctx(v5e_like_trace("%gqa_dense_attend"))) is None
    assert load_reader("serve.full_attend_ms_per_step")(
        _ctx(v5e_like_trace())) is None
    assert read(_ctx(T.Trace({}, [], 0, 1))) is None


def test_the_steady_cell_lists_both_and_they_stand_last():
    bench = load_benchmark()
    assert bench["per_layer"][-2:] == list(ENTRIES.values())
    for w in bench["workloads"]:
        names = {m["name"] for m in Cell(w["name"]).per_layer()}
        assert ({SHARE, KERNEL_MS} <= names) == (w["name"] == CELL), w["name"]
        assert ({SHARE, KERNEL_MS} & names) == (
            {SHARE, KERNEL_MS} if w["name"] == CELL else set()), w["name"]
    assert ENTRIES[SHARE]["moves"] in {
        m["name"] for m in Cell(CELL).end_to_end()}


def test_the_metrics_are_two_files_and_two_entries_and_edit_no_file(
        benchmark_copy):
    """Taken OUT of a copy of the benchmark (their readers, their
    entries), the steady cell loads and names every other reader; added
    again as a ``perf_opt`` PR adds them, ``BENCHMARK.json`` differs by
    two appended ``per_layer`` entries and every file the copy had has the
    hash it had."""
    root = benchmark_copy
    bench_dir = os.path.join(root, "perfbench")
    sources = {}
    for name in (SHARE, KERNEL_MS):
        path = os.path.join(bench_dir, "metrics", name + ".py")
        with open(path) as f:
            sources[path] = f.read()
        os.remove(path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        full = json.load(f)
    without = json.loads(json.dumps(full))
    without["per_layer"] = [m for m in full["per_layer"]
                            if m["name"] not in (SHARE, KERNEL_MS)]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(without, f)
    before = _hashes(bench_dir)
    names = [m["name"] for m in Cell(CELL, root=root).per_layer()]
    assert not {SHARE, KERNEL_MS} & set(names)
    assert "serve.decode_bw_share" in names
    for name in names:
        assert load_reader(name, root=root) is not None
    for path, source in sources.items():
        with open(path, "w") as f:
            f.write(source)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(full, f)
    assert entries_added(without, full, []) == {
        "configs": [], "workloads": [], "end_to_end": [],
        "per_layer": [SHARE, KERNEL_MS]}
    assert Cell(CELL, root=root).per_layer()[-2:] == list(ENTRIES.values())
    after = _hashes(bench_dir)
    for path, digest in before.items():
        assert after[path] == digest, f"{path} was edited"
    assert len(after) == len(before) + 2
