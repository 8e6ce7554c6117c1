"""The reader of ``serve.moe_combine_ms_per_ktoken`` (PR 44): device ms of
the held experts' combine (``%moe_combine_held.N``, one call a block trip
of every expert layer) inside the ``jit_serve_prefill_b<bucket>`` module
events of a capture, per 1,000 bucket tokens of the prefills that ran it;
nothing where the combine is anonymous gathers (the parent of the PR that
added the kernel) or the one-hot matmuls (the shortest bucket); the four
cells that route to held experts list it and no other, and the metric is
one file and one appended entry over a benchmark that lacks them."""

import json
import os
import types

import pytest
from test_glm_cell import _hashes, entries_added

from harness import trace as T
from harness.loader import Cell, load_benchmark, load_reader

NAME = "serve.moe_combine_ms_per_ktoken"
CELL = "nemotron3s-serve-agentic"
CELLS = ["glm52-serve-longctx", "axk1-serve-reasoning",
         "granite4h-serve-chat", CELL]
ENTRY = {"name": NAME, "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "kernels",
         "moves": "serve_ttft_p50_ms", "workloads": CELLS}
# as a v5e capture names it (my chip run, PR 44)
KERNEL = "%moe_combine_held.{} tpu_custom_call f32[{},1024]"
MS = 1_000_000                                           # ns


def _trace(prefills, decode_steps=2):
    """One device: each of ``prefills`` = (bucket, [[kernel ms a trip] a
    layer] or None for a program without the kernel) as a
    ``jit_serve_prefill_b<bucket>`` module event holding the layers'
    ``%gmm`` and fusions with the combine after each block's; decode
    steps between them (the one-hot branch: no combine kernel)."""
    ops, modules, t, n = [], [], 1_000, 0
    for bucket, layers in prefills:
        start = t
        for trips in layers or [[None]] * 5:
            for ms in trips:
                ops.append((f"%gmm.{n} tpu_custom_call f32[8192,2688]", t,
                            MS))
                t += MS
                n += 1
                name = (f"%fusion.{n}" if ms is None
                        else KERNEL.format(n, bucket))
                ops.append((name, t, int((ms or 3.0) * MS)))
                t += int((ms or 3.0) * MS)
        modules.append((f"jit_serve_prefill_b{bucket}(1234)", start,
                        t - start))
        t += 5_000
        for _ in range(decode_steps):
            ops.append((f"%gmm.{n} tpu_custom_call f32[2816,2688]", t,
                        MS // 3))
            modules.append(("jit_serve_decode_step(99)", t, MS // 3))
            t += MS // 3 + 5_000
    dev = {"ops": ops, "async": [], "modules": modules}
    return T.Trace({0: dev}, [], 1_000, t)


@pytest.mark.parametrize("prefills, want", [
    # five layers, one trip of 0.08 ms each at the 1,024 bucket
    ([(1024, [[0.08]] * 5)], 1e3 * 0.4 / 1024),
    # three trips a layer at 4,096: every trip's call counts
    ([(4096, [[0.1, 0.1, 0.11]] * 5)], 1e3 * 1.55 / 4096),
    # two buckets: the kernel's ms over the tokens of both
    ([(1024, [[0.08]] * 5), (2048, [[0.08, 0.07]] * 5)],
     1e3 * 1.15 / 3072),
    # the one-hot bucket runs no combine and stays out of the tokens too
    ([(1024, [[0.08]] * 5), (256, None)], 1e3 * 0.4 / 1024),
    ([(1024, None), (4096, None)], None),               # the parent
    ([], None),                                         # no prefill caught
], ids=["one_trip", "three_trips", "two_buckets", "a_one_hot_bucket",
        "parent", "no_prefill"])
def test_reader_sums_the_named_calls_inside_the_prefill_events(prefills,
                                                               want):
    got = load_reader(NAME)(types.SimpleNamespace(trace=_trace(prefills)))
    assert got == (want if want is None else pytest.approx(want))


def test_reader_without_a_trace_reads_nothing():
    assert load_reader(NAME)(types.SimpleNamespace(trace=None)) is None


def test_a_call_outside_every_prefill_event_is_not_counted():
    """A combine OUTSIDE every prefill module event (a warm-up's tail
    caught by the capture's edge) counts for nothing."""
    tr = _trace([(2048, [[0.08, 0.07]] * 5)])
    tr.devices[0]["ops"].append(
        (KERNEL.format(99, 2048), tr.end_ns + 10, 3 * MS))
    got = load_reader(NAME)(types.SimpleNamespace(trace=tr))
    assert got == pytest.approx(1e3 * 0.75 / 2048)


def test_the_routed_cells_alone_list_it_and_the_entry_stands_last():
    """The cells whose prefill runs ``held_experts``' gathered branch
    (the latent family's two, granite, Nemotron) report the end-to-end
    metric it moves; SALA's and GPT-2's models route to no held expert."""
    bench = load_benchmark()
    assert bench["per_layer"][-1] == ENTRY
    for w in bench["workloads"]:
        names = {m["name"] for m in Cell(w["name"]).per_layer()}
        assert (NAME in names) == (w["name"] in CELLS), w["name"]
    for cell in CELLS:
        assert ENTRY["moves"] in {m["name"]
                                  for m in Cell(cell).end_to_end()}


def test_the_metric_is_one_file_and_one_entry_and_edits_no_file(
        benchmark_copy):
    """Taken OUT of a copy of the benchmark (its reader, its entry), the
    Nemotron cell loads and names every other reader; added again as a
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
    assert NAME not in names and "serve.prefill_ms_per_ktoken" in names
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
