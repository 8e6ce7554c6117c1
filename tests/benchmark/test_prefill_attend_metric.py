"""The reader of ``serve.prefill_attend_ms_per_ktoken`` (PR 38): device
ms of the fused prefill attend (``%mla_prefill_attend.N``, one call a
layer) inside the ``jit_serve_prefill_b<bucket>`` module events of a
capture, per 1,000 bucket tokens; nothing where the prefill attends by an
XLA loop (the parent of the PR that added the kernel: an anonymous
``%while.N``); the two latent-attention cells list it and no other, and
the metric is one file and one appended entry over a benchmark that lacks
them."""

import json
import os
import types

import pytest
from test_glm_cell import _hashes, entries_added

from harness import trace as T
from harness.loader import Cell, load_benchmark, load_reader

NAME = "serve.prefill_attend_ms_per_ktoken"
CELL = "axk1-serve-reasoning"
CELLS = [CELL, "glm52-serve-longctx"]
ENTRY = {"name": NAME, "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "kernels",
         "moves": "serve_ttft_p50_ms", "workloads": CELLS}
# as a v5e capture names it (my chip run, PR 38)
KERNEL = "%mla_prefill_attend.{} tpu_custom_call bf16[64,{},128]"
MS = 1_000_000                                           # ns


def _trace(prefills, decode_steps=2):
    """One device: each of ``prefills`` = (bucket, [kernel ms a layer] or
    None for the XLA loop) as a ``jit_serve_prefill_b<bucket>`` module
    event holding matmul fusions, the attends and the experts' ``%gmm``;
    decode steps between them, with a kernel of a LIKE name that is not
    the prefill's."""
    ops, modules, t = [], [], 1_000
    for bucket, layers in prefills:
        start = t
        for n, ms in enumerate(layers or [12.0] * 5):
            ops.append((f"%fusion.{n}", t, 3 * MS))
            t += 3 * MS
            name = (f"%while.{497 + n}" if layers is None
                    else KERNEL.format(n + 5, bucket))
            ops.append((name, t, int(ms * MS)))
            t += int(ms * MS)
            ops.append((f"%gmm.{n} tpu_custom_call f32[{bucket},2048]", t,
                        MS))
            t += MS
        modules.append((f"jit_serve_prefill_b{bucket}(1234)", start,
                        t - start))
        t += 5_000
        for _ in range(decode_steps):
            ops.append(("%mla_latent_attend_dense.5 tpu_custom_call "
                        "f32[48,64,512]", t, MS // 3))
            modules.append(("jit_serve_decode_step(99)", t, MS // 3))
            t += MS // 3 + 5_000
    dev = {"ops": ops, "async": [], "modules": modules}
    return T.Trace({0: dev}, [], 1_000, t)


@pytest.mark.parametrize("prefills, want", [
    # five layers of 9.5 ms at the 8,192 bucket
    ([(8192, [9.5] * 5)], 1e3 * 47.5 / 8192),
    # two buckets: the kernel's ms over the tokens of both
    ([(8192, [9.5] * 5), (4096, [2.9] * 5)], 1e3 * 62.0 / 12288),
    # a bucket the kernel does not take stays out of the tokens too
    ([(8192, [9.5] * 5), (3000, None)], 1e3 * 47.5 / 8192),
    ([(8192, None), (4096, None)], None),               # the parent
    ([], None),                                         # no prefill caught
], ids=["one_bucket", "two_buckets", "a_bucket_on_the_loop", "parent",
        "no_prefill"])
def test_reader_sums_the_named_calls_inside_the_prefill_events(prefills,
                                                               want):
    got = load_reader(NAME)(types.SimpleNamespace(trace=_trace(prefills)))
    assert got == (want if want is None else pytest.approx(want))


def test_reader_without_a_trace_reads_nothing():
    assert load_reader(NAME)(types.SimpleNamespace(trace=None)) is None


def test_a_like_named_kernel_outside_a_prefill_event_is_not_counted():
    """The decode step's ``%mla_latent_attend_dense`` and a prefill
    attend OUTSIDE every prefill module event (a warm-up's tail caught by
    the capture's edge) count for nothing."""
    tr = _trace([(4096, [3.0] * 5)])
    tr.devices[0]["ops"].append(
        (KERNEL.format(9, 4096), tr.end_ns + 10, 3 * MS))
    got = load_reader(NAME)(types.SimpleNamespace(trace=tr))
    assert got == pytest.approx(1e3 * 15.0 / 4096)


def test_the_latent_cells_alone_list_it_and_the_entry_stands_last():
    """The cells whose prefill runs the kernel (the latent family's two:
    A.X-K1 without a selection, GLM with one as an operand) report the
    end-to-end metric it moves; SALA's masked attend and GPT-2's prefill
    are other code."""
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
    A.X-K1 cell loads and names every other reader; added again as a
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
