"""The six readers of where a served request's milliseconds went (PR 39):
``serve.ttft_mid_wait_admit_ms``, ``.ttft_mid_wait_step_ms``,
``.ttft_mid_prefill_ms`` over the middle fifth of requests by ``ttft_ms``,
``serve.tpot_tail_admit_ms``, ``.tpot_tail_step_ms`` over the slowest tenth
by ``tok_ms``, ``serve.admit_wall_share`` from the run's summary. Each
reads the program's own ``serve_request`` / ``serve_summary`` records
(``wait_ms``, ``decode_ms``, ``iter_ms``); the records of a program without
those fields (the parent of PR 39) give nothing; the four serve cells below
capacity list all six and no other cell does; and the six are seven files
and six appended entries over a benchmark that lacks them.

The module holds its entries by NAME (``ENTRIES``), not one ``ENTRY`` held
to stand last: ``tests/conftest.py`` cuts ``per_layer`` after such an
entry, and six entries cannot all be last."""

import json
import math
import os
import types

import pytest
from test_glm_cell import _hashes, entries_added

from harness import request_parts as R
from harness.loader import Cell, load_benchmark, load_reader

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "glm_serve_request_split.jsonl")
# The recorded run: ``glm52-serve-longctx --trace 1``, seed 3900000011, on a
# TPU v5e (my chip run, PR 39), its ``serve_request`` and ``serve_summary``
# records less the fields no reader here takes. Its capture started here,
# seconds into the window (its log: "served before the capture started at
# 24.1s"; the first tokens nearest to it came at 23.84 and 24.66 s).
CUT_S = 24.1
CELLS = ["gpt2l-serve-steady", "glm52-serve-longctx",
         "axk1-serve-reasoning", "sala-serve-longdoc"]
NEW_FIELDS = ("wait_ms", "decode_ms", "admits_endured", "iter_ms",
              "admissions")


def _entry(name, unit, moves):
    return {"name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": "serve driver",
            "moves": moves, "workloads": CELLS}


ENTRIES = {e["name"]: e for e in (
    _entry("serve.ttft_mid_wait_admit_ms", "ms", "serve_ttft_p50_ms"),
    _entry("serve.ttft_mid_wait_step_ms", "ms", "serve_ttft_p50_ms"),
    _entry("serve.ttft_mid_prefill_ms", "ms", "serve_ttft_p50_ms"),
    _entry("serve.tpot_tail_admit_ms", "ms", "serve_tpot_p95_ms"),
    _entry("serve.tpot_tail_step_ms", "ms", "serve_tpot_p95_ms"),
    _entry("serve.admit_wall_share", "%", "serve_tpot_p95_ms"))}
NAMES = list(ENTRIES)
# What each reader gives for the recorded run, worked out from the
# fixture's records outside the readers: ranks 14-20 of the 33 requests
# served before the capture by ttft_ms (rids 16, 20, 17, 29, 0, 1, 21),
# ranks 29-32 of the 32 decoded before it by tok_ms (rids 3, 0, 2, 1), and
# the 33 requests' prefill_ms over 24.1 s. The run printed the same.
WANT = dict(zip(NAMES, (
    152.78857142857143, 6.751714285714286, 396.66614285714286,
    40.65719829367384, 7.6471758266499785, 46.41163485477178)))


def _records():
    with open(FIXTURE) as f:
        return [json.loads(line) for line in f if line.strip()]


def _ctx(records, cut_s=CUT_S, said=None):
    said = [] if said is None else said
    return types.SimpleNamespace(records=records, cut_s=cut_s,
                                 say=said.append)


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_the_recorded_split_and_nothing_of_the_parent(name):
    records, said = _records(), []
    read = load_reader(name)
    assert read(_ctx(records, said=said)) == pytest.approx(WANT[name],
                                                           rel=1e-9)
    assert len(said) == 1 and said[0].startswith(name + ":")
    # the parent's program writes the same records without the fields
    parent = [{k: v for k, v in r.items() if k not in NEW_FIELDS}
              for r in records]
    assert any("prefill_ms" in r for r in parent)
    assert read(_ctx(parent)) is None
    assert read(_ctx([])) is None
    # its entry, by name; the four cells list it and report what it moves
    bench = load_benchmark()
    assert [m for m in bench["per_layer"]
            if m["name"] == name] == [ENTRIES[name]]
    for w in bench["workloads"]:
        listed = name in {m["name"] for m in Cell(w["name"]).per_layer()}
        assert listed == (w["name"] in CELLS), w["name"]
    for cell in CELLS:
        assert ENTRIES[name]["moves"] in {
            m["name"] for m in Cell(cell).end_to_end()}


def test_the_recorded_parts_add_up_to_the_times_they_split():
    """On the chip's own records the band's three waits and its prefill
    are its mean ``ttft_ms``, the tail's three parts its mean ``tok_ms``,
    each within 3% (what is missing is the host between two spans), and
    the kinds tile the summary's wall."""
    ctx = _ctx(_records())
    mid = R.ttft_mid(ctx, "x")
    assert sum(mid[k] for k in R.KINDS) + mid["prefill"] == pytest.approx(
        mid["ttft"], rel=0.03)
    tail = R.tpot_tail(ctx, "x")
    assert sum(tail[k] for k in R.KINDS) == pytest.approx(tail["tok"],
                                                          rel=0.03)
    assert R.tpot_tail(_ctx(_records(), cut_s=math.inf), "x")["tok"] != \
        tail["tok"]                     # the capture's stall is cut off


def _request(rid, ttft, tok, new_tokens=11, t_first=1.0, wait=None,
             dec=None):
    wait = wait or (ttft / 2, ttft / 10, 0.0)
    dec = dec or (tok * (new_tokens - 1) / 4, tok * (new_tokens - 1) / 2,
                  0.0)
    return {"event": "serve_request", "rid": rid, "ttft_ms": ttft,
            "tok_ms": tok, "new_tokens": new_tokens, "t_first_s": t_first,
            "prefill_ms": ttft - sum(wait), "admits_endured": rid,
            "wait_ms": dict(zip(R.KINDS, wait)),
            "decode_ms": dict(zip(R.KINDS, dec))}


@pytest.mark.parametrize("n, band, tail", [
    (1, [1], [1]), (5, [2, 3], [5]), (10, [4, 5, 6], [9, 10]),
    (33, list(range(14, 21)), [30, 31, 32, 33]),
], ids=["one", "five", "ten", "thirty_three"])
def test_the_bands_are_nearest_rank(n, band, tail):
    """Request ``i`` of ``n`` (ranks from 1) has ``ttft_ms`` 100 i and
    ``tok_ms`` 10 i: the middle fifth is ranks ceil(0.4 n) to ceil(0.6 n),
    the slowest tenth ranks ceil(0.9 n) and above; a request whose first
    token (the tail: whose last) came after the capture started is in
    neither, nor is one of a single token in the tail."""
    reqs = [_request(i, 100.0 * i, 10.0 * i) for i in range(n, 0, -1)]
    late = _request(99, 1.0, 1e6, t_first=30.0)
    ends_late = _request(98, 50.0 * n + 1, 1e6, t_first=23.5)
    one_token = _request(97, 1e6, 1e6, new_tokens=1)
    mid = R.ttft_mid(_ctx(reqs + [late], cut_s=24.0), "x")
    assert mid["ttft"] == pytest.approx(100.0 * sum(band) / len(band))
    assert mid["admit"] == pytest.approx(mid["ttft"] / 2)
    assert mid["prefill"] == pytest.approx(0.4 * mid["ttft"])
    out = R.tpot_tail(_ctx(reqs + [late, one_token, ends_late],
                           cut_s=24.0), "x")
    assert out["tok"] == pytest.approx(10.0 * sum(tail) / len(tail))
    assert out["step"] == pytest.approx(out["tok"] / 2)
    assert out["endured"] == pytest.approx(sum(tail) / len(tail))


_ITER = {"admit": 6300.0, "step": 3500.0, "other": 200.0}


@pytest.mark.parametrize("summary, cut_s, want", [
    # untraced (no capture, or one that never started inside the run):
    # the summary's kinds over the wall they tile
    ({"wall_s": 10.0, "iter_ms": _ITER}, math.inf, 63.0),
    ({"wall_s": 10.0, "iter_ms": _ITER}, 12.0, 63.0),
    # traced: the capture's stall is inside wall_s and inside no kind, and
    # what follows it is another run; the wall BEFORE the capture is read
    # from the requests served in it: (1,000 + 1,400) ms of 4 s
    ({"wall_s": 25.0, "iter_ms": _ITER}, 4.0, 60.0),
    ({"wall_s": 10.0, "iter_ms": {"admit": 0.0, "step": 9000.0,
                                  "other": 1000.0}}, math.inf, 0.0),
    ({"wall_s": 10.0}, 4.0, None),                      # the parent
    ({"wall_s": 0.0, "iter_ms": {"admit": 0.0, "step": 0.0,
                                 "other": 0.0}}, math.inf, None),
], ids=["untraced", "capture_after_the_run", "traced", "no_admission",
        "parent", "empty_run"])
def test_the_wall_share_reads_the_run_or_the_wall_before_the_capture(
        summary, cut_s, want):
    reqs = [_request(0, 1250.0, 10.0, t_first=1.5, wait=(200.0, 50.0, 0.0)),
            _request(1, 1400.0, 10.0, t_first=3.9, wait=(0.0, 0.0, 0.0)),
            _request(2, 900.0, 10.0, t_first=4.2, wait=(0.0, 0.0, 0.0))]
    ctx = _ctx(reqs + [{"event": "serve_summary", **summary}], cut_s=cut_s)
    got = load_reader("serve.admit_wall_share")(ctx)
    assert got == (want if want is None else pytest.approx(want))


def test_the_six_are_seven_files_and_six_entries_and_edit_no_file(
        benchmark_copy):
    """Taken OUT of a copy of the benchmark (six readers, the helper, six
    entries), every serve cell loads and names every reader it has left;
    added again as this PR adds them, ``BENCHMARK.json`` differs by six
    entries appended to ``per_layer`` and every file the copy had has the
    hash it had."""
    root = benchmark_copy
    bench_dir = os.path.join(root, "perfbench")
    files = [os.path.join(bench_dir, "metrics", n + ".py") for n in NAMES]
    files.append(os.path.join(bench_dir, "harness", "request_parts.py"))
    sources = {}
    for path in files:
        with open(path) as f:
            sources[path] = f.read()
        os.remove(path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        full = json.load(f)
    without = json.loads(json.dumps(full))
    without["per_layer"] = [m for m in full["per_layer"]
                            if m["name"] not in ENTRIES]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(without, f)
    before = _hashes(bench_dir)
    for cell in CELLS + ["gpt2l-serve-saturated"]:
        names = [m["name"] for m in Cell(cell, root=root).per_layer()]
        assert not set(names) & set(NAMES)
        for name in names:
            assert load_reader(name, root=root) is not None
    for path, source in sources.items():
        with open(path, "w") as f:
            f.write(source)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(full, f)
    assert entries_added(without, full, []) == {
        "configs": [], "workloads": [], "end_to_end": [],
        "per_layer": NAMES}
    assert [m["name"] for m in full["per_layer"]][-6:] == NAMES
    for cell in CELLS:
        assert [m["name"] for m in Cell(cell, root=root).per_layer()
                ][-6:] == NAMES
    after = _hashes(bench_dir)
    for path, digest in before.items():
        assert after[path] == digest, f"{path} was edited"
    assert len(after) == len(before) + 7
