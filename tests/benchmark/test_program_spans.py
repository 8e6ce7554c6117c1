"""Device idle time split by the program's own spans
(perfbench/harness/program_spans.py): the interval arithmetic on
hand-made events, the five readers, and the whole reduction on a small
recorded serve capture (TPU v5e, PR 25)."""

import os

import pytest

from harness import common, loader
from harness import program_spans as P
from harness import trace as T

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
READERS = ("serve.idle_fetch_ms_per_step", "serve.idle_launch_ms_per_step",
           "serve.idle_sched_ms_per_step",
           "serve.idle_admit_ms_per_admission",
           "serve.idle_sched_ms_per_step.sat")


def test_innermost_names_every_instant_by_its_deepest_span():
    spans = [("tfd.serve.admit", 100, 100),          # 100..200
             ("tfd.serve.prefill_launch", 110, 30),  # 110..140
             ("tfd.serve.first_token_fetch", 140, 50),   # 140..190
             ("tfd.serve.poll", 250, 10)]
    assert P.innermost(spans) == [
        (100, 110, "tfd.serve.admit"),
        (110, 140, "tfd.serve.prefill_launch"),
        (140, 190, "tfd.serve.first_token_fetch"),
        (190, 200, "tfd.serve.admit"),
        (250, 260, "tfd.serve.poll")]
    # given in any order; a child that outlasts its parent is cut there
    assert P.innermost([("b", 5, 20), ("a", 0, 10)]) == [
        (0, 5, "a"), (5, 10, "b")]
    assert P.innermost([]) == []


def hand_capture():
    """Two decode steps and one admission on one device, 0..1000 ns.
    Busy 100..300 (step 1), 400..460 (prefill), 460..500 (insert),
    600..800 (step 2); idle 0..100, 300..400, 500..600, 800..1000."""
    ops = [("%fusion.1", 100, 200), ("%fusion.2", 400, 60),
           ("%fusion.3", 460, 40), ("%fusion.1", 600, 200)]
    mods = [("jit_serve_decode_step(1)", 100, 200),
            ("jit_serve_prefill_b64(2)", 400, 60),
            ("jit_serve_insert_row(3)", 460, 40),
            ("jit_serve_decode_step(1)", 600, 200)]
    trace = T.Trace({0: {"ops": ops, "async": [], "modules": mods}},
                    [], 0, 1000)
    spans = [
        ("tfd.serve.step_upload", 40, 20),       # idle 40..60
        ("tfd.serve.step_dispatch", 60, 30),     # idle 60..90
        ("tfd.serve.token_fetch", 90, 230),      # idle 90..100, 300..320
        ("tfd.serve.retire", 320, 20),           # idle 320..340
        ("tfd.serve.tail", 340, 5),              # idle 340..345
        ("tfd.serve.poll", 345, 15),             # idle 345..360
        ("tfd.serve.admit", 360, 170),           # 360..530: self 360..370,
        ("tfd.serve.prefill_launch", 370, 20),   #   370..390,
        ("tfd.serve.first_token_fetch", 390, 130),   # 390..400, 500..520
        #                                          and admit again 520..530
        ("tfd.serve.poll", 530, 10),
        ("tfd.serve.step_upload", 540, 20),
        ("tfd.serve.step_dispatch", 560, 30),
        ("tfd.serve.token_fetch", 590, 260),     # idle 590..600, 800..850
        ("tfd.serve.retire", 850, 50),           # idle 850..900
    ]
    return trace, spans


def test_idle_by_span_splits_a_gap_exactly_between_its_spans():
    trace, spans = hand_capture()
    idle = P.idle_by_span(trace, spans)
    ns = {k: round(v * 1e9) for k, v in idle.items()}
    # the gap 300..400 straddles six spans and is split among them; a
    # midpoint rule would have given all 100 ns to the one at 350
    assert ns == {
        "tfd.serve.step_upload": 20 + 20,
        "tfd.serve.step_dispatch": 30 + 30,
        "tfd.serve.token_fetch": 10 + 20 + 10 + 50,
        "tfd.serve.retire": 20 + 50,
        "tfd.serve.tail": 5,
        "tfd.serve.poll": 15 + 10,
        "tfd.serve.admit": 10 + 10,
        "tfd.serve.prefill_launch": 20,
        "tfd.serve.first_token_fetch": 10 + 20,
        P.UNATTRIBUTED: 40 + 100}            # 0..40 and 900..1000
    # by construction the split adds up to the window's idle time
    assert sum(ns.values()) == 500
    assert sum(idle.values()) == pytest.approx(
        trace.window_s * T.idle_share(trace) / 100)


def _ctx(trace, spans, said=None):
    return common.Ctx(trace=trace, program_spans=spans,
                      say=(said.append if said is not None
                           else lambda m: None))


def test_readers_on_the_hand_capture():
    trace, spans = hand_capture()
    said = []
    ctx = _ctx(trace, spans, said)
    got = {name: loader.load_reader(name)(ctx) for name in READERS}
    # 2 decode steps, 1 admission; ns over steps, as milliseconds
    assert got["serve.idle_fetch_ms_per_step"] == pytest.approx(
        90e-6 / 2)
    assert got["serve.idle_launch_ms_per_step"] == pytest.approx(
        100e-6 / 2)
    assert got["serve.idle_sched_ms_per_step"] == pytest.approx(
        100e-6 / 2)
    assert got["serve.idle_sched_ms_per_step.sat"] == \
        got["serve.idle_sched_ms_per_step"]
    assert got["serve.idle_admit_ms_per_admission"] == pytest.approx(
        70e-6 / 1)
    # the split is said once for the run, whole: every span name,
    # unattributed, the steps and admissions counted
    text = "\n".join(said)
    assert text.count("device idle by program span") == 1
    assert "2 decode steps, 1 admissions" in text
    for name in list(P.FETCH + P.LAUNCH + P.SCHED + P.ADMIT) + [
            P.UNATTRIBUTED]:
        assert f"  {name}: " in text


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_program_spans(name):
    """The parent of the PR that added the spans: a capture with device
    ops and harness spans and no tfd.* span. Nothing to read, no error."""
    trace, _ = hand_capture()
    trace.host.append(("bench.engine_step", 40, 300))
    read = loader.load_reader(name)
    assert read(_ctx(trace, [])) is None
    assert read(common.Ctx(trace=None, say=lambda m: None)) is None


def test_reader_without_a_capture_on_disk(tmp_path, monkeypatch):
    """No ``program_spans`` handed in: the reader looks for the run's own
    capture under .cache/perfbench/<cell>/trace and finds none."""
    monkeypatch.setattr(P, "ROOT", str(tmp_path))
    trace, _ = hand_capture()

    class _Cell:
        name = "gpt2l-serve-steady"

    ctx = common.Ctx(trace=trace, cell=_Cell(), say=lambda m: None)
    assert loader.load_reader(READERS[0])(ctx) is None


def test_load_spans_reads_the_host_planes_of_a_capture(tmp_path):
    """A real capture (of the CPU, here): the seam's annotations are
    found by prefix on the /host: planes, other events are not, and the
    parse is shared."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.observe.trace import HostSpans

    spans = HostSpans()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for step in range(3):
            with spans.span("serve.token_fetch", step=step):
                with jax.profiler.TraceAnnotation("bench.engine_step"):
                    jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    finally:
        jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    got = P.load_spans(path)
    assert [name for name, _, _ in got] == ["tfd.serve.token_fetch"] * 3
    assert all(dur > 0 for _, _, dur in got)
    assert got == sorted(got, key=lambda e: e[1])
    assert P.load_spans(path) is got


# ---- the recorded chip capture: thirty decode steps from the middle of
# a --trace 1 run of gpt2l-serve-steady on one TPU v5e (chiprun, PR 25),
# cut by tools/spans_look.py. Device ops are stored as busy intervals.

@pytest.fixture(scope="module")
def chip():
    path = os.path.join(FIXTURES, "serve_v5e_spans.json.gz")
    return T.load_json(path), P.load_spans_json(path)


def test_fixture_is_a_serve_capture_with_program_spans(chip):
    trace, spans = chip
    assert P.module_count(trace, P.DECODE_MODULE) == 30
    assert P.module_count(trace, P.PREFILL_MODULE) == 4
    names = {name for name, _, _ in spans}
    assert names == {"tfd.serve." + n for n in (
        "poll", "admit", "prefill_launch", "first_token_fetch",
        "step_upload", "step_dispatch", "token_fetch", "retire", "tail")}
    # the harness's own spans are there too, on the same clock
    assert {h[0] for h in trace.host} == {"bench.engine_step",
                                          "bench.engine_prefill"}


def test_one_recorded_gap_split_by_hand(chip):
    """The idle gap after the fixture's first decode step: 7,927,197 ns
    from the program's last op to the next program's first. Worked out
    by hand from the span list: the host spent it at the end of one
    token_fetch, in retire, tail, poll and step_upload, and most of it
    in step_dispatch, with the engine's slot bookkeeping and the
    harness's wrapper between the spans."""
    trace, spans = chip
    base = trace.start_ns
    gap = T.Trace({0: {"ops": [], "async": [], "modules": []}}, [],
                  base + 19234814, base + 27162011)
    ns = {k: round(v * 1e9) for k, v in P.idle_by_span(gap, spans).items()}
    assert ns == {
        "tfd.serve.token_fetch": 22049345 - 19234814,
        "tfd.serve.retire": 22283905 - 22163225,
        "tfd.serve.tail": 22306435 - 22302085,
        "tfd.serve.poll": 22316455 - 22313485,
        "tfd.serve.step_upload": 22901935 - 22326435,
        "tfd.serve.step_dispatch": 27162011 - 22911655,
        P.UNATTRIBUTED: 113880 + 18180 + 7050 + 9980 + 9720}
    assert sum(ns.values()) == 7927197


def test_recorded_split_adds_up_to_the_idle_share(chip):
    trace, spans = chip
    idle = P.idle_by_span(trace, spans)
    ns = {k: round(v * 1e9) for k, v in idle.items()}
    assert ns == {
        "tfd.serve.step_dispatch": 109233730,
        "tfd.serve.token_fetch": 76419859,
        "tfd.serve.prefill_launch": 29260449,
        "tfd.serve.step_upload": 13996117,
        P.UNATTRIBUTED: 6782817,
        "tfd.serve.retire": 2062618,
        "tfd.serve.poll": 129389,
        "tfd.serve.tail": 110289,
        "tfd.serve.admit": 71139}
    total = sum(idle.values())
    assert total == pytest.approx(
        trace.window_s * T.idle_share(trace) / 100, rel=1e-9)
    # spans are missing nowhere that matters: under 5% of the idle time
    # is in none (here the cut's 1 ms of lead-in and lead-out)
    assert idle[P.UNATTRIBUTED] / total < 0.05


def test_five_readers_on_the_recorded_capture(chip):
    trace, spans = chip
    ctx = _ctx(trace, list(spans))
    got = {name: loader.load_reader(name)(ctx) for name in READERS}
    assert got["serve.idle_fetch_ms_per_step"] == pytest.approx(
        76.419859 / 30)
    assert got["serve.idle_launch_ms_per_step"] == pytest.approx(
        (13.996117 + 109.233730) / 30)
    assert got["serve.idle_sched_ms_per_step"] == pytest.approx(
        (2.062618 + 0.129389 + 0.110289) / 30)
    assert got["serve.idle_admit_ms_per_admission"] == pytest.approx(
        (0.071139 + 29.260449) / 4)
    assert got["serve.idle_sched_ms_per_step.sat"] == \
        got["serve.idle_sched_ms_per_step"]
    # what the readers stand for: the device waits longer for the next
    # launch than for its tokens to be fetched, and hardly at all for
    # the scheduler
    assert (got["serve.idle_launch_ms_per_step"]
            > got["serve.idle_fetch_ms_per_step"]
            > 10 * got["serve.idle_sched_ms_per_step"])


def test_recorded_capture_without_its_spans_reads_nothing(chip):
    trace, _ = chip
    for name in READERS:
        assert loader.load_reader(name)(_ctx(trace, [])) is None
