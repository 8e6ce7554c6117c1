"""The span seam (observe/trace.py::HostSpans): one ``with`` feeds the
profiler annotation, the Chrome trace and the always-on phase totals
under one ``tfd.*`` name. jax-free: a fake clock and a recording
stand-in for ``jax.profiler.TraceAnnotation``."""

from __future__ import annotations

import sys

import pytest

from tensorflow_distributed_tpu.observe.trace import (
    SPAN_PREFIX, ChromeTracer, HostSpans, PhaseTotals, load_trace)


class _Clock:
    """Advances only when told to: a span's wall is what the test put
    inside it."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, ms):
        self.t += ms / 1e3


class _Annotations:
    """Records what the seam hands the profiler."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **args):
        outer = self

        class _Ann:
            def __enter__(self):
                outer.log.append(("enter", name, args))

            def __exit__(self, *exc):
                outer.log.append(("exit", name, args))

        return _Ann()


def _spans(chrome=None):
    clock, ann = _Clock(), _Annotations()
    return HostSpans(chrome=chrome, clock=clock, annotate=ann), clock, ann


def test_totals_accumulate_per_name():
    spans, clock, _ = _spans()
    for ms in (2.0, 3.0, 5.0):
        with spans.span("serve.tail"):
            clock.advance(ms)
    with spans.span("serve.poll"):
        clock.advance(1.0)
    got = spans.totals.as_dict()
    assert set(got) == {"tfd.serve.tail", "tfd.serve.poll"}
    assert got["tfd.serve.tail"]["count"] == 3
    assert got["tfd.serve.tail"]["sum_ms"] == pytest.approx(10.0)
    assert got["tfd.serve.tail"]["max_ms"] == pytest.approx(5.0)
    assert got["tfd.serve.poll"] == {
        "count": 1, "sum_ms": pytest.approx(1.0),
        "max_ms": pytest.approx(1.0), "max_step": 0,
        "max_at_s": pytest.approx(0.011)}


def test_nested_spans_report_self_time_and_inclusive_wall():
    spans, clock, _ = _spans()
    with spans.span("serve.admit", rid=7) as admit:
        clock.advance(1.0)
        with spans.span("serve.prefill_launch", bucket=64):
            clock.advance(4.0)
        with spans.span("serve.first_token_fetch"):
            clock.advance(10.0)
        clock.advance(2.0)
    got = spans.totals.as_dict()
    # the parent's total excludes what its children covered ...
    assert got["tfd.serve.admit"]["sum_ms"] == pytest.approx(3.0)
    assert got["tfd.serve.prefill_launch"]["sum_ms"] == pytest.approx(4.0)
    assert got["tfd.serve.first_token_fetch"]["sum_ms"] == \
        pytest.approx(10.0)
    # ... so the phases tile the wall, and the span itself still knows
    # its inclusive wall (serve_request.prefill_ms)
    assert sum(v["sum_ms"] for v in got.values()) == pytest.approx(17.0)
    assert admit.wall_ms == pytest.approx(17.0)


_KINDS = {"serve.admit": "admit", "serve.prefill_launch": "admit",
          "serve.token_fetch": "step", "serve.retire": "step"}


def test_elapsed_by_sums_closed_spans_by_kind_and_counts_one_name():
    spans, clock, _ = _spans()
    assert spans.elapsed_by(_KINDS, "other", count="serve.admit") == (
        {"admit": 0.0, "step": 0.0, "other": 0.0}, 0)
    for _ in range(2):
        with spans.span("serve.poll"):
            clock.advance(1.0)
        with spans.span("serve.admit", rid=1):
            clock.advance(2.0)
            with spans.span("serve.prefill_launch"):
                clock.advance(30.0)
        with spans.span("serve.token_fetch"):
            clock.advance(8.0)
        with spans.span("serve.made_up_later"):     # no kind: the rest
            clock.advance(0.5)
    by, admits = spans.elapsed_by(_KINDS, "other", count="serve.admit")
    assert admits == 2
    assert by == {"admit": pytest.approx(0.064),
                  "step": pytest.approx(0.016),
                  "other": pytest.approx(0.003)}
    # the kinds tile what the phases tile
    assert sum(by.values()) == pytest.approx(1e-3 * sum(
        v["sum_ms"] for v in spans.totals.as_dict().values()))
    assert spans.elapsed_by(_KINDS, "other")[1] == 0    # nothing asked


def test_elapsed_by_counts_the_open_part_of_every_open_span():
    """Read in the middle of a nest three deep: each open span gives its
    wall so far less its closed children and less the open child above
    it, under its own kind; closing changes no sum; the count is of
    CLOSED spans."""
    spans, clock, _ = _spans()
    with spans.span("serve.poll"):
        clock.advance(1.0)
    with spans.span("serve.admit", rid=3):
        clock.advance(2.0)
        with spans.span("serve.first_token_fetch"):     # the rest
            clock.advance(4.0)
        clock.advance(1.0)
        with spans.span("serve.prefill_launch"):
            clock.advance(5.0)
            with spans.span("serve.retire"):
                clock.advance(7.0)
                by, admits = spans.elapsed_by(_KINDS, "other",
                                              count="serve.admit")
                assert admits == 0                      # still open
                assert by == {
                    # admit's own 2 + 1, prefill_launch's own 5
                    "admit": pytest.approx(0.008),
                    "step": pytest.approx(0.007),
                    # poll, and the closed child with no kind
                    "other": pytest.approx(0.005)}
                clock.advance(3.0)
            mid, _ = spans.elapsed_by(_KINDS, "other")
            assert mid["step"] == pytest.approx(0.010)
            assert mid["admit"] == pytest.approx(0.008)
    by, admits = spans.elapsed_by(_KINDS, "other", count="serve.admit")
    assert admits == 1
    assert by == {"admit": pytest.approx(0.008),
                  "step": pytest.approx(0.010),
                  "other": pytest.approx(0.005)}
    # two reads bracket a stretch of wall: their difference is where
    # it went
    with spans.span("serve.token_fetch"):
        clock.advance(6.0)
        then, _ = spans.elapsed_by(_KINDS, "other")
        clock.advance(2.5)
    with spans.span("serve.tail"):
        clock.advance(1.5)
        now, _ = spans.elapsed_by(_KINDS, "other")
    assert now["step"] - then["step"] == pytest.approx(0.0025)
    assert now["other"] - then["other"] == pytest.approx(0.0015)
    assert now["admit"] == then["admit"]


def test_elapsed_by_reads_no_clock_with_no_span_open():
    spans, clock, _ = _spans()
    with spans.span("serve.poll"):
        clock.advance(1.0)
    reads = []
    spans._clock = lambda: reads.append(1) or clock()
    spans.elapsed_by(_KINDS, "other")
    assert reads == []
    with spans.span("serve.poll"):
        before = len(reads)
        spans.elapsed_by(_KINDS, "other")
        assert len(reads) == before + 1


def test_max_keeps_its_step_and_run_second():
    spans, clock, _ = _spans()
    for step, ms in ((1, 2.0), (2, 40.0), (3, 7.0)):
        spans.step = step
        with spans.span("serve.token_fetch", step=step):
            clock.advance(ms)
    row = spans.totals.as_dict()["tfd.serve.token_fetch"]
    assert (row["max_ms"], row["max_step"]) == (pytest.approx(40.0), 2)
    assert row["max_at_s"] == pytest.approx(0.042)
    # a new run starts the totals and the run clock afresh
    spans.start_run()
    assert spans.totals.as_dict() == {}
    with spans.span("serve.token_fetch"):
        clock.advance(1.0)
    assert spans.totals.as_dict()["tfd.serve.token_fetch"][
        "max_at_s"] == pytest.approx(0.001)


def test_annotation_always_opens_under_the_prefixed_name():
    spans, _, ann = _spans()
    with spans.span("train.data"):
        with spans.span("train.eval", step=4):
            pass
    assert ann.log == [
        ("enter", "tfd.train.data", {}),
        ("enter", "tfd.train.eval", {"step": 4}),
        ("exit", "tfd.train.eval", {"step": 4}),
        ("exit", "tfd.train.data", {})]
    assert SPAN_PREFIX == "tfd."


def test_chrome_event_carries_the_same_name(tmp_path):
    path = str(tmp_path / "t.json")
    clock = _Clock()
    chrome = ChromeTracer(path, clock=clock)
    spans = HostSpans(chrome=chrome, clock=clock,
                      annotate=_Annotations())
    with spans.span("serve.token_fetch", step=3, live=2):
        clock.advance(2.0)
    chrome.close()
    xs = [e for e in load_trace(path) if e["ph"] == "X"]
    assert [(e["name"], e["args"]) for e in xs] == [
        ("tfd.serve.token_fetch", {"step": 3, "live": 2})]
    assert xs[0]["dur"] == pytest.approx(2000.0)     # microseconds
    # one vocabulary: the Chrome name is the totals' key
    assert list(spans.totals.as_dict()) == ["tfd.serve.token_fetch"]


@pytest.mark.parametrize("chrome", [
    None, ChromeTracer("", enabled=False)], ids=["none", "disabled"])
def test_disabled_path_allocates_no_event(chrome):
    spans, clock, ann = _spans(chrome)
    for i in range(50):
        with spans.span("serve.poll", queue=i):
            clock.advance(0.1)
    if chrome is not None:
        assert chrome._events == []
    # the name is built once, not once a span
    names = {id(entry[1]) for entry in ann.log}
    assert len(names) == 1
    assert spans.totals.as_dict()["tfd.serve.poll"]["count"] == 50


def test_exception_closes_the_span_and_still_counts_it():
    spans, clock, ann = _spans()
    with pytest.raises(RuntimeError):
        with spans.span("serve.retire"):
            with spans.span("serve.tail"):
                clock.advance(1.0)
                raise RuntimeError("boom")
    assert [e[0] for e in ann.log] == ["enter", "enter", "exit", "exit"]
    assert spans.totals.as_dict()["tfd.serve.tail"]["count"] == 1
    with spans.span("serve.poll"):      # the stack is clean again
        clock.advance(1.0)
    assert spans.totals.as_dict()["tfd.serve.poll"]["sum_ms"] == \
        pytest.approx(1.0)


def test_phase_totals_alone():
    t = PhaseTotals()
    t.add("a", 0.002, step=5, at_s=1.5)
    t.add("a", 0.001, step=6, at_s=1.6)
    assert t.as_dict() == {"a": {"count": 2, "sum_ms": 3.0, "max_ms": 2.0,
                                 "max_step": 5, "max_at_s": 1.5}}


def test_module_imports_without_jax():
    """The seam's module is importable (and usable with an injected
    annotation) in a process that never loads jax."""
    import subprocess

    code = (
        "import sys\n"
        "from tensorflow_distributed_tpu.observe import trace\n"
        "import contextlib\n"
        "s = trace.HostSpans(annotate=lambda n, **a: "
        "contextlib.nullcontext())\n"
        "with s.span('x'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "print(s.totals.as_dict()['tfd.x']['count'])\n")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "1"
