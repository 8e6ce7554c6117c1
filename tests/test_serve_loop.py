"""The serve loop, characterized: ONE scripted workload through the real
``Scheduler`` on a host-only engine, everything on one hand-advanced
clock, and every observable event of the run in order against a
recording kept beside the tests (``tests/fixtures/serve_loop_trace.txt``).

The workload crosses the loop's branches: an admission at the loop's
top, admissions dispatched from ``on_wait`` behind a step in flight (the
engine calls the hook between the slices of its wait), a burst spaced by
``decode_priority``, a poisoned slot quarantined and continued, a live
weight swap (both from the fault plan's points), an SLO preemption, two
turns of one session, page pressure that defers an admission, and a
feed that sends a request, an unservable one, a ``cancel`` of each kind,
a ``tune``, a ``drain`` and a request after it.

The recording holds, interleaved as they happened: the engine's calls
with their arguments, the spans opened and closed with theirs (the
engine shares its ``HostSpans`` with the scheduler, as the real one
does), every record emitted field for field (the clock is scripted, so
the milliseconds are exact), the journal's writes and flushes, the
tracer's calls and the token stream. It was recorded on the tree BEFORE
the loop became a step over a run state (PR 46) and has to hold across
any change that claims to move no behaviour. A change that means to
reorder the loop records anew and reads the diff:

    TFD_RECORD_SERVE_LOOP=1 python -m pytest tests/test_serve_loop.py
"""

import ast
import contextlib
import os
import types

import numpy as np
import pytest

from tensorflow_distributed_tpu.observe.trace import HostSpans
from tensorflow_distributed_tpu.resilience.faults import parse_fault_plan
from tensorflow_distributed_tpu.serve import scheduler as sched_mod
from tensorflow_distributed_tpu.serve.scheduler import Request, Scheduler

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "fixtures", "serve_loop_trace.txt")


class _Clock:
    """Seconds, kept in whole microseconds so sums are exact."""

    def __init__(self):
        self.us = 0

    def __call__(self) -> float:
        return self.us * 1e-6

    def advance(self, seconds: float) -> None:
        self.us += int(round(seconds * 1e6))


def _fmt(fields: dict) -> str:
    return " ".join(f"{k}={fields[k]!r}" for k in sorted(fields))


class _Recorder:
    """The run's one log, written by everything the scheduler touches."""

    def __init__(self):
        self.lines = []

    def say(self, _kind: str, _what: str, /, **fields) -> None:
        self.lines.append(f"{_kind} {_what} {_fmt(fields)}".rstrip())

    # the registry
    def emit(self, _event: str, /, **fields) -> None:
        self.say("emit", _event, **fields)

    # HostSpans' annotate seam: every span's entry and exit
    @contextlib.contextmanager
    def annotate(self, _name: str, /, **args):
        self.say("open", _name, **args)
        try:
            yield
        finally:
            self.say("close", _name)

    def on_token(self, rid: int, tok: int, done: bool) -> None:
        self.say("token", str(rid), tok=tok, done=done)


class _Logged:
    """A journal or a tracer: every call is a line."""

    def __init__(self, rec: _Recorder, kind: str):
        self._rec, self._kind = rec, kind

    def __getattr__(self, name):
        def call(*args, **kwargs):
            args = tuple(a.tolist() if isinstance(a, np.ndarray) else a
                         for a in args)
            self._rec.say(self._kind, name, args=args, **kwargs)
            return contextlib.nullcontext()   # tracer.prefill's span
        return call


class _Feed:
    """A dispatch file on the clock: ``poll()`` hands over what has
    come due, in order."""

    def __init__(self, rec: _Recorder, clock: _Clock, items):
        self._rec, self._clock, self._items = rec, clock, list(items)
        self.polls = 0

    def poll(self):
        self.polls += 1
        out = []
        while self._items and self._items[0][0] <= self._clock():
            out.append(self._items.pop(0)[1])
        if out:
            self._rec.say("call", "feed.poll", items=len(out),
                          poll=self.polls)
        return out


class _ScriptedEngine:
    """A host-only engine with the WHOLE surface the scheduler may call
    or read, declared here and inherited from nothing (it has to run on
    the tree that had no base class to inherit). Paged in the
    scheduler's sense: admission context, retention, ``can_admit``.

    The stream is a pure function of (rid, tokens so far): ``rid`` rides
    ``prompt[0]``, a continuation's prompt carries its tokens, so a
    quarantined or preempted request resumes the same stream. On the
    clock a prefill's launch takes 1 ms and its fetch 3 ms, a decode
    step's dispatch 0.5 ms and its wait 10 ms in four slices with the
    hook between them, a weight swap 2 ms."""

    paged = True
    on_wait = None
    spec_tokens = swaps = steps_ahead = ahead_rows_dropped = 0
    admits_first = 0
    tp_width = 1
    model = None
    set_spec_k = None
    last_verify_fallback = ()

    def __init__(self, rec: _Recorder, clock: _Clock, num_slots=2,
                 max_len=64, deny=lambda eng, plen, max_new: False):
        self.rec, self.clock, self.deny = rec, clock, deny
        self.spans = HostSpans(clock=clock, annotate=rec.annotate)
        self.num_slots, self.max_len = num_slots, max_len
        self.buckets = (16, 32)
        self.active = np.zeros((num_slots,), bool)
        self.step_valid = np.zeros((num_slots,), bool)
        self.slot_rid, self.counts = {}, {}
        self.prefills = self.prefill_compiles = self.decode_steps = 0
        self._admitting = None
        self._poisoned, self._bad = set(), []

    def fits(self, plen, max_new):
        return plen <= max(self.buckets) and plen + max_new <= self.max_len

    def free_slots(self):
        return [s for s in range(self.num_slots) if not self.active[s]]

    def occupancy(self):
        return float(self.active.sum()) / self.num_slots

    def can_admit(self, plen, max_new):
        if self.deny(self, plen, max_new):
            self.rec.say("call", "can_admit", max_new=max_new, ok=False)
            return False
        return True

    def reservation_fits(self, plen, max_new):
        return True

    def prefill(self, prompt, slot, max_new_tokens=0, session="",
                fetch=True):
        rid = int(prompt[0])
        self.rec.say("call", "prefill", rid=rid, slot=slot,
                     plen=len(prompt), max_new=max_new_tokens,
                     session=session, fetch=fetch)
        assert not self.active[slot] and self._admitting is None
        with self.spans.span("serve.prefill_launch", bucket=16):
            self.clock.advance(0.001)
        self._admitting = (slot, rid, len(prompt) - 1)
        self.admits_first += 1
        return self.first_token() if fetch else None

    def first_token(self):
        slot, rid, count = self._admitting
        self._admitting = None
        self.rec.say("call", "first_token", rid=rid, slot=slot)
        with self.spans.span("serve.first_token_fetch"):
            self.clock.advance(0.003)
        self.active[slot] = True
        self.slot_rid[slot], self.counts[rid] = rid, count
        self._poisoned.discard(slot)          # a full-row overwrite
        self.prefills += 1
        return rid * 100 + count

    def step(self):
        assert self._admitting is None, "first_token() comes first"
        no = self.decode_steps + 1
        self.rec.say("call", "step", no=no)
        with self.spans.span("serve.step_dispatch", step=no):
            self.clock.advance(0.0005)
        rows = self.active.copy()
        with self.spans.span("serve.token_fetch", step=no,
                             live=int(rows.sum())):
            hooked = False
            for _ in range(4):
                if not hooked and self.on_wait is not None:
                    hooked = bool(self.on_wait())
                self.clock.advance(0.0025)
        out = np.zeros((self.num_slots,), np.int32)
        self._bad = []
        for s in np.flatnonzero(rows):
            if s in self._poisoned:
                out[s] = 999_999              # garbage, to be dropped
                self._bad.append(int(s))
                continue
            rid = self.slot_rid[s]
            self.counts[rid] += 1
            out[s] = rid * 100 + self.counts[rid]
        self.step_valid = rows
        self.decode_steps += 1
        return out

    def take_bad_slots(self):
        bad, self._bad = self._bad, []
        return [s for s in bad if self.active[s]]

    def release(self, slot, tokens=None, session=""):
        self.rec.say("call", "release", slot=slot,
                     rid=self.slot_rid.get(slot), session=session,
                     tokens=None if tokens is None else len(tokens))
        self.active[slot] = False

    def free(self, slot):
        self.rec.say("call", "free", slot=slot)
        self.active[slot] = False

    def drain(self):
        self.rec.say("call", "drain")

    def poison_slot(self, slot):
        self.rec.say("call", "poison_slot", slot=slot)
        self._poisoned.add(slot)

    def swap_params(self, params):
        self.rec.say("call", "swap_params", params=params)
        self.clock.advance(0.002)
        self.swaps += 1

    def can_verify(self):
        return False

    def verify_fallback_slots(self):
        return None

    def model_stats(self):
        return {}

    def paging_stats(self):
        return {"pool_occupancy": 0.5}

    def cache_bytes_per_slot(self):
        return 4096


def _req(rid, max_new, at=0.0, slo="standard", tenant="", session="",
         plen=1, eos=-1):
    return Request(rid=rid, prompt=np.asarray([rid] + [7] * (plen - 1),
                                              np.int32),
                   max_new_tokens=max_new, eos_id=eos, arrival_s=at,
                   slo=slo, tenant=tenant, session=session)


def _run_scripted(monkeypatch):
    rec, clock = _Recorder(), _Clock()
    # an idle engine sleeps on this clock, and a snapshot's wall stamp
    # is not the host's
    monkeypatch.setattr(sched_mod, "time", types.SimpleNamespace(
        sleep=clock.advance, time=lambda: 0.0))
    monkeypatch.setattr(sched_mod, "os",
                        types.SimpleNamespace(getpid=lambda: 1))
    # page pressure: the 9-token request finds no room before step 17
    eng = _ScriptedEngine(
        rec, clock,
        deny=lambda e, plen, max_new: max_new == 9 and e.decode_steps < 17)
    feed = _Feed(rec, clock, [
        (0.150, _req(20, 4, slo="high")),          # a fed request
        (0.150, _req(21, 500)),                    # does not fit
        (0.200, {"cmd": "cancel", "rid": 5}),      # wherever it is
        (0.204, _req(22, 6, session="b")),
        (0.204, {"cmd": "cancel", "rid": 22}),     # still pending
        (0.204, _req(23, 3)),                      # behind a command
        (0.230, {"cmd": "cancel", "rid": 8}),
        (0.260, {"cmd": "tune", "knob": "decode_priority", "value": 1}),
        (0.300, {"cmd": "swap"}),
        (0.330, {"cmd": "cancel", "rid": 99}),     # nobody's
        (0.400, {"cmd": "drain"}),
        (0.400, _req(24, 2)),                      # after the drain
    ])
    requests = [
        _req(0, 14),                               # the loop's top, idle
        _req(1, 10, slo="batch", tenant="t"),      # the burst, spaced
        _req(2, 3, slo="batch", tenant="t"),
        _req(3, 5, at=0.045, slo="high"),          # evicts a batch row
        _req(4, 8, at=0.060, session="a", eos=402),  # two turns, in order
        _req(5, 30, at=0.060, session="a", plen=4),
        _req(6, 9, at=0.100),                      # page pressure
        _req(7, 1, at=0.120),                      # done at its prefill
        _req(8, 4, at=0.130, slo="batch"),         # cancelled in the queue
    ]
    sched = Scheduler(
        eng, decode_priority=2, registry=rec, on_token=rec.on_token,
        clock=clock, fault_plan=parse_fault_plan("slot_nan@9:0,reload@17"),
        journal=_Logged(rec, "journal"), reload_fn=lambda: ("params", 7),
        policy="slo", tenant_quota=6, tracer=_Logged(rec, "tracer"),
        feed=feed, export_every=0.1, summary_extra={"seed": 3})
    done = sched.run(requests)
    rec.say("done", "order", rids=[c.rid for c in done],
            tokens=[len(c.tokens) for c in done])
    rec.say("done", "feed", polls=feed.polls)
    rec.say("done", "hook", on_wait=eng.on_wait)
    rec.say("done", "snapshot", **sched.metrics_snapshot())
    return rec.lines


@pytest.fixture(scope="module")
def traces():
    with pytest.MonkeyPatch.context() as mp:
        got = _run_scripted(mp)
    if os.environ.get("TFD_RECORD_SERVE_LOOP"):
        with open(TRACE, "w") as f:
            f.write("\n".join(got) + "\n")
    with open(TRACE) as f:
        return got, f.read().splitlines()


#: What each case holds of the recording: the lines of these kinds, in
#: their order.
VIEWS = {
    "engine_calls": ("call",),
    "spans": ("open", "close"),
    "records": ("emit",),
    "journal": ("journal",),
    "stream": ("token", "tracer"),
    "everything_interleaved": ("call", "open", "close", "emit", "journal",
                               "token", "tracer", "done"),
}


@pytest.mark.parametrize("view", sorted(VIEWS))
def test_the_scripted_run_is_the_recorded_one(traces, view):
    got, want = ([ln for ln in lines if ln.split(" ", 1)[0] in VIEWS[view]]
                 for lines in traces)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g == w, (
            f"{view}: line {i} differs; before it:\n  "
            + "\n  ".join(want[max(0, i - 5):i]))
    assert len(got) == len(want)


def test_the_script_crosses_every_branch(traces):
    """The recording is only worth its branches: each one the script
    names is in it."""
    text = "\n".join(traces[1])
    for needle in (
            "fetch=True", "fetch=False",              # top, and behind
            "emit preempt", "kind='slot_quarantine'",
            "kind='weight_swap'", "emit serve_reject",
            "where='live'", "where='pending'", "where='queue'",
            "draining=True", "call poison_slot", "call drain",
            "call can_admit", "call swap_params", "decode_priority=1",
            "finish='eos'", "finish='length'", "session='a'",
            "emit metrics_snapshot", "emit serve_summary"):
        assert needle in text, needle
    # the hook was called, and given back
    assert "done hook on_wait=None" in text


# --- the shape of serve/scheduler.py, so the loop does not grow back ------

#: Lines a function of serve/scheduler.py may have; ``_run`` fewer (the
#: loop is ``_iterate``'s phases).
MAX_LINES, MAX_LINES_OF = 150, {"_run": 59}


def _scheduler_ast():
    with open(sched_mod.__file__) as f:
        return ast.parse(f.read())


def _shape_nonlocal():
    tree = _scheduler_ast()
    return [f"line {n.lineno}: nonlocal {', '.join(n.names)}"
            for n in ast.walk(tree) if isinstance(n, ast.Nonlocal)]


def _shape_long_function():
    tree = _scheduler_ast()
    return [f"{n.name}: {n.end_lineno - n.lineno + 1} lines"
            for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n.end_lineno - n.lineno + 1
            > MAX_LINES_OF.get(n.name, MAX_LINES)]


def _shape_engine_probe():
    """``getattr``/``hasattr`` on the engine: what it has is declared
    (serve/engine.py ``EngineSurface``), not asked."""
    tree = _scheduler_ast()
    return [f"line {n.lineno}: {ast.unparse(n)}"
            for n in ast.walk(tree)
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
            and n.func.id in ("getattr", "hasattr") and n.args
            and ast.unparse(n.args[0]) in ("eng", "engine", "self.engine")]


@pytest.mark.parametrize("finding", [
    _shape_nonlocal, _shape_long_function, _shape_engine_probe],
    ids=lambda f: f.__name__[len("_shape_"):])
def test_the_scheduler_keeps_its_shape(finding):
    """No state rebound through ``nonlocal`` (a run's state is one
    ``_Run``), no function over 150 lines (``_run`` under 60: the loop
    is ``_iterate``'s phases), no probe of the engine's attributes."""
    assert finding() == []
