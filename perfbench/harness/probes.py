"""The seams through which the harness reaches the program.

Every cell runs through ``cli.main`` as a user would call it. The program
has no hook for a benchmark yet (no wall-clock stop in the train loop, no
way to hand it weights, no per-token callback without ``--serve.stream``'s
printing), so the harness rebinds five names inside the package for the
length of one ``cli.main`` call and restores them after:

    train.loop._build_model_and_state   -> the benchmark's weights go in
    train.loop.make_train_step          -> TrainProbe around the real step
    serve.run.SlotDecodeEngine          -> subclass: trace window, spans
    serve.paging.engine.PagedSlotEngine -> the same subclass body, for a
                                           configuration that serves with
                                           ``--serve.paged true``
    serve.run.Scheduler                 -> subclass: the benchmark's own
                                           clock on every token

The weights that go in are the cell's model's (``cell.model``, a file
under ``perfbench/models/``): nothing here knows an architecture.

The timed path is still the program's: its loop, its compiled step, its
scheduler and engine. A probe adds one Python call and one clock read per
step. The same seams are where the tests break the timed path (``fault``)
to show that ``correct`` comes out false.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .common import memory_peak_bytes, root_key, say

# What the tests may break underneath the timed path.
FAULTS = (None, "frozen_state", "half_batch", "altered_token")


class WindowClosed(Exception):
    """Raised out of the train loop when the measured window is over: the
    loop has no wall-clock stop of its own."""


class TraceWindow:
    """A profiler capture of part of the measured window."""

    def __init__(self, log_dir: str, seconds: float):
        self.log_dir = log_dir
        self.seconds = seconds
        self.t_start: Optional[float] = None
        self.t_stop: Optional[float] = None

    def start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no per-Python-call events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        jax.profiler.stop_trace()
        self.t_stop = time.perf_counter()

    @property
    def running(self) -> bool:
        return self.t_start is not None and self.t_stop is None

    def due_to_stop(self, now: float) -> bool:
        return self.running and now - self.t_start >= self.seconds


def _program_layout(program_params, mine: Dict[str, Any]):
    """Arrange ``mine`` (plain nested dicts) into the program's tree,
    refusing any difference in paths, shapes or dtypes."""
    flat = {jax.tree_util.keystr(p): leaf for p, leaf
            in jax.tree_util.tree_leaves_with_path(mine)}
    paths, treedef = jax.tree_util.tree_flatten_with_path(program_params)
    leaves = []
    for path, leaf in paths:
        key = jax.tree_util.keystr(path)
        if key not in flat:
            raise ValueError(f"the program's parameter {key} is not one "
                             f"the benchmark makes")
        got = flat.pop(key)
        if got.shape != leaf.shape or got.dtype != leaf.dtype:
            raise ValueError(
                f"parameter {key}: the program holds {leaf.shape} "
                f"{leaf.dtype}, the benchmark makes {got.shape} {got.dtype}")
        leaves.append(got)
    if flat:
        raise ValueError(f"the benchmark makes parameters the program "
                         f"lacks: {sorted(flat)}")
    return jax.tree_util.tree_unflatten(treedef, leaves)


def swap_in_weights(state, seed: int, model, sizes: Dict[str, int]):
    """Replace ``state.params`` by ``model``'s weights for ``seed``, made
    on the device in one jitted call, laid out as the program's. The
    program's own buffers are freed before the benchmark's are made, so
    the chip never holds two copies: a configuration may fill it as its
    deployment does."""
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state.params)
    shardings = jax.tree_util.tree_map(lambda a: a.sharding, state.params)

    def make(key):
        return _program_layout(shapes, model.make_params(key, sizes))

    key = root_key(seed)
    # Tracing refuses a leaf that differs while the program's tree is
    # still whole; only then are its buffers given back.
    lowered = jax.jit(make, out_shardings=shardings).lower(key)
    for leaf in jax.tree_util.tree_leaves(state.params):
        leaf.delete()
    return state.replace(params=lowered.compile()(key))


def _find_mu(opt_state):
    """Adam's first moment inside an optax state, whatever wraps it."""
    nodes = jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu"))
    found = [x.mu for x in nodes if hasattr(x, "mu")]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state, found {len(found)}")
    return found[0]


@jax.jit
def _leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        tree)


class TrainProbe:
    """Wraps the program's compiled train step. It clocks the window at
    the points where the loop has just fetched a loss (so the device is
    drained), keeps what the first ``check_steps`` steps consumed and
    produced for the comparison with the reference, and ends the run."""

    def __init__(self, seed: int, model, sizes: Dict[str, int],
                 seconds: float, log_every: int, skip_steps: int,
                 check_steps: int = 3, trace: Optional[TraceWindow] = None,
                 fault: Optional[str] = None):
        if fault not in FAULTS:
            raise ValueError(f"fault {fault!r}; have {FAULTS}")
        self.seed, self.model, self.sizes = seed, model, sizes
        self.seconds = seconds
        self.log_every, self.skip_steps = log_every, skip_steps
        self.check_steps, self.trace, self.fault = check_steps, trace, fault
        self.calls = 0
        self.t_first_call: Optional[float] = None
        self.marks: List[tuple] = []      # (steps done, clock) at drains
        self.batches: List[Dict[str, np.ndarray]] = []
        self.losses: List[Any] = []
        self.mu_norms = None
        self.delta_norms = None
        self.batch_shards: Optional[List[tuple]] = None
        self.timeline: List[tuple] = []   # (label, clock) in set-up

    # -- the wrapped call ---------------------------------------------------
    def on_step(self, real, state, batch):
        now = time.perf_counter()
        self.calls += 1
        n = self.calls                    # this call runs step n
        done = n - 1
        if n == 1:
            self.t_first_call = now
        if done >= self.skip_steps and done % self.log_every == 0:
            # The loop fetched step `done`'s loss just before this call:
            # every step up to it has finished on the device.
            self.marks.append((done, now))
            if self.trace is not None:
                if len(self.marks) == 1:
                    self.trace.start()
                    self.marks[0] = (done, time.perf_counter())
                elif self.trace.due_to_stop(now):
                    self.trace.stop()
            if now - self.marks[0][1] >= self.seconds:
                if self.trace is not None and self.trace.running:
                    self.trace.stop()
                raise WindowClosed()
        checking = n <= self.check_steps
        if n <= self.skip_steps + 1:
            self.timeline.append((f"call{n}", now))
        if checking:
            self.batches.append(jax.device_get(batch))
            if n == 1:
                self.batch_shards = [
                    (str(s.device), s.data.shape[0])
                    for s in batch["tokens"].addressable_shards]
        if self.fault == "half_batch":
            # Leaves half of every row out of the loss.
            half = batch["mask"].shape[1] // 2
            batch = dict(batch, mask=batch["mask"].at[:, half:].set(0.0))
        keep = None
        if self.fault == "frozen_state":
            keep = jax.tree_util.tree_map(jnp.copy, state.params)
        with jax.profiler.TraceAnnotation("bench.train_step_call"):
            state, metrics = real(state, batch)
        if checking:
            # call1 -> stepped1 is the trace, lowering and compile or
            # cache load of the step program
            self.timeline.append((f"stepped{n}", time.perf_counter()))
        if keep is not None:
            state = state.replace(params=keep)
        if checking:
            self.losses.append(metrics["loss"])
            if n == 1:
                self.mu_norms = _leaf_norms(_find_mu(state.opt_state))
            if n == self.check_steps:
                self.delta_norms = self._delta_norms(state.params)
            self.timeline.append((f"kept{n}", time.perf_counter()))
        return state, metrics

    def _delta_norms(self, params):
        model, sizes = self.model, self.sizes

        # The key is an argument, not a constant of the program: a seed
        # baked into it would compile anew (74 s, my chip run, PR 24) in
        # every run instead of loading from the cache.
        def run(p, key):
            p0 = _program_layout(p, model.make_params(key, sizes))
            return jax.tree_util.tree_map(
                lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p, p0)

        return jax.jit(run)(params, root_key(self.seed))

    # -- what the window held ------------------------------------------------
    def window(self) -> Dict[str, float]:
        (s0, t0), (s1, t1) = self.marks[0], self.marks[-1]
        return {"steps": s1 - s0, "seconds": t1 - t0, "first_step": s0,
                "t_start": t0, "t_end": t1}


def _build_with_weights(real_build, probe):
    """The program's model and state, with the cell's weights swapped in."""
    def build(cfg, mesh, task):
        model, state = real_build(cfg, mesh, task)
        state = swap_in_weights(state, probe.seed, probe.model, probe.sizes)
        held = sum(x.nbytes for x in jax.tree_util.tree_leaves(state.params))
        say(f"weights swapped in: {held} parameter bytes; peak_bytes_in_use "
            f"so far {memory_peak_bytes(jax.device_count())}")
        return model, state

    return build


@contextlib.contextmanager
def train_seams(probe: TrainProbe):
    from tensorflow_distributed_tpu.train import loop

    real_build, real_make = loop._build_model_and_state, loop.make_train_step

    def make(*args, **kwargs):
        real = real_make(*args, **kwargs)

        def step(state, batch):
            return probe.on_step(real, state, batch)

        return step

    loop._build_model_and_state = _build_with_weights(real_build, probe)
    loop.make_train_step = make
    try:
        yield
    finally:
        loop._build_model_and_state = real_build
        loop.make_train_step = real_make


class ServeProbe:
    """What the serve seams collect: every token with the benchmark's own
    clock, the run's start and end, and the engine for its sizes."""

    def __init__(self, seed: int, model, sizes: Dict[str, int],
                 trace: Optional[TraceWindow] = None,
                 trace_after_s: float = 0.0,
                 fault: Optional[str] = None):
        if fault not in FAULTS:
            raise ValueError(f"fault {fault!r}; have {FAULTS}")
        self.seed, self.model, self.sizes = seed, model, sizes
        self.trace, self.trace_after_s, self.fault = (trace, trace_after_s,
                                                      fault)
        self.events: List[tuple] = []     # (rid, token, clock)
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self.engine = None

    def on_token(self, rid: int, tok: int, done: bool) -> None:
        self.events.append((rid, tok, time.perf_counter()))

    def before_engine_call(self) -> None:
        tr = self.trace
        if tr is None or self.t0 is None:
            return
        now = time.perf_counter()
        if tr.t_start is None and now - self.t0 >= self.trace_after_s:
            tr.start()
        elif tr.due_to_stop(now):
            tr.stop()


def _probed_engine(real_engine, probe: ServeProbe):
    """``real_engine`` (the dense slot engine or the paged one) with the
    probe around its two calls: the trace window, the ``bench.*`` spans,
    and the fault the tests put underneath."""

    class Engine(real_engine):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            probe.engine = self

        def prefill(self, *args, **kwargs):
            probe.before_engine_call()
            with jax.profiler.TraceAnnotation("bench.engine_prefill"):
                return super().prefill(*args, **kwargs)

        def step(self):
            probe.before_engine_call()
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                nxt = super().step()
            if (probe.fault == "altered_token"
                    and self.decode_steps % 3 == 0):
                # A token altered where it is produced: every live slot
                # is fed, and serves, another token than the greedy one.
                act = self.active
                nxt = np.array(nxt)
                nxt[act] = (nxt[act] + 1) % probe.sizes["vocab_size"]
                self.tok[act] = nxt[act]
            return nxt

    return Engine


@contextlib.contextmanager
def serve_seams(probe: ServeProbe):
    from tensorflow_distributed_tpu.serve import run as serve_run_mod
    from tensorflow_distributed_tpu.serve.paging import engine as paged_mod
    from tensorflow_distributed_tpu.train import loop

    real_build = loop._build_model_and_state
    real_engine, real_sched = (serve_run_mod.SlotDecodeEngine,
                               serve_run_mod.Scheduler)
    real_paged = paged_mod.PagedSlotEngine

    class Sched(real_sched):
        def __init__(self, *args, **kwargs):
            if kwargs.get("on_token") is None:
                kwargs["on_token"] = probe.on_token
            super().__init__(*args, **kwargs)

        def run(self, requests):
            probe.t0 = time.perf_counter()
            try:
                return super().run(requests)
            finally:
                probe.t1 = time.perf_counter()
                if probe.trace is not None and probe.trace.running:
                    probe.trace.stop()

    loop._build_model_and_state = _build_with_weights(real_build, probe)
    serve_run_mod.SlotDecodeEngine = _probed_engine(real_engine, probe)
    # serve_run imports the paged class from its module when it is called
    paged_mod.PagedSlotEngine = _probed_engine(real_paged, probe)
    serve_run_mod.Scheduler = Sched
    try:
        yield
    finally:
        loop._build_model_and_state = real_build
        serve_run_mod.SlotDecodeEngine = real_engine
        paged_mod.PagedSlotEngine = real_paged
        serve_run_mod.Scheduler = real_sched
