"""Tracing/profiling subsystem.

The reference had none — its only observability was `time.time()`
deltas around the train and validation loops (SURVEY.md §5 "tracing:
none"; mnist_python_m.py:285-307, mnist_single.py:102,119-134). Here
profiling is a first-class switch: a step-windowed `jax.profiler`
trace (XPlane/TensorBoard format, viewable in Perfetto) captures the
XLA execution timeline — per-op device time, HBM traffic, and the ICI
collectives that replaced the reference's gRPC ps round-trip.

Captures also write the Perfetto JSON export
(``create_perfetto_trace``) beside the XPlane, which is what
``observe/xprof.py`` PARSES to attribute device wall time back to the
instrumented programs — the capture is no longer write-only.

Host spans on the capture's timeline come from the span seam,
``observe/trace.py::HostSpans`` (``tfd.*`` on ``/host:CPU``).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Iterator, Optional

import jax


def _start_trace(log_dir: str, perfetto: bool) -> None:
    """start_trace with the Perfetto JSON export when this jax
    supports the kwarg (older versions write XPlane only — xprof then
    degrades to its explicit-null records)."""
    if perfetto:
        try:
            jax.profiler.start_trace(log_dir,
                                     create_perfetto_trace=True)
            return
        except TypeError:
            pass
    jax.profiler.start_trace(log_dir)


@dataclasses.dataclass
class StepProfiler:
    """Trace a window of steps: [start_step, start_step + num_steps).

    Inactive (zero overhead) when ``log_dir`` is empty. Only the chief
    process traces — one XPlane per job, like one `performance` table
    per job in the reference.
    """

    log_dir: str = ""
    start_step: int = 10
    num_steps: int = 5
    # Also write the Perfetto JSON export observe/xprof.py parses for
    # device-time attribution (XPlane alone is write-only here).
    perfetto: bool = True
    # True once a window actually started — the loop's device-time
    # emission keys on it (a run whose horizon never reached the
    # window has nothing to parse).
    captured: bool = dataclasses.field(default=False, init=False)
    _running: bool = dataclasses.field(default=False, init=False)

    def observe(self, step: int, pending=None) -> None:
        """Call once per step with the just-issued step number.

        ``pending``: device values the last traced step produced (e.g.
        the metrics dict). The training loop dispatches steps
        asynchronously, so without draining them before stop_trace the
        XPlane would be missing the tail of the traced window.
        """
        if not self.log_dir:
            return
        in_window = (self.start_step <= step
                     < self.start_step + self.num_steps)
        if not self._running and in_window:
            # Window test, not equality: a resumed run whose first step
            # is already past start_step still gets (the tail of) a trace.
            _start_trace(self.log_dir, self.perfetto)
            self._running = True
            self.captured = True
        elif self._running and step >= self.start_step + self.num_steps:
            self.stop(pending)

    def stop(self, pending=None) -> None:
        """Finalize an open trace window. Safe to call when no window is
        open; the drain is try/finally-wrapped so a failing device_get
        (e.g. the very exception that ended training) still closes the
        trace instead of leaving it running into interpreter exit."""
        if self._running:
            try:
                if pending is not None:
                    jax.device_get(pending)  # drain in-flight traced steps
            finally:
                jax.profiler.stop_trace()
                self._running = False


@contextlib.contextmanager
def trace(log_dir: Optional[str], perfetto: bool = True
          ) -> Iterator[None]:
    """Whole-span trace: ``with trace('/tmp/tb'): run()``."""
    if not log_dir:
        yield
        return
    _start_trace(log_dir, perfetto)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
